"""What JAX says of the devices a run used."""


def describe(devices, memory_peak_bytes):
    """The ``device`` object of the result line."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(memory_peak_bytes)}


def memory_peak(devices, compiled_high_water=0):
    """Peak bytes on the fullest chip: the larger of the allocator's
    ``peak_bytes_in_use`` and the largest compiled executable's HBM
    high-water (``memory_analysis``: arguments + outputs + temporaries
    less aliasing).  On this runtime the allocator's peak leaves an
    executable's temporaries out (PERF.md section 5, PR 21: 1.70 GB read
    where the compiled step holds 12.76 GB), so alone it under-reports."""
    allocator = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in devices)
    return max(int(allocator), int(compiled_high_water or 0))
