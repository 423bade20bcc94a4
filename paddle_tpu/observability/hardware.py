"""Hardware accounting: chip peak FLOP/s, MFU, and device-memory stats.

The peak table is the single source of truth the trainer's MFU field
and the tuner's roofline both read —
public bf16 chip specs keyed by ``device_kind`` substring.

``device_memory_stats`` wraps ``jax.Device.memory_stats()`` (None on CPU)
and ``sample_memory`` publishes per-device ``device.bytes_in_use`` /
``device.peak_bytes_in_use`` gauges plus a process-wide
``device.hbm_high_water_bytes`` high-water mark into the metrics
registry — the capacity instrument every OOM postmortem starts from.
"""

import os

from . import metrics as _metrics

__all__ = [
    "PEAK_BF16", "HBM_BW", "device_peak_flops", "total_peak_flops",
    "mfu", "device_memory_stats", "sample_memory", "device_hbm_bytes",
    "device_hbm_bandwidth",
]

# bf16 peak FLOP/s by device_kind substring (public chip specs); order
# matters — first match wins ("v5 lite" before "v5e"-less kinds etc.)
PEAK_BF16 = (
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
    ("v6", 918e12), ("v4", 275e12), ("v3", 123e12),
)

# HBM bandwidth (bytes/s) by the same device_kind substrings (public
# chip specs) — the memory side of the attribution engine's roofline
# (observability.attribution): est_ms = max(flops/peak, bytes/bw)
HBM_BW = (
    ("v5 lite", 819e9), ("v5e", 819e9), ("v5p", 2765e9),
    ("v6", 1640e9), ("v4", 1228e9), ("v3", 900e9),
)

# Nominal CPU peak so MFU stays defined on CPU runs (dev loops, CI).
# Absolute CPU MFU is not meaningful against this — only step-to-step
# deltas are; override with PT_CPU_PEAK_FLOPS.
_CPU_NOMINAL_PEAK = 1e12

# Nominal CPU memory bandwidth (same caveat; PT_CPU_HBM_BW to override)
_CPU_NOMINAL_BW = 50e9


def _lookup(table, device, what):
    """The chip-spec ``table`` entry for ``device``: a CPU gets None
    (callers apply their nominal constant), an accelerator whose
    ``device_kind`` is not in the table is an error — a peak it does
    not know is not a default."""
    kind = getattr(device, "device_kind", "")
    for sub, value in table:
        if sub in kind.lower():
            return value
    if getattr(device, "platform", "cpu") == "cpu":
        return None
    raise ValueError(
        f"no {what} known for accelerator device_kind {kind!r}: add it "
        f"to paddle_tpu/observability/hardware.py with its source")


def device_peak_flops(device=None):
    """Peak bf16 FLOP/s for one device: the chip-spec table by
    device_kind; a nominal constant (PT_CPU_PEAK_FLOPS) on a CPU so MFU
    stays computable there; an unknown accelerator raises."""
    if device is None:
        import jax

        device = jax.devices()[0]
    peak = _lookup(PEAK_BF16, device, "bf16 peak FLOP/s")
    if peak is None:
        return float(os.environ.get("PT_CPU_PEAK_FLOPS",
                                    _CPU_NOMINAL_PEAK))
    return peak


def device_hbm_bandwidth(device=None):
    """HBM bandwidth in bytes/s for one device — the memory axis of
    the attribution roofline.  Chip-spec table by device_kind; a nominal
    constant (PT_CPU_HBM_BW) on a CPU (CPU figures are only meaningful
    relative to each other); an unknown accelerator raises."""
    if device is None:
        import jax

        device = jax.devices()[0]
    bw = _lookup(HBM_BW, device, "HBM bandwidth")
    if bw is None:
        return float(os.environ.get("PT_CPU_HBM_BW", _CPU_NOMINAL_BW))
    return bw


def total_peak_flops(mesh=None, device=None):
    """Aggregate peak over the devices a step runs on: the mesh's devices
    when sharded, else one device."""
    if mesh is not None:
        return sum(device_peak_flops(d) for d in mesh.devices.flat)
    return device_peak_flops(device)


def mfu(flops_per_step, step_seconds, peak_flops):
    """Model FLOPs utilization in [0, 1]; None when not computable."""
    if not flops_per_step or not step_seconds or not peak_flops:
        return None
    if step_seconds <= 0 or peak_flops <= 0:
        return None
    return flops_per_step / step_seconds / peak_flops


def device_memory_stats(device=None):
    """``device.memory_stats()`` as a plain dict; {} when the backend
    does not report (CPU, some plugin backends)."""
    if device is None:
        import jax

        device = jax.local_devices()[0]
    try:
        stats = device.memory_stats()
    except Exception:
        return {}
    return dict(stats) if stats else {}


def device_hbm_bytes(device=None):
    """The device's usable memory capacity in bytes (the allocator's
    ``bytes_limit``), or None when the backend does not report one (CPU).
    The preflight ceiling a compiled step's ``hbm_high_water_bytes``
    is checked against before a capacity config runs."""
    stats = device_memory_stats(device)
    limit = stats.get("bytes_limit")
    return int(limit) if limit else None


def sample_memory(registry=None, devices=None):
    """Sample every local device's memory stats into gauges and advance
    the process-wide HBM high-water mark.  Returns
    ``{"bytes_in_use": max, "peak_bytes_in_use": max, "high_water": hw}``
    over devices, or {} when no backend reports memory.  Cheap host-only
    call — safe to run every step."""
    reg = registry or _metrics.get_registry()
    if devices is None:
        import jax

        devices = jax.local_devices()
    in_use_max = peak_max = 0
    reported = False
    for i, d in enumerate(devices):
        stats = device_memory_stats(d)
        if not stats:
            continue
        reported = True
        in_use = int(stats.get("bytes_in_use", 0))
        peak = int(stats.get("peak_bytes_in_use", in_use))
        reg.gauge("device.bytes_in_use", device=str(i)).set(in_use)
        reg.gauge("device.peak_bytes_in_use", device=str(i)).set(peak)
        limit = stats.get("bytes_limit")
        if limit:
            reg.gauge("device.bytes_limit", device=str(i)).set(int(limit))
        in_use_max = max(in_use_max, in_use)
        peak_max = max(peak_max, peak)
    if not reported:
        return {}
    hw = reg.gauge("device.hbm_high_water_bytes")
    hw.set_max(max(in_use_max, peak_max))
    return {"bytes_in_use": in_use_max, "peak_bytes_in_use": peak_max,
            "high_water": hw.value}
