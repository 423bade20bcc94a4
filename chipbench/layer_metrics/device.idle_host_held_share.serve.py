"""Share of the traced window in which the chip was idle WHILE the
engine's driver thread was at work: device-idle seconds (the complement
of the busy union between a chip's first and last operation) that
overlap a driver-thread span other than ``serving.idle``, over the
traced window.  The rest of ``device.idle_share.serve`` is idle because
nothing was due.  The driver's spans (``serving.step`` and, inside it,
``serving.admit``, ``.prefill``, ``.decode_chunk``, ``.fetch``, ``.emit``)
are profiler annotations on the
host plane of the same ``.xplane.pb``, in the device trace's clock; the
driver thread is the host line that holds them.  A program without those
spans gives nothing to read."""

from chipbench import trace_reduce

NAME = "device.idle_host_held_share.serve"
LAYER = "Device"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

IDLE = "serving.idle"
AT_WORK = ("serving.step", "serving.admit", "serving.prefill",
           "serving.decode_chunk", "serving.fetch", "serving.emit")


def driver_spans(profile):
    """(at work, idle): merged [start_ns, end_ns] intervals of the
    driver thread's spans, from every host line that holds any."""
    work, idle = [], []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in AT_WORK:
                    work.append((e.start_ns, e.start_ns + e.duration_ns))
                elif e.name == IDLE:
                    idle.append((e.start_ns, e.start_ns + e.duration_ns))
    return (trace_reduce.busy_union(sorted(work)),
            trace_reduce.busy_union(sorted(idle)))


def overlap_ns(gaps, spans):
    """Nanoseconds of ``gaps`` covered by ``spans`` (both sorted, each
    without overlaps of its own)."""
    total, j = 0, 0
    for g0, g1 in gaps:
        while j < len(spans) and spans[j][1] <= g0:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < g1:
            total += min(g1, spans[k][1]) - max(g0, spans[k][0])
            k += 1
    return total


def held_seconds(profile):
    """Idle seconds under a working driver span, averaged over the chips
    that ran anything; None where the trace has no driver span."""
    work, idle = driver_spans(profile)
    chips = trace_reduce.chip_ops(profile)
    if not chips or not (work or idle):
        return None
    held = 0
    for events in chips.values():
        merged = trace_reduce.busy_union(events)
        gaps = [(merged[i][1], merged[i + 1][0])
                for i in range(len(merged) - 1)]
        held += overlap_ns(gaps, work)
    return held * 1e-9 / len(chips)


def read(facts):
    if not facts.get("trace") or not facts.get("trace_path"):
        return None
    held = held_seconds(trace_reduce.load(facts["trace_path"]))
    if held is None:
        return None
    return 100.0 * held / facts["trace_window_s"]
