"""Retention's step kernel against its roofline over the traced window:
the least time the chip could take to read and write the state of the
slots that decoded in the window (``retention_bytes.step``: 2 x
34,080,768 B a slot a layer a step at the published sizes, against the
operations that decay, update and query it) over the device time of the
Mosaic calls whose HLO instruction is named ``retention_step`` (the name
the program gives its ``pallas_call``).

The slot-steps are the program's own: every ``serving.decode_chunk`` span
is a profiler annotation on the host plane of the same ``.xplane.pb`` and
carries ``active`` (the slots live when the chunk was sent: exactly the
slots the kernel visits in every step of it), ``steps`` and
``retention_layers``.  A chunk whose span began before the profiler did
is not in the trace while some of its calls are, so the count errs low.
(The requests' own times cannot give it: they spread a request's tokens
evenly over its life, and over three seconds with five requests live
that read 918 slot-steps where the spans had 728; my chip run, PR 42.)
A reading over 100 is a fault of the count.  A trace in which no call
carries the name or no span the attributes, or a family with no
retention layer, gives nothing to read."""

from chipbench import retention_bytes, trace_reduce

NAME = "retention.step_kernel_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLE = "retention_step"
CALL = 'custom_call_target="tpu_custom_call"'


def kernels(cfg, mix):
    return {NEEDLE: ("%" + NEEDLE, CALL)}


def call_seconds(trace, needle=NEEDLE):
    """Device seconds of the calls named after the kernel, or None."""
    seconds = [rec["seconds"] for rec in trace["ops"].values()
               if needle in rec["provenance"].split(" = ")[0]
               and CALL in rec["provenance"]]
    return sum(seconds) if seconds else None


def spans(profile, name, *attrs):
    """The attributes ``attrs`` of every host span called ``name`` that
    carries them all, one tuple a span."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    stats = dict(e.stats)
                    if all(a in stats for a in attrs):
                        out.append(tuple(stats[a] for a in attrs))
    return out


def read(facts):
    trace = facts.get("trace")
    if not trace or not facts.get("trace_path"):
        return None
    if retention_bytes.sizes(facts["config"]) is None:
        return None
    spent = call_seconds(trace)
    if not spent:
        return None
    chunks = spans(trace_reduce.load(facts["trace_path"]),
                   "serving.decode_chunk", "active", "steps",
                   "retention_layers")
    if not chunks:
        return None
    slot_steps = sum(int(a) * int(s) * int(n) for a, s, n in chunks)
    least = retention_bytes.least_seconds(
        *retention_bytes.step(facts["config"]), facts["peak"])
    return 100.0 * slot_steps * least / spent
