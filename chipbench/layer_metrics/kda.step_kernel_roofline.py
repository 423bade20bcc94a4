"""The delta rule's step kernel against its roofline over the traced
window: the least time the chip could take to read and write the state
of the slots that decoded in the window (``delta_bytes.step``: 2 x
4,194,304 B a slot a layer a step at the published sizes, 10.2 us at 819
GB/s, against the operations that decay, correct and read it) over the
device time of the Mosaic calls whose HLO instruction is named
``delta_step`` (the name the program gives its ``pallas_call``).

The slot-steps are the program's own: every ``serving.decode_chunk``
span is a profiler annotation on the host plane of the same
``.xplane.pb`` and carries ``active`` (the slots live when the chunk was
sent: the slots the kernel visits in every step of it), ``steps`` and
``delta_layers``.  Only the spans that START inside the traced window's
interval are counted (``delta_bytes.spans_inside``), as the calls'
seconds are cut to it; a chunk sent just before the window's end runs
partly after it, one sent just before its start partly inside: the two
edges cancel to within one chunk in some hundred.  A reading over 105 is
refused (``delta_bytes.share``).  A trace in which no call carries the
name or no span the attributes, or a family with no such layer, gives
nothing to read."""

from chipbench import delta_bytes, trace_reduce
from chipbench import run as bench_run

NAME = "kda.step_kernel_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLE = "delta_step"
CALL = 'custom_call_target="tpu_custom_call"'


def kernels(cfg, mix):
    return {NEEDLE: ("%" + NEEDLE, CALL)}


def read(facts):
    trace = facts.get("trace")
    if not trace or not facts.get("trace_path"):
        return None
    if delta_bytes.sizes(facts["config"]) is None:
        return None
    spent = bench_run.load_reader(
        "retention.step_kernel_roofline").call_seconds(trace, NEEDLE)
    if not spent:
        return None
    chunks = delta_bytes.spans_inside(
        trace_reduce.load(facts["trace_path"]), facts["trace_interval"],
        "serving.decode_chunk", "active", "steps", "delta_layers")
    if not chunks:
        return None
    slot_steps = sum(int(a) * int(s) * int(n) for a, s, n in chunks)
    least = delta_bytes.least_seconds(*delta_bytes.step(facts["config"]),
                                      facts["peak"])
    return delta_bytes.share(NAME, 100.0 * slot_steps * least / spent)
