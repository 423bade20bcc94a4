"""Mamba-2's step kernel against its roofline over the traced window:
the least time the chip could take to read and write the state of the
slots that decoded in the window (``ssm_moe_bytes.step``: 2 x 2,097,152
B a slot a layer a step at the published sizes, against the operations
that decay, update and read it) over the device time of the Mosaic calls
whose HLO instruction is named ``ssm_step`` (the name the program gives
its ``pallas_call``).

The slot-steps are the program's own, as ``retention.step_kernel_
roofline`` takes them: every ``serving.decode_chunk`` span is a profiler
annotation on the host plane of the same ``.xplane.pb`` and carries
``active`` (the slots live when the chunk was sent: the slots the kernel
visits in every step of it), ``steps`` and ``ssm_layers``.  A chunk
whose span began before the profiler did is not in the trace while some
of its calls are, so the count errs low.  A reading over 100 is a fault
of the count.  A trace in which no call carries the name or no span the
attributes, or a family with no such layer, gives nothing to read."""

from chipbench import run as bench_run
from chipbench import ssm_moe_bytes, trace_reduce

NAME = "ssm.step_kernel_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLE = "ssm_step"
CALL = 'custom_call_target="tpu_custom_call"'


def kernels(cfg, mix):
    return {NEEDLE: ("%" + NEEDLE, CALL)}


def read(facts):
    trace = facts.get("trace")
    if not trace or not facts.get("trace_path"):
        return None
    if ssm_moe_bytes.sizes(facts["config"]) is None:
        return None
    helper = bench_run.load_reader("retention.step_kernel_roofline")
    spent = helper.call_seconds(trace, NEEDLE)
    if not spent:
        return None
    chunks = helper.spans(trace_reduce.load(facts["trace_path"]),
                          "serving.decode_chunk", "active", "steps",
                          "ssm_layers")
    if not chunks:
        return None
    slot_steps = sum(int(a) * int(s) * int(n) for a, s, n in chunks)
    least = ssm_moe_bytes.least_seconds(
        *ssm_moe_bytes.step(facts["config"]), facts["peak"])
    return 100.0 * slot_steps * least / spent
