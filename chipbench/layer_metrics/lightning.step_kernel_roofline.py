"""The constant-decay recurrence's step against its roofline over the
traced window: the least time the chip could take to read and write the
state of the slots that decoded in the window (``sala_bytes.step``: 2 x
2,097,152 B a slot a layer a step at the published sizes, 5.1 us at 819
GB/s, against the operations that decay, update and read it) over the
device time of the Mosaic calls whose HLO instruction is named
``ssm_step`` (``kernels/ssm.py``'s kernel, which a lightning layer rides
at 32 groups of one head with no convolution).

The slot-steps are the program's own: every ``serving.decode_chunk`` span
that STARTS inside the traced window's interval carries ``active``,
``steps`` and ``lightning_layers`` (``sala_bytes.spans_inside``).  A
reading over 105 is refused.  A trace in which no call carries the name
or no span the attributes, or a family with no such layer, gives nothing
to read."""

from chipbench import run as bench_run
from chipbench import sala_bytes, trace_reduce

NAME = "lightning.step_kernel_roofline"
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
    if sala_bytes.sizes(facts["config"]) is None:
        return None
    spent = bench_run.load_reader(
        "retention.step_kernel_roofline").call_seconds(trace, NEEDLE)
    if not spent:
        return None
    chunks = sala_bytes.spans_inside(
        trace_reduce.load(facts["trace_path"]), facts["trace_interval"],
        "serving.decode_chunk", "active", "steps", "lightning_layers")
    if not chunks:
        return None
    slot_steps = sum(int(a) * int(s) * int(n) for a, s, n in chunks)
    least = sala_bytes.least_seconds(*sala_bytes.step(facts["config"]),
                                     facts["peak"])
    return sala_bytes.share(NAME, 100.0 * slot_steps * least / spent)
