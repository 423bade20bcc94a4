"""Retention's chunk kernel against its roofline over the traced window:
the least time the chip could take for the prefill pieces that ran in
the window (``retention_bytes.piece(rows)`` a piece a layer: the larger
of the slot's state read and written once with the rows in and out, and
of the quadratic scores, the read through ``phi(q)`` and the state's
advance, at the chip's bfloat16 peak) over the device time of the Mosaic
calls whose HLO instruction is named ``retention_chunk``.

The pieces are the program's own: every ``serving.prefill`` span is a
profiler annotation on the host plane of the same ``.xplane.pb`` and
carries ``bucket`` (the rows its pieces computed, padding included),
``pieces`` and ``retention_layers``; all pieces but the last are
``retention_bytes.PIECE`` rows wide.  A prefill whose span began before
the profiler did is not in the trace while some of its calls are, so the
count errs low.  The operations are counted once, at the peak of ONE
bfloat16 pass: a kernel that makes the float32 products of the state in
several passes reads that much lower.  A reading over 100 is a fault of
the count.  A trace in which no call carries the name or no span the
attributes, or a family with no retention layer, gives nothing to
read."""

from chipbench import retention_bytes, trace_reduce
from chipbench import run as bench_run

NAME = "retention.chunk_kernel_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLE = "retention_chunk"
CALL = 'custom_call_target="tpu_custom_call"'


def kernels(cfg, mix):
    return {NEEDLE: ("%" + NEEDLE, CALL)}


def widths(bucket, pieces):
    """The piece widths of one admission."""
    full = int(pieces) - 1
    return [retention_bytes.PIECE] * full + [
        int(bucket) - retention_bytes.PIECE * full]


def read(facts):
    trace = facts.get("trace")
    if not trace or not facts.get("trace_path"):
        return None
    if retention_bytes.sizes(facts["config"]) is None:
        return None
    step = bench_run.load_reader("retention.step_kernel_roofline")
    spent = step.call_seconds(trace, NEEDLE)
    if not spent:
        return None
    admitted = step.spans(trace_reduce.load(facts["trace_path"]),
                          "serving.prefill", "bucket", "pieces",
                          "retention_layers")
    if not admitted:
        return None
    least = sum(
        int(layers) * retention_bytes.least_seconds(
            *retention_bytes.piece(facts["config"], w), facts["peak"])
        for bucket, pieces, layers in admitted
        for w in widths(bucket, pieces))
    return 100.0 * least / spent
