"""The constant-decay recurrence's chunked form against its roofline over
the traced window: the least time the chip could take over the prefill
pieces sent in the window (``sala_bytes.piece(rows)`` a piece an ``L``
layer: the larger of its bytes, the one slot's state in and out and the
rows' ``v | k | q`` and read, at 819 GB/s, and its operations at 197
TFLOP/s) over the device time of the operations under the scope
``ssm_chunk`` (``kernels/ssm.py``: XLA einsums at float32 accuracy, six
bfloat16 passes a product, so a reading of a few percent is the form's,
not a fault).

The pieces are the program's own: every ``serving.prefill`` span that
STARTS inside the traced window's interval carries ``bucket``, ``pieces``
and ``lightning_layers``; all pieces but the last are 512 rows wide.  A
reading over 105 is refused.  A program without the map or the
attributes, or a family with no such layer, gives nothing to read."""

from chipbench import run as bench_run
from chipbench import sala_bytes, trace_reduce

NAME = "lightning.chunk_kernel_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLE = "ssm_chunk"


def read(facts):
    trace = facts.get("trace")
    if not trace or not facts.get("trace_path"):
        return None
    if sala_bytes.sizes(facts["config"]) is None:
        return None
    kda = bench_run.load_reader("kda.chunk_kernel_roofline")
    spent = bench_run.load_reader(
        "dsa.indexer_roofline").scope_seconds(facts, NEEDLE)
    if not spent:
        return None
    admitted = sala_bytes.spans_inside(
        trace_reduce.load(facts["trace_path"]), facts["trace_interval"],
        "serving.prefill", "bucket", "pieces", "lightning_layers")
    if not admitted:
        return None
    least = sum(
        int(layers) * sala_bytes.least_seconds(
            *sala_bytes.piece(facts["config"], w), facts["peak"])
        for bucket, pieces, layers in admitted
        for w in kda.widths(bucket, pieces) if w > 0)
    return sala_bytes.share(NAME, 100.0 * least / spent)
