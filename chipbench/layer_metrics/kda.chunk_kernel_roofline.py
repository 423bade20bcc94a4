"""The delta rule's WY form against its roofline over the traced window:
the least time the chip could take over the prefill pieces sent in the
window (``delta_bytes.piece(rows)`` a piece a delta layer: the larger of
its bytes, the one slot's state in and out and the rows' ``q | k | v``,
decay and read, at 819 GB/s, and its operations at 197 TFLOP/s) over the
device time of the operations under the scope ``delta_chunk`` (XLA
einsums: the program's map from instruction to scope finds them,
``observability.trace.device_seconds_by_scope``; a Mosaic call of that
name would be found by name).

The pieces are the program's own: every ``serving.prefill`` span that
STARTS inside the traced window's interval carries ``bucket`` (the
padded tokens of the admission), ``pieces`` and ``delta_layers``; all
pieces but the last are 512 rows wide.  The form multiplies float32
operands at the highest precision (six bfloat16 passes a product) and
its products are 64 rows tall, so a reading of a few percent is the
form's, not a fault.  A reading over 105 is refused.  A program without
the map or the attributes, or a family with no such layer, gives nothing
to read."""

from chipbench import delta_bytes, trace_reduce
from chipbench import run as bench_run

NAME = "kda.chunk_kernel_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLE = "delta_chunk"


def widths(bucket, pieces):
    """The piece widths of one admission."""
    full = int(pieces) - 1
    return [delta_bytes.PIECE] * full + [
        int(bucket) - delta_bytes.PIECE * full]


def scope_seconds(facts):
    """Device seconds of the operations under the scope, or None."""
    try:
        from paddle_tpu.observability import trace
    except ImportError:
        return None
    if not hasattr(trace, "device_seconds_by_scope"):
        return None
    scopes = facts.get("device_scopes")
    if scopes is None:
        scopes = trace.device_scopes()
    got = trace.device_seconds_by_scope(facts["trace_path"], scopes) \
        if scopes else None
    if not got:
        return None
    return sum(s for s, _kind, _phase, path in got.get("ops", {}).values()
               if NEEDLE in path) or None


def read(facts):
    trace = facts.get("trace")
    if not trace or not facts.get("trace_path"):
        return None
    if delta_bytes.sizes(facts["config"]) is None:
        return None
    spent = bench_run.load_reader(
        "retention.step_kernel_roofline").call_seconds(trace, NEEDLE) \
        or scope_seconds(facts)
    if not spent:
        return None
    admitted = delta_bytes.spans_inside(
        trace_reduce.load(facts["trace_path"]), facts["trace_interval"],
        "serving.prefill", "bucket", "pieces", "delta_layers")
    if not admitted:
        return None
    least = sum(
        int(layers) * delta_bytes.least_seconds(
            *delta_bytes.piece(facts["config"], w), facts["peak"])
        for bucket, pieces, layers in admitted
        for w in widths(bucket, pieces) if w > 0)
    return delta_bytes.share(NAME, 100.0 * least / spent)
