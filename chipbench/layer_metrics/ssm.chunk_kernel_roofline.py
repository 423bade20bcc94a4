"""Mamba-2's chunked form against its roofline over the traced window:
the least time the chip could take for the prefill pieces that ran in
the window (``ssm_moe_bytes.piece(rows)`` a piece a layer: the larger of
the slot's state read and written once with the rows in and out, and of
the chunk's quadratic form and the state's advance and read at the
chip's bfloat16 peak) over the device time of the chunked form.

The chunked form is XLA einsums under the named scope ``ssm_chunk``
(``kernels/ssm.py``), so its seconds are those of the operations whose
scope path holds that name, by the program's own map from HLO
instruction to scope (``trace.device_scopes``, the join
``scope_join.py`` makes); were it a Mosaic call of that name, the
call's.  The pieces are the program's own: every ``serving.prefill``
span carries ``bucket`` (the rows its pieces computed, padding
included), ``pieces`` and ``ssm_layers``; all pieces but the last are
``ssm_moe_bytes.PIECE`` rows wide.  A prefill whose span began before
the profiler did is not in the trace while some of its operations are,
so the count errs low.  The operations are counted once, at the peak of
ONE bfloat16 pass: einsums that make float32 products in six passes read
that much lower.  A reading over 100 is a fault of the count.  A program
without the map or the attributes, or a family with no such layer, gives
nothing to read."""

from chipbench import run as bench_run
from chipbench import ssm_moe_bytes, trace_reduce

NAME = "ssm.chunk_kernel_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLE = "ssm_chunk"


def widths(bucket, pieces):
    """The piece widths of one admission."""
    full = int(pieces) - 1
    return [ssm_moe_bytes.PIECE] * full + [
        int(bucket) - ssm_moe_bytes.PIECE * full]


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
    if ssm_moe_bytes.sizes(facts["config"]) is None:
        return None
    helper = bench_run.load_reader("retention.step_kernel_roofline")
    spent = helper.call_seconds(trace, NEEDLE) or scope_seconds(facts)
    if not spent:
        return None
    admitted = helper.spans(trace_reduce.load(facts["trace_path"]),
                            "serving.prefill", "bucket", "pieces",
                            "ssm_layers")
    if not admitted:
        return None
    least = sum(
        int(layers) * ssm_moe_bytes.least_seconds(
            *ssm_moe_bytes.piece(facts["config"], w), facts["peak"])
        for bucket, pieces, layers in admitted
        for w in widths(bucket, pieces) if w > 0)
    return 100.0 * least / spent
