"""The paged-attention kernel's share of its roofline over the traced
window for a stack whose planes are not all alike: the least time the
chip could take to attend what the masks let through at the decode
positions processed in the window (``hybrid_bytes.paged_live``: a window
plane clipped to its window, the full plane once a reader, K/V heads as
the pool holds them; memory-bound) over the device time of the Mosaic
calls whose HLO instruction is named ``paged_attention``.

The calls are found by the kernel's name and the contexts taken from the
requests' times, both as ``paged_attention_named_roofline`` does (whose
byte count, ``flops.paged_attention_live``, multiplies planes by heads
and knows no window).  Decode positions only: a prefill window of 8 rows
or more attends densely and makes no call to the kernel."""

from chipbench import families, flops, hybrid_bytes
from chipbench import run as bench_run

NAME = "paged_attention_window_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def _named():
    return bench_run.load_reader("paged_attention_named_roofline")


def kernels(cfg, mix):
    return _named().kernels(cfg, mix)


def read(facts):
    trace = facts.get("trace")
    if not trace or "trace_span" not in facts:
        return None
    if not hasattr(families.of(facts["config"]), "hybrid_sizes"):
        return None
    spent = _named().call_seconds(trace)
    if not spent:
        return None
    contexts = _named().decode_contexts(facts["requests"],
                                        *facts["trace_span"])
    ops, nbytes = hybrid_bytes.paged_live(facts["config"], contexts)
    least, _ = flops.roofline_seconds(ops, nbytes, facts["peak"])
    return 100.0 * least / spent
