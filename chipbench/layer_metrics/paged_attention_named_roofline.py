"""The paged-attention kernel's share of its roofline over the traced
window, with the kernel's calls found by the kernel's NAME: the least
time the chip could take to attend the LIVE tokens of the decode
positions processed in the window (K and V of those tokens only,
``flops.paged_attention_live``; memory-bound) over the device time of
the Mosaic calls whose HLO instruction is named ``paged_attention``
(the name the program gives its ``pallas_call``; the instruction's own
name, not its operands').

``paged_attention_roofline`` finds the calls by the pool's shape among
their operands, which a pool with several passes folded into its block
axis does not have; this reader is for such cells.  It counts decode
positions only: a prefill window of 8 rows or more attends densely and
makes no call to the kernel.  A trace in which no call carries the name
gives nothing to read."""

from chipbench import flops
from chipbench import run as bench_run

NAME = "paged_attention_named_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLE = "paged_attention"
CALL = 'custom_call_target="tpu_custom_call"'


def kernels(cfg, mix):
    return {"paged_attention": ("%" + NEEDLE, CALL)}


def call_seconds(trace):
    """Device seconds of the calls named after the kernel, or None."""
    seconds = [rec["seconds"] for rec in trace["ops"].values()
               if NEEDLE in rec["provenance"].split(" = ")[0]
               and CALL in rec["provenance"]]
    return sum(seconds) if seconds else None


def decode_contexts(requests, w0, w1):
    """Tokens attended, one entry per decode position in [w0, w1]: the
    shape-found reader's arithmetic with the prefill positions left out
    (a prompt that is all prefix hit prefills nothing)."""
    live = bench_run.load_reader("paged_attention_roofline").live_contexts
    return live([dict(r, prefix_hit=r["prompt_len"]) for r in requests],
                w0, w1)


def read(facts):
    trace = facts.get("trace")
    if not trace or "trace_span" not in facts:
        return None
    spent = call_seconds(trace)
    if not spent:
        return None
    contexts = decode_contexts(facts["requests"], *facts["trace_span"])
    ops, nbytes = flops.paged_attention_live(facts["config"], contexts)
    least, _ = flops.roofline_seconds(ops, nbytes, facts["peak"])
    return 100.0 * least / spent
