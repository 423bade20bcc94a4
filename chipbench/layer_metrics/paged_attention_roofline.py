"""The paged-attention kernel's share of its roofline over the traced
window: the least time the chip could take to attend the LIVE tokens of
the positions processed in the window (K and V of those tokens only,
``chipbench/flops.py``; memory-bound) over the device time the kernel's
calls took.  Positions processed come from the requests' own timestamps:
prefill positions over the prefill interval, one position per emitted
token between first token and finish, each clipped to the window.

The trace gives a Mosaic call no name of the kernel's, so the calls are
found by the pool's shape among their operands ([blocks, block_tokens,
heads, 128], the block count from the engine geometry in the traffic
file): decode steps and prefill's single-slot steps alike."""

from chipbench import families, flops, trace_reduce

NAME = "paged_attention_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def kernels(cfg, mix):
    eng = mix["engine"]
    per_slot = -(-eng["max_len"] // eng["block_tokens"])
    cache = eng.get("cache_blocks", 2 * per_slot)  # the engine's default
    blocks = 1 + eng["max_slots"] * per_slot + cache
    size = families.sizes(cfg)
    pool = (f"bf16[{blocks},{eng['block_tokens']},{size['heads']},"
            f"{size['head_dim']}]")
    return {"paged_attention": ('custom_call_target="tpu_custom_call"',
                                pool, "s32[")}


def _share(t0, t1, w0, w1):
    """Fraction of [t0, t1] inside [w0, w1]."""
    if t1 <= t0:
        return 1.0 if w0 <= t0 <= w1 else 0.0
    return max(0.0, min(t1, w1) - max(t0, w0)) / (t1 - t0)


def live_contexts(requests, w0, w1):
    """Tokens attended, one entry per position processed in [w0, w1]."""
    out = []
    for r in requests:
        if r["prefill_t1"] is None:
            continue
        hit, n_p = r["prefix_hit"], r["prompt_len"]
        part = _share(r["prefill_t0"], r["prefill_t1"], w0, w1)
        n = int(round(part * (n_p - hit)))
        out += [hit + i + 1 for i in range(n)]
        if r["finish"] is not None and r["out"] > 1:
            part = _share(r["first"], r["finish"], w0, w1)
            lo = max(0.0, (w0 - r["first"]) / (r["finish"] - r["first"]))
            first = int(lo * (r["out"] - 1))
            n = int(round(part * (r["out"] - 1)))
            out += [n_p + first + i + 1 for i in range(n)]
    return out


def read(facts):
    trace = facts.get("trace")
    if not trace or "trace_span" not in facts:
        return None
    needles = kernels(facts["config"], facts["traffic"])["paged_attention"]
    _, spent = trace_reduce.matching(trace, *needles)
    if not spent:
        return None
    contexts = live_contexts(facts["requests"], *facts["trace_span"])
    ops, nbytes = flops.paged_attention_live(facts["config"], contexts)
    least, _ = flops.roofline_seconds(ops, nbytes, facts["peak"])
    return 100.0 * least / spent
