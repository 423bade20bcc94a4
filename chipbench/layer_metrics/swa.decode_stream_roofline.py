"""A sink-window routed stack's decode step against its memory roofline:
the least time one batched decode step could take over the median step
the engine measured (``serving.step_seconds``: chunk wall over steps in
the chunk, the wall ending in the token fetch).

``chipbench/mixed_kv_bytes.py`` counts 2 bytes for every matmul parameter
OUTSIDE the routed experts, 2 bytes a parameter of each expert TOUCHED
(``serving.moe_experts_touched{phase=decode}`` over the decode steps,
which are ``serving.moe_expert_visits{phase=decode}`` over held experts
x routed layers) and the K/V of the live contexts at the PUBLISHED
values: 2,560 B a position a full plane, 5,120 B x min(context, 128) a
window plane (the requests' own lengths, as the hybrid reader takes
them), whatever the pool stores.  By counting touched experts and
published lanes only it cannot pass 100% whatever the kernels skip; a
reading over 100 is a miscount.  A program without the counters, or a
family whose planes are of one kind, gives nothing to read."""

from chipbench import mixed_kv_bytes, moe_bytes
from chipbench import run as bench_run

NAME = "swa.decode_stream_roofline"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    hist = facts["stats"].get("serving.step_seconds") or {}
    peak, config = facts.get("peak"), facts.get("config")
    if (not peak or not hist.get("p50")
            or mixed_kv_bytes.sizes(config) is None):
        return None
    count = moe_bytes.counts(facts["stats"], "decode")
    if count is None:
        return None
    n_steps = mixed_kv_bytes.steps(config, count)
    contexts = bench_run.load_reader(
        "hybrid.decode_stream_roofline").decode_contexts(facts["requests"])
    nbytes = mixed_kv_bytes.decode_step_bytes(
        config, count["touched"] / n_steps, contexts, n_steps)
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / hist["p50"]
