"""A state-space mixture-of-experts stack's decode step against its
memory roofline: the least time one batched decode step could take over
the median step the engine measured (``serving.step_seconds``: chunk
wall over steps in the chunk, the wall ending in the token fetch).

``chipbench/ssm_moe_bytes.py`` counts 2 bytes for every matmul parameter
OUTSIDE the routed experts (once for the whole batch), 2 bytes a
parameter of each expert TOUCHED (``serving.moe_experts_touched{phase=
decode}`` over the decode steps, which are ``serving.moe_expert_visits
{phase=decode}`` over held experts x routed layers), every LIVE slot's
state of every Mamba-2 layer read once and written once (2 x 2,097,152 B
at the published sizes) and the K/V the model caches of the live
contexts (the requests' own lengths: one entry a decode position).  A
step cannot stream less, so the share cannot pass 100; a reading over
100 is a miscount.  A program without the counters, or a family with no
such layers, gives nothing to read."""

from chipbench import moe_bytes, ssm_moe_bytes
from chipbench import run as bench_run

NAME = "ssm_moe.decode_stream_roofline"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    hist = facts["stats"].get("serving.step_seconds") or {}
    peak, config = facts.get("peak"), facts.get("config")
    if not peak or not hist.get("p50") or not config:
        return None
    if ssm_moe_bytes.sizes(config) is None:
        return None
    count = moe_bytes.counts(facts["stats"], "decode")
    if count is None:
        return None
    n_steps = ssm_moe_bytes.steps(config, count)
    contexts = bench_run.load_reader(
        "hybrid.decode_stream_roofline").decode_contexts(facts["requests"])
    nbytes = ssm_moe_bytes.decode_step_bytes(
        config, count["touched"] / n_steps, len(contexts) / n_steps,
        sum(contexts) / n_steps)
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / hist["p50"]
