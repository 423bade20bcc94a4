"""A sparse-latent routed stack's decode step against its memory
roofline: the least time one batched decode step could take over the
median step the engine measured (``serving.step_seconds``: chunk wall
over steps in the chunk, the wall ending in the token fetch).

``chipbench/dsa_bytes.py`` counts 2 bytes for every matmul parameter
OUTSIDE the routed experts, 2 bytes a parameter of each expert TOUCHED
(``serving.moe_experts_touched{phase=decode}`` over the decode steps,
which are ``serving.moe_expert_visits{phase=decode}`` over held experts
x routed layers) and what the attention of the live contexts reads at
the STORED lanes: every position's index key and ``min(context,
index_topk)`` rows a full plane, ``min(context, window)`` rows a sliding
plane (the requests' own lengths, as the hybrid reader takes them).  By
counting touched experts and selected rows only it cannot pass 100%; a
reading over 100 is a miscount.  A program without the counters, or a
family with no indexer, gives nothing to read."""

from chipbench import dsa_bytes, moe_bytes
from chipbench import run as bench_run

NAME = "dsa.decode_stream_roofline"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    hist = facts["stats"].get("serving.step_seconds") or {}
    peak, config = facts.get("peak"), facts.get("config")
    if (not peak or not hist.get("p50") or not config
            or dsa_bytes.sizes(config) is None):
        return None
    count = moe_bytes.counts(facts["stats"], "decode")
    if count is None:
        return None
    n_steps = dsa_bytes.steps(config, count)
    contexts = bench_run.load_reader(
        "hybrid.decode_stream_roofline").decode_contexts(facts["requests"])
    nbytes = dsa_bytes.decode_step_bytes(
        config, count["touched"] / n_steps, contexts, n_steps)
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / hist["p50"]
