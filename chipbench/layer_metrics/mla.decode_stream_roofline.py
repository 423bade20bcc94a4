"""A latent-cache routed stack's decode step against its memory
roofline: the least time one batched decode step could take over the
median step the engine measured (``serving.step_seconds``: chunk wall
over steps in the chunk, the wall ending in the token fetch).

``moe.decode_stream_roofline`` counts the cache as K and V planes of
heads (``hybrid_bytes.paged_live``), which a latent plane is not.
``chipbench/latent_bytes.py`` counts 2 bytes for every matmul parameter
OUTSIDE the routed experts, 2 bytes a parameter of each expert TOUCHED
(``serving.moe_experts_touched{phase=decode}`` over the decode steps,
which are ``serving.moe_expert_visits{phase=decode}`` over held experts
x routed layers), and the latent rows of the live contexts at the
values the model caches (576 a position a plane at the published sizes,
whatever the pool stores), from the requests' own lengths.  By counting
touched experts and cached values only it cannot pass 100%; a reading
over 100 is a miscount.  A program without the counters, or a family
with no latent plane, gives nothing to read."""

from chipbench import latent_bytes, moe_bytes
from chipbench import run as bench_run

NAME = "mla.decode_stream_roofline"
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
    size = latent_bytes.sizes(config)
    if size is None or "expert_params" not in size:
        return None
    count = moe_bytes.counts(facts["stats"], "decode")
    if count is None:
        return None
    n_steps = count["visits"] / (size["experts_held"] * size["moe_layers"])
    contexts = bench_run.load_reader(
        "hybrid.decode_stream_roofline").decode_contexts(facts["requests"])
    nbytes = latent_bytes.decode_step_bytes(
        config, count["touched"] / n_steps, contexts, n_steps)
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / hist["p50"]
