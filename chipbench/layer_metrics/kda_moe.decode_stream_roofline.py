"""A delta-rule mixture-of-experts stack's decode step against its memory
roofline: the least time the run's MEAN batched decode step could take
over the MEAN step the engine measured (``serving.step_seconds``: chunk
wall over steps in the chunk, the wall ending in the token fetch).  Both
are means over the same steps, so the share is the bytes every decode
step of the run had to stream over the seconds they took: it cannot pass
100 whatever the spread of the load.  (The sibling readers hold the mean
step's bytes against the MEDIAN step's seconds; where the load is skewed
to a few busy steps, as in this cell, the mean step's bytes are more than
the median step's: that reading was 96.0 in PR 57's first traced run.)

``chipbench/delta_bytes.py`` counts 2 bytes for every matmul parameter
OUTSIDE the routed experts (once for the whole batch: 1.38 GB at the
published sizes), 2 bytes a parameter of each expert TOUCHED (``serving.
moe_experts_touched{phase=decode}`` over the decode steps, which are
``serving.moe_expert_visits{phase=decode}`` over held experts x routed
layers; 31.5 MB an expert), every LIVE slot's state of every delta layer
read once and written once (2 x 4,194,304 B a layer) and the K/V the
model caches of the live contexts at 4,096 B a position (the requests'
own lengths: one entry a decode position).  A step cannot stream less, so
the share cannot pass 100; a reading over 105 is refused as a miscount.
A program without the counters, or a family with no such layers, gives
nothing to read."""

from chipbench import delta_bytes, moe_bytes
from chipbench import run as bench_run

NAME = "kda_moe.decode_stream_roofline"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    hist = facts["stats"].get("serving.step_seconds") or {}
    peak, config = facts.get("peak"), facts.get("config")
    if not peak or not hist.get("mean") or not config:
        return None
    if delta_bytes.sizes(config) is None:
        return None
    count = moe_bytes.counts(facts["stats"], "decode")
    if count is None:
        return None
    n_steps = delta_bytes.steps(config, count)
    contexts = bench_run.load_reader(
        "hybrid.decode_stream_roofline").decode_contexts(facts["requests"])
    nbytes = delta_bytes.decode_step_bytes(
        config, count["touched"] / n_steps, len(contexts) / n_steps,
        sum(contexts) / n_steps)
    return delta_bytes.share(
        NAME, 100.0 * nbytes / peak["hbm_bytes_per_s"] / hist["mean"])
