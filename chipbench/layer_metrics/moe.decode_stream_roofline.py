"""A routed stack's decode step against its memory roofline: the least
time one batched decode step could take over the median step the engine
measured (``serving.step_seconds``: chunk wall over steps in the chunk,
the wall ending in the token fetch).

``hybrid.decode_stream_roofline`` counts every matmul parameter once a
step, which is not what a routed layer streams: of the experts held
here a step has to read those that a live row selected, and no others.
``chipbench/moe_bytes.py`` counts 2 bytes for every matmul parameter
OUTSIDE the routed experts, 2 bytes a parameter of each expert TOUCHED
(``serving.moe_experts_touched{phase=decode}`` over the decode steps,
which are ``serving.moe_expert_visits{phase=decode}`` over held experts
x routed layers), and the K/V of the live contexts clipped to each
plane's window (the requests' own lengths, as the hybrid reader takes
them).  By counting touched experts only it cannot pass 100% whatever
the kernel skips; a reading over 100 is a miscount.  A program without
the counters, or a family with no routed layer, gives nothing to
read."""

from chipbench import moe_bytes
from chipbench import run as bench_run

NAME = "moe.decode_stream_roofline"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    hist = facts["stats"].get("serving.step_seconds") or {}
    peak, config = facts.get("peak"), facts.get("config")
    if not peak or not hist.get("p50") or moe_bytes.sizes(config) is None:
        return None
    count = moe_bytes.counts(facts["stats"], "decode")
    if count is None:
        return None
    n_steps = moe_bytes.steps(config, count)
    contexts = bench_run.load_reader(
        "hybrid.decode_stream_roofline").decode_contexts(facts["requests"])
    nbytes = moe_bytes.decode_step_bytes(
        config, count["touched"] / n_steps, contexts, n_steps)
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / hist["p50"]
