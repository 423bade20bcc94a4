"""The decode step's share of its memory roofline: the least time one
batched decode step could take over the median step the engine measured
(``serving.step_seconds``: chunk wall over steps in the chunk, the wall
ending in the token fetch).

The least time is the bytes a step cannot avoid over the chip's peak HBM
rate: every matmul parameter APPLIED to a token, at 2 bytes (a weight a
looped stack applies four times a token is read four times: the layers
do not fit on the chip between passes), read once for the whole batch,
plus K and V of the LIVE contexts only (``flops.kv_bytes_per_token`` a
cached token; idle slots, block padding and overrun steps hold nothing
live).  The live tokens a step attends are the mean over the run's
decode steps, from the requests' own lengths.  What the model is made of
comes from ``families.sizes`` through ``flops``.  The step includes the
host's turn-round, so the share says how far the whole step, not a
kernel, is from the stream it cannot avoid; it cannot pass 100%."""

from chipbench import flops

NAME = "step.decode_stream_roofline"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def live_tokens_per_step(requests, steps):
    """Mean tokens attended in one decode step: the step that emits a
    request's token ``i + 1`` consumes token ``i`` and attends the prompt
    and the ``i`` tokens emitted so far; over the decode steps the
    engine ran."""
    attended = sum(r["prompt_len"] + i for r in requests
                   if r["first"] is not None
                   for i in range(1, r["out"]))
    return attended / steps


def read(facts):
    hist = facts["stats"].get("serving.step_seconds") or {}
    peak = facts.get("peak")
    if not hist.get("count") or not peak or not hist.get("p50"):
        return None
    config = facts["config"]
    steps = hist["count"] * facts["decode_chunk"]
    nbytes = (2 * flops.matmul_params(config)
              + live_tokens_per_step(facts["requests"], steps)
              * flops.kv_bytes_per_token(config))
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / hist["p50"]
