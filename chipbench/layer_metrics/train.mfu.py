"""Model FLOP/s utilization: tokens per second per chip (a step's tokens
over the median time between step completions, so that the pauses of a
traced run do not enter) times the operations a trained token requires (``chipbench/flops.py``: 6 per
matmul parameter plus causal attention, recompute not counted) over the
chip's bf16 peak (``chipbench/peaks.json``)."""

import statistics

from chipbench import flops

NAME = "train.mfu"
LAYER = "Program lowering"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"
RUNNERS = ("train",)


def read(facts):
    per_token = flops.train_flops_per_token(facts["config"],
                                            facts["traffic"]["seq_len"])
    done = facts.get("step_done_t") or []
    if len(done) < 3:
        return None
    step_s = statistics.median(b - a for a, b in zip(done, done[1:]))
    rate = facts["tokens_per_step"] / step_s / facts["chips"]
    return 100.0 * rate * per_token / facts["peak"]["bf16_flops_per_s"]
