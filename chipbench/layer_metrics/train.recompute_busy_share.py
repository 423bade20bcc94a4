"""Share of the chip's busy time spent computing again what the forward
pass had computed (every kind, phase ``recompute``): what the remat
policy times the micro-batches costs.

Percent of the seconds ``chipbench/scope_join.py`` joins: the device
trace's operations against the program's own map from HLO instruction to
named scope (``paddle_tpu.observability.trace.device_scopes``), SELF
seconds, over the traced window's busy time.  A program without the map
gives nothing to read."""

from chipbench import scope_join

NAME = "train.recompute_busy_share"
LAYER = "Program lowering"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
RUNNERS = ("train",)


def read(facts):
    return scope_join.share(facts, None, "recompute")
