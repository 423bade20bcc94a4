"""Share of the chip's busy time in the optimizer's update ops
(``optimizer``: ``adam`` and its kin, once a step after the
micro-batches).

Percent of the seconds ``chipbench/scope_join.py`` joins: the device
trace's operations against the program's own map from HLO instruction to
named scope (``paddle_tpu.observability.trace.device_scopes``), SELF
seconds, over the traced window's busy time.  A program without the map
gives nothing to read."""

from chipbench import scope_join

NAME = "train.optimizer_busy_share"
LAYER = "Program lowering"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
RUNNERS = ("train",)


def read(facts):
    return scope_join.share(facts, ("optimizer",))
