"""The instrument's blind share of a training step: busy seconds of the
operations whose HLO instruction has no scope or no match in the
program's map (see ``step.unnamed_busy_share``).

Percent of the seconds ``chipbench/scope_join.py`` joins: the device
trace's operations against the program's own map from HLO instruction to
named scope (``paddle_tpu.observability.trace.device_scopes``), SELF
seconds, over the traced window's busy time.  A program without the map
gives nothing to read."""

from chipbench import scope_join

NAME = "train.unnamed_busy_share"
LAYER = "Program lowering"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
RUNNERS = ("train",)


def read(facts):
    return scope_join.unnamed_share(facts)
