"""Share of the chip's busy time in the attention projections
(``attn.proj``: the q, k, v and out products with their bias adds), all
phases: forward, backward and recompute.

Percent of the seconds ``chipbench/scope_join.py`` joins: the device
trace's operations against the program's own map from HLO instruction to
named scope (``paddle_tpu.observability.trace.device_scopes``), SELF
seconds, over the traced window's busy time.  A program without the map
gives nothing to read."""

from chipbench import scope_join

NAME = "train.attn_proj_busy_share"
LAYER = "Program lowering"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
RUNNERS = ("train",)


def read(facts):
    return scope_join.share(facts, ("attn.proj",))
