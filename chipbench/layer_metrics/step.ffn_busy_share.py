"""Share of the chip's busy time in the feed-forward halves: the dense
FFN or gated MLP (``ffn``) and, in a routed layer, routing, sort and
gather (``moe.route``), the grouped products (``moe.experts``) and the
shared expert (``moe.shared``).

Percent of the seconds ``chipbench/scope_join.py`` joins: the device
trace's operations against the program's own map from HLO instruction to
named scope (``paddle_tpu.observability.trace.device_scopes``), SELF
seconds, over the traced window's busy time.  A program without the map
gives nothing to read."""

from chipbench import scope_join

NAME = "step.ffn_busy_share"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def read(facts):
    return scope_join.share(facts, ("ffn", "moe.route", "moe.experts", "moe.shared"))
