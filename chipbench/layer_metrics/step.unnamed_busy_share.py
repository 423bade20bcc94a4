"""The instrument's blind share: busy seconds of the operations whose
HLO instruction has no scope (copies and async starts the compiler made
and nothing named uses) or no match in the program's map (an executable
loaded from a cache an older tree wrote carries that tree's scopes: the
cure is a cold cache).

Percent of the seconds ``chipbench/scope_join.py`` joins: the device
trace's operations against the program's own map from HLO instruction to
named scope (``paddle_tpu.observability.trace.device_scopes``), SELF
seconds, over the traced window's busy time.  A program without the map
gives nothing to read."""

from chipbench import scope_join

NAME = "step.unnamed_busy_share"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def read(facts):
    return scope_join.unnamed_share(facts)
