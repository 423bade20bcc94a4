"""Share of the chip's busy time in what holds the sub-layers together:
the embedding (``embed``), the norms (``norm``), K/V writes, table
gathers and state rows (``cache``), and the work under a named scope
outside every kind (residual adds, the chunk loop's own bookkeeping).

Percent of the seconds ``chipbench/scope_join.py`` joins: the device
trace's operations against the program's own map from HLO instruction to
named scope (``paddle_tpu.observability.trace.device_scopes``), SELF
seconds, over the traced window's busy time.  A program without the map
gives nothing to read."""

from chipbench import scope_join

NAME = "step.glue_busy_share"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def read(facts):
    return scope_join.share(facts, scope_join.GLUE)
