"""Share of the chip's busy time in the recurrent mixers (``mixer``):
Mamba's projections, convolution and recurrence, and the gated memory
units.  ``hybrid.recurrent_busy_share`` finds the recurrence's state by
its shape and reads a part of this.  Listed only in the cells whose
stack has a mixer: 0 there means the map named none (an executable
from an older tree's cache), and is said, not left out.

Percent of the seconds ``chipbench/scope_join.py`` joins: the device
trace's operations against the program's own map from HLO instruction to
named scope (``paddle_tpu.observability.trace.device_scopes``), SELF
seconds, over the traced window's busy time.  A program without the map
gives nothing to read."""

from chipbench import scope_join

NAME = "step.mixer_busy_share"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def read(facts):
    return scope_join.share(facts, ("mixer",))
