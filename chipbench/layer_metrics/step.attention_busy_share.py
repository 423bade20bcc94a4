"""Share of the chip's busy time in attention: the q/k/v/out projections,
gates and rotary (``attn.proj``) and the paged or dense attention call
with what the architecture does to its output (``attn.core``: the paged
kernel's calls are inside it).

Percent of the seconds ``chipbench/scope_join.py`` joins: the device
trace's operations against the program's own map from HLO instruction to
named scope (``paddle_tpu.observability.trace.device_scopes``), SELF
seconds, over the traced window's busy time.  A program without the map
gives nothing to read."""

from chipbench import scope_join

NAME = "step.attention_busy_share"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def read(facts):
    return scope_join.share(facts, ("attn.proj", "attn.core"))
