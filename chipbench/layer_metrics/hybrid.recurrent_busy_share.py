"""Share of the chip's busy time spent in the recurrences of a stack
that holds per-slot state: device seconds of the operations that read or
write a state-shaped array, over the busy union of the traced window.
What nine latency-bound recurrences cost a step.

This runtime's trace carries the HLO instruction and not its
``op_name``, so the operations are found by what they touch: an
instruction whose text names an array ``f32[n, inner, state]`` (any
``n``: the slots of a decode step, the one slot of a prefill piece), the
shape from the family's ``hybrid_sizes``.  A ``while`` that merely
CARRIES the state (the loop over a chunk's steps, a prefill's scan over
its rows) is not counted itself; the operations in its body are.  Each
operation counts its own time (``self``: less what it holds).  A program
that holds no such state gives nothing to read."""

import re

from chipbench import families

NAME = "hybrid.recurrent_busy_share"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def touches_state(cfg):
    """Matches the text of an instruction, other than a ``while``, that
    names a state-shaped array."""
    inner, state = families.of(cfg).hybrid_sizes(cfg)["state_shape"]
    shape = re.compile(r"f32\[\d+," + f"{inner},{state}" + r"\]")

    def matches(provenance):
        if " while(" in provenance.split(", condition=")[0]:
            return False
        return bool(shape.search(provenance))

    return matches


def read(facts):
    trace = facts.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    if not hasattr(families.of(facts["config"]), "hybrid_sizes"):
        return None
    matches = touches_state(facts["config"])
    seconds = sum(rec["self"] for rec in trace["ops"].values()
                  if matches(rec["provenance"]))
    if not seconds:
        return None
    return 100.0 * seconds / trace["busy_s"]
