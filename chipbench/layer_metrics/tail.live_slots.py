"""The load the TAIL SET's chunks ran at (``chipbench/tail_account.py``): the
rows the device stepped in each chunk of a tail request, weighed by the
chunk's steps, ``serving.tpot_tail_slot_steps`` / ``serving.tpot_tail_steps``.
``step.decode_base_ms`` + this x ``step.decode_ms_per_live_slot`` is what the
window's own line gives for ``tail.step_ms``."""

from chipbench import tail_account

NAME = "tail.live_slots"
LAYER = "Serving scheduler"
UNIT = "slots"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    t = tail_account.tail(facts["stats"])
    return None if t is None else t["slot_steps"] / t["K"]
