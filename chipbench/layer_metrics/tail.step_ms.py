"""A decode step's clock-pair time inside the TAIL SET's own chunks
(``chipbench/tail_account.py``): the seconds its requests spent inside their
chunks' clock pairs over the steps those chunks ran, C / K.  Beside
``step.decode_ms`` (the median step of every chunk of the window) it says
what the step was at the load the slowest fifth met."""

from chipbench import tail_account

NAME = "tail.step_ms"
LAYER = "Decode/prefill step"
UNIT = "ms"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    t = tail_account.tail(facts["stats"])
    return None if t is None else 1e3 * t["seconds"]["chunk"] / t["K"]
