"""Time per output token of the TAIL SET (``chipbench/tail_account.py``): its
seconds from first token to finish over its tokens after the first, T / N,
as the engine sums them.  It lies between the 80th percentile and the
largest of the requests' own values, and is the product of ``tail.step_ms``,
``tail.steps_per_token`` and 100 / (100 - the two stall shares)."""

from chipbench import tail_account

NAME = "tail.tpot_ms"
LAYER = "Entry points"
UNIT = "ms"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    t = tail_account.tail(facts["stats"])
    return None if t is None else 1e3 * t["T"] / t["N"]
