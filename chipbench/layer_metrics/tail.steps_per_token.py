"""The steps a token of the TAIL SET cost (``chipbench/tail_account.py``):
the steps its requests' chunks ran over their tokens after the first, K / N.
Tokens come in chunks of ``decode_chunk`` steps and a request that ends
inside a chunk paid for all of it: 1 where every request ends at a chunk's
last step, more the shorter the outputs are beside the chunk."""

from chipbench import tail_account

NAME = "tail.steps_per_token"
LAYER = "Serving scheduler"
UNIT = "steps"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    t = tail_account.tail(facts["stats"])
    return None if t is None else t["K"] / t["N"]
