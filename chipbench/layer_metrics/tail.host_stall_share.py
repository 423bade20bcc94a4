"""Share of the TAIL SET's seconds from first token to finish
(``chipbench/tail_account.py``) spent waiting for a chunk OUTSIDE any
prefill clock pair: the driver's admission bookkeeping (the queue pick, the
trie's match and insert, block allocation), the table build and the
dispatch, with nothing of the request's on the device."""

from chipbench import tail_account

NAME = "tail.host_stall_share"
LAYER = "Serving scheduler"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    t = tail_account.tail(facts["stats"])
    return (None if t is None
            else 100.0 * t["seconds"]["stall_host"] / t["T"])
