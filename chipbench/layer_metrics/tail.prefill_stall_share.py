"""Share of the TAIL SET's seconds from first token to finish
(``chipbench/tail_account.py``) spent waiting for a chunk INSIDE some other
request's prefill clock pair: the device ran another prompt's pieces and its
first-token fetch.  With ``tail.host_stall_share`` and the chunks' own share
it sums to 100."""

from chipbench import tail_account

NAME = "tail.prefill_stall_share"
LAYER = "Serving scheduler"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    t = tail_account.tail(facts["stats"])
    return (None if t is None
            else 100.0 * t["seconds"]["stall_prefill"] / t["T"])
