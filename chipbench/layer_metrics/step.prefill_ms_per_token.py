"""Median, over the window's requests, of the prefill call's wall time
(``Request.prefill_t1 - prefill_t0``; it ends in a host fetch) per prompt
token actually prefilled (the prompt less its prefix hit)."""

import statistics

NAME = "step.prefill_ms_per_token"
LAYER = "Decode/prefill step"
UNIT = "ms"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    per = [(r["prefill_t1"] - r["prefill_t0"])
           / max(1, r["prompt_len"] - r["prefix_hit"])
           for r in facts["requests"] if r["prefill_t1"] is not None]
    return statistics.median(per) * 1e3 if per else None
