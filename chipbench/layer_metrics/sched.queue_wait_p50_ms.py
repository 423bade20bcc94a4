"""Median wait from submission to admission (``Request.admit_t`` -
``Request.submit_t``, the interval ``serving.queue_wait`` observes) over
the window's requests."""

import statistics

NAME = "sched.queue_wait_p50_ms"
LAYER = "Serving scheduler"
UNIT = "ms"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    waits = [r["admit"] - r["submit"] for r in facts["requests"]
             if r["admit"] is not None]
    return statistics.median(waits) * 1e3 if waits else None
