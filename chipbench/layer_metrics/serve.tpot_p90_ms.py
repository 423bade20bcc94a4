"""The judged metric as the ENGINE measures it: ``serving.tpot_p90_seconds``,
the 90th percentile (nearest rank) of the finished requests' time per output
token, each (finish - first token) / (tokens - 1) taken where the request
finished.  The runner's ``tpot_p90_ms`` is the Harrell-Davis estimate over
the same requests from outside; a traced run's line carries this one alone.
A program without the gauge (the parent of PR 53) gives nothing to read."""

NAME = "serve.tpot_p90_ms"
LAYER = "Entry points"
UNIT = "ms"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    p90 = facts["stats"].get("serving.tpot_p90_seconds")
    return None if p90 is None else 1e3 * p90
