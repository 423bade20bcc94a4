"""Median wall time of one batched decode step: the engine's
``serving.step_seconds`` histogram (chunk wall over steps in the chunk;
the wall ends in the token fetch), zeroed after the warm pass."""

NAME = "step.decode_ms"
LAYER = "Decode/prefill step"
UNIT = "ms"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    hist = facts["stats"].get("serving.step_seconds") or {}
    return hist["p50"] * 1e3 if hist.get("count") else None
