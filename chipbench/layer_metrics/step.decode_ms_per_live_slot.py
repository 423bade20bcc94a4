"""Slope of the least-squares line of a decode step's clock-pair time
against the rows its chunk stepped, over EVERY chunk of the window
(``serving.chunk_fit``; ``chipbench/tail_account.py::chunk_fit``): what one
more live slot adds to a step.  None where the rows never varied."""

from chipbench import tail_account

NAME = "step.decode_ms_per_live_slot"
LAYER = "Decode/prefill step"
UNIT = "ms"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    fit = tail_account.chunk_fit(facts["stats"])
    return None if fit is None else 1e3 * fit[1]
