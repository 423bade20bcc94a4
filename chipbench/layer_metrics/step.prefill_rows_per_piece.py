"""Real prompt rows a prefill window call computes, over the window:
``serving.prefill_real_tokens`` / the sum over widths of
``serving.prefill_pieces{width}``.  The engine counts both at every
admission: the suffix tokens it prefilled (padding left out) and the
window calls it dispatched for them, by width.  A piece under the chip's
ridge (some 240 rows of a bf16 matrix on a v5e) is a stream of the
weights with the MXU part empty, so this says which cell a wider piece
can reach: a fact of the traffic (how long the suffixes are) and of the
engine's ladder of widths, not of any kernel.  A program without the two
counters gives nothing to read."""

NAME = "step.prefill_rows_per_piece"
LAYER = "Decode/prefill step"
UNIT = "rows"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)

PIECES = "serving.prefill_pieces{"


def read(facts):
    stats = facts["stats"]
    pieces = sum(v for k, v in stats.items() if k.startswith(PIECES))
    if not pieces:
        return None
    return stats.get("serving.prefill_real_tokens", 0.0) / pieces
