"""Of the cached positions a full plane's indexer scored in the decode
chunks, the share its attention then read:
``serving.sparse_positions_attended{phase=decode}`` over
``serving.index_positions_scored{phase=decode}`` (6% at 33,000 positions
under an ``index_topk`` of 2,048; 100% while contexts stay within it).
A program without the counters gives nothing to read."""

NAME = "dsa.attended_position_share"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    stats = facts["stats"]
    scored = stats.get("serving.index_positions_scored{phase=decode}")
    picked = stats.get("serving.sparse_positions_attended{phase=decode}")
    if not scored or picked is None:
        return None
    return 100.0 * picked / scored
