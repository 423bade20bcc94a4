"""Of the blocks the decode positions past ``dense_len`` had cached, the
share their attention read after the selection:
``serving.sparse_blocks_selected{phase=decode}`` over
``serving.sparse_blocks_live{phase=decode}``, both a (position, K/V head)
in every ``S`` layer, tallied on the device by the stack itself (97 of
some 2,060 blocks at 131,072 positions: 4.7%).  A program without the
counters gives nothing to read."""

NAME = "sala.attended_block_share"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    stats = facts["stats"]
    live = stats.get("serving.sparse_blocks_live{phase=decode}")
    picked = stats.get("serving.sparse_blocks_selected{phase=decode}")
    if not live or picked is None:
        return None
    return 100.0 * picked / live
