"""What the window planes' chains keep of what whole chains would: 100 x
``serving.window_blocks_held`` / ``serving.window_blocks_whole``.  At
every decode chunk the engine adds, on the host, the blocks the live
slots hold in the window planes' chain and the blocks whole chains would
hold of the same slots (an entry for every ``block_tokens`` positions up
to each slot's own).  Some 3% at 6,000 positions where a slot keeps its
window and the chunk in flight (5 blocks of 188); 100 on a tree that
gives nothing back.  A fact of the engine's chains by kind, not of any
kernel: it says what the pool of the window planes need not hold, which
is what lets 24 slots of 13,312 positions fit beside the weights (whole
chains store 0.57 GB a slot and some 14 slots of them would fit: a
request that would wait for a slot decodes beside the others, and the
step follows the load, so the tail it moves is ``tpot_p90_ms``).  A
program without the two counters (every plane's chain whole) gives
nothing to read."""

NAME = "kv.window_held_share"
LAYER = "Serving scheduler"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    stats = facts["stats"]
    whole = stats.get("serving.window_blocks_whole")
    if not whole:
        return None
    return 100.0 * stats.get("serving.window_blocks_held", 0.0) / whole
