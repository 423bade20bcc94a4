"""Most KV blocks the window had in use at once (live requests and the
prefix trie together), as a share of the pool's blocks: the
``serving.blocks_in_use`` gauge over ``serving.kv_blocks_total``, read by
the load generator 20 times a second.  What is left is reserved and
empty."""

NAME = "sched.block_occupancy_peak"
LAYER = "Serving scheduler"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    seen = facts.get("blocks_in_use_seen")
    return 100.0 * max(seen) / facts["pool_blocks"] if seen else None
