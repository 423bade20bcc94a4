"""Of the bytes the walk of the selected blocks copies, the share the
model caches of them: what ``sala_bytes.sparse_call`` counts for ONE
selected block of ONE K/V head (``block`` positions of that head's
``head_dim`` lanes of K and of V: 32,768 B at 64 positions of 128 lanes
in bfloat16) over ``serving.sparse_walk_bytes_per_block``, the bytes one
K/V head's walk copies for such a block, which the architecture states
from its plane's block shape (``serving.arch.SparseLightning.gauges``).
100 where a walk fetches its own head's rows alone (a head-major plane,
a ``[64, 128]`` slab of K and of V a head); 12.5 where a block stores its
two K/V heads in eight rows a position and is copied whole.  A share of
BYTES, not of a roofline: ``sala.sparse_attention_roofline`` is the time.
A program without the gauge, or a family with no such layer, gives
nothing to read."""

from chipbench import sala_bytes

NAME = "sala.walk_fetched_share"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    size = sala_bytes.sizes(facts["config"])
    moved = facts["stats"].get("serving.sparse_walk_bytes_per_block")
    if size is None or not moved:
        return None
    # one position past dense_len: its selected blocks, a K/V head each
    _, cached = sala_bytes.sparse_call(facts["config"],
                                       [size["dense_len"] + 1])
    return 100.0 * cached / (size["selected_blocks"] * size["kv_heads"]
                             ) / moved
