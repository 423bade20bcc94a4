"""Bytes and operations of a state-space mixture-of-experts stack on the
serving path (``families/ssm_moe.py``), from sizes and from counts of
what was routed: whatever implements a layer, this is what it cannot
avoid.  ``chipbench/SSM.md`` has the arithmetic at the published sizes.

A Mamba-2 layer holds, a slot, the state ``S [H, P, N]`` float32
(2,097,152 B at 64 heads of 64 over a state of 128, whatever layout
stores it).  A decode step reads it once and writes it once for every
LIVE slot in every such layer; it decays it (one operation a value),
adds the rank-one update (two) and reads it through ``C`` (two).  A
prefill piece of ``n`` rows reads and writes the ONE slot's state once a
layer, takes its rows in (the convolved channels and ``dt``, 2 bytes a
value) and gives ``y`` out (float32); in the chunked form at
``chunk_size`` ``Q`` it scores each row against the rows of its chunk up
to itself (``C B^T`` a group and the weighted ``X`` a head: two
operations a lane a pair), adds each row to its chunk's state and reads
the state before its chunk (``2 H P N`` each a row).  A routed layer's
TWO grouped products read the two matrices of each expert TOUCHED and
multiply the (row, expert) pairs that fell on a held expert (``4 d e`` a
pair).  An attention layer's decode position reads the K and V the model
caches of every position it attends.  The sizes come from the family's ``ssm_moe_sizes(config)``; the
routing counts from the program's counters, read as ``moe_bytes.counts``
reads them.
"""

from . import families, moe_bytes

PHASES = moe_bytes.PHASES
# the widest piece the engine prefills (serving.batched_decode.
# PREFILL_PIECE): all pieces of an admission but its last are this wide
PIECE = 512


def sizes(config):
    """``ssm_moe_sizes`` of the configuration's family, or ``None`` for
    a family with no such layers."""
    family = families.of(config)
    if not hasattr(family, "ssm_moe_sizes"):
        return None
    return family.ssm_moe_sizes(config)


def least_seconds(ops, nbytes, peak):
    return max(nbytes / peak["hbm_bytes_per_s"],
               ops / peak["bf16_flops_per_s"])


def step(config):
    """(operations, bytes) of ONE slot's step in ONE Mamba-2 layer."""
    size = sizes(config)
    return 5 * size["state_bytes"] // 4, 2 * size["state_bytes"]


def piece(config, rows, itemsize=2):
    """(operations, bytes) of the chunked form over ONE prefill piece of
    ``rows`` rows in ONE Mamba-2 layer."""
    size = sizes(config)
    H, P = size["ssm_heads"], size["ssm_head_dim"]
    G, N = size["ssm_groups"], size["ssm_state"]
    Q = min(size["chunk_size"], rows)
    pairs = rows * (Q + 1) // 2
    ops = 2 * pairs * (G * N + H * P) + 4 * rows * H * P * N
    nbytes = (2 * size["state_bytes"]
              + rows * (size["conv_channels"] + H) * itemsize
              + rows * H * P * 4)
    return ops, nbytes


def attention(config, attended):
    """(operations, bytes) of the decode positions that together attend
    ``attended`` cached positions, over all the attention layers: the K
    and V the model caches of each (not what the pool stores of it),
    and every query head's score and weighted value (``4 x head size``
    a head a position)."""
    size = sizes(config)
    return (4 * size["attention_layers"] * size["query_lanes"] * attended,
            attended * size["kv_bytes_per_token"])


def steps(config, count):
    """Steps (decode) or pieces (prefill) the routing counters of one
    phase were summed over."""
    size = sizes(config)
    return count["visits"] / (size["experts_held"] * size["moe_layers"])


def expert_call_seconds(config, touched, assignments, peak, itemsize=2):
    """The least seconds ONE routed layer's two grouped products can
    take on ``touched`` experts with ``assignments`` (row, expert)
    pairs."""
    size = sizes(config)
    return least_seconds(size["expert_ops_per_row"] * assignments,
                         touched * size["expert_params"] * itemsize, peak)


def decode_step_bytes(config, touched_per_step, live_slots, attended,
                      itemsize=2):
    """Bytes ONE batched decode step cannot avoid at ``live_slots`` slots
    live that together attend ``attended`` cached positions and touch
    ``touched_per_step`` (expert, layer) pairs: every matmul parameter
    OUTSIDE the routed experts once for the whole batch, the matrices of
    the experts touched, the live slots' state of every Mamba-2 layer
    read and written, and the K/V the model caches of the positions
    attended."""
    size = sizes(config)
    return (itemsize * (size["outside_params"]
                        + size["expert_params"] * touched_per_step)
            + live_slots * size["ssm_layers"] * 2 * size["state_bytes"]
            + attended * size["kv_bytes_per_token"])
