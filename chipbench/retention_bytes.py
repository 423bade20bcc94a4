"""Bytes and operations of power retention on the serving path, and of
the decode step around it, from sizes alone: whatever implements the
layer, this is what it cannot avoid.  ``chipbench/RETENTION.md`` has the
arithmetic at the published sizes.

A K/V head's state is ``S [rows, d]`` and ``z [rows]`` (``rows`` =
``d (d + 1) / 2`` features: 8,256 at 128 lanes, whatever the layout
stores), float32: 34,080,768 B a slot a layer at 8 heads.  A decode
step reads it once and writes it once for every LIVE slot in every
layer; it decays it (one operation a value), adds the rank-one update
(two) and reads it through the group's query rows (two a query head a
value).  A prefill piece of ``n`` rows reads and writes the ONE slot's
state once a layer, scores its rows against each other (the quadratic
form: ``q k^T`` and the weighted values, ``4 n^2 d`` a query head),
reads the state through ``phi(q)`` (``2 rows d`` a row a query head)
and advances it by ``phi(K)^T V`` (``2 rows d`` a row a K/V head).  The
sizes come from the family's ``retention_sizes(config)``.
"""

from . import families

# the widest piece the engine prefills (serving.batched_decode.
# PREFILL_PIECE): all pieces of an admission but its last are this wide
PIECE = 128


def sizes(config):
    """``retention_sizes`` of the configuration's family, or ``None``
    for a family with no retention layer."""
    family = families.of(config)
    if not hasattr(family, "retention_sizes"):
        return None
    return family.retention_sizes(config)


def step(config):
    """(operations, bytes) of ONE slot's decode step in ONE layer."""
    size = sizes(config)
    values = size["kv_heads"] * size["state_rows"] * (size["head_dim"] + 1)
    ops = 3 * values + 2 * size["heads"] * size["state_rows"] * (
        size["head_dim"] + 1)
    return ops, 2 * size["state_bytes"]


def piece(config, rows, itemsize=2):
    """(operations, bytes) of ONE prefill piece of ``rows`` rows in ONE
    layer: the slot's state read and written once, the rows' q, k, v in
    and the outputs out (``itemsize`` bytes a value)."""
    size = sizes(config)
    d, feats = size["head_dim"], size["state_rows"]
    ops = (4 * size["heads"] * rows * rows * d
           + 2 * size["heads"] * rows * feats * (d + 1)
           + 2 * size["kv_heads"] * rows * feats * (d + 1))
    nbytes = (2 * size["state_bytes"]
              + rows * d * itemsize * (2 * size["heads"]
                                       + 2 * size["kv_heads"]))
    return ops, nbytes


def least_seconds(ops, nbytes, peak):
    return max(nbytes / peak["hbm_bytes_per_s"],
               ops / peak["bf16_flops_per_s"])


def decode_step_bytes(config, matmul_params, live_slots, itemsize=2):
    """Bytes ONE batched decode step cannot avoid at ``live_slots``
    slots live: every matmul parameter once for the whole batch and the
    live slots' state of every layer, read and written."""
    size = sizes(config)
    return (itemsize * matmul_params
            + live_slots * size["layers"] * 2 * size["state_bytes"])
