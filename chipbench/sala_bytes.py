"""Bytes and operations of a block-sparse / lightning stack on the
serving path (``families/sparse_lightning.py``), from the configuration's
sizes and from counts of what was served: whatever implements a layer,
this is what it cannot avoid, so that a later kernel is read against the
same work.  ``chipbench/SALA.md`` has the arithmetic at the published
sizes.

An ``L`` layer holds, a slot, the state ``S [H, D, D]`` float32 (2,097,152
B at 32 heads of 128).  A decode step reads it once and writes it once
for every LIVE slot in every such layer: it decays it (one operation a
value), adds ``k^T v`` (two) and reads it through ``q`` (two).  A prefill
piece of ``n`` rows reads and writes the ONE slot's state once a layer,
takes its rows in (``v | k | q`` at 2 bytes a value) and gives the read
out (float32); in the chunked form at ``CHUNK`` rows it scores each row's
query against the keys of its chunk (``2 D`` a pair), weighs the chunk's
values (``2 D`` a pair), and makes two products against the carried state
a row (``2 D D`` each).

An ``S`` layer's decode position past ``dense_len`` SCORES the compressed
keys of its context (one row of ``H_kv D`` values every ``stride``
positions: ``2 H D`` operations a row) and ATTENDS ``selected_blocks``
blocks of ``block`` positions a K/V HEAD (each K/V head has its own
selection, and reads its own ``D`` lanes of K and of V: what the model
caches of them, not what a pool block stores beside them): ``4 D``
operations a query head a position.

``decode_least`` is how this family's readers count the traced window's
decode positions: their lengths from the requests' times, their number
from the program's ``serving.decode_chunk`` spans, as
``dsa.indexer_roofline`` does it.
"""

from . import delta_bytes, families
from . import run as bench_run

# the widest piece the engine prefills, and the rows of a chunk of the
# chunked form (``serving.arch.SparseLightning.chunk_size``)
PIECE = delta_bytes.PIECE
CHUNK = 128

least_seconds = delta_bytes.least_seconds
spans_inside = delta_bytes.spans_inside
share = delta_bytes.share


def sizes(config):
    """``sala_sizes`` of the configuration's family, or ``None`` for a
    family with no such layers."""
    family = families.of(config)
    if not hasattr(family, "sala_sizes"):
        return None
    return family.sala_sizes(config)


def step(config):
    """(operations, bytes) of ONE slot's step in ONE ``L`` layer."""
    size = sizes(config)
    return 5 * size["state_bytes"] // 4, 2 * size["state_bytes"]


def piece(config, rows, itemsize=2):
    """(operations, bytes) of the chunked form over ONE prefill piece of
    ``rows`` rows in ONE ``L`` layer."""
    size = sizes(config)
    H, D = size["lin_heads"], size["lin_head_dim"]
    C = min(CHUNK, rows)
    ops = rows * H * (2 * D * C + 2 * D * C + 2 * 2 * D * D)
    nbytes = (2 * size["state_bytes"] + rows * 3 * H * D * itemsize
              + rows * H * D * 4)
    return ops, nbytes


def select_call(config, contexts, itemsize=2):
    """(operations, bytes) of the block scores of the decode positions
    with ``contexts`` (positions attended, one entry a position) in ONE
    ``S`` layer: every compressed row of each context read once."""
    size = sizes(config)
    rows = sum(c // size["stride"] for c in contexts
               if c > size["dense_len"])
    return (2 * size["heads"] * size["head_dim"] * rows,
            rows * size["kv_heads"] * size["head_dim"] * itemsize)


def sparse_call(config, contexts, itemsize=2):
    """(operations, bytes) of the attention of the selected blocks for
    the decode positions with ``contexts`` in ONE ``S`` layer."""
    size = sizes(config)
    n = sum(1 for c in contexts if c > size["dense_len"])
    positions = n * size["selected_blocks"] * size["block"]
    return (4 * size["heads"] * size["head_dim"] * positions,
            positions * size["kv_heads"] * 2 * size["head_dim"] * itemsize)


def decode_step_bytes(config, live_slots, contexts, steps, itemsize=2):
    """Bytes ONE batched decode step cannot avoid, as the mean over
    ``steps`` steps that served the decode positions ``contexts`` at
    ``live_slots`` slots live in the mean: every matmul parameter once
    for the whole batch, the live slots' state of every ``L`` layer read
    and written, and a position's compressed keys and selected blocks in
    every ``S`` layer."""
    size = sizes(config)
    sparse = (select_call(config, contexts, itemsize)[1]
              + sparse_call(config, contexts, itemsize)[1])
    return (itemsize * size["matmul_params"]
            + live_slots * size["lightning_layers"] * 2 * size["state_bytes"]
            + size["sparse_layers"] * sparse / steps)


def decode_least(facts, call):
    """The least seconds of ``call(config, contexts)`` in ONE ``S`` layer
    over the decode positions of the traced window: lengths from the
    requests, their number from the program's spans."""
    contexts = bench_run.load_reader(
        "paged_attention_named_roofline").decode_contexts(
            facts["requests"], *facts["trace_span"])
    if not contexts:
        return None
    least = least_seconds(*call(facts["config"], contexts), facts["peak"])
    sent = bench_run.load_reader("swa.paged_attention_roofline").positions(
        facts)
    return least * (sent / len(contexts) if sent else 1.0)
