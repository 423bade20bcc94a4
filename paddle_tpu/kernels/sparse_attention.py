"""Learned sparse attention over a paged LATENT plane: a row attends
the cached positions its INDEXER picked, not a range of the chain.

The planes of ``kernels/paged_attention.py`` are walked from a lower
bound to the context.  Here (``serving.arch.SparseLatentMoE``'s full
layers; DeepSeek-V3.2's sparse attention) a query row at position ``t``
reads, of the ``t + 1`` cached rows, the ``topk`` whose index score is
largest: by position, not by block, another set for every slot and row.
A plane holds TWO arrays under one block id: the latent rows ``pool
[blocks, B, L]`` (``paged_attention``'s latent plane) and the index keys
``pool_idx [blocks, B, d_I]``.  Three steps, one call
(:func:`sparse_attend`, which ``serving/batched_decode._Cache.sparse``
makes after it has written both arrays):

* :func:`index_scores` (op class ``index_scores``, named scope
  ``paged_index_scores``): ``I[s, w, j] = sum_h w_h relu(q_h . k_j)``
  over EVERY position of the slot's chain, float32, ``-inf`` past the
  row's position.  No softmax: the heads are reduced and nothing else.
* the selection (:func:`select_positions`, named scope
  ``index_select``): EXACTLY the ``topk`` positions of largest score, no
  approximate top-k, and no sort either: the ``topk``-th largest score
  by counting passes over the scores' bits, then the positions of the
  lanes at or above it.  A row with fewer than ``topk`` positions selects
  them all, ``-1`` filling the rest, which the attention masks.
* :func:`sparse_latent_attention` (op class
  ``sparse_latent_attention``, named scope
  ``paged_sparse_latent_attention``): the absorbed query rows ``[S, W,
  h, L]`` against the ``topk`` gathered latent rows of each (slot, row),
  one softmax, the values those rows' first ``value_lanes`` lanes.

A table that cannot hold more than ``topk`` positions never binds the
selection: :func:`sparse_attend` then IS ``paged_attention.attend`` on
the latent array (and lowers to it), and no index key is read.

WHAT A DEAD SLOT COSTS.  A decode step's table has a row a slot, and a
released slot's row is zeros with ``pos = -1``.  The three steps have
static shapes, so run on the table they gather 1,064 blocks of keys for
such a slot, score 34,048 positions, select among as many minus
infinities and gather 2,048 rows of block 0: a dead slot costs what a
live one does, and more (ten slots of 34,048 positions take 1,051 us
with all live and 1,015 - 1,403 with 9 - 1 live: benchmarks/
sparse_walk.py ``--live``, PERF.md PR 56).  :func:`sparse_attend`
therefore packs the LIVE slots to the front and takes, under one
``lax.switch``, the branch that runs the three steps for
:func:`slots_run` of them: a dead slot beyond that count costs nothing,
one within it what it cost before.

Both op classes have the ``xla_ref`` backend only: the gather is XLA's
(``pool[block, offset]``), in pieces of query rows so that a 512-row
prefill piece never holds more than ``GATHER_BYTES`` of gathered rows or
``INDEX_SCORE_BYTES`` of per-head index scores.  Selection by row DMA
inside one Mosaic kernel is not written (PERF.md section 7).
"""

import jax
import jax.numpy as jnp

from . import paged_attention as _paged
from .registry import register_kernel, resolve
from .xla_ref import NEG_INF

__all__ = ["COMPACT_LANES", "GATHER_BYTES", "INDEX_SCORE_BYTES",
           "SELECT_BYTES", "index_scores", "select_positions",
           "slots_run", "sparse_attend", "sparse_latent_attention"]

# what one piece of query rows may hold of float32 per-head index scores
# ``[rows, H_I, T]`` before the heads are reduced, and of gathered latent
# rows ``[rows, topk, L]``: a wide window goes through in pieces of the
# largest power of two of rows within them (``_piece_rows``)
INDEX_SCORE_BYTES = 256 << 20
GATHER_BYTES = 128 << 20
# the selection's own intermediates a piece (``_compact``: for each of
# ``topk`` output slots one comparison a block of the chain and the
# gathered ranks of one block), and the lanes of a block there
SELECT_BYTES = 128 << 20
COMPACT_LANES = 128


def _piece_rows(width, row_bytes, budget):
    """Query rows one piece takes: the largest power of two that divides
    ``width`` and whose ``row_bytes`` a row stay within ``budget`` (at
    least one row)."""
    rows = 1
    while (rows * 2 <= width and width % (rows * 2) == 0
           and rows * 2 * row_bytes <= budget):
        rows *= 2
    return rows


def _in_pieces(fn, rows, width, *arrays):
    """``fn`` over ``arrays [S, W, ...]`` in pieces of ``rows`` of the
    ``W`` axis (``lax.map``: a piece's intermediates are dropped before
    the next), the results joined on that axis again."""
    if rows >= width:
        return fn(*arrays)
    n = width // rows
    split = [jnp.moveaxis(a.reshape(a.shape[0], n, rows, *a.shape[2:]), 1, 0)
             for a in arrays]
    out = jax.lax.map(lambda piece: fn(*piece), tuple(split))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape(out.shape[0], width, *out.shape[3:])


def index_scores_ref(q, weight, pool_idx, table, pos):
    """``q [S, W, H_I, d_I]``, ``weight [S, W, H_I]`` float32, ``pool_idx
    [blocks, B, d_I]``, ``table [S, NB]``, ``pos [S, W]`` -> ``I [S, W,
    NB * B]`` float32: ``sum_h weight_h relu(q_h . k_j)`` for ``j <=
    pos``, ``-inf`` beyond (a row at ``pos < 0`` scores nothing)."""
    S, W, H, d = q.shape
    T = table.shape[1] * pool_idx.shape[1]
    at = jnp.arange(T, dtype=jnp.int32)

    # the gather of the chain's keys through the table is the call's own
    # work: inside the scope, where its seconds are counted
    with jax.named_scope("paged_index_scores"):
        keys = pool_idx[table.astype(jnp.int32)].reshape(S, T, d)

        def piece(qp, wp, pp):
            s = jnp.einsum("swhd,std->swht", qp, keys,
                           preferred_element_type=jnp.float32)
            score = jnp.sum(jax.nn.relu(s) * wp[..., None], axis=2)
            return jnp.where(at[None, None, :] <= pp[..., None], score,
                             -jnp.inf)

        return _in_pieces(piece, _piece_rows(W, 4 * S * H * T,
                                             INDEX_SCORE_BYTES),
                          W, q, weight.astype(jnp.float32), pos)


def index_scores(q, weight, pool_idx, table, pos):
    return resolve("index_scores").impl.call(q, weight, pool_idx, table, pos)


def _keys(scores):
    """Float32 scores as uint32 keys of the same order (``-inf`` lowest,
    above 0: key 0 is left for what is no candidate at all)."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _kth_largest(keys, k):
    """The ``k``-th largest of ``keys [..., T]`` uint32 (the largest
    ``v`` with ``count(keys >= v) >= k``), two bits at a time from the
    top: 16 counting passes, every row at once, unrolled (a loop's own
    instruction has no name in the trace, and its turns cost as much as
    a pass over ten rows)."""
    found = jnp.zeros(keys.shape[:-1], jnp.uint32)
    for shift in range(30, -1, -2):
        cands = jnp.stack([found | jnp.uint32(c << shift) for c in (1, 2, 3)],
                          axis=-1)                                # [..., 3]
        enough = jnp.sum(keys[..., None, :] >= cands[..., :, None], axis=-1,
                         dtype=jnp.int32) >= k
        # the counts fall as the candidate rises: as many as have enough
        found |= jnp.sum(enough, axis=-1).astype(jnp.uint32) << shift
    return found


def _compact(mask, k):
    """The positions of the first ``k`` true lanes of ``mask [R, T]``,
    ascending, ``-1`` where it has fewer: ``[R, k]`` int32, with no sort
    and no scatter.  ``T`` is cut in blocks of ``COMPACT_LANES``: a
    block's count and a lane's rank inside its block (a product with a
    triangle of ones) say in which block output slot ``r`` falls and
    which lane of it it is; one row gather of ``k`` blocks' ranks."""
    R, T = mask.shape
    C = min(COMPACT_LANES, T)
    pad = (-T) % C
    if pad:
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nb = (T + pad) // C
    blocks = mask.reshape(R, nb, C)
    tri = jnp.triu(jnp.ones((C, C), jnp.bfloat16))
    rank = jnp.einsum("rbc,cd->rbd", blocks.astype(jnp.bfloat16), tri,
                      preferred_element_type=jnp.float32).astype(jnp.int32)
    rank = jnp.where(blocks, rank, 0)          # 1 .. C on the true lanes
    upto = jnp.cumsum(jnp.sum(blocks, axis=-1, dtype=jnp.int32), axis=-1)
    slot = jnp.arange(k, dtype=jnp.int32)
    before = upto[:, None, :] <= slot[None, :, None]              # [R, k, nb]
    blk = jnp.sum(before, axis=-1, dtype=jnp.int32)
    base = jnp.max(jnp.where(before, upto[:, None, :], 0), axis=-1)
    rows = jnp.take_along_axis(rank, jnp.minimum(blk, nb - 1)[..., None],
                               axis=1)                            # [R, k, C]
    hit = rows == (slot[None, :] - base + 1)[..., None]
    lane = jnp.argmax(hit, axis=-1).astype(jnp.int32)
    return jnp.where((blk < nb) & jnp.any(hit, axis=-1), blk * C + lane, -1)


def select_positions(scores, topk):
    """The ``topk`` positions of largest score, ``[S, W, topk]`` int32,
    EXACT, ascending by position; ``-1`` fills a row that has fewer than
    ``topk`` scores above ``-inf``.  Not ``lax.top_k``: on the chip that
    is a sort a row (275 us a row of 34,048, 2.75 ms of a decode step's
    3.4 ms a layer: benchmarks/sparse_walk.py, PERF.md PR 55).  The
    ``topk``-th largest score is found by 16 counting passes over the
    scores' bits (``_kth_largest``), the scores above it and the first
    of those equal to it (ties go to the lower position, as ``top_k``'s
    do) are a mask of exactly ``topk`` lanes, and ``_compact`` turns the
    mask into positions."""
    S, W, T = scores.shape

    def piece(sc):
        keys = jnp.where(sc > -jnp.inf, _keys(sc), jnp.uint32(0))
        keys = keys.reshape(-1, T)
        kth = _kth_largest(keys, topk)[:, None]
        above, equal = keys > kth, (keys == kth) & (kth > 0)
        short = topk - jnp.sum(above, axis=-1, keepdims=True,
                               dtype=jnp.int32)
        equal &= jnp.cumsum(equal, axis=-1, dtype=jnp.int32) <= short
        return _compact(above | equal, topk).reshape(*sc.shape[:2], topk)

    with jax.named_scope("index_select"):
        return _in_pieces(piece, _piece_rows(
            W, S * topk * (T // COMPACT_LANES + COMPACT_LANES) * 4,
            SELECT_BYTES), W, scores)


def sparse_latent_attention_ref(q, pool, table, pos, sel, value_lanes,
                                scale=None, out_dtype=None):
    """``q [S, W, h, L]`` against the rows of ``pool [blocks, B, L]`` at
    the positions ``sel [S, W, K]`` of each slot's chain (``table [S,
    NB]``), masked ``sel <= pos``: one softmax over the ``K`` gathered
    rows, float32, the values their first ``value_lanes`` lanes ->
    ``[S, W, h, value_lanes]``.  A row none of whose positions is live
    returns zeros."""
    S, W, h, L = q.shape
    B, K, dv = pool.shape[1], sel.shape[-1], int(value_lanes)
    out_dtype = q.dtype if out_dtype is None else out_dtype
    if scale is None:
        scale = 1.0 / float(L) ** 0.5
    tbl = table.astype(jnp.int32)

    def piece(qp, pp, sp):
        live = (sp <= pp[..., None]) & (sp >= 0)                 # [S, w, K]
        blk = jnp.take_along_axis(tbl[:, None, :], sp // B, axis=-1)
        rows = pool[jnp.where(live, blk, 0), sp % B]             # [S, w, K, L]
        s = jnp.einsum("swhl,swkl->swhk", qp, rows,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(live[:, :, None, :], s, NEG_INF)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(live[:, :, None, :], p, 0.0)
        l = jnp.sum(p, axis=-1)
        ctx = jnp.einsum("swhk,swkv->swhv", p,
                         rows[..., :dv].astype(jnp.float32))
        return (ctx / jnp.where(l == 0.0, 1.0, l)[..., None]).astype(
            out_dtype)

    with jax.named_scope("paged_sparse_latent_attention"):
        return _in_pieces(
            piece, _piece_rows(W, S * K * L * pool.dtype.itemsize,
                               GATHER_BYTES), W, q, pos, sel)


def sparse_latent_attention(q, pool, table, pos, sel, value_lanes,
                            scale=None, out_dtype=None):
    return resolve("sparse_latent_attention").impl.call(
        q, pool, table, pos, sel, value_lanes, scale=scale,
        out_dtype=out_dtype)


def slots_run(live, slots):
    """The slots a decode step's call runs its three steps for when
    ``live`` of the table's ``slots`` are live: half the table (rounded
    up) where that holds them, the table where it does not, none for
    none (0 | 5 | 10 of ten slots).  The call costs a fixed part and a
    part a slot it runs (84 + 88 us a slot at 34,048 positions, dead or
    live: benchmarks/sparse_walk.py, PERF.md PR 56), so finer counts
    would save more of it; but every count is one more compiled copy of
    the three steps a full plane in the decode chunk, 3.3 s of a WARM
    start (and 10 s of a cold one) in ``dots3np.doc_qa_32k``, whose
    start-up may grow by 7 s: one count beside the table's."""
    if live <= 0:
        return 0
    half = -(-slots // 2)
    return half if live <= half else slots


def _three_steps(pool, pool_idx, q, table, pos, q_idx, w_idx, *, topk,
                 value_lanes, scale, out_dtype):
    sel = select_positions(
        index_scores(q_idx, w_idx, pool_idx, table, pos), topk)
    return sparse_latent_attention(q, pool, table, pos, sel, value_lanes,
                                   scale=scale, out_dtype=out_dtype)


def sparse_attend(q, pool, pool_idx, table, pos, q_idx, w_idx, topk,
                  value_lanes, scale=None, out_dtype=None):
    """One full layer's attention through the table, the one call the
    serving step makes (module docstring): index scores over the chain,
    the ``topk`` positions, the gathered rows attended.  Where the table
    holds no more than ``topk`` positions every live one is selected
    whatever its score: the call is ``paged_attention.attend`` on the
    latent plane.

    A table of several slots (a decode step) pays for the slots that are
    LIVE (any row at ``pos >= 0``), not for its rows: the live slots'
    small operands (``q``, ``table``, ``pos``, ``q_idx``, ``w_idx``) are
    packed to the front in their order, ``lax.switch`` takes the branch
    of ``slots_run(live, S)`` slots, which is the three steps on that
    many packed slots, and the result goes back to the slots' own rows;
    a dead slot's rows are zeros, and a step with no live slot does
    nothing.  Each slot's rows are independent in all three steps, so a
    live slot's output is the bits of the call on that slot alone.  The
    pools are operands the branches only read."""
    if table.shape[1] * pool.shape[1] <= topk:
        return _paged.attend(q, pool, None, table, pos,
                             value_lanes=value_lanes, scale=scale,
                             out_dtype=out_dtype)
    how = dict(topk=topk, value_lanes=value_lanes, scale=scale,
               out_dtype=q.dtype if out_dtype is None else out_dtype)
    S = table.shape[0]
    if S == 1:
        return _three_steps(pool, pool_idx, q, table, pos, q_idx, w_idx,
                            **how)
    # a stable partition of the slots, live first: where slot s goes ...
    alive = jnp.any(pos >= 0, axis=1)
    n_live = jnp.sum(alive, dtype=jnp.int32)
    to = jnp.where(alive, jnp.cumsum(alive, dtype=jnp.int32) - 1,
                   n_live + jnp.cumsum(~alive, dtype=jnp.int32) - 1)
    # ... and which slot packed row r holds (a one-hot [S, S], no sort)
    at = jnp.arange(S, dtype=jnp.int32)
    of = jnp.sum(jnp.where(to[None, :] == at[:, None], at[None, :], 0),
                 axis=1)
    packed = tuple(a[of] for a in (q, table, pos, q_idx, w_idx))
    counts = sorted({slots_run(n, S) for n in range(1, S + 1)})

    def none(pool, pool_idx, *packed):
        return jnp.zeros(q.shape[:-1] + (int(value_lanes),),
                         how["out_dtype"])

    def first(b):
        def run(pool, pool_idx, *packed):
            out = _three_steps(pool, pool_idx, *(a[:b] for a in packed),
                               **how)
            return jnp.pad(out, ((0, S - b),) + ((0, 0),) * (out.ndim - 1))
        return run

    out = jax.lax.switch(
        jnp.sum(n_live > jnp.asarray([0] + counts[:-1]), dtype=jnp.int32),
        [none] + [first(b) for b in counts], pool, pool_idx, *packed)
    return out[to]


class _IndexScoresXlaRef:
    call = staticmethod(index_scores_ref)


class _SparseLatentXlaRef:
    call = staticmethod(sparse_latent_attention_ref)


register_kernel("index_scores", "xla_ref", _IndexScoresXlaRef)
register_kernel("sparse_latent_attention", "xla_ref", _SparseLatentXlaRef)
