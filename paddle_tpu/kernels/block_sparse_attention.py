"""Block-sparse attention over a paged K/V plane: a K/V head's queries
attend the BLOCKS of the chain that their scores on a plane of
COMPRESSED keys select (InfLLM-V2, arXiv:2509.24663; MiniCPM4,
arXiv:2506.07900; ``serving.arch.SparseLightning``'s ``S`` layers).

``kernels/sparse_attention.py`` selects single ROWS of a latent plane
with a learned indexer.  Here nothing is learned: a layer caches, beside
K and V, the MEAN of every ``2 stride`` consecutive keys, one row every
``stride`` positions, and a query scores those.  The compressed plane is
an array of its own under the K/V plane's block ids, ``pool_c [blocks, B
/ stride, H_kv D]``, a row where its window ENDS: compressed row ``c`` is
the mean of the keys at ``stride (c - 1) .. stride (c + 1) - 1`` and lies
at ``(table[c // (B / stride)], c % (B / stride))``, in the block that
holds its last position.  So a block's compressed rows depend on nothing
after the block, a chain the prefix trie shares carries them with it, and
the row whose window straddles the end of a shared document lies in the
TAIL's first block and is written by the suffix's prefill
(:func:`compressed_rows`, which ``serving/batched_decode._Cache`` calls
after it has written K).  Row 0 would average positions before the
sequence and is never scored.

For a query at position ``t`` (``pos``; a row at ``pos < 0`` attends
nothing and reads zeros) the call is three steps, each under a named
scope of its own:

* :func:`block_scores` (op class ``block_scores``, scope
  ``paged_block_scores``): over the compressed rows that lie wholly at
  or before ``t``, ``p = softmax(q_h . Kc / sqrt(D))`` a query head,
  float32, summed over the ``group`` heads of the K/V head; a block of
  ``block`` positions scores the maximum of ``p`` over the ``block /
  stride + 1`` compressed rows whose window overlaps it.
* :func:`select_blocks` (scope ``block_select``): the first
  ``init_blocks`` blocks, the ``window_blocks`` blocks that end with the
  query's own, and EXACTLY the ``topk`` of largest score among the
  others before them (``sparse_attention.select_positions``: counting
  passes, no sort), ascending: ``init_blocks + topk + window_blocks``
  blocks a (query row, K/V head).
* :func:`block_sparse_attention` (op class ``block_sparse_attention``,
  scope ``paged_block_sparse_attention``): ONE softmax over the selected
  blocks' positions ``<= t``.  The selected blocks are a shorter TABLE a
  (row, K/V head): its entries are ``table``'s at the selected blocks,
  and the paged kernel (``paged_attention.attend``: the Mosaic loop on a
  TPU) walks it as it walks any chain; because the selection ends with
  the query's own block, position ``t`` is the last block's ``t % block``
  -th and every other selected block lies whole under it.  No gathered
  copy of K or V exists.

**The plane is stored HEAD-MAJOR**: ``pool_k`` and ``pool_v [blocks, H_kv,
B, D]``, a block one ``[B, D]`` slab a K/V head and no row beside them
(``kernels.paged_attention.pool_rows`` is not asked: what the pool stores
is what the model caches).  ``pool.reshape(blocks * H_kv, B, D)`` is a
view in which entry ``id * H_kv + j`` is head ``j``'s slab of block
``id``, so the table of (row, K/V head ``j``) holds ``ids * H_kv + j``,
its query is that head's ``group`` rows alone, and the walk fetches the
16 KB of K and of V it reads and nothing of another head
(:func:`walk`; ``paged_attention.attend``'s slab plane).  A row the
serving step attends DENSELY (under its dense length) walks its whole
chain the same way, head by head, with the same program
(:func:`dense_attention`): one layout serves both sides of the switch.
A position's K (or V) of all the heads is ONE scatter through the same
view (:func:`write`).

:func:`attend` is the one call the serving step makes.  It runs the
three steps only where some row has ``pos >= 0`` (``lax.cond``), and a
decode step's table of several slots pays for the slots that are live:
their small operands packed to the front, one ``lax.switch`` over
``sparse_attention.slots_run`` of them, as ``sparse_attend`` does it.
All three steps have the ``xla_ref`` backend only; the walk of the
selected table is ``paged_attention``'s.
"""

import jax
import jax.numpy as jnp

from . import paged_attention as _paged
from .registry import register_kernel, resolve
from .sparse_attention import (_in_pieces, _piece_rows, select_positions,
                               slots_run)

__all__ = ["SCORE_BYTES", "TABLE_ROWS", "attend", "block_scores",
           "block_sparse_attention", "compressed_rows", "dense_attention",
           "select_blocks", "selected_blocks", "walk", "write"]

# what one piece of query rows may hold of float32 scores on the
# compressed rows ``[rows, H, NC]``, and the (row, K/V head) tables one
# walk of the paged kernel takes (their entries are scalar-prefetched)
SCORE_BYTES = 256 << 20
TABLE_ROWS = 128


def selected_blocks(init_blocks, topk, window_blocks):
    """Blocks a (query row, K/V head) attends."""
    return int(init_blocks) + int(topk) + int(window_blocks)


def write(pool, blk, off, rows):
    """``rows [*blk.shape, H_kv, D]`` written into the head-major ``pool
    [blocks, H_kv, B, D]`` at ``(blk, :, off, :)``: ONE scatter through
    the slab view, a position's row of head ``j`` at ``(blk * H_kv + j,
    off)`` (``paged_attention.write``'s plane with no head axis)."""
    blocks, hk, B, D = pool.shape
    slab = blk[..., None] * hk + jnp.arange(hk, dtype=blk.dtype)
    return _paged.write(pool.reshape(blocks * hk, B, D), slab,
                        off[..., None], rows).reshape(pool.shape)


def compressed_rows(pool_k, table, rows, stride):
    """The compressed keys ``rows [S, n]`` (``c``: the mean of the keys at
    ``stride (c - 1) .. stride (c + 1) - 1``) of the chains ``table [S,
    NB]``, read from ``pool_k [blocks, H_kv, B, D]`` as it holds them
    (through the slab view, as the write and the walk reach it: a gather
    of the plane as it lies has the compiler copy the whole pool into
    another layout, 2.3 ms a decode step at 10,241 blocks; PERF.md, PR
    63): ``[S, n, H_kv * D]`` in the pool's dtype, the mean in float32."""
    blocks, hk, B, D = pool_k.shape
    at = (rows[..., None] - 1) * stride + jnp.arange(2 * stride)   # [S, n, w]
    at = jnp.clip(at, 0, table.shape[1] * B - 1)
    blk = jnp.take_along_axis(table[:, None, :], at // B, axis=-1)
    slab = blk[..., None] * hk + jnp.arange(hk, dtype=blk.dtype)
    keys = pool_k.reshape(blocks * hk, B, D)[slab, (at % B)[..., None]]
    mean = jnp.mean(keys.astype(jnp.float32), axis=2)      # [S, n, H_kv, D]
    return mean.reshape(*mean.shape[:2], -1).astype(pool_k.dtype)


def block_scores_ref(q, pool_c, table, pos, *, group, stride, block):
    """``q [S, W, H, D]``, ``pool_c [blocks, B / stride, H_kv D]``, ``table
    [S, NB]``, ``pos [S, W]`` -> ``[S, W, H_kv, n_blocks]`` float32: the
    blocks' scores (module docstring), 0 for a block no visible
    compressed row overlaps."""
    S, W, H, D = q.shape
    hk = H // group
    per = block // stride
    NC = table.shape[1] * pool_c.shape[1]
    n_blocks = -(-NC // per)
    at = jnp.arange(NC, dtype=jnp.int32)

    with jax.named_scope("paged_block_scores"):
        keys = pool_c[table.astype(jnp.int32)].reshape(S, NC, hk, D)

        def piece(qp, pp):
            qg = qp.reshape(S, -1, hk, group, D)
            s = jnp.einsum("swjgd,scjd->swjgc", qg, keys,
                           preferred_element_type=jnp.float32) * D ** -0.5
            # row c ends at stride (c + 1) - 1; row 0 is never a row
            whole = ((at >= 1) & (stride * (at + 1) - 1
                                  <= pp[..., None]))[:, :, None, None, :]
            s = jnp.where(whole, s, -jnp.inf)
            m = jnp.max(s, axis=-1, keepdims=True)
            e = jnp.where(whole, jnp.exp(s - jnp.where(whole, m, 0.0)), 0.0)
            l = jnp.sum(e, axis=-1, keepdims=True)
            p = jnp.sum(e / jnp.where(l == 0.0, 1.0, l), axis=3)
            # block b is overlapped by rows per b .. per b + per
            p = jnp.pad(p, ((0, 0),) * 3 + ((0, n_blocks * per + 1 - NC),))
            inside = jnp.max(p[..., :n_blocks * per].reshape(
                *p.shape[:3], n_blocks, per), axis=-1)
            return jnp.maximum(inside, p[..., per::per])

        return _in_pieces(piece, _piece_rows(W, 4 * S * H * NC, SCORE_BYTES),
                          W, q, pos)


def block_scores(q, pool_c, table, pos, **how):
    return resolve("block_scores").impl.call(q, pool_c, table, pos, **how)


def select_blocks(scores, pos, *, block, topk, init_blocks, window_blocks):
    """``scores [S, W, H_kv, n_blocks]``, ``pos [S, W]`` -> ``[S, W, H_kv,
    selected_blocks]`` int32, ascending: the forced blocks and the
    ``topk`` of largest score among the others before them (``-1`` where
    a context has fewer: ``attend``'s callers never ask then)."""
    S, W, hk, n_blocks = scores.shape
    with jax.named_scope("block_select"):
        b = jnp.arange(n_blocks, dtype=jnp.int32)
        own = (pos // block)[..., None]                           # [S, W, 1]
        first_local = own - window_blocks + 1
        cand = ((b >= init_blocks) & (b < first_local))[:, :, None, :]
        picked = select_positions(
            jnp.where(cand, scores, -jnp.inf).reshape(S, W * hk, n_blocks),
            topk).reshape(S, W, hk, topk)
        lead = jnp.broadcast_to(jnp.arange(init_blocks, dtype=jnp.int32),
                                (S, W, hk, init_blocks))
        local = jnp.broadcast_to(
            (first_local + jnp.arange(window_blocks, dtype=jnp.int32)
             )[:, :, None, :], (S, W, hk, window_blocks))
        return jnp.concatenate([lead, picked, local], axis=-1)


def walk(q, pool_k, pool_v, ids, at, *, group, scale=None, out_dtype=None):
    """``q [S, W, H, D]`` through the tables ``ids [S, W, H_kv, n]`` (pool
    blocks, ascending positions) of the head-major plane ``pool_k``,
    ``pool_v [blocks, H_kv, B, D]``: K/V head ``j`` of a row attends the
    keys at ``<= at [S, W]``, counted along ITS table (``-1``: nothing,
    and zeros come back), its ``group`` query rows against head ``j``'s
    slabs alone, ``TABLE_ROWS`` tables a call of ``paged_attention.attend``
    -> ``[S, W, H, D]``."""
    S, W, H, D = q.shape
    blocks, hk, B, _ = pool_k.shape
    if H != hk * group or pool_k.shape != pool_v.shape:
        raise ValueError(
            f"block_sparse: {H} query heads in groups of {group} over a "
            f"head-major plane [blocks, H_kv, B, D]; got {pool_k.shape} "
            f"and {pool_v.shape}")
    rows = S * W * hk
    slabs = (pool_k.reshape(blocks * hk, B, D),
             pool_v.reshape(blocks * hk, B, D))
    tbl = (ids.astype(jnp.int32) * hk
           + jnp.arange(hk, dtype=jnp.int32)[:, None]).reshape(rows, -1)
    qj = q.reshape(rows, 1, group, D)
    atj = jnp.repeat(at.reshape(S * W), hk)[:, None]

    def tables(qp, tp, ap):
        return _paged.attend(qp, *slabs, tp, ap, scale=scale,
                             out_dtype=out_dtype)

    if rows <= TABLE_ROWS:
        ctx = tables(qj, tbl, atj)
    else:
        pieces = -(-rows // TABLE_ROWS)
        spare = pieces * TABLE_ROWS - rows
        cut = lambda a, fill: jnp.pad(                           # noqa: E731
            a, ((0, spare),) + ((0, 0),) * (a.ndim - 1),
            constant_values=fill).reshape(pieces, TABLE_ROWS, *a.shape[1:])
        ctx = jax.lax.map(lambda p: tables(*p), (
            cut(qj, 0), cut(tbl, 0), cut(atj, -1)))
        ctx = ctx.reshape(pieces * TABLE_ROWS, 1, group, D)[:rows]
    # a row that attends nothing reads zeros whatever the walk's backend
    # makes of a table of trash blocks
    return jnp.where((at >= 0)[:, :, None, None], ctx.reshape(S, W, H, D), 0)


def dense_attention(q, pool_k, pool_v, table, pos, *, group, entries=None,
                    scale=None, out_dtype=None):
    """The rows that attend their chain WHOLE (``pos [S, W]`` under the
    caller's dense length; ``-1``: a row that attends nothing here and
    reads zeros): the first ``entries`` entries of ``table [S, NB]`` (all
    of them if None), every K/V head its own slabs, through the walk the
    selected blocks take (:func:`walk`) -> ``[S, W, H, D]``."""
    S, W = pos.shape
    hk = pool_k.shape[1]
    n = table.shape[1] if entries is None else min(entries, table.shape[1])
    ids = jnp.broadcast_to(table[:, None, None, :n], (S, W, hk, n))
    return walk(q, pool_k, pool_v, ids, pos, group=group, scale=scale,
                out_dtype=out_dtype)


def block_sparse_attention_ref(q, pool_k, pool_v, table, pos, sel, *, group,
                               block, scale=None, out_dtype=None):
    """``q [S, W, H, D]`` over the blocks ``sel [S, W, H_kv, n]`` (ascending,
    the last the query's own) of the chains ``table [S, NB]``, masked ``<=
    pos``: the selected blocks as a table a (row, K/V head), walked over
    that head's slabs of the head-major plane (:func:`walk`; module
    docstring) -> ``[S, W, H, D]``."""
    S, W, H, D = q.shape
    hk, B, n = H // group, pool_k.shape[2], sel.shape[-1]
    per = block // B                  # pool blocks a selected block
    with jax.named_scope("paged_block_sparse_attention"):
        live = pos >= 0
        entries = (jnp.maximum(sel, 0)[..., None] * per
                   + jnp.arange(per, dtype=jnp.int32)).reshape(S, W, hk, -1)
        ids = jnp.take_along_axis(
            table.astype(jnp.int32)[:, None, None, :], entries, axis=-1)
        ids = jnp.where((live[:, :, None] & (sel[..., -1] >= 0))[..., None],
                        ids, 0)
        at = jnp.where(live, (n - 1) * block + pos % block, -1)
        return walk(q, pool_k, pool_v, ids, at, group=group, scale=scale,
                    out_dtype=out_dtype)


def block_sparse_attention(q, pool_k, pool_v, table, pos, sel, **how):
    return resolve("block_sparse_attention").impl.call(
        q, pool_k, pool_v, table, pos, sel, **how)


def _three_steps(pool_k, pool_v, pool_c, q, table, pos, *, group, stride,
                 block, topk, init_blocks, window_blocks, scale, out_dtype):
    scores = block_scores(q, pool_c, table, pos, group=group, stride=stride,
                          block=block)
    sel = select_blocks(scores, pos, block=block, topk=topk,
                        init_blocks=init_blocks, window_blocks=window_blocks)
    return block_sparse_attention(q, pool_k, pool_v, table, pos, sel,
                                  group=group, block=block, scale=scale,
                                  out_dtype=out_dtype)


def attend(q, pool_k, pool_v, pool_c, table, pos, *, group, stride, block,
           topk, init_blocks, window_blocks, scale=None, out_dtype=None):
    """One layer's block-sparse attention through the table, the one call
    the serving step makes for the rows past its dense length: ``q [S, W,
    H, D]``, ``pos [S, W]`` (``-1``: a row that attends nothing here) ->
    ``[S, W, H, D]``, zeros for such a row.  Every row at ``pos >= 0``
    must have ``init_blocks + topk + window_blocks`` blocks at or before
    its own (the caller's dense length sees to it)."""
    how = dict(group=group, stride=stride, block=block, topk=topk,
               init_blocks=init_blocks, window_blocks=window_blocks,
               scale=scale,
               out_dtype=q.dtype if out_dtype is None else out_dtype)
    S = table.shape[0]
    alive = jnp.any(pos >= 0, axis=1)
    n_live = jnp.sum(alive, dtype=jnp.int32)
    pools = (pool_k, pool_v, pool_c)

    def none(pool_k, pool_v, pool_c, *packed):
        return jnp.zeros(q.shape, how["out_dtype"])

    if S == 1:
        return jax.lax.cond(
            n_live > 0,
            lambda *a: _three_steps(*a, **how), none, *pools, q, table, pos)
    # a stable partition of the slots, live first (``sparse_attend``)
    to = jnp.where(alive, jnp.cumsum(alive, dtype=jnp.int32) - 1,
                   n_live + jnp.cumsum(~alive, dtype=jnp.int32) - 1)
    at = jnp.arange(S, dtype=jnp.int32)
    of = jnp.sum(jnp.where(to[None, :] == at[:, None], at[None, :], 0),
                 axis=1)
    packed = tuple(a[of] for a in (q, table, pos))
    counts = sorted({slots_run(n, S) for n in range(1, S + 1)})

    def first(b):
        def run(pool_k, pool_v, pool_c, *packed):
            out = _three_steps(pool_k, pool_v, pool_c,
                               *(a[:b] for a in packed), **how)
            return jnp.pad(out, ((0, S - b),) + ((0, 0),) * (out.ndim - 1))
        return run

    out = jax.lax.switch(
        jnp.sum(n_live > jnp.asarray([0] + counts[:-1]), dtype=jnp.int32),
        [none] + [first(b) for b in counts], *pools, *packed)
    return out[to]


class _BlockScoresXlaRef:
    call = staticmethod(block_scores_ref)


class _BlockSparseXlaRef:
    call = staticmethod(block_sparse_attention_ref)


register_kernel("block_scores", "xla_ref", _BlockScoresXlaRef)
register_kernel("block_sparse_attention", "xla_ref", _BlockSparseXlaRef)
