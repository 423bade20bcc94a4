"""Paged slot-batched KV-cache decode — the pure-JAX compute under
``paddle_tpu.serving``.

What a model is made of comes from ``serving/arch.py``: an
``Architecture`` gives the attention geometry, the number of K/V planes
and the forward (embedding, stack, head), written once and fed a decode
step's rows or a window's through the cache interface ``_Cache``
(attention through the block table, and the slots' rows of whatever
per-slot state the architecture holds beside the pool).  This file
knows blocks, tables and windows.

KV lives in a physical block pool: per layer one
``[passes * num_blocks, block_tokens, h, dh]`` array (``passes`` is 1
unless the stack runs several times over the same weights; pass ``p``
then owns blocks ``p * num_blocks ..``, see ``_Cache``), and each slot's logical
sequence is a chain of block ids in a per-slot BLOCK TABLE row
(``[max_slots, blocks_per_slot]`` int32, host-managed by
``serving.kvcache``).  Position ``t`` of slot ``s`` lives at
``(table[s, t // B], t % B)``.  Every compiled entry point writes and
gathers THROUGH the table, so:

* identical prompt prefixes can share physical blocks across slots
  (prefix reuse — the table is data, not shape, so sharing costs no
  recompile);
* the compiled-executable count is bounded by shapes, never by
  requests or prompt lengths — ONE decode chunk plus one prefill per
  window WIDTH (``prefill_rungs``: from the engine's ``min_bucket`` a
  factor of ``RUNG_STEP`` apart under the chip's ridge, doublings from
  there to ``PREFILL_PIECE``);
* unused table entries point at physical block 0, the trash block:
  overrun steps (a finished slot riding out the chunk) and window rows
  past their ``limit`` (prefill bucket padding, a verify window
  overhanging its request) write garbage there and nowhere else.

One forward fed two ways, three compiled entry points built once per
engine:

* ``paged_step_logits`` — ONE token per slot for S slots; under
  ``make_decode_chunk`` a ``lax.scan`` of ``chunk`` batched steps
  between host syncs.  Attention consumes the block table DIRECTLY
  through the ``paged_attention`` op class (online softmax block by
  block — the ``[S, T, h, dh]`` gathered view never materializes).
* ``_window_forward`` — a teacher-forced ``[S, W]`` WINDOW in one
  pass: per layer one ``[S*W, d]`` matmul per projection (the weights
  are read once for W tokens), all W K/V rows written through the
  table before attention, row ``j`` attending ``<= pos + j``.  Two
  executables are built on it:

  - ``make_prefill`` — one executable per window width, ``S = 1``:
    the non-cached tail of a prompt (``tokens[start:start+length]``
    padded to the width) at runtime position ``start``, attending the
    slot's cached blocks through the table; the LM head runs at the
    last real row only.  A suffix longer than ``PREFILL_PIECE`` is the
    engine's loop over consecutive pieces (``ServingEngine._run_pieces``),
    each attending what the earlier ones wrote.  A request whose prefix
    is fully cached prefills only its last prompt token (the logits
    that seed decode are never cached).  The optional copy-on-write
    fork (``cow_src -> cow_dst``) is folded into the SAME executable as
    a leading whole-block copy, so CoW adds no executable (``cow_src ==
    cow_dst == 0`` is the no-op spelling — trash copied onto trash).
  - ``make_verify_window`` — the speculative-decoding verify step
    (``serving.speculative``): a ``k + 1``-token window per slot (the
    slot's committed last token followed by its k draft proposals),
    every row put through the head.  The table is data, so speculative
    decode adds exactly one executable per engine, never one per ``k``.

  HOW a row attends through the table (which kernel, streaming or
  dense) is not decided here: ``_Cache`` hands ``(q, pool, table,
  pos)`` to ``kernels.paged_attention.attend`` and that module
  owns the choice.

Correctness discipline (unchanged from the contiguous engine): every op
is row-wise per slot and window row, each position's K/V is written
BEFORE anything attends it (mask ``<= t``), and garbage (trash-block
content, bucket padding, CoW tail beyond the shared span) is either
overwritten before it is ever attended or masked to exactly zero
attention weight.  A window and the token steps compute the same
mathematics in the same dtypes and differ only in a matmul's reduction
shape — so greedy decode through the paged engine is token-identical
to single-stream ``transformer.generate`` in f32, prefix reuse on or
off (``tests/test_serving.py`` / ``tests/test_kvcache.py``), and agrees
with it in bf16 within the reference margin (docs/serving.md "Numerics
contract"; ``tests/test_prefill_window.py`` pins K/V rows and
first-token logits against the token steps in both dtypes).
"""

import jax
import jax.numpy as jnp

from ..kernels import block_sparse_attention as _blocks_sparse
from ..kernels import paged_attention as _paged
from ..kernels import sparse_attention as _sparse
from ..observability.trace import STACK_SCOPE, sublayer

__all__ = ["paged_step_logits", "make_decode_chunk", "make_prefill",
           "make_verify_window", "make_state_copy", "PREFILL_PIECE", "RUNG_STEP",
           "STREAM_ROWS", "prefill_rungs", "piece_widths"]

# the widest window one prefill call computes; a longer suffix is
# prefilled as consecutive pieces of this width plus one remainder on a
# narrower rung.  A bf16 matrix costs 2 B a parameter to stream and 2
# operations a parameter a row to apply, so a window meets a v5e's ridge
# (197 TFLOP/s over 819 GB/s) at 240 rows: under it a piece is a weight
# stream with the MXU part empty, whatever the architecture.  512 is 2.1
# times the ridge (the stream is under half of a whole piece; 1,024 buys
# no more a token and holds the driver and the dense scores [h, W,
# max_len] twice as long).  PR 26 compared 64 / 128 / 256 on the chip
# and found no difference, in a cell whose suffixes were 8-128 tokens:
# no request could fill a wider piece (PERF.md section 6, PR 43;
# benchmarks/RESULTS.md "prefill_walk" has a piece's cost by width).
# Read at trace time, like kernels.paged_attention.DENSE_WINDOW.
PREFILL_PIECE = 512
# the rungs of window widths: a factor of RUNG_STEP apart up to
# STREAM_ROWS, the widest power of two under the ridge (there a piece is
# a weight stream and a padded row costs no weight byte: two rungs more
# on a ladder of doublings would be two executables more in every engine
# that sees short suffixes, 2-10 s each, warm), doublings from there to
# the piece (past the ridge a padded row costs its operations, and the
# dense attention, a recurrence and retention's chunk kernel cost theirs
# at every width: benchmarks/RESULTS.md "prefill_walk" has a hybrid
# stack's 512-row piece at 3.4 times its 128-row one)
RUNG_STEP = 4
STREAM_ROWS = 128


def prefill_rungs(min_bucket, max_len):
    """The window widths an engine may compile, ascending: ``min_bucket``
    and its multiples a factor of ``RUNG_STEP`` apart up to
    ``STREAM_ROWS``, doublings above, the piece width the widest, none
    wider than ``max_len``."""
    rungs, b = [], int(min_bucket)
    while b < PREFILL_PIECE:
        rungs.append(b)
        b *= RUNG_STEP if b * RUNG_STEP <= STREAM_ROWS else 2
    rungs.append(max(int(min_bucket), PREFILL_PIECE))
    return sorted({min(r, int(max_len)) for r in rungs})


def piece_widths(n, rungs):
    """Window widths that prefill a suffix of ``n`` tokens on the ladder
    ``rungs``: whole pieces of the widest rung, then the remainder in
    the narrowest rung that covers it."""
    full, rem = divmod(max(int(n), 0), rungs[-1])
    widths = [rungs[-1]] * full
    if rem or not full:
        widths.append(next(r for r in rungs if r >= rem))
    return widths


class _Cache:
    """The cache interface an architecture's ``stack`` receives
    (``arch.py``): called, ``attend(planes, plane, i_pass, q, k, v,
    **how) -> (ctx, planes')`` writes the rows' K/V into pool array
    ``plane`` (of pass ``i_pass``) at ``(blk, off)`` (one scatter over
    the pool's whole row, ``kernels.paged_attention.write``: how a row
    is written belongs to the module that decides how it is read), then
    attends that plane through ``table`` masked ``<= pos`` (and by
    ``how``'s lower bound); with ``k`` and ``v`` ``None`` it writes
    nothing and attends what another layer wrote.  Its state side
    (``state``, ``put_state``, ``valid``) reads and writes the slots'
    rows of the per-slot state.

    ``planes = (pool_k, pool_v, state)``: one pool array a plane
    (``pool_v`` holds the SECOND array of the planes that have one, in
    order, ``arch.second_array(plane)`` its index or ``None``: ``()``
    where every plane is ONE array; called with ``vh=None, kh=`` the
    latent row, it writes that array only and hands ``attend``
    ``pool_v=None``; ``sparse`` is the call of a plane whose second
    array holds index keys), one
    tuple of ``[max_slots, ...]`` arrays a state layer (``()`` for an
    architecture that holds none).  A stack that runs ``arch.passes``
    times folds its passes into the block axis: a plane's array holds
    ``passes * num_blocks`` blocks and pass ``p`` reads and writes block
    ``b`` at ``b + p * num_blocks``, so a block id names the same
    positions in every plane, every pass has a trash block of its own
    (``p * num_blocks``), no plane is ever sliced out or copied and the
    kernels see an ordinary pool and table.  ``pos`` ``[S]`` is a decode
    step (rows ``[S, ...]``), ``[S, W]`` a window.

    A DEAD slot attends nothing: its rows are handed to attention at
    ``pos = -1``, for which the Mosaic kernel fetches no block and
    returns zeros.  Dead is read off the UNSHIFTED table, ``table[:, 0]
    == 0``: a live slot's first entry is a real block from admission on
    and ``ServingEngine._release_slot`` zeroes the row, whereas the
    ``pos`` a decode chunk carries on the device goes stale for a
    released slot (it keeps counting).  Writes (``blk``, ``off``) are
    untouched: a dead slot's land in the trash block as before.

    TWO KINDS OF CHAIN.  Where the engine keeps the window planes'
    chains apart (``kvcache.WindowChains``) ``table`` is ``[S, 2, NB]``
    and ``blk`` a pair: kind 0 the whole chains (dead is read off it),
    kind 1 the window planes', whose entries under a slot's window are
    the trash block; a call goes through ``arch.chain_kind(plane)``'s.

    ``valid`` (``pos``'s shape, bool) marks the rows that are real: a
    window's rows up to its ``limit`` (``writable``), a live slot's
    step.  ``slot`` (prefill: the one slot the ``S = 1`` window belongs
    to) selects the state row, and a window that starts a prompt (``pos
    == 0``) starts from zero state whatever the slot's last request
    left; without ``slot`` the state arrays' rows ARE the call's slots.
    The state side traces nothing unless an architecture calls it.

    ``advance(planes, i, kernel, *rows, **how) -> (y, planes')`` is the
    state side IN PLACE, the ONE call every recurrence whose state is
    large makes (power retention: ``kernels/retention.py``, rows ``q, k,
    v, lg``; Mamba-2: ``kernels/ssm.py``, rows ``xbc, dt``): the arrays
    of state layer ``i`` go whole to ``kernel.step(*arrays, *rows, valid,
    **how)`` (a decode step) or to ``kernel.chunk(*arrays, slot, fresh,
    *rows, valid, **how)`` (a window: the one slot's rows, ``fresh``
    where the piece starts a prompt, one call for each of
    ``kernel.chunk_rows(width)``), and come back advanced for the live
    slots only; nothing is gathered by slot and nothing scattered back.

    An architecture with NO plane (``arch.planes == ()``) has no table:
    ``table``, ``blk`` and ``off`` are ``None``, calling the cache is an
    error, and a decode step's ``valid`` is ``live`` (``[S]`` bool),
    which the entry points take from the engine where the others take
    the table.

    ``shared`` (a decode step of an architecture that ``shares_runs``,
    from an engine with a prefix trie; else ``None`` and nothing is
    traced) is ``kernels.paged_attention.shared_runs``'s array: which
    live slots' chains start alike, for a latent plane attended whole to
    fetch such a run once.  It rides beside ``table`` as data.

    ``tally(counts)`` adds an int32 vector (``arch.count_names`` says
    what its entries are) to ``counts``, which the entry points return
    beside their tokens; ``()`` for a stack that tallies nothing."""

    def __init__(self, arch, table, blk, off, pos, writable=None,
                 slot=None, live=None, shared=None):
        self.arch, self.off = arch, off
        self.pos, self.writable, self.slot = pos, writable, slot
        self.live, self.shared = live, shared
        self.counts = ()
        self.step = pos.ndim == 1
        # two kinds of chain (``[S, 2, NB]``): a table and the rows'
        # write blocks a kind, the whole chains first
        self.tables = (None if table is None else (table,) if table.ndim == 2
                       else tuple(table[:, k] for k in range(table.shape[1])))
        self.blks = blk if isinstance(blk, tuple) else (blk,)
        self.table = None if table is None else self.tables[0]
        if table is not None:
            pos4 = pos[:, None] if self.step else pos
            self.pos4 = jnp.where((self.table[:, 0] == 0)[:, None], -1, pos4)

    @property
    def valid(self):
        if self.writable is not None:
            return self.writable
        if self.live is not None:
            return self.live
        return self.table[:, 0] != 0

    def _plane(self, planes, plane, i_pass):
        """Plane ``plane``'s arrays (the second ``None`` where it has
        one array), the second's index in ``pool_v``, and the table and
        write blocks of its kind of chain (of pass ``i_pass``)."""
        pool_k, pool_v = planes[:2]
        kind = self.arch.chain_kind(plane) if len(self.tables) > 1 else 0
        tbl, b = self.tables[kind], self.blks[kind]
        if self.arch.passes > 1:
            shift = i_pass * (pool_k[plane].shape[0] // self.arch.passes)
            tbl, b = tbl + shift, b + shift
        # a plane of ONE array (a latent plane) has no second: pool_v
        # holds the second arrays of the planes that have one, in order
        j = self.arch.second_array(plane)
        return pool_k[plane], None if j is None else pool_v[j], j, tbl, b

    @staticmethod
    def _written(planes, plane, pk, j, pv):
        pool_k, pool_v = planes[:2]
        return (pool_k[:plane] + (pk,) + pool_k[plane + 1:],
                pool_v if j is None else pool_v[:j] + (pv,) + pool_v[j + 1:]
                ) + planes[2:]

    def __call__(self, planes, plane, i_pass, qh, kh, vh, **how):
        pk, pv, j, tbl, b = self._plane(planes, plane, i_pass)
        if kh is not None:
            # every write lands before the attention below: the
            # write-before-attend discipline, one scatter per plane
            # (distinct live positions, disjoint per-slot blocks, overruns
            # and rows past their limit in the trash block — content
            # nobody ever attends) that covers the pool's WHOLE row, the
            # rows kernels.paged_attention.pool_rows added as zeros: a
            # write of part of the head axis is a serial loop on the chip
            with sublayer("cache"):
                pk = _paged.write(pk, b, self.off, kh)
                if pv is not None:
                    pv = _paged.write(pv, b, self.off, vh)
        if self.shared is not None:
            how["shared"] = self.shared     # ``attend`` knows who takes it
        # attend THROUGH the table: row j attends <= pos_j inside the
        # paged_attention op class, the [S, T, h, dh] view never exists
        ctx = _paged.attend(qh[:, None] if self.step else qh, pk, pv, tbl,
                            self.pos4, **how)
        if self.step:
            ctx = ctx[:, 0]
        if kh is None:
            return ctx, planes
        return ctx, self._written(planes, plane, pk, j, pv)

    def sparse(self, planes, plane, qh, row, q_idx, w_idx, k_idx, **how):
        """A plane whose rows an INDEXER selects
        (``kernels/sparse_attention.py``): writes the latent ``row`` and
        the index key ``k_idx`` of every position under ONE block id (the
        plane's two arrays), then scores the chain's index keys with
        ``q_idx``, ``w_idx``, selects and attends (``sparse_attend``;
        ``how``: ``topk``, ``value_lanes``, ``scale``)."""
        pk, pi, j, tbl, b = self._plane(planes, plane, 0)
        with sublayer("cache"):
            pk = _paged.write(pk, b, self.off, row)
            pi = _paged.write(pi, b, self.off, k_idx)
        lead = (lambda a: a[:, None]) if self.step else (lambda a: a)
        ctx = _sparse.sparse_attend(lead(qh), pk, pi, tbl, self.pos4,
                                    lead(q_idx), lead(w_idx), **how)
        return (ctx[:, 0] if self.step else ctx), self._written(
            planes, plane, pk, j, pi)

    def block_sparse(self, planes, plane, plane_c, qh, kh, vh, *, dense_len,
                     stride, block, topk, init_blocks, window_blocks,
                     group, scale):
        """A K/V plane whose queries SELECT blocks past ``dense_len``
        (``kernels/block_sparse_attention.py``; the plane is stored
        HEAD-MAJOR, ``[blocks, H_kv, B, D]``, as the architecture's
        ``plane_block_shapes`` declares it): writes K and V (one scatter
        each, no row beside the heads'), then the
        compressed keys that the call's rows complete into ``plane_c``
        (one array under the same block ids, a row where its window
        ends: the mean of the last ``2 stride`` keys as the pool holds
        them, the trash block for a row that completes none), then
        attends: a row at a position under ``dense_len`` the whole chain
        head by head (``dense_attention``), a row from there on the blocks
        it selects, both through one walk of a K/V head's slabs, each call
        made only where such a row is live.  A table
        that cannot reach ``dense_len`` traces the dense call alone.
        Returns ``(ctx, planes', counts)``, ``counts`` int32 ``[5]``:
        (row, K/V head) pairs that read densely and that selected, the
        blocks those selected and the blocks they had cached, the
        compressed rows written."""
        pk, pv, j, tbl, b = self._plane(planes, plane, 0)
        pc = planes[0][plane_c]
        B, hk = pc.shape[1] * stride, kh.shape[-2]
        if pk.shape[1:3] != (hk, B) or block % B or B % stride:
            raise ValueError(
                f"block_sparse: a head-major plane [blocks, {hk}, B, D] "
                f"whose blocks of B positions divide a selection block of "
                f"{block} and hold whole strides of {stride} "
                f"({pc.shape[1]} compressed rows a block); got {pk.shape}")
        pos = self.pos[:, None] if self.step else self.pos
        with sublayer("cache"):
            pk = _blocks_sparse.write(pk, b, self.off, kh)
            pv = _blocks_sparse.write(pv, b, self.off, vh)
            # the compressed rows whose window ends on one of the call's
            # real rows: candidates first // stride .., at most one a
            # stride of the window and one more
            real = self.valid[:, None] if self.step else self.valid
            first = jnp.maximum(pos[:, :1], 0)
            cand = first // stride + jnp.arange(
                1 if self.step else pos.shape[1] // stride + 1)
            end = stride * (cand + 1) - 1
            last = jnp.max(jnp.where(real, pos, -1), axis=1, keepdims=True)
            done = (cand >= 1) & (end >= first) & (end <= last)
            rows = _blocks_sparse.compressed_rows(pk, tbl, cand, stride)
            per = B // stride
            at = jnp.take_along_axis(
                tbl, jnp.minimum(cand // per, tbl.shape[1] - 1), axis=1)
            pc = _paged.write(pc, jnp.where(done, at, 0), cand % per, rows)
        how = dict(group=group, scale=scale)
        q4 = qh[:, None] if self.step else qh
        if tbl.shape[1] * B <= dense_len:
            ctx = _blocks_sparse.dense_attention(q4, pk, pv, tbl, self.pos4,
                                                 **how)
            dense, sparse = self.pos4 >= 0, jnp.zeros_like(self.pos4, bool)
        else:
            dense = (self.pos4 >= 0) & (self.pos4 < dense_len)
            sparse = self.pos4 >= dense_len
            # a dense row's keys lie in the entries under dense_len
            ctx = jax.lax.cond(
                jnp.any(dense),
                lambda q, pk, pv, tbl, at: _blocks_sparse.dense_attention(
                    q, pk, pv, tbl, at, entries=-(-dense_len // B), **how),
                lambda q, *_: jnp.zeros_like(q),
                q4, pk, pv, tbl, jnp.where(dense, self.pos4, -1))
            # (a backend's reading of a row at -1 is its own: the rows
            # are taken from the call that was made for them)
            ctx = jnp.where(sparse[..., None, None], _blocks_sparse.attend(
                q4, pk, pv, pc, tbl, jnp.where(sparse, self.pos4, -1),
                stride=stride, block=block, topk=topk,
                init_blocks=init_blocks, window_blocks=window_blocks, **how),
                ctx)
        n = lambda m: jnp.sum(m, dtype=jnp.int32)              # noqa: E731
        counts = jnp.stack([
            hk * n(dense), hk * n(sparse),
            hk * n(sparse) * _blocks_sparse.selected_blocks(
                init_blocks, topk, window_blocks),
            hk * n(jnp.where(sparse, self.pos4 // block + 1, 0)),
            n(done)])
        planes = self._written(planes, plane, pk, j, pv)
        planes = (planes[0][:plane_c] + (pc,) + planes[0][plane_c + 1:],
                  ) + planes[1:]
        return (ctx[:, 0] if self.step else ctx), planes, counts

    def advance(self, planes, i, kernel, *rows, **how):
        arrays = planes[2][i]
        if self.step:
            y, *arrays = kernel.step(*arrays, *rows, self.valid, **how)
        elif self.slot is None:
            raise ValueError(
                "advance: a window of several slots (a verify window) has "
                "no in-place form: a kernel's chunk advances ONE slot's "
                "state over a piece")
        else:
            # a window is ONE kernel call (the kernel walks its rows in
            # tiles); one wider than any rung is consecutive calls, the
            # state threaded through in place: only the first can start
            # a prompt, and each honours its own rows' limit
            fresh, ys, at = self.pos[0, 0] == 0, [], 0
            for n in kernel.chunk_rows(rows[0].shape[1]):
                cut = slice(at, at + n)
                y, *arrays = kernel.chunk(
                    *arrays, self.slot, fresh, *(r[0, cut] for r in rows),
                    self.valid[0, cut], **how)
                ys.append(y)
                fresh, at = False, at + n
            y = (ys[0] if len(ys) == 1 else jnp.concatenate(ys))[None]
        return y, planes[:2] + (
            planes[2][:i] + (tuple(arrays),) + planes[2][i + 1:],)

    def tally(self, counts):
        self.counts = (counts if isinstance(self.counts, tuple)
                       else self.counts + counts)

    def state(self, planes, i):
        rows = planes[2][i]
        if self.slot is not None:
            with sublayer("cache"):
                rows = tuple(jax.lax.dynamic_index_in_dim(a, self.slot, 0)
                             for a in rows)
                fresh = self.pos[:, 0] == 0
                rows = tuple(jnp.where(
                    fresh.reshape((-1,) + (1,) * (a.ndim - 1)), 0, a)
                    for a in rows)
        return rows

    def put_state(self, planes, i, rows):
        old = planes[2][i]
        with sublayer("cache"):
            rows = tuple(r.astype(a.dtype) for r, a in zip(rows, old))
            if self.slot is not None:
                rows = tuple(jax.lax.dynamic_update_slice_in_dim(
                    a, r, self.slot, 0) for r, a in zip(rows, old))
        return planes[:2] + (planes[2][:i] + (rows,) + planes[2][i + 1:],)


def _blocks(table, rows, entry, writable=None):
    """The physical blocks rows are written to, ``table[rows, entry]``
    (the trash block where not ``writable``); one array a kind of chain
    where ``table`` is ``[S, kinds, NB]``."""
    def one(tbl):
        blk = tbl[rows, entry]
        return blk if writable is None else jnp.where(writable, blk, 0)

    if table.ndim == 2:
        return one(table)
    return tuple(one(table[:, k]) for k in range(table.shape[1]))


def paged_step_logits(p, tok, t, pool_k, pool_v, table, arch, state=(),
                      shared=None):
    """One decode step for S independent slots through the block table.

    tok [S] int32 current tokens, t [S] int32 per-slot positions,
    pool_k/pool_v tuples of one array a layer ``[passes * num_blocks, B,
    h, dh]``, table [S, NB] int32 block ids (logical capacity T = NB *
    B; ``[S, 2, NB]`` under two kinds of chain, ``_Cache``); ``arch`` the model, an ``arch.Architecture``.  For an
    architecture with NO plane (``pool_k == ()``) ``table`` is ``[S]``
    int32, nonzero where the slot is live, and positions are bounded by
    nothing here.
    Writes each slot's K/V at ``(table[s, t_s // B], t_s % B)`` in every
    plane (clamped — overrun slots land in whatever their last table
    entry maps to, by construction the trash block or an
    already-consumed position), attends the chain masked ``<= t_s``,
    advances the per-slot ``state`` of the live slots (``table[:, 0] !=
    0``) and returns ``(logits [S, vocab] f32, pool_k', pool_v',
    state', counts)``, ``counts`` what the stack tallied (``_Cache``).
    ``shared``: which slots' chains start alike (``_Cache``), or ``None``.
    """
    if not arch.planes:
        tw = jnp.maximum(t, 0)
        x = arch.embed(p, tok, tw)
        cache = _Cache(arch, None, None, None, t, live=table != 0)
    else:
        S = tok.shape[0]
        B = pool_k[0].shape[1 + arch.plane_tokens_axis(0)]
        T = table.shape[-1] * B
        tw = jnp.clip(t, 0, T - 1)
        with sublayer("cache"):
            blk = _blocks(table, jnp.arange(S), tw // B)  # [S] write block
        x = arch.embed(p, tok, tw)                           # [S, d]
        cache = _Cache(arch, table, blk, tw % B, t, shared=shared)
    with jax.named_scope(STACK_SCOPE):
        x, (pool_k, pool_v, state) = arch.stack(
            p, x, tw, (pool_k, pool_v, state), cache)
    return arch.head(p, x), pool_k, pool_v, state, cache.counts


def make_decode_chunk(arch, chunk, donate=True):
    """Build the batched decode executable: ``chunk`` greedy steps for
    every slot in one device call, for ``arch`` (an ``Architecture``).

    ``fn(params, pool_k, pool_v, last_tok, pos, table, state=(),
    shared=None) -> (pool_k', pool_v', last_tok', pos', toks [chunk, S]
    int32, state', counts)`` — ``toks[j]`` is the token each slot emitted at its
    ``pos+j``'th position; ``counts`` is what the stack tallied, summed
    over the chunk's steps (int32 ``[len(arch.count_names)]``; ``()`` and
    no output of the lowered program for an architecture that tallies
    nothing).  The pool, the slot scalars and the per-slot ``state``
    (``arch.state_spec``; ``()`` and no argument of the lowered program
    for an architecture that holds none) are donated (updated in place
    on TPU); the table is a small host-fed int32 array (data, not
    donated), and so is ``shared`` (``_Cache``; ``None`` and no argument
    of the lowered program unless the engine passes one).  Callers must
    replace their references with the outputs.
    """

    def decode_chunk(p, pool_k, pool_v, last_tok, pos, table, state=(),
                     shared=None):
        def body(carry, _):
            pk, pv, st, tok, t = carry
            logits, pk, pv, st, counts = paged_step_logits(
                p, tok, t, pk, pv, table, arch, st, shared)
            with sublayer("head"):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (pk, pv, st, nxt, t + 1), (nxt, counts)

        (pk, pv, state, tok, t), (toks, counts) = jax.lax.scan(
            body, (pool_k, pool_v, state, last_tok, pos), None,
            length=chunk)
        counts = jax.tree.map(lambda c: jnp.sum(c, axis=0), counts)
        return pk, pv, tok, t, toks, state, counts

    return jax.jit(decode_chunk, donate_argnums=_donated(
        arch, donate, (1, 2, 3, 4), 6))


def _donated(arch, donate, argnums, state_argnum):
    """The donated arguments of an entry point: ``argnums`` and, where
    the architecture holds per-slot state, the state argument."""
    if not donate:
        return ()
    return argnums + ((state_argnum,) if arch.state_spec("float32") else ())


def _window_forward(p, pool_k, pool_v, toks, pos, limit, table, arch,
                    state=(), slot=None):
    """The teacher-forced WINDOW forward both ``make_verify_window``
    and ``make_prefill`` are built on: ``toks [S, W]`` consumed at
    logical positions ``pos_s + j`` in ONE pass — per layer one
    ``[S*W, d] @ [d, .]`` matmul per projection (the weights are read
    once for W tokens), all W K/V rows scattered through the table
    BEFORE attention, window row ``j`` attending keys ``<= pos_s + j``
    (the cached chain plus the in-window rows before it).  The forward
    itself is ``arch``'s, the same lines the decode step runs.

    ``limit[s]`` is the last logical position slot ``s`` may write:
    rows beyond it (a verify window overhanging the end of a request, a
    dead slot's ``-1``, prefill's bucket padding) route their K/V to
    the trash block, so they can never scatter into a live block nor
    race a real row for a clamped table entry.  Their outputs are
    garbage nobody reads.

    Per-slot ``state`` advances over the rows up to ``limit`` only.
    With ``slot`` (prefill: ``S = 1``) the window belongs to that slot:
    its state row is read and written back, starting from zeros where
    the window starts a prompt (``pos == 0``).

    Returns ``(x [S, W, d], pool_k', pool_v', state', counts)`` — the
    stack's output, what ``arch.head`` consumes (the callers differ in
    which rows they put through the head), and what the stack tallied
    over the rows up to ``limit``.
    """
    S, W = toks.shape
    P = pos[:, None] + jnp.arange(W)[None, :]                # [S, W]
    if not arch.planes:
        # no plane, no table (``table`` is ignored)
        Pw = jnp.maximum(P, 0)
        writable = P <= limit[:, None]
        x = arch.embed(p, toks, Pw)
        cache = _Cache(arch, None, None, None, P, writable, slot)
    else:
        B = pool_k[0].shape[1 + arch.plane_tokens_axis(0)]
        T = table.shape[-1] * B
        Pw = jnp.clip(P, 0, T - 1)
        writable = P <= limit[:, None]
        with sublayer("cache"):
            blk = _blocks(table, jnp.arange(S)[:, None], Pw // B, writable)
        x = arch.embed(p, toks, Pw)                          # [S, W, d]
        cache = _Cache(arch, table, blk, Pw % B, P, writable, slot)
    with jax.named_scope(STACK_SCOPE):
        x, (pool_k, pool_v, state) = arch.stack(
            p, x, Pw, (pool_k, pool_v, state), cache)
    return x, pool_k, pool_v, state, cache.counts


def _copy_block(planes, src, dst, passes):
    """Block ``src`` copied whole onto ``dst`` in every plane: once for
    each pass folded into an array's block axis.  Of a latent plane's
    missing V arrays (``()``) there is nothing to copy."""
    if not planes:
        return planes
    per = planes[0].shape[0] // passes
    out = []
    with sublayer("cache"):
        for c in planes:
            c = c.at[dst].set(c[src])
            for i in range(1, passes):
                c = c.at[dst + i * per].set(c[src + i * per])
            out.append(c)
    return tuple(out)


def make_state_copy(donate=True):
    """Build the two device-side copies of a prefix hit over recurrent
    state: ``fn(dst, src, to, of) -> dst'`` writes row ``of`` of every
    array of ``src`` onto row ``to`` of the matching array of ``dst``
    (both ``arch.state_spec``'s structure, any leading sizes), ``dst``
    donated.  TAKING a snapshot is ``fn(snapshots, state, row, slot)``,
    queued behind the prefill piece that ended on the block boundary;
    RESTORING one is ``fn(state, snapshots, slot, row)``, queued before
    the suffix's first piece, which then reads the slot's rows as a piece
    that continues a prompt does."""

    def copy(dst, src, to, of):
        with sublayer("cache"):
            return jax.tree.map(
                lambda d, s: jax.lax.dynamic_update_index_in_dim(
                    d, jax.lax.dynamic_index_in_dim(s, of, 0, False), to, 0),
                dst, src)

    return jax.jit(copy, donate_argnums=(0,) if donate else ())


def make_verify_window(arch, k, donate=True):
    """Build the speculative VERIFY executable: one teacher-forced
    target forward over a ``W = k + 1``-token window for every slot.

    ``fn(params, pool_k, pool_v, toks [S, W], pos [S], limit [S],
    table [S, NB]) -> (pool_k', pool_v', greedy [S, W] int32)`` —
    ``toks[s] = [last_s, d_1 .. d_k]`` (the committed last token
    followed by the slot's draft proposals), window position ``j``
    lives at logical position ``pos_s + j``, and ``greedy[s, j]`` is
    the target's argmax after consuming ``toks[s, j]`` there — exactly
    the token sequential greedy decode would emit after the prefix
    extended by ``toks[s, :j]``.  Scoring all W positions in ONE
    forward (``_window_forward``) is the speculative win: the weights
    are read once for W tokens instead of W times.

    ``limit[s]`` is the last logical position slot ``s`` may ever
    legitimately write (``p_len + max_new - 1``; ``-1`` for a dead
    slot).  Greedy outputs at positions past ``limit`` are garbage;
    the host-side acceptance walk never commits them.
    """

    def verify(p, pool_k, pool_v, toks, pos, limit, table):
        if toks.shape[1] != k + 1:
            raise ValueError(f"verify window built for k={k} got "
                             f"{toks.shape[1]} tokens a slot")
        x, pool_k, pool_v, _, _ = _window_forward(
            p, pool_k, pool_v, toks, pos, limit, table, arch)
        logits = arch.head(p, x)
        with sublayer("head"):
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return pool_k, pool_v, greedy

    return jax.jit(verify, donate_argnums=(1, 2) if donate else ())


def make_prefill(arch, bucket, donate=True):
    """Build the prefill executable for one window WIDTH (a rung of
    ``prefill_rungs``: at most ``PREFILL_PIECE`` tokens).

    ``fn(params, pool_k, pool_v, last_tok, pos, slot, table_row [NB],
    toks [bucket], start, length, cow_src, cow_dst, state=()) ->
    (pool_k', pool_v', last_tok', pos', first_tok, state', counts)`` —
    first copies block
    ``cow_src`` onto ``cow_dst`` whole (the copy-on-write fork; the
    no-fork spelling passes ``0, 0``, trash onto trash), then runs ONE
    window forward (``_window_forward`` at ``S = 1``) over the padded
    tokens at positions ``start + j``, writing K/V through
    ``table_row`` and attending the slot's chain (positions ``< start``
    — the prefix shared from the trie, or what an earlier piece of the
    same prompt wrote — are read, never recomputed).  The final
    LayerNorm, LM head and argmax run at row ``length - 1`` ONLY: one
    ``[1, d] @ [d, vocab]`` matvec per call, whatever the width.  Seeds
    the slot's ``last_tok`` with that greedy token and ``pos`` with
    ``start + length``.  ``first_tok`` is also returned as a scalar so
    the scheduler can report TTFT / detect an immediate EOS without
    pulling slot state back.

    Rows past ``length`` are padding: their K/V go to the trash block
    (``limit = start + length - 1``) and their outputs are never read;
    a real row never attends them (mask ``<= start + j``), and the
    slot's row of the per-slot ``state`` advances over the real rows
    only, from zeros where ``start == 0`` and else from what the earlier
    piece wrote.
    """

    def prefill(p, pool_k, pool_v, last_tok, pos, slot, table_row,
                toks, start, length, cow_src, cow_dst, state=()):
        if toks.shape != (bucket,):
            raise ValueError(f"prefill built for width {bucket} got "
                             f"tokens of shape {toks.shape}")
        # copy-on-write fork: duplicate the whole source block; the
        # shared tokens are the live prefix, the tail is garbage the
        # window / decode overwrites before ever attending it
        pool_k = _copy_block(pool_k, cow_src, cow_dst, arch.passes)
        pool_v = _copy_block(pool_v, cow_src, cow_dst, arch.passes)
        end = start + length
        x, pool_k, pool_v, state, counts = _window_forward(
            p, pool_k, pool_v, toks[None], start[None], (end - 1)[None],
            table_row[None], arch, state, slot)
        with sublayer("head"):
            row = jax.lax.dynamic_slice_in_dim(x[0], length - 1, 1)  # [1, d]
            first = jnp.argmax(arch.head(p, row)[0]).astype(jnp.int32)
        last_tok = last_tok.at[slot].set(first)
        pos = pos.at[slot].set(end)
        return pool_k, pool_v, last_tok, pos, first, state, counts

    return jax.jit(prefill, donate_argnums=_donated(
        arch, donate, (1, 2, 3, 4), 12))
