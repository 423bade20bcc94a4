"""Flash attention as a Pallas TPU kernel.

The reference has no fused attention (attention is composed from fc +
softmax in ``trainer_config_helpers/networks.py simple_attention``); on TPU
the fused blockwise kernel is the difference between O(t^2) HBM traffic and
O(t) — this is the hot-op Pallas path of the framework (pallas_guide.md
patterns: grid over (batch*heads, q-blocks), online softmax in VMEM,
custom VJP with recompute backward).

Layout: q [b, t_q, h, d], k/v [b, t_k, h, d] (same as parallel.ring_attention,
whose per-device inner block this kernel accelerates).

Forward: Pallas kernel, grid (batch*head, q-blocks, k-blocks) with the
k axis innermost; online-softmax state carried in VMEM scratch.  Backward:
custom_vjp into one fused Pallas kernel (k-major grid, dq as per-k-block
partials) or, past its partials' budget, two — dq (q-major grid) and
dk/dv (k-major grid) — recomputing p from the saved lse.  All four walk a
grid cell the same way (``_walk_cell``): a causal cell below the diagonal
is one full tile, one above it is skipped and fetches nothing, and one
that straddles it is walked in static strips of DIAG_W q rows, the mask
select only on the columns the diagonal crosses.
delta = rowsum(do*o) is computed inside the kernels.  HBM residuals are
O(t) rows (lse is stored 2-D [bh, t] — 4 B/row; the in-kernel softmax
state uses 128-lane scratch tiles); VMEM stays O(block^2).

MXU feeds stay in the input dtype: bf16 q/k/v/do go straight into the
dots with f32 accumulation (bf16 input is 2x the f32 MXU rate on v5e);
only softmax state (m/l/lse/p pre-cast) is f32.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..analysis.jaxpr_tools import KERNEL_RESIDUAL_TAG

# The backward residual contract, pinned by tests/test_memory_engine.py:
# the custom VJP recomputes p from EXACTLY these five arrays and closes
# over nothing else.  q/k/v are upstream projection outputs (saved once,
# shared with the matmul residuals), o is the kernel's own output, lse is
# the narrow 2-D [b*h, t] softmax statistic.  Anything beyond this set
# (a saved p tile, a delta row, a replicated lse) multiplies per-layer
# residual memory at long context — at the t=16k flagship every extra
# bf16 [b, t, d] residual is 144 MB/layer.
FLASH_BWD_RESIDUALS = ("q", "k", "v", "o", "lse")

NEG_INF = -1e30
LSE_LANES = 128  # Mosaic min lane tile (in-kernel m/l scratch width);
# lse ITSELF is stored narrow: [bq, 1] kernel outputs, 2-D [bh, t] residuals

# Scoped-VMEM ceiling for the flash kernels.  Mosaic's default is 16 MiB;
# at the flagship geometry (1024x1024 blocks, d_head 128, bf16) the
# forward's f32 score/probability tiles put its stack at 16.29 MiB and
# libtpu 0.0.34 refuses the compile (chip run, PR 21).  A v5e core has
# 128 MiB of VMEM; half of it leaves the backward's four block^2 f32
# tiles room without shrinking the measured block sizes.
VMEM_LIMIT_BYTES = 64 << 20


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


# Strip height: a causal cell that straddles the diagonal is walked in
# strips of DIAG_W q rows, each ONE [DIAG_W, visible columns] score tile
# and one softmax update (``_walk_cell``).  In the diagonal cell of equal
# blocks a strip's columns are the static range [0, (qs+1) * DIAG_W) and
# the iota/select mask runs on its last DIAG_W columns only: nothing above
# the diagonal band is computed and nothing inside the cell is decided at
# run time.  Wide tiles win on the chip (PERF.md, PR 31: a full 1024 x
# 1024 cell runs at 66% of the forward's roofline, the same scores in 256
# x 256 sub-tiles under branches at a fifth of that), so taller strips
# schedule more masked scores and still run faster.  (The name is from
# when this was the width of a square sub-tile of the diagonal cells; the
# tune cache stores it as ``diag_w``.)  A process-wide TUNABLE:
# PADDLE_TPU_DIAG_W pins it (the env knob wins over everything), and the
# autotune engine (paddle_tpu.tune, docs/autotune.md) sets the module
# global while measuring a candidate / applying a tuned winner
# (apply_tuned_diag_w) — the kernels read it at trace time, so fwd and
# all three bwd kernels always agree within one compile.
_DIAG_W_ENV = int(os.environ.get("PADDLE_TPU_DIAG_W", "0") or 0)
DIAG_W = _DIAG_W_ENV or 256


def apply_tuned_diag_w(width):
    """Apply a tuned strip height process-wide (the autotune hot path /
    search loop).  The PADDLE_TPU_DIAG_W env pin always wins; returns the
    height actually in effect."""
    global DIAG_W
    if width and not _DIAG_W_ENV:
        DIAG_W = int(width)
    return DIAG_W


def _pick_block(t, cap):
    """Largest divisor of t that is <= cap (TPU-friendly when t is a
    multiple of 128; always exact so no masking is needed)."""
    b = min(t, cap)
    while t % b:
        b -= 1
    return b


def packed_sub_heads(n_head, d_head):
    """How many heads one 128-lane slice of the packed layout carries.

    Returns 1 (one lane-aligned head per slice), 2 (two d=64 heads packed
    per slice), or None when the geometry has no packed spelling and
    callers must use the 4-D ``flash_attention`` path.  This is THE
    geometry decision: tests pin it per (n_head, d_head)."""
    if n_head == 1:
        return 1
    if d_head % 128 == 0:
        return 1
    if d_head == 64 and n_head % 2 == 0:
        return 2
    return None


def _strip_cols(qs, strip, block_q, block_k):
    """``(visible, masked)``: how many of a straddling cell's columns
    strip ``qs`` (``strip`` q rows) takes, from the first on, and how many
    of the last of those carry the mask.  Equal blocks make the straddling
    cell the diagonal one, its first row on its first column: the strip
    sees up to its own last row and the diagonal crosses its last
    ``strip`` columns.  Unequal blocks put the diagonal anywhere, and the
    strip takes every column under the mask."""
    if block_q == block_k:
        return (qs + 1) * strip, strip
    return block_k, block_k


def _cell_kind(off, block_q, block_k):
    """``(full, straddling)`` for the cell whose first q row lies ``off``
    positions past its first k column (``j * block_q - kb * block_k``;
    Python ints for the accounting, traced program ids in the kernels):
    full when even the first row sees the last column, straddling when
    only some (row, column) pairs are allowed, neither when none is."""
    full = off >= block_k - 1
    needed = off > -block_q
    if isinstance(off, int):
        return full, needed and not full
    return full, jnp.logical_and(needed, jnp.logical_not(full))


class FlashWalk(tuple):
    """``(scheduled, useful)`` flops of ``causal_flash_flops``, with what
    the walk that schedules them costs in bookkeeping as attributes:
    ``updates_per_row`` (the most softmax updates any q row goes through
    from its first k block to its last) and ``branches_per_cell`` (the most
    run-time branches inside one grid cell, the cell's own full | diagonal
    | skipped choice not counted)."""

    def __new__(cls, scheduled, useful, updates_per_row, branches_per_cell):
        self = super().__new__(cls, (scheduled, useful))
        self.updates_per_row = updates_per_row
        self.branches_per_cell = branches_per_cell
        return self


def causal_flash_flops(t_q, t_k, d, block_q=1024, block_k=1024,
                       diag_w=None, per_head=True):
    """MXU flops the causal forward kernel SCHEDULES for one (batch, head),
    by simulating exactly the kernel's cell walk (``_cell_kind`` and
    ``_strip_cols`` are what ``_walk_cell`` runs in the forward AND all
    three backward kernels, so this accounting IS the grid-shape
    assertion; the backward schedules the same (row, col) coverage with
    5-7 dots per pair instead of 2).  Returns a ``FlashWalk``:
    ``(scheduled, useful)`` where useful counts only unmasked (q_pos >=
    k_pos) score entries, both in flops of the two forward block dots
    (q@k^T and p@v: 4*d per score entry), with the walk's softmax updates
    per q row and branches per cell as attributes."""
    block_q = _pick_block(t_q, block_q)
    block_k = _pick_block(t_k, block_k)
    strip = _pick_block(block_q, diag_w or DIAG_W)
    scheduled = updates = 0
    for j in range(t_q // block_q):
        cells = 0  # live cells of this q block: an update a row in each
        for kb in range(t_k // block_k):
            full, straddling = _cell_kind(j * block_q - kb * block_k,
                                          block_q, block_k)
            cells += full or straddling
            if full:
                scheduled += block_q * block_k
            elif straddling:
                scheduled += strip * sum(
                    _strip_cols(qs, strip, block_q, block_k)[0]
                    for qs in range(block_q // strip))
        updates = max(updates, cells)
    useful = sum(min(r + 1, t_k) for r in range(t_q))
    # _walk_cell branches once a cell (full | straddling | skipped) and
    # never inside one
    return FlashWalk(4 * d * scheduled, 4 * d * useful, updates, 0)


def _masked_tail(s, mask, masked):
    """``s`` with the last ``masked`` of its columns put to NEG_INF where
    ``mask`` ([rows, masked] bool) is false; the columns before them are
    not touched (no iota, no select)."""
    if not masked:
        return s
    w = s.shape[1] - masked
    tail = jnp.where(mask, s[:, w:], NEG_INF)
    return tail if not w else jnp.concatenate([s[:, :w], tail], axis=1)


def _walk_cell(tile, causal, off, block_q, block_k):
    """Walk one grid cell: call ``tile(rows, cols, mask, masked)`` over
    what the causal mask allows of its [block_q, block_k] score tile, each
    call one [rows, cols] tile whose last ``masked`` columns take ``mask``
    (see ``_masked_tail``), every q row in at most one call.  ``off`` is
    the cell's first q row less its first k column (traced program ids).

    One run-time branch a causal cell: full (one tile, no mask) |
    straddling | skipped.  A straddling cell is walked in strips of DIAG_W
    q rows.  With ``block_q == block_k`` it is the diagonal cell (``off``
    is 0) and all of it is static: strip ``qs`` takes columns ``[0,
    (qs + 1) * DIAG_W)``, masked on the last DIAG_W, so nothing above the
    diagonal band is computed.  Unequal blocks put the diagonal anywhere
    in the cell: there a strip takes every column under a mask placed by
    ``off``.  ``causal_flash_flops`` counts this walk."""
    import jax.experimental.pallas as pl

    rows_all, cols_all = slice(0, block_q), slice(0, block_k)
    if not causal:
        tile(rows_all, cols_all, None, 0)
        return
    full, straddling = _cell_kind(off, block_q, block_k)
    pl.when(full)(lambda: tile(rows_all, cols_all, None, 0))

    @pl.when(straddling)
    def _straddling():
        R = _pick_block(block_q, DIAG_W)
        W = _strip_cols(0, R, block_q, block_k)[1]
        # equal blocks: this is the diagonal cell and ``off`` is 0
        at = 0 if block_q == block_k else off
        # row less column inside a strip's masked columns, the first of
        # which is column ``visible - masked`` of the cell
        rel = (jax.lax.broadcasted_iota(jnp.int32, (R, W), 0)
               - jax.lax.broadcasted_iota(jnp.int32, (R, W), 1))
        for qs in range(block_q // R):
            visible, masked = _strip_cols(qs, R, block_q, block_k)
            mask = rel >= (visible - masked) - (at + qs * R)
            tile(slice(qs * R, (qs + 1) * R), slice(0, visible), mask,
                 masked)


def _dot_t(a, b):
    """a^T @ b (contraction over the rows of both), f32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a @ b^T (contraction over the columns of both)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _last_k_block(causal, j, block_q, block_k, nk):
    """The last k block q block ``j`` attends, where a q-major kernel
    finalizes.  Clamped to nk-1: cross-attention with t_q > t_k has q
    blocks whose diagonal lies beyond the last k block, and the finalize
    step must still fire for them."""
    if not causal:
        return nk - 1
    return jnp.minimum(((j + 1) * block_q - 1) // block_k, nk - 1)


def _side_by_side(parts, dtype):
    """The sub-heads' [rows, d] parts as one [rows, S * d] value to store."""
    return (parts[0] if len(parts) == 1
            else jnp.concatenate(parts, axis=-1)).astype(dtype)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                      acc_scr, *, sm_scale, causal, block_q, block_k, nk,
                      sub_heads):
    """One (batch*head-slice, q-block, k-block) grid cell.  The k-block
    axis is the INNERMOST grid dimension (TPU grids run sequentially), so
    the online-softmax state lives in VMEM scratch carried across k steps
    — VMEM holds only O(block_q*d + block_k*d), never the full K/V (a
    whole-K/V block spec OOMs scoped vmem at t ~ 16k).

    ``sub_heads`` (S): heads carried per 128-lane feature slice.  S=1 is
    the lane-aligned layout (d_head % 128 == 0); S=2 packs two d=64 heads
    per slice — each sub-head is an independent attention over its own
    64-lane half (separate softmax state in the leading scratch axis), so
    d_head=64 models get the transpose-free packed path too.  The 64-lane
    value sub-slices are plain static lane slices (interpret mode and
    Mosaic's masked vector loads both handle them).

    ``_walk_cell`` decides which [rows, cols] tiles of the cell are
    computed; each is one online-softmax update of its rows' state.
    """
    import jax.experimental.pallas as pl

    j = pl.program_id(1)
    kb = pl.program_id(2)
    S = sub_heads
    d = q_ref.shape[-1] // S

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    def _tile(rows, cols, mask, masked):
        for sh in range(S):
            sl = slice(sh * d, (sh + 1) * d)
            # MXU feeds stay in the INPUT dtype (bf16 in = 2x the f32 MXU
            # rate); only the softmax state is f32.  Same convention as
            # the public TPU flash kernels.
            s = _masked_tail(
                _dot_nt(q_ref[0, rows, sl], k_ref[0, cols, sl]) * sm_scale,
                mask, masked)
            m_prev = m_scr[sh, rows]
            m2 = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m2)
            p = jnp.exp(s - m2[:, :1])
            v = v_ref[0, cols, sl]
            l_scr[sh, rows] = (l_scr[sh, rows] * alpha
                               + jnp.sum(p, axis=-1, keepdims=True))
            acc_scr[sh, rows] = acc_scr[sh, rows] * alpha[:, :1] + _dot(
                p.astype(v.dtype), v)
            m_scr[sh, rows] = m2

    # causal block skip: k blocks strictly above the diagonal touch no
    # unmasked entries and are walked by no tile (halves the causal
    # forward's work)
    _walk_cell(_tile, causal, j * block_q - kb * block_k, block_q, block_k)

    @pl.when(kb == _last_k_block(causal, j, block_q, block_k, nk))
    def _finalize():
        lses = []
        outs = []
        for sh in range(S):
            l_fin = l_scr[sh]
            l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
            outs.append((acc_scr[sh] / l_safe[:, :1]).astype(o_ref.dtype))
            # narrow [bq, 1] store (Mosaic masked store) — the residual /
            # ring-merge layout, 4 B/row instead of a 512 B replicated tile
            lses.append((m_scr[sh] + jnp.log(l_safe))[:, :1])
        o_ref[0] = outs[0] if S == 1 else jnp.concatenate(outs, axis=-1)
        lse_ref[...] = jnp.stack(lses)


def _packed_geom(q, k, n_head, sub_heads=1):
    """Shapes + block-index maps for the supported layouts.

    ``n_head=None``: q/k/v are [b*h, t, d] (the packed-by-transpose layout
    the 4-D public API produces).  ``n_head=h``: q/k/v are [b, t, h*d] —
    the RAW projection output.  Heads live in the lane dimension, so each
    grid cell's block is a 128-aligned lane slice selected by the INDEX
    MAP ((i // n_slices, ·, i % n_slices) block coords) and no
    [b,t,h,d]<->[bh,t,d] transpose ever exists.  (A 4-D h-sliced BlockSpec
    is rejected by the Mosaic tiling rules — measured in round 4; the
    lane-slice form is the legal spelling of the same thing.)

    ``sub_heads`` (S): heads per 128-lane slice — 1 for d_head % 128 == 0,
    2 for d_head == 64 (two heads packed per slice; the kernels run S
    independent softmax states over the 64-lane halves).  The grid's
    leading axis then has b * h / S cells over h / S slices.

    Returns (bh_cells, t_q, t_k, width, qix, kix) where ``width`` is the
    feature-slice width each block spec carries (S * d_head) and qix/kix
    map (grid cell, q-or-k block index) -> block coords.
    """
    if n_head is None:
        bh, t_q, d = q.shape
        t_k = k.shape[1]

        def qix(i, blk):
            return (i, blk, 0)

        return bh, t_q, t_k, d, qix, qix
    h = n_head
    S = sub_heads
    b, t_q, hd = q.shape
    t_k = k.shape[1]
    width = (hd // h) * S
    n_slices = h // S

    def pix(i, blk):
        return (i // n_slices, blk, i % n_slices)

    return b * (h // S), t_q, t_k, width, pix, pix


def _live_k_block(kix, causal, block_q, block_k):
    """The K/V index map of a q-major grid (i, j, kb).  A causal cell
    above the diagonal is skipped, so it names its row's last live block
    again: the pipeline sees an unchanged index and fetches nothing."""
    if not causal:
        return lambda i, j, kb: kix(i, kb)
    return lambda i, j, kb: kix(
        i, jnp.minimum(kb, ((j + 1) * block_q - 1) // block_k))


def _live_q_block(causal, block_q, block_k, nq):
    """The q block a k-major grid cell (i, kb, jq) fetches: skipped cells
    (q blocks wholly before k block ``kb``) name the first live one."""
    if not causal:
        return lambda kb, jq: jq
    return lambda kb, jq: jnp.minimum(
        jnp.maximum(jq, (kb * block_k) // block_q), nq - 1)


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
               n_head=None, sub_heads=1):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S = sub_heads
    bh, t_q, t_k, width, qix, kix = _packed_geom(q, k, n_head, S)
    block_q = _pick_block(t_q, block_q)
    block_k = _pick_block(t_k, block_k)
    nk = t_k // block_k

    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk, sub_heads=S,
    )
    kv_at = _live_k_block(kix, causal, block_q, block_k)
    scratch = [
        pltpu.VMEM((S, block_q, LSE_LANES), jnp.float32),  # m
        pltpu.VMEM((S, block_q, LSE_LANES), jnp.float32),  # l
        pltpu.VMEM((S, block_q, width // S), jnp.float32),  # acc
    ]
    # lse stays [b*h, t_q, 1] in ALL layouts: it is a per-token scalar
    # (1.5 MB at the flagship shape) so writing it row-major-by-(b,h)
    # costs nothing — grid cell i owns rows [i*S, (i+1)*S), and the
    # backward kernels read it back with the same (i, j, 0) map.  Only
    # the O(t*d) tensors need the lane-slice maps to dodge transposes.
    n_lse_rows = bh * S
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, t_q // block_q, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, width), lambda i, j, kb: qix(i, j)),
            pl.BlockSpec((1, block_k, width), kv_at),
            pl.BlockSpec((1, block_k, width), kv_at),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, width), lambda i, j, kb: qix(i, j)),
            pl.BlockSpec((S, block_q, 1), lambda i, j, kb: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((n_lse_rows, t_q, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    # lse leaves the kernel [b*h, t_q, 1] but is squeezed to 2-D [b*h, t_q]
    # immediately: a trailing size-1 dim gets tile-padded back to 128
    # lanes by XLA's T(8,128) layout (402 MB/layer at t=16k bs8 — exactly
    # the lane-replicated waste again, just hidden in padding).  The 2-D
    # form is compact; backward re-expands it transiently.
    return o, lse[:, :, 0]


def _bwd_p(q, k, lse, sm_scale, mask, masked):
    """p (f32) of one [rows, cols] tile, recomputed from the saved lse
    ([rows, 1])."""
    s = _dot_nt(q, k) * sm_scale
    return jnp.exp(_masked_tail(s, mask, masked) - lse)


def _bwd_ds(p, do, v, delta, sm_scale):
    """ds (f32) of the tile from its p and delta = rowsum(do * o)
    ([rows, 1])."""
    return p * (_dot_nt(do, v) - delta) * sm_scale


def _bwd_delta(do, o, dlse):
    """delta = rowsum(do * o) in f32 ([rows, 1]); an lse cotangent (from
    callers that consume lse, e.g. ring-attention merges) folds in as
    ds = p * (dp - delta + dlse) * scale."""
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    return delta if dlse is None else delta - dlse


def _bwd_dq_kernel(*refs, sm_scale, causal, block_q, block_k, nk,
                   has_dlse, sub_heads):
    """dq: grid (bh, q-blocks, k-blocks), k innermost; accumulate in VMEM.
    delta = rowsum(do*o) is computed here (kb==0).  ``sub_heads`` > 1: each
    128-lane slice carries S independent d=64 heads (see the forward
    kernel) — per-sub-head score/delta math, one concatenated dq store."""
    import jax.experimental.pallas as pl

    if has_dlse:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dlse_ref,
         dq_ref, dq_scr, delta_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dq_ref, dq_scr, delta_scr) = refs
        dlse_ref = None

    j = pl.program_id(1)
    kb = pl.program_id(2)
    S = sub_heads
    d = q_ref.shape[-1] // S

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])
        for sh in range(S):
            sl = slice(sh * d, (sh + 1) * d)
            d_row = _bwd_delta(do_ref[0, :, sl], o_ref[0, :, sl],
                               None if dlse_ref is None else dlse_ref[sh])
            delta_scr[sh] = jnp.broadcast_to(d_row, delta_scr.shape[1:])

    def _tile(rows, cols, mask, masked):
        for sh in range(S):
            sl = slice(sh * d, (sh + 1) * d)
            k = k_ref[0, cols, sl]
            p = _bwd_p(q_ref[0, rows, sl], k, lse_ref[sh, rows], sm_scale,
                       mask, masked)
            ds = _bwd_ds(p, do_ref[0, rows, sl], v_ref[0, cols, sl],
                         delta_scr[sh, rows][:, :1], sm_scale)
            dq_scr[sh, rows] += _dot(ds.astype(k.dtype), k)

    _walk_cell(_tile, causal, j * block_q - kb * block_k, block_q, block_k)

    @pl.when(kb == _last_k_block(causal, j, block_q, block_k, nk))
    def _finalize():
        dq_ref[0] = _side_by_side([dq_scr[sh] for sh in range(S)],
                                  dq_ref.dtype)


def _bwd_dkv_kernel(*refs, sm_scale, causal, block_q, block_k, nq,
                    has_dlse, sub_heads):
    """dk/dv: grid (bh, k-blocks, q-blocks), q innermost."""
    import jax.experimental.pallas as pl

    if has_dlse:
        (k_ref, v_ref, q_ref, do_ref, o_ref, lse_ref, dlse_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (k_ref, v_ref, q_ref, do_ref, o_ref, lse_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        dlse_ref = None

    kb = pl.program_id(1)
    jq = pl.program_id(2)
    S = sub_heads
    d = q_ref.shape[-1] // S

    @pl.when(jq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    def _tile(rows, cols, mask, masked):
        for sh in range(S):
            sl = slice(sh * d, (sh + 1) * d)
            q, do = q_ref[0, rows, sl], do_ref[0, rows, sl]
            delta = _bwd_delta(
                do, o_ref[0, rows, sl],
                None if dlse_ref is None else dlse_ref[sh, rows])
            p = _bwd_p(q, k_ref[0, cols, sl], lse_ref[sh, rows], sm_scale,
                       mask, masked)
            dv_scr[sh, cols] += _dot_t(p.astype(do.dtype), do)
            ds = _bwd_ds(p, do, v_ref[0, cols, sl], delta, sm_scale)
            dk_scr[sh, cols] += _dot_t(ds.astype(q.dtype), q)

    _walk_cell(_tile, causal, jq * block_q - kb * block_k, block_q, block_k)

    @pl.when(jq == nq - 1)
    def _finalize():
        dk_ref[0] = _side_by_side([dk_scr[sh] for sh in range(S)],
                                  dk_ref.dtype)
        dv_ref[0] = _side_by_side([dv_scr[sh] for sh in range(S)],
                                  dv_ref.dtype)


def _bwd_fused_kernel(*refs, sm_scale, causal, block_q, block_k, nq,
                      has_dlse, sub_heads):
    """Single-pass backward: grid (bh, k-blocks, q-blocks), q innermost.
    Computes the s/p tile ONCE per (k, q) block pair (the split dq + dkv
    kernels each recompute it — 7 block matmuls per pair vs 5 here) and
    emits dk/dv via VMEM accumulators plus dq as per-k-block partials
    ``dq_part[kb]`` that the caller reduces over kb.  Used when the
    partial buffer is small (nk grows with t; the split kernels remain
    the long-context path).  ``_walk_cell`` takes every q row of a live
    cell through exactly one tile, so a tile's ``ds @ k`` IS those rows'
    partial and is stored as it is made."""
    import jax.experimental.pallas as pl

    if has_dlse:
        (k_ref, v_ref, q_ref, do_ref, o_ref, lse_ref, dlse_ref,
         dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (k_ref, v_ref, q_ref, do_ref, o_ref, lse_ref,
         dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr) = refs
        dlse_ref = None

    kb = pl.program_id(1)
    jq = pl.program_id(2)
    S = sub_heads
    d = q_ref.shape[-1] // S

    @pl.when(jq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    def _tile(rows, cols, mask, masked):
        dqs = []
        for sh in range(S):
            sl = slice(sh * d, (sh + 1) * d)
            q, do, k = (q_ref[0, rows, sl], do_ref[0, rows, sl],
                        k_ref[0, cols, sl])
            delta = _bwd_delta(
                do, o_ref[0, rows, sl],
                None if dlse_ref is None else dlse_ref[sh, rows])
            p = _bwd_p(q, k, lse_ref[sh, rows], sm_scale, mask, masked)
            dv_scr[sh, cols] += _dot_t(p.astype(do.dtype), do)
            ds = _bwd_ds(p, do, v_ref[0, cols, sl], delta,
                         sm_scale).astype(q.dtype)
            dk_scr[sh, cols] += _dot_t(ds, q)
            dqs.append(_dot(ds, k))
        dqp_ref[0, 0, rows] = _side_by_side(dqs, dqp_ref.dtype)

    off = jq * block_q - kb * block_k
    _walk_cell(_tile, causal, off, block_q, block_k)
    if causal:
        # skipped cells still own their dq_part block — zero it so the
        # caller's reduce over kb sees no garbage
        @pl.when(off <= -block_q)
        def _zero():
            dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])

    @pl.when(jq == nq - 1)
    def _finalize():
        dk_ref[0] = _side_by_side([dk_scr[sh] for sh in range(S)],
                                  dk_ref.dtype)
        dv_ref[0] = _side_by_side([dv_scr[sh] for sh in range(S)],
                                  dv_ref.dtype)


# fused-backward dq partials budget: [nk, bh, t, d] must stay under this
# (past it — long t — the split dq/dkv kernels take over)
FUSED_BWD_PARTIAL_BYTES = 512 << 20


def _flash_bwd_fused(q, k, v, o, lse, do, sm_scale, causal, block_q,
                     block_k, interpret, dlse=None, n_head=None,
                     sub_heads=1):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S = sub_heads
    bh, t_q, t_k, width, qix, kix = _packed_geom(q, k, n_head, S)
    d_sub = width // S
    block_q = _pick_block(t_q, block_q)
    block_k = _pick_block(t_k, block_k)
    nq = t_q // block_q
    nk = t_k // block_k
    has_dlse = dlse is not None

    live = _live_q_block(causal, block_q, block_k, nq)
    kspec = pl.BlockSpec((1, block_k, width), lambda i, kb, jq: kix(i, kb))
    qspec = pl.BlockSpec((1, block_q, width),
                         lambda i, kb, jq: qix(i, live(kb, jq)))
    qstat = pl.BlockSpec((S, block_q, 1),
                         lambda i, kb, jq: (i, live(kb, jq), 0))
    in_specs = [kspec, kspec, qspec, qspec, qspec, qstat]
    args = [k, v, q, do, o, lse]
    if has_dlse:
        in_specs.append(qstat)
        args.append(dlse)
    if n_head is None:
        dqp_spec = pl.BlockSpec((1, 1, block_q, width),
                                lambda i, kb, jq: (kb, i, jq, 0))
        dqp_shape = jax.ShapeDtypeStruct((nk, bh, t_q, width), q.dtype)
    else:
        n_slices = n_head // S
        dqp_spec = pl.BlockSpec(
            (1, 1, block_q, width),
            lambda i, kb, jq: (kb, i // n_slices, jq, i % n_slices))
        dqp_shape = jax.ShapeDtypeStruct((nk,) + q.shape, q.dtype)
    dq_part, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          nq=nq, has_dlse=has_dlse, sub_heads=S),
        grid=(bh, nk, nq),
        in_specs=in_specs,
        out_specs=[dqp_spec, kspec, kspec],
        out_shape=[
            dqp_shape,
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((S, block_k, d_sub), jnp.float32),
                        pltpu.VMEM((S, block_k, d_sub), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_bwd_fused",
    )(*args)
    dq = jnp.sum(dq_part.astype(jnp.float32), axis=0).astype(q.dtype)
    return dq, dk, dv


def _flash_bwd(q, k, v, o, lse, do, sm_scale, causal, block_q, block_k,
               interpret, dlse=None, n_head=None, sub_heads=1):
    """Pallas backward.  Short/medium t: one fused kernel (s recomputed
    once per block pair, dq as per-k-block partials).  Long t (partials
    over budget): dq kernel (q-major) + dk/dv kernel (k-major), both with
    causal block skip; O(block^2) VMEM.  ``lse`` and the optional ``dlse``
    (the cotangent of the returned lse, for callers that consume it —
    ring-attention merges) arrive in the narrow [b*h, t_q, 1] residual
    layout in ALL q/k/v layouts (packed mode keeps lse row-major by
    (b, h) — see the forward's lse note)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S = sub_heads
    bh, t_q, t_k, width, qix, kix = _packed_geom(q, k, n_head, S)
    d_sub = width // S
    block_q = _pick_block(t_q, block_q)
    block_k = _pick_block(t_k, block_k)
    nq = t_q // block_q
    nk = t_k // block_k
    has_dlse = dlse is not None

    part_bytes = nk * bh * t_q * width * q.dtype.itemsize
    if part_bytes <= FUSED_BWD_PARTIAL_BYTES:
        return _flash_bwd_fused(q, k, v, o, lse, do, sm_scale, causal,
                                block_q, block_k, interpret, dlse=dlse,
                                n_head=n_head, sub_heads=S)

    qspec = pl.BlockSpec((1, block_q, width), lambda i, j, kb: qix(i, j))
    kspec = pl.BlockSpec((1, block_k, width),
                         _live_k_block(kix, causal, block_q, block_k))
    qstat = pl.BlockSpec((S, block_q, 1), lambda i, j, kb: (i, j, 0))
    dq_in_specs = [qspec, kspec, kspec, qspec, qspec, qstat]
    dq_args = [q, k, v, do, o, lse]
    if has_dlse:
        dq_in_specs.append(qstat)
        dq_args.append(dlse)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          has_dlse=has_dlse, sub_heads=S),
        grid=(bh, nq, nk),
        in_specs=dq_in_specs,
        out_specs=[qspec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        scratch_shapes=[pltpu.VMEM((S, block_q, d_sub), jnp.float32),
                        pltpu.VMEM((S, block_q, LSE_LANES), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*dq_args)[0]

    live = _live_q_block(causal, block_q, block_k, nq)
    kspec2 = pl.BlockSpec((1, block_k, width), lambda i, kb, jq: kix(i, kb))
    qspec2 = pl.BlockSpec((1, block_q, width),
                          lambda i, kb, jq: qix(i, live(kb, jq)))
    qstat2 = pl.BlockSpec((S, block_q, 1),
                          lambda i, kb, jq: (i, live(kb, jq), 0))
    dkv_in_specs = [kspec2, kspec2, qspec2, qspec2, qspec2, qstat2]
    dkv_args = [k, v, q, do, o, lse]
    if has_dlse:
        dkv_in_specs.append(qstat2)
        dkv_args.append(dlse)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          nq=nq, has_dlse=has_dlse, sub_heads=S),
        grid=(bh, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[kspec2, kspec2],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((S, block_k, d_sub), jnp.float32),
                        pltpu.VMEM((S, block_k, d_sub), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*dkv_args)
    return dq, dk, dv


def _sub_heads_for(n_head, q):
    """The sub_heads (S) the kernels run for this call: the geometry
    decision of ``packed_sub_heads``, with unsupported widths falling back
    to S=1 (reachable only in interpret mode — the public API rejects
    them on hardware)."""
    if n_head is None:
        return 1
    d = q.shape[-1] // n_head
    return packed_sub_heads(n_head, d) or 1


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                n_head=None):
    o, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                      n_head=n_head, sub_heads=_sub_heads_for(n_head, q))
    return o


def _flash_core_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                    n_head=None):
    o, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                        interpret, n_head=n_head,
                        sub_heads=_sub_heads_for(n_head, q))
    # FLASH_BWD_RESIDUALS contract: tag the kernel-owned residuals (o is
    # ALSO the primal output — one tagged value, saved once) so a
    # name-policy checkpoint (memory_optimize(policy="offload")) keeps
    # them instead of re-running the forward kernel in the backward pass.
    # Outside a name-policy region the tag is an identity no-op.
    o = checkpoint_name(o, KERNEL_RESIDUAL_TAG)
    lse = checkpoint_name(lse, KERNEL_RESIDUAL_TAG)
    return o, (q, k, v, o, lse)


def _flash_core_bwd(sm_scale, causal, block_q, block_k, interpret, n_head,
                    res, do):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse[:, :, None], do, sm_scale, causal,
                      block_q, block_k, interpret, n_head=n_head,
                      sub_heads=_sub_heads_for(n_head, q))


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _resolve_backend(backend):
    """One selection path for every flash entry point: the kernel
    registry's resolution (explicit arg > per-op env > global env >
    auto; docs/kernels.md).  Returns ``(name, impl)``.  The old ad-hoc
    per-platform fallback — ``interpret = jax.default_backend() !=
    "tpu"`` at each call site — is now the ``pallas_tpu`` backend's own
    interpret default behind this path."""
    from ..kernels import resolve  # late: kernels imports this module

    kernel = resolve("flash_attention", backend)
    return kernel.backend, kernel.impl


def _pallas_flash_attention(q, k, v, causal=False, sm_scale=None,
                            block_q=1024, block_k=1024, interpret=None):
    """The Mosaic (``pallas_tpu``) flash attention: q [b, t_q, h, d],
    k/v [b, t_k, h, d] -> [b, t_q, h, d].  Differentiable (custom VJP).
    ``interpret=None`` auto-selects Pallas interpreter mode off-TPU so
    the same kernel logic runs in CPU tests."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    sm_scale = d ** -0.5 if sm_scale is None else sm_scale

    def pack(x, t):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, t, x.shape[-1])

    o = _flash_core(
        pack(q, t_q), pack(k, t_k), pack(v, t_k),
        float(sm_scale), bool(causal), int(block_q), int(block_k),
        bool(interpret), None,
    )
    return jnp.swapaxes(o.reshape(b, h, t_q, d), 1, 2)


def flash_attention(q, k, v, causal=False, sm_scale=None, block_q=1024,
                    block_k=1024, interpret=None, backend=None):
    """Fused attention, routed through the kernel registry
    (docs/kernels.md): ``backend`` picks pallas_tpu | xla_ref
    explicitly, None resolves env overrides then the platform's auto
    order.  q [b, t_q, h, d], k/v [b, t_k, h, d] -> [b, t_q, h, d];
    differentiable through every backend (each carries the same
    custom-VJP residual contract)."""
    name, impl = _resolve_backend(backend)
    if name == "pallas_tpu":
        return _pallas_flash_attention(q, k, v, causal=causal,
                                       sm_scale=sm_scale, block_q=block_q,
                                       block_k=block_k,
                                       interpret=interpret)
    return impl.call(q, k, v, causal=causal, sm_scale=sm_scale,
                     block_q=block_q, block_k=block_k,
                     interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core_lse(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                        interpret)
    return o, lse


def _flash_core_lse_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                        interpret):
    o, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                        interpret)
    # same FLASH_BWD_RESIDUALS tagging as _flash_core_fwd (o and lse are
    # both primal outputs here — still one tagged value each)
    o = checkpoint_name(o, KERNEL_RESIDUAL_TAG)
    lse = checkpoint_name(lse, KERNEL_RESIDUAL_TAG)
    return (o, lse), (q, k, v, o, lse)


def _flash_core_lse_bwd(sm_scale, causal, block_q, block_k, interpret,
                        res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _flash_bwd(q, k, v, o, lse[:, :, None], do, sm_scale, causal,
                      block_q, block_k, interpret,
                      dlse=dlse.astype(jnp.float32)[:, :, None])


_flash_core_lse.defvjp(_flash_core_lse_fwd, _flash_core_lse_bwd)


def flash_attention_with_lse(q, k, v, causal=False, sm_scale=None,
                             block_q=1024, block_k=1024, interpret=None,
                             backend=None):
    """flash_attention that ALSO returns the per-row logsumexp
    (o [b, t, h, d], lse [b, h, t]) — the building block for composing
    partial attentions with online-softmax merges (ring attention).
    Fully differentiable including through lse; registry-routed like
    ``flash_attention``."""
    name, impl = _resolve_backend(backend)
    if name != "pallas_tpu":
        return impl.call_with_lse(q, k, v, causal=causal,
                                  sm_scale=sm_scale, block_q=block_q,
                                  block_k=block_k, interpret=interpret)
    return _pallas_flash_attention_with_lse(
        q, k, v, causal=causal, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, interpret=interpret)


def _pallas_flash_attention_with_lse(q, k, v, causal=False, sm_scale=None,
                                     block_q=1024, block_k=1024,
                                     interpret=None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    sm_scale = d ** -0.5 if sm_scale is None else sm_scale

    def pack(x, t):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, t, x.shape[-1])

    o, lse = _flash_core_lse(
        pack(q, t_q), pack(k, t_k), pack(v, t_k),
        float(sm_scale), bool(causal), int(block_q), int(block_k),
        bool(interpret),
    )
    return (jnp.swapaxes(o.reshape(b, h, t_q, d), 1, 2),
            lse.reshape(b, h, t_q))



def flash_attention_packed(q, k, v, n_head, causal=False, sm_scale=None,
                           block_q=1024, block_k=1024, interpret=None,
                           backend=None):
    """Fused attention on the RAW projection layout: q/k/v [b, t, h*d]
    (heads concatenated in the feature dim, exactly what the QKV matmuls
    emit) -> o [b, t, h*d] (exactly what the output projection consumes).

    Numerically identical to ``flash_attention`` on the reshaped 4-D view,
    but the [b,t,h,d]<->[b*h,t,d] pack/unpack transposes — 23 ms/step on
    the GPT flagship, 8% of device time (measured in round 4) — never
    exist: each 128-lane slice is selected by the kernels' block index
    maps.  Supported geometries (``packed_sub_heads``): ``d_head % 128 ==
    0`` (one head per slice), ``d_head == 64`` with even ``n_head`` (TWO
    heads per slice — the kernels run two independent softmax states over
    the 64-lane halves, so d_head-64 models dodge the transpose tax too),
    or ``n_head == 1``.  Other widths raise; callers use
    ``flash_attention``.  Registry-routed: the xla_ref backend is
    shape-complete here (its head split is a reshape, not a Mosaic
    lane slice), so every head width works off the TPU path."""
    name, impl = _resolve_backend(backend)
    if name != "pallas_tpu":
        return impl.call_packed(q, k, v, n_head, causal=causal,
                                sm_scale=sm_scale, block_q=block_q,
                                block_k=block_k, interpret=interpret)
    return _pallas_flash_attention_packed(
        q, k, v, n_head, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=interpret)


def _pallas_flash_attention_packed(q, k, v, n_head, causal=False,
                                   sm_scale=None, block_q=1024,
                                   block_k=1024, interpret=None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t_q, hd = q.shape
    if hd % n_head:
        raise ValueError(f"feature dim {hd} not divisible by n_head {n_head}")
    d = hd // n_head
    if packed_sub_heads(n_head, d) is None and not interpret:
        # interpret mode has no Mosaic tiling rules — CPU tests exercise
        # small head widths through the identical code path
        raise ValueError(
            f"flash_attention_packed needs d_head % 128 == 0 or d_head == "
            f"64 with even n_head (lane-aligned or paired head slices), "
            f"got d_head={d}, n_head={n_head}; use flash_attention for "
            f"other head widths")
    sm_scale = d ** -0.5 if sm_scale is None else sm_scale
    return _flash_core(
        q, k, v, float(sm_scale), bool(causal), int(block_q), int(block_k),
        bool(interpret), int(n_head))


def attention_reference(q, k, v, causal=False, sm_scale=None):
    """Dense reference implementation (for tests and tiny shapes)."""
    d = q.shape[-1]
    sm_scale = d ** -0.5 if sm_scale is None else sm_scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        t_q, t_k = logits.shape[-2:]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


# -- op registration ---------------------------------------------------------
from ..core.registry import register_op


def _kernel_mesh(_ctx, op_class, backend, batch, manual=False):
    """``(mesh, batch_axis)`` when this op's kernel call must run inside
    a ``shard_map`` over the executor's mesh, else ``(None, None)``.

    GSPMD cannot partition a Mosaic custom call ("Mosaic kernels cannot
    be automatically partitioned"), so on a mesh a NATIVELY compiled
    kernel runs manual over every mesh axis: the batch splits over
    ``batch_axis`` and the operands are replicated over the axes the
    specs do not name (compute is replicated along ``fsdp``, as in the
    GSPMD spelling).  An interpreted kernel (off-TPU) lowers to plain
    XLA ops that GSPMD partitions itself, so it stays unwrapped unless
    the op asks with ``manual`` (the tensor-parallel recipes, which need
    their own collectives).

    ``batch_axis`` is the mesh axis the activations' leading dim is
    split over at this point of the trace: ``dp``, or None where an
    enclosing vmap already holds it (``LoweringCtx.batch_axis``, the
    local-accumulation lanes) or ``batch`` does not divide."""
    mesh = _ctx_mesh(_ctx)
    if mesh is None or mesh.size == 1:
        return None, None
    if not manual:
        from ..kernels import resolve_name

        if not (jax.default_backend() == "tpu"
                and resolve_name(op_class, backend) == "pallas_tpu"):
            return None, None
    axis = getattr(_ctx, "batch_axis", None)
    if axis not in mesh.axis_names or batch % mesh.shape[axis]:
        axis = None
    return mesh, axis


def _ctx_mesh(_ctx):
    return getattr(getattr(_ctx, "executor", None), "mesh", None)


@register_op("flash_attention")
def flash_attention_op(Q, K, V, causal=False, sm_scale=0.0, block_q=1024,
                       block_k=1024, backend="", _ctx=None, **_):
    scale = None if not sm_scale else float(sm_scale)
    backend = backend or None

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=causal, sm_scale=scale,
                               block_q=int(block_q), block_k=int(block_k),
                               backend=backend)

    mesh, db = _kernel_mesh(_ctx, "flash_attention", backend, Q.shape[0])
    if mesh is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        spec = P(db, None, None, None)
        attend = shard_map(attend, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
    return {"Out": attend(Q, K, V)}


@register_op("flash_attention_packed")
def flash_attention_packed_op(Q, K, V, n_head=None, causal=False,
                              sm_scale=0.0, block_q=1024, block_k=1024,
                              backend="", _ctx=None, **_):
    if n_head is None:
        # no safe default: 1 would silently softmax across the whole
        # concatenated h*d feature dim as a single head
        raise ValueError("flash_attention_packed op requires the n_head attr")
    n_head = int(n_head)
    block_q, block_k = int(block_q), int(block_k)
    scale = None if not sm_scale else float(sm_scale)
    backend = backend or None
    from ..parallel.mesh import axis_size

    tp = axis_size(_ctx_mesh(_ctx), "tp")
    # Head-sharded tensor parallelism: the packed feature dim IS the
    # head dim, so a 'tp' shard of [b, t, h*d] holds h/tp whole heads
    # and attention needs NO cross-shard communication — each shard
    # runs the kernel on its local heads
    head_tp = tp > 1 and n_head % tp == 0
    mesh, db = _kernel_mesh(_ctx, "flash_attention", backend, Q.shape[0],
                            manual=head_tp)
    if mesh is None:
        return {"Out": flash_attention_packed(
            Q, K, V, n_head, causal=causal, sm_scale=scale,
            block_q=block_q, block_k=block_k, backend=backend)}
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(db, None, "tp" if head_tp else None)
    local_heads = n_head // tp if head_tp else n_head
    d_head = Q.shape[-1] // n_head

    def local(q, k, v):
        if packed_sub_heads(local_heads, d_head) is None:
            # the GLOBAL head count packs but the per-shard count does
            # not (e.g. d_head=64, n_head=6, tp=2 -> 3 local heads can't
            # pair): run the shard through the 4-D kernel — transposes
            # on the local shard beat a trace error
            b, t, hd = q.shape
            r4 = lambda x: x.reshape(b, t, local_heads, d_head)
            o = flash_attention(
                r4(q), r4(k), r4(v), causal=causal, sm_scale=scale,
                block_q=block_q, block_k=block_k, backend=backend)
            return o.reshape(b, t, hd)
        return flash_attention_packed(
            q, k, v, local_heads, causal=causal, sm_scale=scale,
            block_q=block_q, block_k=block_k, backend=backend)

    out = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                    out_specs=spec, check_vma=False)(Q, K, V)
    return {"Out": out}


# -- kernel-registry registration (docs/kernels.md) --------------------------
# The Mosaic kernels above ARE the "pallas_tpu" backend of the
# flash_attention op class: native on TPU, interpret mode off-TPU (the
# CPU test path — the availability reason annotates it).
from ..kernels.registry import (
    pallas_tpu_availability as _pallas_tpu_availability,
    register_kernel as _register_kernel)


class _FlashPallasTpu:
    call = staticmethod(_pallas_flash_attention)
    call_with_lse = staticmethod(_pallas_flash_attention_with_lse)
    call_packed = staticmethod(_pallas_flash_attention_packed)


_register_kernel("flash_attention", "pallas_tpu", _FlashPallasTpu,
                 available=_pallas_tpu_availability)
