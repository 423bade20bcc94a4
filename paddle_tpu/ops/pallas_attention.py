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
k axis innermost; online-softmax state carried in VMEM scratch; causal
k-blocks above the diagonal are skipped, and the mask select runs only on
blocks straddling the diagonal.  Backward: custom_vjp into two Pallas
kernels — dq (q-major grid) and dk/dv (k-major grid) — recomputing p from
the saved lane-replicated lse, also with causal block skip.
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

# causal diagonal sub-tile width: straddling (diagonal) blocks are computed
# as a static grid of (DIAG_W x DIAG_W) sub-tiles and sub-tiles entirely
# above the diagonal are NEVER computed — the forward waste of a causal
# block pair drops from ~block/2 masked columns per row-block (~20% of all
# flops at t=4096 with 1024 blocks) to the DIAG_W-wide band along the
# diagonal (~w/t).  256 keeps the sub-dots MXU-shaped ([256, d] x [d, 256])
# and the unroll at <= 16 regions per straddling cell.  A process-wide
# TUNABLE: PADDLE_TPU_DIAG_W pins it (the env knob wins over everything),
# and the autotune engine (paddle_tpu.tune, docs/autotune.md) sets the
# module global while measuring a candidate / applying a tuned winner
# (apply_tuned_diag_w) — the kernels read it at trace time, so fwd and
# all three bwd kernels always agree within one compile.
_DIAG_W_ENV = int(os.environ.get("PADDLE_TPU_DIAG_W", "0") or 0)
DIAG_W = _DIAG_W_ENV or 256


def apply_tuned_diag_w(width):
    """Apply a tuned causal sub-tile width process-wide (the autotune
    hot path / search loop).  The PADDLE_TPU_DIAG_W env pin always
    wins; returns the width actually in effect."""
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


def _diag_subtile_live(j, kb, qs, ks, block_q, block_k, wq, wk):
    """Sub-tile (qs, ks) of straddling cell (j, kb) intersects the allowed
    causal region (q_pos >= k_pos) — its first k column is at or below the
    sub-tile's last q row.  Works on both Python ints (flop accounting)
    and traced program ids (the kernel's pl.when predicates)."""
    row_last = j * block_q + (qs + 1) * wq - 1
    col0 = kb * block_k + ks * wk
    return col0 <= row_last


def _diag_subtile_needs_mask(j, kb, qs, ks, block_q, block_k, wq, wk):
    """The diagonal passes through sub-tile (qs, ks): its last k column is
    past the sub-tile's first q row, so the iota/select must run."""
    row0 = j * block_q + qs * wq
    col_last = kb * block_k + (ks + 1) * wk - 1
    return col_last > row0


def causal_flash_flops(t_q, t_k, d, block_q=1024, block_k=1024,
                       diag_w=None, per_head=True):
    """MXU flops the causal forward kernel SCHEDULES for one (batch, head),
    by simulating exactly the kernel's block/sub-tile skip logic
    (``_diag_subtile_live`` is shared with the forward AND all three
    backward kernels, so this accounting IS the grid-shape assertion; the
    backward schedules the same (row, col) coverage with 5-7 dots per
    pair instead of 2).  Returns ``(scheduled, useful)`` where useful
    counts only unmasked (q_pos >= k_pos) score entries; both in flops of
    the two forward block dots (q@k^T and p@v: 4*d per score entry)."""
    block_q = _pick_block(t_q, block_q)
    block_k = _pick_block(t_k, block_k)
    wq = _pick_block(block_q, diag_w or DIAG_W)
    wk = _pick_block(block_k, diag_w or DIAG_W)
    nq, nk = t_q // block_q, t_k // block_k
    scheduled = 0
    for j in range(nq):
        last_kb = min(((j + 1) * block_q - 1) // block_k, nk - 1)
        for kb in range(last_kb + 1):
            if j * block_q >= (kb + 1) * block_k - 1:
                scheduled += block_q * block_k  # fully unmasked cell
                continue
            for qs in range(block_q // wq):
                for ks in range(block_k // wk):
                    if _diag_subtile_live(j, kb, qs, ks, block_q,
                                          block_k, wq, wk):
                        scheduled += wq * wk
    useful = sum(min(r + 1, t_k) for r in range(t_q))
    return 4 * d * scheduled, 4 * d * useful


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

    Causal straddling (diagonal) cells run TRIANGULAR: a static grid of
    DIAG_W-wide sub-tiles in which sub-tiles entirely above the diagonal
    are never computed (``_diag_subtile_live``) and the iota/select mask
    runs only on sub-tiles the diagonal actually crosses — the masked
    half-block flops of the old full-tile + select spelling do not exist.
    """
    import jax.experimental.pallas as pl

    j = pl.program_id(1)
    kb = pl.program_id(2)
    S = sub_heads
    d = q_ref.shape[-1] // S
    wq = _pick_block(block_q, DIAG_W)
    wk = _pick_block(block_k, DIAG_W)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    if causal:
        # causal block skip: k blocks strictly above the diagonal touch
        # no unmasked entries — skip their compute entirely (halves the
        # causal forward's work).  Clamp to nk-1: cross-attention with
        # t_q > t_k has q blocks whose diagonal lies beyond the last k
        # block, and the finalize step must still fire for them.
        last_kb = jnp.minimum(((j + 1) * block_q - 1) // block_k, nk - 1)
        needed = kb <= last_kb
    else:
        last_kb = nk - 1
        needed = None

    def _update(sh, rows, s, v_sub):
        """One online-softmax state update for sub-head ``sh``, q rows
        ``rows`` (a static slice) and score tile ``s``."""
        m_prev = m_scr[sh, rows]
        l_prev = l_scr[sh, rows]
        m2 = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m2)
        p = jnp.exp(s - m2[:, :1])
        l2 = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc2 = acc_scr[sh, rows] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v_sub.dtype), v_sub, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[sh, rows] = m2
        l_scr[sh, rows] = l2
        acc_scr[sh, rows] = acc2

    def _score(q_sub, k_sub):
        # MXU feeds stay in the INPUT dtype (bf16 in = 2x the f32 MXU
        # rate); only the softmax state is f32.  Same convention as the
        # public TPU flash kernels.
        return jax.lax.dot_general(
            q_sub, k_sub, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale

    def _full_block():
        for sh in range(S):
            sl = slice(sh * d, (sh + 1) * d)
            s = _score(q_ref[0][:, sl], k_ref[0][:, sl])
            _update(sh, slice(None), s, v_ref[0][:, sl])

    def _diag_block():
        # triangular straddling cell: only sub-tiles intersecting the
        # allowed q_pos >= k_pos region are computed
        for sh in range(S):
            sl = slice(sh * d, (sh + 1) * d)
            q = q_ref[0][:, sl]
            k = k_ref[0][:, sl]
            v = v_ref[0][:, sl]
            for qs in range(block_q // wq):
                rows = slice(qs * wq, (qs + 1) * wq)
                for ks in range(block_k // wk):
                    cols = slice(ks * wk, (ks + 1) * wk)

                    def _sub(masked, rows=rows, cols=cols, qs=qs, ks=ks,
                             sh=sh, q=q, k=k, v=v):
                        s = _score(q[rows], k[cols])
                        if masked:
                            q_pos = (j * block_q + qs * wq
                                     + jax.lax.broadcasted_iota(
                                         jnp.int32, (wq, wk), 0))
                            k_pos = (kb * block_k + ks * wk
                                     + jax.lax.broadcasted_iota(
                                         jnp.int32, (wq, wk), 1))
                            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
                        _update(sh, rows, s, v[cols])

                    live = _diag_subtile_live(j, kb, qs, ks, block_q,
                                              block_k, wq, wk)
                    crossing = _diag_subtile_needs_mask(
                        j, kb, qs, ks, block_q, block_k, wq, wk)
                    pl.when(jnp.logical_and(live, crossing))(
                        lambda _s=_sub: _s(True))
                    pl.when(jnp.logical_and(
                        live, jnp.logical_not(crossing)))(
                        lambda _s=_sub: _s(False))

    if needed is None:
        _full_block()
    else:
        # the diagonal only crosses blocks straddling it; blocks fully
        # below run the plain full-tile dot with no iota/select at all
        unmasked = j * block_q >= (kb + 1) * block_k - 1
        pl.when(jnp.logical_and(needed, unmasked))(_full_block)
        pl.when(jnp.logical_and(needed, jnp.logical_not(unmasked)))(
            _diag_block)

    @pl.when(kb == last_kb)
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
    is rejected by the Mosaic tiling rules — see RESULTS.md round 4; the
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
            pl.BlockSpec((1, block_k, width), lambda i, j, kb: kix(i, kb)),
            pl.BlockSpec((1, block_k, width), lambda i, j, kb: kix(i, kb)),
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


def _bwd_dq_kernel(*refs, sm_scale, causal, block_q, block_k, nk,
                   has_dlse, sub_heads):
    """dq: grid (bh, q-blocks, k-blocks), k innermost; accumulate in VMEM.
    delta = rowsum(do*o) is computed here (kb==0); an lse cotangent (from
    callers that consume lse, e.g. ring-attention merges) folds in as
    ds = p * (dp - delta + dlse) * scale.  ``sub_heads`` > 1: each
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
            d_row = jnp.sum(
                do_ref[0][:, sl].astype(jnp.float32)
                * o_ref[0][:, sl].astype(jnp.float32),
                axis=-1, keepdims=True)
            if dlse_ref is not None:
                d_row = d_row - dlse_ref[sh][:, :1]
            delta_scr[sh] = jnp.broadcast_to(d_row, delta_scr.shape[1:])

    if causal:
        # clamped like the forward: cross-attention t_q > t_k must still
        # finalize the q blocks past the last k block
        last_kb = jnp.minimum(((j + 1) * block_q - 1) // block_k, nk - 1)
    else:
        last_kb = nk - 1

    wq = _pick_block(block_q, DIAG_W)
    wk = _pick_block(block_k, DIAG_W)

    def _sub(sh, rows, cols, q, k, v, do, masked, q0, k0):
        """One (q-rows, k-cols) sub-tile of the dq math for sub-head sh;
        ``q0``/``k0`` are the tile's absolute start positions."""
        lse = lse_ref[sh]
        delta = delta_scr[sh]
        s = jax.lax.dot_general(
            q[rows], k[cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if masked:
            shape = (s.shape[0], s.shape[1])
            q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[rows][:, :1])
        dp = jax.lax.dot_general(
            do[rows], v[cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[rows][:, :1]) * sm_scale).astype(k.dtype)
        dq_scr[sh, rows] += jax.lax.dot_general(
            ds, k[cols], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _block(masked):
        for sh in range(S):
            sl = slice(sh * d, (sh + 1) * d)
            _sub(sh, slice(None), slice(None), q_ref[0][:, sl],
                 k_ref[0][:, sl], v_ref[0][:, sl], do_ref[0][:, sl],
                 masked, j * block_q, kb * block_k)

    def _diag_block():
        # triangular straddling cell (same skip predicate as the forward):
        # sub-tiles entirely above the diagonal are never computed
        for sh in range(S):
            sl = slice(sh * d, (sh + 1) * d)
            q = q_ref[0][:, sl]
            k = k_ref[0][:, sl]
            v = v_ref[0][:, sl]
            do = do_ref[0][:, sl]
            for qs in range(block_q // wq):
                rows = slice(qs * wq, (qs + 1) * wq)
                for ks in range(block_k // wk):
                    cols = slice(ks * wk, (ks + 1) * wk)

                    def _go(masked, sh=sh, rows=rows, cols=cols, qs=qs,
                            ks=ks, q=q, k=k, v=v, do=do):
                        _sub(sh, rows, cols, q, k, v, do, masked,
                             j * block_q + qs * wq,
                             kb * block_k + ks * wk)

                    live = _diag_subtile_live(j, kb, qs, ks, block_q,
                                              block_k, wq, wk)
                    crossing = _diag_subtile_needs_mask(
                        j, kb, qs, ks, block_q, block_k, wq, wk)
                    pl.when(jnp.logical_and(live, crossing))(
                        lambda _g=_go: _g(True))
                    pl.when(jnp.logical_and(
                        live, jnp.logical_not(crossing)))(
                        lambda _g=_go: _g(False))

    if causal:
        unmasked = j * block_q >= (kb + 1) * block_k - 1
        on = kb <= last_kb
        pl.when(jnp.logical_and(on, unmasked))(lambda: _block(False))
        pl.when(jnp.logical_and(on, jnp.logical_not(unmasked)))(
            _diag_block)
    else:
        _block(False)

    @pl.when(kb == last_kb)
    def _finalize():
        if S == 1:
            dq_ref[0] = dq_scr[0].astype(dq_ref.dtype)
        else:
            dq_ref[0] = jnp.concatenate(
                [dq_scr[sh] for sh in range(S)], axis=-1
            ).astype(dq_ref.dtype)


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

    wq = _pick_block(block_q, DIAG_W)
    wk = _pick_block(block_k, DIAG_W)

    def _delta(sh, rows, do, o):
        """delta = rowsum(do*o) for one sub-head's q rows — computed once
        per (sub-head, row group), NOT per k sub-tile."""
        d_row = jnp.sum(
            do[rows].astype(jnp.float32) * o[rows].astype(jnp.float32),
            axis=-1, keepdims=True)
        if dlse_ref is not None:
            d_row = d_row - dlse_ref[sh][rows][:, :1]
        return d_row

    def _sub(sh, rows, cols, k, v, q, do, delta, masked, q0, k0):
        """One (q-rows, k-cols) sub-tile of the dk/dv math: accumulates
        into the k-row slices of the scratch accumulators."""
        lse = lse_ref[sh]
        s = jax.lax.dot_general(
            q[rows], k[cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if masked:
            shape = (s.shape[0], s.shape[1])
            q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[rows][:, :1])
        dv_scr[sh, cols] += jax.lax.dot_general(
            p.astype(do.dtype), do[rows], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do[rows], v[cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, :1]) * sm_scale).astype(q.dtype)
        dk_scr[sh, cols] += jax.lax.dot_general(
            ds, q[rows], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _views(sh):
        sl = slice(sh * d, (sh + 1) * d)
        return (k_ref[0][:, sl], v_ref[0][:, sl], q_ref[0][:, sl],
                do_ref[0][:, sl], o_ref[0][:, sl])

    def _block(masked):
        for sh in range(S):
            k, v, q, do, o = _views(sh)
            delta = _delta(sh, slice(None), do, o)
            _sub(sh, slice(None), slice(None), k, v, q, do, delta, masked,
                 jq * block_q, kb * block_k)

    def _diag_block():
        for sh in range(S):
            k, v, q, do, o = _views(sh)
            for qs in range(block_q // wq):
                rows = slice(qs * wq, (qs + 1) * wq)
                delta = _delta(sh, rows, do, o)
                for ks in range(block_k // wk):
                    cols = slice(ks * wk, (ks + 1) * wk)

                    def _go(masked, sh=sh, rows=rows, cols=cols, qs=qs,
                            ks=ks, k=k, v=v, q=q, do=do, delta=delta):
                        _sub(sh, rows, cols, k, v, q, do, delta, masked,
                             jq * block_q + qs * wq,
                             kb * block_k + ks * wk)

                    live = _diag_subtile_live(jq, kb, qs, ks, block_q,
                                              block_k, wq, wk)
                    crossing = _diag_subtile_needs_mask(
                        jq, kb, qs, ks, block_q, block_k, wq, wk)
                    pl.when(jnp.logical_and(live, crossing))(
                        lambda _g=_go: _g(True))
                    pl.when(jnp.logical_and(
                        live, jnp.logical_not(crossing)))(
                        lambda _g=_go: _g(False))

    if causal:
        # q block jq touches k block kb iff its last row is at/below the
        # block diagonal: (jq+1)*bq - 1 >= kb*bk
        on = jq >= (kb * block_k) // block_q
        unmasked = jq * block_q >= (kb + 1) * block_k - 1
        pl.when(jnp.logical_and(on, unmasked))(lambda: _block(False))
        pl.when(jnp.logical_and(on, jnp.logical_not(unmasked)))(
            _diag_block)
    else:
        _block(False)

    @pl.when(jq == nq - 1)
    def _finalize():
        if S == 1:
            dk_ref[0] = dk_scr[0].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[0].astype(dv_ref.dtype)
        else:
            dk_ref[0] = jnp.concatenate(
                [dk_scr[sh] for sh in range(S)], axis=-1
            ).astype(dk_ref.dtype)
            dv_ref[0] = jnp.concatenate(
                [dv_scr[sh] for sh in range(S)], axis=-1
            ).astype(dv_ref.dtype)


def _bwd_fused_kernel(*refs, sm_scale, causal, block_q, block_k, nq,
                      has_dlse, sub_heads):
    """Single-pass backward: grid (bh, k-blocks, q-blocks), q innermost.
    Computes the s/p tile ONCE per (k, q) block pair (the split dq + dkv
    kernels each recompute it — 7 block matmuls per pair vs 5 here) and
    emits dk/dv via VMEM accumulators plus dq as per-k-block partials
    ``dq_part[kb]`` that the caller reduces over kb.  Used when the
    partial buffer is small (nk grows with t; the split kernels remain
    the long-context path)."""
    import jax.experimental.pallas as pl

    if has_dlse:
        (k_ref, v_ref, q_ref, do_ref, o_ref, lse_ref, dlse_ref,
         dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr, dqp_scr) = refs
    else:
        (k_ref, v_ref, q_ref, do_ref, o_ref, lse_ref,
         dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr, dqp_scr) = refs
        dlse_ref = None

    kb = pl.program_id(1)
    jq = pl.program_id(2)
    S = sub_heads
    d = q_ref.shape[-1] // S
    wq = _pick_block(block_q, DIAG_W)
    wk = _pick_block(block_k, DIAG_W)

    @pl.when(jq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    def _views(sh):
        sl = slice(sh * d, (sh + 1) * d)
        return (k_ref[0][:, sl], v_ref[0][:, sl], q_ref[0][:, sl],
                do_ref[0][:, sl], o_ref[0][:, sl])

    def _block(masked):
        dqps = []
        for sh in range(S):
            k, v, q, do, o = _views(sh)
            lse = lse_ref[sh]
            delta = jnp.sum(
                do.astype(jnp.float32) * o.astype(jnp.float32),
                axis=-1, keepdims=True)
            if dlse_ref is not None:
                delta = delta - dlse_ref[sh][:, :1]
            bq = q.shape[0]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if masked:
                q_pos = jq * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 0)
                k_pos = kb * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 1)
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            p = jnp.exp(s - lse[:, :1])
            dv_scr[sh] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, :1]) * sm_scale).astype(q.dtype)
            dk_scr[sh] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dqps.append(jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        dqp_ref[0, 0] = (
            dqps[0] if S == 1 else jnp.concatenate(dqps, axis=-1)
        ).astype(dqp_ref.dtype)

    def _diag_block():
        # triangular straddling cell: dq partials accumulate in the f32
        # dqp scratch across live sub-tiles (skipped sub-tiles leave
        # their zeros), then one store; dk/dv accumulate into the k-row
        # slices of their scratches exactly like the split kernel
        dqp_scr[...] = jnp.zeros_like(dqp_scr[...])
        for sh in range(S):
            k, v, q, do, o = _views(sh)
            for qs in range(block_q // wq):
                rows = slice(qs * wq, (qs + 1) * wq)
                # delta once per (sub-head, row group), not per k sub-tile
                delta0 = jnp.sum(
                    do[rows].astype(jnp.float32)
                    * o[rows].astype(jnp.float32),
                    axis=-1, keepdims=True)
                if dlse_ref is not None:
                    delta0 = delta0 - dlse_ref[sh][rows][:, :1]
                for ks in range(block_k // wk):
                    cols = slice(ks * wk, (ks + 1) * wk)

                    def _go(masked, sh=sh, rows=rows, cols=cols, qs=qs,
                            ks=ks, k=k, v=v, q=q, do=do, delta=delta0):
                        lse = lse_ref[sh]
                        s = jax.lax.dot_general(
                            q[rows], k[cols], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
                        if masked:
                            shape = (s.shape[0], s.shape[1])
                            q_pos = (jq * block_q + qs * wq
                                     + jax.lax.broadcasted_iota(
                                         jnp.int32, shape, 0))
                            k_pos = (kb * block_k + ks * wk
                                     + jax.lax.broadcasted_iota(
                                         jnp.int32, shape, 1))
                            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
                        p = jnp.exp(s - lse[rows][:, :1])
                        dv_scr[sh, cols] += jax.lax.dot_general(
                            p.astype(do.dtype), do[rows],
                            (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        dp = jax.lax.dot_general(
                            do[rows], v[cols], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        ds = (p * (dp - delta[:, :1]) * sm_scale).astype(
                            q.dtype)
                        dk_scr[sh, cols] += jax.lax.dot_general(
                            ds, q[rows], (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        dqp_scr[sh, rows] += jax.lax.dot_general(
                            ds, k[cols], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

                    live = _diag_subtile_live(jq, kb, qs, ks, block_q,
                                              block_k, wq, wk)
                    crossing = _diag_subtile_needs_mask(
                        jq, kb, qs, ks, block_q, block_k, wq, wk)
                    pl.when(jnp.logical_and(live, crossing))(
                        lambda _g=_go: _g(True))
                    pl.when(jnp.logical_and(
                        live, jnp.logical_not(crossing)))(
                        lambda _g=_go: _g(False))
        dqp_ref[0, 0] = (
            dqp_scr[0] if S == 1 else jnp.concatenate(
                [dqp_scr[sh] for sh in range(S)], axis=-1)
        ).astype(dqp_ref.dtype)

    if causal:
        on = jq >= (kb * block_k) // block_q
        unmasked = jq * block_q >= (kb + 1) * block_k - 1
        pl.when(jnp.logical_and(on, unmasked))(lambda: _block(False))
        pl.when(jnp.logical_and(on, jnp.logical_not(unmasked)))(
            _diag_block)

        # skipped cells still own their dq_part block — zero it so the
        # caller's reduce over kb sees no garbage
        @pl.when(jnp.logical_not(on))
        def _zero():
            dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])
    else:
        _block(False)

    @pl.when(jq == nq - 1)
    def _finalize():
        if S == 1:
            dk_ref[0] = dk_scr[0].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[0].astype(dv_ref.dtype)
        else:
            dk_ref[0] = jnp.concatenate(
                [dk_scr[sh] for sh in range(S)], axis=-1
            ).astype(dk_ref.dtype)
            dv_ref[0] = jnp.concatenate(
                [dv_scr[sh] for sh in range(S)], axis=-1
            ).astype(dv_ref.dtype)


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

    kspec = pl.BlockSpec((1, block_k, width), lambda i, kb, jq: kix(i, kb))
    qspec = pl.BlockSpec((1, block_q, width), lambda i, kb, jq: qix(i, jq))
    qstat = pl.BlockSpec((S, block_q, 1), lambda i, kb, jq: (i, jq, 0))
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
                        pltpu.VMEM((S, block_k, d_sub), jnp.float32),
                        pltpu.VMEM((S, block_q, d_sub), jnp.float32)],
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
    kspec = pl.BlockSpec((1, block_k, width), lambda i, j, kb: kix(i, kb))
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

    kspec2 = pl.BlockSpec((1, block_k, width), lambda i, kb, jq: kix(i, kb))
    qspec2 = pl.BlockSpec((1, block_q, width), lambda i, kb, jq: qix(i, jq))
    qstat2 = pl.BlockSpec((S, block_q, 1), lambda i, kb, jq: (i, jq, 0))
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
    the GPT flagship, 8% of device time (RESULTS.md round 4) — never
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
