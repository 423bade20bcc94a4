"""Fused softmax-cross-entropy head as Pallas TPU kernels.

The reference composes the LM head from a projection plus
``softmax_with_cross_entropy`` (``paddle/operators/
softmax_with_cross_entropy_op.cc``), which materializes the full
``[tokens, vocab]`` logits — at the GPT flagship shape (32k tokens x 32k
vocab) that is ~2 GiB of bf16 logits plus the saved softmax, all HBM
traffic.  This kernel fuses projection -> log-softmax -> NLL the flash
way: the vocab axis is tiled, logit tiles live only in VMEM, an online
max/sum carries the softmax state across vocab tiles, and the label's
logit is picked up by an iota==label select in the visited tile.  HBM
residual is O(tokens) — one f32 lse per token, stored compactly (narrow
[n, 1] kernel output, squeezed to 1-D; same convention as
pallas_attention.py) — never O(tokens x vocab).

Backward mirrors flash: two Pallas kernels recompute the probability
tiles from the saved lse — dx (row-major grid, vocab innermost,
accumulating ``ds @ W^T`` in VMEM) and dW (vocab-major grid, rows
innermost, accumulating ``X^T @ ds``), with ``ds = (p - onehot) * g``.
MXU feeds stay in the input dtype (bf16 in = 2x the f32 MXU rate);
softmax state and accumulators are f32.

Layout: x [N, d] activations, w [d, v] head weight, labels [N] int.
Rows with out-of-range labels (e.g. ignore_index -1) produce a finite
garbage loss that callers mask out; their gradients vanish because the
masked loss contributes a zero cotangent.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.ad_checkpoint import checkpoint_name

from ..analysis.jaxpr_tools import KERNEL_RESIDUAL_TAG
from ..core.registry import register_op
from .pallas_attention import _pick_block

LANES = 128  # Mosaic min lane tile; per-row stats are lane-replicated


def _pick_vocab_block(v, cap):
    """The largest vocab tile <= ``cap`` the TPU lowering accepts: the
    whole vocabulary, or a multiple of the 128-lane tile that divides it
    (interpret mode would take any divisor; the chip refuses them)."""
    if v <= cap:
        return v
    for bv in range(cap - cap % LANES, 0, -LANES):
        if v % bv == 0:
            return bv
    raise ValueError(
        f"fused_softmax_ce_head: vocab={v} has no tile <= {cap} that is "
        f"a multiple of {LANES} and divides it — use a vocabulary that "
        f"is a multiple of {LANES}, or the unfused "
        f"softmax_with_cross_entropy head")


def _ce_fwd_kernel(x_ref, w_ref, y_ref, loss_ref, lse_ref,
                   m_scr, l_scr, pick_scr, *, block_v, nv):
    """One (row-block, vocab-block) grid cell; vocab is the innermost grid
    axis so online-softmax state carries across vocab tiles in VMEM."""
    import jax.experimental.pallas as pl

    jv = pl.program_id(1)

    @pl.when(jv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        pick_scr[...] = jnp.zeros_like(pick_scr[...])

    x = x_ref[...]                      # [bn, d] input dtype
    w = w_ref[...]                      # [d, bv]
    s = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [bn, bv] f32
    m_prev = m_scr[...]
    m2 = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m2)
    p = jnp.exp(s - m2[:, :1])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m2
    y = y_ref[...]                      # [bn, 1] int32
    col = jv * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    pick_scr[...] += jnp.sum(
        jnp.where(col == y, s, 0.0), axis=-1, keepdims=True)

    @pl.when(jv == nv - 1)
    def _finalize():
        lse = m_scr[...] + jnp.log(l_scr[...])
        lse_ref[...] = lse[:, :1]
        loss_ref[...] = (lse - pick_scr[...])[:, :1]


def _ce_dx_kernel(x_ref, w_ref, y_ref, lse_ref, g_ref, dx_ref, dx_scr,
                  *, block_v, nv):
    """dx: grid (row-blocks, vocab-blocks), vocab innermost; recompute the
    probability tile from lse, ds = (p - onehot) * g, dx += ds @ W^T."""
    import jax.experimental.pallas as pl

    jv = pl.program_id(1)

    @pl.when(jv == 0)
    def _init():
        dx_scr[...] = jnp.zeros_like(dx_scr[...])

    x = x_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    p = jnp.exp(s - lse_ref[...][:, :1])
    col = jv * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    onehot = (col == y_ref[...]).astype(jnp.float32)
    ds = ((p - onehot) * g_ref[...][:, :1]).astype(w.dtype)
    dx_scr[...] += jax.lax.dot_general(
        ds, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(jv == nv - 1)
    def _finalize():
        dx_ref[...] = dx_scr[...].astype(dx_ref.dtype)


def _ce_dw_kernel(x_ref, w_ref, y_ref, lse_ref, g_ref, dw_ref, dw_scr,
                  *, block_v, nn):
    """dW: grid (vocab-blocks, row-blocks), rows innermost; dW += X^T @ ds
    accumulated across row tiles in VMEM."""
    import jax.experimental.pallas as pl

    jv = pl.program_id(0)
    jn = pl.program_id(1)

    @pl.when(jn == 0)
    def _init():
        dw_scr[...] = jnp.zeros_like(dw_scr[...])

    x = x_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    p = jnp.exp(s - lse_ref[...][:, :1])
    col = jv * s.shape[1] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    onehot = (col == y_ref[...]).astype(jnp.float32)
    ds = ((p - onehot) * g_ref[...][:, :1]).astype(x.dtype)
    dw_scr[...] += jax.lax.dot_general(
        x, ds, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(jn == nn - 1)
    def _finalize():
        dw_ref[...] = dw_scr[...].astype(dw_ref.dtype)


def _ce_fwd(x, w, y, block_n, block_v, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    v = w.shape[1]
    bn = _pick_block(n, block_n)
    bv = _pick_vocab_block(v, block_v)
    nv = v // bv
    y2 = y.reshape(n, 1)

    loss, lse = pl.pallas_call(
        functools.partial(_ce_fwd_kernel, block_v=bv, nv=nv),
        grid=(n // bn, nv),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, jv: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, jv: (0, jv)),
            pl.BlockSpec((bn, 1), lambda i, jv: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, jv: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, jv: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, LANES), jnp.float32),  # m
            pltpu.VMEM((bn, LANES), jnp.float32),  # l
            pltpu.VMEM((bn, LANES), jnp.float32),  # picked label logit
        ],
        interpret=interpret,
        name="fused_ce_fwd",
    )(x, w, y2)
    # squeeze to 1-D immediately: the [n, 1] kernel buffers get tile-
    # padded to 128 lanes by XLA's layout; the 1-D forms are compact
    return loss[:, 0], lse[:, 0]


def _ce_bwd(x, w, y, lse, g, block_n, block_v, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    v = w.shape[1]
    bn = _pick_block(n, block_n)
    bv = _pick_vocab_block(v, block_v)
    nn_ = n // bn
    nv = v // bv
    y2 = y.reshape(n, 1)
    lse = lse.reshape(n, 1)
    g2 = g.astype(jnp.float32).reshape(n, 1)

    xspec = pl.BlockSpec((bn, d), lambda i, jv: (i, 0))
    wspec = pl.BlockSpec((d, bv), lambda i, jv: (0, jv))
    rstat = pl.BlockSpec((bn, 1), lambda i, jv: (i, 0))
    dx = pl.pallas_call(
        functools.partial(_ce_dx_kernel, block_v=bv, nv=nv),
        grid=(nn_, nv),
        in_specs=[xspec, wspec, rstat, rstat, rstat],
        out_specs=[xspec],
        out_shape=[jax.ShapeDtypeStruct((n, d), x.dtype)],
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32)],
        interpret=interpret,
        name="fused_ce_dx",
    )(x, w, y2, lse, g2)[0]

    xspec2 = pl.BlockSpec((bn, d), lambda jv, jn: (jn, 0))
    wspec2 = pl.BlockSpec((d, bv), lambda jv, jn: (0, jv))
    rstat2 = pl.BlockSpec((bn, 1), lambda jv, jn: (jn, 0))
    dw = pl.pallas_call(
        functools.partial(_ce_dw_kernel, block_v=bv, nn=nn_),
        grid=(nv, nn_),
        in_specs=[xspec2, wspec2, rstat2, rstat2, rstat2],
        out_specs=[pl.BlockSpec((d, bv), lambda jv, jn: (0, jv))],
        out_shape=[jax.ShapeDtypeStruct((d, v), w.dtype)],
        scratch_shapes=[pltpu.VMEM((d, bv), jnp.float32)],
        interpret=interpret,
        name="fused_ce_dw",
    )(x, w, y2, lse, g2)[0]
    return dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ce_core(x, w, y, blocks, interpret):
    loss, _ = _ce_fwd(x, w, y, blocks[0], blocks[2], interpret)
    return loss


def _ce_core_fwd(x, w, y, blocks, interpret):
    loss, lse = _ce_fwd(x, w, y, blocks[0], blocks[2], interpret)
    # kernel-residual tag (see ops/pallas_attention.py): a name-policy
    # checkpoint saves the O(tokens) lse instead of re-running the
    # O(tokens x vocab) forward kernel in the backward pass
    lse = checkpoint_name(lse, KERNEL_RESIDUAL_TAG)
    return loss, (x, w, y, lse)


def _ce_core_bwd(blocks, interpret, res, g):
    x, w, y, lse = res
    dx, dw = _ce_bwd(x, w, y, lse, g, blocks[0], blocks[1], interpret)
    return dx, dw, np.zeros(y.shape, jax.dtypes.float0)


_ce_core.defvjp(_ce_core_fwd, _ce_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ce_core_lse(x, w, y, blocks, interpret):
    """Like ``_ce_core`` but also returns the per-row lse, DIFFERENTIABLE
    through both outputs — the building block for vocab-sharded tensor
    parallelism, where each shard's (loss_s, lse_s) pair is merged by a
    cross-shard logsumexp (same pattern as flash_attention_with_lse for
    ring attention)."""
    return _ce_fwd(x, w, y, blocks[0], blocks[2], interpret)


def _ce_core_lse_fwd(x, w, y, blocks, interpret):
    loss, lse = _ce_fwd(x, w, y, blocks[0], blocks[2], interpret)
    lse = checkpoint_name(lse, KERNEL_RESIDUAL_TAG)
    return (loss, lse), (x, w, y, lse)


def _ce_core_lse_bwd(blocks, interpret, res, cts):
    x, w, y, lse = res
    g, glse = cts
    # loss = lse - picked, so with an extra lse cotangent glse the total
    # logits cotangent is (g + glse)*(p - onehot) + glse*onehot: the
    # first term is exactly the existing backward kernels run with
    # g' = g + glse; the onehot term is a rank-1-per-row correction
    # (dx += glse * W[:, y],  dW[:, y] += glse * x) done in plain JAX.
    g = g.astype(jnp.float32)
    glse = glse.astype(jnp.float32)
    dx, dw = _ce_bwd(x, w, y, lse, g + glse, blocks[0], blocks[1],
                     interpret)
    yi = y.astype(jnp.int32)
    dx = dx + (glse[:, None] * w[:, yi].T).astype(dx.dtype)
    dw = dw + (jnp.zeros(dw.shape, jnp.float32)
               .at[:, yi].add(x.T.astype(jnp.float32) * glse[None, :])
               ).astype(dw.dtype)
    return dx, dw, np.zeros(y.shape, jax.dtypes.float0)


_ce_core_lse.defvjp(_ce_core_lse_fwd, _ce_core_lse_bwd)


def _resolve_backend(backend):
    """One selection path (the kernel registry, docs/kernels.md) for
    both CE entry points — replaces the per-call-site
    ``interpret = jax.default_backend() != "tpu"`` fallback."""
    from ..kernels import resolve  # late: kernels imports this module

    kernel = resolve("fused_ce", backend)
    return kernel.backend, kernel.impl


def fused_softmax_ce_head_with_lse(x, w, labels, block_n=512,
                                   block_v=1024, interpret=None,
                                   block_v_fwd=2048, backend=None):
    """``fused_softmax_ce_head`` that ALSO returns the per-position lse
    (both ``[...]`` f32), differentiable through both — callers compose
    partial losses across vocab shards with a logsumexp merge
    (parallelism: see the fused_softmax_ce_head op's tp path)."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    n = 1
    for s in lead:
        n *= int(s)
    name, impl = _resolve_backend(backend)
    if name != "pallas_tpu":
        loss, lse = impl.call_with_lse(
            x.reshape(n, d), w, labels.reshape(n), block_n=block_n,
            block_v=block_v, block_v_fwd=block_v_fwd,
            interpret=interpret)
        return loss.reshape(lead), lse.reshape(lead)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bn, bv, bv_fwd = _auto_blocks(
        n, d, w.shape[1], x.dtype.itemsize, w.dtype.itemsize,
        int(block_n), int(block_v), int(block_v_fwd))
    loss, lse = _ce_core_lse(
        x.reshape(n, d), w, labels.reshape(n).astype(jnp.int32),
        (bn, bv, bv_fwd), bool(interpret))
    return loss.reshape(lead), lse.reshape(lead)


# per-kernel VMEM budget for the block chooser.  14 MB (of the 16 MB
# scoped limit) reproduces the hand-tuned flagship config exactly
# (bn=512, bv=1024, bv_fwd=2048 at d=768 bf16) while leaving headroom
# for Mosaic's own spills; larger d_model configs shrink to fit instead
# of dying in the Mosaic lowering with a raw VMEM-OOM.
VMEM_BUDGET = 14 << 20


def _vmem_est(kernel, bn, bv, d, ix, iw):
    """Rough per-grid-cell VMEM bytes: double-buffered input blocks +
    the f32 logits tile + kernel-specific accumulators/outputs."""
    inputs = 2 * (bn * d * ix + d * bv * iw)
    s_tile = bn * bv * 4
    if kernel == "fwd":
        extra = 3 * bn * LANES * 4
    elif kernel == "dx":
        extra = bn * d * 4 + bn * d * ix
    else:  # dw
        extra = d * bv * 4 + d * bv * iw
    return inputs + s_tile + extra


def _auto_blocks(n, d, v, ix, iw, block_n, block_v, block_v_fwd,
                 budget=None):
    """Shrink the (caller-capped) block sizes until every kernel's VMEM
    estimate fits the scoped budget.  Raises with an actionable message
    if even the minimum blocks cannot fit (enormous d_model)."""
    budget = budget or VMEM_BUDGET

    def fit(kernel, bn_cap, bv_cap):
        bn = _pick_block(n, bn_cap)
        bv_c = bv_cap
        while True:
            bv = _pick_vocab_block(v, bv_c)
            if _vmem_est(kernel, bn, bv, d, ix, iw) <= budget:
                return bn, bv
            if bv_c > 128:
                bv_c //= 2
                continue
            if bn > 8:
                bn = _pick_block(n, max(8, bn // 2))
                bv_c = bv_cap
                continue
            raise ValueError(
                f"fused_softmax_ce_head: no block config fits VMEM for "
                f"d_model={d}, vocab={v} ({kernel} kernel needs "
                f"{_vmem_est(kernel, bn, bv, d, ix, iw) >> 20} MB at the "
                f"minimum blocks, budget {budget >> 20} MB) — use the "
                f"unfused softmax_with_cross_entropy head for this shape")

    bn_f, bv_f = fit("fwd", block_n, block_v_fwd)
    bn_x, bv_x = fit("dx", block_n, block_v)
    bn_w, bv_w = fit("dw", block_n, block_v)
    # one bn for all kernels (the residual/stat blocks are shared)
    bn = min(bn_f, bn_x, bn_w)
    return bn, min(bv_x, bv_w), bv_f


def fused_softmax_ce_head(x, w, labels, block_n=512, block_v=1024,
                          interpret=None, block_v_fwd=2048, backend=None):
    """Fused projection + softmax cross-entropy: ``x [..., d]``,
    ``w [d, v]``, ``labels [...]`` int -> per-position NLL ``[...]`` f32,
    without ever materializing ``[..., v]`` logits in HBM (the xla_ref
    oracle backend materializes them — that is its point).
    Differentiable in x and w (custom VJP in every backend); routed
    through the kernel registry (docs/kernels.md) — ``backend`` picks
    pallas_tpu | xla_ref explicitly, None resolves env
    overrides then the platform auto order.

    Block args are UPPER bounds: the chooser shrinks them per kernel to
    fit scoped VMEM (the forward fits a wider vocab block than the
    backward kernels, whose accumulators + second input block OOM at
    bv=2048/d=768; measured fwd 10.8 -> 9.7 ms at the flagship shape
    with the split sizes), so d_model >= 1024 configs work instead of
    hitting a raw Mosaic VMEM error."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    n = 1
    for s in lead:
        n *= int(s)
    name, impl = _resolve_backend(backend)
    if name != "pallas_tpu":
        loss = impl.call(x.reshape(n, d), w, labels.reshape(n),
                         block_n=block_n, block_v=block_v,
                         block_v_fwd=block_v_fwd, interpret=interpret)
        return loss.reshape(lead)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bn, bv, bv_fwd = _auto_blocks(
        n, d, w.shape[1], x.dtype.itemsize, w.dtype.itemsize,
        int(block_n), int(block_v), int(block_v_fwd))
    loss = _ce_core(
        x.reshape(n, d), w, labels.reshape(n).astype(jnp.int32),
        (bn, bv, bv_fwd), bool(interpret))
    return loss.reshape(lead)


def fused_softmax_ce_head_reference(x, w, labels):
    """Dense reference (tests / tiny shapes): materializes logits."""
    logits = jnp.einsum("...d,dv->...v", x, w,
                        preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    lbl = labels.astype(jnp.int32)
    return -jnp.take_along_axis(logp, lbl[..., None], axis=-1)[..., 0]


@register_op("fused_softmax_ce_head")
def fused_softmax_ce_head_op(X, W, Label, block_n=512, block_v=1024,
                             block_v_fwd=2048, backend="", _ctx=None,
                             **_):
    backend = backend or None
    lbl = Label
    if lbl.ndim == X.ndim and lbl.shape[-1] == 1:
        lbl = lbl.reshape(lbl.shape[:-1])
    from ..parallel.mesh import axis_size
    from .pallas_attention import _ctx_mesh, _kernel_mesh

    blocks = dict(block_n=block_n, block_v=block_v,
                  block_v_fwd=block_v_fwd, backend=backend)
    tp = axis_size(_ctx_mesh(_ctx), "tp")
    vocab_tp = tp > 1 and W.shape[1] % tp == 0
    mesh, db = _kernel_mesh(_ctx, "fused_ce", backend, X.shape[0],
                            manual=vocab_tp)
    if mesh is None:
        return {"Loss": fused_softmax_ce_head(X, W, lbl, **blocks)[
            ..., None]}
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def local(x, w, y):
        if not vocab_tp:
            return fused_softmax_ce_head(x, w, y, **blocks)
        # Vocab-sharded tensor parallelism: each shard runs the fused
        # kernel over its vocab slice (labels localized by the shard
        # offset) and the global softmax is recovered by a cross-shard
        # logsumexp merge — the same online-softmax algebra the kernel
        # uses across vocab TILES, lifted to mesh shards:
        #   lse  = logsumexp_tp(lse_s)
        #   loss = lse - psum(in_shard ? (lse_s - loss_s) : 0)
        # Differentiable end to end (loss_s/lse_s carry the kernel's
        # custom VJP; the merge is plain JAX).
        vs = w.shape[1]
        off = jax.lax.axis_index("tp") * vs
        y = y.astype(jnp.int32)
        in_s = ((y >= off) & (y < off + vs))
        y_loc = jnp.clip(y - off, 0, vs - 1)
        loss_s, lse_s = fused_softmax_ce_head_with_lse(
            x, w, y_loc, **blocks)
        picked = jnp.where(in_s, lse_s - loss_s, 0.0)
        # the max shift is numerical stabilization only (it cancels
        # algebraically) — stop_gradient keeps the merge on psum's
        # differentiation path (pmax has no JVP rule)
        m = jax.lax.pmax(jax.lax.stop_gradient(lse_s), "tp")
        lse = jnp.log(jax.lax.psum(jnp.exp(lse_s - m), "tp")) + m
        return lse - jax.lax.psum(picked, "tp")

    xspec = P(*([db] + [None] * (X.ndim - 1)))
    lspec = P(*([db] + [None] * (lbl.ndim - 1)))
    loss = shard_map(
        local, mesh=mesh,
        in_specs=(xspec, P(None, "tp" if vocab_tp else None), lspec),
        out_specs=lspec, check_vma=False)(X, W, lbl)
    return {"Loss": loss[..., None]}


# -- kernel-registry registration (docs/kernels.md) --------------------------
# The Mosaic kernels above ARE the "pallas_tpu" backend of the fused_ce
# op class; impl convention is 2-D (x [n, d], w [d, v], labels [n]).
from ..kernels.registry import (
    pallas_tpu_availability as _pallas_tpu_availability,
    register_kernel as _register_kernel)


def _pallas_ce(x, w, labels, block_n=None, block_v=None,
               block_v_fwd=None, interpret=None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, d = x.shape
    bn, bv, bv_fwd = _auto_blocks(
        n, d, w.shape[1], x.dtype.itemsize, w.dtype.itemsize,
        int(block_n or 512), int(block_v or 1024),
        int(block_v_fwd or 2048))
    return _ce_core(x, w, labels.astype(jnp.int32), (bn, bv, bv_fwd),
                    bool(interpret))


def _pallas_ce_with_lse(x, w, labels, block_n=None, block_v=None,
                        block_v_fwd=None, interpret=None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, d = x.shape
    bn, bv, bv_fwd = _auto_blocks(
        n, d, w.shape[1], x.dtype.itemsize, w.dtype.itemsize,
        int(block_n or 512), int(block_v or 1024),
        int(block_v_fwd or 2048))
    return _ce_core_lse(x, w, labels.astype(jnp.int32),
                        (bn, bv, bv_fwd), bool(interpret))


class _CePallasTpu:
    call = staticmethod(_pallas_ce)
    call_with_lse = staticmethod(_pallas_ce_with_lse)


_register_kernel("fused_ce", "pallas_tpu", _CePallasTpu,
                 available=_pallas_tpu_availability)
