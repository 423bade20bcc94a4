"""Pure-XLA reference backend — the universal numerics oracle.

Shape-complete, first-class implementations of every registered op
class: flash attention (causal/non-causal, any d_head, packed layouts,
lse outputs) and the fused CE/LSE head (paged attention's block-scan
oracle lives with its kernel, ``kernels/paged_attention.py``).  No
``pallas_call`` ever appears in a program routed here
(``PADDLE_TPU_KERNEL_BACKEND=xla_ref`` runs the full GPT trainer path —
every ``memory_optimize`` policy — with zero Pallas calls in the
jaxpr; ``tests/test_kernels.py`` asserts it).

These are not test stubs: attention and the CE head carry the SAME
custom-VJP algebra as the Mosaic kernels (backward recomputed from the
saved ``(q, k, v, o, lse)`` / ``(x, w, y, lse)`` residual sets, tagged
``KERNEL_RESIDUAL_TAG`` so the offload name-policy keeps them), so the
memory_optimize contracts hold under this backend too — only the O(t^2)
probability matrix materializes, which is exactly what makes this the
oracle spelling: every sum is a single dense reduction with no tiling
reassociation.  Tolerances for the other backends against this one are
pinned in ``ORACLE_TOL`` (docs/kernels.md).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..analysis.jaxpr_tools import KERNEL_RESIDUAL_TAG
from .registry import register_kernel

NEG_INF = -1e30

# The cross-backend numerics contract (docs/kernels.md "Oracle
# contract"): max |backend - xla_ref| / max|xla_ref|, per op class and
# dtype, forward and grads.  Within one backend the contract is
# BIT-EXACT run-to-run (same compiled fn, same inputs -> identical
# bits; the oracle suite asserts both).  The bounds are set by the
# tiling reassociation the blocked backends introduce (f32) plus input
# rounding (bf16) — an O(1) logic/masking bug clears them by orders of
# magnitude.
ORACLE_TOL = {
    ("flash_attention", "float32"): {"fwd": 2e-4, "grad": 1e-3},
    ("flash_attention", "bfloat16"): {"fwd": 2e-2, "grad": 5e-2},
    ("fused_ce", "float32"): {"fwd": 2e-4, "grad": 1e-3},
    ("fused_ce", "bfloat16"): {"fwd": 2e-2, "grad": 5e-2},
    # paged attention is inference-only (no VJP): fwd bounds match
    # flash_attention — the same blocked online-softmax reassociation
    # against the same dense-softmax reference, per block chain
    ("paged_attention", "float32"): {"fwd": 2e-4, "grad": None},
    ("paged_attention", "bfloat16"): {"fwd": 2e-2, "grad": None},
    # the latent plane's kernel (``pool_v=None``) against the same dense
    # softmax: the same online-softmax reassociation, taken several
    # blocks an update, and the weights kept at float32 accuracy as
    # three bfloat16 pieces; the bounds are paged_attention's
    ("paged_latent_attention", "float32"): {"fwd": 2e-4, "grad": None},
    ("paged_latent_attention", "bfloat16"): {"fwd": 2e-2, "grad": None},
    # a wide window's chain walk against the dense spelling it replaces
    # on the chip: the flash kernels' blocked online softmax over the
    # gathered chain (float32: reassociation only; bfloat16: the scores
    # out of bfloat16 operands and ``p`` rounded once to bfloat16 for the
    # one pass that weighs the values, 2^-9 relative a weight, which is
    # what the dense einsum at the chip's default precision rounds too);
    # the bounds are paged_attention's
    ("chain_attention", "float32"): {"fwd": 2e-4, "grad": None},
    ("chain_attention", "bfloat16"): {"fwd": 2e-2, "grad": None},
    # the grouped matrix product is inference-only: the two backends
    # multiply the same rows by the same matrices and differ by the
    # order of one float32 sum over k (float32: a few ulp of a sum of k
    # products; bfloat16: one rounding of the result, 2^-8 relative)
    ("grouped_matmul", "float32"): {"fwd": 2e-4, "grad": None},
    ("grouped_matmul", "bfloat16"): {"fwd": 2e-2, "grad": None},
    # power retention is inference-only: the step kernel is float32
    # arithmetic in another order; the chunk kernel multiplies float32
    # operands as two bfloat16 pieces each (16 bits: 2e-5 relative)
    ("retention", "float32"): {"fwd": 2e-4, "grad": None},
    # Mamba-2's recurrence is inference-only and float32 whatever the
    # rows' dtype (the state, the decay and the update are float32 on
    # both sides): the step kernel sums ``S C`` down the sublanes in
    # another order than the oracle's lane sum, and the chunked form
    # reassociates a chunk's rows (the quadratic form, then the state)
    # against the scan: a few ulp of sums of ``N`` and of ``chunk_size``
    # products.  bfloat16 rows are upcast before anything is multiplied,
    # so the bound is float32's
    ("ssm", "float32"): {"fwd": 2e-4, "grad": None},
    ("ssm", "bfloat16"): {"fwd": 2e-4, "grad": None},
    # the gated delta rule the same way: float32 on both sides whatever
    # the rows' dtype; the step kernel takes ``Sb^T k`` and ``Sb^T q`` in
    # one pass (``o`` from ``Sb`` and ``u``, not from the written state)
    # and the WY form solves a tile's rows together against the scan
    ("delta_rule", "float32"): {"fwd": 2e-4, "grad": None},
    ("delta_rule", "bfloat16"): {"fwd": 2e-4, "grad": None},
    # a learned indexer's scores (no softmax: float32 sums of relu'd
    # products over the index heads) and the attention of the rows it
    # selects (one softmax over the gathered rows): inference-only, one
    # backend so far; a second one reassociates the head sum and the
    # softmax as paged_latent_attention's does, whose bounds these are
    ("index_scores", "float32"): {"fwd": 2e-4, "grad": None},
    ("index_scores", "bfloat16"): {"fwd": 2e-2, "grad": None},
    ("sparse_latent_attention", "float32"): {"fwd": 2e-4, "grad": None},
    ("sparse_latent_attention", "bfloat16"): {"fwd": 2e-2, "grad": None},
    # a K/V plane's block scores (a softmax a head over the compressed
    # keys) and the attention of the blocks they select: the oracle's
    # backend only; the walk of the selected table is paged_attention's
    ("block_scores", "float32"): {"fwd": 2e-4, "grad": None},
    ("block_scores", "bfloat16"): {"fwd": 2e-2, "grad": None},
    ("block_sparse_attention", "float32"): {"fwd": 2e-4, "grad": None},
    ("block_sparse_attention", "bfloat16"): {"fwd": 2e-2, "grad": None},
}


def oracle_tol(op_class, dtype, kind="fwd"):
    """The documented tolerance for comparing ``op_class`` outputs in
    ``dtype`` against this backend (``kind``: "fwd" | "grad")."""
    key = (op_class, str(jnp.dtype(dtype)))
    if key not in ORACLE_TOL:
        raise KeyError(f"no oracle tolerance documented for {key}")
    return ORACLE_TOL[key][kind]


# -- flash attention ---------------------------------------------------------

def _attn_fwd(q, k, v, sm_scale, causal):
    """Dense forward on [b, t, h, d]: returns (o [b, t_q, h, d] in the
    input dtype, lse [b, h, t_q] f32).  Same numerics conventions as the
    kernels: f32 scores/softmax state, NEG_INF causal mask, output
    normalized once at the end."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        t_q, t_k = s.shape[-2:]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    l_bqh = jnp.moveaxis(l_safe[..., 0], -1, 1)          # [b, q, h]
    o = (acc / l_bqh[..., None]).astype(q.dtype)
    lse = (m + jnp.log(l_safe))[..., 0]                  # [b, h, q]
    return o, lse


def _attn_bwd_math(q, k, v, o, lse, do, sm_scale, causal, dlse=None):
    """Backward recomputed from the flash residual contract
    ``(q, k, v, o, lse)`` — the same ds/delta algebra as the Mosaic
    backward kernels, spelled dense."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        t_q, t_k = s.shape[-2:]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse[..., None])                      # [b, h, q, k]
    delta = jnp.einsum("bqhd,bqhd->bhq", do.astype(jnp.float32),
                       o.astype(jnp.float32))
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", do, v,
                    preferred_element_type=jnp.float32)
    ds = p * (dp - delta[..., None]) * sm_scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds,
                    k.astype(jnp.float32)).astype(q.dtype)
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds,
                    q.astype(jnp.float32)).astype(k.dtype)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p,
                    do.astype(jnp.float32)).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attn_core(q, k, v, sm_scale, causal):
    o, _ = _attn_fwd(q, k, v, sm_scale, causal)
    return o


def _attn_core_fwd(q, k, v, sm_scale, causal):
    o, lse = _attn_fwd(q, k, v, sm_scale, causal)
    # the flash residual contract, backend-invariant: a name-policy
    # checkpoint (memory_optimize offload) keeps these instead of
    # re-running the forward in the backward pass
    o = checkpoint_name(o, KERNEL_RESIDUAL_TAG)
    lse = checkpoint_name(lse, KERNEL_RESIDUAL_TAG)
    return o, (q, k, v, o, lse)


def _attn_core_bwd(sm_scale, causal, res, do):
    q, k, v, o, lse = res
    return _attn_bwd_math(q, k, v, o, lse, do, sm_scale, causal)


_attn_core.defvjp(_attn_core_fwd, _attn_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attn_core_lse(q, k, v, sm_scale, causal):
    return _attn_fwd(q, k, v, sm_scale, causal)


def _attn_core_lse_fwd(q, k, v, sm_scale, causal):
    o, lse = _attn_fwd(q, k, v, sm_scale, causal)
    o = checkpoint_name(o, KERNEL_RESIDUAL_TAG)
    lse = checkpoint_name(lse, KERNEL_RESIDUAL_TAG)
    return (o, lse), (q, k, v, o, lse)


def _attn_core_lse_bwd(sm_scale, causal, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _attn_bwd_math(q, k, v, o, lse, do, sm_scale, causal,
                          dlse=dlse)


_attn_core_lse.defvjp(_attn_core_lse_fwd, _attn_core_lse_bwd)


def flash_attention(q, k, v, causal=False, sm_scale=None, block_q=None,
                    block_k=None, interpret=None):
    """The 4-D entry point (``q/k/v [b, t, h, d]``).  Block sizes and
    ``interpret`` are accepted for signature parity with the kernel
    backends and ignored — XLA owns the tiling here."""
    del block_q, block_k, interpret
    d = q.shape[-1]
    sm_scale = d ** -0.5 if sm_scale is None else sm_scale
    return _attn_core(q, k, v, float(sm_scale), bool(causal))


def flash_attention_with_lse(q, k, v, causal=False, sm_scale=None,
                             block_q=None, block_k=None, interpret=None):
    """Returns ``(o [b, t, h, d], lse [b, h, t])``, differentiable
    through both — the ring-attention merge building block."""
    del block_q, block_k, interpret
    d = q.shape[-1]
    sm_scale = d ** -0.5 if sm_scale is None else sm_scale
    return _attn_core_lse(q, k, v, float(sm_scale), bool(causal))


def flash_attention_packed(q, k, v, n_head, causal=False, sm_scale=None,
                           block_q=None, block_k=None, interpret=None):
    """The packed layout (``[b, t, h*d]``) is shape-complete here for
    ANY head width: the head split is a free reshape (no data movement
    in XLA's row-major layout), so no geometry restriction applies."""
    del block_q, block_k, interpret
    b, t, hd = q.shape
    if hd % n_head:
        raise ValueError(
            f"feature dim {hd} not divisible by n_head {n_head}")
    d = hd // n_head
    sm_scale = d ** -0.5 if sm_scale is None else sm_scale
    r4 = lambda x: x.reshape(b, x.shape[1], n_head, d)
    o = _attn_core(r4(q), r4(k), r4(v), float(sm_scale), bool(causal))
    return o.reshape(b, t, hd)


# -- fused CE / LSE head -----------------------------------------------------

def _ce_fwd(x, w, y):
    """Dense forward on ``x [n, d]``, ``w [d, v]``, ``y [n]`` int32:
    returns (loss [n] f32, lse [n] f32).  The [n, v] logits materialize
    — that is the point of the oracle spelling."""
    s = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [n, v]
    m = jnp.max(s, axis=-1, keepdims=True)
    l = jnp.sum(jnp.exp(s - m), axis=-1, keepdims=True)
    lse = (m + jnp.log(l))[:, 0]
    # out-of-range labels (ignore_index) produce finite garbage the
    # caller masks, exactly like the kernel's iota==label pick
    yc = jnp.clip(y, 0, s.shape[1] - 1)
    picked = jnp.take_along_axis(s, yc[:, None], axis=-1)[:, 0]
    in_range = (y >= 0) & (y < s.shape[1])
    picked = jnp.where(in_range, picked, 0.0)
    return lse - picked, lse


def _ce_bwd_math(x, w, y, lse, g_eff, g_pick):
    """ds = p * g_eff - onehot * g_pick, then dx/dW — the kernel's
    backward algebra dense.  ``g_eff`` multiplies the softmax term
    (g + glse for the lse variant), ``g_pick`` the picked-logit term
    (always g)."""
    s = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    p = jnp.exp(s - lse[:, None])
    col = jnp.arange(s.shape[1], dtype=jnp.int32)[None, :]
    onehot = (col == y[:, None]).astype(jnp.float32)
    ds = p * g_eff[:, None] - onehot * g_pick[:, None]
    dx = jax.lax.dot_general(
        ds, w.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)
    dw = jax.lax.dot_general(
        x.astype(jnp.float32), ds, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(w.dtype)
    return dx, dw


@jax.custom_vjp
def _ce_core(x, w, y):
    loss, _ = _ce_fwd(x, w, y)
    return loss


def _ce_core_fwd(x, w, y):
    loss, lse = _ce_fwd(x, w, y)
    lse = checkpoint_name(lse, KERNEL_RESIDUAL_TAG)
    return loss, (x, w, y, lse)


def _ce_core_bwd(res, g):
    x, w, y, lse = res
    g = g.astype(jnp.float32)
    dx, dw = _ce_bwd_math(x, w, y, lse, g, g)
    return dx, dw, np.zeros(y.shape, jax.dtypes.float0)


_ce_core.defvjp(_ce_core_fwd, _ce_core_bwd)


@jax.custom_vjp
def _ce_core_lse(x, w, y):
    return _ce_fwd(x, w, y)


def _ce_core_lse_fwd(x, w, y):
    loss, lse = _ce_fwd(x, w, y)
    lse = checkpoint_name(lse, KERNEL_RESIDUAL_TAG)
    return (loss, lse), (x, w, y, lse)


def _ce_core_lse_bwd(res, cts):
    x, w, y, lse = res
    g, glse = cts
    g = g.astype(jnp.float32)
    glse = glse.astype(jnp.float32)
    # loss = lse - picked: the total logits cotangent is
    # p*(g + glse) - onehot*g (one fused ds — algebraically identical
    # to pallas_ce's run-with-g'=g+glse plus rank-1 onehot correction)
    dx, dw = _ce_bwd_math(x, w, y, lse, g + glse, g)
    return dx, dw, np.zeros(y.shape, jax.dtypes.float0)


_ce_core_lse.defvjp(_ce_core_lse_fwd, _ce_core_lse_bwd)


def fused_softmax_ce_head(x, w, labels, block_n=None, block_v=None,
                          block_v_fwd=None, interpret=None):
    """``x [n, d]``, ``w [d, v]``, ``labels [n]`` -> NLL ``[n]`` f32.
    Block args are accepted for signature parity and ignored."""
    del block_n, block_v, block_v_fwd, interpret
    return _ce_core(x, w, labels.astype(jnp.int32))


def fused_softmax_ce_head_with_lse(x, w, labels, block_n=None,
                                   block_v=None, block_v_fwd=None,
                                   interpret=None):
    del block_n, block_v, block_v_fwd, interpret
    return _ce_core_lse(x, w, labels.astype(jnp.int32))


# -- registration ------------------------------------------------------------

class _FlashXlaRef:
    call = staticmethod(flash_attention)
    call_with_lse = staticmethod(flash_attention_with_lse)
    call_packed = staticmethod(flash_attention_packed)


class _CeXlaRef:
    call = staticmethod(fused_softmax_ce_head)
    call_with_lse = staticmethod(fused_softmax_ce_head_with_lse)


register_kernel("flash_attention", "xla_ref", _FlashXlaRef)
register_kernel("fused_ce", "xla_ref", _CeXlaRef)
