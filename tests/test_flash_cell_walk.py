"""The flash kernels' cell walk (``ops/pallas_attention.py``,
``_walk_cell``) at every kind of call that reaches it, fused and split
backward, against the reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas_attention import attention_reference


# The cell walk (``_walk_cell``) at every kind of call that reaches it:
# (api, t_q, t_k, heads, head width, dtype, causal, block_q, block_k,
# strip height)
WALKS = {
    "causal_several_strips": ("4d", 128, 128, 2, 16, "float32", True,
                              64, 64, 16),
    "causal_one_strip": ("4d", 128, 128, 2, 16, "float32", True, 64, 64,
                         64),
    "noncausal": ("4d", 128, 128, 2, 16, "float32", False, 64, 64, 16),
    "packed_d128_bf16_strips": ("packed", 128, 128, 2, 128, "bfloat16",
                                True, 64, 64, 16),
    "paired_d64_strips": ("packed", 128, 128, 2, 64, "float32", True, 64,
                          64, 16),
    "tq_gt_tk_causal": ("4d", 128, 64, 2, 16, "float32", True, 32, 32, 8),
    "tq_lt_tk_causal": ("4d", 64, 128, 2, 16, "float32", True, 32, 32, 8),
    "block_q_lt_block_k": ("4d", 128, 128, 2, 16, "float32", True, 32, 64,
                           16),
    "block_q_gt_block_k": ("4d", 128, 128, 2, 16, "float32", True, 64, 32,
                           16),
    "dlse_path": ("lse", 128, 128, 2, 16, "float32", True, 64, 64, 16),
    "dlse_path_noncausal": ("lse", 128, 128, 2, 16, "float32", False, 64,
                            64, 16),
}


@pytest.mark.parametrize("backward", ["fused", "split"])
@pytest.mark.parametrize("walk", list(WALKS))
def test_cell_walk_matches_reference(walk, backward, monkeypatch):
    """Values and gradients of the strip walk against the dense
    reference, through the fused backward and through the split dq / dkv
    kernels (the partial budget forced to 0)."""
    import paddle_tpu.ops.pallas_attention as pa

    api, tq, tk, h, d, dtype, causal, bq, bk, strip = WALKS[walk]
    monkeypatch.setattr(pa, "DIAG_W", strip)
    if backward == "split":
        monkeypatch.setattr(pa, "FUSED_BWD_PARTIAL_BYTES", 0)
    rng = np.random.default_rng(29)
    qf, kf, vf = (jnp.asarray(rng.normal(size=(1, t, h, d)) * 0.5,
                              jnp.float32) for t in (tq, tk, tk))
    kw = dict(causal=causal, block_q=bq, block_k=bk, interpret=True)

    def dense(q, k, v):
        o = attention_reference(q, k, v, causal=causal)
        if api != "lse":
            return o
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((tq, tk), bool)), s, -1e30)
        return o, jax.scipy.special.logsumexp(s, axis=-1)

    def flash(q, k, v):
        if api == "4d":
            return pa.flash_attention(q, k, v, **kw)
        if api == "lse":
            return pa.flash_attention_with_lse(q, k, v, **kw)
        pk = lambda x: x.reshape(1, x.shape[1], h * d)
        return pa.flash_attention_packed(pk(q), pk(k), pk(v), h,
                                         **kw).reshape(q.shape)

    def loss(fn):
        def f(q, k, v):
            out = fn(q, k, v)
            o, lse = out if api == "lse" else (out, jnp.zeros(()))
            o = o.astype(jnp.float32)
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(jnp.sin(lse))
        return f

    args = tuple(x.astype(dtype) for x in (qf, kf, vf))
    tol = dict(atol=2e-5, rtol=2e-4) if dtype == "float32" else dict(
        atol=4e-2, rtol=4e-2)
    # a program each (called eagerly the reference and the loss run op
    # by op, each op compiled for this case's shapes)
    got, ref = jax.jit(flash)(*args), jax.jit(dense)(qf, kf, vf)
    for a, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(r), **tol)
    g_got = jax.jit(jax.grad(loss(flash), (0, 1, 2)))(*args)
    g_ref = jax.jit(jax.grad(loss(dense), (0, 1, 2)))(qf, kf, vf)
    for a, r, nm in zip(g_got, g_ref, "qkv"):
        scale = max(float(jnp.abs(r).max()), 1.0)
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale, np.asarray(r) / scale,
            err_msg=f"grad wrt {nm}", **tol)
