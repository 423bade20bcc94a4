"""Oracle contract of the flash-attention and fused cross-entropy op
classes (docs/kernels.md): every registered backend AVAILABLE on this
host is compared against the ``xla_ref`` reference within the documented
``ORACLE_TOL`` bounds (f32 + bf16, causal + non-causal, d_head 64/128,
grads through the custom-vjp); unavailable backends SKIP with the
registry's reason.  Within a backend the contract is bit-exact run to
run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kernel_cases import impl_or_skip, rel_err
from paddle_tpu import kernels
from paddle_tpu.kernels import get_kernel, oracle_tol


def _qkv(dt, d, b=1, t=128, h=2, seed=5):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(b, t, h, d)) * 0.5, dt)
                 for _ in range(3))


# -- oracle suite ------------------------------------------------------------

@pytest.mark.parametrize("backend", kernels.BACKENDS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("d_head", [64, 128])
def test_flash_oracle_parity(backend, dtype, causal, d_head):
    impl = impl_or_skip("flash_attention", backend)
    oracle = get_kernel("flash_attention", "xla_ref").impl
    q, k, v = _qkv(jnp.dtype(dtype), d_head)
    # explicit 64-wide blocks: t=128 then tiles 2x2, so the online-
    # softmax state actually carries across k blocks and causal cells
    # straddle the diagonal — default (1024-capped) blocks would make
    # this a degenerate single-block kernel
    got = impl.call(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = oracle.call(q, k, v, causal=causal)
    assert rel_err(got, ref) <= oracle_tol(
        "flash_attention", dtype, "fwd")


@pytest.mark.parametrize("backend", kernels.BACKENDS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_oracle_grads_through_custom_vjp(backend, dtype):
    impl = impl_or_skip("flash_attention", backend)
    oracle = get_kernel("flash_attention", "xla_ref").impl
    q, k, v = _qkv(jnp.dtype(dtype), 64, b=1)
    wgt = jnp.asarray(np.random.default_rng(7).normal(size=q.shape),
                      jnp.float32)

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=True, **kw).astype(jnp.float32) * wgt)

    got = jax.grad(loss(impl.call, block_q=64, block_k=64),
                   (0, 1, 2))(q, k, v)
    ref = jax.grad(loss(oracle.call), (0, 1, 2))(q, k, v)
    tol = oracle_tol("flash_attention", dtype, "grad")
    for a, r in zip(got, ref):
        assert rel_err(a, r) <= tol


@pytest.mark.parametrize("backend", kernels.BACKENDS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ce_oracle_parity_and_grads(backend, dtype):
    impl = impl_or_skip("fused_ce", backend)
    oracle = get_kernel("fused_ce", "xla_ref").impl
    rng = np.random.default_rng(9)
    n, d, vocab = 64, 32, 512
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.normal(size=(n, d)) * 0.3, dt)
    w = jnp.asarray(rng.normal(size=(d, vocab)) * 0.05, dt)
    y = jnp.asarray(rng.integers(0, vocab, (n,)), jnp.int32)
    # small explicit blocks so the vocab axis actually tiles (nv=4)
    # and the row axis splits — the online-softmax carry is the thing
    # under test (128 is the narrowest vocab tile the chip accepts)
    blocks = dict(block_n=32, block_v=128, block_v_fwd=128)
    assert rel_err(impl.call(x, w, y, **blocks),
                   oracle.call(x, w, y)) <= oracle_tol(
                       "fused_ce", dtype, "fwd")
    gvec = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    got = jax.grad(lambda x, w: jnp.sum(
        impl.call(x, w, y, **blocks) * gvec), (0, 1))(x, w)
    ref = jax.grad(lambda x, w: jnp.sum(oracle.call(x, w, y) * gvec),
                   (0, 1))(x, w)
    tol = oracle_tol("fused_ce", dtype, "grad")
    for a, r in zip(got, ref):
        assert rel_err(a, r) <= tol


@pytest.mark.parametrize("backend", ["pallas_tpu", "xla_ref"])
def test_bit_exact_run_to_run_within_backend(backend):
    impl = impl_or_skip("flash_attention", backend)
    q, k, v = _qkv(jnp.float32, 64, t=64)
    jf = jax.jit(lambda q, k, v: impl.call(q, k, v, causal=True,
                                           block_q=32, block_k=32))
    assert bool(jnp.array_equal(jf(q, k, v), jf(q, k, v)))
