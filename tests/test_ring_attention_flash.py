"""ring_attention(impl='flash') on the 8-device sp mesh: the Pallas
inner-block path against the global reference, values and gradients,
causal and not, float32 and bfloat16 (interpret-mode kernels on the
CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest


def test_ring_attention_flash_impl_matches_dense():
    """ring_attention(impl='flash'): the Pallas inner-block path must match
    the dense-impl ring AND the global reference, values and grads, causal
    and not (8-device sp mesh, interpret-mode kernels on CPU)."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.ring_attention import ring_attention
    from paddle_tpu.ops.pallas_attention import attention_reference

    sp = 8
    mesh = make_mesh({"sp": sp}, devices=jax.devices()[:sp])
    b, t, h, d = 2, 8 * 16, 2, 8
    rng_ = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng_.randn(b, t, h, d) * 0.5, jnp.float32)
               for _ in range(3))

    for causal in (False, True):
        def flash(q, k, v):
            return ring_attention(q, k, v, mesh, causal=causal,
                                  impl="flash", block_q=16, block_k=16)

        # ONE program gives the values and the gradients of sum(o ** 2)
        # (every call of ring_attention shard_maps a new closure, which
        # the eager path runs op by op and compiles afresh)
        def both(q, k, v):
            o, pull = jax.vjp(flash, q, k, v)
            return o, pull(2 * o)

        o_flash, ga = jax.jit(both)(q, k, v)
        o_ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(o_flash), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)
        # bf16 inputs (the TPU configuration) must also run
        o_bf = jax.jit(flash)(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                              v.astype(jnp.bfloat16))
        np.testing.assert_allclose(
            np.asarray(o_bf.astype(jnp.float32)), np.asarray(o_ref),
            rtol=5e-2, atol=5e-2)
        with pytest.raises(ValueError, match="impl"):
            ring_attention(q, k, v, mesh, impl="falsh")

        gr = jax.grad(lambda q, k, v: jnp.sum(attention_reference(
            q, k, v, causal=causal) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a_, r in zip(ga, gr):
            np.testing.assert_allclose(np.asarray(a_), np.asarray(r),
                                       rtol=2e-3, atol=2e-4)
