"""Expert parallelism (ep) on the 8-device CPU mesh: the MoE all_to_all
dispatch == the dense per-token expert compute it approximates, every
token routed once nothing overflows, and gradients flow.  (The reference
has no expert parallelism: SURVEY §2.3 "TP/PP/CP/EP: ABSENT".)"""

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel.moe import init_moe_params, moe_ffn


class TestMoE:
    # ``moe_ffn`` called eagerly runs its shard_map op by op (seconds a
    # call on the CPU): the first two cases keep that path, the others
    # call it under ``jax.jit``, as a program does
    def _dense_reference(self, params, x, capacity):
        """Per-token top-2 expert compute with the same capacity rule,
        computed densely without any collective."""
        from paddle_tpu.parallel.moe import _top2_dispatch
        logits = x @ params["gate"]
        dispatch, combine, _ = _top2_dispatch(logits, capacity)
        expert_in = jnp.einsum("nec,nd->ecd", dispatch, x)
        h = jax.nn.relu(jnp.einsum("end,edf->enf", expert_in, params["w1"])
                        + params["b1"][:, None, :])
        y = jnp.einsum("enf,efd->end", h, params["w2"]) + params["b2"][:, None, :]
        return jnp.einsum("nec,ecd->nd", combine, y)

    def test_matches_dense_single_shard(self):
        # ep=1: the all_to_all is identity, so sharded == dense exactly.
        mesh = make_mesh({"ep": 1}, devices=jax.devices()[:1])
        d, f, e, n = 8, 16, 4, 32
        params = init_moe_params(jax.random.PRNGKey(0), e, d, f)
        x = jnp.asarray(np.random.RandomState(0).randn(n, d), jnp.float32)
        y, aux = moe_ffn(params, x, mesh, capacity_factor=2.0)
        cap = int(2.0 * n / e)
        want = self._dense_reference(params, x, cap)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        assert float(aux) > 0

    def test_multi_shard_finite_and_shaped(self):
        ep = 4
        mesh = make_mesh({"ep": ep}, devices=jax.devices()[:ep])
        d, f, e, n = 8, 16, 8, 64
        params = init_moe_params(jax.random.PRNGKey(1), e, d, f)
        x = jnp.asarray(np.random.RandomState(1).randn(n, d), jnp.float32)
        y, aux = moe_ffn(params, x, mesh, capacity_factor=2.0)
        assert y.shape == (n, d)
        assert np.isfinite(np.asarray(y)).all()
        # aux loss ~ O(1): perfectly balanced routing gives exactly 1.0
        assert 0.5 < float(aux) < 8.0

    def test_multi_shard_matches_dense(self):
        """ep=4, e=8 (e_local=2): with capacity high enough that no token
        drops, the all_to_all path must equal per-shard dense expert
        compute — guards the shard/expert axis ordering in the dispatch
        reshape."""
        ep = 4
        mesh = make_mesh({"ep": ep}, devices=jax.devices()[:ep])
        d, f, e, n = 8, 16, 8, 32
        params = init_moe_params(jax.random.PRNGKey(4), e, d, f)
        x = jnp.asarray(np.random.RandomState(5).randn(n, d), jnp.float32)
        cf = float(2 * e)  # local cap = cf*n_local/e = 2*n_local: no drops
        y, _ = jax.jit(lambda p, x: moe_ffn(p, x, mesh,
                                            capacity_factor=cf))(params, x)
        # dense reference shard by shard (capacity applies per token shard)
        n_local = n // ep
        cap = int(cf * n_local / e)
        wants = [
            self._dense_reference(
                params, x[i * n_local:(i + 1) * n_local], cap)
            for i in range(ep)
        ]
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(jnp.concatenate(wants)),
            rtol=1e-4, atol=1e-4)

    def test_high_capacity_token_conservation(self):
        """With capacity >= n every token is routed; combine weights sum
        to 1 so output magnitude is expert-mixture, not dropped."""
        ep = 2
        mesh = make_mesh({"ep": ep}, devices=jax.devices()[:ep])
        d, f, e, n = 4, 8, 2, 16
        params = init_moe_params(jax.random.PRNGKey(2), e, d, f)
        x = jnp.asarray(np.random.RandomState(2).randn(n, d), jnp.float32)
        y_lo, _ = jax.jit(lambda p, x: moe_ffn(
            p, x, mesh, capacity_factor=8.0))(params, x)
        y_hi, _ = jax.jit(lambda p, x: moe_ffn(
            p, x, mesh, capacity_factor=16.0))(params, x)
        # once nothing overflows, more capacity changes nothing
        np.testing.assert_allclose(np.asarray(y_lo), np.asarray(y_hi),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_flows(self):
        ep = 2
        mesh = make_mesh({"ep": ep}, devices=jax.devices()[:ep])
        d, f, e, n = 4, 8, 4, 16
        params = init_moe_params(jax.random.PRNGKey(3), e, d, f)
        x = jnp.asarray(np.random.RandomState(3).randn(n, d), jnp.float32)

        def loss(params):
            y, aux = moe_ffn(params, x, mesh, capacity_factor=4.0)
            return jnp.sum(y ** 2) + 0.01 * aux

        g = jax.jit(jax.grad(loss))(params)
        flat = jax.tree.leaves(g)
        assert all(np.isfinite(np.asarray(l)).all() for l in flat)
        assert any(float(jnp.abs(l).sum()) > 0 for l in flat)
