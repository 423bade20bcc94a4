"""Pipeline parallelism (pp) on the 8-device CPU mesh: the GPipe
microbatch pipeline == sequential stage application (fwd and grad), the
interleaved schedule, embedding and head inside the pipeline, and pp
composed with dp and with ring attention over sp.  (The reference has no
pipeline parallelism: SURVEY §2.3 "TP/PP/CP/EP: ABSENT"; expert
parallelism is ``tests/test_moe_ffn.py``, the flash ring
``tests/test_ring_attention_flash.py``.)"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel.pipeline import pipeline, stack_stage_params


def _stage_fn(params, h):
    return jnp.tanh(h @ params["w"] + params["b"])


def _make_stages(n, d, seed=0):
    rng = np.random.RandomState(seed)
    return [
        {"w": jnp.asarray(rng.randn(d, d).astype(np.float32) * 0.3),
         "b": jnp.asarray(rng.randn(d).astype(np.float32) * 0.1)}
        for _ in range(n)
    ]


class TestPipeline:
    def test_matches_sequential(self):
        pp, d, batch = 4, 16, 8
        mesh = make_mesh({"pp": pp}, devices=jax.devices()[:pp])
        stages = _make_stages(pp, d)
        x = jnp.asarray(np.random.RandomState(1).randn(batch, d),
                        jnp.float32)

        want = x
        for p in stages:
            want = _stage_fn(p, want)

        got = pipeline(_stage_fn, stack_stage_params(stages), x, mesh,
                       num_microbatches=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_microbatch_count_irrelevant(self):
        pp, d, batch = 2, 8, 12
        mesh = make_mesh({"pp": pp}, devices=jax.devices()[:pp])
        stages = _make_stages(pp, d, seed=3)
        sp = stack_stage_params(stages)
        x = jnp.asarray(np.random.RandomState(2).randn(batch, d), jnp.float32)
        o2 = pipeline(_stage_fn, sp, x, mesh, num_microbatches=2)
        o6 = pipeline(_stage_fn, sp, x, mesh, num_microbatches=6)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(o6),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_matches_sequential(self):
        pp, d, batch = 4, 8, 8
        mesh = make_mesh({"pp": pp}, devices=jax.devices()[:pp])
        stages = _make_stages(pp, d, seed=5)
        sp = stack_stage_params(stages)
        x = jnp.asarray(np.random.RandomState(4).randn(batch, d), jnp.float32)

        def loss_pipe(sp):
            return jnp.sum(pipeline(_stage_fn, sp, x, mesh,
                                    num_microbatches=4) ** 2)

        def loss_seq(sp):
            h = x
            for i in range(pp):
                h = _stage_fn(jax.tree.map(lambda l: l[i], sp), h)
            return jnp.sum(h ** 2)

        # one program each: eagerly the pipeline's shard_map runs op by op
        g_pipe = jax.jit(jax.grad(loss_pipe))(sp)
        g_seq = jax.jit(jax.grad(loss_seq))(sp)
        for a, b in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_seq)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_under_jit(self):
        pp, d, batch = 4, 8, 8
        mesh = make_mesh({"pp": pp}, devices=jax.devices()[:pp])
        sp = stack_stage_params(_make_stages(pp, d))
        x = jnp.ones((batch, d), jnp.float32)
        f = jax.jit(lambda sp, x: pipeline(_stage_fn, sp, x, mesh))
        out = f(sp, x)
        assert out.shape == (batch, d)
        assert np.isfinite(np.asarray(out)).all()


# ---- round 2: interleaved schedule + in-pipeline embed/head -------------

def _mlp_stage_r2(params, h):
    return h + jnp.tanh(h @ params["w"] + params["b"])


def _make_stages_r2(n, d, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), n)
    return [{"w": jax.random.normal(k, (d, d)) * 0.3,
             "b": jnp.full((d,), 0.01)} for k in ks]


@pytest.mark.parametrize("m", [4, 8])
def test_pipeline_interleaved_matches_sequential(m):
    """virtual_stages=2: 8 stages on a 4-device pp ring, every microbatch
    making 2 laps; output must equal the sequential 8-stage composition,
    and grads must match too."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.pipeline import pipeline, stack_stage_params

    pp, v, d, b = 4, 2, 8, 2 * m
    mesh = make_mesh({"pp": pp}, devices=jax.devices()[:pp])
    stages = _make_stages_r2(v * pp, d)
    sp = stack_stage_params(stages)
    x = jax.random.normal(jax.random.PRNGKey(9), (b, d))

    def seq(sp, x):
        h = x
        for s in range(v * pp):
            h = _mlp_stage_r2(jax.tree.map(lambda p: p[s], sp), h)
        return h

    got = jax.jit(lambda sp, x: pipeline(
        _mlp_stage_r2, sp, x, mesh, num_microbatches=m, virtual_stages=v))(
            sp, x)
    want = seq(sp, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    def loss_pipe(sp):
        out = pipeline(_mlp_stage_r2, sp, x, mesh, num_microbatches=m,
                       virtual_stages=v)
        return jnp.mean(out ** 2)

    def loss_seq(sp):
        return jnp.mean(seq(sp, x) ** 2)

    g1 = jax.jit(jax.grad(loss_pipe))(sp)
    g2 = jax.grad(loss_seq)(sp)
    for a, e in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=2e-4, atol=2e-5)


def test_pipeline_interleaved_needs_enough_microbatches():
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.pipeline import pipeline, stack_stage_params

    mesh = make_mesh({"pp": 4}, devices=jax.devices()[:4])
    sp = stack_stage_params(_make_stages_r2(8, 4))
    x = jnp.zeros((4, 4))
    with pytest.raises(ValueError, match="num_microbatches >= pp"):
        pipeline(_mlp_stage_r2, sp, x, mesh, num_microbatches=2,
                 virtual_stages=2)


def test_pipeline_lm_embed_and_head_inside():
    """Unequal first/last layers INSIDE the pipelined region: token
    embedding on stage 0, loss head on the final stage; loss and all
    grads (embed, blocks, head) match the sequential model."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.pipeline import pipeline_lm, stack_stage_params

    pp, d, vocab, tlen, m = 4, 8, 12, 5, 4
    b = 2 * m
    mesh = make_mesh({"pp": pp}, devices=jax.devices()[:pp])
    stages = _make_stages_r2(pp, d, key=3)
    sp = stack_stage_params(stages)
    emb = {"table": jax.random.normal(jax.random.PRNGKey(4), (vocab, d)) * 0.2}
    head = {"w": jax.random.normal(jax.random.PRNGKey(5), (d, vocab)) * 0.2}
    tok = jax.random.randint(jax.random.PRNGKey(6), (b, tlen), 0, vocab)
    tgt = jax.random.randint(jax.random.PRNGKey(7), (b, tlen), 0, vocab)

    def embed_fn(p, tok):
        return p["table"][tok]

    def head_loss_fn(p, h, tgt):
        logits = h @ p["w"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(
            jnp.take_along_axis(logp, tgt[..., None], axis=-1))

    def seq_loss(emb, sp, head):
        h = embed_fn(emb, tok.reshape(m, b // m, tlen))
        # sequential over microbatches to mirror per-microbatch mean
        losses = []
        for j in range(m):
            hj = h[j]
            for s in range(pp):
                hj = _mlp_stage_r2(jax.tree.map(lambda p: p[s], sp), hj)
            losses.append(head_loss_fn(
                head, hj, tgt.reshape(m, b // m, tlen)[j]))
        return jnp.mean(jnp.stack(losses))

    def pipe_loss(emb, sp, head):
        return pipeline_lm(embed_fn, _mlp_stage_r2, head_loss_fn,
                           emb, sp, head, tok, tgt, mesh,
                           num_microbatches=m)

    lp = jax.jit(pipe_loss)(emb, sp, head)
    ls = seq_loss(emb, sp, head)
    np.testing.assert_allclose(float(lp), float(ls), rtol=2e-5)

    gp = jax.jit(jax.grad(pipe_loss, argnums=(0, 1, 2)))(emb, sp, head)
    gs = jax.grad(seq_loss, argnums=(0, 1, 2))(emb, sp, head)
    for a, e in zip(jax.tree.leaves(gp), jax.tree.leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=2e-4, atol=2e-5)


def test_pipeline_lm_composes_with_dp():
    """pp=2 x dp=2: pipeline_lm over a 2-axis mesh with the batch sharded
    over dp; loss equals the pp-only value on the same data."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.pipeline import pipeline_lm, stack_stage_params

    pp, d, vocab, tlen, m = 2, 4, 6, 3, 2
    b = 4
    stages = _make_stages_r2(pp, d, key=8)
    sp = stack_stage_params(stages)
    emb = {"table": jax.random.normal(jax.random.PRNGKey(1), (vocab, d))}
    head = {"w": jax.random.normal(jax.random.PRNGKey(2), (d, vocab))}
    tok = jax.random.randint(jax.random.PRNGKey(3), (b, tlen), 0, vocab)
    tgt = jax.random.randint(jax.random.PRNGKey(4), (b, tlen), 0, vocab)

    def embed_fn(p, tok):
        return p["table"][tok]

    def head_loss_fn(p, h, tgt):
        logits = h @ p["w"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(
            jnp.take_along_axis(logp, tgt[..., None], axis=-1))

    mesh_pp = make_mesh({"pp": pp}, devices=jax.devices()[:pp])
    l_ref = pipeline_lm(embed_fn, _mlp_stage_r2, head_loss_fn, emb, sp, head,
                        tok, tgt, mesh_pp, num_microbatches=m)
    mesh2 = make_mesh({"pp": pp, "dp": 2}, devices=jax.devices()[:4])
    l_dp = pipeline_lm(embed_fn, _mlp_stage_r2, head_loss_fn, emb, sp, head,
                       tok, tgt, mesh2, num_microbatches=m,
                       batch_axis="dp")
    np.testing.assert_allclose(float(l_dp), float(l_ref), rtol=2e-5)


def test_pipeline_lm_interleaved():
    """pipeline_lm with virtual_stages=2 (shared schedule machinery):
    loss matches the sequential 2*pp-stage model."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.pipeline import pipeline_lm, stack_stage_params

    pp, v, d, vocab, tlen, m = 2, 2, 4, 6, 3, 4
    b = 2 * m
    mesh = make_mesh({"pp": pp}, devices=jax.devices()[:pp])
    stages = _make_stages_r2(v * pp, d, key=13)
    sp = stack_stage_params(stages)
    emb = {"table": jax.random.normal(jax.random.PRNGKey(1), (vocab, d))}
    head = {"w": jax.random.normal(jax.random.PRNGKey(2), (d, vocab))}
    tok = jax.random.randint(jax.random.PRNGKey(3), (b, tlen), 0, vocab)
    tgt = jax.random.randint(jax.random.PRNGKey(4), (b, tlen), 0, vocab)

    def embed_fn(p, tok):
        return p["table"][tok]

    def head_loss_fn(p, h, tgt):
        logp = jax.nn.log_softmax(h @ p["w"])
        return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], axis=-1))

    lp = pipeline_lm(embed_fn, _mlp_stage_r2, head_loss_fn, emb, sp, head,
                     tok, tgt, mesh, num_microbatches=m, virtual_stages=v)

    # interleaved placement: stage s = r*pp + d executes in order
    # lap 0 (stages 0..pp-1), then lap 1 (stages pp..2pp-1)
    losses = []
    tok_m = tok.reshape(m, b // m, tlen)
    tgt_m = tgt.reshape(m, b // m, tlen)
    for j in range(m):
        h = embed_fn(emb, tok_m[j])
        for s in range(v * pp):
            h = _mlp_stage_r2(jax.tree.map(lambda p: p[s], sp), h)
        losses.append(head_loss_fn(head, h, tgt_m[j]))
    np.testing.assert_allclose(float(lp), float(jnp.mean(jnp.stack(losses))),
                               rtol=2e-5)


def test_pipeline_composes_with_ring_attention_pp_sp():
    """pp x sp composition (round-3 dryrun axis): attention stages
    pipelined over pp=2 while each stage rings the sequence over sp=4,
    vs the same stages applied sequentially with dense attention on one
    logical device.  Fwd values and grads must match."""
    from paddle_tpu.parallel.ring_attention import ring_attention_local
    from paddle_tpu.ops.pallas_attention import attention_reference

    pp, sp = 2, 4
    mesh = make_mesh({"pp": pp, "sp": sp})
    b, t, heads, dh = 2, 16, 2, 4
    d = heads * dh

    def stage_fn(params, h):
        mb, tl, _ = h.shape
        qkv = h @ params["w_qkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shp = (mb, tl, heads, dh)
        o = ring_attention_local(q.reshape(shp), k.reshape(shp),
                                 v.reshape(shp), sp, axis_name="sp",
                                 causal=True)
        return h + o.reshape(mb, tl, d) @ params["w_o"]

    def stage_ref(params, h):
        mb, tl, _ = h.shape
        qkv = h @ params["w_qkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shp = (mb, tl, heads, dh)
        o = attention_reference(q.reshape(shp), k.reshape(shp),
                                v.reshape(shp), causal=True)
        return h + o.reshape(mb, tl, d) @ params["w_o"]

    keys = jax.random.split(jax.random.PRNGKey(0), pp)
    stages = [{"w_qkv": jax.random.normal(k, (d, 3 * d)) * 0.1,
               "w_o": jax.random.normal(k, (d, d)) * 0.1} for k in keys]
    sp_params = stack_stage_params(stages)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, t, d))
    y = jax.random.normal(jax.random.PRNGKey(2), (b, t, d))

    def loss_pp(params):
        out = pipeline(stage_fn, params, x, mesh,
                       num_microbatches=2, wire_spec=("sp", None))
        return jnp.mean((out - y) ** 2)

    def loss_ref(params):
        h = x
        for i in range(pp):
            h = stage_ref(jax.tree.map(lambda p: p[i], params), h)
        return jnp.mean((h - y) ** 2)

    l1, g1 = jax.jit(jax.value_and_grad(loss_pp))(sp_params)
    l2, g2 = jax.jit(jax.value_and_grad(loss_ref))(sp_params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for a, r in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=1e-6)
