"""Tensor-parallel transformer recipe on the 8-device CPU mesh: tp=2
training == unsharded training, numerically — head-sharded flash
attention (shard_map over heads), row/column-sharded projections, and
the vocab-sharded fused CE head's logsumexp merge."""

import numpy as np
import jax
import pytest

import paddle_tpu as pt
from paddle_tpu.models import transformer
from paddle_tpu.parallel import api as papi
from paddle_tpu.parallel.mesh import make_mesh


VOCAB, LAYERS, HEADS, DMODEL, SEQ = 64, 2, 2, 32, 16


def _train(mesh, tp_shard, steps=4, seed=3, n_head=HEADS):
    # Sharded init == unsharded init because JAX's threefry lowering is
    # partitionable (the default): every element derives from its
    # global counter regardless of layout, so the tp row-sharded weights
    # (att_out.w, ffn2.w under tp_rules' P('tp', None)) start from the
    # same values as the reference and the test measures tp TRAINING
    # numerics, not PRNG lowering.
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = 7
    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        with pt.program_guard(main, startup):
            outs = transformer.build(
                vocab_size=VOCAB, n_layer=LAYERS, n_head=n_head,
                d_model=DMODEL, max_len=SEQ, dropout_rate=0.0,
                dtype="float32", fused_head=True, learning_rate=0.1)
        if mesh is not None:
            papi.data_parallel(main, "dp", programs=(startup,))
            if tp_shard:
                for prog in (main, startup):
                    papi.shard_parameters_by_rule(
                        prog, transformer.tp_rules())
        exe = pt.Executor(mesh=mesh, donate_state=False)
        exe.run(startup, scope=scope)
        rng = np.random.default_rng(seed)
        toks = rng.integers(0, VOCAB, (4, SEQ)).astype(np.int64)
        lbls = np.roll(toks, -1, axis=1)
        lbls[:, -1] = -1
        losses = []
        for _ in range(steps):
            (c,) = exe.run(main, feed={"tokens": toks, "labels": lbls},
                           fetch_list=[outs["avg_cost"]], scope=scope)
            losses.append(float(np.asarray(c)))
        return losses
    finally:
        pt.core.scope._scope_stack.pop()


def test_tp2_matches_unsharded():
    """dp=2 x tp=2 sharded training tracks the single-device run step
    for step (same seed, same data, f32)."""
    ref = _train(None, False)
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    got = _train(mesh, True)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
    assert got[-1] < got[0]  # it actually learns


def test_tp4_pure_tensor_parallel():
    """A pure tp mesh (dp=1): n_head=4 so tp=4 divides the heads and the
    shard_map-over-heads attention path actually engages (2 heads would
    silently fall back to the GSPMD path)."""
    ref = _train(None, False, n_head=4)
    mesh = make_mesh({"dp": 1, "tp": 4}, devices=jax.devices()[:4])
    got = _train(mesh, True, n_head=4)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def test_tp_rules_cover_the_sharded_params():
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        transformer.build(vocab_size=VOCAB, n_layer=1, n_head=HEADS,
                          d_model=DMODEL, max_len=SEQ, dropout_rate=0.0,
                          dtype="float32", fused_head=True)
    papi.shard_parameters_by_rule(main, transformer.tp_rules())
    specs = {v.name: getattr(v, "partition_spec", None)
             for v in main.global_block().vars.values() if v.persistable}
    sharded = {n for n, s in specs.items() if s is not None and any(s)}
    assert "block0_att_q.w" in sharded
    assert "block0_ffn2.w" in sharded
    assert "lm_head.w" in sharded
    assert "tok_emb.w" not in sharded  # embeddings replicate
