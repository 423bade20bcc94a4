"""Fused softmax-cross-entropy head (ops/pallas_ce.py): Pallas kernels
(interpret mode on CPU) vs dense references, forward and backward, plus
the layer/program path and fused-vs-composed head equivalence on the
transformer flagship — the composed path it replaces is the reference's
``softmax_with_cross_entropy_op.cc`` after an fc projection."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.ops.pallas_ce import (
    fused_softmax_ce_head,
    fused_softmax_ce_head_reference,
)

from op_test import run_op


def _inputs(n, d, v, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, v)) * 0.3, jnp.float32)
    y = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
    return x, w, y


@pytest.mark.parametrize("n,d,v", [(16, 8, 32), (64, 12, 100), (8, 5, 7)])
def test_fused_ce_forward_matches_dense(n, d, v):
    x, w, y = _inputs(n, d, v)
    got = fused_softmax_ce_head(x, w, y)
    ref = fused_softmax_ce_head_reference(x, w, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_fused_ce_forward_matches_numpy():
    """Independent numpy golden (not jax log_softmax)."""
    n, d, v = 12, 6, 40
    x, w, y = _inputs(n, d, v, seed=3)
    xn, wn, yn = map(np.asarray, (x, w, y))
    logits = xn @ wn
    m = logits.max(axis=1)
    lse = m + np.log(np.exp(logits - m[:, None]).sum(axis=1))
    ref = lse - logits[np.arange(n), yn]
    got = fused_softmax_ce_head(x, w, y)
    np.testing.assert_allclose(np.asarray(got), ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("n,d,v", [(16, 8, 32), (24, 10, 50)])
def test_fused_ce_grads_match_dense(n, d, v):
    x, w, y = _inputs(n, d, v, seed=1)
    g = jnp.asarray(np.random.default_rng(2).normal(size=(n,)), jnp.float32)

    def f_fused(x, w):
        return jnp.sum(fused_softmax_ce_head(x, w, y) * g)

    def f_ref(x, w):
        return jnp.sum(fused_softmax_ce_head_reference(x, w, y) * g)

    dx1, dw1 = jax.grad(f_fused, argnums=(0, 1))(x, w)
    dx2, dw2 = jax.grad(f_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dx1), np.asarray(dx2),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(dw1), np.asarray(dw2),
                               atol=2e-5, rtol=2e-5)


def test_fused_ce_ignored_labels_zero_grads():
    """ignore_index semantics: out-of-range labels with a zero cotangent
    (the mask multiplies the loss) contribute exactly zero gradient."""
    x, w, _ = _inputs(8, 8, 16, seed=4)
    y = jnp.asarray([-1, 3, -1, 5, -1, -1, 2, -1], jnp.int32)
    mask = (np.asarray(y) >= 0).astype(np.float32)
    y_safe = jnp.maximum(y, 0)

    def f(x, w):
        return jnp.sum(fused_softmax_ce_head(x, w, y_safe) * mask)

    def f_ref(x, w):
        return jnp.sum(
            fused_softmax_ce_head_reference(x, w, y_safe) * mask)

    dx1, dw1 = jax.grad(f, argnums=(0, 1))(x, w)
    dx2, dw2 = jax.grad(f_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dx1), np.asarray(dx2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(dw1), np.asarray(dw2), atol=2e-5)
    # masked rows have exactly zero dx
    assert np.abs(np.asarray(dx1)[np.asarray(y) < 0]).max() == 0.0


def test_fused_ce_batched_leading_dims():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 6, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 16, (2, 6)), jnp.int32)
    got = fused_softmax_ce_head(x, w, y)
    ref = fused_softmax_ce_head_reference(x, w, y)
    assert got.shape == (2, 6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_fused_ce_bf16_inputs():
    rng = np.random.default_rng(6)
    xf = jnp.asarray(rng.normal(size=(16, 8)) * 0.5, jnp.float32)
    wf = jnp.asarray(rng.normal(size=(8, 32)) * 0.5, jnp.float32)
    y = jnp.asarray(rng.integers(0, 32, (16,)), jnp.int32)
    got = fused_softmax_ce_head(xf.astype(jnp.bfloat16),
                                wf.astype(jnp.bfloat16), y)
    ref = fused_softmax_ce_head_reference(xf, wf, y)
    assert got.dtype == jnp.float32  # loss always f32
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=5e-2, rtol=5e-2)


def test_fused_ce_op_registered():
    rng = np.random.default_rng(7)
    x = rng.normal(size=(2, 4, 8)).astype(np.float32)
    w = rng.normal(size=(8, 16)).astype(np.float32)
    y = rng.integers(0, 16, (2, 4, 1)).astype(np.int64)
    out = run_op("fused_softmax_ce_head", {"X": x, "W": w, "Label": y})
    ref = fused_softmax_ce_head_reference(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(y[..., 0]))
    assert out["Loss"].shape == (2, 4, 1)
    np.testing.assert_allclose(out["Loss"][..., 0], np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_transformer_fused_head_matches_composed():
    """The flagship trained with fused_head=True takes an identical first
    step (loss and post-step params) to the composed fc+softmax head when
    started from the same weights."""
    from paddle_tpu.core.scope import Scope, scope_guard
    from paddle_tpu.models import transformer

    rng = np.random.default_rng(0)
    toks = rng.integers(0, 50, (4, 16)).astype(np.int64)
    lbls = np.roll(toks, -1, axis=1)
    lbls[:, -1] = -1

    def run(fused, params=None):
        main, startup = pt.Program(), pt.Program()
        sc = Scope()
        with scope_guard(sc), pt.program_guard(main, startup):
            outs = transformer.build(
                vocab_size=50, n_layer=2, n_head=2, d_model=32,
                max_len=16, dropout_rate=0.0, dtype="float32",
                fused_head=fused)
            exe = pt.Executor()
            exe.run(startup)
            if params is not None:
                sc.update(params)
            snap = transformer.extract_params(sc, main)
            (cost,) = exe.run(main,
                              feed={"tokens": toks, "labels": lbls},
                              fetch_list=[outs["avg_cost"]])
            after = transformer.extract_params(sc, main)
        return float(np.asarray(cost).ravel()[0]), snap, after

    c0, params, after0 = run(False)
    c1, params1, after1 = run(True, params=params)
    assert sorted(params) == sorted(params1)  # same parameter surface
    assert abs(c0 - c1) < 1e-5, (c0, c1)
    for k in after0:
        np.testing.assert_allclose(
            np.asarray(after0[k], np.float32),
            np.asarray(after1[k], np.float32), atol=5e-5,
            err_msg=f"post-step param {k}")


def test_transformer_fused_head_all_masked_zero_loss():
    from paddle_tpu.models import transformer

    outs = transformer.build(vocab_size=20, n_layer=1, n_head=2,
                             d_model=16, max_len=8, dropout_rate=0.0,
                             dtype="float32", fused_head=True)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 20, (2, 8)).astype(np.int64)
    lbls = np.full((2, 8), -1, np.int64)
    (cost,) = exe.run(feed={"tokens": toks, "labels": lbls},
                      fetch_list=[outs["avg_cost"]])
    assert abs(float(np.asarray(cost).ravel()[0])) < 1e-6


def test_fused_head_trains_under_dp_mesh():
    """The fused CE head's Pallas call lowers under GSPMD with a
    batch-sharded dp mesh and the loss descends."""
    import jax

    from paddle_tpu.models import transformer
    from paddle_tpu.parallel import api as papi
    from paddle_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    main = pt.default_main_program()
    startup = pt.default_startup_program()
    with pt.program_guard(main, startup):
        outs = transformer.build(vocab_size=64, n_layer=2, n_head=2,
                                 d_model=32, max_len=16, dropout_rate=0.0,
                                 dtype="float32", fused_head=True)
    papi.data_parallel(main, "dp", programs=(startup,))
    exe = pt.Executor(mesh=mesh)
    exe.run(startup)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 64, (8, 16)).astype(np.int64)
    lbls = np.roll(toks, -1, axis=1)
    lbls[:, -1] = -1
    losses = []
    for _ in range(4):
        (c,) = exe.run(main, feed={"tokens": toks, "labels": lbls},
                       fetch_list=[outs["avg_cost"]])
        losses.append(float(np.asarray(c).ravel()[0]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_block_chooser_preserves_flagship_and_shrinks_big_dmodel():
    """The VMEM-model block chooser returns the hand-tuned flagship
    config unchanged and shrinks (never dies in Mosaic) for d_model
    >= 1024 shapes."""
    from paddle_tpu.ops.pallas_ce import _auto_blocks

    assert _auto_blocks(32768, 768, 32768, 2, 2, 512, 1024, 2048) == (
        512, 1024, 2048)
    bn, bv, bvf = _auto_blocks(4096, 2048, 50304, 2, 2, 512, 1024, 2048)
    assert bn >= 8 and 50304 % bv == 0 and 50304 % bvf == 0
    assert bv < 1024 and bvf < 2048  # shrank to fit


def test_block_chooser_returns_only_tiles_the_chip_accepts():
    """A vocab tile is the whole vocabulary or a multiple of 128 that
    divides it (interpret mode takes any divisor, the TPU lowering does
    not); a vocabulary with no such tile raises, naming it."""
    from paddle_tpu.ops.pallas_ce import _auto_blocks

    _, bv, bvf = _auto_blocks(32768, 768, 50304, 2, 2, 512, 1024, 2048)
    assert bv % 128 == 0 and bvf % 128 == 0
    assert 50304 % bv == 0 and 50304 % bvf == 0
    assert _auto_blocks(64, 32, 61, 4, 4, 512, 1024, 2048)[1:] == (61, 61)
    with pytest.raises(ValueError, match="vocab=50257"):
        _auto_blocks(32768, 768, 50257, 2, 2, 512, 1024, 2048)


@pytest.mark.slow
def test_fused_ce_d2048_v50k_interpret_matches_reference():
    """Large-d_model shape through the SAME code path (interpret mode):
    forward + dx + dW against the dense reference."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_ce import (
        fused_softmax_ce_head, fused_softmax_ce_head_reference)

    rng = np.random.default_rng(9)
    n, d, v = 16, 2048, 50304
    x = jnp.asarray(rng.normal(size=(n, d)) * 0.1, jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, v)) * 0.02, jnp.float32)
    y = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)

    loss = fused_softmax_ce_head(x, w, y)
    ref = fused_softmax_ce_head_reference(x, w, y)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    g = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    dxf, dwf = jax.grad(
        lambda x, w: jnp.sum(fused_softmax_ce_head(x, w, y) * g),
        (0, 1))(x, w)
    dxr, dwr = jax.grad(
        lambda x, w: jnp.sum(fused_softmax_ce_head_reference(x, w, y) * g),
        (0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dxf), np.asarray(dxr),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dwf), np.asarray(dwr),
                               rtol=2e-3, atol=2e-4)


def test_fused_ce_impossible_shape_fails_helpfully():
    from paddle_tpu.ops.pallas_ce import _auto_blocks

    with pytest.raises(ValueError, match="no block config fits"):
        # absurd d_model: even minimum blocks exceed the budget
        _auto_blocks(4096, 1 << 22, 32768, 4, 4, 512, 1024, 2048)
