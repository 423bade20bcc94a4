"""Transformer LM model family (models/transformer.py) — the long-context
flagship NEW capability (the reference predates transformers; its attention
is composed fc+softmax, networks.py simple_attention)."""

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu.models import transformer

from tiny import train_steps


def _lm_batch(rng, batch, seq, vocab):
    toks = rng.integers(0, vocab, (batch, seq)).astype(np.int64)
    lbls = np.roll(toks, -1, axis=1)
    lbls[:, -1] = -1  # padding position, masked out of the loss
    return toks, lbls


def test_transformer_lm_trains():
    outs = transformer.build(vocab_size=50, n_layer=2, n_head=2, d_model=32,
                             max_len=16, dropout_rate=0.0,
                             learning_rate=1e-2, dtype="float32")
    rng = np.random.default_rng(0)
    toks, lbls = _lm_batch(rng, 4, 16, 50)
    train_steps(outs, {"tokens": toks, "labels": lbls}, steps=6)


def test_transformer_label_mask():
    """All-padding labels give zero loss: the mask really gates the loss."""
    outs = transformer.build(vocab_size=20, n_layer=1, n_head=2, d_model=16,
                             max_len=8, dropout_rate=0.0, dtype="float32")
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 20, (2, 8)).astype(np.int64)
    lbls = np.full((2, 8), -1, np.int64)
    (cost,) = exe.run(feed={"tokens": toks, "labels": lbls},
                      fetch_list=[outs["avg_cost"]])
    assert abs(float(np.asarray(cost).ravel()[0])) < 1e-6


def test_transformer_dp_tp_mesh():
    """Train step on a dp x tp mesh: batch sharded over dp, attention/FFN
    weights column-sharded over tp (GSPMD inserts the collectives)."""
    from paddle_tpu.parallel import api as papi
    from paddle_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    main = pt.default_main_program()
    startup = pt.default_startup_program()
    with pt.program_guard(main, startup):
        outs = transformer.build(vocab_size=64, n_layer=2, n_head=2,
                                 d_model=32, max_len=16, dropout_rate=0.0,
                                 learning_rate=1e-2, dtype="float32")
    papi.data_parallel(main, "dp", programs=(startup,))
    for prog in (main, startup):
        papi.shard_parameters_by_rule(
            prog, [(r".*_ffn1\.w", P(None, "tp")),
                   (r".*_ffn2\.w", P("tp", None)),
                   (r"^lm_head\.w", P(None, "tp"))])

    exe = pt.Executor(mesh=mesh)
    exe.run(startup)
    rng = np.random.default_rng(2)
    toks, lbls = _lm_batch(rng, 8, 16, 64)
    losses = []
    for _ in range(4):
        (cost,) = exe.run(main, feed={"tokens": toks, "labels": lbls},
                          fetch_list=[outs["avg_cost"]])
        losses.append(float(np.asarray(cost).ravel()[0]))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses


def test_multi_head_attention_layer_shapes_and_grad():
    outs_dim = 24
    x = pt.layers.data("x", shape=[6, outs_dim], dtype="float32")
    y = pt.layers.multi_head_attention(x, x, x, d_model=outs_dim, n_head=4,
                                       causal=True)
    cost = pt.layers.mean(y * y)
    pt.optimizer.SGD(learning_rate=0.1).minimize(cost)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.default_rng(3)
    xv = rng.normal(size=(2, 6, outs_dim)).astype(np.float32)
    (yv, cv) = exe.run(feed={"x": xv}, fetch_list=[y, cost])
    assert yv.shape == (2, 6, outs_dim)
    assert np.isfinite(cv).all()


def test_generate_matches_program_forward():
    """KV-cache incremental decode reproduces the Program forward logits
    on the prompt prefix (same weights, same math, different schedule —
    the test_NetworkCompare pattern, SURVEY section 4)."""
    vocab, nl, nh, dm, T = 40, 2, 2, 32, 12
    outs = transformer.build(vocab_size=vocab, n_layer=nl, n_head=nh,
                             d_model=dm, max_len=T, dropout_rate=0.0,
                             is_test=True, dtype="float32")
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.default_rng(5)
    toks = rng.integers(0, vocab, (2, T)).astype(np.int64)
    lbls = np.roll(toks, -1, axis=1)
    # snapshot weights BEFORE the train step (the program updates them)
    params = transformer.extract_params()
    (prog_logits,) = exe.run(feed={"tokens": toks, "labels": lbls},
                             fetch_list=[outs["logits"]])
    gen_tokens, gen_logits = transformer.generate(
        params, toks, max_len=T, n_layer=nl, n_head=nh, d_model=dm)
    np.testing.assert_allclose(np.asarray(gen_logits), prog_logits,
                               rtol=2e-3, atol=2e-3)
    # full-length prompt comes back verbatim (no last-token overwrite)
    np.testing.assert_array_equal(np.asarray(gen_tokens), toks)


def test_infer_compute_dtype_ignores_stray_adapters():
    """Regression (ADVICE round 5): the serving-dtype scan is restricted
    to block/lm_head matmul weights — a stray low-precision matrix (an
    f16 adapter bolted onto the dict) must not silently downgrade the
    whole decode, and the f32 embedding tables must not promote it."""
    import jax.numpy as jnp

    base = {
        "tok_emb.w": np.zeros((8, 4), np.float32),
        "pos_emb.w.w": np.zeros((8, 4), np.float32),
        "block0_att_q.w": jnp.zeros((4, 4), jnp.bfloat16),
        "lm_head.w": jnp.zeros((4, 8), jnp.bfloat16),
    }
    assert transformer.infer_compute_dtype(base) == jnp.bfloat16
    # stray f16 adapter outside the block/head namespace: ignored
    with_adapter = dict(base, **{
        "adapter0.w": jnp.zeros((4, 4), jnp.float16)})
    assert transformer.infer_compute_dtype(with_adapter) == jnp.bfloat16
    # no block/head names at all: fall back to any >=2-D floating weight
    assert transformer.infer_compute_dtype(
        {"tok_emb.w": np.zeros((8, 4), np.float32)}) == jnp.float32


def test_generate_greedy_continuation():
    """After training next-token = (tok+1) mod vocab, greedy decode
    continues the pattern from a short prompt."""
    vocab, nl, nh, dm, T = 16, 1, 2, 32, 8
    outs = transformer.build(vocab_size=vocab, n_layer=nl, n_head=nh,
                             d_model=dm, max_len=T, dropout_rate=0.0,
                             learning_rate=5e-3, dtype="float32")
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.default_rng(6)
    for _ in range(150):
        toks = rng.integers(0, vocab, (8, T)).astype(np.int64)
        lbls = (toks + 1) % vocab
        exe.run(feed={"tokens": toks, "labels": lbls},
                fetch_list=[outs["avg_cost"]])
    params = transformer.extract_params()
    prompt = np.asarray([[3, 4], [10, 11]], np.int64)
    tokens, _ = transformer.generate(params, prompt, max_len=T,
                                     n_layer=nl, n_head=nh, d_model=dm)
    tokens = np.asarray(tokens)
    expect = (prompt[:, -1:] + np.arange(1, T - 1)) % vocab
    assert (tokens[:, 2:] == expect).mean() > 0.7, tokens
