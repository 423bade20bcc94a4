"""Flash-attention kernel tests: Pallas (interpret mode on CPU) vs the dense
reference, forward and backward — the cross-device comparison pattern of the
reference's function/*OpTest.cpp suites."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas_attention import (
    attention_reference,
    flash_attention,
)


def _inputs(b=2, tq=16, tk=16, h=2, d=8, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, tq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, tk, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, tk, h, d)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _inputs()
    out = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_cross_attention_shapes():
    q, k, v = _inputs(tq=8, tk=24)
    out = flash_attention(q, k, v, block_q=4, block_k=8)
    ref = attention_reference(q, k, v)
    assert out.shape == (2, 8, 2, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_uneven_block_fallback():
    # t not divisible by requested block: _pick_block shrinks to a divisor
    q, k, v = _inputs(tq=12, tk=20)
    out = flash_attention(q, k, v, block_q=8, block_k=8)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_reference(causal):
    q, k, v = _inputs(b=1, tq=8, tk=8, h=1, d=4)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=4, block_k=4)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = attention_reference(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-5, rtol=5e-4,
            err_msg=f"grad wrt {name}",
        )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bf16_and_d128(causal):
    """bf16 inputs take the bf16 MXU-feed path; d=128 heads (the MFU
    config) must be numerically sound fwd+bwd vs an f32 dense reference."""
    rng = np.random.default_rng(7)
    b, t, h, d = 1, 64, 2, 128
    qf, kf, vf = (jnp.asarray(rng.normal(size=(b, t, h, d)) * 0.5,
                              jnp.float32) for _ in range(3))
    q, k, v = (x.astype(jnp.bfloat16) for x in (qf, kf, vf))
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = attention_reference(qf, kf, vf, causal=causal)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2, rtol=3e-2)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(qf, kf, vf)
    for gf, gr, nm in zip(g_flash, g_ref, "qkv"):
        # bf16 ~ 3 decimal digits; compare against the row scale
        scale = np.maximum(np.abs(np.asarray(gr)).max(), 1.0)
        np.testing.assert_allclose(
            np.asarray(gf, np.float32) / scale, np.asarray(gr) / scale,
            atol=4e-2, err_msg=f"grad wrt {nm}")


def test_flash_attention_op_registered():
    from tests.op_test import run_op

    q, k, v = _inputs(b=1, tq=8, tk=8, h=1, d=4)
    out = run_op(
        "flash_attention",
        {"Q": np.asarray(q), "K": np.asarray(k), "V": np.asarray(v)},
        attrs={"causal": True},
    )
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out["Out"], np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_jit_under_program():
    """The kernel works inside a jitted step function."""
    q, k, v = _inputs(b=1, tq=8, tk=8, h=1, d=4)

    @jax.jit
    def step(q, k, v):
        return flash_attention(q, k, v)

    np.testing.assert_allclose(
        np.asarray(step(q, k, v)),
        np.asarray(attention_reference(q, k, v)),
        atol=2e-5, rtol=2e-5,
    )


def test_flash_cross_attention_causal_tq_gt_tk():
    """Regression: causal cross-attention with t_q > t_k — q blocks whose
    diagonal lies beyond the last k block must still finalize (the 3-D
    grid kernel's last_kb needs clamping to nk-1)."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 16, 2, 8) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(2, 8, 2, 8) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(2, 8, 2, 8), jnp.float32)
    o = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                        interpret=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    ga = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=8, block_k=8, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda q, k, v: attention_reference(
        q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(ga, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-5)


def test_flash_attention_with_lse_matches_dense_including_lse_grads():
    """o, lse, and gradients THROUGH lse (the ring-merge path) vs dense."""
    rng = np.random.RandomState(0)
    b, t, h, d = 2, 64, 2, 16
    q, k, v = (jnp.asarray(rng.randn(b, t, h, d) * 0.5, jnp.float32)
               for _ in range(3))
    from paddle_tpu.ops.pallas_attention import flash_attention_with_lse

    def dense_with_lse(q, k, v, causal):
        scale = d ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if causal:
            mask = jnp.tril(jnp.ones((t, t), bool))
            s = jnp.where(mask[None, None], s, -1e30)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None])
        return jnp.einsum("bhqk,bkhd->bqhd", p, v), lse

    for causal in (False, True):
        o1, l1 = flash_attention_with_lse(q, k, v, causal=causal,
                                          block_q=16, block_k=16,
                                          interpret=True)
        o2, l2 = dense_with_lse(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-5, atol=1e-5)

        def loss(fn):
            def f(q, k, v):
                o, lse = fn(q, k, v)
                return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))
            return f

        ga = jax.grad(loss(lambda q, k, v: flash_attention_with_lse(
            q, k, v, causal=causal, block_q=16, block_k=16,
            interpret=True)), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(lambda q, k, v: dense_with_lse(q, k, v, causal)),
                      argnums=(0, 1, 2))(q, k, v)
        for a, r in zip(ga, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_split_bwd_matches_fused(causal, monkeypatch):
    """The long-context backward (split dq + dkv kernels, used when the
    fused kernel's dq partials exceed budget) stays in lockstep with the
    fused backward and the dense reference."""
    from paddle_tpu.ops import pallas_attention as pa

    q, k, v = _inputs(b=1, tq=16, tk=16, h=2, d=4)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=4, block_k=4)
        return jnp.sum(o * jnp.cos(o))

    g_fused = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setattr(pa, "FUSED_BWD_PARTIAL_BYTES", 0)
    g_split = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def loss_ref(q, k, v):
        o = attention_reference(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gs, gr, name in zip(g_fused, g_split, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gr),
                                   atol=5e-5, rtol=5e-4,
                                   err_msg=f"split grad wrt {name}")
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gs),
                                   atol=5e-5, rtol=5e-4,
                                   err_msg=f"fused vs split wrt {name}")


@pytest.mark.parametrize("causal", [False, True])
def test_flash_packed_matches_4d_values_and_grads(causal):
    """The packed-layout kernel ([b, t, h*d], heads as lane slices in the
    block index maps) is bit-identical to the 4-D path (same math,
    same blocks — only block index maps differ), values and gradients."""
    from paddle_tpu.ops.pallas_attention import flash_attention_packed

    b, t, h, d = 2, 64, 2, 8
    q, k, v = _inputs(b=b, tq=t, tk=t, h=h, d=d, seed=3)
    pk = lambda x: x.reshape(b, t, h * d)

    out4 = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    outp = flash_attention_packed(pk(q), pk(k), pk(v), h, causal=causal,
                                  block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(outp), np.asarray(pk(out4)),
                               atol=1e-6, rtol=1e-5)

    def l4(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=16,
                                       block_k=16) ** 2)

    def lp(q, k, v):
        return jnp.sum(flash_attention_packed(q, k, v, h, causal=causal,
                                              block_q=16, block_k=16) ** 2)

    g4 = jax.grad(l4, (0, 1, 2))(q, k, v)
    gp = jax.grad(lp, (0, 1, 2))(pk(q), pk(k), pk(v))
    for a, b_ in zip(g4, gp):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(pk(a)),
                                   atol=1e-5, rtol=1e-5)


def test_flash_packed_split_bwd_matches_fused(monkeypatch):
    """Packed layout through the long-context split dq/dkv kernels (budget
    forced to 0) agrees with the fused backward."""
    import paddle_tpu.ops.pallas_attention as pa

    b, t, h, d = 1, 64, 2, 8
    q, k, v = _inputs(b=b, tq=t, tk=t, h=h, d=d, seed=5)
    pk = lambda x: x.reshape(b, t, h * d)

    def lp(q, k, v):
        return jnp.sum(pa.flash_attention_packed(
            q, k, v, h, causal=True, block_q=16, block_k=16) ** 2)

    g_fused = jax.grad(lp, (0, 1, 2))(pk(q), pk(k), pk(v))
    monkeypatch.setattr(pa, "FUSED_BWD_PARTIAL_BYTES", 0)
    g_split = jax.grad(lp, (0, 1, 2))(pk(q), pk(k), pk(v))
    for a, b_ in zip(g_fused, g_split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-5, rtol=1e-5)


def test_flash_packed_head_width_guard():
    """d_head not lane-aligned (and n_head > 1) is a clear error, not a
    Mosaic crash."""
    from paddle_tpu.ops.pallas_attention import flash_attention_packed

    x = jnp.zeros((1, 16, 2 * 8), jnp.float32)
    with pytest.raises(ValueError, match="d_head % 128"):
        flash_attention_packed(x, x, x, 2, interpret=False)


def test_flash_attention_packed_op_registered():
    from tests.op_test import run_op

    b, t, h, d = 1, 16, 1, 4
    q, k, v = _inputs(b=b, tq=t, tk=t, h=h, d=d)
    pk = lambda x: np.asarray(x).reshape(b, t, h * d)
    out = run_op(
        "flash_attention_packed",
        {"Q": pk(q), "K": pk(k), "V": pk(v)},
        attrs={"n_head": h, "causal": True},
    )
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out["Out"], pk(ref), atol=2e-5, rtol=2e-5)


def test_packed_geometry_paths_pinned():
    """THE geometry decision table (ISSUE 3): which code path each
    (n_head, d_head) takes — one lane-aligned head per slice, two paired
    d=64 heads per slice, or no packed spelling at all (4-D fallback)."""
    from paddle_tpu.ops.pallas_attention import packed_sub_heads

    assert packed_sub_heads(6, 128) == 1    # flagship: lane-aligned
    assert packed_sub_heads(1, 8) == 1      # single head: whole feature
    assert packed_sub_heads(12, 64) == 2    # d64: two heads per slice
    assert packed_sub_heads(4, 64) == 2
    assert packed_sub_heads(3, 64) is None  # odd head count can't pair
    assert packed_sub_heads(2, 8) is None   # narrow heads: 4-D fallback
    assert packed_sub_heads(2, 256) == 1

    # the layer builder must route accordingly
    import paddle_tpu as pt
    from paddle_tpu import layers

    def attn_ops(d_model, n_head):
        pt.core.unique_name.reset()
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", shape=[16, d_model])
            layers.multi_head_attention(x, x, x, d_model=d_model,
                                        n_head=n_head, causal=True)
        return {op.type for op in main.global_block().ops}

    assert "flash_attention_packed" in attn_ops(256, 2)   # dh=128
    assert "flash_attention_packed" in attn_ops(128, 2)   # dh=64 paired
    assert "flash_attention" in attn_ops(48, 3)           # dh=16 fallback
    assert "flash_attention_packed" not in attn_ops(48, 3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_packed_d64_paired_matches_reference(causal):
    """d_head=64 packed layout (two heads per 128-lane slice, sub_heads=2
    kernels): values and gradients vs the dense reference."""
    from paddle_tpu.ops.pallas_attention import flash_attention_packed

    rng = np.random.default_rng(9)
    b, t, h, d = 2, 32, 4, 64
    q4, k4, v4 = (jnp.asarray(rng.normal(size=(b, t, h, d)) * 0.5,
                              jnp.float32) for _ in range(3))
    pk = lambda x: x.reshape(b, t, h * d)
    outp = flash_attention_packed(pk(q4), pk(k4), pk(v4), h, causal=causal,
                                  block_q=16, block_k=16)
    ref = attention_reference(q4, k4, v4, causal=causal)
    np.testing.assert_allclose(np.asarray(outp), np.asarray(pk(ref)),
                               atol=2e-5, rtol=2e-5)

    def lp(q, k, v):
        return jnp.sum(flash_attention_packed(
            q, k, v, h, causal=causal, block_q=16, block_k=16) ** 2)

    def lr(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    gp = jax.grad(lp, (0, 1, 2))(pk(q4), pk(k4), pk(v4))
    gr = jax.grad(lr, (0, 1, 2))(q4, k4, v4)
    for a, r, nm in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(pk(r)),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"paired grad wrt {nm}")


def test_flash_packed_d64_split_bwd_matches_fused(monkeypatch):
    """d64 paired layout through the long-context split dq/dkv kernels."""
    import paddle_tpu.ops.pallas_attention as pa

    rng = np.random.default_rng(11)
    b, t, h, d = 1, 32, 2, 64
    q = jnp.asarray(rng.normal(size=(b, t, h * d)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, h * d)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, h * d)), jnp.float32)

    def lp(q, k, v):
        return jnp.sum(pa.flash_attention_packed(
            q, k, v, h, causal=True, block_q=16, block_k=16) ** 2)

    g_fused = jax.grad(lp, (0, 1, 2))(q, k, v)
    monkeypatch.setattr(pa, "FUSED_BWD_PARTIAL_BYTES", 0)
    g_split = jax.grad(lp, (0, 1, 2))(q, k, v)
    for a, b_ in zip(g_fused, g_split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-5, rtol=1e-5)


def test_causal_triangular_no_masked_half_flops():
    """Flop accounting via the kernel's OWN cell walk (``_cell_kind`` and
    ``_strip_cols`` are what ``_walk_cell`` runs and what
    ``causal_flash_flops`` counts): the masked halves of diagonal blocks
    are never scheduled — only the DIAG_W-high steps along the diagonal
    remain, no scheduled column lies wholly above a strip's last row, and
    the walk's bookkeeping (softmax updates a q row, branches inside a
    cell) is pinned at the geometries that train."""
    from paddle_tpu.ops.pallas_attention import (
        causal_flash_flops, _cell_kind, _strip_cols)

    # flagship geometry: t=4096, 1024 blocks.  Old full-tile + select
    # spelling scheduled ~1.25x the useful flops; triangular must be
    # within the diagonal band bound (~1 + DIAG_W/t + slack).
    walk = causal_flash_flops(4096, 4096, 128, 1024, 1024, diag_w=256)
    sched, useful = walk
    assert sched / useful < 1.08, sched / useful
    # old spelling for comparison: every cell at/below the block diagonal
    # fully computed
    nq = nk = 4096 // 1024
    old = sum(min(((j + 1) * 1024 - 1) // 1024, nk - 1) + 1
              for j in range(nq)) * 1024 * 1024 * 4 * 128
    assert sched < 0.9 * old
    # one update a row in each of its q block's live cells (256 x 256
    # sub-tiles made up to 7 a row here, under 32 branches a diagonal
    # cell), and no branch inside a cell
    assert (walk.updates_per_row, walk.branches_per_cell) == (4, 0)
    # the training cell's call: t=2048, two k blocks
    walk = causal_flash_flops(2048, 2048, 128, 1024, 1024, diag_w=256)
    assert 1.12 < walk[0] / walk[1] < 1.13
    assert (walk.updates_per_row, walk.branches_per_cell) == (2, 0)
    # taller strips: fewer, wider tiles for more masked scores
    tall = causal_flash_flops(2048, 2048, 128, 1024, 1024, diag_w=512)
    assert walk[0] < tall[0] and tall[1] == walk[1]
    assert tall.updates_per_row == 2

    # grid-shape assertion: a cell is full, straddling or skipped by where
    # the diagonal lies, and a strip of the diagonal cell takes exactly
    # the columns up to its last row, the mask on those past its first
    bq = bk = 1024
    w = 256
    for j in range(4):
        for kb in range(4):
            full, straddling = _cell_kind(j * bq - kb * bk, bq, bk)
            assert (full, straddling) == (kb < j, kb == j)
    for qs in range(bq // w):
        visible, masked = _strip_cols(qs, w, bq, bk)
        row0, row_last = qs * w, (qs + 1) * w - 1
        assert visible - 1 == row_last          # last column taken
        assert visible - masked == row0         # first masked column
    # unequal blocks: the diagonal may lie anywhere in a straddling cell,
    # a strip takes every column and masks them all
    assert _strip_cols(1, 256, 512, 1024) == (1024, 1024)
    assert _cell_kind(512 - 0, 512, 1024) == (False, True)
    assert _cell_kind(1536 - 0, 512, 1024) == (True, False)
    assert _cell_kind(0 - 1024, 512, 1024) == (False, False)


def test_causal_triangular_multi_subtile_matches_reference(monkeypatch):
    """Force several strips a diagonal cell (DIAG_W smaller than the
    block) and check the forward against the dense reference — a strip's
    one update over the columns it sees, masked on the last DIAG_W, must
    reduce to the same attention."""
    import paddle_tpu.ops.pallas_attention as pa

    monkeypatch.setattr(pa, "DIAG_W", 32)
    rng = np.random.default_rng(13)
    b, t, h, d = 1, 256, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)) * 0.5,
                           jnp.float32) for _ in range(3))
    o = pa.flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # uneven aspect, both ways: the diagonal lies anywhere in a
    # straddling cell and the strips' mask follows the program ids
    for bq, bk in ((64, 128), (128, 32)):
        o2 = pa.flash_attention(q, k, v, causal=True, block_q=bq,
                                block_k=bk)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_causal_triangular_multi_subtile_grads(monkeypatch):
    """Gradients through the strips of the diagonal cells of BOTH
    backward spellings (fused, and split dq/dkv with the partial budget
    forced to 0), vs the dense reference — the triangular pass covers the
    whole causal step, not just the forward."""
    import paddle_tpu.ops.pallas_attention as pa

    monkeypatch.setattr(pa, "DIAG_W", 32)
    rng = np.random.default_rng(17)
    b, t, h, d = 1, 128, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)) * 0.5,
                           jnp.float32) for _ in range(3))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * jnp.cos(fn(q, k, v)))

    flash = lambda q, k, v: pa.flash_attention(
        q, k, v, causal=True, block_q=64, block_k=64)
    dense = lambda q, k, v: attention_reference(q, k, v, causal=True)
    g_ref = jax.grad(loss(dense), (0, 1, 2))(q, k, v)
    g_fused = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    monkeypatch.setattr(pa, "FUSED_BWD_PARTIAL_BYTES", 0)
    g_split = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    for gf, gs, gr, nm in zip(g_fused, g_split, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-5, rtol=5e-4,
                                   err_msg=f"fused tri grad wrt {nm}")
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gr),
                                   atol=5e-5, rtol=5e-4,
                                   err_msg=f"split tri grad wrt {nm}")

    # d64 paired (sub_heads=2) through the sub-tiled diagonal as well
    b, t, h, d = 1, 64, 2, 64
    q2, k2, v2 = (jnp.asarray(rng.normal(size=(b, t, h, d)) * 0.5,
                              jnp.float32) for _ in range(3))
    pk = lambda x: x.reshape(b, t, h * d)

    def lp(q, k, v):
        return jnp.sum(pa.flash_attention_packed(
            q, k, v, h, causal=True, block_q=64, block_k=64) ** 2)

    def lr(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    gp = jax.grad(lp, (0, 1, 2))(pk(q2), pk(k2), pk(v2))
    gr = jax.grad(lr, (0, 1, 2))(q2, k2, v2)
    for a, r, nm in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(pk(r)),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"paired tri grad wrt {nm}")


def test_packed_op_tp_odd_local_heads_falls_back_to_4d():
    """TP regression: global n_head packs (d=64, 6 heads -> pairs) but
    the per-shard count does not (6/2 = 3 local heads can't pair) — the
    op must route each shard through the 4-D kernel instead of raising
    at trace time, and still match the dense reference."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.pallas_attention import (
        flash_attention_packed_op, packed_sub_heads)
    from paddle_tpu.parallel.mesh import make_mesh

    h, d = 6, 64
    assert packed_sub_heads(h, d) == 2
    assert packed_sub_heads(h // 2, d) is None

    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])

    class _Exe:
        pass

    class _Ctx:
        executor = _Exe()

    _Ctx.executor.mesh = mesh
    rng = np.random.default_rng(21)
    b, t = 2, 16
    q4, k4, v4 = (jnp.asarray(rng.normal(size=(b, t, h, d)) * 0.5,
                              jnp.float32) for _ in range(3))
    pk = lambda x: jax.device_put(
        x.reshape(b, t, h * d), NamedSharding(mesh, P(None, None, "tp")))
    out = flash_attention_packed_op(
        pk(q4), pk(k4), pk(v4), n_head=h, causal=True, _ctx=_Ctx())["Out"]
    ref = attention_reference(q4, k4, v4, causal=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.reshape(b, t, h * d)),
                               atol=2e-5, rtol=2e-5)
