"""``serving.arch.SparseLightning`` (the ``minicpm_sala`` layout) at tiny
widths, float32, on the CPU, against its plain reference
(``chipbench/families/sparse_lightning_reference.py``): prefill pieces, a
prefix hit that starts from a state snapshot, and decode, with contexts on
BOTH sides of ``dense_len`` in one batch; the compressed keys at the end
of a shared document; the selection a K/V head; the parameter count at
the published configuration; and the test that ties the cut to the model.

Tolerances.  Engine and reference are both float32 here and differ in the
order of their sums (a piece's chunked recurrence against a scan, online
softmax against one softmax): logits of some 0.1 agree to ``TOL`` 2e-5.
A selection that differed in one block, a compressed row off by one
position, a slope of the wrong layer or a state held in bfloat16 each
move them by 1e-3 and more (the last is measured below)."""

import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from chipbench.families import sparse_lightning as family  # noqa: E402
from chipbench.families import sparse_lightning_reference as ref  # noqa: E402
from paddle_tpu.kernels import block_sparse_attention as bsa  # noqa: E402
from paddle_tpu.kernels import ssm  # noqa: E402
from paddle_tpu.observability.metrics import MetricsRegistry  # noqa: E402
from paddle_tpu.serving import batched_decode as _bd  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5
B, PIECE, T, HEAD, VOCAB = 8, 32, 128, 64, 128
SPARSE = {"kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 2,
          "init_blocks": 1, "window_size": 16, "dense_len": 48}


def _cfg(mixers=("minicpm4", "lightning-attn", "lightning-attn", "minicpm4"),
         **more):
    return dict({
        "name": "tiny-sala", "family": "sparse_lightning",
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "lightning_nh": 4, "lightning_head_dim": 16, "vocab_size": VOCAB,
        "mixer_types": list(mixers), "num_hidden_layers": len(mixers),
        "rms_norm_eps": 1e-6, "rope_theta": 10000, "scale_emb": 12,
        "scale_depth": 1.4, "dim_model_base": 16,
        "compute_dtype": "float32", "sparse_config": SPARSE,
        "sparse_qk_gain": 4.0, "centre_tokens": 64}, **more)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, family.make_params(cfg, T, 7)


def _engine(cfg, params, block_tokens=B, **kw):
    return family.serving_engine(
        params, cfg, MetricsRegistry(),
        dict(max_len=T, max_slots=4, block_tokens=block_tokens,
             cache_blocks=256 // block_tokens, prefix_reuse=True,
             min_bucket=8, donate=False, **kw))


def _next_logits(eng):
    """The logits of every slot's NEXT decode step, from the engine's own
    arrays (nothing is written back)."""
    if not hasattr(eng, "_test_step"):
        eng._test_step = jax.jit(
            lambda p, last, pos, pk, pv, table, state:
            _bd.paged_step_logits(p, last, pos, pk, pv, table, eng.arch,
                                  state)[0])
    return np.asarray(eng._test_step(
        eng._p, eng._last, eng._pos, eng._pk, eng._pv,
        jnp.asarray(eng._table), eng._state))


def _reference_row(cfg, params, tokens):
    """The reference's logits after the last of ``tokens``."""
    lg = family.logits(params, np.asarray(tokens)[None], cfg)
    return np.asarray(lg)[0, -1]


@pytest.mark.parametrize("block_tokens", [8, 4])
def test_engine_matches_reference_on_both_sides_of_dense_len(
        model, monkeypatch, block_tokens):
    """A head of 64 tokens (past ``dense_len`` 48) served once; then ONE
    batch of a request that hits it (its pieces and steps select blocks)
    and a short one (dense), compared with the reference after the
    prefill and again after eight decode steps."""
    monkeypatch.setattr(_bd, "PREFILL_PIECE", PIECE)
    cfg, params = model
    rng = np.random.default_rng(3)
    draw = lambda n: rng.integers(0, VOCAB, n, dtype=np.int32)  # noqa: E731
    head = draw(HEAD)
    eng = _engine(cfg, params, block_tokens)
    warm = eng.submit(np.concatenate([head, draw(21)]), max_new_tokens=2)
    eng.run_until_idle()
    assert warm.done and eng.stats()["serving.state_snapshots_taken"] == 1
    reqs = []
    for prompt in (np.concatenate([head, draw(13)]), draw(10)):
        reqs.append(eng.submit(prompt, max_new_tokens=24))
        eng._admit()
    assert reqs[0].prefix_hit == HEAD and reqs[1].prefix_hit == 0
    assert eng.stats()["serving.state_snapshot_hits"] == 1
    for _round in range(2):
        got = _next_logits(eng)
        for req in reqs:
            slot = eng._slots.index(req)
            want = _reference_row(
                cfg, params, np.concatenate([req.prompt, req.tokens]))
            np.testing.assert_allclose(got[slot], want, rtol=0, atol=TOL)
        eng.step()
        eng.step()
        while eng._chunks:          # the tokens of every chunk sent
            eng._collect()
    st = eng.stats()
    # both forms ran in the same decode steps, and the counters say so
    assert st["serving.sparse_calls{form=dense,phase=decode}"] > 0
    assert st["serving.sparse_calls{form=sparse,phase=decode}"] > 0
    assert (st["serving.sparse_blocks_selected{phase=decode}"]
            == 5 * st["serving.sparse_calls{form=sparse,phase=decode}"])
    assert (st["serving.sparse_blocks_live{phase=decode}"]
            > st["serving.sparse_blocks_selected{phase=decode}"])
    eng.run_until_idle()
    assert all(r.done for r in reqs)


def test_compressed_rows_at_a_shared_documents_end(model, monkeypatch):
    """The chain the trie hands out carries the compressed keys of its
    blocks; the row whose window straddles the head's end lies in the
    tail's first block and is the suffix prefill's: after a hit the
    slot's compressed plane holds the means of ITS keys, row for row."""
    monkeypatch.setattr(_bd, "PREFILL_PIECE", PIECE)
    cfg, params = model
    rng = np.random.default_rng(5)
    draw = lambda n: rng.integers(0, VOCAB, n, dtype=np.int32)  # noqa: E731
    head = draw(HEAD)
    eng = _engine(cfg, params)
    eng.submit(np.concatenate([head, draw(20)]), max_new_tokens=2)
    eng.run_until_idle()
    prompt = np.concatenate([head, draw(21)])
    req = eng.submit(prompt, max_new_tokens=40)
    eng._admit()
    assert req.prefix_hit == HEAD
    slot = eng._slots.index(req)
    stride = SPARSE["kernel_stride"]
    layers = [i for i, m in enumerate(cfg["mixer_types"]) if m == "minicpm4"]
    # the reference's K of the FIRST sparse layer (it reads the table's
    # rows alone) and its compressed keys
    x = 12.0 * params["tok_emb.w"][jnp.asarray(prompt)].astype(jnp.float32)
    wa = {k: params[f"block{layers[0]}_{k}"] for k in ref._ATT_KEYS}
    K, _ = ref._keys(x, wa, 0, kv_heads=2, eps=1e-6, theta=1e4)
    want = np.asarray(ref._compressed(K, SPARSE["kernel_size"], stride))
    pc = np.asarray(eng._pk[eng.arch.sparse_layers + 0])    # [blocks, 4, 32]
    chain = eng._table[slot]
    per = B // stride
    n_rows = (len(prompt) - SPARSE["kernel_size"]) // stride + 1
    assert n_rows > HEAD // stride       # rows past the head's end exist
    for j in range(n_rows):
        c = j + 1                         # stored where its window ENDS
        got = pc[chain[c // per], c % per].reshape(2, -1)
        np.testing.assert_allclose(got, want[j], rtol=0, atol=1e-6,
                                   err_msg=f"compressed row {j}")
    # the straddling row (positions 62 .. 65) is in a block of the TAIL
    straddle = HEAD // stride            # c of the window 62 .. 65
    assert chain[straddle // per] not in set(
        eng.prefix_trie._path(prompt, HEAD)[-1:])
    eng.run_until_idle()


def test_selection_is_the_kv_heads_own():
    """``block_scores`` and ``select_blocks`` against NumPy, a K/V head at
    a time; the two K/V heads of one row select different blocks."""
    rng = np.random.default_rng(0)
    S, H, hk, D, NB, stride, block = 2, 4, 2, 16, 12, 2, 8
    per = B // stride
    q = rng.normal(size=(S, 1, H, D)).astype(np.float32) * 2.0
    pool_c = rng.normal(size=(1 + S * NB, per, hk * D)).astype(np.float32)
    table = 1 + np.arange(S * NB, dtype=np.int32).reshape(S, NB)
    pos = np.array([[93], [70]], np.int32)
    how = dict(group=H // hk, stride=stride, block=block)
    scores = np.asarray(bsa.block_scores(
        jnp.asarray(q), jnp.asarray(pool_c), jnp.asarray(table),
        jnp.asarray(pos), **how))
    sel = np.asarray(bsa.select_blocks(
        jnp.asarray(scores), jnp.asarray(pos), block=block, topk=3,
        init_blocks=1, window_blocks=2))
    for s in range(S):
        t = int(pos[s, 0])
        rows = pool_c[table[s]].reshape(NB * per, hk, D)
        n_c = (t + 1) // stride          # rows 1 .. n_c - 1 end at or before t
        for j in range(hk):
            qs = q[s, 0, j * 2:(j + 1) * 2]                       # [g, D]
            sc = qs @ rows[1:n_c, j].T / np.sqrt(D)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p = (p / p.sum(-1, keepdims=True)).sum(0)
            p = np.concatenate([[0.0], p, np.zeros(NB * per + 1 - n_c)])
            want = np.array([p[4 * b:4 * b + 5].max()
                             for b in range(NB * B // block)])
            np.testing.assert_allclose(scores[s, 0, j], want, atol=1e-6)
            own = t // block
            cand = np.arange(1, own - 1)
            top = np.sort(cand[np.argsort(-want[cand], kind="stable")[:3]])
            assert sel[s, 0, j].tolist() == [0, *top, own - 1, own]
    assert (sel[:, 0, 0] != sel[:, 0, 1]).any()


def test_state_in_bfloat16_fails_the_tolerance():
    """The recurrence through ``kernels/ssm.py`` (no convolution, a
    constant decay) against the reference's scan over 96 positions: the
    float32 state agrees to 1e-5, a state rounded to bfloat16 after every
    step is off by 100 times that."""
    rng = np.random.default_rng(1)
    H, D, n = 4, 16, 96
    q, k, v = (rng.normal(size=(n, H, D)).astype(np.float32) for _ in "qkv")
    slopes = np.asarray(family.slopes(_cfg())[0], np.float32)
    S_ref, want = np.zeros((H, D, D), np.float32), []
    for t in range(n):
        S_ref = (np.exp(-slopes)[:, None, None] * S_ref
                 + k[t][:, :, None] * v[t][:, None, :])
        want.append(np.einsum("hd,hde->he", q[t], S_ref))
    layer = dict(conv_w=None, conv_b=None,
                 dt_bias=jnp.full((H,), np.log(np.e - 1.0), jnp.float32),
                 A_log=jnp.log(slopes), D=jnp.zeros((H,)), heads=H, groups=H)

    def run(round_state):
        shape, tail = ssm.state_shapes(H, D, H, D, 1)
        S, tl = jnp.zeros((1,) + shape), jnp.zeros((1,) + tail)
        out = []
        for t in range(n):
            xbc = jnp.concatenate([v[t].ravel(), k[t].ravel(),
                                   q[t].ravel()])[None]
            y, S, tl = ssm.step(S, tl, xbc, jnp.zeros((1, H)),
                                jnp.ones((1,), bool), **layer)
            if round_state:
                S = S.astype(jnp.bfloat16).astype(jnp.float32)
            out.append(np.asarray(y).reshape(H, D))
        return np.max(np.abs(np.stack(out) - np.stack(want)))

    scale = np.max(np.abs(np.stack(want)))
    assert run(False) <= 1e-5 * scale
    assert run(True) >= 1e-3 * scale


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kv_planes_pool_holds_kv_heads_rows_a_position(model, dtype):
    """A K/V plane is stored head-major, ``[blocks, kv_heads, B, head_dim]``
    whatever the dtype (no row ``pool_rows`` would add): what a position
    STORES is what the model caches of it, a walk's copy of one selected
    block is one head's slab of K and of V, and both gauges are facts of
    the engine that a zeroed registry shows again."""
    cfg, params = model
    cfg = dict(cfg, compute_dtype=dtype)
    eng = _engine(cfg, {k: v.astype(dtype) for k, v in params.items()})
    arch, item = eng.arch, jnp.dtype(dtype).itemsize
    hk, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    for plane in range(arch.sparse_layers):
        assert arch.plane_block_shapes(plane, B, dtype) == ((hk, B, dh),) * 2
        assert arch.plane_tokens_axis(plane) == 1
        assert eng._pk[plane].shape[1:] == (hk, B, dh) == eng._pv[
            plane].shape[1:]
    assert arch.plane_tokens_axis(arch.sparse_layers) == 0
    st = eng.stats()
    assert (st["serving.kv_stored_bytes_per_token"]
            == st["serving.kv_bytes_per_token"]
            == arch.kv_bytes_per_token(item)
            == 2 * (2 * hk * dh + hk * dh // SPARSE["kernel_stride"]) * item)
    assert st["serving.kv_write_fill"] == 1.0
    eng._reg.reset(prefix="serving.")
    assert (eng.stats()["serving.sparse_walk_bytes_per_block"]
            == 2 * SPARSE["block_size"] * dh * item)


def test_parameter_count_at_the_published_configuration():
    """9.48B by the layer equations, to the digit; 2,820,544,768 held."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "minicpm-sala.json")) as f:
        cfg = json.load(f)
    S, L = 52_428_800 + 201_326_592 + 8_192, 83_886_080 + 201_326_592 + 8_192
    assert family.parameters(cfg) == cfg["parameters_held"] == (
        2 * S + 6 * (L + 384) + 2 * 300_843_008 + 4_096) == 2_820_544_768
    whole = dict(cfg, **cfg["published"])
    assert family.parameters(whole) == (
        8 * S + 24 * (L + 384) + 2 * 300_843_008 + 4_096) == 9_477_108_736
    # the held layers are the published stack's 9 .. 16, two S to six L
    first = cfg["first_layer"]
    assert (cfg["published"]["mixer_types"][first:first + 8]
            == cfg["mixer_types"])
    assert "".join(family.KINDS[m] for m in cfg["mixer_types"]) == "SLLLLLLS"
    assert sorted(cfg["reduced"]) == ["mixer_types", "num_hidden_layers"]


def test_the_held_layers_are_the_uncut_models_layers_9_to_16():
    """A tiny 32-layer stack with the PUBLISHED ``mixer_types``: the held
    configuration (layers 9-16, ``first_layer`` 9, the published depth in
    ``published``) fed the uncut reference's residual at layer 9 gives
    its residual after layer 16: the slopes' layer factor and the
    residual scale follow the published index and depth."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "minicpm-sala.json")) as f:
        published = json.load(f)["published"]["mixer_types"]
    whole = _cfg(published)
    params = family.make_params(dict(whole, centre_tokens=8), T, 11)
    tokens = np.random.default_rng(2).integers(0, VOCAB, 80, dtype=np.int32)

    def upto(n):
        """The uncut reference's residual after its first ``n`` layers."""
        z = family._dims(whole)
        out = ref.trunk(params, tokens, z["mixers"][:n], family.slopes(whole),
                        z["heads"], z["kv"], z["H"], z["sparse"],
                        **family._how(whole))
        return np.concatenate([np.asarray(rows) for *_, rows in out])

    before, after = upto(9), upto(17)
    held = _cfg(published[9:17], first_layer=9,
                published={"num_hidden_layers": 32,
                           "mixer_types": published})
    mine = {k: v for k, v in params.items() if not k.startswith("block")}
    for i in range(8):
        mine.update({k.replace(f"block{9 + i}_", f"block{i}_"): v
                     for k, v in params.items()
                     if k.startswith(f"block{9 + i}_")})
    out = ref.trunk(mine, tokens, *family._layout(held), residual=before,
                    **family._how(held))
    got = np.concatenate([np.asarray(rows) for *_, rows in out])
    np.testing.assert_allclose(got, after, rtol=0, atol=1e-5)
    # and a held configuration that forgot its place in the stack differs
    lost = dict(held, first_layer=0)
    out = ref.trunk(mine, tokens, *family._layout(lost), residual=before,
                    **family._how(lost))
    assert np.max(np.abs(np.concatenate(
        [np.asarray(rows) for *_, rows in out]) - after)) > 1e-3
