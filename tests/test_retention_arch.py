"""``serving.arch.PowerRetention`` through ``ServingEngine`` on the CPU
at 3 layers x 64 wide: prefill pieces and then decode through the state
against ``models/retention_reference.py``'s logits (the quadratic form),
several slots of different lengths at once, a slot reused by a second
prompt; the engine with no plane builds no pool and no table and refuses
``prefix_reuse=True`` and a draft, with the reason; the gauges and
counters; the parameter count of the published model from the layer
equations, shape-only."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as pt  # noqa: E402
import tiny  # noqa: E402
from paddle_tpu.observability.metrics import MetricsRegistry  # noqa: E402
from paddle_tpu.serving.arch import PowerRetention  # noqa: E402
from tiny import retention as fam  # noqa: E402

L, H, HK, D, DH, F, V = (fam.sizes[k] for k in (
    "layers", "heads", "kv_heads", "d", "dh", "f", "rows"))


@pytest.fixture(scope="module")
def served():
    params = fam.init(0)
    reg = MetricsRegistry()
    eng = fam.engine(params, registry=reg, compute_dtype="float32")[0]
    rng = np.random.default_rng(1)
    # more prompts than slots, so slots are reused; one and several
    # pieces, every bucket width, a prompt that ends on a piece boundary
    prompts = [rng.integers(0, V, n, dtype=np.int32)
               for n in (300, 5, 140, 17, 128, 61)]
    outs = eng.generate_many(prompts, max_new_tokens=12)
    return params, eng, reg, prompts, outs


def test_engine_through_pieces_and_decode_is_the_reference(served):
    params, eng, _, prompts, outs = served
    # float32 end to end: greedy tokens ARE the reference's argmax
    assert max(tiny.gaps(fam, params, prompts, outs)) <= 1e-4


@pytest.mark.parametrize("switch", [
    {"gate": False}, {"normaliser": False}, {"degree": 1},
    {"rotary": False}, {"piece": 128}, {"grouped": False}])
def test_each_line_of_the_layer_is_seen_by_the_comparison(served, switch):
    params, _, _, prompts, outs = served
    assert max(tiny.gaps(fam, params, prompts, outs, **switch)) > 0.01


def test_a_reused_slot_starts_from_zeros(served):
    params, eng, _, prompts, _ = served
    # the same prompt alone in a fresh engine gives the same tokens as it
    # gave in a slot that an earlier, longer request had left its state in
    again = fam.engine(params, compute_dtype="float32")[0].generate_many(
        [prompts[3]], max_new_tokens=12)
    assert np.array_equal(again[0], served[4][3])


def test_no_plane_no_pool_no_table(served):
    _, eng, reg, _, _ = served
    assert fam.arch().planes == () and fam.arch().kv_planes == 0
    assert eng.kv_pool is None and eng.prefix_trie is None
    assert eng._pk == () and eng._pv == ()
    assert eng._table.shape == (3,) and not eng._table.any()
    stats = eng.stats()
    assert stats["serving.kv_planes"] == 0
    assert stats["serving.kv_pool_bytes"] == 0
    assert stats["serving.kv_blocks_total"] == 0
    assert stats["serving.retention_layers"] == L
    assert stats["serving.retention_degree"] == 2
    assert stats["serving.retention_state_rows{kind=published}"] == 136
    assert stats["serving.retention_state_rows{kind=stored}"] == 144
    per_slot = L * HK * (144 * DH + 144) * 4
    assert stats["serving.state_bytes_per_slot"] == per_slot
    assert stats["serving.state_bytes"] == 3 * per_slot
    assert not any(k.startswith("serving.paged_") for k in stats)
    assert stats["serving.retention_slot_steps"] > 0
    # by the retention CALL's width, which is the piece's: 300 rows are
    # one piece of 400 (the widest rung, capped at max_len), 140 one of
    # 256; 128 and 61 one of 128 each; 17 one of 32; 5 one of 8
    assert stats["serving.retention_piece_rows{width=400}"] == 400
    assert stats["serving.retention_piece_rows{width=256}"] == 256
    assert stats["serving.retention_piece_rows{width=128}"] == 128 * 2
    assert stats["serving.retention_piece_rows{width=32}"] == 32
    assert stats["serving.retention_piece_rows{width=8}"] == 8
    # every one of them started its prompt: no call read a state
    assert stats["serving.retention_calls{fresh=1}"] == 6
    assert "serving.retention_calls{fresh=0}" not in stats
    assert stats["serving.prefill_pieces{width=400}"] == 1
    assert stats["serving.prefill_pieces{width=256}"] == 1


def test_retention_calls_count_the_pieces_that_continue_a_prompt(monkeypatch):
    """A prompt of several pieces: ONE retention call a piece a layer,
    the first ``fresh`` (it never reads the slot), the rest not."""
    from paddle_tpu.serving import batched_decode as _bd

    monkeypatch.setattr(_bd, "PREFILL_PIECE", 32)
    params, reg = fam.init(0), MetricsRegistry()
    eng = fam.engine(params, registry=reg, compute_dtype="float32")[0]
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, V, n, dtype=np.int32) for n in (70, 20)]
    outs = eng.generate_many(prompts, max_new_tokens=4)
    assert max(tiny.gaps(fam, params, prompts, outs)) <= 1e-4
    stats = eng.stats()
    # 70 tokens: pieces of 32, 32 and 8 rows; 20: one of 32
    assert stats["serving.retention_calls{fresh=1}"] == 2
    assert stats["serving.retention_calls{fresh=0}"] == 2
    assert stats["serving.retention_piece_rows{width=32}"] == 32 * 3
    assert stats["serving.retention_piece_rows{width=8}"] == 8


def test_state_spec_is_two_float32_arrays_a_layer():
    spec = fam.arch().state_spec(jnp.bfloat16)
    assert len(spec) == L
    assert spec[0] == (((HK, 144, DH), jnp.float32), ((HK, 144), jnp.float32))
    assert fam.arch().attn_form == "retention"
    assert fam.arch().retention_layers == L


def test_refusals_say_why():
    params = fam.init(0)
    with pytest.raises(ValueError, match="hold recurrent state"):
        pt.serving.ServingEngine(params, arch=fam.arch(), max_len=64,
                                 prefix_reuse=True)
    with pytest.raises(ValueError, match="no block pool"):
        pt.serving.ServingEngine(params, arch=fam.arch(), max_len=64,
                                 prefix_reuse=False, cache_blocks=8)
    from paddle_tpu.serving.speculative import validate_draft

    with pytest.raises(ValueError, match="state rolled back"):
        validate_draft(params, params, fam.arch(), 64)
    with pytest.raises(ValueError, match="degree 3"):
        PowerRetention(L, H, HK, D, DH, F, degree=3)
    with pytest.raises(ValueError, match="FFN is 128 wide"):
        PowerRetention(L, H, HK, D, DH, 256).check_params(params, 64)


def test_bfloat16_engine_stays_within_a_margin_of_the_reference():
    params = {k: np.asarray(jnp.asarray(v, jnp.bfloat16).astype(jnp.float32))
              for k, v in fam.init(4).items()}
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, V, n, dtype=np.int32) for n in (200, 33)]
    outs = fam.engine(params, compute_dtype="bfloat16")[0].generate_many(
        prompts, max_new_tokens=10)
    assert max(tiny.gaps(fam, params, prompts, outs)) < 0.05


def test_the_published_model_counts_14_77_billion_parameters():
    """From the layer equations at the published widths, shape-only: the
    check that the layout is the model's (the card's 14B; Qwen3-14B's
    14.8B)."""
    d, h, hk, dh, f, rows, layers = 5120, 40, 8, 128, 17408, 151936, 40
    a = PowerRetention(layers, h, hk, d, dh, f, rope_theta=1e6)
    layer = {"att_q.w": (d, h * dh), "att_k.w": (d, hk * dh),
             "att_v.w": (d, hk * dh), "att_out.w": (h * dh, d),
             "att_gate.w": (d, hk), "att_gate.b": (hk,),
             "att_qnorm.scale": (dh,), "att_knorm.scale": (dh,),
             "norm1.scale": (d,), "norm2.scale": (d,),
             "ffn_gate.w": (d, f), "ffn_up.w": (d, f), "ffn_down.w": (f, d)}
    one = sum(int(np.prod(s)) for s in layer.values())
    assert one == 330_352_904
    total = layers * one + 2 * rows * d + d
    assert total == 14_769_945_920
    # a slot's state: 34,080,768 B a layer at the published 8,256 rows,
    # 34,344,960 at the 8,320 the layout stores
    assert hk * (8256 * dh + 8256) * 4 == 34_080_768
    assert a.state_bytes_per_slot(jnp.bfloat16) == layers * hk * (
        8320 * dh + 8320) * 4
