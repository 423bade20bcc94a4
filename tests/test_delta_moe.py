"""``serving.arch.DeltaMoE`` and ``kernels/delta.py`` on the CPU: the
parameter counts of the published model and of one chip's share from the
layer equations, shape-only; the step (both backends, a dead slot
bit-equal) and the WY form (every rung, starting a prompt and continuing
one, ``beta`` on both sides of 1, both ends of the init's decays) against
the recurrence row by row; prefill pieces and then decode through the
cache, the in-place state and a prefix hit that starts from a snapshot
against ``chipbench/families/delta_moe_reference.py``'s logits (one
position at a time); each line of the mathematics seen by the comparison;
the eight shares of the router's experts adding up to the uncut layer;
gauges, counters and refusals."""

import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import tiny  # noqa: E402
from chipbench.families import delta_moe as family  # noqa: E402
from chipbench.families import delta_moe_reference as ref  # noqa: E402
from paddle_tpu.kernels import delta  # noqa: E402
from paddle_tpu.kernels.xla_ref import oracle_tol  # noqa: E402
from paddle_tpu.observability.metrics import MetricsRegistry  # noqa: E402
from paddle_tpu.serving import batched_decode as _bd  # noqa: E402
from paddle_tpu.serving.arch import DeltaMoE  # noqa: E402
from tiny import delta_moe as fam  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "solar-open2-250b.json")))
TINY = tiny.delta_config()
V, PIECE = TINY["vocab_size"], 32
LAYOUT = family._layout(TINY)


# -- the counts ---------------------------------------------------------------

def test_the_published_model_counts_250_288_105_216_parameters():
    published = dict(CFG, **CFG["published"])
    assert family.parameters(published) == 250_288_105_216
    assert family.parameters(CFG) == CFG["parameters_held"] == 3_308_377_920
    z = family._dims(CFG)
    mixer = lambda kind: sum(                                  # noqa: E731
        int(np.prod(s)) for n, s in family.layer_shapes(kind, z).items()
        if n.startswith(("att_", "delta_")))
    assert (mixer("gqa"), mixer("delta")) == (109_051_904, 137_740_480)
    # a slot's state and convolution rows, a cached token's one plane
    a = family._arch(CFG)
    assert a.state_bytes_per_slot(jnp.bfloat16) == 3 * (4_194_304 + 147_456)
    assert a.kv_bytes_per_token(2) == 4096
    assert (a.delta_layers, len(a.planes), a.moe_layers) == (3, 1, 4)


# -- the kernels --------------------------------------------------------------

H, D, TAPS = 2, 32, 4


def _layer(rng):
    return {"conv_w": jnp.asarray(rng.uniform(-0.5, 0.5, (3 * H * D, TAPS)),
                                  jnp.float32), "heads": H}


def _rows(rng, n, strong, dtype="float32"):
    """Rows of a call: ``beta`` on both sides of 1; the log decay at the
    strong end of the init (e^-1.6 a row) or its weak end."""
    q, k, v = (jnp.asarray(rng.normal(size=(n, H * D)), dtype)
               for _ in range(3))
    lo, hi = (1.2, 1.6) if strong else (0.001, 0.02)
    g = -jnp.asarray(rng.uniform(lo, hi, (n, H * D)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 1.95, (n, H)), jnp.float32)
    return q, k, v, g, beta


def _state(rng, n, dtype="float32"):
    s_shape, t_shape = delta.state_shapes(H, D, TAPS)
    return (jnp.asarray(rng.normal(size=(n,) + s_shape), jnp.float32),
            jnp.asarray(rng.normal(size=(n,) + t_shape), dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("strong", [False, True], ids=["weak", "strong"])
def test_delta_step_backends_agree_and_leave_dead_slots_alone(strong, dtype):
    rng = np.random.default_rng(3)
    layer = _layer(rng)
    S, tail = _state(rng, 5, dtype)
    rows = _rows(rng, 5, strong, dtype)
    valid = jnp.asarray([True, False, True, True, False])
    want = delta.delta_step_ref(S, tail, *rows, valid, **layer)
    got = jax.jit(lambda *a: delta.delta_step_pallas(
        *a, **layer, interpret=True))(S, tail, *rows, valid)
    tol = oracle_tol("delta_rule", dtype, "fwd")
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)
    for dead in (1, 4):
        assert np.array_equal(np.asarray(got[1][dead]), np.asarray(S[dead]))
        assert np.array_equal(np.asarray(got[2][dead], np.float32),
                              np.asarray(tail[dead], np.float32))
        assert not np.asarray(got[0][dead]).any()
    # and the step is the recurrence's one row
    r = jnp.concatenate([tail, jnp.concatenate(rows[:3], -1).astype(
        tail.dtype)[:, None]], 1)
    qh, kh, vh = delta._heads(delta._conv(r, layer["conv_w"])[:, 0], H)
    o, Sn = delta.delta_scan_ref(S[0], qh[:1], kh[:1], vh[:1],
                                 rows[3][:1].reshape(1, H, D), rows[4][:1])
    np.testing.assert_allclose(np.asarray(want[0][0]),
                               np.asarray(o).reshape(-1), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(want[1][0]), np.asarray(Sn),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("strong", [False, True], ids=["weak", "strong"])
@pytest.mark.parametrize("fresh", [False, True], ids=["continues", "fresh"])
@pytest.mark.parametrize("width,real", [(8, 5), (32, 32), (128, 100),
                                        (256, 256), (512, 512), (40, 33)])
def test_delta_chunk_is_the_scan(width, real, fresh, strong):
    """The WY form in tiles of 64 (sub-blocks of 16; a narrow rung one
    short tile) against the recurrence row by row: at the strong end 512
    rows decay a lane by e^-700 and no exponent is ever positive."""
    rng = np.random.default_rng(width + 2 * fresh + strong)
    layer = _layer(rng)
    S, tail = _state(rng, 3)
    q, k, v, g, beta = _rows(rng, width, strong)
    valid = jnp.arange(width) < real
    o, Sn, tn = jax.jit(lambda *a: delta.delta_chunk(*a, **layer))(
        S, tail, jnp.int32(1), jnp.asarray(fresh), q, k, v, g, beta, valid)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(Sn).all())
    t0 = jnp.zeros_like(tail[1]) if fresh else tail[1]
    S0 = jnp.zeros_like(S[1]) if fresh else S[1]
    r = jnp.concatenate([t0, jnp.concatenate([q, k, v], -1)], 0)
    qh, kh, vh = delta._heads(delta._conv(r, layer["conv_w"]), H)
    want, Sw = delta.delta_scan_ref(
        S0, qh[:real], kh[:real], vh[:real], g[:real].reshape(real, H, D),
        beta[:real])
    tol = oracle_tol("delta_rule", "float32", "fwd")
    np.testing.assert_allclose(np.asarray(o[:real]),
                               np.asarray(want).reshape(real, -1),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(Sn[1]), np.asarray(Sw),
                               rtol=tol, atol=tol)
    # the other slots are as they were; the tails end at the last real row
    for other in (0, 2):
        assert np.array_equal(np.asarray(Sn[other]), np.asarray(S[other]))
        assert np.array_equal(np.asarray(tn[other]), np.asarray(tail[other]))
    assert np.array_equal(np.asarray(tn[1]),
                          np.asarray(r[real:real + TAPS - 1]))


def test_the_reference_walks_one_position_at_a_time_and_in_blocks(
        monkeypatch):
    """Cutting a sequence into blocks of rows (the state and the
    convolution's rows carried) changes nothing."""
    params = fam.init(5)
    tokens = np.random.default_rng(0).integers(0, V, (1, 90), np.int32)
    whole = ref.forward(params, tokens, *LAYOUT)
    monkeypatch.setattr(ref, "ROWS", 32)
    monkeypatch.setattr(ref, "QUERY_ROWS", 16)
    np.testing.assert_allclose(ref.forward(params, tokens, *LAYOUT), whole,
                               rtol=1e-5, atol=1e-5)


# -- the engine against the reference ----------------------------------------

@pytest.fixture(scope="module")
def served():
    params = fam.init(0)
    reg = MetricsRegistry()
    eng = fam.engine(params, registry=reg, compute_dtype="float32")[0]
    rng = np.random.default_rng(1)
    # more prompts than slots, so slots are reused; one and several
    # pieces, every rung, a prompt that ends on a tile's boundary
    prompts = [rng.integers(0, V, n, dtype=np.int32)
               for n in (300, 5, 140, 17, 128, 61)]
    outs = eng.generate_many(prompts, max_new_tokens=12)
    return params, eng, reg, prompts, outs


def test_engine_through_pieces_and_decode_is_the_reference(served):
    params, _, _, prompts, outs = served
    # float32 end to end: greedy tokens ARE the reference's argmax
    assert max(tiny.gaps(fam, params, prompts, outs)) <= 1e-4


@pytest.mark.parametrize("switch", [
    {"beta_scale": 1.0}, {"decay": "head"}, {"delta_term": False},
    {"l2norm": False}, {"gqa_gate": False}, {"route_norm": False},
    {"routed": False}, {"shared": False},
    # the state lost where the longest prompt hands over to its decode
    {"lost": (300,)}], ids=lambda s: next(iter(s)))
def test_each_line_of_the_layers_is_seen_by_the_comparison(served, switch):
    params, _, _, prompts, outs = served
    assert max(tiny.gaps(fam, params, prompts, outs, **switch)) > 0.01


@pytest.fixture(scope="module")
def hit():
    """Two heads served once (``head + 3`` ends a piece ON the head's last
    block), then questions over them: every one starts from the head's
    snapshot."""
    old, _bd.PREFILL_PIECE = _bd.PREFILL_PIECE, PIECE
    try:
        params = fam.init(0)
        reg = MetricsRegistry()
        # three snapshot rows: an eighth of 256 blocks of 2 KiB over 19,200 B
        eng = fam.engine(params, registry=reg, compute_dtype="float32",
                         prefix_reuse=True, cache_blocks=256,
                         min_bucket=8)[0]
        rng = np.random.default_rng(7)
        heads = [rng.integers(0, V, 2 * PIECE, dtype=np.int32)
                 for _ in range(2)]
        tail = lambda n: rng.integers(0, V, n, dtype=np.int32)  # noqa: E731
        eng.generate_many([np.concatenate([h, tail(3)]) for h in heads],
                          max_new_tokens=2)
        prompts = [np.concatenate([heads[i], tail(n)])
                   for i, n in ((0, 5), (1, 13), (0, 40), (1, 9))]
        outs = eng.generate_many(prompts, max_new_tokens=10)
        return params, eng, heads, prompts, outs
    finally:
        _bd.PREFILL_PIECE = old


def test_a_hit_that_starts_from_a_snapshot_is_the_reference(hit):
    params, eng, heads, prompts, outs = hit
    st = eng.stats()
    assert st["serving.state_snapshot_hits"] == 4
    assert st["serving.prefix_hit_tokens"] >= 4 * len(heads[0])
    assert max(tiny.gaps(fam, params, prompts, outs)) <= 1e-4


def test_a_zeroed_or_a_swapped_snapshot_is_seen_by_the_comparison(hit):
    params, _, heads, prompts, outs = hit
    at = len(heads[0])
    assert min(tiny.gaps(fam, params, prompts, outs, lost=(at,))) > 0.01
    # the OTHER head's snapshot restored: its state before position ``at``
    for mine, other in ((0, 1), (1, 0)):
        states = []
        ref.trunk(params, np.concatenate([heads[other], prompts[0][:1]]),
                  *LAYOUT, capture=(at, states))
        assert len(states) == 3
        assert tiny.gaps(fam, params, prompts[mine:mine + 1],
                         outs[mine:mine + 1], inject=(at, states))[0] > 0.01


def test_the_shares_of_a_routed_layer_sum_to_the_uncut_layer():
    """Eight chips hold two experts each: their routed parts, and the
    shared expert counted ONCE, add up to the layer that holds all 16."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(40, TINY["hidden_size"])), jnp.float32)
    whole = ref.routed_ffn(fam.init(2, share=(0, 16)), 1, x, 3, (0, 16), 1.0)
    parts = sum(ref.routed_ffn(fam.init(2, share=(f, 2)), 1, x, 3, (f, 2),
                               1.0, shared=f == 0) for f in range(0, 16, 2))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    # and the program's routed layer is the reference's, share by share
    from paddle_tpu.serving.arch import routed_ffn

    class Rows:
        valid = jnp.ones((40,), bool)

    for first in (0, 6):
        p = fam.init(2, share=(first, 2))
        u = ref._rms(x, 1.0, 1e-5)
        got, _ = routed_ffn(lambda n: jnp.asarray(p[f"block1_{n}"]), u,
                            Rows, (first, 2), 3)
        want = ref.routed_ffn(p, 1, x, 3, (first, 2), 1.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


def test_gauges_counters_and_refusals(served):
    _, eng, reg, prompts, outs = served
    st = eng.stats()
    assert st["serving.delta_layers"] == 3 and st["serving.delta_heads"] == 4
    per_slot = 3 * (4 * 4 * 16 * 16 + 4 * 3 * 3 * 4 * 16)
    assert st["serving.delta_state_bytes_per_slot"] == per_slot
    assert st["serving.state_bytes_per_slot"] == per_slot
    assert st["serving.kv_planes"] == 1 and st["serving.moe_layers"] == 4
    assert st["serving.delta_slot_steps"] % (3 * eng.decode_chunk) == 0
    assert st["serving.delta_piece_rows{width=256}"] == 256
    assert "serving.state_snapshot_bytes" not in st     # no trie, no snapshot
    assert [[a.shape for a in layer] for layer in eng._state] == [
        [(3, 4, 16, 16), (3, 3, 192)]] * 3
    with pytest.raises(ValueError, match="rolled back"):
        fam.engine(fam.init(0), prefix_reuse=False, draft_params=fam.init(0))
    with pytest.raises(ValueError, match="gqa_layers"):
        DeltaMoE(4, (), 4, 2, 16, 64, 4, 16, 4, 16, 3, (0, 4))
    with pytest.raises(ValueError, match="projects keys to"):
        bad = fam.init(0)
        bad["block3_delta_k.w"] = bad["block3_delta_k.w"][:, :-1]
        fam.arch().check_params(bad, 64)
    with pytest.raises(ValueError, match="hold 4 experts"):
        fam.arch(share=(0, 8)).check_params(fam.init(0), 64)


def test_bfloat16_engine_stays_within_a_margin_of_the_reference():
    params = {k: np.asarray(jnp.asarray(v, jnp.bfloat16).astype(jnp.float32))
              for k, v in fam.init(4).items()}
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, V, n, dtype=np.int32) for n in (200, 33)]
    outs = fam.engine(params, compute_dtype="bfloat16")[0].generate_many(
        prompts, max_new_tokens=10)
    assert max(tiny.gaps(fam, params, prompts, outs)) < 0.3
