"""``serving.arch.MambaMoE`` through ``ServingEngine`` on the CPU at
seven layers x 48 wide, at widths that keep the published model's
oddness (an expert width that is not a multiple of 128, fewer groups
than heads, two K/V heads under eight query heads): prefill pieces and
then decode through the cache and the in-place state against
``models/ssm_moe_reference.py``'s logits (the recurrence a scan over
positions), several slots of different lengths at once, a slot reused by
a second prompt; each line of the mathematics seen by the comparison;
the eight shares of the router's experts adding up to the uncut layer;
the gauges, counters and refusals; the parameter count of the published
model from the layer equations, shape-only."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import tiny  # noqa: E402
from paddle_tpu.kernels import ssm  # noqa: E402
from paddle_tpu.models import ssm_moe_reference as ref  # noqa: E402
from paddle_tpu.observability.metrics import MetricsRegistry  # noqa: E402
from paddle_tpu.serving import arch as _arch  # noqa: E402
from paddle_tpu.serving.arch import MambaMoE  # noqa: E402
from tiny import ssm_moe as fam  # noqa: E402

Z = fam.sizes
PATTERN, D, V = Z["pattern"], Z["d"], Z["rows"]
# attention: 4 query heads a K/V head; Mamba-2: 4 heads a group
HEADS, KV, DH = Z["heads"], Z["kv_heads"], Z["dh"]
H, P, G, N, TAPS = (Z[k] for k in ("ssm_heads", "ssm_dh", "groups", "state",
                                   "taps"))
E, WIDTH, TOP_K, SCALE = Z["e"], Z["experts"], Z["top_k"], Z["scale"]
CONV = H * P + 2 * G * N


@pytest.fixture(scope="module")
def served():
    params = fam.init(0)
    reg = MetricsRegistry()
    eng = fam.engine(params, registry=reg, compute_dtype="float32")[0]
    rng = np.random.default_rng(1)
    # more prompts than slots, so slots are reused; one and several
    # chunks, every bucket width, a prompt that ends on a chunk boundary
    prompts = [rng.integers(0, V, n, dtype=np.int32)
               for n in (300, 5, 140, 17, 128, 61)]
    outs = eng.generate_many(prompts, max_new_tokens=12)
    return params, eng, reg, prompts, outs


def test_engine_through_pieces_and_decode_is_the_reference(served):
    params, _, _, prompts, outs = served
    # float32 end to end: greedy tokens ARE the reference's argmax
    assert max(tiny.gaps(fam, params, prompts, outs)) <= 1e-4


@pytest.mark.parametrize("switch", [
    {"skip": False}, {"dt_bias": False}, {"gate_first": False},
    {"group_norm": False}, {"squared": False}, {"route_scaled": False},
    {"routed": False}, {"shared": False}, {"tails_every": 64},
    {"state_every": 64}, {"score_scaled": False},
    # the state lost where the longest prompt hands over to its decode
    {"lost": np.array([300], np.int32)}], ids=lambda s: next(iter(s)))
def test_each_line_of_the_layers_is_seen_by_the_comparison(served, switch):
    params, _, _, prompts, outs = served
    assert max(tiny.gaps(fam, params, prompts, outs, **switch)) > 0.01


def test_a_reused_slot_starts_from_a_zero_state_and_zero_tails(served):
    params, _, _, prompts, outs = served
    # the same prompt alone in a fresh engine gives the same tokens as it
    # gave in a slot that an earlier, longer request had left its state
    # and its convolution's rows in
    again = fam.engine(params, compute_dtype="float32")[0].generate_many(
        [prompts[3]], max_new_tokens=12)
    assert np.array_equal(again[0], outs[3])


def test_a_prompt_of_several_pieces_threads_state_and_tails(monkeypatch):
    """Pieces of 32 rows: the second and third continue the first's
    state and tails (``fresh`` only where a prompt starts), and a chunk
    of 8 rows divides each."""
    from paddle_tpu.serving import batched_decode as _bd

    monkeypatch.setattr(_bd, "PREFILL_PIECE", 32)
    params, reg = fam.init(0), MetricsRegistry()
    eng = fam.engine(params, registry=reg, compute_dtype="float32")[0]
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, V, n, dtype=np.int32) for n in (70, 20)]
    outs = eng.generate_many(prompts, max_new_tokens=4)
    assert max(tiny.gaps(fam, params, prompts, outs)) <= 1e-4
    # a reference that forgets either at a piece boundary is another
    # function on the rows the engine generated: the agreement above is
    # of state and tails carried over the boundaries at 32 and 64
    sound = fam.reference(params, outs[0])[69:]
    for switch in ({"state_every": 32}, {"tails_every": 32}):
        other = fam.reference(params, outs[0], **switch)[69:]
        assert float(np.abs(other - sound).max()) > 0.01, switch
    stats = eng.stats()
    # 70 tokens: pieces of 32, 32 and 8 rows; 20: one of 32
    assert stats["serving.prefill_pieces{width=32}"] == 3
    assert stats["serving.prefill_pieces{width=8}"] == 1


def test_state_planes_gauges_and_counters(served):
    _, eng, reg, _, _ = served
    a = fam.arch()
    assert (a.ssm_layers, a.moe_layers, len(a.planes)) == (3, 3, 1)
    assert a.kv_planes == 1 and a.rows_per_entry == 4
    assert a.last_routed == 6 and a.experts_held == 4
    assert a.expert_form == "relu2" and a.count_names == _arch.MOE_COUNTS
    spec = a.state_spec(jnp.bfloat16)
    # four heads of 8 lanes side by side: [H / 4, N, 4 P]; 3 rows of tails
    assert spec == ((((2, 16, 32), jnp.float32),
                     ((3, CONV), jnp.dtype(jnp.bfloat16))),) * 3
    assert ssm.heads_per_row(64, 64, 8) == 2
    assert ssm.state_shapes(64, 64, 8, 128, 4) == ((32, 128, 128), (3, 6144))
    stats = eng.stats()
    assert stats["serving.ssm_layers"] == 3
    per_slot = 3 * (2 * 16 * 32 * 4 + 3 * CONV * 4)
    assert stats["serving.ssm_state_bytes_per_slot"] == per_slot
    assert stats["serving.state_bytes_per_slot"] == per_slot
    # float32 pool: pool_rows adds nothing to 2 heads
    assert stats["serving.kv_bytes_per_token"] == 2 * KV * DH * 4
    assert stats["serving.kv_stored_bytes_per_token"] == 2 * KV * DH * 4
    assert stats["serving.moe_layers"] == 3
    assert stats["serving.moe_expert_bytes"] == 2 * D * E * 4
    assert stats["serving.moe_rows{phase=decode}"] > 0
    # 300 rows are one piece of 400 (the widest rung, capped at max_len)
    assert stats["serving.prefill_pieces{width=400}"] == 1
    # bfloat16 pool: 2 K/V heads are stored as 8 rows
    gauges = fam.arch().gauges({"tok_emb.w": jnp.zeros((1, 1), jnp.bfloat16),
                            **{f"block6_experts_{m}.w": jnp.zeros(
                                (4, E, D), jnp.bfloat16)
                               for m in ("up", "down")}})
    assert gauges["kv_stored_bytes_per_token"][0] == 2 * 8 * DH * 2
    assert gauges["moe_expert_bytes"][1].endswith("two matrices")


def test_refusals_say_why():
    params = fam.init(0)
    # prefix_reuse=True is served from state snapshots since PR 57
    # (tests/test_state_prefix_hit.py); a draft still is not
    from paddle_tpu.serving.speculative import validate_draft

    with pytest.raises(ValueError, match="rolled back"):
        validate_draft(params, params, fam.arch(), 64)
    with pytest.raises(ValueError, match="pattern characters"):
        MambaMoE("MEX", HEADS, KV, DH, D, H, P, G, N, TAPS, WIDTH, TOP_K,
                 (0, 4))
    with pytest.raises(ValueError, match="do not pack"):
        MambaMoE("M", HEADS, KV, DH, D, 6, 64, 2, N, TAPS, WIDTH, TOP_K,
                 (0, 4))
    with pytest.raises(ValueError, match="projects to"):
        bad = dict(params)
        bad["block5_ssm_in.w"] = bad["block5_ssm_in.w"][:, :-1]
        fam.arch().check_params(bad, 64)
    with pytest.raises(ValueError, match="hold 4 experts"):
        fam.arch(share=(0, 8)).check_params(params, 64)
    with pytest.raises(ValueError, match="not one of"):
        _arch.routed_ffn(None, jnp.zeros((1, 4)), None, (0, 1), 1,
                         form="gelu")


def test_bfloat16_engine_stays_within_a_margin_of_the_reference():
    params = {k: np.asarray(jnp.asarray(v, jnp.bfloat16).astype(jnp.float32))
              for k, v in fam.init(4).items()}
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, V, n, dtype=np.int32) for n in (200, 33)]
    outs = fam.engine(params, compute_dtype="bfloat16")[0].generate_many(
        prompts, max_new_tokens=10)
    assert max(tiny.gaps(fam, params, prompts, outs)) < 0.1


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The program's routed FFN on each of the eight shares of the
    router's 16 experts (two a share), the routed parts summed and the
    shared expert counted ONCE, against the reference's uncut layer
    (all 16); and a share's own result against the reference given the
    same share."""
    i, rows = 1, 24
    whole = fam.init(7, share=(0, WIDTH))
    # rows of unit RMS: the reference's layer norms what it is given (a
    # unit gain here), the program's is handed normed rows
    h = np.random.default_rng(3).normal(size=(rows, D))
    h = jnp.asarray(h / np.sqrt(np.mean(h * h, axis=-1, keepdims=True)),
                    jnp.float32)
    valid = tiny.Rows(jnp.ones((rows,), bool))
    w = lambda p: (lambda name: jnp.asarray(p[f"block{i}_{name}"]))  # noqa
    uncut = ref.routed_ffn({k: jnp.asarray(v) for k, v in whole.items()},
                           i, h[None], TOP_K, (0, WIDTH), SCALE)
    parts, shared = [], None
    for first in range(0, WIDTH, 2):
        share = fam.init(7, share=(first, 2))
        routed, counts = _arch.routed_ffn(
            w(share), h, valid, (first, 2), TOP_K, SCALE, shared=False,
            form="relu2")
        both, _ = _arch.routed_ffn(w(share), h, valid, (first, 2), TOP_K,
                                   SCALE, form="relu2")
        if shared is None:
            shared = both - routed
        # what every chip computes alike is the same on every share
        np.testing.assert_allclose(both - routed, shared, atol=1e-5)
        assert int(counts[0]) == rows and int(counts[3]) == 2
        parts.append(routed)
    np.testing.assert_allclose(sum(parts) + shared, uncut[0], rtol=2e-4,
                               atol=2e-5)
    # every row's top 3 fell on some share: the pairs add up to rows x 3
    # (checked through the parts: no part is all zeros, none is the whole)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)


def test_the_published_model_counts_31_58_billion_parameters():
    """From the layer equations at the published widths, shape-only: the
    card's 31.6B-A3.2B, and what one chip of eight holds."""
    d, vocab = 2688, 131072
    mamba = (d * (2 * 4096 + 2 * 8 * 128 + 64) + 6144 * 4 + 6144 + 3 * 64
             + 4096 + 4096 * d + d)
    attention = d * (32 + 2 * 2) * 128 + 32 * 128 * d + d
    expert = 2 * d * 1856
    outside = d * 128 + 128 + 2 * d * 3712 + d
    assert (mamba, attention, expert, outside) == (
        38_744_896, 23_399_040, 9_977_856, 20_302_592)
    pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    counts = [pattern.count(c) for c in "ME*"]
    assert (len(pattern), counts) == (52, [23, 23, 6])
    assert pattern == "MEMEM*E" * 5 + "MEMEMEM*EMEMEMEME"
    total = (23 * mamba + 6 * attention + 23 * (outside + 128 * expert)
             + 2 * vocab * d + d)
    assert total == 31_577_940_288
    applied = total - 23 * 122 * expert - vocab * d
    assert round(applied / 1e9, 2) == 3.23
    held = (23 * mamba + 6 * attention + 23 * (outside + 16 * expert)
            + 2 * (vocab // 8) * d + d)
    assert held == 5_258_420_544 and round(2 * held / 1e9, 2) == 10.52
    # a slot: 23 layers of [64, 64, 128] float32 and 3 rows of 6144
    assert 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2) == 49_082_368
    # a cached token: 6 planes x K and V x 2 heads x 128 x 2 B; stored 8 rows
    assert 6 * 2 * 2 * 128 * 2 == 6144 and 6 * 2 * 8 * 128 * 2 == 24_576
