"""``serving.arch.GatedMoE`` against its plain reference
(``models/gated_moe_reference.py``) at a small size: gated
grouped-query attention on window and full planes with heads WIDER than
``d_model / n_head``, a dense FFN and routed ones over a share of the
router's experts.  Float32 through the cache has to agree with the
reference's full forward at every generated position; bfloat16 is judged
as the benchmark judges it."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import tiny  # noqa: E402
from paddle_tpu.models import gated_moe_reference as ref  # noqa: E402
from paddle_tpu.observability import trace  # noqa: E402
from paddle_tpu.observability.metrics import MetricsRegistry  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402
from paddle_tpu.serving import arch as arch_mod  # noqa: E402
from paddle_tpu.serving.arch import GatedMoE  # noqa: E402
from tiny import gated_moe as fam  # noqa: E402

TINY = fam.sizes
T, B, PIECE = fam.max_len, fam.block_tokens, fam.piece
TOL = 2e-4
# bfloat16 engine against the float32 reference on the same bfloat16
# weights, judged by the margin of each generated token under the
# reference's maximum: five layers of width 64 round to some 0.05 logits
# of deviation 1.4; 0.25 is five times the worst seen (0.047)
BF16_MARGIN = 0.25


@pytest.fixture(scope="module")
def uncut():
    """All 16 experts, float32."""
    return fam.init()


@pytest.fixture(scope="module")
def params(uncut):
    return fam.held(uncut, ("float32", "bfloat16"))


PROMPTS = [np.arange(3, 3 + 21) % 128, (7 * np.arange(11) + 5) % 128]


@pytest.fixture(scope="module")
def served(params):
    """The float32 and bfloat16 engines' logits through the cache, made
    once: two slots, prompts of 21 and 11 tokens (pieces 8 + 8 + 8 with
    3 rows of padding: the dense spelling of ``attend``; and 8 + 4 with
    1: a piece narrower than ``DENSE_WINDOW``, streamed), 14 decode
    steps, so that both contexts pass the window of 8 and a block
    boundary."""
    mp = pytest.MonkeyPatch()
    try:
        out = {}
        for dt in ("float32", "bfloat16"):
            eng, _ = fam.engine(params[dt], mp)
            out[dt] = tiny.through_the_cache(eng, PROMPTS, 14)
        return out
    finally:
        mp.undo()


def test_float32_through_the_cache_agrees_with_the_reference(served, params):
    """Prefill pieces wider and narrower than ``DENSE_WINDOW``, decode
    steps, contexts that pass the window: logits at every position."""
    for (toks, lg), prompt in zip(served["float32"][0], PROMPTS):
        assert len(toks) > TINY["window"] + len(prompt) // 2
        want = fam.reference(params["float32"], toks)[
            tiny.positions(len(prompt), lg)]
        assert np.abs(lg - want).max() < TOL


def _reference_counts(p, served_dtype):
    return tiny.reference_counts(fam, p, served_dtype, PROMPTS,
                                 len(TINY["types"]) - TINY["dense"])


def test_bfloat16_through_the_cache_stays_within_the_margin(served, params):
    """Judged as the benchmark's check judges it: each generated token
    within the margin of the reference's maximum.  A near-tie at the
    fourth score can flip a selection under rounding, and a row that
    gained or lost a held expert computed a different function (an error
    of 0.3 to 1.8 logits here, against 0.1 elsewhere).  Such a step shows
    in its own tally: where a decode step's count of pairs on a held
    expert differs from the count of the reference's selections at the
    same positions, the step's tokens are left out, and counted."""
    p = params["bfloat16"]
    runs, tallied = served["bfloat16"]
    want_counts = _reference_counts(p, served["bfloat16"])
    steps = [got[2][1] == want[1] for got, want in zip(tallied, want_counts)
             if got[0] == "decode"]
    compared = left_out = 0
    for (toks, lg), prompt in zip(runs, PROMPTS):
        want = fam.reference(p, toks)[tiny.positions(len(prompt), lg)]
        gen = toks[len(prompt):]
        gap = want[:len(gen)].max(-1) - want[np.arange(len(gen)), gen]
        # token j + 1 was chosen from the logits of decode step j
        keep = np.array([True] + steps[:len(gen) - 1])
        compared += int(keep.sum())
        left_out += int((~keep).sum())
        assert gap[keep].max() < BF16_MARGIN, gap[keep].max()
    assert compared >= 20 and left_out <= 8, (compared, left_out)


OMISSIONS = {
    "routed_part_left_out": dict(routed=False),
    "route_norm_and_scale_left_out": dict(route_norm=False),
    "attention_gate_left_out": dict(attention_gate=False),
    "rotary_moved_to_the_full_layer": dict(rotary_on="full"),
    "window_bound_left_out": dict(windowed=False),
}


@pytest.mark.parametrize("omission", list(OMISSIONS))
def test_each_line_left_out_fails_the_float32_comparison(served, params,
                                                         omission):
    worst = 0.0
    for (toks, lg), prompt in zip(served["float32"][0], PROMPTS):
        want = fam.reference(params["float32"], toks, **OMISSIONS[omission])
        worst = max(worst, float(np.abs(
            lg - want[tiny.positions(len(prompt), lg)]).max()))
    assert worst > 200 * TOL, worst


def test_the_counts_a_step_returns_equal_a_numpy_count(served, params):
    """Every call's tally (live rows, pairs on a held expert, held
    experts touched, held experts visited; summed over the routed
    layers) against a count of the reference's own selections at the
    same positions."""
    want = _reference_counts(params["float32"], served["float32"])
    got = [list(counts) for _, _, counts in served["float32"][1]]
    assert got == want


def test_the_shares_add_up_to_the_uncut_layer(uncut):
    """The routed parts of all four shares (4 x 4 experts) and the
    shared expert counted once are the uncut reference's layer output:
    what a chip leaves out is exactly what the other chips hold."""
    x = jax.random.normal(jax.random.PRNGKey(1), (24, TINY["d"]))
    z, i = TINY, 2
    whole = np.asarray(ref.routed_ffn(uncut, i, x[None], z["top_k"],
                                      (0, z["experts"]), z["scale"]))[0]
    shared = np.asarray(ref.routed_ffn(
        uncut, i, x[None], z["top_k"], (0, z["experts"]), z["scale"],
        routed=False))[0]
    parts, pairs = [], 0
    for first in range(0, z["experts"], 4):
        y, counts = tiny.routed_alone(fam, uncut, i, x, (first, 4))
        parts.append(y - shared)
        pairs += counts[1]
    assert pairs == 24 * z["top_k"]          # every pair is some chip's
    assert np.abs(shared + sum(parts) - whole).max() < TOL
    # the one definition of routing selects what the reference selects
    h = arch_mod._rms(x, uncut[f"block{i}_norm3.scale"], 1e-5)
    sel, weight = arch_mod.route(h, uncut[f"block{i}_router.w"],
                                 uncut[f"block{i}_router.bias"], z["top_k"],
                                 z["scale"])
    _, want_sel, want_weight, _ = ref._route(
        x[None], {k: uncut[f"block{i}_{k}"] for k in ref._ROUTE_KEYS},
        top_k=z["top_k"], scale=z["scale"], norm=True, eps=1e-5)
    assert np.array_equal(np.asarray(sel), np.asarray(want_sel)[0])
    assert np.abs(np.asarray(weight) - np.asarray(want_weight)[0]).max() < 1e-6
    assert np.allclose(np.asarray(weight).sum(-1), z["scale"], atol=1e-5)


def test_the_margin_of_a_selection_against_a_count_of_every_pair():
    """``ref._margin``: how far a row's selection is from one that
    differs in a held expert, against the least over every (selected,
    left out) pair of which one is held, of the difference of their
    ``s + b`` over the sigmoid's slope at the worst selected expert;
    ``inf`` where no pair is."""
    rng = np.random.default_rng(34)
    s = 1 / (1 + np.exp(-rng.normal(size=(3, 11, 16)))).astype(np.float32)
    bias = (0.05 * rng.normal(size=16)).astype(np.float32)
    c = s + bias
    sel = np.argsort(-c, axis=-1)[..., :4]
    for first, count in ((4, 4), (0, 16), (12, 4), (0, 1)):
        got = np.asarray(ref._margin(jnp.asarray(s), jnp.asarray(bias),
                                     jnp.asarray(sel), first=first,
                                     count=count))
        held = lambda e: first <= e < first + count
        want = np.full(s.shape[:2], np.inf, np.float32)
        for b, t in np.ndindex(*s.shape[:2]):
            inside = sel[b, t].tolist()
            at_last = s[b, t, inside[-1]]
            for i in inside:
                for j in set(range(16)) - set(inside):
                    if held(i) or held(j):
                        want[b, t] = min(want[b, t], (c[b, t, i] - c[b, t, j])
                                         / (at_last * (1 - at_last)))
        assert np.allclose(got, want, rtol=1e-5), (first, count)
        assert (got > 0).all()
    # no pair has a held expert: a share that holds nothing
    assert np.isinf(np.asarray(ref._margin(
        jnp.asarray(s), jnp.asarray(bias), jnp.asarray(sel), first=16,
        count=0))).all()


@pytest.mark.parametrize("routing", ["all_rows_to_one_held_set",
                                     "an_expert_with_no_row",
                                     "no_row_to_any_held_expert"])
def test_no_token_is_dropped_however_uneven_the_routing(uncut, routing):
    """No capacity: with every row selecting the SAME four held experts
    the buffer of gathered rows is full and every pair is computed; an
    expert no row selects, and a share no row selects, change nothing."""
    z, i, share = TINY, 3, TINY["share"]
    p = dict(uncut)
    bias = np.zeros(z["experts"], np.float32)
    if routing == "all_rows_to_one_held_set":
        bias[4:8] = 10.0
    elif routing == "an_expert_with_no_row":
        bias[5] = -10.0
    else:
        bias[4:8] = -10.0
    p[f"block{i}_router.bias"] = jnp.asarray(bias)
    x = jax.random.normal(jax.random.PRNGKey(2), (40, z["d"]))
    valid = jnp.arange(40) < 37              # three rows of padding
    want = np.asarray(ref.routed_ffn(tiny.share(p, *share), i, x[None],
                                     z["top_k"], share, z["scale"]))[0]
    y, counts = tiny.routed_alone(fam, p, i, x, share, valid)
    assert np.abs(y - want)[:37].max() < TOL
    if routing == "all_rows_to_one_held_set":
        assert list(counts) == [37, 37 * 4, 4, 4]
    elif routing == "no_row_to_any_held_expert":
        assert list(counts) == [37, 0, 0, 4]
    else:
        assert counts[2] <= 3
    # a padding row touches no expert: the shared expert alone
    alone = np.asarray(ref.routed_ffn(tiny.share(p, *share), i, x[None],
                                      z["top_k"], share, z["scale"],
                                      routed=False))[0]
    assert np.abs(y - alone)[37:].max() < TOL


@pytest.mark.parametrize("dtype,limit", [("float32", 1e-3),
                                         ("bfloat16", BF16_MARGIN)])
def test_engine_serves_three_requests_over_two_slots(params, monkeypatch,
                                                     dtype, limit):
    """The whole engine: admission, pieces, decode chunks, a slot
    released and admitted again; the routing counters against the
    engine's own bookkeeping."""
    eng, reg = fam.engine(params[dtype], monkeypatch)
    prompts = [PROMPTS[0], PROMPTS[1], (5 * np.arange(17) + 1) % 128]
    tracer = trace.Tracer(enabled=True)
    old = trace.get_tracer()
    trace.set_tracer(tracer)
    try:
        outs = eng.generate_many(prompts, max_new_tokens=[9, 14, 12])
    finally:
        trace.set_tracer(old)
    for prompt, full in zip(prompts, outs):
        n_p = len(prompt)
        assert np.array_equal(full[:n_p], prompt)
        want = fam.reference(params[dtype], full)[n_p - 1:len(full) - 1]
        gap = want.max(-1) - want[np.arange(len(want)), full[n_p:]]
        if dtype == "float32":
            assert gap.max() < limit, gap.max()
        else:   # a flipped selection may cost a token its margin
            assert np.median(gap) < limit, np.median(gap)
    st = eng.stats()
    assert st["serving.completed"] == 3 and eng.kv_pool.blocks_in_use == 0
    layers, held = 4, TINY["share"][1]
    assert st["serving.moe_layers"] == layers
    assert st["serving.moe_experts_held"] == held
    assert st["serving.moe_router_width"] == 16
    assert st["serving.moe_top_k"] == 4
    item = 4 if dtype == "float32" else 2
    assert st["serving.moe_expert_bytes"] == 3 * 64 * 48 * item
    chunks = [e["args"] for e in tracer.events()
              if e["name"] == "serving.decode_chunk"]
    fills = [e["args"] for e in tracer.events()
             if e["name"] == "serving.prefill"]
    assert all(a["moe_layers"] == layers and a["experts_held"] == held
               for a in chunks + fills)
    assert st["serving.moe_rows{phase=decode}"] == layers * sum(
        a["active"] * a["steps"] for a in chunks)
    assert st["serving.moe_rows{phase=prefill}"] == layers * sum(
        len(p_) for p_ in prompts)
    assert st["serving.moe_expert_visits{phase=decode}"] == (
        held * layers * sum(a["steps"] for a in chunks))
    assert st["serving.moe_expert_visits{phase=prefill}"] == (
        held * layers * sum(a["pieces"] for a in fills))
    for phase in ("decode", "prefill"):
        rows = st[f"serving.moe_rows{{phase={phase}}}"]
        pairs = st[f"serving.moe_assignments_held{{phase={phase}}}"]
        touched = st[f"serving.moe_experts_touched{{phase={phase}}}"]
        assert 0 < pairs <= rows * 4
        assert 0 < touched <= min(
            pairs, st[f"serving.moe_expert_visits{{phase={phase}}}"])


def test_a_prefix_hit_over_window_and_full_planes(params, monkeypatch):
    """No recurrent state, every plane holds the slot's whole chain:
    ``prefix_reuse`` is allowed, and a request that skips a cached
    prefix (whole blocks shared, the partial one forked copy-on-write)
    decodes what it decodes alone."""
    p = params["float32"]
    eng, reg = fam.engine(p, monkeypatch, prefix_reuse=True, cache_blocks=12)
    head = (3 * np.arange(18) + 2) % 128
    first = np.concatenate([head, [9, 8, 7]])
    second = np.concatenate([head, [1, 2, 3, 4, 5]])
    eng.generate_many([first], max_new_tokens=[6])
    out, = eng.generate_many([second], max_new_tokens=[10])
    st = eng.stats()
    assert st["serving.prefix_hit_rate"] > 0
    assert st.get("serving.cow_copies", 0) >= 1
    want = fam.reference(p, out)[len(second) - 1:len(out) - 1]
    gap = want.max(-1) - want[np.arange(len(want)), out[len(second):]]
    assert gap.max() < 1e-3, gap.max()
    assert len(out) > len(second) + TINY["window"] // 2


def test_a_draft_model_is_refused(params):
    p = params["float32"]
    with pytest.raises(ValueError, match="speculative decoding serves the "
                       "GPT-2 block only.*'gated_moe'"):
        ServingEngine(p, arch=fam.arch(), max_len=T, block_tokens=B,
                      draft_params=dict(p))


def test_heads_wider_than_d_model_over_n_head_build_an_engine(params):
    """The head size is the architecture's own: 4 heads of 32 on a model
    64 wide (d / heads would be 16), and 5 heads, which do not divide
    it."""
    eng = ServingEngine(params["float32"], arch=fam.arch(), max_len=T,
                        block_tokens=B, prefix_reuse=False,
                        registry=MetricsRegistry())
    assert eng.arch.head_dim == 32 != eng.arch.d_model // eng.arch.n_head
    assert [a.shape for a in eng._pk] == [(1 + 8 * (T // B), B, 2, 32)] * 5
    st = eng.stats()
    assert st["serving.kv_heads"] == 2
    assert st["serving.kv_bytes_per_token"] == 5 * 2 * 2 * 32 * 4
    assert st["serving.kv_planes{kind=window}"] == 4
    assert st["serving.kv_planes{kind=full}"] == 1
    assert eng.arch.rows_per_entry == 2
    odd = GatedMoE(("full",), 5, 1, 16, 64, window=8, dense_layers=1,
                   router_width=4, top_k=1, experts=(0, 1))
    assert odd.head_dim == 16 and odd.heads(jnp.zeros((3, 80))).shape == (
        3, 5, 16)
    with pytest.raises(ValueError, match="head_dim="):
        arch_mod.Architecture(2, 5, 64)


def test_the_published_layer_equations_count_398p6b_parameters():
    """The layout is the model's: the layer equations at the published
    sizes give the card's 400B total, 13B applied a token."""
    d, q, kv, e, f, rows = 3072, 48 * 128, 8 * 128, 3072, 12288, 200192
    attention = 2 * d * q + 2 * d * kv + q * d            # q, gate, k, v, out
    expert = 3 * d * e
    routed = attention + d * 256 + expert
    total = 54 * (routed + 256 * expert) + 6 * (attention + 3 * d * f) \
        + 2 * rows * d
    applied = 54 * (routed + 4 * expert) + 6 * (attention + 3 * d * f) \
        + rows * d
    assert abs(total - 398.6e9) < 0.1e9, total
    assert abs(applied - 12.76e9) < 0.02e9, applied
