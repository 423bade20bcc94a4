"""``serving.arch.LatentMoE`` against its plain reference
(``models/latent_moe_reference.py``) at a small size: a latent cache of
``rank + rope_dim`` values a layer in ONE pool array, read by queries
absorbed into the latent's width; YaRN rotary positions past their
original length; a dense FFN and routed ones (softmax scores, weights
not normalised) over a share of the router's experts.  The reference is
the NON-absorbed per-head form with no cache.  Float32 through the cache
has to agree with it at every generated position; bfloat16 is judged as
the benchmark judges it."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import tiny  # noqa: E402
from paddle_tpu.kernels import paged_attention as paged  # noqa: E402
from paddle_tpu.models import latent_moe_reference as ref  # noqa: E402
from paddle_tpu.observability import trace  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402
from paddle_tpu.serving import arch as arch_mod  # noqa: E402
from paddle_tpu.serving.arch import LatentMoE  # noqa: E402
from tiny import latent_moe as fam  # noqa: E402

TINY = fam.sizes
T, B, PIECE = fam.max_len, fam.block_tokens, fam.piece
TOL = 2e-4
# bfloat16 engine against the float32 reference on the same bfloat16
# weights, judged by the margin of each generated token under the
# reference's maximum: four layers of width 64 round to some 0.05 logits
# of deviation 1.4 (the absorbed query is rounded once more than the
# per-head form's); 0.25 is the limit tests/test_gated_moe.py uses
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
    """The float32 and bfloat16 engines' logits through the latent
    cache, made once: two slots, prompts of 21 and 11 tokens (pieces 8 +
    8 + 8 with 3 rows of padding: the dense spelling of ``attend``; and
    8 + 4 with 1: a piece narrower than ``DENSE_WINDOW``, streamed), 14
    decode steps, so that the longer context passes YaRN's original 16
    positions in prefill and the shorter one in decode."""
    mp = pytest.MonkeyPatch()
    try:
        out = {}
        for dt in ("float32", "bfloat16"):
            eng, _ = fam.engine(params[dt], mp)
            out[dt] = tiny.through_the_cache(eng, PROMPTS, 14)
        return out
    finally:
        mp.undo()


def test_float32_through_the_latent_cache_agrees_with_the_reference(
        served, params):
    """Prefill pieces wider and narrower than ``DENSE_WINDOW``, decode
    steps, contexts past YaRN's original length: logits at every
    position, absorbed through the cache against per-head and whole."""
    original = TINY["yarn"][2]
    for (toks, lg), prompt in zip(served["float32"][0], PROMPTS):
        assert len(toks) > original + 4
        want = fam.reference(params["float32"], toks)[
            tiny.positions(len(prompt), lg)]
        assert np.abs(lg - want).max() < TOL


def test_absorbed_attention_equals_the_per_head_form(params):
    """The absorbed product on its own: ``q_lat = q_nope W_UK^T`` against
    the latent, values the latent's own lanes, ``u W_UV`` after, equals
    keys and values made per head from the latent (float32, 1e-5)."""
    z, p = TINY, params["float32"]
    arch = fam.arch()
    rng = np.random.default_rng(0)
    t, h, rank, nope = 19, z["heads"], z["rank"], z["nope"]
    q = rng.standard_normal((t, h, nope + z["rope"])).astype(np.float32)
    c = rng.standard_normal((t, rank)).astype(np.float32)
    k_pe = rng.standard_normal((t, z["rope"])).astype(np.float32)
    kvb = np.asarray(p["block1_att_kvb.w"]).reshape(rank, h, -1)
    mask = np.tril(np.ones((t, t), bool))
    # per head: expand the latent
    kv = np.einsum("tr,rhn->thn", c, kvb)
    s = (np.einsum("qhn,khn->hqk", q[..., :nope], kv[..., :nope])
         + np.einsum("qhn,kn->hqk", q[..., nope:], k_pe)) * arch.scale
    a = np.where(mask, s, -np.inf)
    a = np.exp(a - a.max(-1, keepdims=True))
    a /= a.sum(-1, keepdims=True)
    want = np.einsum("hqk,khv->qhv", a, kv[..., nope:])
    # absorbed, through the oracle over a pool that holds the rows
    lanes = arch.lanes
    rows = np.zeros((t, lanes), np.float32)
    rows[:, :rank], rows[:, rank:rank + z["rope"]] = c, k_pe
    pool = np.zeros((1 + -(-t // B), B, lanes), np.float32)
    pool[1:].reshape(-1, lanes)[:t] = rows
    q_row = np.zeros((t, h, lanes), np.float32)
    q_row[..., :rank] = np.einsum("qhn,rhn->qhr", q[..., :nope],
                                  kvb[..., :nope])
    q_row[..., rank:rank + z["rope"]] = q[..., nope:]
    table = jnp.asarray(1 + np.arange(pool.shape[0] - 1), jnp.int32)[None]
    u = paged.attend(jnp.asarray(q_row)[None], jnp.asarray(pool), None, table,
                     jnp.arange(t, dtype=jnp.int32)[None], value_lanes=rank,
                     scale=arch.scale)[0]
    got = np.einsum("qhr,rhv->qhv", np.asarray(u), kvb[..., nope:])
    assert np.abs(got - want).max() < 1e-5


def _reference_counts(p, served_dtype):
    return tiny.reference_counts(fam, p, served_dtype, PROMPTS,
                                 TINY["layers"] - TINY["dense"])


def test_bfloat16_through_the_cache_stays_within_the_margin(served, params):
    """Judged as the benchmark's check judges it: each generated token
    within the margin of the reference's maximum.  A near-tie at the
    third score can flip a selection under rounding (handled as
    ``tests/test_gated_moe.py`` handles the fourth): where a decode
    step's count of pairs on a held expert differs from the count of the
    reference's selections at the same positions, the step's tokens are
    left out, and counted."""
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
        keep = np.array([True] + steps[:len(gen) - 1])
        compared += int(keep.sum())
        left_out += int((~keep).sum())
        assert gap[keep].max() < BF16_MARGIN, gap[keep].max()
    assert compared >= 20 and left_out <= 8, (compared, left_out)


OMISSIONS = {
    "routed_part_left_out": dict(routed=False),
    "selected_weights_renormalised": dict(route_norm=True),
    "rotary_key_left_out_of_the_scores": dict(rotary_key=False),
    "latent_norm_left_out": dict(kv_norm=False),
    "mscale_left_out_of_the_scores": dict(mscale_in_scores=False),
    "yarn_blend_replaced_by_plain_theta": dict(yarn_blend=False),
}


@pytest.mark.parametrize("omission", list(OMISSIONS))
def test_each_line_left_out_fails_the_float32_comparison(served, params,
                                                         omission):
    worst = 0.0
    for (toks, lg), prompt in zip(served["float32"][0], PROMPTS):
        want = fam.reference(params["float32"], toks, **OMISSIONS[omission])
        worst = max(worst, float(np.abs(
            lg - want[tiny.positions(len(prompt), lg)]).max()))
    assert worst > 100 * TOL, worst


def test_the_counts_a_step_returns_equal_a_numpy_count(served, params):
    want = _reference_counts(params["float32"], served["float32"])
    got = [list(counts) for _, _, counts in served["float32"][1]]
    assert got == want


def test_the_shares_add_up_to_the_uncut_layer(uncut):
    """The routed parts of all four shares (4 x 4 experts) and the
    shared MLP counted once are the uncut reference's layer output."""
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


@pytest.mark.parametrize("scoring", ["softmax_unnormalised",
                                     "sigmoid_normalised"])
def test_route_against_numpy(scoring):
    """``arch.route``'s two scorings against NumPy: softmax over all the
    experts with the selected weights as they are (and no bias), sigmoid
    with a bias that selects only and the weights over their sum."""
    rng = np.random.default_rng(40)
    h = rng.standard_normal((13, 32)).astype(np.float32)
    w = (0.5 * rng.standard_normal((32, 16))).astype(np.float32)
    z = h.astype(np.float64) @ w
    if scoring == "softmax_unnormalised":
        sel, weight = arch_mod.route(jnp.asarray(h), jnp.asarray(w), None, 3,
                                     1.5, score="softmax", normalise=False)
        s = np.exp(z - z.max(-1, keepdims=True))
        s /= s.sum(-1, keepdims=True)
        want_sel = np.argsort(-s, axis=-1)[:, :3]
        want = np.take_along_axis(s, want_sel, -1) * 1.5
    else:
        bias = (0.1 * rng.standard_normal(16)).astype(np.float32)
        sel, weight = arch_mod.route(jnp.asarray(h), jnp.asarray(w),
                                     jnp.asarray(bias), 3, 1.5)
        s = 1 / (1 + np.exp(-z))
        want_sel = np.argsort(-(s + bias), axis=-1)[:, :3]
        want = np.take_along_axis(s, want_sel, -1)
        want = want / want.sum(-1, keepdims=True) * 1.5
    assert np.array_equal(np.asarray(sel), want_sel)
    assert np.abs(np.asarray(weight) - want).max() < 1e-6
    with pytest.raises(ValueError, match="score"):
        arch_mod.route(jnp.asarray(h), jnp.asarray(w), None, 3, 1.0,
                       score="tanh")


def test_the_margin_of_a_selection_against_a_count_of_every_pair():
    """``ref._margin``: the least, over every (selected, left out) pair
    of which one is held, of the difference of their scores over the
    worst selected score; ``inf`` where no pair is."""
    rng = np.random.default_rng(40)
    z = rng.normal(size=(3, 11, 16))
    s = (np.exp(z) / np.exp(z).sum(-1, keepdims=True)).astype(np.float32)
    sel = np.argsort(-s, axis=-1)[..., :3]
    for first, count in ((4, 4), (0, 16), (12, 4), (0, 1)):
        got = np.asarray(ref._margin(jnp.asarray(s), jnp.asarray(sel),
                                     first=first, count=count))
        held = lambda e: first <= e < first + count
        want = np.full(s.shape[:2], np.inf, np.float32)
        for b, t in np.ndindex(*s.shape[:2]):
            inside = sel[b, t].tolist()
            for i in inside:
                for j in set(range(16)) - set(inside):
                    if held(i) or held(j):
                        want[b, t] = min(want[b, t], (s[b, t, i] - s[b, t, j])
                                         / s[b, t, inside[-1]])
        assert np.allclose(got, want, rtol=1e-5), (first, count)
        assert (got > 0).all()
    assert np.isinf(np.asarray(ref._margin(
        jnp.asarray(s), jnp.asarray(sel), first=16, count=0))).all()


@pytest.mark.parametrize("routing", ["all_rows_to_one_held_set",
                                     "no_row_to_any_held_expert"])
def test_no_token_is_dropped_however_uneven_the_routing(uncut, routing):
    """No capacity: with every row selecting the SAME three held experts
    the buffer of gathered rows is full and every pair is computed; a
    share no row selects changes nothing.  The router has no bias: its
    columns are moved instead."""
    z, i, share = TINY, 3, TINY["share"]
    p = dict(uncut)
    x = jax.random.normal(jax.random.PRNGKey(2), (40, z["d"]))
    h = arch_mod._rms(x, p[f"block{i}_norm2.scale"], 1e-6)
    push = np.zeros((z["d"], z["experts"]), np.float32)
    column = np.asarray(jnp.mean(h, axis=0))
    column = column / np.sum(column * column)
    # every row's logit moves by about +-40 on those experts: h . column
    # is near 1 for a row near the mean; made exact below with rows that
    # ARE the mean plus a little
    x = jnp.mean(x, axis=0) + 0.05 * x
    sign = 1.0 if routing == "all_rows_to_one_held_set" else -1.0
    held_columns = [4, 5, 6] if sign > 0 else [4, 5, 6, 7]
    push[:, held_columns] = sign * 40.0 * column[:, None]
    p[f"block{i}_router.w"] = p[f"block{i}_router.w"] + jnp.asarray(push)
    valid = jnp.arange(40) < 37              # three rows of padding
    want = np.asarray(ref.routed_ffn(tiny.share(p, *share), i, x[None],
                                     z["top_k"], share, z["scale"]))[0]
    y, counts = tiny.routed_alone(fam, p, i, x, share, valid)
    assert np.abs(y - want)[:37].max() < TOL
    if routing == "all_rows_to_one_held_set":
        assert list(counts) == [37, 37 * 3, 3, 4]
    else:
        assert list(counts) == [37, 0, 0, 4]
    alone = np.asarray(ref.routed_ffn(tiny.share(p, *share), i, x[None],
                                      z["top_k"], share, z["scale"],
                                      routed=False))[0]
    assert np.abs(y - alone)[37:].max() < TOL


@pytest.mark.parametrize("dtype,limit", [("float32", 1e-3),
                                         ("bfloat16", BF16_MARGIN)])
def test_engine_serves_three_requests_over_two_slots(params, monkeypatch,
                                                     dtype, limit):
    """The whole engine: admission, pieces, decode chunks, a slot
    released and admitted again; the gauges of the latent plane, the
    routing counters and the latent positions against a NumPy count of
    the engine's own spans."""
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
    layers, held, planes = 3, TINY["share"][1], TINY["layers"]
    item = 4 if dtype == "float32" else 2
    lanes = paged.latent_lanes(TINY["rank"] + TINY["rope"])
    assert lanes == 128
    # the latent plane's gauges: ONE array, no head axis
    assert eng._pv == () and [a.shape for a in eng._pk] == [
        (1 + 2 * (T // B), B, lanes)] * planes
    assert st["serving.latent_planes"] == planes
    assert st["serving.latent_rank"] == TINY["rank"]
    assert st["serving.latent_rope_lanes"] == TINY["rope"]
    assert st["serving.latent_lanes_stored"] == lanes
    assert st["serving.kv_planes"] == planes
    assert st["serving.kv_heads"] == 1
    assert st["serving.kv_bytes_per_token"] == planes * item * lanes
    assert st["serving.kv_write_fill"] == (TINY["rank"] + TINY["rope"]) / lanes
    assert st["serving.kv_pool_bytes"] == (
        planes * (1 + 2 * (T // B)) * B * lanes * item)
    assert eng.arch.rows_per_entry == TINY["heads"]
    assert st["serving.moe_layers"] == layers
    assert st["serving.moe_experts_held"] == held
    assert st["serving.moe_router_width"] == 16
    assert st["serving.moe_top_k"] == 3
    assert st["serving.moe_expert_bytes"] == 3 * 64 * 24 * item
    chunks = [e["args"] for e in tracer.events()
              if e["name"] == "serving.decode_chunk"]
    fills = [e["args"] for e in tracer.events()
             if e["name"] == "serving.prefill"]
    assert all(a["moe_layers"] == layers and a["experts_held"] == held
               and a["latent_planes"] == planes
               and a["attn_form"] == "absorbed" for a in chunks + fills)
    assert st["serving.moe_rows{phase=decode}"] == layers * sum(
        a["active"] * a["steps"] for a in chunks)
    assert st["serving.moe_rows{phase=prefill}"] == layers * sum(
        len(p_) for p_ in prompts)
    assert st["serving.moe_expert_visits{phase=decode}"] == (
        held * layers * sum(a["steps"] for a in chunks))
    # every real prompt row j attends j + 1 positions in every plane
    assert st["serving.latent_positions_read{phase=prefill}"] == planes * sum(
        len(p_) * (len(p_) + 1) // 2 for p_ in prompts)
    # a decode step attends its slot's context; a chunk of 4 steps from a
    # context of c attends 4 c + 6; the contexts at each chunk's start
    # are bounded by what the requests reached
    read = st["serving.latent_positions_read{phase=decode}"]
    steps = sum(a["active"] * a["steps"] for a in chunks)
    assert planes * steps * min(len(p_) for p_ in prompts) < read
    assert read < planes * steps * T
    assert read % planes == 0
    assert st["serving.paged_entries_shared"] == 0   # no trie, no sharing


def test_a_prefix_hit_on_a_shared_head_then_a_fork(params, monkeypatch):
    """No recurrent state: ``prefix_reuse`` is allowed.  Two requests
    with one head: the second's tail is prefilled over the first's
    latent blocks (whole blocks shared, the partial one forked
    copy-on-write: ONE array a plane is copied), and decodes what the
    reference says; while both are live the decode calls count the
    shared head's entries as shared."""
    p = params["float32"]
    eng, reg = fam.engine(p, monkeypatch, prefix_reuse=True, cache_blocks=12)
    head = (3 * np.arange(18) + 2) % 128
    first = np.concatenate([head, [9, 8, 7]])
    second = np.concatenate([head, [1, 2, 3, 4, 5]])
    third = np.concatenate([head, [11, 12, 13, 14]])
    eng.generate_many([first], max_new_tokens=[6])
    out, = eng.generate_many([second], max_new_tokens=[10])
    st = eng.stats()
    assert st["serving.prefix_hit_rate"] > 0
    assert st.get("serving.cow_copies", 0) >= 1
    want = fam.reference(p, out)[len(second) - 1:len(out) - 1]
    gap = want.max(-1) - want[np.arange(len(want)), out[len(second):]]
    assert gap.max() < 1e-3, gap.max()
    assert st["serving.paged_entries_shared"] == 0   # one live slot a time
    # two live slots over one head: its four whole blocks are named twice
    outs = eng.generate_many([second, third], max_new_tokens=[8, 8])
    for prompt, full in zip((second, third), outs):
        want = fam.reference(p, full)[len(prompt) - 1:len(full) - 1]
        gap = want.max(-1) - want[np.arange(len(want)), full[len(prompt):]]
        assert gap.max() < 1e-3, gap.max()
    st = eng.stats()
    shared, live = (st["serving.paged_entries_shared"],
                    st["serving.paged_entries_live"])
    assert 0 < shared < live
    assert shared % (2 * (len(head) // B)) == 0


def test_paged_entries_shared_against_a_numpy_count(params, monkeypatch):
    """``serving.paged_entries_shared``: of the table entries the decode
    calls visit, those whose block two live slots name, against a count
    over a table written by hand."""
    eng, reg = fam.engine(params["float32"], monkeypatch, prefix_reuse=True,
                          cache_blocks=12, max_slots=3)

    class _Req:
        def __init__(self, n):
            self.prompt, self.tokens = np.zeros(n, np.int32), []

    eng._slots = [_Req(18), _Req(9), None]
    eng._table[0, :5] = [3, 4, 5, 6, 7]       # 18 keys: 5 entries
    eng._table[1, :3] = [3, 4, 9]             # 9 keys: 3 entries, 2 shared
    eng._table[2, :2] = [3, 4]                # not live: not counted
    eng._count_paged_entries([(0, 18), (1, 9)])
    assert reg.value("serving.paged_entries_live") == 8
    assert reg.value("serving.paged_entries_shared") == 4
    planes, steps = TINY["layers"], eng.decode_chunk
    assert reg.value("serving.latent_positions_read", phase="decode") == (
        planes * (steps * (18 + 9) + 2 * steps * (steps - 1) // 2))


def test_a_draft_model_is_refused(params):
    p = params["float32"]
    with pytest.raises(ValueError, match="speculative decoding serves the "
                       "GPT-2 block only.*'latent_moe'"):
        ServingEngine(p, arch=fam.arch(), max_len=T, block_tokens=B,
                      draft_params=dict(p))


def test_kv_bytes_per_token_of_the_other_four_is_unchanged():
    """The plane's own shape sizes the pool: K and V of every head for
    the four architectures that cache them, as before this one."""
    a = arch_mod
    gpt = a.Gpt2(2, 4, 64)
    assert gpt.kv_bytes_per_token(2) == 2 * 2 * 4 * 16 * 2
    loop = a.LoopedRmsRope(2, 4, 64, passes=3)
    assert loop.kv_bytes_per_token(2) == 6 * 2 * 4 * 16 * 2
    samba = a.SambaY(8, 8, 4, 128, window=8, d_inner=32)
    assert samba.kv_bytes_per_token(2) == len(samba.planes) * 2 * 4 * 16 * 2
    moe = a.GatedMoE(("window", "full"), 4, 2, 32, 64, window=8,
                     dense_layers=1, router_width=4, top_k=1, experts=(0, 2))
    assert moe.kv_bytes_per_token(2) == 2 * 2 * 2 * 32 * 2
    for arch in (gpt, loop, samba, moe):
        assert arch.pool_arrays == 2 and arch.latent_planes == 0
        assert arch.written_values == arch.kv_heads * arch.head_dim
    latent = fam.arch()
    assert latent.pool_arrays == 1
    assert latent.kv_bytes_per_token(2) == 4 * 2 * 128


def test_yarn_frequencies_at_the_published_constants():
    """The bounds the published ``rope_scaling`` gives over 64 rotary
    lanes: frequencies 0..10 kept, 23..31 divided by 40, a linear blend
    between; ``sigma = 192 ** -0.5 * mscale(40, 0.707) ** 2``."""
    inv = arch_mod.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert np.allclose(inv[:11], plain[:11], rtol=1e-6)
    assert np.allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    mid = inv[11:23] / plain[11:23]
    assert (np.diff(mid) < 0).all() and 1 / 40 < mid.min() < mid.max() < 1
    assert np.allclose(inv, ref.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0,
                                              1.0))
    assert abs(arch_mod.yarn_mscale(40.0, 0.707) - 1.2608) < 1e-4
    big = LatentMoE(2, 16, 2048, rank=512, nope_dim=128, rope_dim=64,
                    v_dim=128, dense_layers=1, router_width=64, top_k=6,
                    experts=(0, 16), rope_factor=40.0, mscale=0.707,
                    mscale_all_dim=0.707)
    assert abs(big.scale - 0.11472) < 1e-5 and big.rope_gain == 1.0
    assert big.lanes == 640 and big.written_values == 576
    assert big.kv_bytes_per_token(2) == 2 * 2 * 640
    assert big.pool_block_shape(32, "bfloat16") == (32, 640)


def test_the_published_layer_equations_count_15p7b_parameters():
    """The layout is the model's: the layer equations at the published
    sizes give the card's 15.7B total, 2.4B applied a token, and this
    chip's share 4,595.6M."""
    d, rows = 2048, 102400
    attention = d * 16 * 192 + d * 576 + 512 * 16 * 256 + 16 * 128 * d
    assert attention == 13_762_560
    expert = 3 * d * 1408
    routed_outside = attention + 3 * d * 2816 + d * 64
    assert routed_outside == 31_195_136
    dense = attention + 3 * d * 10944
    total = 26 * (routed_outside + 64 * expert) + dense + 2 * rows * d
    applied = 26 * (routed_outside + 6 * expert) + dense + rows * d
    assert abs(total - 15.71e9) < 0.01e9, total
    assert abs(applied - 2.45e9) < 0.01e9, applied
    held = 26 * (routed_outside + 16 * expert) + dense + 2 * (rows // 4) * d
    assert abs(held - 4595.6e6) < 0.1e6, held
