"""``serving.arch.SinkWindowMoE`` against its plain reference
(``models/sink_window_moe_reference.py``) at a small size: full planes
of one K/V geometry beside window planes of another, keys of more lanes
than values, a learned sink on the window planes, rotary on part of a
head at two thetas, a scaled value, routed layers with no shared expert;
and the window planes' chains, which hold only their window and give the
other blocks back while the slot goes on.  Float32 through the cache has
to agree with the reference's full forward at every generated position,
at contexts several times the window."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import tiny  # noqa: E402
from paddle_tpu.models import sink_window_moe_reference as ref  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402
from paddle_tpu.serving.arch import SinkWindowMoE  # noqa: E402
from tiny import sink_window_moe as fam  # noqa: E402

TINY = fam.sizes
T, B, SLOTS = fam.max_len, fam.block_tokens, fam.max_slots
TOL = 3e-4


@pytest.fixture(scope="module")
def uncut():
    return fam.init()


@pytest.fixture(scope="module")
def params(uncut):
    return fam.held(uncut)["float32"]


PROMPTS = [np.arange(3, 3 + 21) % 128, (7 * np.arange(11) + 5) % 128,
           (5 * np.arange(13) + 2) % 128]
# the decode step before which each prompt is admitted: the third comes
# while the first two decode far past their windows
ADMIT_AT = (0, 0, 12)
STEPS = 30


@pytest.fixture(scope="module")
def served(params):
    """The float32 logits through the cache under window chains, and
    under whole chains (the engine a prefix trie keeps whole)."""
    mp = pytest.MonkeyPatch()
    try:
        out = {}
        for name, reuse in (("windowed", False), ("whole", True)):
            eng, _ = fam.engine(params, mp, prefix_reuse=reuse)
            assert (eng.window_chains is not None) == (not reuse)
            out[name] = tiny.through_the_window_chains(
                eng, PROMPTS, ADMIT_AT, STEPS)
        return out
    finally:
        mp.undo()


@pytest.mark.parametrize("slot", range(len(PROMPTS)))
def test_float32_through_the_cache_agrees_with_the_reference(served, params,
                                                             slot):
    """Prefill in pieces (the dense spelling) and decode steps, full and
    window planes, contexts of several windows: logits at every position,
    through chains that hold only their window."""
    toks, lg, _ = served["windowed"][0][slot]
    prompt = PROMPTS[slot]
    assert len(toks) >= len(prompt) + STEPS - ADMIT_AT[slot]
    assert len(toks) > 3 * TINY["window"]
    want = fam.reference(params, toks)[tiny.positions(len(prompt), lg)]
    assert np.abs(lg - want).max() < TOL


def test_window_blocks_are_given_back_and_reused_by_another_slot(served):
    """While slot 0 still decodes, blocks it gave back are handed to a
    slot admitted later, and nobody's logits move (the case above)."""
    assert served["windowed"][1]
    assert not served["whole"][1]


@pytest.mark.parametrize("slot", range(len(PROMPTS)))
def test_whole_chains_read_the_same_logits_to_the_bit(served, slot):
    """An engine built with ``prefix_reuse=True`` keeps its window planes
    whole; what the other gave back was never attended, so the kernels'
    own arithmetic gives the same bits."""
    (toks_w, lg_w, _), (toks_h, lg_h, _) = (served["windowed"][0][slot],
                                            served["whole"][0][slot])
    assert np.array_equal(toks_w, toks_h)
    assert np.array_equal(lg_w, lg_h)


OMISSIONS = {
    "sink_left_out": dict(sink=False),
    "value_scale_left_out": dict(value_scale=1.0),
    "rotary_on_other_lanes": dict(rot=16),
    "second_theta_replaced_by_the_first": dict(
        window_rope_theta=TINY["theta"]),
    "norm_topk_prob_left_out": dict(norm_topk=False),
    "selecting_bias_left_out": dict(route_bias=False),
    "window_edge_moved_by_one": dict(window=TINY["window"] + 1),
    "window_planes_attended_whole": dict(windowed=False),
}


@pytest.mark.parametrize("omission", list(OMISSIONS))
def test_each_line_left_out_fails_the_float32_comparison(served, params,
                                                         omission):
    worst = 0.0
    for (toks, lg, _), prompt in zip(served["windowed"][0], PROMPTS):
        want = fam.reference(params, toks, **OMISSIONS[omission])
        worst = max(worst, float(np.abs(
            lg - want[tiny.positions(len(prompt), lg)]).max()))
    assert worst > 100 * TOL, worst


# -- the real engine: submit, driver, admission, release -------------------

def _accounted(eng):
    """Every block of either kind is free, or held by exactly the slots
    whose tables name it."""
    for pool, table in ((eng.kv_pool, eng._table),
                        (eng.window_chains.pool, eng.window_chains.table)):
        named = table[table > 0]
        assert len(named) == len(set(named.tolist()))         # once each
        assert pool.blocks_in_use == len(named)
        assert all(pool.refcount(int(b)) == 1 for b in named)
    for s, req in enumerate(eng._slots):
        if req is None:
            assert not eng._table[s].any()
            assert not eng.window_chains.table[s].any()
        else:
            assert eng.window_chains.held(s) <= eng.window_blocks_per_slot


def test_the_engine_serves_the_references_greedy_chain(params, monkeypatch):
    """``submit`` / ``step`` on the normal path, requests arriving while
    others decode: each result is the float32 reference's own greedy
    chain, the blocks of both kinds are accounted for after every step,
    window blocks are given back all through, and everything is free at
    the end."""
    eng, reg = fam.engine(params, monkeypatch)
    assert eng.window_chains is not None
    handles = [eng.submit(PROMPTS[0], max_new_tokens=30),
               eng.submit(PROMPTS[1], max_new_tokens=9)]
    late = [(3, PROMPTS[2], 25), (6, PROMPTS[1][:7], 40),
            (9, PROMPTS[0][:15], 12)]
    n = 0
    while not eng.idle or late:
        while late and late[0][0] <= n:
            _, prompt, new = late.pop(0)
            handles.append(eng.submit(prompt, max_new_tokens=new))
        eng.step()
        _accounted(eng)
        n += 1
    for h in handles:
        full = h.result(timeout=0)
        want = fam.reference(params, full)
        chain = want[len(h.prompt) - 1:len(full) - 1].argmax(-1)
        assert np.array_equal(full[len(h.prompt):], chain)
    stats = eng.stats()
    assert stats["serving.window_blocks_released"] > 20
    assert eng.kv_pool.blocks_in_use == 0
    assert eng.window_chains.pool.blocks_in_use == 0
    held = stats["serving.window_blocks_held"]
    whole = stats["serving.window_blocks_whole"]
    assert 0 < held < 0.6 * whole
    # every window block a slot held fitted its reservation
    assert stats["serving.kv_blocks_total"] == (
        eng.kv_pool.num_blocks - 1 + SLOTS * eng.window_blocks_per_slot)


def test_an_aborted_engine_gives_every_block_back(params, monkeypatch):
    eng, _ = fam.engine(params, monkeypatch)
    eng.submit(PROMPTS[0], max_new_tokens=20)
    eng.submit(PROMPTS[2], max_new_tokens=20)
    for _ in range(3):
        eng.step()
    assert eng.window_chains.pool.blocks_in_use > 0
    eng._abort(RuntimeError("test"))
    assert eng.kv_pool.blocks_in_use == 0
    assert eng.window_chains.pool.blocks_in_use == 0
    assert not eng.window_chains.table.any()


@pytest.mark.parametrize("reuse", [False, True])
def test_gauges_and_span_attributes_say_what_is_held(params, monkeypatch,
                                                     reuse):
    from paddle_tpu.observability import trace

    tracer = trace.Tracer(enabled=True)
    monkeypatch.setattr(trace, "get_tracer", lambda: tracer)
    eng, reg = fam.engine(params, monkeypatch, prefix_reuse=reuse)
    eng.generate_many([PROMPTS[1]], max_new_tokens=6)
    stats = eng.stats()
    z = TINY
    assert stats["serving.kv_planes{kind=window}"] == 3
    assert stats["serving.kv_planes{kind=full}"] == 2
    assert stats["serving.kv_heads{kind=full}"] == z["kv"]
    assert stats["serving.kv_heads{kind=window}"] == z["wkv"]
    assert stats["serving.kv_lanes{array=k,form=published}"] == z["dh"]
    assert stats["serving.kv_lanes{array=k,form=stored}"] == 128
    assert stats["serving.kv_lanes{array=v,form=stored}"] == z["dv"]
    assert stats["serving.attn_sink_planes"] == 3
    full = z["kv"] * (z["dh"] + z["dv"]) * 4
    win = z["wkv"] * (z["dh"] + z["dv"]) * 4
    assert stats["serving.kv_bytes_per_token{kind=full}"] == full
    assert stats["serving.kv_bytes_per_token{kind=window}"] == (
        win if reuse else win * z["window"])
    assert ("serving.window_blocks_per_slot" in stats) == (not reuse)
    spans = [e for e in tracer.events()
             if e["name"] in ("serving.decode_chunk", "serving.prefill")]
    assert spans and all(e["args"]["sink_planes"] == 3
                         and e["args"]["kv_kinds"] == (1 if reuse else 2)
                         for e in spans)


def test_the_shares_add_up_to_the_uncut_layer(uncut):
    """The routed parts of all four shares (4 x 4 experts) are the uncut
    reference's layer output: there is no shared expert, so nothing is
    counted twice and nothing once."""
    x = jax.random.normal(jax.random.PRNGKey(1), (24, TINY["d"]))
    z, i = TINY, 2
    whole = np.asarray(ref.routed_ffn(uncut, i, x[None], z["top_k"],
                                      (0, z["experts"])))[0]
    total = np.zeros_like(whole)
    pairs = 0
    for first in range(0, z["experts"], 4):
        y, counts = tiny.routed_alone(fam, uncut, i, x, (first, 4))
        total += y
        pairs += int(counts[1])
    assert pairs == 24 * z["top_k"]          # every selection held once
    assert np.abs(total - whole).max() < 1e-4


def test_parameter_count_at_the_published_config():
    """308.8B parameters, 14.8B applied a token, counted from the
    architecture's own shapes at the published ``config.json``."""
    pattern = [0 if i in (0, 5, 11, 17, 23, 29, 35, 41, 47) else 1
               for i in range(48)]
    arch = SinkWindowMoE(
        tuple("window" if k else "full" for k in pattern), 64, 4, 8, 192,
        128, 4096, window=128, rotary_lanes=64, dense_layers=1,
        router_width=256, top_k=8, experts=(0, 256), value_scale=0.707)
    d, f, e, vocab = 4096, 16384, 2048, 152576

    def attention(i):
        hk = arch.plane_kv_heads(i)
        return (d * (64 * 192 + hk * (192 + 128)) + 64 * 128 * d)

    assert attention(0) == 89_128_960 and attention(1) == 94_371_840
    assert 3 * d * e == 25_165_824 and d * 256 == 1_048_576
    assert 3 * d * f == 201_326_592 and d * vocab == 624_951_296
    att = sum(attention(i) for i in range(48))
    whole = att + 3 * d * f + 47 * (256 * 3 * d * e + d * 256) + 2 * d * vocab
    applied = att + 3 * d * f + 47 * (8 * 3 * d * e + d * 256) + d * vocab
    assert round(whole / 1e9, 1) == 308.8
    assert round(applied / 1e9, 1) == 14.8
    # what is cached, at the published values
    assert arch.plane_block_bytes(0, 1, 2) == 2560
    assert arch.plane_block_bytes(1, 1, 2) == 5120
    assert arch.plane_block_shapes(0, 32, "bfloat16") == (
        (32, 8, 256), (32, 8, 128))
    assert arch.kv_bytes_per_token(2) == 9 * 2560 + 39 * 5120 == 222_720


def test_refusals():
    with pytest.raises(ValueError, match="must divide n_head"):
        fam.arch(wkv=3)
    with pytest.raises(ValueError, match="rotary_lanes"):
        fam.arch(rot=7)
    with pytest.raises(ValueError, match="layer types"):
        fam.arch(types=("full", "latent"))
    p = tiny.share(fam.init(0), 4, 4)
    bad = dict(p)
    del bad["block1_att_sink.b"]
    with pytest.raises(ValueError, match="att_sink.b"):
        ServingEngine(bad, arch=fam.arch(), max_len=T, block_tokens=B,
                      prefix_reuse=False)
    bad = dict(p, **{"block0_att_qkv.w": p["block1_att_qkv.w"]})
    with pytest.raises(ValueError, match="layer 0"):
        ServingEngine(bad, arch=fam.arch(), max_len=T, block_tokens=B,
                      prefix_reuse=False)
