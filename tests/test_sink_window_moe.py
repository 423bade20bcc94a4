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
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.models import sink_window_moe_reference as ref  # noqa: E402
from paddle_tpu.observability.metrics import MetricsRegistry  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402
from paddle_tpu.serving import arch as arch_mod  # noqa: E402
from paddle_tpu.serving import batched_decode as _bd  # noqa: E402
from paddle_tpu.serving.arch import SinkWindowMoE  # noqa: E402

# keys of 24 lanes over values of 16, 8 of the 24 rotated; one K/V head
# on full planes, two on window planes; 16 experts, top 4, 4 held (4..7)
TINY = {"d": 64, "heads": 4, "kv": 1, "wkv": 2, "dh": 24, "dv": 16,
        "rot": 8, "f": 128, "e": 48, "experts": 16, "top_k": 4,
        "share": (4, 4), "window": 8, "scale": 0.707,
        "types": ("full", "window", "window", "full", "window"),
        "dense": 1, "rows": 128, "theta": 1e7, "wtheta": 1e4}
T, B, PIECE, SLOTS = 64, 4, 8, 3
TOL = 3e-4


def _init(key, z, dtype, experts=None):
    """Seeded weights under ``SinkWindowMoE``'s names: matrices at 0.2 (a
    width of 64 then gives activations of order one), the router at 0.3,
    gains near one, sinks around the score of a strong key."""
    n = len(z["types"])
    experts = z["experts"] if experts is None else experts
    keys = iter(jax.random.split(key, 16 * n + 4))
    d, dh, dv, e = z["d"], z["dh"], z["dv"], z["e"]

    def normal(*shape, scale=0.2):
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)

    branch = (2 * n) ** -0.5
    p = {"tok_emb.w": normal(z["rows"], d, scale=1.0),
         "norm_f.scale": 1 + normal(d), "lm_head.w": normal(d, z["rows"])}
    for i, kind in enumerate(z["types"]):
        b = f"block{i}_"
        hk = z["wkv"] if kind == "window" else z["kv"]
        p.update({
            b + "norm1.scale": 1 + normal(d), b + "norm2.scale": 1 + normal(d),
            b + "att_qkv.w": normal(d, z["heads"] * dh + hk * (dh + dv)),
            b + "att_out.w": normal(z["heads"] * dv, d, scale=0.2 * branch)})
        if kind == "window":
            p[b + "att_sink.b"] = 1.0 + normal(z["heads"], scale=1.0)
        if i < z["dense"]:
            p.update({b + "ffn_gate.w": normal(d, z["f"]),
                      b + "ffn_up.w": normal(d, z["f"]),
                      b + "ffn_down.w": normal(z["f"], d,
                                               scale=0.2 * branch)})
        else:
            p.update({
                b + "router.w": normal(d, z["experts"], scale=0.3),
                b + "router.bias": normal(z["experts"], scale=0.05),
                b + "experts_gate.w": normal(experts, d, e),
                b + "experts_up.w": normal(experts, d, e),
                b + "experts_down.w": normal(experts, e, d)})
    return p


def _share(p, first, count):
    return {k: (v[first:first + count] if "_experts_" in k else v)
            for k, v in p.items()}


@pytest.fixture(scope="module")
def uncut():
    return _init(jax.random.PRNGKey(46), TINY, jnp.float32)


@pytest.fixture(scope="module")
def params(uncut):
    return _share(uncut, *TINY["share"])


def _arch(share=TINY["share"], z=TINY):
    return SinkWindowMoE(
        z["types"], z["heads"], z["kv"], z["wkv"], z["dh"], z["dv"], z["d"],
        window=z["window"], rotary_lanes=z["rot"], dense_layers=z["dense"],
        router_width=z["experts"], top_k=z["top_k"], experts=share,
        value_scale=z["scale"], rope_theta=z["theta"],
        window_rope_theta=z["wtheta"])


def _engine(p, monkeypatch, **kw):
    monkeypatch.setattr(_bd, "PREFILL_PIECE", PIECE)
    reg = MetricsRegistry()
    kw.setdefault("max_slots", SLOTS)
    kw.setdefault("prefix_reuse", False)
    kw.setdefault("decode_chunk", 4)
    eng = ServingEngine(p, arch=_arch(), max_len=T, block_tokens=B,
                        min_bucket=4, donate=False, registry=reg, **kw)
    return eng, reg


LAYOUT = dict(value_scale=TINY["scale"], rope_theta=TINY["theta"],
              window_rope_theta=TINY["wtheta"])


def _reference(p, tokens, share=TINY["share"], window=TINY["window"],
               rot=TINY["rot"], **switches):
    z = TINY
    return np.asarray(ref.forward(
        p, np.asarray(tokens)[None], z["types"], z["heads"], z["kv"],
        z["wkv"], z["dh"], window, rot, z["dense"], z["top_k"], share,
        **dict(LAYOUT, **switches)))[0]


PROMPTS = [np.arange(3, 3 + 21) % 128, (7 * np.arange(11) + 5) % 128,
           (5 * np.arange(13) + 2) % 128]
# the decode step before which each prompt is admitted: the third comes
# while the first two decode far past their windows
ADMIT_AT = (0, 0, 12)
STEPS = 30


def _through_the_cache(eng, prompts=PROMPTS, admit_at=ADMIT_AT, steps=STEPS):
    """Each prompt into a slot of its own before decode step
    ``admit_at[s]``, prefilled in the pieces the engine would dispatch,
    then greedy decode steps for ALL slots at once (a slot not admitted
    yet is a dead one).  The full planes go through whole chains; the
    window planes through the ENGINE'S OWN window chains where it has
    them (``eng.window_chains``: blocks are given back and handed to
    whoever asks next), else through whole chains too.  Returns per slot
    (tokens, logits at every position from the prompt's last on) and the
    window blocks slot 0 gave back that another slot was handed while
    slot 0 still decoded."""
    arch, chains = eng.arch, eng.window_chains
    S, nb = len(prompts), T // B
    whole = 1 + np.arange(S * nb, dtype=np.int32).reshape(S, nb)
    live = np.zeros(S, bool)

    def rows(s):
        if chains is None:
            return jnp.asarray(whole[s])
        return jnp.asarray(np.stack([whole[s], chains.table[s]]))

    def table():
        full = np.where(live[:, None], whole, 0).astype(np.int32)
        if chains is None:
            return jnp.asarray(full)
        return jnp.asarray(np.stack([full, chains.table[:S]], axis=1))

    @jax.jit
    def window(p, pk, pv, toks, at, n, row):
        x, pk, pv, _, _ = _bd._window_forward(
            p, pk, pv, toks[None], at[None], (at + n - 1)[None], row[None],
            arch)
        return arch.head(p, x[0])[n - 1], pk, pv

    @jax.jit
    def step(p, pk, pv, tok, at, tbl):
        lg, pk, pv, _, _ = _bd.paged_step_logits(p, tok, at, pk, pv, tbl,
                                                 arch)
        return lg, pk, pv

    pk, pv = eng._pk, eng._pv
    logits = [[] for _ in prompts]
    toks = [list(p_) for p_ in prompts]
    given_back, reused = set(), set()
    for j in range(steps):
        for s, prompt in enumerate(prompts):
            if admit_at[s] != j:
                continue
            pieces = eng._pieces(np.asarray(prompt), 0)
            assert len(pieces) >= 2
            for _w, padded, at, n in pieces:
                if chains is not None:
                    chains.advance(s, at, at + n - 1)
                    if s:
                        reused |= given_back & set(
                            chains.table[s][chains.table[s] > 0].tolist())
                lg, pk, pv = window(eng._p, pk, pv, padded, jnp.int32(at),
                                    jnp.int32(n), rows(s))
            live[s] = True
            logits[s].append(lg)
        last = np.zeros(S, np.int32)
        at = np.zeros(S, np.int32)
        for s in range(S):
            if live[s]:
                last[s] = int(jnp.argmax(logits[s][-1]))
                at[s] = len(toks[s])
                toks[s].append(int(last[s]))
                if chains is not None:
                    before = set(chains.table[s][chains.table[s] > 0].tolist())
                    chains.advance(s, int(at[s]), int(at[s]))
                    now = set(chains.table[s][chains.table[s] > 0].tolist())
                    if s == 0:
                        given_back |= before - now
                    else:
                        reused |= given_back & (now - before)
        lg, pk, pv = step(eng._p, pk, pv, jnp.asarray(last), jnp.asarray(at),
                          table())
        for s in range(S):
            if live[s]:
                logits[s].append(lg[s])
    return ([(np.asarray(t_), np.asarray(jnp.stack(l), np.float32))
             for t_, l in zip(toks, logits)], reused)


@pytest.fixture(scope="module")
def served(params):
    """The float32 logits through the cache under window chains, and
    under whole chains (the engine a prefix trie keeps whole)."""
    mp = pytest.MonkeyPatch()
    try:
        out = {}
        for name, reuse in (("windowed", False), ("whole", True)):
            eng, _ = _engine(params, mp, prefix_reuse=reuse)
            assert (eng.window_chains is not None) == (not reuse)
            out[name] = _through_the_cache(eng)
        return out
    finally:
        mp.undo()


def _positions(prompt_len, lg):
    return slice(prompt_len - 1, prompt_len - 1 + len(lg))


@pytest.mark.parametrize("slot", range(len(PROMPTS)))
def test_float32_through_the_cache_agrees_with_the_reference(served, params,
                                                             slot):
    """Prefill in pieces (the dense spelling) and decode steps, full and
    window planes, contexts of several windows: logits at every position,
    through chains that hold only their window."""
    toks, lg = served["windowed"][0][slot]
    prompt = PROMPTS[slot]
    assert len(toks) >= len(prompt) + STEPS - ADMIT_AT[slot]
    assert len(toks) > 3 * TINY["window"]
    want = _reference(params, toks)[_positions(len(prompt), lg)]
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
    (toks_w, lg_w), (toks_h, lg_h) = (served["windowed"][0][slot],
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
    for (toks, lg), prompt in zip(served["windowed"][0], PROMPTS):
        want = _reference(params, toks, **OMISSIONS[omission])
        worst = max(worst, float(np.abs(
            lg - want[_positions(len(prompt), lg)]).max()))
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
    eng, reg = _engine(params, monkeypatch)
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
        want = _reference(params, full)
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
    eng, _ = _engine(params, monkeypatch)
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
    eng, reg = _engine(params, monkeypatch, prefix_reuse=reuse)
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

    class _Rows:
        def __init__(self, valid):
            self.valid = valid

    x = jax.random.normal(jax.random.PRNGKey(1), (24, TINY["d"]))
    z, i = TINY, 2
    whole = np.asarray(ref.routed_ffn(uncut, i, x[None], z["top_k"],
                                      (0, z["experts"])))[0]
    total = np.zeros_like(whole)
    pairs = 0
    for first in range(0, z["experts"], 4):
        arch = _arch((first, 4))
        held = _share(uncut, first, 4)
        h = arch_mod._rms(x, held[f"block{i}_norm2.scale"], arch.eps)
        y, counts = arch_mod.routed_ffn(
            lambda nm: held[f"block{i}_{nm}"], h,
            _Rows(jnp.ones(x.shape[:-1], bool)), arch.experts, arch.top_k,
            normalise=arch.norm_topk, shared=False)
        total += np.asarray(y)
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
    z = dict(TINY)
    with pytest.raises(ValueError, match="must divide n_head"):
        _arch(z=dict(z, wkv=3))
    with pytest.raises(ValueError, match="rotary_lanes"):
        _arch(z=dict(z, rot=7))
    with pytest.raises(ValueError, match="layer types"):
        _arch(z=dict(z, types=("full", "latent")))
    p = _share(_init(jax.random.PRNGKey(0), TINY, jnp.float32), 4, 4)
    bad = dict(p)
    del bad["block1_att_sink.b"]
    with pytest.raises(ValueError, match="att_sink.b"):
        ServingEngine(bad, arch=_arch(), max_len=T, block_tokens=B,
                      prefix_reuse=False)
    bad = dict(p, **{"block0_att_qkv.w": p["block1_att_qkv.w"]})
    with pytest.raises(ValueError, match="layer 0"):
        ServingEngine(bad, arch=_arch(), max_len=T, block_tokens=B,
                      prefix_reuse=False)
