"""``serving.arch.SparseLatentMoE`` against its plain reference
(``models/sparse_latent_moe_reference.py``) at a small size that keeps
the oddities: two latent ranks and two head counts by layer type, a
stored width that is not the written one, a query latent, a gate a head,
an ``index_topk`` SMALLER than the contexts so that the selection binds,
a window smaller than the contexts, index keys in a second array of the
full planes only.  Float32 through the cache has to agree with the
reference's full forward at every generated position."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import tiny  # noqa: E402
from paddle_tpu.kernels import paged_attention as paged  # noqa: E402
from paddle_tpu.kernels import sparse_attention as sparse  # noqa: E402
from paddle_tpu.models import sparse_latent_moe_reference as ref  # noqa: E402
from paddle_tpu.observability import trace  # noqa: E402
from paddle_tpu.serving import batched_decode as _bd  # noqa: E402
from paddle_tpu.serving.arch import SparseLatentMoE  # noqa: E402
from tiny import sparse_latent_moe as fam  # noqa: E402

TINY = fam.sizes
FULL, SLIDING = TINY["full"], TINY["sliding"]
T, B, PIECE = fam.max_len, fam.block_tokens, fam.piece
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
# while the first two decode far past their windows and past index_topk
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


@pytest.mark.parametrize("chains", ["windowed", "whole"])
@pytest.mark.parametrize("slot", range(len(PROMPTS)))
def test_float32_through_the_cache_agrees_with_the_reference(served, params,
                                                             slot, chains):
    """Prefill in pieces and decode steps, sparse full planes and sliding
    planes under their bound, contexts of several windows and several
    ``index_topk``: logits at every position."""
    toks, lg, _ = served[chains][0][slot]
    prompt = PROMPTS[slot]
    assert len(toks) > 3 * TINY["window"]
    assert len(toks) > 2 * TINY["index_topk"]
    want = fam.reference(params, toks)[tiny.positions(len(prompt), lg)]
    assert np.abs(lg - want).max() < TOL


def test_a_prompt_in_pieces_across_the_index_topk_boundary(served, params):
    """Prompt 0 (21 tokens) is prefilled in pieces of 8: the second piece
    holds rows on both sides of ``t = index_topk`` (12), the first rows
    select every position, the later ones a strict subset; EVERY row of
    every piece against the reference."""
    toks, _, rows = served["whole"][0][0]
    n = len(PROMPTS[0])
    assert PIECE < TINY["index_topk"] < 2 * PIECE < n
    want = fam.reference(params, toks)[:n]
    assert np.abs(rows - want).max() < TOL


def test_sliding_blocks_are_given_back_and_reused_by_another_slot(served):
    """An engine without the trie holds the sliding planes to their
    window: while slot 0 still decodes, blocks it gave back are handed to
    a slot admitted later, and nobody's logits move (the case above)."""
    assert served["windowed"][1]
    assert not served["whole"][1]


OMISSIONS = {
    "whole_chain_attended_in_place_of_the_selection": dict(select="all"),
    "relu_left_out_of_the_index_scores": dict(index_relu=False),
    "indexer_rope_left_out": dict(index_rope=False),
    "selection_taken_from_the_first_positions": dict(select="first"),
    "sliding_planes_attended_whole": dict(windowed=False),
    "sliding_theta_replaced_by_the_full_layers": dict(sliding_theta=8e7),
    "lora_rescale_left_out": dict(rescale=False),
    "gate_left_out": dict(gate=False),
    "norm_topk_prob_left_out": dict(route_norm=False),
}


@pytest.mark.parametrize("omission", list(OMISSIONS))
def test_each_assumed_line_changed_in_the_reference_is_seen(served, params,
                                                            omission):
    worst = 0.0
    for (toks, lg, _), prompt in zip(served["whole"][0], PROMPTS):
        want = fam.reference(params, toks, **OMISSIONS[omission])
        worst = max(worst, float(np.abs(
            lg - want[tiny.positions(len(prompt), lg)]).max()))
    assert worst > 30 * TOL, worst


def test_a_table_within_index_topk_lowers_to_the_dense_latent_call():
    """Where the table cannot hold more than ``index_topk`` positions the
    selection never binds: ``sparse_attend`` IS ``attend`` on the latent
    array, and no index key is read."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 1, 4, 128)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(9, 4, 128)), jnp.float32)
    idx = jnp.zeros((9, 4, 16), jnp.float32)
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    pos = jnp.asarray([[9], [14]], jnp.int32)
    qi, wi = jnp.zeros((2, 1, 3, 16)), jnp.zeros((2, 1, 3))

    def both(topk):
        return (jax.jit(lambda *a: sparse.sparse_attend(
                    *a, topk=topk, value_lanes=16, scale=0.3)).lower(
                        q, pool, idx, table, pos, qi, wi).as_text(),
                jax.jit(lambda q, pool, table, pos: paged.attend(
                    q, pool, None, table, pos, value_lanes=16,
                    scale=0.3)).lower(q, pool, table, pos).as_text())

    # the selection reads the scores' bits: nothing else here does
    selects = lambda text: "stablehlo.bitcast_convert" in text
    body = lambda text: text[text.index("{", text.index("@main")):]
    sparse_text, dense_text = both(16)
    assert not selects(sparse_text)
    assert body(sparse_text) == body(dense_text)
    assert selects(both(8)[0])


def test_index_scores_and_sparse_attention_against_numpy():
    """The two op classes alone: scores with ``-inf`` past each row's
    position (a dead row scores nothing), and one softmax over the
    selected rows only."""
    rng = np.random.default_rng(1)
    S, W, H, d, L, V, nb, K = 2, 3, 3, 16, 128, 24, 5, 6
    q = rng.normal(size=(S, W, H, d)).astype(np.float32)
    wgt = rng.normal(size=(S, W, H)).astype(np.float32)
    idx = rng.normal(size=(1 + S * nb, B, d)).astype(np.float32)
    pool = rng.normal(size=(1 + S * nb, B, L)).astype(np.float32)
    table = 1 + np.arange(S * nb, dtype=np.int32).reshape(S, nb)
    pos = np.asarray([[9, 10, 11], [-1, -1, -1]], np.int32)
    got = np.asarray(sparse.index_scores(
        jnp.asarray(q), jnp.asarray(wgt), jnp.asarray(idx),
        jnp.asarray(table), jnp.asarray(pos)))
    keys = idx[table].reshape(S, nb * B, d)
    want = np.einsum("swht,swh->swt", np.maximum(
        np.einsum("swhd,std->swht", q, keys), 0), wgt)
    live = np.arange(nb * B)[None, None] <= pos[..., None]
    assert np.all(np.isneginf(got[~live]))
    assert np.abs(got[live] - want[live]).max() < 1e-4
    sel = np.asarray(sparse.select_positions(jnp.asarray(got), K))
    assert set(sel[0, 0]) == set(np.argsort(-want[0, 0, :10])[:K])
    assert (np.diff(sel[0]) > 0).all()       # ascending by position
    assert (sel[1] == -1).all()              # the dead slot selects nothing
    qa = rng.normal(size=(S, W, 4, L)).astype(np.float32)
    ctx = np.asarray(sparse.sparse_latent_attention(
        jnp.asarray(qa), jnp.asarray(pool), jnp.asarray(table),
        jnp.asarray(pos), jnp.asarray(sel), V, scale=0.2))
    rows = pool[table].reshape(S, nb * B, L)
    for w in range(W):
        r = rows[0][sel[0, w]]
        s = qa[0, w] @ r.T * 0.2
        p = np.exp(s - s.max(-1, keepdims=True))
        assert np.abs(ctx[0, w] - (p / p.sum(-1, keepdims=True)) @ r[:, :V]
                      ).max() < 1e-4
    assert not ctx[1].any()                  # the dead slot: zeros


@pytest.mark.parametrize("width,window", [(1, 9), (4, 9), (16, 9), (16, 40)])
def test_a_latent_plane_under_a_lower_bound(width, window):
    """``attend(.., pool_v=None, window=)``: the streaming spelling (the
    ``xla_ref`` scan and the Mosaic kernel, interpreted) and the dense
    spelling of a wide piece, which gathers ``window_entries`` and no
    more, against a NumPy softmax over ``t - window < j <= t``; the
    entries under the bound name the trash block, as an engine that gave
    them back leaves them."""
    rng = np.random.default_rng(width)
    S, h, L, V, nb = 2, 4, 128, 48, 16
    pool = rng.normal(size=(1 + S * nb, B, L)).astype(np.float32)
    pool[0] = 1e3                            # the trash block: never read
    table = 1 + np.arange(S * nb, dtype=np.int32).reshape(S, nb)
    start = np.asarray([37, 20])
    pos = (start[:, None] + np.arange(width)[None]).astype(np.int32)
    for s in range(S):                       # given back under the bound
        table[s, :max(start[s] - window + 1, 0) // B] = 0
    q = rng.normal(size=(S, width, h, L)).astype(np.float32)
    rows = pool[np.where(table == 0, 1, table)].reshape(S, nb * B, L)
    want = np.zeros((S, width, h, V), np.float32)
    for s in range(S):
        for w in range(width):
            t = pos[s, w]
            j = np.arange(max(t - window + 1, 0), t + 1)
            sc = q[s, w] @ rows[s, j].T * 0.25
            p = np.exp(sc - sc.max(-1, keepdims=True))
            want[s, w] = (p / p.sum(-1, keepdims=True)) @ rows[s, j, :V]
    args = [jnp.asarray(a) for a in (q, pool)] + [None] + [
        jnp.asarray(a) for a in (table, pos)]
    how = dict(value_lanes=V, scale=0.25, window=window)
    got = np.asarray(paged.attend(*args, **how))
    assert np.abs(got - want).max() < 2e-4
    if width < paged.DENSE_WINDOW:
        mosaic = np.asarray(paged.paged_attention_pallas(
            *args, interpret=True, **how))
        assert np.abs(mosaic - want).max() < 2e-4
    else:
        assert paged.window_entries(nb, B, width, window) < nb


def test_the_shares_add_up_to_the_uncut_layer(uncut):
    """The routed parts of all four shares (4 x 4 experts) and the shared
    expert counted once are the uncut reference's layer output."""
    z, i = TINY, 2
    x = jax.random.normal(jax.random.PRNGKey(1), (24, z["d"]))
    whole = np.asarray(ref.routed_ffn(uncut, i, x, z["top_k"],
                                      (0, z["experts"]), z["scale"]))
    shared = np.asarray(ref.routed_ffn(uncut, i, x, z["top_k"],
                                       (0, z["experts"]), z["scale"],
                                       routed=False))
    parts, pairs = [], 0
    for first in range(0, z["experts"], 4):
        y, counts = tiny.routed_alone(fam, uncut, i, x, (first, 4))
        parts.append(y - shared)
        pairs += counts[1]
    assert pairs == 24 * z["top_k"]          # every pair is some chip's
    assert np.abs(shared + sum(parts) - whole).max() < TOL


PUBLISHED = {
    "full": {"heads": 128, "q_rank": 1024, "rank": 512, "nope": 128,
             "rope": 64, "v": 128, "theta": 8e7},
    "sliding": {"heads": 64, "q_rank": 1024, "rank": 1024, "nope": 192,
                "rope": 64, "v": 128, "theta": 5e4},
    "types": tuple("full" if i < 2 or i % 4 == 1 else "sliding"
                   for i in range(46)),
}


def _count(shapes):
    return sum(int(np.prod(s)) for s in shapes.values())


def test_parameter_count_at_the_published_config():
    """The layer equations at the published widths count 279,551,726,592
    parameters, and the chip's share of the configuration 4,087,154,176."""
    types = PUBLISHED["types"]
    assert types.count("full") == 13 and types.count("sliding") == 33
    assert types[:6] == ("full", "full", "sliding", "sliding", "sliding",
                         "full") and types[-1] == "full"

    def shapes(rows, held, types):
        return ref.param_shapes(5120, rows, 13824, 1536, 256, held, types,
                                PUBLISHED["full"], PUBLISHED["sliding"],
                                64, 128, 1)

    whole = shapes(152064, 256, types)
    assert _count(whole) == 279_551_726_592
    layer = lambda i: _count({k: v for k, v in whole.items()
                              if k.startswith(f"block{i}_")})
    ffn = lambda i: _count({k: v for k, v in whole.items()
                            if k.startswith(f"block{i}_") and any(
                                n in k for n in ("ffn_", "router", "shared_",
                                                 "experts_"))})
    assert layer(1) - ffn(1) == 144_060_160       # a full layer's attention
    assert layer(2) - ffn(2) == 90_845_184        # a sliding layer's
    assert ffn(0) == 212_336_640
    assert ffn(1) == 257 * 23_592_960 + 1_310_976
    assert _count({k: v for k, v in whole.items() if k.startswith(
        "block1_idx_")}) == 9_371_904
    assert _count(shapes(19008, 32, types[:5])) == 4_087_154_176


def test_the_engine_serves_shared_heads_forks_and_counts(params, monkeypatch):
    """The whole engine with the trie: a head served once, a second
    request whose suffix selects rows INSIDE the shared head (prefix hit
    past ``index_topk``), a partial block forked copy-on-write with its
    index keys, and the counters and span attributes that say what is
    stored and what is attended."""
    eng, reg = fam.engine(params, monkeypatch, prefix_reuse=True,
                          cache_blocks=16, max_slots=2)
    head = (3 * np.arange(26) + 2) % 128
    first = np.concatenate([head, [9, 8, 7]])
    second = np.concatenate([head, [1, 2, 3, 4, 5]])
    tracer = trace.Tracer(enabled=True)
    old = trace.get_tracer()
    trace.set_tracer(tracer)
    try:
        eng.generate_many([first], max_new_tokens=[6])
        out, = eng.generate_many([second], max_new_tokens=[10])
    finally:
        trace.set_tracer(old)
    st = eng.stats()
    fills = [e["args"] for e in tracer.events()
             if e["name"] == "serving.prefill"]
    assert fills[1]["prefix_hit"] == len(head) > 2 * TINY["index_topk"]
    assert st.get("serving.cow_copies", 0) >= 1
    want = fam.reference(params, out)[len(second) - 1:len(out) - 1]
    gap = want.max(-1) - want[np.arange(len(want)), out[len(second):]]
    assert gap.max() < 1e-3, gap.max()
    # the suffix's rows select inside the shared head: the reference's own
    # selection of the last prompt row names positions under the hit
    a, c_q, _, _ = ref._latents(
        params["tok_emb.w"][jnp.asarray(second)],
        {k: params[f"block0_{k}"] for k in ref._LATENT_KEYS},
        rank=FULL["rank"], theta=FULL["theta"], eps=1e-5, rescale=True)
    keep = np.asarray(ref._selection(
        a, c_q, {k: params[f"block0_{k}"] for k in ref._INDEX_KEYS},
        heads=TINY["index_heads"], lanes=TINY["index_dim"],
        rope=FULL["rope"], topk=TINY["index_topk"], theta=FULL["theta"],
        relu=True, rotary=True, select="topk"))
    assert keep[len(second) - 1, :len(head)].sum() >= TINY["index_topk"] // 2
    # what is stored
    nfull, nslide = 2, 3
    assert [a.shape[1:] for a in eng._pk] == [(B, 128)] * 5
    assert [a.shape[1:] for a in eng._pv] == [(B, TINY["index_dim"])] * nfull
    assert st["serving.index_planes"] == nfull
    assert st["serving.latent_window_planes"] == nslide
    assert st["serving.index_topk"] == TINY["index_topk"]
    assert st["serving.index_lanes_stored"] == TINY["index_dim"]
    assert st["serving.latent_lanes_stored{kind=full}"] == 128
    assert st["serving.latent_lanes_stored{kind=sliding}"] == 128
    assert st["serving.kv_stored_bytes_per_token"] == 4 * (
        nfull * (128 + 16) + nslide * 128)
    assert st["serving.kv_bytes_per_token"] == 4 * (
        nfull * (24 + 16) + nslide * 40)
    chunks = [e["args"] for e in tracer.events()
              if e["name"] == "serving.decode_chunk"]
    assert all(a["index_planes"] == nfull
               and a["latent_window_planes"] == nslide
               and a["attn_form"] == "absorbed" for a in chunks + fills)
    # what is attended: every real prefill row j scores j + 1 index keys
    # and attends min(j + 1, index_topk) of the rows
    runs = [(1, len(first)), (len(head) + 1, len(second) - len(head))]
    rows = np.concatenate([np.arange(a, a + n) for a, n in runs])
    assert st["serving.index_positions_scored{phase=prefill}"] == (
        nfull * rows.sum())
    assert st["serving.sparse_positions_attended{phase=prefill}"] == (
        nfull * np.minimum(rows, TINY["index_topk"]).sum())
    assert st["serving.latent_positions_read{phase=prefill}"] == (
        nfull * np.minimum(rows, TINY["index_topk"]).sum()
        + nslide * np.minimum(rows, TINY["window"]).sum())
    assert st["serving.latent_window_calls{phase=prefill}"] == nslide * sum(
        a["pieces"] for a in fills)
    steps = sum(a["steps"] for a in chunks)
    assert st["serving.latent_window_calls{phase=decode}"] == nslide * steps
    scored = st["serving.index_positions_scored{phase=decode}"]
    picked = st["serving.sparse_positions_attended{phase=decode}"]
    assert picked == nfull * steps * TINY["index_topk"] < scored
    # one request at a time in a table of two slots: the sparse calls ran
    # for one slot where one was live
    assert st["serving.sparse_slots_live"] == nfull * steps
    assert st["serving.sparse_slots_run"] == nfull * steps * sparse.slots_run(
        1, 2) == nfull * steps


def test_a_copy_on_write_fork_copies_the_index_keys(params):
    """``make_prefill``'s leading copy: block ``src`` lands on ``dst`` in
    EVERY array of every plane, the full planes' index keys among them."""
    arch = fam.arch()
    rng = np.random.default_rng(3)
    shapes = [arch.plane_block_shapes(i, B, jnp.float32) for i in range(5)]
    pk = tuple(jnp.asarray(rng.normal(size=(6,) + s[0]), jnp.float32)
               for s in shapes)
    pv = tuple(jnp.asarray(rng.normal(size=(6,) + s[1]), jnp.float32)
               for s in shapes if len(s) > 1)
    assert len(pv) == 2 and [arch.second_array(i) for i in range(5)] == [
        0, 1, None, None, None]
    fn = _bd.make_prefill(arch, 8, donate=False)
    out = fn(params, pk, pv, jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.int32),
             jnp.int32(0), jnp.zeros(T // B, jnp.int32),
             jnp.zeros(8, jnp.int32), jnp.int32(0), jnp.int32(0),
             jnp.int32(2), jnp.int32(4))
    for before, after in zip(pk + pv, out[0] + out[1]):
        assert np.array_equal(np.asarray(after[4]), np.asarray(before[2]))
        assert np.array_equal(np.asarray(after[3]), np.asarray(before[3]))


def test_refusals(params):
    with pytest.raises(ValueError, match="a layer is 'full' or 'sliding'"):
        SparseLatentMoE(("window",), 64, FULL, SLIDING, 9, 3, 16, 12, 0,
                        16, 4, (0, 4))
    with pytest.raises(ValueError, match="rotates the full layers' 8 lanes"):
        SparseLatentMoE(("full",), 64, FULL, SLIDING, 9, 3, 4, 12, 0, 16,
                        4, (0, 4))
    bad = dict(params)
    bad.pop("block1_idx_w.w")
    with pytest.raises(ValueError, match="parameters lack block1_idx_w.w"):
        fam.arch().check_params(bad, T)
    bad = dict(params, **{"block2_att_kva.w": params["block0_att_kva.w"]})
    with pytest.raises(ValueError, match="layer 2 .sliding. holds att_kva.w"):
        fam.arch().check_params(bad, T)
    with pytest.raises(ValueError, match="no group"):
        paged.attend(jnp.zeros((1, 1, 4, 128)), jnp.zeros((2, 4, 128)), None,
                     jnp.zeros((1, 2), jnp.int32), jnp.zeros((1, 1), jnp.int32),
                     group=2, value_lanes=16)


@pytest.mark.parametrize("shape,k", [((2, 3, 64), 12), ((1, 8, 520), 100),
                                     ((2, 1, 34048), 2048)])
def test_the_selection_is_exact_without_a_sort(shape, k):
    """``select_positions`` against a NumPy sort: exactly the ``k``
    largest scores of every row (all of them and ``-1`` beyond where a
    row has fewer above ``-inf``), ascending by position, ties at the
    ``k``-th score going to the lower position; no ``top_k`` and no sort
    in the lowered program."""
    rng = np.random.default_rng(k)
    S, W, T = shape
    sc = rng.normal(size=shape).astype(np.float32)
    pos = rng.integers(0, T, size=(S, W))
    pos[0, 0] = k // 2
    sc = np.where(np.arange(T)[None, None] <= pos[..., None], sc, -np.inf)
    sc[-1, -1] = np.where(np.isfinite(sc[-1, -1]),
                          np.round(sc[-1, -1] * 2) / 2, -np.inf)    # ties
    fn = jax.jit(lambda x: sparse.select_positions(x, k))
    text = fn.lower(jnp.asarray(sc)).as_text()
    assert "chlo.top_k" not in text and "stablehlo.sort" not in text
    got = np.asarray(fn(jnp.asarray(sc)))
    for s in range(S):
        for w in range(W):
            row, n = sc[s, w], int(min(pos[s, w] + 1, k))
            live = got[s, w][got[s, w] >= 0]
            assert len(live) == n and (np.diff(live) > 0).all()
            want = np.sort(row)[::-1][:n]
            assert np.array_equal(np.sort(row[live])[::-1], want)
            tied = np.flatnonzero(row == want[-1])
            mine = [j for j in live if row[j] == want[-1]]
            assert mine == tied[:len(mine)].tolist()


# what a decode step's table looks like to ``sparse_attend``: which slots
# are dead (a table row of zeros, every row at ``pos = -1``)
DEAD = {
    "none": lambda S: np.zeros(S, bool),
    "first": lambda S: np.arange(S) == 0,
    "last": lambda S: np.arange(S) == S - 1,
    "alternating": lambda S: np.arange(S) % 2 == 1,
    "all_but_one": lambda S: np.arange(S) != S // 2,
    "all": lambda S: np.ones(S, bool),
}
_SP = dict(nb=6, blk=8, topk=12, L=128, V=16, heads=4, h_idx=2, d_idx=16)


@functools.lru_cache(maxsize=None)
def _sparse_call():
    return jax.jit(lambda *a: sparse.sparse_attend(
        *a, topk=_SP["topk"], value_lanes=_SP["V"], scale=0.3))


def _sparse_table(S, W, dead, seed=0):
    """``sparse_attend``'s operands for ``S`` slots of ``W`` rows, bf16
    pools as the cell's, the slots of ``dead`` as a released slot is; at
    ``W > 1`` the first live slot's first two rows are dead too."""
    rng = np.random.default_rng(seed)
    nb, blk = _SP["nb"], _SP["blk"]
    blocks = 1 + S * nb + 3
    bf = jnp.bfloat16
    pool = jnp.asarray(rng.normal(size=(blocks, blk, _SP["L"])), bf)
    idx = jnp.asarray(rng.normal(size=(blocks, blk, _SP["d_idx"])), bf)
    table = np.stack([rng.permutation(blocks - 1)[:nb] + 1
                      for _ in range(S)]).astype(np.int32)
    pos = (rng.integers(_SP["topk"] + W, nb * blk, size=(S, 1)) - W
           + np.arange(W)[None]).astype(np.int32)
    table[dead], pos[dead] = 0, -1
    if W > 1 and not dead.all():
        pos[np.flatnonzero(~dead)[0], :2] = -1
    q = jnp.asarray(rng.normal(size=(S, W, _SP["heads"], _SP["L"])), bf)
    qi = jnp.asarray(
        rng.normal(size=(S, W, _SP["h_idx"], _SP["d_idx"])), bf)
    wi = jnp.asarray(rng.normal(size=(S, W, _SP["h_idx"])), jnp.float32)
    return q, pool, idx, jnp.asarray(table), jnp.asarray(pos), qi, wi


@pytest.mark.parametrize("W", [1, 4])
@pytest.mark.parametrize("S", [1, 3, 5, 10])
@pytest.mark.parametrize("pattern", list(DEAD))
def test_the_sparse_call_runs_for_the_live_slots(pattern, S, W):
    """Dead slots in every pattern of a small table: every live row is,
    to the bit, the row of the call made on its slot alone (what the
    call was before it packed its live slots), every dead row zeros."""
    dead = DEAD[pattern](S)
    q, pool, idx, table, pos, qi, wi = _sparse_table(S, W, dead, seed=S + W)
    got = np.asarray(_sparse_call()(q, pool, idx, table, pos, qi, wi))
    assert got.shape == (S, W, _SP["heads"], _SP["V"])
    rows_live = np.asarray(pos) >= 0
    assert not got[~rows_live].astype(np.float32).any()
    for s in np.flatnonzero(~dead):
        one = slice(s, s + 1)
        alone = np.asarray(_sparse_call()(
            q[one], pool, idx, table[one], pos[one], qi[one], wi[one]))
        assert alone[0][rows_live[s]].astype(np.float32).any()
        assert got[s].tobytes() == alone[0].tobytes(), (pattern, S, W, s)


def _conds(jaxpr):
    """Every ``cond`` equation of a jaxpr, its sub-jaxprs' too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _conds(sub)


@pytest.mark.parametrize("S", [3, 5, 10])
def test_one_conditional_a_call_a_branch_a_slot_count_and_no_sort(S):
    """The lowered call holds ONE ``case`` with as many branches as
    ``slots_run`` has values (none among them), and packs its live slots
    with no sort; a table of one slot (a prefill piece) holds none."""
    args = _sparse_table(S, 1, np.zeros(S, bool))
    text = _sparse_call().lower(*args).as_text()
    assert text.count("stablehlo.case") == 1
    assert "stablehlo.sort" not in text and "chlo.top_k" not in text
    (cond,) = _conds(jax.make_jaxpr(_sparse_call())(*args).jaxpr)
    assert len(cond.params["branches"]) == len(
        {sparse.slots_run(n, S) for n in range(S + 1)})
    one = _sparse_call().lower(*_sparse_table(1, 4, np.zeros(1, bool)))
    assert "stablehlo.case" not in one.as_text()


@pytest.mark.parametrize("S", [1, 2, 3, 4, 5, 7, 9, 10, 12, 16, 33, 64])
def test_slots_run_holds_the_live_slots_in_a_few_counts(S):
    """Monotone in the live slots, at least as many, the table's at
    most and AT all of them live, none for none; two counts at most
    beside none whatever the table (5 and 10 of ten)."""
    run = [sparse.slots_run(n, S) for n in range(S + 1)]
    assert run[0] == 0 and run[S] == S
    assert all(a <= b for a, b in zip(run, run[1:]))
    assert all(n <= r <= S for n, r in enumerate(run))
    assert len(set(run[1:])) <= 2
    if S == 10:
        assert sorted(set(run)) == [0, 5, 10]


@pytest.mark.parametrize("stats,want", [
    ({}, None),                                      # a parent: no counter
    ({"serving.sparse_slots_live": 0.0}, None),
    ({"serving.sparse_slots_live": 28.0, "serving.sparse_slots_run": 40.0},
     70.0),
    ({"serving.sparse_slots_live": 28.0, "serving.sparse_slots_run": 100.0},
     28.0),
])
def test_the_run_slot_live_share_reads_the_engines_counters(stats, want):
    from chipbench import run as bench_run

    reader = bench_run.load_reader("dsa.run_slot_live_share")
    assert reader.read({"stats": stats}) == want
