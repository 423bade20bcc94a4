"""Paged prefix-reuse KV cache (paddle_tpu/serving/kvcache.py) — block
pool refcount lifecycle, prefix-trie match/insert/copy-on-write fork,
LRU eviction under capacity pressure, and the engine-level bit-exact
served-vs-single-stream contract parameterized over prefix reuse on/off
and f32/bf16 (docs/serving.md "Numerics contract": token identity in
f32, the float32-reference margin in bf16).  All on the CPU backend
(conftest), tiny model shapes."""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import transformer
from paddle_tpu.observability import metrics as _obs
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kvcache import BlockPool, PoolExhausted, PrefixTrie


# -- block pool: refcount lifecycle -----------------------------------------

def test_pool_alloc_ref_deref_free():
    pool = BlockPool(num_blocks=6, block_tokens=4)
    assert pool.free_blocks == 5 and pool.blocks_in_use == 0
    a, b = pool.alloc(2)
    assert pool.blocks_in_use == 2
    assert pool.refcount(a) == pool.refcount(b) == 1
    pool.ref(a)                       # a second owner (trie or slot)
    assert pool.refcount(a) == 2
    pool.deref(a)
    assert pool.blocks_in_use == 2    # still held once
    pool.deref(a)
    pool.deref(b)
    assert pool.blocks_in_use == 0 and pool.free_blocks == 5


def test_pool_trash_block_pinned():
    pool = BlockPool(num_blocks=4, block_tokens=2)
    assert pool.refcount(BlockPool.TRASH) == 1
    pool.ref(BlockPool.TRASH)         # no-ops: trash is unaccounted
    pool.deref(BlockPool.TRASH)
    assert pool.refcount(BlockPool.TRASH) == 1
    got = pool.alloc(3)               # every real block
    assert BlockPool.TRASH not in got


def test_pool_exhausted_is_all_or_nothing():
    pool = BlockPool(num_blocks=4, block_tokens=2)
    pool.alloc(2)
    with pytest.raises(PoolExhausted):
        pool.alloc(2)                 # only 1 free
    assert pool.free_blocks == 1      # the failed alloc took nothing


def test_pool_double_free_rejected():
    pool = BlockPool(num_blocks=4, block_tokens=2)
    (b,) = pool.alloc(1)
    pool.deref(b)
    with pytest.raises(ValueError):
        pool.deref(b)
    with pytest.raises(ValueError):
        pool.ref(b)                   # can't revive a freed block


# -- prefix trie: match / insert / CoW / LRU --------------------------------

def _trie(num_blocks=32, block_tokens=4, budget=16):
    pool = BlockPool(num_blocks, block_tokens)
    return pool, PrefixTrie(pool, budget)


def test_trie_insert_then_full_match():
    pool, trie = _trie()
    toks = list(range(100, 112))      # 3 full blocks of 4
    bids = pool.alloc(3)
    assert trie.insert(toks, bids) == 3
    # trie holds one ref each; our allocation still holds the other
    assert all(pool.refcount(b) == 2 for b in bids)
    shared, cow, hit = trie.match(toks, limit=len(toks) - 1)
    # limit 11 caps the match at 2 full blocks + a 3-token CoW tail
    assert shared == bids[:2]
    assert cow == (bids[2], 3)
    assert hit == 11
    # an unrelated prompt misses entirely
    shared, cow, hit = trie.match(list(range(50, 62)), limit=11)
    assert shared == [] and cow is None and hit == 0


def test_trie_cow_partial_match():
    pool, trie = _trie()
    toks = list(range(100, 108))
    bids = pool.alloc(2)
    trie.insert(toks, bids)
    # diverge inside the second block: first block shared, second CoW
    fork = toks[:6] + [999, 998]
    shared, cow, hit = trie.match(fork, limit=len(fork) - 1)
    assert shared == [bids[0]]
    assert cow == (bids[1], 2)        # 2 common tokens into the block
    assert hit == 6
    # diverge inside the FIRST block: pure CoW, nothing fully shared
    fork2 = toks[:3] + [999] * 5
    shared, cow, hit = trie.match(fork2, limit=len(fork2) - 1)
    assert shared == [] and cow == (bids[0], 3) and hit == 3


def test_trie_duplicate_insert_keeps_existing():
    pool, trie = _trie()
    toks = list(range(100, 108))
    first = pool.alloc(2)
    trie.insert(toks, first)
    dup = pool.alloc(2)
    assert trie.insert(toks, dup) == 0      # chunks already cached
    assert all(pool.refcount(b) == 1 for b in dup)  # ours stays private
    shared, _, _ = trie.match(toks, limit=7)
    assert shared == [first[0]]


def test_trie_refcount_lifecycle_through_release():
    """The engine pattern: match -> ref -> (serve) -> deref leaves the
    trie's own references intact; clear() releases them."""
    pool, trie = _trie()
    toks = list(range(100, 108))
    bids = pool.alloc(2)
    trie.insert(toks, bids)
    for b in bids:                    # slot releases its own refs
        pool.deref(b)
    assert all(pool.refcount(b) == 1 for b in bids)   # trie-only now
    assert pool.blocks_in_use == 2
    trie.clear()
    assert pool.blocks_in_use == 0    # refcount zero -> freed


def test_trie_lru_eviction_under_capacity_pressure():
    pool, trie = _trie(num_blocks=32, block_tokens=4, budget=4)
    # insert three 2-block chains; budget 4 trie-only blocks forces the
    # LEAST RECENTLY USED chain's tail out
    chains = []
    for base in (100, 200, 300):
        toks = list(range(base, base + 8))
        bids = pool.alloc(2)
        trie.insert(toks, bids)
        for b in bids:
            pool.deref(b)             # trie-only
        trie.enforce_budget()         # the engine's release-path call
        chains.append((toks, bids))
    # chain 0 was least recently touched: its blocks evicted first
    assert len(trie) == 4
    s0, _, _ = trie.match(chains[0][0], limit=7)
    assert s0 == []                   # fully evicted
    s2, _, _ = trie.match(chains[2][0], limit=7)
    assert s2 == [chains[2][1][0]]    # most recent survives
    # every surviving trie block is still accounted, none leaked
    assert pool.blocks_in_use == len(trie)


def test_trie_never_evicts_slot_referenced_chain():
    pool, trie = _trie(num_blocks=32, block_tokens=4, budget=1)
    toks = list(range(100, 108))
    bids = pool.alloc(2)              # "slot" keeps its refs live
    trie.insert(toks, bids)
    trie.enforce_budget()             # budget 1 < 2 cached blocks, but
    shared, _, _ = trie.match(toks, limit=7)
    assert shared == [bids[0]]        # referenced chain untouched
    for b in bids:
        pool.deref(b)                 # slot leaves -> now evictable
    trie.enforce_budget()
    assert trie._trie_only_count() <= 1


def test_trie_evict_lru_frees_for_alloc():
    pool, trie = _trie(num_blocks=6, block_tokens=4, budget=8)
    bids = pool.alloc(4)
    trie.insert(list(range(100, 116)), bids)
    for b in bids:
        pool.deref(b)
    assert pool.free_blocks == 1
    with pytest.raises(PoolExhausted):
        pool.alloc(3)
    freed = trie.evict_lru(2)
    assert freed == 2
    assert len(pool.alloc(3)) == 3    # now fits


# -- engine-level: bit-exact identity with reuse on/off, f32 + bf16 ---------

VOCAB, NL, NH, DM, T = 50, 2, 2, 32, 32


def _make_params(dtype="float32"):
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        transformer.build(vocab_size=VOCAB, n_layer=NL, n_head=NH,
                          d_model=DM, max_len=T, dropout_rate=0.0,
                          dtype=dtype)
    exe = pt.Executor()
    exe.run(startup)
    return transformer.extract_params(program=main)


@pytest.fixture(autouse=True)
def fresh_serving_metrics():
    _obs.get_registry().clear(prefix="serving.")
    yield


# What a bf16 engine may differ by from the float32 reference on the
# same weights: the gap between a served token's reference logit and the
# reference maximum at its position.  Measured on this model over twelve
# runs (prompt seeds 7-12, reuse on and off; PR 30): the worst sound gap
# is 0.0063, one near-tie of twenty (the reference's own top two lie
# 0.0063 apart there; its closest pair on any served path is 0.0035
# apart) that bf16 rounding resolves the other way, after which the
# streams agree again; every other served token is the reference's
# argmax.  A token drawn at random lies a median 1.9-2.0 under the
# maximum.  0.05 is eight times the worst sound gap and a fortieth of a
# random token's: it passes a near-tie and fails any other token.
BF16_LOGIT_MARGIN = 0.05


def _reference_gap(params, prompt, served):
    """The worst gap, over the generated tokens of ``served``, between
    the token's logit and the maximum under the float32 reference
    forward of the same weights, teacher-forced on the served tokens
    (the check of ``chipbench/runners/serve.py``)."""
    import jax.numpy as jnp

    _, logits = transformer.generate(
        params, served[None], max_len=len(served), n_layer=NL, n_head=NH,
        d_model=DM, compute_dtype=jnp.float32)
    at = np.asarray(logits)[0][len(prompt) - 1:len(served) - 1]
    new = served[len(prompt):]
    return float((at.max(axis=-1) - at[np.arange(len(new)), new]).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("reuse", [True, False])
def test_served_equals_single_stream_with_prefix_traffic(dtype, reuse):
    """The acceptance bar over the PAGED cache, as docs/serving.md
    "Numerics contract" states it: shared-prefix traffic (full-block
    hits AND copy-on-write forks when reuse is on) through the batched
    engine produces, in f32, exactly the tokens of running each request
    ALONE through transformer.generate (greedy, same weights, prefix
    reuse on or off); in bf16 prefill is a window forward whose
    roundings may differ from the token steps' in the last place, so
    every served token's float32-reference logit lies within
    ``BF16_LOGIT_MARGIN`` of the reference maximum, teacher-forced on
    the served tokens (a near-tie may resolve either way)."""
    params = _make_params(dtype)
    if dtype == "bfloat16":
        import jax.numpy as jnp

        params = {k: (jnp.asarray(v, jnp.bfloat16)
                      if (k.startswith("block") or k.startswith("lm_head"))
                      and k.endswith(".w") else v)
                  for k, v in params.items()}
    eng = ServingEngine(params, NL, NH, DM, max_len=T, max_slots=3,
                        decode_chunk=5, min_bucket=4, block_tokens=4,
                        prefix_reuse=reuse)
    rng = np.random.default_rng(7)
    base = rng.integers(1, VOCAB, (12,)).astype(np.int32)
    prompts = [
        base.copy(),                                   # cold
        base.copy(),                                   # full-block hits
        np.concatenate([base[:6],                      # CoW fork at 6
                        rng.integers(1, VOCAB, (5,)).astype(np.int32)]),
        rng.integers(1, VOCAB, (9,)).astype(np.int32),  # unrelated
        base[:10].copy(),                              # shorter re-serve
    ]
    # two waves so later requests hit chains the first wave cached
    outs = eng.generate_many(prompts[:2], max_new_tokens=8)
    outs += eng.generate_many(prompts[2:], max_new_tokens=8)
    for p, o in zip(prompts, outs):
        o = np.asarray(o)
        assert o.shape == (len(p) + 8,)
        if dtype == "bfloat16":
            np.testing.assert_array_equal(o[:len(p)], p)
            assert _reference_gap(params, p, o) <= BF16_LOGIT_MARGIN
            continue
        ref, _ = transformer.generate(params, p[None], max_len=T,
                                      n_layer=NL, n_head=NH, d_model=DM,
                                      return_logits=False)
        np.testing.assert_array_equal(o, np.asarray(ref)[0][: len(p) + 8])
    st = eng.stats()
    if reuse:
        assert st["serving.prefix_hit_rate"] > 0
        assert st["serving.cow_copies"] >= 1
    else:
        assert st.get("serving.prefix_hit_rate", 0.0) == 0.0
        assert eng.prefix_trie is None


def test_architecture_object_serves_prefix_traffic_like_positional():
    """Prefix hits and a copy-on-write fork through an engine built
    from ``arch=Gpt2(...)``: token-identical to the positional engine,
    same trie and pool accounting."""
    from paddle_tpu.serving.arch import Gpt2

    params = _make_params()
    rng = np.random.default_rng(9)
    base = rng.integers(1, VOCAB, (12,)).astype(np.int32)
    prompts = [base.copy(), base.copy(),
               np.concatenate([base[:6],
                               rng.integers(1, VOCAB, (5,)).astype(np.int32)])]
    kw = dict(max_len=T, max_slots=3, decode_chunk=5, min_bucket=4,
              block_tokens=4)
    outs, stats = [], []
    from paddle_tpu.observability.metrics import MetricsRegistry

    for eng in (ServingEngine(params, NL, NH, DM,
                              registry=MetricsRegistry(), **kw),
                ServingEngine(params, arch=Gpt2(NL, NH, DM),
                              registry=MetricsRegistry(), **kw)):
        out = eng.generate_many(prompts[:1], max_new_tokens=8)
        outs.append(out + eng.generate_many(prompts[1:], max_new_tokens=8))
        st = eng.stats()
        stats.append({k: st[k] for k in (
            "serving.prefix_hit_tokens", "serving.cow_copies",
            "serving.blocks_in_use", "serving.kv_blocks_total",
            "serving.kv_pool_bytes")})
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)
    assert stats[0] == stats[1] and stats[0]["serving.cow_copies"] >= 1


# -- engine-level: dead slots and the entries a paged call must visit --------

_CHUNK, _SLOTS = 4, 3
_MAX_NEW = (3, 11, 7)


def _serve_with_a_slot_released_mid_run(params):
    """Three requests admitted together into an engine of three slots;
    the first finishes after one chunk, so its slot rides dead (table
    row 0, attention at ``pos = -1``) through the others' later chunks.
    Asserts the single-stream tokens; returns the engine and prompts."""
    eng = ServingEngine(params, NL, NH, DM, max_len=T, max_slots=_SLOTS,
                        decode_chunk=_CHUNK, min_bucket=4, block_tokens=4,
                        prefix_reuse=False)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, VOCAB, (l,)).astype(np.int32)
               for l in (9, 5, 12)]
    outs = eng.generate_many(prompts, max_new_tokens=list(_MAX_NEW))
    for p, o, m in zip(prompts, outs, _MAX_NEW):
        ref, _ = transformer.generate(params, p[None], max_len=T,
                                      n_layer=NL, n_head=NH, d_model=DM,
                                      return_logits=False)
        np.testing.assert_array_equal(o, np.asarray(ref)[0][: len(p) + m])
    return eng, prompts


def test_engine_counts_the_table_entries_a_paged_call_must_visit():
    """``serving.paged_entries_live`` / ``_total`` equal the arithmetic
    over the requests' lengths: at each chunk's start a live request
    holds ``ceil((prompt + tokens) / B)`` entries with a key it attends,
    of ``max_slots x blocks_per_slot`` in the table; the tokens are the
    single-stream tokens although a slot dies mid-run."""
    eng, prompts = _serve_with_a_slot_released_mid_run(_make_params())
    B = eng.block_tokens
    # prefill leaves one token; every chunk adds ``_CHUNK`` more until
    # max_new is reached
    live, chunks = 0, 0
    for p, m in zip(prompts, _MAX_NEW):
        k = 0
        while 1 + k * _CHUNK < m:
            live += -(-(len(p) + 1 + k * _CHUNK) // B)
            k += 1
        chunks = max(chunks, k)
    st = eng.stats()
    assert st["serving.paged_entries_live"] == live
    # one row a block: an entry an iteration
    assert st["serving.paged_iterations_live"] == live
    assert st["serving.paged_entries_total"] == chunks * _SLOTS * (T // B)
    assert 0 < live < st["serving.paged_entries_total"]


def test_engine_serves_single_stream_tokens_through_the_mosaic_kernel(
        monkeypatch):
    """The same traffic with every attention call routed to the
    Mosaic kernel (interpret mode; off the chip the registry would
    resolve ``xla_ref``): the dead slot's ``pos = -1`` rows and the
    chains' unvisited tails change no token."""
    from paddle_tpu.kernels import paged_attention

    monkeypatch.setattr(
        paged_attention, "attend",
        lambda qh, pk, pv, table, pos: paged_attention.paged_attention_pallas(
            qh, pk, pv, table, pos, interpret=True))
    _serve_with_a_slot_released_mid_run(_make_params())


def test_engine_pool_accounting_no_leak():
    """Every served request returns its blocks: with reuse OFF the pool
    drains to zero; with reuse ON exactly the trie-held blocks remain
    and clear() returns the pool to empty."""
    params = _make_params()
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, VOCAB, (l,)).astype(np.int32)
               for l in (9, 9, 12, 5, 7)]
    for reuse in (False, True):
        _obs.get_registry().clear(prefix="serving.")
        eng = ServingEngine(params, NL, NH, DM, max_len=T, max_slots=2,
                            decode_chunk=4, min_bucket=4, block_tokens=4,
                            prefix_reuse=reuse)
        eng.generate_many(prompts, max_new_tokens=6)
        # the gauge tracks the pool at every engine release point
        st = eng.stats()
        assert st["serving.blocks_in_use"] == eng.kv_pool.blocks_in_use
        if reuse:
            assert eng.kv_pool.blocks_in_use == len(eng.prefix_trie)
            eng.prefix_trie.clear()
        assert eng.kv_pool.blocks_in_use == 0
        assert eng.kv_pool.free_blocks == eng.kv_pool.num_blocks - 1


def test_engine_trie_respects_cache_budget():
    """cache_blocks is a hard budget on trie-only blocks: heavy
    distinct-prefix traffic cannot grow the cache past it (LRU chains
    evict instead)."""
    params = _make_params()
    eng = ServingEngine(params, NL, NH, DM, max_len=T, max_slots=2,
                        decode_chunk=4, min_bucket=4, block_tokens=4,
                        cache_blocks=3, prefix_reuse=True)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, VOCAB, (12,)).astype(np.int32)
               for _ in range(6)]
    eng.generate_many(prompts, max_new_tokens=4)
    assert eng.prefix_trie._trie_only_count() <= 3
    assert eng.kv_pool.blocks_in_use == len(eng.prefix_trie)


def test_engine_prefix_hit_reduces_prefill_tokens():
    """The compute claim behind reuse: identical prompts the second
    time around scan strictly fewer prefill tokens, bit-exactness
    already covered above."""
    params = _make_params()
    rng = np.random.default_rng(10)
    base = rng.integers(1, VOCAB, (12,)).astype(np.int32)

    def served_prefill_tokens(reuse):
        _obs.get_registry().clear(prefix="serving.")
        eng = ServingEngine(params, NL, NH, DM, max_len=T, max_slots=2,
                            decode_chunk=4, min_bucket=4, block_tokens=4,
                            prefix_reuse=reuse)
        eng.generate_many([base.copy()], max_new_tokens=4)
        eng.generate_many([base.copy(), base.copy()], max_new_tokens=4)
        return eng.stats()["serving.prefill_tokens"]

    assert served_prefill_tokens(True) < served_prefill_tokens(False)


# -- engine-level: a pool the deployment states ------------------------------

def test_a_stated_pool_makes_an_admission_wait_at_the_front_and_finish():
    """``pool_blocks`` under what three slots' chains ask for: the
    request whose chain finds no room is put back at the FRONT of the
    queue (``PoolExhausted``), is admitted when a slot's blocks come
    back, and every request ends with the tokens the engine's own rule
    (every slot's worst case beside the cache) gives."""
    from paddle_tpu.serving.kvcache import PoolExhausted

    params = _make_params()
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, VOCAB, (14,)).astype(np.int32)
               for _ in range(5)]
    kw = dict(max_len=T, max_slots=3, decode_chunk=4, min_bucket=4,
              block_tokens=4, cache_blocks=4)
    roomy = ServingEngine(params, NL, NH, DM, **kw)
    want = roomy.generate_many(prompts, max_new_tokens=14)
    # a chain of 28 positions is 7 blocks: 12 hold one and most of a second
    eng = ServingEngine(params, NL, NH, DM, pool_blocks=12, **kw)
    assert eng.kv_pool.num_blocks == 13 < roomy.kv_pool.num_blocks == 29
    waited, admitted = [], []
    prefill_into = eng._prefill_into

    def spy(slot, req):
        try:
            out = prefill_into(slot, req)
        except PoolExhausted:
            waited.append((req, eng.active_slots))
            raise
        admitted.append(req)
        return out

    eng._prefill_into = spy
    got = eng.generate_many(prompts, max_new_tokens=14)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    # it waited while another slot was live, never with none (that is
    # fatal), and kept its place: admissions are in the order submitted
    assert waited and all(live >= 1 for _, live in waited)
    assert len(admitted) == len(prompts)
    for req, prompt in zip(admitted, prompts):
        np.testing.assert_array_equal(req.prompt, prompt)
    assert eng.kv_pool.blocks_in_use == len(eng.prefix_trie) <= 12


@pytest.mark.parametrize("how, more", [
    ("under one whole chain", dict(pool_blocks=7)),
    ("no less than the engine's own rule", dict(pool_blocks=28)),
    ("beside a draft's scratch chains", dict(pool_blocks=12, draft=True)),
])
def test_a_stated_pool_is_refused(how, more):
    """8 blocks a chain, 3 slots and a cache budget of 4: the rule's pool
    is 28 blocks beside the trash block."""
    from paddle_tpu.serving import speculative

    params = _make_params()
    kw = dict(max_len=T, max_slots=3, decode_chunk=4, min_bucket=4,
              block_tokens=4, cache_blocks=4,
              pool_blocks=more["pool_blocks"])
    if more.get("draft"):
        assert speculative.spec_enabled()
        kw.update(draft_params=params, draft_n_layer=NL, draft_n_head=NH)
    with pytest.raises(ValueError, match="a stated pool holds"):
        ServingEngine(params, NL, NH, DM, **kw)
    ServingEngine(params, NL, NH, DM, **dict(
        kw, pool_blocks=8, draft_params=None))
