"""Speculative decoding on the paged serving engine
(paddle_tpu/serving/speculative.py) — token-exact parity vs plain
greedy decode, geometry validation at construction, kill switch,
zero scratch-block leak, mid-verify slot death, and the tuned
``op=spec_decode`` draft window.  All on the CPU mesh (conftest),
tiny model shapes."""

import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import transformer
from paddle_tpu.observability import metrics as _obs
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import speculative as spec


def _make_params(vocab=50, n_layer=2, n_head=2, d_model=32, max_len=48,
                 dtype="float32", seed=7):
    """Randomly initialized flagship weights (greedy chains over random
    weights are deterministic — spec parity doesn't need training)."""
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = seed
    with pt.program_guard(main, startup):
        transformer.build(vocab_size=vocab, n_layer=n_layer,
                          n_head=n_head, d_model=d_model, max_len=max_len,
                          dropout_rate=0.0, dtype=dtype)
    exe = pt.Executor()
    exe.run(startup)
    return transformer.extract_params(program=main)


VOCAB, NL, NH, DM, T = 50, 2, 2, 32, 48


@pytest.fixture
def params():
    return _make_params(VOCAB, NL, NH, DM, T)


@pytest.fixture(autouse=True)
def fresh_serving_metrics():
    _obs.get_registry().clear(prefix="serving.")
    yield


def _engine(params, **kw):
    kw.setdefault("max_len", T)
    kw.setdefault("max_slots", 4)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("min_bucket", 4)
    return ServingEngine(params, NL, NH, DM, **kw)


def _refs(params, prompts, max_new):
    outs = []
    for p in prompts:
        toks, _ = transformer.generate(params, np.asarray(p)[None],
                                       max_len=T, n_layer=NL, n_head=NH,
                                       d_model=DM, return_logits=False)
        outs.append(np.asarray(toks)[0][: len(p) + max_new])
    return outs


def _prompts(rng, n, lens=(3, 7, 5, 9, 4, 11)):
    return [rng.integers(1, VOCAB, (lens[i % len(lens)],)).astype(np.int32)
            for i in range(n)]


# -- token-exact parity ------------------------------------------------------

@pytest.mark.parametrize("reuse", [True, False])
def test_spec_parity_token_exact_f32(params, reuse):
    """The acceptance bar: a speculative engine (depth-pruned draft)
    emits EXACTLY the tokens of plain greedy decode — mixed lengths,
    slot reuse, block-boundary crossings and all — and actually ran
    speculative rounds (proposed > 0)."""
    rng = np.random.default_rng(11)
    prompts = _prompts(rng, 6)
    eng = _engine(params, prefix_reuse=reuse,
                  draft_params=spec.depth_draft(params, 1), spec_k=3)
    assert eng._spec is not None and eng.spec_k == 3
    outs = eng.generate_many(prompts, max_new_tokens=10)
    for o, ref in zip(outs, _refs(params, prompts, 10)):
        np.testing.assert_array_equal(o, ref)
    assert eng._spec.proposed > 0
    # propose/verify/accept actually happened and is observable
    st = eng.stats()
    assert st["serving.spec_compiles"] >= 2  # draft chunk + verify
    assert 0.0 <= st["serving.spec_accept_rate"] <= 1.0


def test_spec_parity_bf16_bit_exact(params):
    """bf16 weights: speculative output is bit-identical to the plain
    bf16 engine (parity is exactness of the SCHEDULE, not a numeric
    tolerance — both paths run the same bf16 kernels)."""
    import jax.numpy as jnp

    p16 = {k: (jnp.asarray(v, jnp.bfloat16)
               if (k.startswith("block") or k.startswith("lm_head"))
               and k.endswith(".w") else v)
           for k, v in params.items()}
    rng = np.random.default_rng(12)
    prompts = _prompts(rng, 4)
    plain = _engine(p16).generate_many(prompts, max_new_tokens=8)
    _obs.get_registry().clear(prefix="serving.")
    eng = _engine(p16, draft_params=spec.depth_draft(p16, 1), spec_k=3)
    for o, ref in zip(eng.generate_many(prompts, max_new_tokens=8), plain):
        np.testing.assert_array_equal(o, ref)


def test_adversarial_draft_stays_exact(params):
    """A draft with UNRELATED weights (different init seed): acceptance
    collapses but every committed token is still exact — the guarantee
    is unconditional on draft quality, rejection just costs rollback."""
    adv = _make_params(VOCAB, NL, NH, DM, T, seed=1234)
    rng = np.random.default_rng(13)
    prompts = _prompts(rng, 5)
    # small blocks so rejected proposals cross block boundaries and the
    # rollback path (not just pointer rewind inside one block) runs
    eng = _engine(params, block_tokens=4,
                  draft_params=spec.depth_draft(adv, 1), spec_k=4)
    outs = eng.generate_many(prompts, max_new_tokens=12)
    for o, ref in zip(outs, _refs(params, prompts, 12)):
        np.testing.assert_array_equal(o, ref)
    sp = eng._spec
    assert sp.proposed > 0
    assert sp.accepted / sp.proposed < 0.5  # the draft really is bad
    assert eng.stats()["serving.spec_rollback_blocks"] > 0


def test_self_draft_accepts_nearly_every_proposal(params):
    """The draft IS the target: every proposal the parallel verify
    window judges should be the token the sequential step would have
    made, so any numeric drift between the two shows up here as
    spurious rejections (measured 1.000; the bar leaves room for a
    near-tie)."""
    rng = np.random.default_rng(16)
    prompts = _prompts(rng, 6)
    eng = _engine(params, prefix_reuse=False, draft_params=params,
                  spec_k=4)
    outs = eng.generate_many(prompts, max_new_tokens=12)
    for o, ref in zip(outs, _refs(params, prompts, 12)):
        np.testing.assert_array_equal(o, ref)
    sp = eng._spec
    assert sp.proposed > 0
    assert sp.accepted / sp.proposed >= 0.8


# -- construction-time geometry validation -----------------------------------

def test_geometry_mismatches_rejected(params):
    """Every draft/target geometry mismatch fails LOUDLY at engine
    construction with a message naming the mismatch — never as garbage
    tokens at serve time."""
    other_vocab = _make_params(vocab=60)
    with pytest.raises(ValueError, match="vocab mismatch"):
        _engine(params, draft_params=other_vocab)

    other_width = _make_params(d_model=64, n_head=2)
    with pytest.raises(ValueError, match="d_model"):
        _engine(params, draft_params=other_width)

    # differing head count (even at equal d_model) changes the pool
    # block shape the draft would write into
    with pytest.raises(ValueError, match="n_head"):
        _engine(params, draft_params=spec.depth_draft(params, 1),
                draft_n_head=1)

    # depth bounds: zero layers, more layers than the dict carries,
    # deeper than the target (the draft rides the FIRST pool arrays)
    draft = spec.depth_draft(params, 1)
    with pytest.raises(ValueError, match="outside"):
        _engine(params, draft_params=draft, draft_n_layer=0)
    with pytest.raises(ValueError, match="outside"):
        _engine(params, draft_params=draft, draft_n_layer=2)
    deep = _make_params(n_layer=3, max_len=T)
    with pytest.raises(ValueError, match="cannot be deeper"):
        _engine(params, draft_params=deep)

    # a draft whose position table is shorter than max_len would index
    # out of bounds mid-serve
    short = _make_params(max_len=16)
    with pytest.raises(ValueError, match="position-embedding"):
        _engine(params, draft_params=short)

    with pytest.raises(ValueError, match="spec_k"):
        _engine(params, draft_params=draft, spec_k=0)


def test_depth_draft_helper_bounds(params):
    assert spec.draft_depth(params) == NL
    assert spec.draft_depth(spec.depth_draft(params, 1)) == 1
    with pytest.raises(ValueError, match="outside"):
        spec.depth_draft(params, 0)
    with pytest.raises(ValueError, match="outside"):
        spec.depth_draft(params, NL + 1)


# -- kill switch -------------------------------------------------------------

def test_kill_switch_is_bit_exact_plain_engine(params):
    """PADDLE_TPU_SPEC=0: draft_params is ignored wholesale — no spec
    state, no spec metrics, and output bit-identical to an engine built
    with no draft at all."""
    rng = np.random.default_rng(14)
    prompts = _prompts(rng, 4)
    plain = _engine(params).generate_many(prompts, max_new_tokens=8)
    os.environ["PADDLE_TPU_SPEC"] = "0"
    try:
        _obs.get_registry().clear(prefix="serving.")
        eng = _engine(params, draft_params=spec.depth_draft(params, 1),
                      spec_k=3)
        assert eng._spec is None and eng.spec_k is None
        outs = eng.generate_many(prompts, max_new_tokens=8)
    finally:
        os.environ.pop("PADDLE_TPU_SPEC", None)
    for o, ref in zip(outs, plain):
        np.testing.assert_array_equal(o, ref)
    assert not any(k.startswith("serving.spec_") for k in eng.stats())


# -- zero-leak discipline ----------------------------------------------------

@pytest.mark.parametrize("reuse", [True, False])
def test_scratch_blocks_never_leak(params, reuse):
    """After run_until_idle every scratch chain is released: pool
    accounting matches a plain engine's endpoint (cached prefix chains
    only with reuse on; zero without), scratch table zeroed."""
    rng = np.random.default_rng(15)
    prompts = _prompts(rng, 6)
    plain = _engine(params, prefix_reuse=reuse)
    plain.generate_many(prompts, max_new_tokens=8)
    base_in_use = plain.kv_pool.blocks_in_use

    _obs.get_registry().clear(prefix="serving.")
    eng = _engine(params, prefix_reuse=reuse,
                  draft_params=spec.depth_draft(params, 1), spec_k=3)
    eng.generate_many(prompts, max_new_tokens=8)
    sp = eng._spec
    assert eng.kv_pool.blocks_in_use == base_in_use
    if not reuse:
        assert eng.kv_pool.blocks_in_use == 0
    assert all(not (c or ()) for c in sp.chains)
    assert not np.count_nonzero(sp.table)
    assert (eng._table == 0).all()


# -- fault injection: slot death mid-verify ----------------------------------

def test_slot_death_mid_verify_reclaims_scratch_and_real_chains(params):
    """PADDLE_TPU_FAULT=slot_death:n fires at the decode point — in
    speculative mode that is MID-VERIFY, with the victim holding both a
    real chain and a draft scratch chain.  Both are reclaimed (pool
    back to baseline, both tables zeroed), survivors stay token-exact,
    and the driver keeps serving."""
    from paddle_tpu.resilience import faults

    eng = _engine(params, max_slots=3, prefix_reuse=False,
                  block_tokens=4,
                  draft_params=spec.depth_draft(params, 1), spec_k=3)
    rng = np.random.default_rng(16)
    baseline_in_use = eng.kv_pool.blocks_in_use
    os.environ["PADDLE_TPU_FAULT"] = "slot_death:2"
    faults.reset()
    eng.start()
    try:
        reqs = [eng.submit(rng.integers(1, VOCAB, (5,)),
                           max_new_tokens=10) for _ in range(6)]
        for r in reqs:
            assert r.wait(timeout=120), "request did not finish"
    finally:
        eng.stop()
        os.environ.pop("PADDLE_TPU_FAULT", None)
        faults.reset()
    dead = [r for r in reqs if r.error is not None]
    ok = [r for r in reqs if r.error is None]
    assert len(dead) == 1 and len(ok) == 5
    for r in ok:
        ref, _ = transformer.generate(params, r.prompt[None], max_len=T,
                                      n_layer=NL, n_head=NH, d_model=DM,
                                      return_logits=False)
        np.testing.assert_array_equal(
            r.result(timeout=0),
            np.asarray(ref)[0][: len(r.prompt) + 10])
    # neither the real chains nor the draft scratch chains leak
    assert eng.kv_pool.blocks_in_use == baseline_in_use == 0
    assert (eng._table == 0).all()
    assert not np.count_nonzero(eng._spec.table)
    assert all(not (c or ()) for c in eng._spec.chains)
    st = eng.stats()
    assert st["serving.slot_deaths"] == 1
    assert st["serving.completed"] == 5
    assert eng.idle
