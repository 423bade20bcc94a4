"""The looped RMSNorm / rotary / gated-SiLU stack (``serving/arch.py``,
``LoopedRmsRope``: the Ouro layout) on the serving path, against its
plain reference (``models/ouro_reference.py``): logits of
prefill-then-decode through the paged cache at every generated position,
whatever the pieces, the shared prefix or the copy-on-write fork; the
comparison bites for each thing the loop forces (a pass fewer, a plane
shared by the passes, no norm between passes, the other rotary pairing,
a lower matmul precision); the lowered programs do not grow with the
number of passes; the pool is sized in bytes from the planes.

Tiny sizes (d 64, 4 heads of 16, f 96, 2 layers, 3 passes, 128 rows),
seeded random weights with non-unit norm scales, on the CPU."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.transformer import infer_compute_dtype
from paddle_tpu.observability import trace
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.serving import ServingEngine, speculative
from paddle_tpu.serving import batched_decode as _bd
from paddle_tpu.serving.arch import Gpt2, LoopedRmsRope
from tiny import ouro as fam

VOCAB, NL, NH, DM, PASSES = (fam.sizes[k] for k in (
    "rows", "layers", "heads", "d", "passes"))
T, B = fam.max_len, fam.block_tokens
PIECE = fam.piece   # the piece width these tests give the engine
# float32 engine against the float32 reference: the two differ in
# reduction order (the cache attends block by block with an online
# softmax, a window's matmuls reduce in another shape) and in the CPU
# backend's default f32 dot, through 6 layer applications of 4 norms
# each; the worst gap seen is 1.5e-5 on logits of deviation 1.6, so 1e-4
# leaves room and is 40,000 times under what the least of the faults
# below moves (4.7)
TOL = 1e-4
# bfloat16 engine: every matmul output, every K/V row and the residual
# stream round to 8 bits of mantissa through those 6 applications; worst
# gap seen 0.33 (mean 0.05) on logits of deviation 1.6; twice that, and
# still 6 times under the least fault
BF16_MARGIN = 0.7


def _row(first, n=T // B):
    """A table row of ``n`` private blocks starting at block ``first``."""
    return jnp.asarray(np.arange(first, first + n), jnp.int32)


def _jitted(eng):
    """The window forward (with the head on one row) and the decode step
    of ``eng``'s architecture, jitted once an engine."""
    if not hasattr(eng, "_test_fns"):
        arch = eng.arch

        def window(p, pk, pv, toks, at, n, row):
            x, pk, pv, *_ = _bd._window_forward(
                p, pk, pv, toks[None], at[None], (at + n - 1)[None],
                row[None], arch)
            return arch.head(p, x[0])[n - 1], pk, pv

        def step(p, pk, pv, tok, at, row):
            lg, pk, pv, *_ = _bd.paged_step_logits(
                p, tok[None], at[None], pk, pv, row[None], arch)
            return lg[0], pk, pv

        eng._test_fns = jax.jit(window), jax.jit(step)
    return eng._test_fns


def _prefill(eng, pk, pv, row, toks, start):
    """The pieces the engine would dispatch for ``toks`` at ``start``,
    through the one window forward; the logits after the last token."""
    logits = None
    for _w, padded, at, n in eng._pieces(np.asarray(toks), start):
        logits, pk, pv = _jitted(eng)[0](eng._p, pk, pv, padded,
                                         jnp.int32(at), jnp.int32(n), row)
    return logits, pk, pv


def _step(eng, pk, pv, row, tok, at):
    return _jitted(eng)[1](eng._p, pk, pv, jnp.int32(tok), jnp.int32(at),
                           row)


def _decode(eng, pk, pv, row, tok, at, n):
    """``n`` greedy steps through the cache from token ``tok`` at
    position ``at``: (logits [n, V], tokens [n])."""
    out, toks = [], []
    for j in range(n):
        lg, pk, pv = _step(eng, pk, pv, row, tok, at + j)
        out.append(lg)
        tok = int(jnp.argmax(lg))
        toks.append(tok)
    return jnp.stack(out), toks


def _served_logits(eng, prompt, n_new, row=None, start=0, pools=None):
    """Prefill ``prompt[start:]`` then decode ``n_new`` tokens; logits at
    every generated position ``[n_new, V]`` and the tokens."""
    row = _row(1) if row is None else row
    pk, pv = pools or (eng._pk, eng._pv)
    first, pk, pv = _prefill(eng, pk, pv, row, prompt[start:], start)
    tok = int(jnp.argmax(first))
    rest, toks = _decode(eng, pk, pv, row, tok, len(prompt), n_new - 1)
    return (np.asarray(jnp.concatenate([first[None], rest])),
            [tok] + toks, (pk, pv))


PROMPT = np.random.default_rng(5).integers(1, VOCAB, 19).astype(np.int32)
N_NEW = 9


@pytest.fixture(scope="module")
def served():
    """The float32 engine's logits for PROMPT (19 tokens: pieces of 8, 8
    and a bucket of 4) and N_NEW greedy tokens, and the reference's."""
    mp = pytest.MonkeyPatch()
    try:
        params = fam.init()
        eng, _ = fam.engine(params, mp)
        got, toks, _ = _served_logits(eng, PROMPT, N_NEW)
        full = np.concatenate([PROMPT, toks])
        return params, got, toks, full
    finally:
        mp.undo()


def _want(params, full, **kw):
    return fam.reference(params, full, **kw)[len(PROMPT) - 1:len(full) - 1]


def test_float32_logits_equal_the_reference_at_every_position(served):
    params, got, toks, full = served
    want = _want(params, full)
    assert got.shape == want.shape == (N_NEW, VOCAB)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert toks == list(want.argmax(-1))


def test_engine_tokens_equal_the_reference_greedy_chain(monkeypatch, served):
    """Tokens through ``generate_many`` (the driver loop, the trie, the
    compiled executables with the pool folded by pass)."""
    params, _got, toks, _full = served
    eng, reg = fam.engine(params, monkeypatch)
    out = eng.generate_many([PROMPT], max_new_tokens=N_NEW)[0]
    assert list(out[len(PROMPT):]) == toks
    assert reg.value("serving.prefill_pieces", width=PIECE) == 2


def test_bfloat16_engine_within_its_margin_and_outside_float32s(
        monkeypatch, served):
    """A lower matmul precision than the configuration states (float32
    here) fails the float32 tolerance; the bf16 engine has its own."""
    params, _got, _toks, full = served
    p16 = {k: jnp.asarray(v, jnp.bfloat16) for k, v in params.items()}
    eng, _ = fam.engine(p16, monkeypatch)
    assert eng.compute_dtype == jnp.bfloat16
    # teacher-forced on the float32 chain, so that positions compare
    first, pk, pv = _prefill(eng, eng._pk, eng._pv, _row(1), PROMPT, 0)
    rows = [first]
    for j, tok in enumerate(full[len(PROMPT):-1]):
        lg, pk, pv = _step(eng, pk, pv, _row(1), tok, len(PROMPT) + j)
        rows.append(lg)
    gap = np.abs(np.asarray(jnp.stack(rows)) - _want(p16, full)).max()
    assert TOL < gap < BF16_MARGIN, gap


@pytest.mark.parametrize("fault", [
    {"passes": PASSES - 1}, {"norm_between_passes": False},
    {"interleaved": True}], ids=["a_pass_fewer", "no_norm_between_passes",
                                 "rotate_interleaved"])
def test_the_comparison_bites_on_the_reference_side(served, fault):
    params, got, _toks, full = served
    gap = np.abs(got - _want(params, full, **fault)).max()
    assert gap > 100 * TOL, gap


class _SharedPlane(LoopedRmsRope):
    """The fault a cache can make: every pass writes and reads pass 0's
    plane."""

    def one_pass(self, p, i_pass, x, rope, planes, attend):
        return super().one_pass(p, 0 * i_pass, x, rope, planes, attend)


def test_the_comparison_bites_on_a_plane_shared_by_the_passes(
        monkeypatch, served):
    params, _got, _toks, full = served
    eng, _ = fam.engine(params, monkeypatch, arch=fam.arch(cls=_SharedPlane))
    got, _t, _ = _served_logits(eng, PROMPT, N_NEW)
    # the first logits come from one window per piece, whose later
    # pieces already attend the wrong pass's rows
    assert np.abs(got - _want(params, full)).max() > 100 * TOL


def test_shared_prefix_and_copy_on_write_give_the_unshared_logits(
        monkeypatch, served):
    """Request B shares PROMPT's first 8 tokens as two full blocks;
    request C its first 6, forking the second block copy-on-write in
    every plane of every pass: same logits as prefilling alone."""
    params, _got, _toks, _full = served
    eng, _ = fam.engine(params, monkeypatch)
    rng = np.random.default_rng(6)
    _first, pk, pv = _prefill(eng, eng._pk, eng._pv, _row(1), PROMPT, 0)
    for shared, fork in ((8, None), (6, (2, 60))):
        prompt = np.concatenate(
            [PROMPT[:shared], rng.integers(1, VOCAB, 7).astype(np.int32)])
        alone, toks, _ = _served_logits(eng, prompt, 4, row=_row(33))
        row = np.arange(17, 17 + T // B)
        row[:shared // B] = np.arange(1, 1 + shared // B)
        pools = (pk, pv)
        if fork:
            src, dst = fork
            row[shared // B] = dst
            pools = tuple(_bd._copy_block(c, src, dst, PASSES)
                          for c in pools)
        got, toks2, _ = _served_logits(eng, prompt, 4,
                                       row=jnp.asarray(row, jnp.int32),
                                       start=shared, pools=pools)
        np.testing.assert_allclose(got, alone, atol=TOL, rtol=0)
        assert toks2 == toks
        want = fam.reference(params, np.concatenate([prompt, toks]))
        np.testing.assert_allclose(
            got, want[len(prompt) - 1:len(prompt) + 3], atol=TOL, rtol=0)


def test_engine_serves_prefix_traffic_token_identical(monkeypatch, served):
    """Two waves through the driver: a cold prompt, the same again (full
    blocks hit), a fork inside a cached block, an unrelated one."""
    params, *_ = served
    eng, reg = fam.engine(params, monkeypatch)
    rng = np.random.default_rng(7)
    prompts = [PROMPT, PROMPT.copy(),
               np.concatenate([PROMPT[:6],
                               rng.integers(1, VOCAB, 5).astype(np.int32)]),
               rng.integers(1, VOCAB, 9).astype(np.int32)]
    outs = eng.generate_many(prompts[:1], max_new_tokens=6)
    outs += eng.generate_many(prompts[1:], max_new_tokens=6)
    for p, o in zip(prompts, outs):
        # the reference's greedy chain, read off ONE forward over what
        # the engine wrote: by causality token j + 1 of a chain that
        # agrees so far is the argmax at position j
        assert list(o[:len(p)]) == list(p) and len(o) == len(p) + 6
        want = fam.reference(params, o)[len(p) - 1:len(o) - 1].argmax(-1)
        assert list(o[len(p):]) == list(want)
    st = eng.stats()
    assert st["serving.prefix_hit_rate"] > 0
    assert st["serving.cow_copies"] >= 1
    assert st["serving.blocks_in_use"] <= eng.cache_blocks


class _Unlooped(LoopedRmsRope):
    """The same stack with the passes unrolled in Python."""

    def stack(self, p, x, pos, planes, attend):
        rope = self._angles(pos)
        for i in range(self.passes):
            x, planes = self.one_pass(p, i, x, rope, planes, attend)
        return x, planes


@pytest.mark.parametrize("passes", [1, PASSES])
def test_the_looped_forward_equals_the_same_stack_unlooped(
        monkeypatch, passes):
    params = fam.init()
    out = []
    for cls in (LoopedRmsRope, _Unlooped):
        eng, _ = fam.engine(params, monkeypatch,
                            arch=fam.arch(passes=passes, cls=cls))
        out.append(_served_logits(eng, PROMPT, 3)[0])
    np.testing.assert_allclose(out[0], out[1], atol=TOL, rtol=0)


def _lowered_counts(arch, params):
    """dot and while instructions of the lowered decode chunk and of one
    prefill width (off the TPU the paged attention is the xla_ref scan:
    its dots and its loop stand for the kernel's calls)."""
    eng = ServingEngine(params, arch=arch, max_len=T, max_slots=2,
                        block_tokens=B, decode_chunk=2, min_bucket=4,
                        donate=False, registry=MetricsRegistry())
    tbl = jnp.asarray(eng._table)
    decode = _bd.make_decode_chunk(arch, chunk=2, donate=False).lower(
        eng._p, eng._pk, eng._pv, eng._last, eng._pos, tbl).as_text()
    z = np.int32(0)
    prefill = _bd.make_prefill(arch, bucket=8, donate=False).lower(
        eng._p, eng._pk, eng._pv, eng._last, eng._pos, z, tbl[0],
        jnp.zeros(8, jnp.int32), z, np.int32(8), z, z).as_text()
    return [(len(re.findall(r"stablehlo\.dot_general", t)),
             len(re.findall(r"stablehlo\.while", t)))
            for t in (decode, prefill)]


def test_the_lowered_programs_do_not_grow_with_the_passes():
    params = fam.init()
    one = _lowered_counts(fam.arch(passes=1), params)
    four = _lowered_counts(fam.arch(passes=4), params)
    assert one == four
    dots, loops = one[0]
    # seven projections a layer and the head, besides the attention's
    assert dots >= 7 * NL + 1 and loops >= 2
    # and a stack unrolled in Python does grow: the count means something
    assert _lowered_counts(fam.arch(passes=4, cls=_Unlooped),
                           params)[0][0] > dots


def test_pool_is_sized_in_bytes_from_the_planes(monkeypatch):
    params = fam.init(dtype=jnp.bfloat16)
    eng, reg = fam.engine(params, monkeypatch, max_slots=2, cache_blocks=5)
    blocks = 1 + 2 * (T // B) + 5
    assert eng.kv_pool.num_blocks == blocks
    assert reg.value("serving.kv_blocks_total") == blocks - 1
    assert reg.value("serving.kv_planes") == NL * PASSES
    assert reg.value("serving.stack_passes") == PASSES
    per_token = 2 * NL * PASSES * DM * 2
    assert reg.value("serving.kv_bytes_per_token") == per_token
    want = NL * PASSES * blocks * (2 * B * DM * 2)
    assert reg.value("serving.kv_pool_bytes") == want == blocks * B * per_token
    assert sum(a.nbytes for a in eng._pk + eng._pv) == want
    assert eng._pk[0].shape == (PASSES * blocks, B, NH, DM // NH)


def test_spans_carry_the_passes(monkeypatch, served):
    params, *_ = served
    eng, _ = fam.engine(params, monkeypatch)
    tracer = trace.Tracer(enabled=True)
    monkeypatch.setattr(trace, "_TRACER", tracer, raising=False)
    trace.set_tracer(tracer)
    try:
        eng.generate_many([PROMPT[:5]], max_new_tokens=6)
    finally:
        trace.set_tracer(None)
    seen = {e["name"]: e.get("args", {}) for e in tracer.events()
            if e["name"] in ("serving.prefill", "serving.decode_chunk")}
    assert seen["serving.prefill"]["passes"] == PASSES
    assert seen["serving.decode_chunk"]["passes"] == PASSES


def test_what_is_refused_says_what_is_missing():
    with pytest.raises(ValueError, match="leave the stack at different "
                                         "passes"):
        LoopedRmsRope(NL, NH, DM, PASSES, early_exit_threshold=0.9)
    params = fam.init()
    with pytest.raises(ValueError, match="exit_gate.w"):
        ServingEngine({k: v for k, v in params.items()
                       if not k.startswith("exit_gate")}, arch=fam.arch(),
                      max_len=T, block_tokens=B)
    with pytest.raises(ValueError, match="GPT-2 block only.*3 passes"):
        speculative.validate_draft(params, params, fam.arch(), T)
    with pytest.raises(ValueError, match="GPT-2 block only"):
        ServingEngine(params, arch=fam.arch(), max_len=T, block_tokens=B,
                      draft_params=params)
    with pytest.raises(ValueError, match="not both"):
        ServingEngine(params, NL, NH, DM, arch=fam.arch())
    with pytest.raises(ValueError, match="needs an architecture"):
        ServingEngine(params)


def test_infer_compute_dtype_answers_for_the_new_names():
    p16 = fam.init(dtype=jnp.bfloat16)
    # float32 norm scales, gate and embedding must not promote the decode
    mixed = {k: (v if k.endswith(".w") and (k.startswith("block")
                                            or k.startswith("lm_head"))
                 else jnp.asarray(v, jnp.float32)) for k, v in p16.items()}
    assert infer_compute_dtype(mixed) == jnp.bfloat16
    assert infer_compute_dtype(fam.init()) == jnp.float32
    assert isinstance(Gpt2(2, 2, 32), Gpt2) and Gpt2(2, 2, 32).kv_planes == 2
