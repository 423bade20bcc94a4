"""The ladder of prefill window widths (``serving/batched_decode.py``:
``PREFILL_PIECE``, ``RUNG_STEP``, ``prefill_rungs``, ``piece_widths``)
and a WIDE piece against narrow ones.

The ladder: every suffix length is covered by widths off the rungs, the
padded sum is monotone in the length, and no serving mix of
``chipbench/traffic`` can reach more distinct widths (executables) than
it could on the ladder of doublings up to 128 that PR 43 replaced.  A
wide piece: for each of the six architectures one prompt of 500 tokens
through ONE 512-row window and through four 128-row windows leaves the
same K/V (or latent) rows, the same per-slot state and the same
first-token logits (float32 at 1e-4 of the largest magnitude; bfloat16
inside the margin its family's own tests use), and the two engines then
decode the same greedy tokens.  Power retention inside a wide piece is
the consecutive ``chunk`` calls to the bit, a ``limit`` inside the
second included.  And a piece that straddles a copy-on-write fork, and a
trie hit that leaves a suffix between two rungs."""

import functools
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from paddle_tpu.kernels import retention as _retention
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import batched_decode as _bd

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench", "traffic")


# -- the ladder ---------------------------------------------------------------

def test_the_piece_is_past_the_ridge_and_the_rungs_four_apart_under_it():
    assert _bd.PREFILL_PIECE == 512 and _bd.RUNG_STEP == 4
    assert _bd.STREAM_ROWS == 128
    assert _bd.prefill_rungs(8, 2048) == [8, 32, 128, 256, 512]
    assert _bd.prefill_rungs(8, 9216) == [8, 32, 128, 256, 512]
    # no rung is wider than a slot, and none is there twice
    assert _bd.prefill_rungs(8, 400) == [8, 32, 128, 256, 400]
    assert _bd.prefill_rungs(8, 96) == [8, 32, 96]
    assert _bd.prefill_rungs(16, 512) == [16, 64, 128, 256, 512]
    assert _bd.prefill_rungs(4, 48) == [4, 16, 48]
    # a bucket wider than the piece is the one rung
    assert _bd.prefill_rungs(1024, 4096) == [1024]


@pytest.mark.parametrize("min_bucket,max_len", [
    (8, 2048), (8, 768), (8, 400), (4, 48), (16, 512), (8, 1300)])
def test_every_length_is_covered_and_the_sum_is_monotone(min_bucket, max_len):
    rungs = _bd.prefill_rungs(min_bucket, max_len)
    last = 0
    for n in range(1, max_len + 1):
        widths = _bd.piece_widths(n, rungs)
        assert set(widths) <= set(rungs)
        # whole pieces of the widest rung, then ONE remainder that no
        # narrower rung covers
        assert all(w == rungs[-1] for w in widths[:-1])
        total = sum(widths)
        assert n <= total and total - widths[-1] < n
        assert not any(r < widths[-1] and total - widths[-1] + r >= n
                       for r in rungs)
        assert total >= last
        last = total
    assert _bd.piece_widths(0, rungs) == [rungs[0]]


def _doublings_to_128(n, min_bucket, max_len):
    """The ladder PR 43 replaced: whole pieces of 128, then the smallest
    power-of-two multiple of ``min_bucket`` that covers the rest."""
    full, rem = divmod(n, 128)
    widths = [128] * full
    if rem or not full:
        b = min_bucket
        while b < rem:
            b *= 2
        widths.append(min(b, 128, max_len))
    return widths


def _mixes():
    out = []
    for path in sorted(glob.glob(os.path.join(TRAFFIC, "*.json"))):
        with open(path) as f:
            mix = json.load(f)
        if mix["runner"] == "serve":
            out.append(pytest.param(mix, id=os.path.basename(path)[:-5]))
    return out


@pytest.mark.parametrize("mix", _mixes())
def test_no_mix_reaches_more_widths_than_on_the_ladder_it_replaced(mix):
    """An executable a width: what a cell compiles in set-up.  The
    suffixes a mix can send: every tail length between its bounds (a
    prefix hit ends on a block edge or forks one, and leaves the tail or
    a few tokens more), and in set-up each shared head with a tail of
    ``min_bucket`` behind it."""
    max_len = mix["engine"]["max_len"]
    min_bucket = mix["engine"].get("min_bucket", 8)
    tail, heads = mix["prompt_tail"], mix["shared_heads"]
    lengths = list(range(tail["min"], tail["max"] + 1))
    if heads["count"]:
        lengths.append(heads["tokens"] + min_bucket)
    rungs = _bd.prefill_rungs(min_bucket, max_len)
    new = {w for n in lengths for w in _bd.piece_widths(n, rungs)}
    old = {w for n in lengths
           for w in _doublings_to_128(n, min_bucket, max_len)}
    assert len(new) <= len(old), (sorted(new), sorted(old))
    assert new <= set(rungs)


# -- a wide piece against narrow ones, an architecture a case ------------------

N, T_WIDE, SLOT = 500, 640, 1


def _served(family, margin, **cut):
    """``(parameters, architecture, vocabulary rows, bfloat16 margin)``
    of ``family``'s tiny model: a routed family's share of float32
    weights cast, as its own tests cast them."""
    def case(dtype, monkeypatch):
        if "share" in family.sizes:
            p = family.held(family.init(**cut), (dtype,))[dtype]
        else:
            p = {k: jnp.asarray(v, dtype)
                 for k, v in family.init(dtype=dtype, **cut).items()}
        return p, family.arch(), family.rows, margin
    return case


# gpt2: the position table has to cover the longer slot; sambay: its
# window (8) is far inside the piece, which spans many; the margins are
# each family's own tests'
ARCHS = {"gpt2": _served(tiny.gpt2, 0.05, max_len=T_WIDE),
         "looped": _served(tiny.ouro, 0.7),
         "sambay": _served(tiny.sambay, 0.7),
         "gated_moe": _served(tiny.gated_moe, 0.25),
         "latent_moe": _served(tiny.latent_moe, 0.25),
         "retention": _served(tiny.retention, 0.1)}


def _engine(params, arch, piece, monkeypatch):
    monkeypatch.setattr(_bd, "PREFILL_PIECE", piece)
    kw = ({"prefix_reuse": False, "cache_blocks": 0} if not arch.planes
          else {"prefix_reuse": False, "block_tokens": 4})
    return ServingEngine(params, arch=arch, max_len=T_WIDE, max_slots=2,
                         decode_chunk=4, min_bucket=8, donate=False,
                         registry=MetricsRegistry(), **kw)


def _prefill(eng, prompt):
    """The engine's own pieces for ``prompt`` through the one window
    forward into slot ``SLOT``: (logits after the last token, the rows
    the prompt's positions hold in every pool array, the slot's state)."""
    arch = eng.arch
    nb = eng.blocks_per_slot
    row = jnp.asarray(1 + nb * SLOT + np.arange(nb), jnp.int32)

    @jax.jit
    def window(p, pk, pv, st, toks, at, n):
        x, pk, pv, st, _ = _bd._window_forward(
            p, pk, pv, toks[None], at[None], (at + n - 1)[None], row[None],
            arch, st, jnp.int32(SLOT))
        last = jax.lax.dynamic_slice_in_dim(x[0], n - 1, 1)
        return arch.head(p, last)[0], pk, pv, st

    pk, pv = eng._pk, eng._pv
    # whatever the slot's last request left must not show
    st = jax.tree.map(lambda a: a + 3.0, eng._state)
    for _w, padded, at, n in eng._pieces(prompt, 0):
        logits, pk, pv, st = window(eng._p, pk, pv, st, padded,
                                    jnp.int32(at), jnp.int32(n))
    at = np.arange(len(prompt))
    rows = []
    for a in pk + pv:
        a = np.asarray(a, np.float32)
        a = a.reshape((arch.passes, -1) + a.shape[1:])
        rows.append(a[:, np.asarray(row)[at // 4], at % 4])
    state = [np.asarray(a[SLOT], np.float32)
             for layer in st for a in layer]
    return np.asarray(logits, np.float32), rows, state


def _worst(got, want):
    """The largest error over the largest reference magnitude."""
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


@pytest.mark.parametrize("name", list(ARCHS))
def test_one_wide_piece_equals_four_narrow_ones(name, monkeypatch):
    params, arch, vocab, _ = ARCHS[name]("float32", monkeypatch)
    prompt = np.random.default_rng(43).integers(1, vocab, N, dtype=np.int32)
    wide = _engine(params, arch, 512, monkeypatch)
    narrow = _engine(params, arch, 128, monkeypatch)
    assert [w for w, *_ in wide._pieces(prompt, 0)] == [512]
    assert [w for w, *_ in narrow._pieces(prompt, 0)] == [128] * 4
    lw, rows_w, state_w = _prefill(wide, prompt)
    ln, rows_n, state_n = _prefill(narrow, prompt)
    assert len(rows_w) == len(arch.planes) * arch.pool_arrays
    assert len(state_w) == sum(len(layer) for layer in wide._state)
    for got, want in zip(rows_w + state_w + [lw], rows_n + state_n + [ln]):
        assert np.isfinite(got).all()
        assert _worst(got, want) <= 1e-4
    # and through the engines themselves: the same greedy tokens
    out_w = wide.generate_many([prompt], max_new_tokens=12)[0]
    out_n = narrow.generate_many([prompt], max_new_tokens=12)[0]
    assert np.array_equal(out_w, out_n)
    assert int(out_w[N]) == int(np.argmax(lw))
    assert sorted(wide._prefill_fns) == [512]
    assert sorted(narrow._prefill_fns) == [128]


@pytest.mark.parametrize("name", list(ARCHS))
def test_a_wide_piece_in_bfloat16_stays_within_the_familys_margin(
        name, monkeypatch):
    """In bfloat16 a window's matmuls round in another shape than four
    narrower windows': the first-token logits differ, by less than the
    margin the family's own tests give a bfloat16 engine against the
    float32 reference."""
    params, arch, vocab, margin = ARCHS[name]("bfloat16", monkeypatch)
    prompt = np.random.default_rng(44).integers(1, vocab, N, dtype=np.int32)
    lw, _, _ = _prefill(_engine(params, arch, 512, monkeypatch), prompt)
    ln, _, _ = _prefill(_engine(params, arch, 128, monkeypatch), prompt)
    assert np.isfinite(lw).all()
    # how far under the narrow pieces' maximum they rate the wide
    # piece's token, and the other way round
    gap = max(float(ln.max() - ln[np.argmax(lw)]),
              float(lw.max() - lw[np.argmax(ln)]))
    assert gap <= margin, gap


# -- power retention inside a wide piece ---------------------------------------

@pytest.mark.parametrize("real", [512, 200, 128, 3])
def test_retain_over_a_wide_piece_is_one_chunk_call_to_the_bit(real):
    """``_Cache.advance`` hands a 512-row window to ``kernels.retention.
    chunk`` in ONE call (the kernel walks the rows in tiles of
    ``CHUNK_ROWS`` itself) and equals that call to the bit; four threaded
    128-row calls, the form before PR 44, give the same within float32
    rounding; ``real`` rows are within the limit (200: it ends inside
    the second row tile) and the rows past it advance nothing."""
    W, h, kv, d, slots = 512, 4, 2, 16, 3
    assert _retention.CALL_ROWS == _bd.PREFILL_PIECE == W
    assert _retention.chunk_rows(W) == [512]
    assert _retention.chunk_rows(400) == [400]
    assert _retention.chunk_rows(32) == [32]
    assert _retention.chunk_rows(1200) == [512, 512, 176]
    rng = np.random.default_rng(real)
    f = lambda *s: jnp.asarray(rng.normal(0, 0.5, s), jnp.float32)  # noqa: E731
    q, k, v = f(1, W, h, d), f(1, W, kv, d), f(1, W, kv, d)
    lg = -jnp.abs(f(1, W, kv)) * 0.05
    R = _retention.stored_rows(d)
    S0, z0 = f(slots, kv, R, d), jnp.abs(f(slots, kv, R))
    valid = jnp.arange(W) < real

    @jax.jit
    def retain(S, z, start):
        cache = _bd._Cache(None, None, None, None,
                           start + jnp.arange(W)[None],
                           writable=valid[None], slot=jnp.int32(1))
        y, planes = cache.advance(((), (), ((S, z),)), 0, _retention, q, k, v, lg)
        return (y[0],) + planes[2][0]

    @functools.partial(jax.jit, static_argnames=("calls", "rows"))
    def by_hand(S, z, start, calls=1, rows=W):
        ys, fresh = [], start == 0
        for i in range(calls):
            cut = slice(rows * i, rows * (i + 1))
            y, S, z = _retention.chunk(
                S, z, jnp.int32(1), fresh, q[0, cut], k[0, cut], v[0, cut],
                lg[0, cut], valid[cut])
            ys.append(y)
            fresh = False
        return jnp.concatenate(ys), S, z

    close = functools.partial(np.testing.assert_allclose, rtol=1e-6,
                              atol=1e-6)
    for start in (0, 640):
        y, S, z = retain(S0, z0, jnp.int32(start))
        yh, Sh, zh = by_hand(S0, z0, jnp.int32(start))
        assert np.array_equal(np.asarray(S), np.asarray(Sh))
        assert np.array_equal(np.asarray(z), np.asarray(zh))
        assert np.array_equal(np.asarray(y)[:real], np.asarray(yh)[:real])
        # four threaded 128-row calls, as a window went before
        y4, S4, z4 = by_hand(S0, z0, jnp.int32(start), calls=4, rows=128)
        close(np.asarray(S), np.asarray(S4))
        close(np.asarray(z), np.asarray(z4))
        # a row's output is a quotient of two sums taken in another order
        np.testing.assert_allclose(np.asarray(y)[:real],
                                   np.asarray(y4)[:real], rtol=1e-5,
                                   atol=1e-5)
        # the other slots' state is as it was
        for s in (0, 2):
            assert np.array_equal(np.asarray(S[s]), np.asarray(S0[s]))
            assert np.array_equal(np.asarray(z[s]), np.asarray(z0[s]))
        if start:
            assert not np.array_equal(np.asarray(S[1]), np.asarray(
                retain(S0, z0, jnp.int32(0))[1][1]))
        if real <= 256:
            # the rows past the limit advance nothing
            _, S2, z2 = by_hand(S0, z0, jnp.int32(start), calls=2, rows=128)
            close(np.asarray(S), np.asarray(S2))
            close(np.asarray(z), np.asarray(z2))


# -- a fork and a hit under a wide piece ---------------------------------------

def test_a_piece_straddles_a_fork_and_a_hit_ends_between_rungs(monkeypatch):
    """With rungs 4, 16 and 32 over 4-token blocks: a request whose
    prompt shares 14 tokens with a cached one forks the fourth block and
    prefills 40 tokens from INSIDE it (one piece of 32 rows over nine
    blocks, then 16 rows of which 8 are real); one that shares 12 (a
    block edge) prefills 21 tokens in one piece of 32.  Both give the
    tokens an engine with no prefix cache gives."""
    gpt2 = tiny.gpt2
    params = gpt2.init(max_len=96)

    def engine(reuse):
        monkeypatch.setattr(_bd, "PREFILL_PIECE", 32)
        reg = MetricsRegistry()
        return ServingEngine(params, arch=gpt2.arch(), max_len=96, max_slots=2,
                             block_tokens=4, decode_chunk=4, min_bucket=4,
                             donate=False, registry=reg,
                             prefix_reuse=reuse), reg

    rng = np.random.default_rng(7)
    base = rng.integers(1, gpt2.rows, 40, dtype=np.int32)
    forked = np.concatenate([base[:14],
                             rng.integers(1, gpt2.rows, 40, dtype=np.int32)])
    forked[14] = (base[14] + 1) % gpt2.rows or 1      # diverge mid-block
    edge = np.concatenate([base[:12],
                           rng.integers(1, gpt2.rows, 21, dtype=np.int32)])
    edge[12] = (base[12] + 1) % gpt2.rows or 1
    prompts = [base, forked, edge]
    on, reg = engine(True)
    assert on._rungs == [4, 16, 32]
    got = [on.generate_many([p], max_new_tokens=6)[0] for p in prompts]
    off, _ = engine(False)
    want = [off.generate_many([p], max_new_tokens=6)[0] for p in prompts]
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    st = on.stats()
    assert st["serving.cow_copies"] == 1
    assert st["serving.prefix_hit_tokens"] == 14 + 12
    # 40 -> 32 + 16 (the base and the forked suffix), 21 -> 32
    assert st["serving.prefill_pieces{width=32}"] == 3
    assert st["serving.prefill_pieces{width=16}"] == 2
    assert st["serving.prefill_real_tokens"] == 40 + 40 + 21
