"""Prefill as a window forward through the block table
(``serving/batched_decode.py``): the K/V rows it writes and the logits
that seed decode equal those of ``paged_step_logits`` stepped token by
token on a copy of the same pool, whatever the padding, the number of
pieces, the cached prefix in front or the copy-on-write fork; nothing
outside the slot's own blocks and the trash block changes; the LM head
runs on one row; and the engine counts pieces, real and padded tokens."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import oracle_tol
from paddle_tpu.observability import trace
from paddle_tpu.serving import batched_decode as _bd
from tiny import gpt2 as fam

VOCAB, NL, NH, DM = (fam.sizes[k] for k in ("rows", "layers", "heads", "d"))
T, B = fam.max_len, fam.block_tokens
NB = T // B
ARCH = fam.arch()


def _noise_pool(eng, seed):
    """A pool full of finite garbage: whatever prefill must not read has
    to be masked, and whatever it must not write has to stay as it is."""
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(0.0, 1.0, c.shape), c.dtype)
                 for c in eng._pk)


@jax.jit
def _step(p, tok, t, pk, pv, row):
    return _bd.paged_step_logits(p, tok, t, pk, pv, row[None], ARCH)[:3]


@jax.jit
def _window(p, pk, pv, toks, at, last, row):
    return _bd._window_forward(p, pk, pv, toks[None], at[None], last[None],
                               row[None], ARCH)[:3]


def _step_through(p, pk, pv, row, toks, start):
    """The reference: one ``paged_step_logits`` call per token."""
    logits = None
    for j, tok in enumerate(toks):
        logits, pk, pv = _step(p, jnp.asarray([tok], jnp.int32),
                               jnp.asarray([start + j], jnp.int32), pk, pv,
                               row)
    return logits[0], pk, pv


def _window_logits(eng, pk, pv, row, toks, start):
    """The same pieces the engine dispatches, through the one window
    forward, with the logits (the executable keeps only their argmax)."""
    logits = None
    for _w, padded, at, n in eng._pieces(toks, start):
        x, pk, pv = _window(eng._p, pk, pv, padded, jnp.int32(at),
                            jnp.int32(at + n - 1), row)
        logits = eng.arch.head(eng._p, x[0, n - 1])
    return logits


# suffix, start, fork: (name, tokens behind the cached prefix, cached
# prefix tokens, whether the prefix ends inside a block that is forked)
CASES = [
    ("padding_rows", 5, 0, False),          # one piece of 8, 3 rows padding
    ("exactly_a_bucket", 8, 0, False),      # one piece, no padding
    ("two_pieces_and_a_remainder", 19, 0, False),   # 8 + 8 + 4 (3 real)
    ("behind_a_cached_prefix", 6, 8, False),        # start on a block edge
    ("cow_fork", 11, 6, True),              # start inside a forked block
]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name,suffix,start,fork", CASES,
                         ids=[c[0] for c in CASES])
def test_window_prefill_equals_the_token_steps(monkeypatch, dtype, name,
                                               suffix, start, fork):
    eng, _reg = fam.engine(fam.init(dtype=dtype), monkeypatch)
    rng = np.random.default_rng(suffix * 31 + start)
    prompt = rng.integers(1, VOCAB, start + suffix, dtype=np.int32)
    # the slot's chain: blocks 9.. in a shuffled order, the rest trash
    n_blocks = -(-(start + suffix + 4) // B)
    chain = 9 + rng.permutation(n_blocks + 2)[:n_blocks]
    row_h = np.zeros(NB, np.int32)
    row_h[:n_blocks] = chain
    shared = start // B                 # whole blocks of the prefix
    cow = (0, 0)
    pk, pv = _noise_pool(eng, 3), _noise_pool(eng, 4)
    if start:
        # the cached prefix, as an earlier request's steps left it; with
        # a fork its last, partial block lives in another chain's block
        src_row = row_h.copy()
        if fork:
            src_row[shared] = 7
            cow = (7, int(chain[shared]))
        _, pk, pv = _step_through(eng._p, pk, pv, jnp.asarray(src_row),
                                  prompt[:start], 0)
    row = jnp.asarray(row_h)
    own = set(int(b) for b in chain[shared:])
    pk0, pv0 = pk, pv

    # reference: the fork as a plain block copy, then token by token
    rk = tuple(c.at[cow[1]].set(c[cow[0]]) for c in pk)
    rv = tuple(c.at[cow[1]].set(c[cow[0]]) for c in pv)
    ref_logits, rk, rv = _step_through(eng._p, rk, rv, row, prompt[start:],
                                       start)

    pieces = eng._pieces(prompt[start:], start)
    assert [w for w, *_ in pieces] == eng._piece_widths(suffix)
    assert sum(n for *_, n in pieces) == suffix
    gk, gv, first = eng._run_pieces(eng._prefill_fn, eng._p, pk, pv, 1, row,
                                    pieces, cow=cow)

    tol = (1e-5 if dtype == "float32"
           else oracle_tol("paged_attention", dtype))
    f32 = lambda a: np.asarray(a, np.float32)

    def close(got, ref):
        # the oracle suites' measure: the largest error over the
        # largest reference magnitude
        assert np.abs(got - ref).max() <= tol * np.abs(ref).max()

    for got, ref, before in zip(gk + gv, rk + rv, pk0 + pv0):
        got, ref, before = f32(got), f32(ref), f32(before)
        # every real position of the suffix holds the steps' K/V row
        at = np.arange(start, start + suffix)
        close(got[row_h[at // B], at % B], ref[row_h[at // B], at % B])
        # nothing but the slot's own blocks and the trash block moved:
        # not the shared prefix, not the fork's source, not a neighbour
        untouched = [b for b in range(got.shape[0])
                     if b not in own and b != 0]
        np.testing.assert_array_equal(got[untouched], before[untouched])
    logits = _window_logits(
        eng, tuple(c.at[cow[1]].set(c[cow[0]]) for c in pk0),
        tuple(c.at[cow[1]].set(c[cow[0]]) for c in pv0), row,
        prompt[start:], start)
    close(f32(logits), f32(ref_logits))
    assert int(first) == int(np.argmax(f32(ref_logits)))
    # the slot's scalars are seeded for decode
    assert int(eng._last[1]) == int(first)
    assert int(eng._pos[1]) == start + suffix


@pytest.mark.parametrize("width", [4, 8])
def test_prefill_runs_the_lm_head_on_one_row(width):
    """The lowered prefill of any width holds exactly one matmul against
    ``lm_head.w`` ([d, vocab]; no other operand has that shape), and its
    left operand has one row."""
    p = fam.init()
    pool = tuple(jnp.zeros((12, B, NH, DM // NH), jnp.float32)
                 for _ in range(NL))
    fn = _bd.make_prefill(ARCH, width, donate=False)
    i32 = lambda v: np.int32(v)
    text = fn.lower(p, pool, pool, jnp.zeros(3, jnp.int32),
                    jnp.zeros(3, jnp.int32), i32(1),
                    jnp.zeros(NB, jnp.int32), jnp.zeros(width, jnp.int32),
                    i32(0), i32(width - 1), i32(0), i32(0)).as_text()
    dots = re.findall(r"stablehlo\.dot_general.*?:\s*\((tensor<[^>]*>), "
                      r"(tensor<[^>]*>)\)", text)
    head = [(a, b) for a, b in dots if b == f"tensor<{DM}x{VOCAB}xf32>"]
    assert head == [(f"tensor<1x{DM}xf32>", f"tensor<{DM}x{VOCAB}xf32>")]
    # the trunk's matmuls are window-wide: the weights are read once
    assert (f"tensor<1x{width}x{DM}xf32>",
            f"tensor<{DM}x{DM}xf32>") in dots


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_wide_windows_attend_densely_narrow_ones_stream(monkeypatch,
                                                        platform):
    """The attention spelling follows the call's SHAPES at trace time:
    under ``DENSE_WINDOW`` rows whatever the registry resolves with its
    own default geometry; from there up one step over the whole chain of
    the ``xla_ref`` spelling; and from ``CHAIN_SCORE_BYTES`` of dense
    scores up (folded rows x the chain's positions) the ``chain_attention``
    op class, which is the Mosaic walk on a TPU and the same dense step
    off it.  A latent plane stays dense whatever its size.  The choice
    lives with the kernel (``kernels.paged_attention.attend``), not in
    the serving step."""
    from paddle_tpu.kernels import paged_attention as _pa

    calls = []
    real = _pa.resolve
    monkeypatch.setattr(jax, "default_backend", lambda: platform)

    def spy(op, backend=None, **kw):
        ker = real(op, backend=backend, **kw)

        class Impl:
            @staticmethod
            def call(q, *a, block_step=None, **k):
                calls.append((op, q.shape[1], ker.backend, block_step))
                if ker.backend == "pallas_tpu":
                    return q            # no chip here: the choice is the test
                return ker.impl.call(q, *a, block_step=block_step, **k)

        return type("K", (), {"impl": Impl, "backend": ker.backend})

    monkeypatch.setattr(_pa, "resolve", spy)
    dh = DM // NH
    pool = jnp.zeros((6, B, NH, dh), jnp.float32)
    table = jnp.zeros((1, NB), jnp.int32)
    dense_w = _pa.DENSE_WINDOW
    for w in (1, dense_w - 1, dense_w, 4 * dense_w):
        q = jnp.zeros((1, w, NH, dh), jnp.float32)
        _pa.attend(q, pool, pool, table, jnp.zeros((1, w), jnp.int32))
    # the rule: the same shapes walk once their dense scores pass it
    assert not _pa.walks_chain(4 * dense_w, NH, NB * B)
    monkeypatch.setattr(_pa, "CHAIN_SCORE_BYTES",
                        4 * 4 * dense_w * NH * NB * B)
    assert _pa.walks_chain(4 * dense_w, NH, NB * B)
    for w in (dense_w - 1, dense_w, 4 * dense_w):
        q = jnp.zeros((1, w, NH, dh), jnp.float32)
        _pa.attend(q, pool, pool, table, jnp.zeros((1, w), jnp.int32))
    # a latent plane of the same size keeps the dense spelling
    _pa.attend(jnp.zeros((1, 4 * dense_w, NH, dh), jnp.float32),
               jnp.zeros((6, B, dh), jnp.float32), None, table,
               jnp.zeros((1, 4 * dense_w), jnp.int32), value_lanes=dh)
    streams = "pallas_tpu" if platform == "tpu" else "xla_ref"
    dense = ("paged_attention", "xla_ref", NB)
    walk = ([("chain_attention", 4 * dense_w, "pallas_tpu", None)]
            if platform == "tpu" else
            [("chain_attention", 4 * dense_w, "xla_ref", None),
             dense[:1] + (4 * dense_w,) + dense[1:]])
    assert calls == [
        ("paged_attention", 1, streams, None),
        ("paged_attention", dense_w - 1, streams, None),
        dense[:1] + (dense_w,) + dense[1:],
        dense[:1] + (4 * dense_w,) + dense[1:],
        ("paged_attention", dense_w - 1, streams, None),
        dense[:1] + (dense_w,) + dense[1:],
        *walk,
        dense[:1] + (4 * dense_w,) + dense[1:]]


def test_engine_counts_pieces_real_and_padded_tokens(monkeypatch):
    """After admissions of known suffix lengths the counters read what
    the lengths imply, one ``serving.prefill`` span covers an admission
    whatever its pieces, and no executable is wider than a piece."""
    eng, reg = fam.engine(fam.init(), monkeypatch, prefix_reuse=False)
    lens = [3, 8, 13, 19, 24]     # 4 | 8 | 8+8 | 8+8+4 | 8+8+8
    widths = {4: 2, 8: 8}
    t = trace.Tracer(enabled=True, registry=None)
    old = trace.set_tracer(t)
    try:
        rng = np.random.default_rng(5)
        eng.generate_many([rng.integers(1, VOCAB, n, dtype=np.int32)
                           for n in lens], max_new_tokens=3)
    finally:
        trace.set_tracer(old)
    st = eng.stats()
    assert {k: v for k, v in st.items()
            if k.startswith("serving.prefill_pieces")} == {
        f"serving.prefill_pieces{{width={w}}}": n
        for w, n in widths.items()}
    assert st["serving.prefill_real_tokens"] == sum(lens)
    assert st["serving.prefill_tokens"] == sum(
        w * n for w, n in widths.items()) == sum(
        eng.bucket_for(n) for n in lens)
    spans = t.events(name="serving.prefill")
    assert len(spans) == len(lens) == st["serving.prefill_seconds"]["count"]
    assert sorted(e["args"]["pieces"] for e in spans) == [1, 1, 2, 3, 3]
    assert sorted(eng._prefill_fns) == sorted(widths)
    assert st["serving.prefill_compiles"] == len(widths)
    # the accounting window re-opens with the other prefill counters
    eng.reset_slo_accounting()
    st = eng.stats()
    assert st["serving.prefill_real_tokens"] == 0
    assert all(v == 0 for k, v in st.items()
               if k.startswith("serving.prefill_pieces"))


@pytest.mark.parametrize("n,widths", [
    (1, [4]), (4, [4]), (5, [8]), (8, [8]), (9, [8, 4]), (16, [8, 8]),
    (21, [8, 8, 8]), (29, [8, 8, 8, 8])])
def test_piece_widths_are_buckets_up_to_the_piece(monkeypatch, n, widths):
    eng, _reg = fam.engine(fam.init(), monkeypatch)
    assert eng._piece_widths(n) == widths
    assert eng.bucket_for(n) == sum(widths)
