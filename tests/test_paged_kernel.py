"""Paged-attention contract: every backend of the ``paged_attention`` op
class matches an independent dense gather+masked-softmax spelling over
ragged block chains (CoW fork, trash-padded tail, garbage trash block)
for W=1 decode and W>1 verify windows; tokens past ``pos`` and the
trash block are provably inert (corruption leaves output bit-equal).
The Mosaic kernel runs interpret-forced so its logic is covered on the
CPU: the live rows it visits, the value product's float32 weights, the
K/V write.  The one entry point the serving step calls
(``kernels.paged_attention.attend``) chooses dense or streaming by the
window's width and nothing else."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kernel_cases import (
    LIVE_FORMS, impl_or_skip, paged_backends, primitive_counts, rel_err,
    windowed_truth)
from paddle_tpu import kernels
from paddle_tpu.kernels import get_kernel, oracle_tol


# -- paged attention oracle suite --------------------------------------------

def _paged_case(dt, w=1, seed=11):
    """Three ragged chains over a 10-block pool: a copy-on-write fork
    (slot 2 shares slot 0's head block), a trash-padded tail (slot 1's
    last table entry is block 0), and a garbage-filled trash block so
    any masking bug surfaces as 1e3-scale output."""
    rng = np.random.default_rng(seed)
    S, NB, B, h, dh = 3, 3, 4, 2, 16
    pool_k = jnp.asarray(
        rng.normal(size=(1 + S * NB, B, h, dh)) * 0.5, dt)
    pool_v = jnp.asarray(
        rng.normal(size=(1 + S * NB, B, h, dh)) * 0.5, dt)
    pool_k = pool_k.at[0].set(1e3)
    pool_v = pool_v.at[0].set(1e3)
    table = jnp.asarray(1 + np.arange(S * NB).reshape(S, NB), jnp.int32)
    table = table.at[2, 0].set(table[0, 0])      # CoW fork
    table = table.at[1, 2].set(0)                # trash tail
    q = jnp.asarray(rng.normal(size=(S, w, h, dh)) * 0.5, dt)
    # per-slot last-visible positions; slot 1 must stay short of its
    # trash tail (chain tokens 8..11) for every window column
    base = jnp.asarray([[7], [5], [9]], jnp.int32)
    pos = base - (w - 1) + jnp.arange(w, dtype=jnp.int32)[None, :]
    return q, pool_k, pool_v, table, pos


def _paged_dense(q, pool_k, pool_v, table, pos):
    """Independent spelling: each slot's logical view gathered inline
    (``pool[table]``) followed by one dense masked softmax — exactly
    the materialization the paged op class exists to kill."""
    S, NB = table.shape
    kb = pool_k[table].reshape(S, NB * pool_k.shape[1], *pool_k.shape[2:])
    vb = pool_v[table].reshape(S, NB * pool_v.shape[1], *pool_v.shape[2:])
    s = jnp.einsum("swhd,sthd->swht", q, kb,
                   preferred_element_type=jnp.float32)
    s = s * (1.0 / float(np.sqrt(q.shape[-1])))
    j = jnp.arange(kb.shape[1], dtype=jnp.int32)
    s = jnp.where(j[None, None, None, :] <= pos[:, :, None, None],
                  s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    l = jnp.sum(p, axis=-1)
    ctx = jnp.einsum("swht,sthd->swhd", p, vb.astype(jnp.float32))
    return (ctx / jnp.where(l == 0.0, 1.0, l)[..., None]).astype(q.dtype)


@pytest.mark.parametrize("backend", kernels.BACKENDS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("w", [1, 3])
def test_paged_oracle_parity(backend, dtype, w):
    """Every available backend matches the dense gather+softmax oracle
    within ORACLE_TOL — single-token decode (W=1) and the speculative
    verify window (W=3), CoW fork and trash masking included."""
    impl = impl_or_skip("paged_attention", backend)
    q, pk, pv, tbl, pos = _paged_case(jnp.dtype(dtype), w=w)
    got = impl.call(q, pk, pv, tbl, pos)
    assert got.dtype == q.dtype and got.shape == q.shape
    assert rel_err(got, _paged_dense(q, pk, pv, tbl, pos)) <= oracle_tol(
        "paged_attention", dtype, "fwd")


@pytest.mark.parametrize("backend", ["pallas_tpu"])
def test_paged_interpret_covers_kernel_logic(backend):
    """The Mosaic kernel runs interpret-forced so its block-streaming
    logic is covered on CPU-only CI."""
    impl = get_kernel("paged_attention", backend).impl
    q, pk, pv, tbl, pos = _paged_case(jnp.float32, w=2)
    assert rel_err(
        impl.call(q, pk, pv, tbl, pos, interpret=True),
        _paged_dense(q, pk, pv, tbl, pos)) <= oracle_tol(
            "paged_attention", "float32", "fwd")


def test_paged_block_step_invariance():
    """block_step is a pure schedule knob: every step width — including
    the clamped-to-chain one-wide-step spelling that takes the no-scan
    direct path — lands within the f32 oracle bound of the dense
    reference."""
    impl = get_kernel("paged_attention", "xla_ref").impl
    q, pk, pv, tbl, pos = _paged_case(jnp.float32, w=2)
    ref = _paged_dense(q, pk, pv, tbl, pos)
    tol = oracle_tol("paged_attention", "float32", "fwd")
    for bs in (None, 1, 2, 3, 99):
        assert rel_err(impl.call(q, pk, pv, tbl, pos, block_step=bs),
                       ref) <= tol, bs


def test_paged_bit_exact_run_to_run():
    impl = get_kernel("paged_attention", "xla_ref").impl
    q, pk, pv, tbl, pos = _paged_case(jnp.float32)
    jf = jax.jit(lambda *a: impl.call(*a))
    assert bool(jnp.array_equal(jf(q, pk, pv, tbl, pos),
                                jf(q, pk, pv, tbl, pos)))


def test_paged_masking_ignores_future_and_trash_content():
    """Tokens past ``pos`` and the trash block never reach the output:
    corrupting them leaves the result bit-identical.  This invariant is
    what makes block-granular reservation and CoW forks safe — reserved
    tail blocks hold stale garbage by design."""
    impl = get_kernel("paged_attention", "xla_ref").impl
    q, pk, pv, tbl, pos = _paged_case(jnp.float32, w=1)
    base = impl.call(q, pk, pv, tbl, pos)
    # slot 0 (pos 7): chain block 2 entirely unused; slot 1 (pos 5):
    # tokens 6..7 of chain block 1 unused; slot 2 (pos 9): tokens
    # 10..11 of chain block 2 unused; trash block 0 always masked
    def corrupt(pool):
        return (pool.at[tbl[0, 2]].set(7e4)
                    .at[tbl[1, 1], 2:].set(7e4)
                    .at[tbl[2, 2], 2:].set(7e4)
                    .at[0].set(-9e4))
    again = impl.call(q, corrupt(pk), corrupt(pv), tbl, pos)
    assert bool(jnp.array_equal(base, again))


# the Mosaic kernel visits the live entries of the live chains only
# (kernels/paged_attention.py): each case is (W, passes, rows of pos;
# None marks a dead slot: table row 0, pos -1), over S=4 slots of NB=4
# blocks of B=4 tokens (T = 16)
_LIVE_CASES = {
    "dead_slot_between_live": (1, 1, [[5], None, [9], [14]]),
    "block_edges_and_stale_pos": (1, 1, [[3], [4], [15], [21]]),
    "window_rows_in_different_blocks": (
        3, 1, [[2, 3, 4], [7, 8, 9], None, [13, 14, 15]]),
    "table_shifted_into_second_pass": (1, 2, [[6], None, [11], [0]]),
    "every_chain_full": (1, 1, [[15], [15], [15], [15]]),
}


def _live_case(name, dtype, h, seed=3):
    w, passes, rows = _LIVE_CASES[name]
    rng = np.random.default_rng(seed)
    S, NB, B, dh = 4, 4, 4, 16
    dt = jnp.dtype(dtype)
    num_blocks = 1 + S * NB
    shape = (passes * num_blocks, B, h, dh)
    pool_k = np.asarray(rng.normal(size=shape) * 0.5, np.float32)
    pool_v = np.asarray(rng.normal(size=shape) * 0.5, np.float32)
    table = 1 + np.arange(S * NB, dtype=np.int32).reshape(S, NB)
    live = np.array([r is not None for r in rows])
    table[~live] = 0
    table += (passes - 1) * num_blocks
    pos = np.array([r if r is not None else [-1] * w for r in rows],
                   np.int32)
    q = jnp.asarray(rng.normal(size=(S, w, h, dh)) * 0.5, dt)
    # the blocks a call has to visit: entries up to the furthest row's
    # position in the live slots; everything else in the pool is fair
    # game for garbage
    visited = np.zeros(shape[0], bool)
    for s in np.flatnonzero(live):
        n = min(NB, int(pos[s].max()) // B + 1)
        visited[table[s, :n]] = True
    return (q, jnp.asarray(pool_k, dt), jnp.asarray(pool_v, dt),
            jnp.asarray(table), jnp.asarray(pos), live, visited)


@pytest.mark.parametrize("dtype,h", LIVE_FORMS)
@pytest.mark.parametrize("case", list(_LIVE_CASES))
def test_paged_mosaic_live_rows_match_the_oracles(case, dtype, h):
    """Live rows of the Mosaic kernel (interpret) match ``xla_ref`` and
    the dense gather+softmax spelling; a dead slot's rows are zeros."""
    from paddle_tpu.kernels.paged_attention import (
        paged_attention_pallas, paged_attention_ref)

    q, pk, pv, tbl, pos, live, _ = _live_case(case, dtype, h)
    got = paged_attention_pallas(q, pk, pv, tbl, pos, interpret=True)
    assert got.dtype == q.dtype and got.shape == q.shape
    tol = oracle_tol("paged_attention", dtype, "fwd")
    for ref in (paged_attention_ref(q, pk, pv, tbl, pos),
                _paged_dense(q, pk, pv, tbl, pos)):
        assert rel_err(got[live], ref[live]) <= tol
    assert not np.asarray(got, np.float32)[~live].any()


@pytest.mark.parametrize("dtype,h", LIVE_FORMS)
@pytest.mark.parametrize("case", list(_LIVE_CASES))
def test_paged_mosaic_never_touches_what_it_need_not_visit(case, dtype, h):
    """The proof of the skip: NaN in every block the call must not visit
    (table entries past a chain's live length, dead slots' rows, the
    trash block, another pass's plane) leaves every live row finite and
    bit-identical.  ``p = 0`` times a NaN value is NaN, so a block that
    was only MASKED would show."""
    from paddle_tpu.kernels.paged_attention import paged_attention_pallas

    q, pk, pv, tbl, pos, live, visited = _live_case(case, dtype, h)
    base = paged_attention_pallas(q, pk, pv, tbl, pos, interpret=True)
    assert not visited.all()
    poison = jnp.asarray(~visited)[:, None, None, None]
    again = paged_attention_pallas(
        q, jnp.where(poison, jnp.nan, pk), jnp.where(poison, jnp.nan, pv),
        tbl, pos, interpret=True)
    assert bool(jnp.all(jnp.isfinite(again.astype(jnp.float32))))
    assert bool(jnp.array_equal(base[live], again[live]))


# the value product on the MXU (PR 35) keeps the weights' float32: a
# bfloat16 pool of 8 K/V heads takes the loop form, of 6 the grid form
_WEIGHT_FORMS = [8, 6]


# (window rows, K/V group): 2, 4, 6 and 20 rows a block
_WEIGHT_ROWS = [(2, 1), (1, 4), (1, 6), (5, 4)]


BF16_MAX = float(jnp.finfo(jnp.bfloat16).max)


def _float32_weights_case(w, group, hk, extra=0, seed=17):
    """``shared_fold_case``'s three slots over a bfloat16 pool (with
   ``extra`` rows past its K/V heads) whose scores spread over some 14
    (weights from 2^-20 to 1, several of a size near the top) against
    values of magnitude up to 64; a window of 48 gives every chain a
    first block too.  Also ``unseen``: what no row's mask lets through
    in the pool (the trash block, the tokens past a chain's end and
    under every row's lower bound, the rows past ``hk``)."""
    rng = np.random.default_rng(seed)
    S, NB, B, dh, window = 3, 16, 8, 16, 48
    shape = (1 + S * NB, B, hk + extra, dh)
    pk = jnp.asarray(rng.normal(size=shape) * 1.9, jnp.bfloat16)
    pv = jnp.asarray(rng.uniform(-64, 64, size=shape), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(S, w, hk * group, dh)) * 1.9,
                    jnp.bfloat16)
    table = 1 + np.arange(S * NB, dtype=np.int32).reshape(S, NB)
    last = np.array([70, 37, NB * B - 1])
    pos = last[:, None] - (w - 1) + np.arange(w)[None, :]
    if w > 1:
        pos[1, 0] = -1
    unseen = np.ones(shape[:3], bool)
    for s_ in range(S):
        at = pos[s_][pos[s_] >= 0]
        tok = np.arange(max(0, at.min() - window + 1), at.max() + 1)
        unseen[table[s_, tok // B], tok % B, :hk] = False
    how = dict(group=group, window=window)
    want = windowed_truth(q, pk, pv, table, pos, group, window, dh ** -0.5)
    return (q, pk, pv, jnp.asarray(table), jnp.asarray(pos, jnp.int32), how,
            want, pos >= 0, jnp.asarray(unseen)[..., None])


@pytest.mark.parametrize("hk", _WEIGHT_FORMS)
@pytest.mark.parametrize("w,group", _WEIGHT_ROWS)
def test_paged_value_product_keeps_float32_weights(w, group, hk):
    """A bfloat16 pool read out in float32: the Mosaic kernel (interpret)
    matches the dense truth at the FLOAT32 tolerance, which the same
    truth with ONE bfloat16 cast of ``p`` misses: the MXU is fed the
    weights whole."""
    from paddle_tpu.kernels.paged_attention import (
        _block_is_sliceable, paged_attention_pallas)

    q, pk, pv, tbl, pos, how, want, live, _ = _float32_weights_case(
        w, group, hk)
    assert _block_is_sliceable(pk) == (hk == 8)
    got = paged_attention_pallas(q, pk, pv, tbl, pos, interpret=True,
                                 out_dtype=jnp.float32, **how)
    tol = oracle_tol("paged_attention", "float32", "fwd")
    assert float(np.abs(want).max()) > 32.0
    assert rel_err(got[live], want[live]) <= tol
    cast = windowed_truth(
        q, pk, pv, np.asarray(tbl), np.asarray(pos), scale=q.shape[-1] ** -0.5,
        weights=lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16),
                                     np.float32), **how)
    assert rel_err(cast[live], want[live]) > tol


@pytest.mark.parametrize("hk", _WEIGHT_FORMS)
@pytest.mark.parametrize("w,group", _WEIGHT_ROWS)
def test_paged_value_product_gives_garbage_no_weight(w, group, hk):
    """The largest finite bfloat16 at every place of the pool no row's
    mask lets through, in blocks the call does visit (tokens past a
    row's position and under its lower bound, the rows ``pool_rows``
    added) and in the trash block: the product sums over all of them, a
    zero weight times garbage stays zero, so no bit of a live row moves
    and a row with ``pos < 0`` beside live ones stays zeros."""
    from paddle_tpu.kernels.paged_attention import (
        _block_is_sliceable, paged_attention_pallas)

    q, pk, pv, tbl, pos, how, want, live, unseen = _float32_weights_case(
        w, group, hk, extra=1 if hk == 6 else 8)
    assert _block_is_sliceable(pk) == (hk == 8)
    assert bool(unseen.any())
    base = paged_attention_pallas(
        q, jnp.where(unseen, 0, pk), jnp.where(unseen, 0, pv), tbl, pos,
        interpret=True, out_dtype=jnp.float32, **how)
    sign = jnp.where(jnp.arange(pk.shape[1])[None, :, None, None] % 2 == 0,
                     BF16_MAX, -BF16_MAX).astype(pk.dtype)
    again = paged_attention_pallas(
        q, jnp.where(unseen, sign, pk), jnp.where(unseen, -sign, pv), tbl,
        pos, interpret=True, out_dtype=jnp.float32, **how)
    assert bool(jnp.all(jnp.isfinite(again)))
    assert bool(jnp.array_equal(base, again))
    assert rel_err(again[live], want[live]) <= oracle_tol(
        "paged_attention", "float32", "fwd")
    assert not np.asarray(again)[~live].any()


def _write_case(dtype, heads, index, seed=23):
    """K/V rows of ``heads`` heads for ``write``: a pool of 1 + S * NB
    blocks of B = 4 tokens whose head axis is ``pool_rows(heads,
    dtype)`` (5 -> 8 in bfloat16: rows to spare; float32 and 8 heads:
    none), S = 4 slots of NB = 3 blocks.  ``index`` "step": one row a
    slot, slot 1 dead (block 0, the trash block); "window": W = 6 rows a
    slot that cross a block boundary, slot 1 dead, and slot 3's rows
    past its ``limit`` in the trash block as ``_window_forward`` routes
    them."""
    from paddle_tpu.kernels.paged_attention import pool_rows

    rng = np.random.default_rng(seed)
    S, NB, B, dh = 4, 3, 4, 16
    table = 1 + np.arange(S * NB, dtype=np.int32).reshape(S, NB)
    table[1] = 0
    start = np.array([5, 0, 2, 3])
    if index == "step":
        at = start
        blk = table[np.arange(S), at // B]
    else:
        at = start[:, None] + np.arange(6)[None, :]
        blk = table[np.arange(S)[:, None], at // B]
        blk = np.where((np.arange(6) < 4)[None, :] | (np.arange(S) != 3
                                                       )[:, None], blk, 0)
    shape = (1 + S * NB, B, pool_rows(heads, dtype), dh)
    rows = jnp.asarray(rng.normal(size=(*at.shape, heads, dh)), dtype)
    return (shape, jnp.asarray(blk, jnp.int32),
            jnp.asarray(at % B, jnp.int32), rows)


@pytest.mark.parametrize("index", ["step", "window"])
@pytest.mark.parametrize("dtype,heads", [("bfloat16", 5), ("bfloat16", 8),
                                         ("float32", 5)])
def test_kv_write_is_the_two_spellings_it_replaced(dtype, heads, index):
    """``write`` into a pool made of zeros against what ``_Cache`` spelt
    until PR 37 (the whole head axis where the rows fill it, its first
    ``heads`` rows where ``pool_rows`` added some): every bit of the
    pool, the trash block included, and one ``scatter`` where the
    partial spelling traced one too (what differs is what the chip's
    compiler makes of them: tests/test_paged_compiles_for_chip.py)."""
    from paddle_tpu.kernels.paged_attention import write

    shape, blk, off, rows = _write_case(dtype, heads, index)
    pool = jnp.zeros(shape, dtype)
    assert (shape[2] > heads) == ((dtype, heads) == ("bfloat16", 5))
    if shape[2] == heads:
        old = pool.at[blk, off].set(rows)
        # rows that fill the head axis: the spelling itself, nothing added
        assert str(jax.make_jaxpr(write)(pool, blk, off, rows)) == str(
            jax.make_jaxpr(lambda p, b, o, r: p.at[b, o].set(r))(
                pool, blk, off, rows))
    else:
        old = pool.at[blk, off, :heads].set(rows)
    got = jax.jit(write)(pool, blk, off, rows)
    assert got.dtype == pool.dtype and got.shape == pool.shape
    assert bool(jnp.array_equal(got, old))
    assert primitive_counts(jax.make_jaxpr(write)(
        pool, blk, off, rows).jaxpr).get("scatter") == 1
    # the live rows are where they belong (block 0 is the trash block)
    b, o = np.asarray(blk), np.asarray(off)
    live = b != 0
    assert bool(jnp.array_equal(np.asarray(got)[b[live], o[live], :heads],
                                np.asarray(rows)[live]))


@pytest.mark.parametrize("backend", ["xla_ref", "pallas_tpu_interpret"])
@pytest.mark.parametrize("index", ["step", "window"])
def test_kv_write_leaves_zeros_in_the_rows_pool_rows_added(index, backend):
    """Over a pool filled with finite garbage every written position
    reads exactly zero from row ``heads`` up (the zeros are WRITTEN, not
    left), and attention over the chains so written agrees to the bit
    with the same writes over a pool made of zeros: what no row's mask
    lets through weighs nothing."""
    from paddle_tpu.kernels.paged_attention import write

    heads, group = 5, 2
    shape, _, _, _ = _write_case("bfloat16", heads, index)
    S, NB, B, dh = 4, 3, 4, shape[-1]
    rng = np.random.default_rng(29)
    table = 1 + np.arange(S * NB, dtype=np.int32).reshape(S, NB)
    table[1] = 0
    last = np.array([9, -1, 6, 11])
    w = 1 if index == "step" else 3
    pos = np.where(last[:, None] < 0, -1,
                   last[:, None] - (w - 1) + np.arange(w)[None, :])
    garbage = jnp.asarray(rng.normal(size=shape) * 40.0, jnp.bfloat16)
    pools = {"zeros": [jnp.zeros(shape, jnp.bfloat16)] * 2,
             "garbage": [garbage, -garbage]}
    # every position a live row attends is written, one position a slot
    # at a time (a decode step) or three (a window)
    for t in range(0, B * NB, w):
        at = np.minimum(t + np.arange(w)[None, :], B * NB - 1) + np.zeros(
            (S, 1), int)
        keep = at <= last[:, None]
        blk = np.where(keep, table[np.arange(S)[:, None], at // B], 0)
        k = jnp.asarray(rng.normal(size=(S, w, heads, dh)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(S, w, heads, dh)), jnp.bfloat16)
        if index == "step":
            blk, at, k, v = blk[:, 0], at[:, 0], k[:, 0], v[:, 0]
        for name, (pk, pv) in pools.items():
            pools[name] = [
                write(pk, jnp.asarray(blk, jnp.int32),
                      jnp.asarray(at % B, jnp.int32), k),
                write(pv, jnp.asarray(blk, jnp.int32),
                      jnp.asarray(at % B, jnp.int32), v)]
    written = np.zeros(shape[:2], bool)
    for s_ in range(S):
        for t in range(max(int(last[s_]) + 1, 0)):
            written[table[s_, t // B], t % B] = True
    untouched = ~written
    untouched[0] = False          # the trash block took the dead rows
    for pool in pools["garbage"]:
        assert not np.asarray(pool, np.float32)[written][:, heads:].any()
        assert np.asarray(pool, np.float32)[untouched][:, heads:].all()
    q = jnp.asarray(rng.normal(size=(S, w, heads * group, dh)) * 0.5,
                    jnp.bfloat16)
    fn = paged_backends()[backend]
    out = {name: fn(q, pk, pv, jnp.asarray(table),
                    jnp.asarray(pos, jnp.int32), group=group)
           for name, (pk, pv) in pools.items()}
    assert bool(jnp.all(jnp.isfinite(out["garbage"])))
    assert bool(jnp.any(out["zeros"] != 0))
    assert bool(jnp.array_equal(out["zeros"], out["garbage"]))


def test_paged_defaults_lower_to_the_program_they_always_did():
    """``group``, ``window``, ``scale`` and ``out_dtype`` are Python
    constants: at their defaults neither backend traces one primitive
    more than a call that does not name them."""
    from paddle_tpu.kernels.paged_attention import (
        paged_attention_pallas, paged_attention_ref)

    q, pk, pv, tbl, pos = _paged_case(jnp.float32, w=2)
    for fn in (paged_attention_ref,
               lambda *a, **k: paged_attention_pallas(*a, interpret=True,
                                                      **k)):
        plain = primitive_counts(jax.make_jaxpr(fn)(q, pk, pv, tbl,
                                                    pos).jaxpr)
        named = primitive_counts(jax.make_jaxpr(
            lambda *a: fn(*a, group=1, window=None, scale=None,
                          out_dtype=None))(q, pk, pv, tbl, pos).jaxpr)
        windowed = primitive_counts(jax.make_jaxpr(
            lambda *a: fn(*a, window=5))(q, pk, pv, tbl, pos).jaxpr)
        assert plain == named
        assert sum(windowed.values()) > sum(plain.values())


def _entry_case(w, seed=7):
    """A ragged table with a dead slot under a ``w``-wide window: S = 4
    slots of NB = 8 blocks of B = 4 tokens (T = 32); each live slot owns
    the entries its window reaches and trash entries behind them, slot
    1 is dead (row of trash, ``pos = -1``), the trash block is garbage."""
    rng = np.random.default_rng(seed)
    S, NB, B, h, dh = 4, 8, 4, 2, 16
    base = [3, None, 9, 15]
    shape = (1 + S * NB, B, h, dh)
    pool_k = jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)
    pool_k, pool_v = pool_k.at[0].set(1e3), pool_v.at[0].set(1e3)
    table = np.zeros((S, NB), np.int32)
    pos = np.full((S, w), -1, np.int32)
    for s, b in enumerate(base):
        if b is None:
            continue
        pos[s] = b + np.arange(w)
        n = (b + w - 1) // B + 1
        table[s, :n] = 1 + s * NB + np.arange(n)
    live = np.array([b is not None for b in base])
    q = jnp.asarray(rng.normal(size=(S, w, h, dh)) * 0.5, jnp.float32)
    return q, pool_k, pool_v, jnp.asarray(table), jnp.asarray(pos), live


@pytest.mark.parametrize("w", [1, 4, 8, 16])
def test_attend_entry_point_chooses_by_window_width(w, monkeypatch):
    """``kernels.paged_attention.attend``, the one call the serving step
    makes, decides by the window's width alone: from ``DENSE_WINDOW``
    rows up it gathers the chain ONCE (one gather of K, one of V, no
    loop, no ``pallas_call``: the ``xla_ref`` spelling with one step
    over the whole chain), narrower it streams blocks through the
    backend the registry resolves.  The Mosaic backend is made servable
    here (available, interpreted) so the narrow side runs the kernel."""
    import functools

    from paddle_tpu.kernels import paged_attention as pa

    mosaic = get_kernel("paged_attention", "pallas_tpu")
    monkeypatch.setattr(mosaic, "_available", lambda: (True, ""))
    monkeypatch.setattr(mosaic.impl, "call", staticmethod(functools.partial(
        pa.paged_attention_pallas, interpret=True)))
    q, pk, pv, tbl, pos, live = _entry_case(w)

    kernels.reset_selected()
    counts = primitive_counts(
        jax.make_jaxpr(pa.attend)(q, pk, pv, tbl, pos).jaxpr)
    dense = w >= pa.DENSE_WINDOW
    assert kernels.selected_backends() == {
        "paged_attention": "xla_ref" if dense else "pallas_tpu"}
    if dense:
        assert counts.get("gather") == 2, counts
        assert not {"pallas_call", "scan", "while"} & set(counts), counts
    else:
        assert counts.get("pallas_call") == 1, counts
        assert "gather" not in counts, counts

    got = pa.attend(q, pk, pv, tbl, pos)
    assert got.dtype == q.dtype and got.shape == q.shape
    assert rel_err(got[live], _paged_dense(q, pk, pv, tbl, pos)[live]) \
        <= oracle_tol("paged_attention", "float32", "fwd")
    assert bool(jnp.all(jnp.isfinite(got)))
    if not dense:
        # the Mosaic kernel fetches nothing for a dead slot
        assert not np.asarray(got)[~live].any()
