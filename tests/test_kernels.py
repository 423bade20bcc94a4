"""Multi-backend kernel registry: the oracle suite + the registry unit
suite (docs/kernels.md).

Oracle contract: every registered backend AVAILABLE on this host is
compared against the ``xla_ref`` reference within the documented
``ORACLE_TOL`` bounds (f32 + bf16, causal + non-causal, d_head 64/128,
grads through the custom-vjp); unavailable backends SKIP with the
registry's reason.  The Mosaic paged kernel additionally runs
interpret-forced so its logic is covered on CPU-only CI.  Within a
backend the contract is bit-exact run-to-run.

Paged-attention contract: every backend of the ``paged_attention`` op
class matches an independent dense gather+masked-softmax spelling over
ragged block chains (CoW fork, trash-padded tail, garbage trash block)
for W=1 decode and W>1 verify windows; tokens past ``pos`` and the
trash block are provably inert (corruption leaves output bit-equal).
The one entry point the serving step calls
(``kernels.paged_attention.attend``) chooses dense or streaming by the
window's width and nothing else.

Registry contract: precedence explicit arg > per-op env > global env >
auto; unknown backends raise ValueError; explicitly requested
unavailable backends raise KernelUnavailable with a reason; a global
env pin an op cannot serve degrades to auto.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu import kernels  # noqa: E402
from paddle_tpu.kernels import (  # noqa: E402
    KernelUnavailable, available_backends, forced_backend, get_kernel,
    oracle_tol, resolve_name)


def _rel_err(a, ref):
    a = jnp.asarray(a, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    scale = float(jnp.max(jnp.abs(ref))) or 1.0
    return float(jnp.max(jnp.abs(a - ref))) / scale


def _impl_or_skip(op, backend):
    rows = {b: (ok, reason) for b, ok, reason in available_backends(op)}
    if backend not in rows:
        pytest.skip(f"{backend} not registered for {op}")
    ok, reason = rows[backend]
    if not ok:
        pytest.skip(f"{backend} unavailable: {reason}")
    return get_kernel(op, backend).impl


def _qkv(dt, d, b=1, t=128, h=2, seed=5):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(b, t, h, d)) * 0.5, dt)
                 for _ in range(3))


# -- oracle suite ------------------------------------------------------------

@pytest.mark.parametrize("backend", kernels.BACKENDS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("d_head", [64, 128])
def test_flash_oracle_parity(backend, dtype, causal, d_head):
    impl = _impl_or_skip("flash_attention", backend)
    oracle = get_kernel("flash_attention", "xla_ref").impl
    q, k, v = _qkv(jnp.dtype(dtype), d_head)
    # explicit 64-wide blocks: t=128 then tiles 2x2, so the online-
    # softmax state actually carries across k blocks and causal cells
    # straddle the diagonal — default (1024-capped) blocks would make
    # this a degenerate single-block kernel
    got = impl.call(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = oracle.call(q, k, v, causal=causal)
    assert _rel_err(got, ref) <= oracle_tol(
        "flash_attention", dtype, "fwd")


@pytest.mark.parametrize("backend", kernels.BACKENDS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_oracle_grads_through_custom_vjp(backend, dtype):
    impl = _impl_or_skip("flash_attention", backend)
    oracle = get_kernel("flash_attention", "xla_ref").impl
    q, k, v = _qkv(jnp.dtype(dtype), 64, b=1)
    wgt = jnp.asarray(np.random.default_rng(7).normal(size=q.shape),
                      jnp.float32)

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=True, **kw).astype(jnp.float32) * wgt)

    got = jax.grad(loss(impl.call, block_q=64, block_k=64),
                   (0, 1, 2))(q, k, v)
    ref = jax.grad(loss(oracle.call), (0, 1, 2))(q, k, v)
    tol = oracle_tol("flash_attention", dtype, "grad")
    for a, r in zip(got, ref):
        assert _rel_err(a, r) <= tol


@pytest.mark.parametrize("backend", kernels.BACKENDS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ce_oracle_parity_and_grads(backend, dtype):
    impl = _impl_or_skip("fused_ce", backend)
    oracle = get_kernel("fused_ce", "xla_ref").impl
    rng = np.random.default_rng(9)
    n, d, vocab = 64, 32, 512
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.normal(size=(n, d)) * 0.3, dt)
    w = jnp.asarray(rng.normal(size=(d, vocab)) * 0.05, dt)
    y = jnp.asarray(rng.integers(0, vocab, (n,)), jnp.int32)
    # small explicit blocks so the vocab axis actually tiles (nv=4)
    # and the row axis splits — the online-softmax carry is the thing
    # under test (128 is the narrowest vocab tile the chip accepts)
    blocks = dict(block_n=32, block_v=128, block_v_fwd=128)
    assert _rel_err(impl.call(x, w, y, **blocks),
                    oracle.call(x, w, y)) <= oracle_tol(
                        "fused_ce", dtype, "fwd")
    gvec = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    got = jax.grad(lambda x, w: jnp.sum(
        impl.call(x, w, y, **blocks) * gvec), (0, 1))(x, w)
    ref = jax.grad(lambda x, w: jnp.sum(oracle.call(x, w, y) * gvec),
                   (0, 1))(x, w)
    tol = oracle_tol("fused_ce", dtype, "grad")
    for a, r in zip(got, ref):
        assert _rel_err(a, r) <= tol


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
    impl = _impl_or_skip("paged_attention", backend)
    q, pk, pv, tbl, pos = _paged_case(jnp.dtype(dtype), w=w)
    got = impl.call(q, pk, pv, tbl, pos)
    assert got.dtype == q.dtype and got.shape == q.shape
    assert _rel_err(got, _paged_dense(q, pk, pv, tbl, pos)) <= oracle_tol(
        "paged_attention", dtype, "fwd")


@pytest.mark.parametrize("backend", ["pallas_tpu"])
def test_paged_interpret_covers_kernel_logic(backend):
    """The Mosaic kernel runs interpret-forced so its block-streaming
    logic is covered on CPU-only CI."""
    impl = get_kernel("paged_attention", backend).impl
    q, pk, pv, tbl, pos = _paged_case(jnp.float32, w=2)
    assert _rel_err(
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
        assert _rel_err(impl.call(q, pk, pv, tbl, pos, block_step=bs),
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
# float32 with 2 heads takes the kernel's loop over the chain, bfloat16
# with 6 heads the grid Mosaic needs where it cannot slice the pool
_LIVE_FORMS = [("float32", 2), ("bfloat16", 6)]


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


@pytest.mark.parametrize("dtype,h", _LIVE_FORMS)
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
        assert _rel_err(got[live], ref[live]) <= tol
    assert not np.asarray(got, np.float32)[~live].any()


@pytest.mark.parametrize("dtype,h", _LIVE_FORMS)
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


# K/V groups and a lower bound (PR 32): query head i reads K/V head
# i // group; with a window a query sees itself and the window - 1 keys
# before it.  (group, window, rows the pool has beyond its K/V heads)
_GROUP_WINDOW_CASES = {
    "group_2": (2, None, 0),
    "window_6": (1, 6, 0),
    "group_2_window_6": (2, 6, 0),
    "group_4_window_5_rows_padded": (4, 5, 5),
    "window_wider_than_any_context": (2, 64, 0),
}


def _windowed_truth(q, pk, pv, table, pos, group, window, scale,
                    weights=lambda a: a):
    """The dense truth, in numpy, from the values the backends see: row
    ``r`` of slot ``s`` attends keys ``max(0, pos - window + 1) .. pos``
    of its chain, query head ``i`` the K/V head ``i // group``; a row
    with ``pos < 0`` stays zeros.  ``weights`` is what becomes of the
    unnormalized weights before they meet the values (a rounding, for a
    test that has to tell one from none)."""
    S, NB = table.shape
    dh = q.shape[-1]
    k32 = np.asarray(pk, np.float32)[table].reshape(S, -1, pk.shape[2], dh)
    v32 = np.asarray(pv, np.float32)[table].reshape(S, -1, pv.shape[2], dh)
    q32 = np.asarray(q, np.float32)
    want = np.zeros(q.shape, np.float32)
    for s_ in range(S):
        for r in range(q.shape[1]):
            at = int(pos[s_, r])
            if at < 0:
                continue
            lo = 0 if window is None else max(0, at - window + 1)
            for i in range(q.shape[2]):
                sc = k32[s_, lo:at + 1, i // group] @ q32[s_, r, i] * scale
                a = np.exp(sc - sc.max())
                want[s_, r, i] = (weights(a) / a.sum()) @ v32[
                    s_, lo:at + 1, i // group]
    return want


def _group_window_case(name, dtype, hk, w, seed=5):
    group, window, extra = _GROUP_WINDOW_CASES[name]
    rng = np.random.default_rng(seed)
    S, NB, B, dh = 4, 4, 4, 16
    dt = jnp.dtype(dtype)
    shape = (1 + S * NB, B, hk + extra, dh)
    pool_k = np.asarray(rng.normal(size=shape) * 0.5, np.float32)
    pool_v = np.asarray(rng.normal(size=shape) * 0.5, np.float32)
    table = 1 + np.arange(S * NB, dtype=np.int32).reshape(S, NB)
    table[1] = 0                                   # a dead slot
    last = np.array([[5], [-1], [11], [15]], np.int32)
    pos = np.where(last < 0, -1, last - (w - 1) + np.arange(w)[None, :])
    q = jnp.asarray(rng.normal(size=(S, w, hk * group, dh)) * 0.5, dt)
    pk, pv = jnp.asarray(pool_k, dt), jnp.asarray(pool_v, dt)
    want = _windowed_truth(q, pk, pv, table, pos, group, window, 0.3)
    # blocks some row's bounds let through
    visited = np.zeros(shape[0], bool)
    for s_ in range(S):
        if pos[s_].max() < 0:
            continue
        lo = (0 if window is None
              else max(0, int(pos[s_].min()) - window + 1))
        visited[table[s_, lo // B:int(pos[s_].max()) // B + 1]] = True
    return (q, pk, pv, jnp.asarray(table), jnp.asarray(pos, jnp.int32),
            dict(group=group, window=window, scale=0.3), want,
            pos.max(axis=1) >= 0, visited)


def _paged_backends():
    from paddle_tpu.kernels.paged_attention import (
        paged_attention_pallas, paged_attention_ref)

    return {"xla_ref": paged_attention_ref,
            "xla_ref_block_step_1": lambda *a, **k: paged_attention_ref(
                *a, block_step=1, **k),
            "pallas_tpu_interpret": lambda *a, **k: paged_attention_pallas(
                *a, interpret=True, **k)}


# float32 with 2 K/V heads takes the Mosaic kernel's loop over the chain,
# bfloat16 with 6 the grid form
@pytest.mark.parametrize("dtype,hk", _LIVE_FORMS)
@pytest.mark.parametrize("w", [1, 3])
@pytest.mark.parametrize("backend", ["xla_ref", "xla_ref_block_step_1",
                                     "pallas_tpu_interpret"])
@pytest.mark.parametrize("case", list(_GROUP_WINDOW_CASES))
def test_paged_groups_and_windows_match_the_dense_truth(case, backend, w,
                                                        dtype, hk):
    q, pk, pv, tbl, pos, how, want, live, _ = _group_window_case(
        case, dtype, hk, w)
    got = _paged_backends()[backend](q, pk, pv, tbl, pos, **how)
    assert got.dtype == q.dtype and got.shape == q.shape
    assert _rel_err(got[live], jnp.asarray(want)[live]) <= oracle_tol(
        "paged_attention", dtype, "fwd")
    # float32 out of a bf16 pool, for a caller that combines contexts
    wide = _paged_backends()[backend](q, pk, pv, tbl, pos,
                                      out_dtype=jnp.float32, **how)
    assert wide.dtype == jnp.float32
    assert _rel_err(wide[live], jnp.asarray(want)[live]) <= oracle_tol(
        "paged_attention", dtype, "fwd")
    if backend == "pallas_tpu_interpret":
        assert not np.asarray(got, np.float32)[~live].any()


@pytest.mark.parametrize("dtype,hk", _LIVE_FORMS)
@pytest.mark.parametrize("case", ["window_6", "group_2_window_6",
                                  "group_4_window_5_rows_padded"])
def test_paged_mosaic_window_starts_at_its_first_block(case, dtype, hk):
    """NaN in every block under every row's lower bound (and past the
    chain's live length) changes no bit: the loop starts at the window's
    first block, the grid form skips the steps before it with nothing
    fetched."""
    from paddle_tpu.kernels.paged_attention import paged_attention_pallas

    q, pk, pv, tbl, pos, how, _, live, visited = _group_window_case(
        case, dtype, hk, 1)
    # slots 2 and 3 attend from positions 6 and 10 (window 6): their
    # first one and two blocks lie under the bound
    assert not visited[np.asarray(tbl)[3, :2]].any()
    base = paged_attention_pallas(q, pk, pv, tbl, pos, interpret=True, **how)
    poison = jnp.asarray(~visited)[:, None, None, None]
    again = paged_attention_pallas(
        q, jnp.where(poison, jnp.nan, pk), jnp.where(poison, jnp.nan, pv),
        tbl, pos, interpret=True, **how)
    assert bool(jnp.all(jnp.isfinite(again.astype(jnp.float32))))
    assert bool(jnp.array_equal(base[live], again[live]))


# the shared fold (PR 33): a live block is folded ONCE for all the rows
# of the window (a K/V group's rows included): the rows side by side on
# the lanes of one array, one softmax update for all of them.  Three slots over
# NB = 16 blocks of B = 8 tokens (T = 128): slot 0's window ends at
# position 70 with its rows at DIFFERENT positions (a verify window: no
# two rows share a mask), slot 1 has a row with ``pos < 0`` beside live
# ones (dead where W = 1), slot 2's window ends at the chain's last
# position.
def _shared_fold_case(w, group, window, dtype, hk, seed=13):
    rng = np.random.default_rng(seed)
    S, NB, B, dh = 3, 16, 8, 16
    dt = jnp.dtype(dtype)
    shape = (1 + S * NB, B, hk, dh)
    pool_k = np.asarray(rng.normal(size=shape) * 0.5, np.float32)
    pool_v = np.asarray(rng.normal(size=shape) * 0.5, np.float32)
    pool_k[0] = pool_v[0] = 1e3                      # the trash block
    table = 1 + np.arange(S * NB, dtype=np.int32).reshape(S, NB)
    last = np.array([70, 37, NB * B - 1])
    pos = last[:, None] - (w - 1) + np.arange(w)[None, :]
    pos[1, 0] = -1
    table[0, 70 // B + 1:] = 0
    table[1, 37 // B + 1:] = 0
    if w == 1:
        table[1] = 0
    q = jnp.asarray(rng.normal(size=(S, w, hk * group, dh)) * 0.5, dt)
    pk, pv = jnp.asarray(pool_k, dt), jnp.asarray(pool_v, dt)
    want = _windowed_truth(q, pk, pv, table, pos, group, window,
                           dh ** -0.5)             # the kernels' default
    return (q, pk, pv, jnp.asarray(table), jnp.asarray(pos, jnp.int32),
            dict(group=group, window=window), jnp.asarray(want), pos >= 0)


@pytest.mark.parametrize("dtype,hk", _LIVE_FORMS)
@pytest.mark.parametrize("window", [None, 48, 512])
@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("w", [1, 2, 4, 5])
def test_paged_shared_fold_matches_the_oracle_and_the_dense_truth(
        w, group, window, dtype, hk):
    """Every row of every window width, K/V group and lower bound, in
    the loop form and the grid form: the Mosaic kernel (interpret)
    against the dense truth and against ``xla_ref``; a row with ``pos <
    0`` is zeros although its neighbours in the window are live."""
    from paddle_tpu.kernels.paged_attention import (
        paged_attention_pallas, paged_attention_ref)

    q, pk, pv, tbl, pos, how, want, live = _shared_fold_case(
        w, group, window, dtype, hk)
    got = paged_attention_pallas(q, pk, pv, tbl, pos, interpret=True,
                                 out_dtype=jnp.float32, **how)
    assert got.shape == q.shape and got.dtype == jnp.float32
    tol = oracle_tol("paged_attention", dtype, "fwd")
    ref = paged_attention_ref(q, pk, pv, tbl, pos, out_dtype=jnp.float32,
                              **how)
    assert live.any() and not live.all()
    assert _rel_err(got[live], want[live]) <= tol
    assert _rel_err(got[live], ref[live]) <= tol
    assert not np.asarray(got)[~live].any()


# G table entries an iteration (PR 52): the loop of two rows or more
# takes a group of consecutive entries as ONE block of G x B tokens.  The
# rule gives these chains of 16 entries one entry (``GROUP_SHARE``), so
# its answer is overridden here: chains of 9, 5 and 16 live entries (no
# multiple of 4 or 8 among the first two, 5 shorter than 8), a window of
# 48 whose first entries (2 and 10) are no multiple of a group, a verify
# window whose rows sit at different positions, a float32 pool, a slot
# whose only row is dead; every entry past a chain's end names the trash
# block, whose values are 1e3.  (window rows, K/V group, lower bound,
# dtype, K/V rows: the loop form)
_ENTRY_CASES = {
    "group_4_ragged_chains": (1, 4, None, "bfloat16", 8),
    "group_4_window_first_entry_off_a_group": (1, 4, 48, "bfloat16", 8),
    "verify_5_rows_window": (5, 1, 48, "bfloat16", 8),
    "float32_pool_2_rows_group_2": (2, 2, None, "float32", 2),
}


@pytest.mark.parametrize("entries", [1, 2, 4, 8])
@pytest.mark.parametrize("case", list(_ENTRY_CASES))
def test_paged_shared_fold_takes_g_entries_an_iteration(case, entries,
                                                        monkeypatch):
    from paddle_tpu.kernels import paged_attention as pa

    w, group, window, dtype, hk = _ENTRY_CASES[case]
    q, pk, pv, tbl, pos, how, want, live = _shared_fold_case(
        w, group, window, dtype, hk)
    monkeypatch.setattr(pa, "entries_per_iteration", lambda *a: entries)
    got = pa.paged_attention_pallas(q, pk, pv, tbl, pos, interpret=True,
                                    out_dtype=jnp.float32, **how)
    tol = oracle_tol("paged_attention", dtype, "fwd")
    ref = pa.paged_attention_ref(q, pk, pv, tbl, pos, out_dtype=jnp.float32,
                                 **how)
    assert live.any() and not live.all()
    assert _rel_err(got[live], want[live]) <= tol
    assert _rel_err(got[live], ref[live]) <= tol
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("window,entries", [(None, 2), (48, 4), (48, 8)])
def test_paged_group_fetches_its_own_entries_and_no_other(window, entries,
                                                          monkeypatch):
    """The kernel's trip count, read off what it fetches: NaN in every
    block but those of entries ``[f_s, f_s + iterations x G)`` of a live
    slot's table (``loop_iterations`` of the slot's live entries; the
    index clamped to the table) changes no bit, a dead slot's row and
    the entries under a lower bound among them; NaN in the LAST entry of
    a chain's last group, past the chain's end, shows (a zero weight
    times NaN): that entry is fetched, and a finite block under a zero
    weight adds nothing."""
    from paddle_tpu.kernels import paged_attention as pa

    q, pk, pv, tbl, pos, how, _, live = _shared_fold_case(
        1, 4, window, "bfloat16", 8)
    B, NB = pk.shape[1], tbl.shape[1]
    monkeypatch.setattr(pa, "entries_per_iteration", lambda *a: entries)
    # distinct blocks past the chains' ends, so that each can be poisoned
    table = np.asarray(tbl).copy()
    spare = iter(range(int(table.max()) + 1, 10 ** 6))
    grown = np.concatenate(
        [np.asarray(pk, np.float32),
         np.full((int((table == 0).sum()),) + pk.shape[1:], 1e3,
                 np.float32)])
    for s, n in np.argwhere(table == 0):
        if live[s, 0]:
            table[s, n] = next(spare)
    pk2 = pv2 = jnp.asarray(grown, pk.dtype)
    shapes = ((B,) + pk.shape[2:],) * 2
    fetched, tails = np.zeros(grown.shape[0], bool), []
    for s in np.flatnonzero(live[:, 0]):
        at = int(np.asarray(pos)[s, 0])
        first = 0 if window is None else max(at - window + 1, 0) // B
        n = at // B + 1 - first
        trips = pa.loop_iterations(n, 4, shapes, pk.dtype, NB, window)
        assert trips == -(-n // entries)     # the override reaches it too
        last = min(first + trips * entries, NB) - 1
        fetched[table[s, first:last + 1]] = True
        if last >= first + n:
            tails.append(int(table[s, last]))
    base = pa.paged_attention_pallas(q, pk2, pv2, jnp.asarray(table), pos,
                                     interpret=True, **how)
    assert not fetched.all() and tails
    poison = jnp.asarray(~fetched)[:, None, None, None]
    again = pa.paged_attention_pallas(
        q, jnp.where(poison, jnp.nan, pk2), jnp.where(poison, jnp.nan, pv2),
        jnp.asarray(table), pos, interpret=True, **how)
    assert bool(jnp.all(jnp.isfinite(again.astype(jnp.float32))))
    assert bool(jnp.array_equal(base, again))
    tail = jnp.zeros(grown.shape[0], bool).at[jnp.asarray(tails)].set(True)
    shown = pa.paged_attention_pallas(
        q, pk2, jnp.where(tail[:, None, None, None], jnp.nan, pv2),
        jnp.asarray(table), pos, interpret=True, **how)
    assert bool(jnp.any(jnp.isnan(shown.astype(jnp.float32))))


def test_paged_group_of_a_slot_with_no_live_row_fetches_nothing(monkeypatch):
    """Every row at ``pos < 0``: zeros, whatever the pool holds (NaN in
    every block) and whatever the group."""
    from paddle_tpu.kernels import paged_attention as pa

    q, pk, pv, tbl, pos, how, _, _ = _shared_fold_case(2, 2, None,
                                                       "bfloat16", 8)
    monkeypatch.setattr(pa, "entries_per_iteration", lambda *a: 4)
    got = pa.paged_attention_pallas(
        q, jnp.full_like(pk, jnp.nan), jnp.full_like(pv, jnp.nan), tbl,
        jnp.full_like(pos, -1), interpret=True, **how)
    assert not np.asarray(got, np.float32).any()


# the rule at the serving cells' decode geometries (blocks of 32 tokens,
# bfloat16): (K/V rows, K lanes, V lanes, folded rows a K/V row, table
# entries, lower bound) -> entries an iteration
_ENTRY_RULE = {
    "long_reason_full": ((8, 256, 128, 16, 416, None), 8),
    "long_reason_window_5_live_entries": ((8, 256, 128, 8, 416, 128), 1),
    "think_decode_full": ((16, 128, 128, 4, 64, None), 2),
    "think_decode_window_17_live_entries": ((16, 128, 128, 4, 64, 512), 1),
    "chat_moe_window_wider_than_the_table": ((8, 128, 128, 6, 64, 4096), 2),
    "chat_ssm": ((8, 128, 128, 16, 80, None), 2),
    "verify_window_under_group_4_fits_vmem": ((16, 128, 128, 20, 416, None),
                                              2),
}


@pytest.mark.parametrize("geometry", list(_ENTRY_RULE))
def test_entries_per_iteration_follows_the_shapes(geometry):
    """A power of two up to ``MAX_ENTRIES``, no more than a
    ``GROUP_SHARE``-th of the entries a chain can have live, within the
    loop's VMEM; ``loop_iterations`` is the kernel's trip count at it."""
    from paddle_tpu.kernels import paged_attention as pa

    (h, dk, dv, rows, NB, window), want = _ENTRY_RULE[geometry]
    live = pa.window_entries(NB, 32, 1, window)
    got = pa.entries_per_iteration(32, h, dk, dv, rows * h, jnp.bfloat16,
                                   live)
    assert got == want
    assert got * pa.GROUP_SHARE <= live or got == 1
    assert got == 1 or pa._loop_vmem_bytes(
        got, 32, h, dk, dv, rows * h, jnp.bfloat16) <= pa.LOOP_VMEM_BYTES
    shapes = ((32, h, dk), (32, h, dv))
    for entries in (0, 1, got, got + 1, 5 * got + 3):
        assert pa.loop_iterations(entries, rows, shapes, jnp.bfloat16, NB,
                                  window) == -(-entries // got)


@pytest.mark.parametrize("form,want", [("one_row", 37), ("grid", 37),
                                       ("latent", 5), ("short_table", 19)])
def test_loop_iterations_of_the_other_forms(form, want):
    """One row a block and the grid form take an entry an iteration (a
    step); a latent plane ``LATENT_BLOCKS``, and no more than its table
    has."""
    from paddle_tpu.kernels import paged_attention as pa

    kv = lambda h: ((32, h, 128), (32, h, 128))  # noqa: E731
    args = {"one_row": (1, kv(16), jnp.bfloat16, 416),
            "grid": (5, kv(12), jnp.bfloat16, 416),
            "latent": (16, ((32, 640),), jnp.bfloat16, 288),
            "short_table": (16, ((32, 640),), jnp.bfloat16, 2)}[form]
    assert pa.loop_iterations(37, *args) == want


@pytest.mark.parametrize("dtype,hk", _LIVE_FORMS)
@pytest.mark.parametrize("w,group", [(1, 4), (5, 1), (2, 2)])
def test_paged_shared_fold_is_bit_exact_run_to_run(w, group, dtype, hk):
    from paddle_tpu.kernels.paged_attention import paged_attention_pallas

    q, pk, pv, tbl, pos, how, _, _ = _shared_fold_case(w, group, 48, dtype,
                                                       hk)
    a = paged_attention_pallas(q, pk, pv, tbl, pos, interpret=True, **how)
    b = paged_attention_pallas(q, pk, pv, tbl, pos, interpret=True, **how)
    assert bool(jnp.array_equal(a, b))
    assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))


@pytest.mark.parametrize("dtype,hk", _LIVE_FORMS)
@pytest.mark.parametrize("w,group", [(1, 1), (2, 1), (1, 4), (5, 1), (3, 2)])
def test_paged_mosaic_makes_one_softmax_update_a_block(w, group, dtype, hk):
    """What the fold shares, read off the traced kernel: however many
    rows attend a block (window rows x K/V group) ONE update holds TWO
    ``exp`` (``alpha`` and ``p``), where a per-row body holds two a row;
    from two rows up the scores AND the value product are ONE
    ``dot_general`` each a block, and their count does not grow with the
    rows.  The loop form traces each at two places (the first group's
    scores ahead of the loop and the next group's inside it; the update
    inside the loop and the last group's after it: four products and
    four ``exp`` in the body), the grid form at one; one row keeps the
    per-row program (a product and a lane reduction, no matmul), which
    is what every ``W = 1`` caller lowered to before."""
    from paddle_tpu.kernels.paged_attention import (
        _block_is_sliceable, paged_attention_pallas, softmax_updates)

    q, pk, pv, tbl, pos, how, _, _ = _shared_fold_case(w, group, 48, dtype,
                                                       hk)
    counts = _primitive_counts(jax.make_jaxpr(
        lambda *a: paged_attention_pallas(*a, interpret=True, **how))(
            q, pk, pv, tbl, pos).jaxpr)
    assert counts.get("pallas_call") == 1
    shared_loop = w * group > 1 and _block_is_sliceable(pk)
    places = 2 if shared_loop else 1
    assert counts.get("exp") == 2 * places * softmax_updates(w * group), counts
    assert counts.get("dot_general", 0) == (0 if w * group == 1
                                            else 2 * places)


# the value product on the MXU (PR 35) keeps the weights' float32: a
# bfloat16 pool of 8 K/V heads takes the loop form, of 6 the grid form
_WEIGHT_FORMS = [8, 6]
# (window rows, K/V group): 2, 4, 6 and 20 rows a block
_WEIGHT_ROWS = [(2, 1), (1, 4), (1, 6), (5, 4)]
BF16_MAX = float(jnp.finfo(jnp.bfloat16).max)


def _float32_weights_case(w, group, hk, extra=0, seed=17):
    """``_shared_fold_case``'s three slots over a bfloat16 pool (with
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
    want = _windowed_truth(q, pk, pv, table, pos, group, window, dh ** -0.5)
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
    assert _rel_err(got[live], want[live]) <= tol
    cast = _windowed_truth(
        q, pk, pv, np.asarray(tbl), np.asarray(pos), scale=q.shape[-1] ** -0.5,
        weights=lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16),
                                     np.float32), **how)
    assert _rel_err(cast[live], want[live]) > tol


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
    assert _rel_err(again[live], want[live]) <= oracle_tol(
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
    assert _primitive_counts(jax.make_jaxpr(write)(
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
    fn = _paged_backends()[backend]
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
        plain = _primitive_counts(jax.make_jaxpr(fn)(q, pk, pv, tbl,
                                                     pos).jaxpr)
        named = _primitive_counts(jax.make_jaxpr(
            lambda *a: fn(*a, group=1, window=None, scale=None,
                          out_dtype=None))(q, pk, pv, tbl, pos).jaxpr)
        windowed = _primitive_counts(jax.make_jaxpr(
            lambda *a: fn(*a, window=5))(q, pk, pv, tbl, pos).jaxpr)
        assert plain == named
        assert sum(windowed.values()) > sum(plain.values())


def _primitive_counts(jaxpr, counts=None):
    """Primitive name -> occurrences, sub-jaxprs (scan and while bodies,
    pjit) included."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitive_counts(sub, counts)
    return counts


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
    counts = _primitive_counts(
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
    assert _rel_err(got[live], _paged_dense(q, pk, pv, tbl, pos)[live]) \
        <= oracle_tol("paged_attention", "float32", "fwd")
    assert bool(jnp.all(jnp.isfinite(got)))
    if not dense:
        # the Mosaic kernel fetches nothing for a dead slot
        assert not np.asarray(got)[~live].any()


# -- the latent plane (kernels/paged_attention.py, pool_v=None) ---------------

def _latent_case(dt, w=1, seed=40):
    """Four slots over a pool of latent rows ``[blocks, B, L]``, 40 of
    128 lanes values: slot 1 shares slot 0's first two blocks (a chain
    shared by two slots), slot 2 is dead (``pos = -1``), slot 3 ends in
    a trash-padded tail; the trash block and every lane past the values
    hold 1e3 resp. zeros, so a masking bug shows as 1e3-scale output."""
    from paddle_tpu.kernels.paged_attention import latent_lanes

    rng = np.random.default_rng(seed)
    S, NB, B, h, values, dv = 4, 6, 4, 4, 40, 32
    L = latent_lanes(values)
    pool = np.zeros((1 + S * NB, B, L), np.float32)
    pool[..., :values] = rng.normal(size=(1 + S * NB, B, values)) * 0.5
    pool[0] = 1e3
    table = 1 + np.arange(S * NB).reshape(S, NB)
    table[1, :2] = table[0, :2]
    table[2] = 0
    table[3, 4:] = 0
    q = np.zeros((S, w, h, L), np.float32)
    q[..., :values] = rng.normal(size=(S, w, h, values)) * 0.5
    base = np.array([[21], [13], [-1], [15]])
    pos = base - (w - 1) + np.arange(w)[None, :]
    pos[2] = -1
    return (jnp.asarray(q, dt), jnp.asarray(pool, dt),
            jnp.asarray(table, jnp.int32), jnp.asarray(pos, jnp.int32), dv)


def _latent_dense(q, pool, table, pos, dv, scale):
    """Independent spelling in NumPy: a slot's rows gathered, one masked
    softmax a query row, the values the rows' first ``dv`` lanes."""
    q, pool = np.asarray(q, np.float32), np.asarray(pool, np.float32)
    out = np.zeros(q.shape[:3] + (dv,), np.float32)
    for s, w in np.ndindex(*q.shape[:2]):
        n = int(pos[s, w]) + 1
        if n <= 0:
            continue
        rows = pool[np.asarray(table[s])].reshape(-1, pool.shape[-1])[:n]
        a = q[s, w] @ rows.T * scale
        a = np.exp(a - a.max(-1, keepdims=True))
        out[s, w] = (a / a.sum(-1, keepdims=True)) @ rows[:, :dv]
    return out


def _latent_backends():
    from paddle_tpu.kernels.paged_attention import (
        latent_attention_pallas, paged_attention_pallas, paged_attention_ref)

    def mosaic(blocks):
        return lambda q, pool, tbl, pos, dv, scale: latent_attention_pallas(
            q, pool, tbl, pos, dv, scale=scale, interpret=True, blocks=blocks)

    return {
        "xla_ref": lambda q, pool, tbl, pos, dv, scale: paged_attention_ref(
            q, pool, None, tbl, pos, value_lanes=dv, scale=scale),
        "xla_ref_one_step": lambda q, pool, tbl, pos, dv, scale:
            paged_attention_ref(q, pool, None, tbl, pos, value_lanes=dv,
                                scale=scale, block_step=tbl.shape[1]),
        "pallas_tpu_interpret": lambda q, pool, tbl, pos, dv, scale:
            paged_attention_pallas(q, pool, None, tbl, pos, value_lanes=dv,
                                   scale=scale, interpret=True),
        "mosaic_one_block_an_update": mosaic(1),
        "mosaic_groups_past_the_chain": mosaic(5),
    }


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("w", [1, 3])
@pytest.mark.parametrize("backend", list(_latent_backends()))
def test_latent_attend_backends_match_the_dense_truth(backend, w, dtype):
    """A latent plane through both backends (the Mosaic kernel under
    ``interpret=True``, at one, four and five table entries an update:
    five do not divide the chain, so the last group runs past it into
    the trash block): live slots against the dense truth, a chain shared
    by two slots, a trash-padded tail; the dead slot's rows come back
    zero from the kernel."""
    q, pool, tbl, pos, dv = _latent_case(jnp.dtype(dtype), w=w)
    got = np.asarray(_latent_backends()[backend](q, pool, tbl, pos, dv, 0.2),
                     np.float32)
    want = _latent_dense(q, pool, tbl, pos, dv, 0.2)
    live = np.array([0, 1, 3])
    assert got.shape == want.shape
    tol = oracle_tol("paged_latent_attention", dtype, "fwd") * np.abs(
        want).max()
    assert np.abs(got - want)[live].max() <= tol
    if not backend.startswith("xla_ref"):
        assert not got[2].any()


def test_latent_attend_chooses_dense_from_eight_rows_up():
    """``attend`` with ``pool_v=None``: a window of ``DENSE_WINDOW`` rows
    gathers the chain once (the oracle's one step), a narrower one
    streams; both are the dense truth.  A group, a lower bound or a row
    narrower than the pool's are refused."""
    from paddle_tpu.kernels.paged_attention import DENSE_WINDOW, attend

    for w in (DENSE_WINDOW, DENSE_WINDOW - 1):
        q, pool, tbl, pos, dv = _latent_case(jnp.float32, w=w)
        pos = jnp.maximum(pos, -1)
        got = np.asarray(attend(q, pool, None, tbl, pos, value_lanes=dv,
                                scale=0.2))
        want = _latent_dense(q, pool, tbl, np.asarray(pos), dv, 0.2)
        rows = np.asarray(pos) >= 0
        assert np.abs(got - want)[rows].max() <= 2e-4 * np.abs(want).max()
    with pytest.raises(ValueError, match="no group"):
        attend(q, pool, None, tbl, pos, value_lanes=dv, group=2)
    # a lower bound is served since PR 55 (tests/test_sparse_latent_moe.py
    # holds it to a NumPy softmax): a window wider than any context is no
    # bound at all, a narrow one moves the rows that had more to see
    wide = np.asarray(attend(q, pool, None, tbl, pos, value_lanes=dv,
                             scale=0.2, window=1 << 20))
    assert np.abs(wide - got)[rows].max() <= 2e-4 * np.abs(want).max()
    narrow = np.asarray(attend(q, pool, None, tbl, pos, value_lanes=dv,
                               scale=0.2, window=2))
    assert np.abs(narrow - got)[np.asarray(pos) >= 4].max() > 1e-2
    with pytest.raises(ValueError, match="value_lanes"):
        attend(q, pool, None, tbl, pos)
    with pytest.raises(ValueError, match="latent plane is"):
        attend(q[..., :64], pool, None, tbl, pos, value_lanes=dv)


@pytest.mark.parametrize("index", [(5,), (2, 3)])
def test_latent_write_covers_the_whole_row(index):
    """``write`` into a latent plane: one row a position, the lanes past
    its values zeros whatever the pool held there."""
    from paddle_tpu.kernels.paged_attention import write

    rng = np.random.default_rng(0)
    pool = jnp.full((7, 4, 128), 9.0)
    n = int(np.prod(index))
    blk = jnp.asarray((1 + np.arange(n)).reshape(index) % 7, jnp.int32)
    off = jnp.asarray(np.arange(n).reshape(index) % 4, jnp.int32)
    rows = jnp.asarray(rng.normal(size=(*index, 40)), jnp.float32)
    out = np.asarray(write(pool, blk, off, rows))
    for at in np.ndindex(*index):
        b, o = int(blk[at]), int(off[at])
        assert np.array_equal(out[b, o, :40], np.asarray(rows[at]))
        assert not out[b, o, 40:].any()
    untouched = np.ones(out.shape[:2], bool)
    untouched[np.asarray(blk).ravel(), np.asarray(off).ravel()] = False
    assert (out[untouched] == 9.0).all()


# -- the grouped matrix product (kernels/grouped_matmul.py) -------------------

# (rows, k, n, rows of each group): empty groups, rows that belong to no
# group (the sum falls short of the rows), a group across row tiles of 32,
# every row in one group, no row in any
_GROUPED_CASES = {
    "uneven_with_an_empty_group": (64, 32, 48, [10, 0, 30, 5]),
    "groups_across_row_tiles": (256, 64, 256, [0, 130, 0, 1, 100]),
    "every_row_in_one_group": (96, 32, 128, [0, 96, 0]),
    "no_row_in_any_group": (48, 32, 128, [0, 0, 0]),
    "rows_not_a_multiple_of_the_tile": (40, 32, 128, [7, 0, 20]),
    # the routed experts of serving.arch.LatentMoE at the published
    # widths: 16 held, 2048 -> 1408 (11 lane tiles: only 128-wide panels
    # divide it) and the transpose-shaped down product, empty groups
    "k2048_n1408_16_groups": (64, 2048, 1408,
                              [0, 9, 0, 0, 17, 1, 0, 0, 0, 20, 0, 3, 0, 0,
                               0, 6]),
    "k1408_n2048_16_groups": (64, 1408, 2048,
                              [5, 0, 0, 30, 0, 0, 0, 0, 11, 0, 0, 0, 0, 0,
                               0, 2]),
    # the routed experts of serving.arch.MambaMoE at the published
    # widths: 2688 -> 1856 (14.5 lane tiles: held transposed, panels of
    # 640 lanes, the last overhanging the matrix; as it lies, a block
    # equal to the array, which no caller has) and back over k = 1856
    "k2688_n1856_not_whole_lane_tiles": (48, 2688, 1856,
                                         [0, 9, 0, 17, 1, 0, 20, 0]),
    "k1856_n2688_not_whole_lane_tiles": (48, 1856, 2688,
                                         [5, 0, 30, 0, 0, 11, 0, 2]),
    "n200_two_panels_one_overhanging": (40, 72, 200, [3, 0, 17, 9, 2]),
}
# the same cases with the matrices held [g, n, k] (``transpose_rhs``):
# what an architecture does with a width that is not whole lane tiles
_GROUPED_TRANSPOSED = ("uneven_with_an_empty_group",
                       "k2688_n1856_not_whole_lane_tiles",
                       "n200_two_panels_one_overhanging")


def _grouped_truth(lhs, rhs, sizes):
    want = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    at = 0
    for g, n in enumerate(sizes):
        want[at:at + n] = (np.asarray(lhs[at:at + n], np.float32)
                           @ np.asarray(rhs[g], np.float32))
        at += n
    return want


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(_GROUPED_CASES))
def test_grouped_matmul_backends_agree_with_the_truth(case, dtype):
    """Both backends against a NumPy loop over the groups, and against
    each other within ``ORACLE_TOL``; the rows of no group come back
    zero, a group with no row costs nothing and changes nothing."""
    from paddle_tpu.kernels import grouped_matmul as gm

    m, k, n, sizes = _GROUPED_CASES[case]
    rng = np.random.default_rng(34)
    lhs = jnp.asarray(rng.normal(size=(m, k)), dtype)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), k, n)) / np.sqrt(k), dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    want = _grouped_truth(lhs, rhs, sizes)
    ref = np.asarray(get_kernel("grouped_matmul", "xla_ref").impl.call(
        lhs, rhs, gs), np.float32)
    mosaic = np.asarray(jax.jit(
        lambda *a: gm.grouped_matmul_pallas(*a, interpret=True, block_m=32))(
        lhs, rhs, gs), np.float32)
    tol = oracle_tol("grouped_matmul", dtype, "fwd") * max(
        np.abs(want).max(), 1.0)
    assert np.abs(ref - want).max() <= tol
    assert np.abs(mosaic - ref).max() <= tol
    assert not mosaic[sum(sizes):].any() and not ref[sum(sizes):].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", _GROUPED_TRANSPOSED)
def test_grouped_matmul_with_the_matrices_held_transposed(case, dtype):
    """``transpose_rhs``: the matrices as ``[g, n, k]``, both backends
    against the NumPy loop and each other within ``ORACLE_TOL``."""
    from paddle_tpu.kernels import grouped_matmul as gm

    m, k, n, sizes = _GROUPED_CASES[case]
    rng = np.random.default_rng(51)
    lhs = jnp.asarray(rng.normal(size=(m, k)), dtype)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), n, k)) / np.sqrt(k), dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    want = _grouped_truth(lhs, jnp.swapaxes(rhs, 1, 2), sizes)
    ref = np.asarray(get_kernel("grouped_matmul", "xla_ref").impl.call(
        lhs, rhs, gs, transpose_rhs=True), np.float32)
    mosaic = np.asarray(jax.jit(lambda *a: gm.grouped_matmul_pallas(
        *a, interpret=True, block_m=32, transpose_rhs=True))(lhs, rhs, gs),
        np.float32)
    assert mosaic.shape == ref.shape == (m, n)
    tol = oracle_tol("grouped_matmul", dtype, "fwd") * max(
        np.abs(want).max(), 1.0)
    assert np.abs(ref - want).max() <= tol
    assert np.abs(mosaic - ref).max() <= tol
    assert not mosaic[sum(sizes):].any() and not ref[sum(sizes):].any()


def test_grouped_matmul_panels_for_a_width_that_is_not_whole_lane_tiles():
    from paddle_tpu.kernels.grouped_matmul import _block_n

    # whole lane tiles: the widest divisor within PANEL_BYTES, as before
    assert _block_n(3072, 3072, 2) == 512 and _block_n(2048, 1408, 2) == 128
    assert _block_n(1856, 2688, 2) == 896
    # 1,856 = 14.5 tiles, held transposed: three panels of 640 cover
    # 1,920, the fewest lanes past the matrix of any panel within
    # PANEL_BYTES; as it lies, a block equal to the array, as before
    assert _block_n(2688, 1856, 2, overhang=True) == 640
    assert _block_n(2688, 1856, 2) == 1856
    assert _block_n(72, 200, 4, overhang=True) == 128
    assert _block_n(72, 200, 4) == 200
    assert _block_n(64, 24, 4, overhang=True) == _block_n(64, 24, 4) == 24
    assert _block_n(3072, 3072, 2, overhang=True) == 512


# -- Mamba-2's step and chunked form (kernels/ssm.py) --------------------------

# (slots, heads, head lanes, groups, state, taps): a row of the state
# that holds several heads of one group, a row a head, one group
_SSM_CASES = {
    "four_heads_a_lane_row": (5, 8, 16, 2, 32, 4),
    "a_head_a_lane_row": (3, 4, 128, 2, 16, 4),
    "two_heads_a_row_one_group": (4, 2, 64, 1, 128, 3),
}


def _ssm_layer(rng, heads, lanes, groups, state, taps, dtype):
    width = heads * lanes + 2 * groups * state
    f = lambda *s: jnp.asarray(rng.normal(size=s), dtype)       # noqa: E731
    return width, dict(
        conv_w=0.4 * f(width, taps), conv_b=0.1 * f(width),
        dt_bias=f(heads) - 3.0, D=f(heads),
        A_log=jnp.asarray(rng.uniform(0.0, 2.7, heads), dtype),
        heads=heads, groups=groups)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(_SSM_CASES))
def test_ssm_step_backends_agree_and_leave_dead_slots_alone(case, dtype):
    """The Mosaic step kernel (interpret mode) against the oracle within
    ``ORACLE_TOL``: outputs, the state in its packed layout and the
    convolution's tails; a slot that is not valid reads zeros and keeps
    both to the bit."""
    from paddle_tpu.kernels import ssm

    slots, heads, lanes, groups, state, taps = _SSM_CASES[case]
    rng = np.random.default_rng(51)
    width, layer = _ssm_layer(rng, heads, lanes, groups, state, taps, dtype)
    s_shape, t_shape = ssm.state_shapes(heads, lanes, groups, state, taps)
    S = jnp.asarray(rng.normal(size=(slots,) + s_shape), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(slots,) + t_shape), dtype)
    xbc = jnp.asarray(rng.normal(size=(slots, width)), dtype)
    dt = jnp.asarray(rng.normal(size=(slots, heads)), dtype)
    valid = jnp.arange(slots) % 3 != 1
    want = ssm.ssm_step_ref(S, tail, xbc, dt, valid, **layer)
    got = jax.jit(lambda *a: ssm.ssm_step_pallas(
        *a, interpret=True, **layer))(S, tail, xbc, dt, valid)
    tol = oracle_tol("ssm", dtype, "fwd")
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1.0)
    dead = ~np.asarray(valid)
    assert not np.asarray(got[0])[dead].any()
    assert np.array_equal(np.asarray(got[1])[dead], np.asarray(S)[dead])
    assert np.array_equal(np.asarray(got[2], np.float32)[dead],
                          np.asarray(tail, np.float32)[dead])
    # the packed layout holds S[h, p, n]: pack and unpack are inverses
    S4 = ssm.unpack(S, heads, groups)
    assert S4.shape == (slots, heads, lanes, state)
    assert np.array_equal(np.asarray(ssm.pack(S4, groups)), np.asarray(S))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows,real,chunk", [(20, 17, 8), (32, 32, 8),
                                             (8, 3, 128), (48, 40, 16)])
def test_ssm_chunked_form_is_the_scan(rows, real, chunk, dtype):
    """The chunked form (``(C B^T . L) X`` within a chunk, the state
    across chunks) against the recurrence row by row (``ssm_scan_ref``)
    within ``ORACLE_TOL``, across chunk boundaries, with a suffix of rows
    that are not real, from a held state and from a fresh one."""
    from paddle_tpu.kernels import ssm

    slots, heads, lanes, groups, state, taps = _SSM_CASES[
        "four_heads_a_lane_row"]
    rng = np.random.default_rng(rows)
    width, layer = _ssm_layer(rng, heads, lanes, groups, state, taps, dtype)
    s_shape, t_shape = ssm.state_shapes(heads, lanes, groups, state, taps)
    S = jnp.asarray(rng.normal(size=(slots,) + s_shape), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(slots,) + t_shape), dtype)
    xbc = jnp.asarray(rng.normal(size=(rows, width)), dtype)
    dt = jnp.asarray(rng.normal(size=(rows, heads)), dtype)
    valid = jnp.arange(rows) < real
    tol = oracle_tol("ssm", dtype, "fwd")
    for fresh in (False, True):
        y, Sn, tn = jax.jit(lambda *a: ssm.ssm_chunk(
            *a, chunk_size=chunk, **layer))(
            S, tail, jnp.int32(2), jnp.bool_(fresh), xbc, dt, valid)
        # the same rows through the scan, letter for letter
        keep = 0.0 if fresh else 1.0
        t0 = tail[2] * jnp.asarray(keep, tail.dtype)
        a = ssm._conv(jnp.concatenate([t0, xbc]), layer["conv_w"],
                      layer["conv_b"])
        x, B, C, delta, A = ssm._parts(
            a, dt, layer["dt_bias"], layer["A_log"], heads, groups,
            heads * lanes)
        per = heads // groups
        want, S_want = ssm.ssm_scan_ref(
            ssm.unpack(S[2], heads, groups) * keep, x[:real],
            jnp.repeat(B, per, axis=1)[:real],
            jnp.repeat(C, per, axis=1)[:real], delta[:real], A,
            layer["D"].astype(jnp.float32))
        want = np.asarray(want).reshape(real, -1)
        assert np.abs(np.asarray(y)[:real] - want).max() <= tol * max(
            np.abs(want).max(), 1.0)
        S_want = np.asarray(ssm.pack(S_want, groups))
        assert np.abs(np.asarray(Sn[2]) - S_want).max() <= tol * max(
            np.abs(S_want).max(), 1.0)
        # the tails end at the last REAL row; other slots are untouched
        rows_seen = np.asarray(jnp.concatenate([t0, xbc]), np.float32)
        assert np.array_equal(np.asarray(tn[2], np.float32),
                              rows_seen[real:real + taps - 1])
        assert np.array_equal(np.asarray(Sn)[:2], np.asarray(S)[:2])


def test_grouped_matmul_work_items_name_the_pairs_that_hold_a_row():
    from paddle_tpu.kernels.grouped_matmul import work_items

    group_of, tile_of, n_items, offsets = work_items(
        jnp.asarray([0, 130, 0, 1, 100], jnp.int32), 256, 32)
    n = int(n_items)
    # group 1 holds rows 0..129: tiles 0..4; group 3 row 130: tile 4;
    # group 4 rows 131..230: tiles 4..7; the empty groups have no item
    assert list(np.asarray(group_of)[:n]) == [1] * 5 + [3] + [4] * 4
    assert list(np.asarray(tile_of)[:n]) == [0, 1, 2, 3, 4, 4, 4, 5, 6, 7]
    assert list(np.asarray(offsets)) == [0, 0, 130, 130, 131, 231]
    assert group_of.shape == (256 // 32 + 5 - 1,)
    assert int(work_items(jnp.zeros(3, jnp.int32), 64, 32)[2]) == 0


def test_grouped_matmul_resolves_to_the_oracle_off_the_tpu():
    from paddle_tpu.kernels.grouped_matmul import grouped_matmul

    assert resolve_name("grouped_matmul") == "xla_ref"
    lhs = jnp.ones((8, 4), jnp.float32)
    out = grouped_matmul(lhs, jnp.ones((2, 4, 3), jnp.float32),
                         jnp.asarray([3, 2], jnp.int32))
    assert np.array_equal(np.asarray(out)[:, 0], [4, 4, 4, 4, 4, 0, 0, 0])


@pytest.mark.parametrize("backend", ["pallas_tpu", "xla_ref"])
def test_bit_exact_run_to_run_within_backend(backend):
    impl = _impl_or_skip("flash_attention", backend)
    q, k, v = _qkv(jnp.float32, 64, t=64)
    jf = jax.jit(lambda q, k, v: impl.call(q, k, v, causal=True,
                                           block_q=32, block_k=32))
    assert bool(jnp.array_equal(jf(q, k, v), jf(q, k, v)))


# -- registry unit suite -----------------------------------------------------

def test_precedence_explicit_arg_beats_env(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_KERNEL_BACKEND", "xla_ref")
    assert resolve_name("flash_attention") == "xla_ref"
    assert resolve_name("flash_attention", "pallas_tpu") == "pallas_tpu"


def test_precedence_per_op_env_beats_global(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_KERNEL_BACKEND", "xla_ref")
    monkeypatch.setenv("PADDLE_TPU_KERNEL_BACKEND_FLASH_ATTENTION",
                       "pallas_tpu")
    assert resolve_name("flash_attention") == "pallas_tpu"
    # the per-op pin does not leak to other op classes
    assert resolve_name("fused_ce") == "xla_ref"


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown kernel backend"):
        resolve_name("flash_attention", "cuda_graphs")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        with forced_backend("notabackend"):
            pass


def _off_tpu_only():
    """The registered-but-unavailable backend of the registry unit
    suite is the Mosaic paged kernel off the TPU."""
    if get_kernel("paged_attention", "pallas_tpu").availability()[0]:
        pytest.skip("pallas_tpu paged attention is available here")


def test_unavailable_backend_raises_with_reason():
    _off_tpu_only()
    with pytest.raises(KernelUnavailable) as ei:
        resolve_name("paged_attention", "pallas_tpu")
    assert ei.value.reason


def test_global_env_fallback_to_auto(monkeypatch):
    # off the TPU the Mosaic paged kernel is unavailable: a fleet-wide
    # pallas_tpu pin must degrade that op to auto instead of crashing
    # serving
    _off_tpu_only()
    monkeypatch.setenv("PADDLE_TPU_KERNEL_BACKEND", "pallas_tpu")
    assert resolve_name("paged_attention") == "xla_ref"


def test_global_env_fallback_counted_once_per_resolution(monkeypatch):
    """The degrade-to-auto path's accounting contract (ISSUE 14
    satellite): a global env pin an op cannot serve increments
    ``kernels.env_fallbacks`` EXACTLY once per resolution — no double
    count inside one resolve, no missed count across repeats — while a
    servable pin and a strict (raising) explicit request increment
    nothing."""
    from paddle_tpu.observability import get_registry

    reg = get_registry()

    def count():
        return int(reg.value("kernels.env_fallbacks") or 0)

    _off_tpu_only()
    monkeypatch.setenv("PADDLE_TPU_KERNEL_BACKEND", "pallas_tpu")
    c0 = count()
    assert resolve_name("paged_attention") == "xla_ref"
    assert count() == c0 + 1
    assert resolve_name("paged_attention") == "xla_ref"
    assert count() == c0 + 2
    # a pin the op CAN serve resolves directly: no fallback counted
    monkeypatch.setenv("PADDLE_TPU_KERNEL_BACKEND", "xla_ref")
    assert resolve_name("paged_attention") == "xla_ref"
    assert count() == c0 + 2
    # strict sources raise instead of degrading: still no count
    monkeypatch.delenv("PADDLE_TPU_KERNEL_BACKEND")
    with pytest.raises(KernelUnavailable):
        resolve_name("paged_attention", "pallas_tpu")
    assert count() == c0 + 2


def test_two_backends_ten_op_classes_and_any_platform_is_served():
    """What the registry holds since the GPU lowerings and the gather op
    class went and the grouped matrix product, retention, a wide window's
    chain walk, Mamba-2's recurrence, the gated delta rule, a learned
    indexer's scores and the attention of the rows it selects came: two
    backends, ten op classes (the last two in the oracle's backend only),
    an auto order
    for the TPU and the CPU; a platform with no order of its own is
    served by the oracle for every op class."""
    one_backend = {"index_scores", "sparse_latent_attention"}
    assert kernels.BACKENDS == ("pallas_tpu", "xla_ref")
    assert sorted(kernels.registered_op_classes()) == sorted([
        "chain_attention", "delta_rule", "flash_attention", "fused_ce",
        "grouped_matmul", "paged_attention", "retention", "ssm",
        *one_backend])
    assert set(kernels.AUTO_ORDER) == {"tpu", "cpu"}
    for op in kernels.registered_op_classes():
        assert {b for b, _, _ in available_backends(op)} == (
            {"xla_ref"} if op in one_backend else set(kernels.BACKENDS))
        assert resolve_name(op, platform="gpu") == "xla_ref"
        assert resolve_name(op, platform="tpu") in kernels.BACKENDS
        for dtype in ("float32", "bfloat16"):
            if (op, dtype) in kernels.ORACLE_TOL:
                assert kernels.oracle_tol(op, dtype) > 0
    assert all((op, "float32") in kernels.ORACLE_TOL for op in one_backend)
    with pytest.raises(ValueError, match="unknown kernel backend"):
        resolve_name("flash_attention", "triton")


def test_forced_backend_scopes_and_restores():
    before = resolve_name("fused_ce")
    with forced_backend("xla_ref"):
        assert resolve_name("fused_ce") == "xla_ref"
    with forced_backend("xla_ref", op_class="fused_ce"):
        assert resolve_name("fused_ce") == "xla_ref"
        # op-scoped force does not leak across op classes
        assert resolve_name("flash_attention") == resolve_name(
            "flash_attention", None)
    assert resolve_name("fused_ce") == before


def test_selected_backends_recorded_per_compile():
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        from paddle_tpu.models import transformer

        outs = transformer.build(vocab_size=64, n_layer=1, n_head=2,
                                 d_model=32, max_len=16,
                                 dropout_rate=0.0, dtype="float32",
                                 fused_head=True)
    scope = pt.core.scope.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        toks = np.zeros((2, 16), np.int64)
        exe.run(main, feed={"tokens": toks, "labels": toks},
                fetch_list=[outs["avg_cost"]], scope=scope)
        kb = (exe.last_step_cost or {}).get("kernel_backends")
        assert kb and kb.get("flash_attention") and kb.get("fused_ce")
        att = exe.last_attribution or {}
        assert f"|kb={kb['flash_attention']}" in att.get("workload", "")
    finally:
        pt.core.scope._scope_stack.pop()


@pytest.mark.parametrize("policy", [None, "selective", "offload",
                                    "compact", "full"])
def test_xla_ref_trainer_zero_pallas(monkeypatch, policy):
    """The acceptance bar at toy scale: under every memory_optimize
    policy an env-routed xla_ref GPT training step resolves both kernel
    op classes to xla_ref and traces with zero pallas calls."""
    from paddle_tpu.analysis.jaxpr_tools import walk_report
    from paddle_tpu.models import transformer

    monkeypatch.setenv("PADDLE_TPU_KERNEL_BACKEND", "xla_ref")
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        outs = transformer.build(vocab_size=64, n_layer=3, n_head=2,
                                 d_model=32, max_len=16,
                                 dropout_rate=0.0, dtype="float32",
                                 fused_head=True)
        if policy:
            pt.memory_optimize(main, policy=policy)
    scope = pt.core.scope.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        toks = np.zeros((2, 16), np.int64)
        loss = exe.run(main, feed={"tokens": toks, "labels": toks},
                       fetch_list=[outs["avg_cost"]], scope=scope)[0]
        assert np.isfinite(np.asarray(loss)).all()
        kb = exe.last_step_cost["kernel_backends"]
        assert kb["flash_attention"] == kb["fused_ce"] == "xla_ref"
        state_names = tuple(sorted(
            v.name for v in main.persistable_vars()
            if scope.find_var(v.name) is not None))
        step, _ = exe.lower(main, ["labels", "tokens"],
                            [outs["avg_cost"].name], state_names)
        state = {n: scope.get(n) for n in state_names}
        state[pt.core.scope.RNG_VAR] = scope.get(pt.core.scope.RNG_VAR)
        rep = walk_report(jax.make_jaxpr(step)(state, toks, toks))
        assert rep["pallas_total"] == 0
    finally:
        pt.core.scope._scope_stack.pop()


def test_timed_run_lint_fires_on_interpret_kernels():
    if jax.default_backend() == "tpu":
        pytest.skip("interpret planting needs a non-TPU host")
    from paddle_tpu.models import transformer

    def compile_under(env_backend):
        pt.core.unique_name.reset()
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            outs = transformer.build(
                vocab_size=64, n_layer=1, n_head=2, d_model=32,
                max_len=16, dropout_rate=0.0, dtype="float32",
                fused_head=True)
        scope = pt.core.scope.Scope()
        pt.core.scope._scope_stack.append(scope)
        try:
            if env_backend:
                os.environ["PADDLE_TPU_KERNEL_BACKEND"] = env_backend
            exe = pt.Executor()
            with kernels.timed_run():
                exe.run(startup, scope=scope)
                toks = np.zeros((2, 16), np.int64)
                exe.run(main, feed={"tokens": toks, "labels": toks},
                        fetch_list=[outs["avg_cost"]], scope=scope)
            return exe.last_step_cost or {}
        finally:
            os.environ.pop("PADDLE_TPU_KERNEL_BACKEND", None)
            pt.core.scope._scope_stack.pop()

    planted = compile_under(None)
    assert planted.get("interpret_in_timed_run") is True
    assert "jaxpr.kernel-backend" in (planted.get("lint_checks") or [])
    clean = compile_under("xla_ref")
    assert not clean.get("interpret_in_timed_run")
    assert "jaxpr.kernel-backend" not in (clean.get("lint_checks") or [])


# -- tuner integration -------------------------------------------------------

def test_attention_candidates_backend_dimension():
    from paddle_tpu.tune.space import attention_candidates, prune_static

    plain = attention_candidates(256, 64, 2)
    assert all("backend" not in c for c in plain)
    cands = attention_candidates(256, 64, 2,
                                 backends=("pallas_tpu", "xla_ref"))
    by_backend = {}
    for c in cands:
        by_backend.setdefault(c.get("backend"), []).append(c)
    assert set(by_backend) == {"pallas_tpu", "xla_ref"}
    # geometry-free backend contributes ONE candidate, not a cross
    assert len(by_backend["xla_ref"]) == 1
    # pruning keeps the xla_ref candidate (VMEM/roofline models are
    # Pallas-schedule models) while still vmem/roofline-pruning pallas
    surv, _pruned = prune_static(256, 64, 2, cands)
    assert any(c.get("backend") == "xla_ref" for c in surv)


def test_workload_key_backend_token():
    from paddle_tpu.tune.space import WorkloadKey

    plain = WorkloadKey("flash", 256, 64, 2, "bfloat16", "cpu",
                        remat="-")
    assert "kb=" not in plain.s
    keyed = WorkloadKey("flash", 256, 64, 2, "bfloat16", "cpu",
                        remat="-", backend="xla_ref")
    assert keyed.s.endswith("|kb=xla_ref")
    assert keyed.s.startswith(plain.s)


def test_tuned_winner_backend_reaches_flash_op():
    """A tuned config that persisted a kernel choice re-resolves on the
    hot path: multi_head_attention threads it into the flash op's
    ``backend`` attr."""
    from paddle_tpu import layers
    from paddle_tpu.tune import forced_attention_config

    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        with forced_attention_config({"block_q": 128, "block_k": 128,
                                      "backend": "xla_ref"}):
            x = layers.data("x", shape=[2, 256, 64], dtype="float32")
            layers.multi_head_attention(x, x, x, d_model=64, n_head=1,
                                        causal=True)
    ops = [op for op in main.global_block().ops
           if op.type.startswith("flash_attention")]
    assert ops, "no flash op built"
    assert ops[0].attrs.get("backend") == "xla_ref"
    assert ops[0].attrs.get("block_q") == 128


def test_cache_fingerprint_covers_registry_surface(monkeypatch):
    from paddle_tpu.tune import cache as tcache

    base = tcache.geometry_fingerprint()
    # reordering a platform's auto preference changes what a cached
    # config resolves to -> the fingerprint must move
    monkeypatch.setitem(kernels.AUTO_ORDER, "cpu",
                        ("xla_ref", "pallas_tpu"))
    assert tcache.geometry_fingerprint() != base


def test_tune_search_measures_backend_candidate(tmp_path, monkeypatch):
    """Live regression for the backend-forced measurement window: a
    search over a backend-carrying candidate must build, compile,
    measure and persist the winner's kernel choice (the forced context
    is single-use — entering it per phase used to crash the search)."""
    from paddle_tpu.tune import reset_cache, tune_gpt_step

    monkeypatch.setenv("PADDLE_TPU_TUNE_CACHE",
                       str(tmp_path / "tuned.json"))
    monkeypatch.setenv("PADDLE_TPU_TUNE", "search")
    reset_cache()
    try:
        rep = tune_gpt_step(
            seq_len=32, n_layer=1, d_model=32, n_head=2, vocab=61,
            batch=4, dtype="float32", steps=1, warmup=0, repeats=1,
            block_caps=(32,), policies=("none",), accums=(1,),
            backends=("xla_ref", "pallas_tpu"), max_measure=3,
            mode="search", force=True)
        assert rep["source"] == "search", rep
        measured = [m for m in rep["measured"]
                    if m.get("verdict") == "measured"]
        # every candidate's record carries the backend that ran
        assert {m.get("backend") for m in measured} == {"xla_ref",
                                                        "pallas_tpu"}
        assert rep["entry"]["config"].get("backend") in ("xla_ref",
                                                         "pallas_tpu")
    finally:
        reset_cache()


def test_truncate_survivors_keeps_every_backend():
    from paddle_tpu.tune.search import _truncate_survivors

    survivors = ([{"block_q": 64, "backend": "pallas_tpu", "roofline": 1.0}]
                 * 5 + [{"block_q": 64, "backend": "xla_ref"}])
    report = {}
    keep = _truncate_survivors(list(survivors), 3, report)
    assert any(c.get("backend") == "xla_ref" for c in keep)
    assert report["truncated_to"] == len(keep) == 4
    # no truncation -> untouched, no report key
    report2 = {}
    same = _truncate_survivors(list(survivors), 10, report2)
    assert len(same) == 6 and "truncated_to" not in report2
