"""The head-major K/V plane of ``kernels/block_sparse_attention.py`` (PR
63): a block is one ``[B, D]`` slab a K/V head, ``[blocks, H_kv, B, D]``,
and a K/V head's walk fetches its own slabs alone.  The walk (a decode
step, and a prefill piece whose rows cross the dense length, so that both
sides of the switch run in one call) against the dense float32 truth and
against the walk of the token-major layout it replaced (``[blocks, B,
pool_rows, D]``, a K/V head's call carrying zeros in the other heads'
query rows) on the same K and V; run to run to the bit; NaN in every slab
the walk has no business with; rows that attend nothing; the write and the
compressed rows through the same view.  The Mosaic kernel runs in
interpret mode (``paged_slab_attention``: the latent kernel's loop with a
buffer for V)."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.kernels import block_sparse_attention as bsa
from paddle_tpu.kernels import oracle_tol
from paddle_tpu.kernels import paged_attention as pa

HK, GROUP, D, B, NB = 2, 2, 16, 8, 12
H = HK * GROUP
SPARSE = dict(stride=2, block=8, topk=2, init_blocks=1, window_blocks=2)
DENSE_LEN = 48
TOL = 2e-5          # tests/test_sparse_lightning.py's, float32

# name -> positions [S, W]: a decode step of three slots, one dead, the
# others past the dense length; a piece of one slot whose first eight rows
# attend their chain whole and whose last eight select
CASES = {
    "decode": np.array([[93], [70], [-1]], np.int32),
    "piece_across_dense_len": (40 + np.arange(16, dtype=np.int32))[None],
}


def _case(name, dtype="float32", seed=0):
    """``(q, pool_k, pool_v, pool_c, table, pos)``: head-major pools in
    ``dtype``, every slot a chain of its own, the trash block's values
    large (a masked key must weigh zero, not little)."""
    pos = CASES[name]
    S, W = pos.shape
    rng = np.random.default_rng(seed)
    blocks = 1 + S * NB
    pool_k, pool_v = (rng.normal(size=(blocks, HK, B, D)).astype(np.float32)
                      for _ in range(2))
    pool_k[0] = pool_v[0] = 1e3
    pool_c = rng.normal(size=(blocks, B // SPARSE["stride"], HK * D)).astype(
        np.float32)
    q = rng.normal(size=(S, W, H, D)).astype(np.float32) * 2.0
    table = 1 + np.arange(S * NB, dtype=np.int32).reshape(S, NB)
    dt = jnp.dtype(dtype)
    return (jnp.asarray(q, dt), jnp.asarray(pool_k, dt),
            jnp.asarray(pool_v, dt), jnp.asarray(pool_c, dt),
            jnp.asarray(table), jnp.asarray(pos))


def _selection(q, pool_c, table, pos):
    """The blocks the rows past the dense length select, by the two steps
    this layout leaves as they were."""
    at = jnp.where(pos >= DENSE_LEN, pos, -1)
    scores = bsa.block_scores(q, pool_c, table, at, group=GROUP,
                              stride=SPARSE["stride"], block=SPARSE["block"])
    return np.asarray(bsa.select_blocks(
        scores, at, block=SPARSE["block"], topk=SPARSE["topk"],
        init_blocks=SPARSE["init_blocks"],
        window_blocks=SPARSE["window_blocks"]))


def _attend(q, pool_k, pool_v, pool_c, table, pos):
    """Both sides of the switch as ``serving/batched_decode._Cache
    .block_sparse`` joins them."""
    how = dict(group=GROUP, scale=D ** -0.5, out_dtype=jnp.float32)
    dense = (pos >= 0) & (pos < DENSE_LEN)
    sparse = pos >= DENSE_LEN
    ctx = bsa.dense_attention(q, pool_k, pool_v, table,
                              jnp.where(dense, pos, -1),
                              entries=-(-DENSE_LEN // B), **how)
    return jnp.where(sparse[..., None, None], bsa.attend(
        q, pool_k, pool_v, pool_c, table, jnp.where(sparse, pos, -1),
        **SPARSE, **how), ctx)


def _truth(q, pool_k, pool_v, table, pos, sel):
    """The masked softmax in float64, a (row, K/V head) at a time."""
    q, pool_k, pool_v = (np.asarray(a, np.float64)
                         for a in (q, pool_k, pool_v))
    table, pos = np.asarray(table), np.asarray(pos)
    out = np.zeros(q.shape)
    for s, w in np.ndindex(*pos.shape):
        t = int(pos[s, w])
        for j in range(HK if t >= 0 else 0):
            at = np.arange(t + 1)
            if t >= DENSE_LEN:
                at = (sel[s, w, j][:, None] * SPARSE["block"]
                      + np.arange(SPARSE["block"])).reshape(-1)
                at = at[at <= t]
            k = pool_k[table[s, at // B], j, at % B]
            v = pool_v[table[s, at // B], j, at % B]
            sc = q[s, w, j * GROUP:(j + 1) * GROUP] @ k.T / np.sqrt(D)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            out[s, w, j * GROUP:(j + 1) * GROUP] = (
                p / p.sum(-1, keepdims=True)) @ v
    return out


def _token_major(q, pool_k, pool_v, table, pos, sel):
    """The walk this layout replaced, on the same K and V: ``[blocks, B,
    pool_rows, D]`` (zeros in the rows ``pool_rows`` adds), a dense row
    through ``paged_attention.attend`` whole, a selecting row one call a
    K/V head with zeros in the other heads' query rows."""
    S, W = pos.shape
    rows = pa.pool_rows(HK, pool_k.dtype)
    pk, pv = (jnp.pad(jnp.swapaxes(a, 1, 2),
                      ((0, 0), (0, 0), (0, rows - HK), (0, 0)))
              for a in (pool_k, pool_v))
    how = dict(group=GROUP, scale=D ** -0.5, out_dtype=jnp.float32)
    dense = (pos >= 0) & (pos < DENSE_LEN)
    sparse = pos >= DENSE_LEN
    ctx = pa.attend(q, pk, pv, table, jnp.where(dense, pos, -1), **how)
    n, block = sel.shape[-1], SPARSE["block"]
    ids = jnp.take_along_axis(table[:, None, None, :],
                              jnp.maximum(jnp.asarray(sel), 0), axis=-1)
    ids = jnp.where(sparse[:, :, None, None], ids, 0)
    at = jnp.where(sparse, (n - 1) * block + pos % block, -1)
    mine = jnp.eye(HK, dtype=q.dtype)[None, None, :, :, None, None]
    qj = (q.reshape(S, W, 1, HK, GROUP, D) * mine).reshape(
        S * W * HK, 1, H, D)
    walked = pa.attend(qj, pk, pv, ids.reshape(S * W * HK, -1),
                       jnp.repeat(at.reshape(S * W), HK)[:, None], **how)
    own = jnp.arange(HK)
    walked = walked.reshape(S, W, HK, HK, GROUP, D)[:, :, own, own]
    return jnp.where(sparse[..., None, None], walked.reshape(S, W, H, D),
                     jnp.where(dense[..., None, None], ctx, 0))


def _through_mosaic(monkeypatch):
    """``paged_attention.attend``'s slab call through the Mosaic kernel in
    interpret mode (on the CPU the registry resolves the scan)."""
    def attend(q, pool_k, pool_v, table, pos, **how):
        assert pool_k.ndim == 3 and q.shape[1] == 1
        return pa.paged_attention_pallas(q, pool_k, pool_v, table, pos,
                                         interpret=True, **how)
    monkeypatch.setattr(bsa._paged, "attend", attend)


@pytest.mark.parametrize("backend", ["xla_ref", "mosaic_interpret"])
@pytest.mark.parametrize("case", list(CASES))
def test_head_major_walk_matches_the_truth_and_the_token_major_walk(
        case, backend, monkeypatch):
    args = _case(case)
    q, pool_k, pool_v, pool_c, table, pos = args
    sel = _selection(q, pool_c, table, pos)
    want = _truth(q, pool_k, pool_v, table, pos, sel)
    old = np.asarray(_token_major(q, pool_k, pool_v, table, pos, sel))
    if backend == "mosaic_interpret":
        _through_mosaic(monkeypatch)
    got = np.asarray(_attend(*args))
    live = np.asarray(pos) >= 0
    tol = max(TOL, oracle_tol("paged_attention", "float32", "fwd"))
    scale = np.abs(want).max()
    assert np.abs(got - want)[live].max() <= tol * scale
    assert np.abs(got - old)[live].max() <= tol * scale
    assert not got[~live].any()
    # the K/V heads of a row select blocks of their own here
    sparse = np.asarray(pos) >= DENSE_LEN
    assert (sel[sparse][:, 0] != sel[sparse][:, 1]).any()


@pytest.mark.parametrize("case", list(CASES))
def test_head_major_walk_is_bit_exact_run_to_run(case, monkeypatch):
    args = _case(case, "bfloat16")
    _through_mosaic(monkeypatch)
    first, again = _attend(*args), _attend(*args)
    assert bool(jnp.all(jnp.isfinite(first)))
    assert bool(jnp.array_equal(first, again))


@pytest.mark.parametrize("backend", ["xla_ref", "mosaic_interpret"])
@pytest.mark.parametrize("case", list(CASES))
def test_walk_fetches_its_own_heads_slabs_and_no_other(case, backend,
                                                       monkeypatch):
    """NaN in every slab no table names for ITS head (the other head's
    slab of a block only one head selected, every unselected block, the
    blocks past a dense row's chain) changes no bit of the result: a
    block's other head is never fetched, let alone scored."""
    args = _case(case, "bfloat16")
    q, pool_k, pool_v, pool_c, table, pos = args
    sel = _selection(q, pool_c, table, pos)
    tbl, at = np.asarray(table), np.asarray(pos)
    wanted = np.zeros(pool_k.shape[:2], bool)            # [blocks, H_kv]
    wanted[0] = True                    # the trash block: a dead row's table
    for s, w in np.ndindex(*at.shape):
        if 0 <= at[s, w] < DENSE_LEN:
            # a dense row's groups of entries may run past its position,
            # over the chain's later blocks (finite, weighed zero)
            wanted[tbl[s, :-(-DENSE_LEN // B)]] = True
        elif at[s, w] >= DENSE_LEN:
            for j in range(HK):
                wanted[tbl[s, sel[s, w, j]], j] = True
    assert not wanted.all()
    if case == "decode":        # a block ONE head selected: the other's NaN
        assert (wanted[1:].sum(1) == 1).any()
    if backend == "mosaic_interpret":
        _through_mosaic(monkeypatch)
    base = _attend(*args)
    poison = jnp.asarray(~wanted)[:, :, None, None]
    again = _attend(q, jnp.where(poison, jnp.nan, pool_k),
                    jnp.where(poison, jnp.nan, pool_v), pool_c, table, pos)
    assert bool(jnp.all(jnp.isfinite(again)))
    assert bool(jnp.array_equal(base, again))


@pytest.mark.parametrize("backend", ["xla_ref", "mosaic_interpret"])
def test_rows_at_a_negative_position_read_zeros(backend, monkeypatch):
    """Whatever the pool holds (NaN in every slab), on either side of the
    switch."""
    q, pool_k, pool_v, pool_c, table, pos = _case("decode", "bfloat16")
    if backend == "mosaic_interpret":
        _through_mosaic(monkeypatch)
    got = _attend(q, jnp.full_like(pool_k, jnp.nan),
                  jnp.full_like(pool_v, jnp.nan), pool_c, table,
                  jnp.full_like(pos, -1))
    assert got.shape == q.shape and not np.asarray(got).any()


@pytest.mark.parametrize("index", [(3,), (1, 5)])
def test_write_puts_a_positions_heads_at_their_slabs_and_nothing_else(index):
    """``rows [.., H_kv, D]`` land at ``(blk, :, off, :)``: every head's
    row in its own slab, no other value of the pool touched (no row of
    zeros beside the heads: the pool has none)."""
    rng = np.random.default_rng(1)
    n = int(np.prod(index))
    blk = jnp.asarray(rng.permutation(np.arange(1, 9))[:n].reshape(index))
    off = jnp.asarray(rng.integers(0, B, n).reshape(index))
    rows = jnp.asarray(rng.normal(size=(*index, HK, D)), jnp.bfloat16)
    pool = bsa.write(jnp.zeros((9, HK, B, D), jnp.bfloat16), blk, off, rows)
    assert pool.shape == (9, HK, B, D)
    assert bool(jnp.array_equal(pool[blk, :, off], rows))
    assert int(jnp.count_nonzero(pool)) == int(jnp.count_nonzero(rows))


def test_compressed_rows_read_the_head_major_plane():
    """Row ``c`` is the float32 mean of the keys at ``stride (c - 1) ..
    stride (c + 1) - 1`` as the pool holds them, the heads side by side."""
    _, pool_k, _, _, table, _ = _case("decode")
    stride = SPARSE["stride"]
    rows = jnp.asarray([[1, 2, 7, 30], [4, 5, 6, 47], [1, 1, 1, 1]])
    got = np.asarray(bsa.compressed_rows(pool_k, table, rows, stride))
    keys, tbl = np.asarray(pool_k), np.asarray(table)
    for s, i in np.ndindex(*rows.shape):
        at = (int(rows[s, i]) - 1) * stride + np.arange(2 * stride)
        want = keys[tbl[s, at // B], :, at % B].mean(0).reshape(-1)
        np.testing.assert_allclose(got[s, i], want, rtol=1e-6, atol=1e-6)
