"""K/V groups and a lower bound on the paged kernels
(``kernels/paged_attention.py``, PR 32): query head i reads K/V head
``i // group``; with a window a query sees itself and the ``window - 1``
keys before it.  Every backend against the dense truth."""

import jax.numpy as jnp
import numpy as np
import pytest

from kernel_cases import LIVE_FORMS, paged_backends, rel_err, windowed_truth
from paddle_tpu.kernels import oracle_tol


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
    want = windowed_truth(q, pk, pv, table, pos, group, window, 0.3)
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


# float32 with 2 K/V heads takes the Mosaic kernel's loop over the chain,
# bfloat16 with 6 the grid form
@pytest.mark.parametrize("dtype,hk", LIVE_FORMS)
@pytest.mark.parametrize("w", [1, 3])
@pytest.mark.parametrize("backend", ["xla_ref", "xla_ref_block_step_1",
                                     "pallas_tpu_interpret"])
@pytest.mark.parametrize("case", list(_GROUP_WINDOW_CASES))
def test_paged_groups_and_windows_match_the_dense_truth(case, backend, w,
                                                        dtype, hk):
    q, pk, pv, tbl, pos, how, want, live, _ = _group_window_case(
        case, dtype, hk, w)
    got = paged_backends()[backend](q, pk, pv, tbl, pos, **how)
    assert got.dtype == q.dtype and got.shape == q.shape
    assert rel_err(got[live], jnp.asarray(want)[live]) <= oracle_tol(
        "paged_attention", dtype, "fwd")
    # float32 out of a bf16 pool, for a caller that combines contexts
    wide = paged_backends()[backend](q, pk, pv, tbl, pos,
                                     out_dtype=jnp.float32, **how)
    assert wide.dtype == jnp.float32
    assert rel_err(wide[live], jnp.asarray(want)[live]) <= oracle_tol(
        "paged_attention", dtype, "fwd")
    if backend == "pallas_tpu_interpret":
        assert not np.asarray(got, np.float32)[~live].any()


@pytest.mark.parametrize("dtype,hk", LIVE_FORMS)
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
