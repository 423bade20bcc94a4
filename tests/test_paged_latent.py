"""The latent plane of the paged kernels (``kernels/paged_attention.py``,
``pool_v=None``): every backend against the dense truth, the choice of
the dense spelling from eight rows up, and the write of a whole row."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import oracle_tol


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
