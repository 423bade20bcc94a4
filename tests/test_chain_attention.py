"""The chain walk of a wide prefill window (``kernels/chain_attention.py``)
in interpret mode against the block-scan oracle: windows, K/V groups, a
sink, a K array of more lanes than the V array, a plane of fewer heads
than its pool has rows, pieces that start a prompt, continue behind a
long context or straddle the window, bucket padding, dead rows, and a
window plane's table whose entries under the bound name a poisoned trash
block.  Tiles far smaller than the chip's, so that every case walks
several query and key tiles, skipped, whole and masked."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.kernels import oracle_tol, resolve_name  # noqa: E402
from paddle_tpu.kernels import paged_attention as pa  # noqa: E402
from paddle_tpu.kernels.chain_attention import (  # noqa: E402
    KEY_TILE, ROW_TILE, chain_attention_pallas)

B, NB = 4, 16                       # a chain of 64 positions


def _case(seed, W, group, hk, rows, dk, dks, dv, window, start, S=2,
          dtype=np.float32, poison=False):
    """Pools of ``rows`` rows of which ``hk`` hold K/V, keys of ``dk``
    lanes stored at ``dks``; slot ``s``'s piece of ``W`` rows starts at
    ``start[s]``.  A window plane's entries under its first row's bound
    name the trash block, which ``poison`` fills with NaN."""
    rng = np.random.default_rng(seed)
    blocks = 1 + S * NB
    pk = np.zeros((blocks, B, rows, dks), np.float32)
    pk[:, :, :hk, :dk] = rng.normal(size=(blocks, B, hk, dk))
    pv = np.zeros((blocks, B, rows, dv), np.float32)
    pv[:, :, :hk] = rng.normal(size=(blocks, B, hk, dv))
    table = 1 + np.arange(S * NB, dtype=np.int32).reshape(S, NB)
    pos = (np.asarray(start)[:, None] + np.arange(W)[None]).astype(np.int32)
    if window is not None:
        for s in range(S):
            table[s, :max(pos[s, 0] - window + 1, 0) // B] = 0
        if poison:
            pk[0] = pv[0] = np.nan
    q = np.zeros((S, W, hk * group, dks), np.float32)
    q[..., :dk] = rng.normal(size=(S, W, hk * group, dk))
    sink = (rng.normal(size=(hk * group,)) + 1.0).astype(np.float32)
    cast = lambda a: jnp.asarray(a, dtype)
    return (cast(q), cast(pk), cast(pv), jnp.asarray(table),
            jnp.asarray(pos), jnp.asarray(sink))


def _both(args, tiles=(16, 8), **how):
    q, pk, pv, table, pos = args
    got = chain_attention_pallas(q, pk, pv, table, pos, interpret=True,
                                 row_tile=tiles[0], key_tile=tiles[1],
                                 out_dtype=jnp.float32, **how)
    # a poisoned trash block is the walk's to avoid, not the oracle's
    clean = [jnp.nan_to_num(a) for a in (pk, pv)]
    want = np.array(pa.paged_attention_ref(
        q, *clean, table, pos, block_step=1, out_dtype=jnp.float32, **how))
    # a row with no key (dead, or past the chain's end under a window):
    # the walk returns zeros, as the decode kernel does; the oracle
    # weighs every masked key alike there unless a sink holds the mass
    at, low = np.asarray(pos), 0
    if how.get("window") is not None:
        low = np.maximum(at - how["window"] + 1, 0)
    no_key = (at < 0) | (low > np.minimum(at, NB * B - 1))
    got = np.asarray(got)
    assert not got[no_key].any()
    want[no_key] = 0.0
    return got, want


def _rel(got, want):
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# window: none | narrower than the piece (the cell's 128 of 512, scaled)
# | wider than the piece; group 1 | 6 | 16; sink | none; key lanes over
# value lanes (256 over 128, scaled) | equal; 4 heads in 8 pool rows
GEOMETRIES = {
    "full_group_16_4_heads_in_8_rows": dict(
        W=16, group=16, hk=4, rows=8, dk=6, dks=8, dv=4, window=None,
        sink=False),
    "full_group_1_equal_lanes": dict(
        W=16, group=1, hk=2, rows=2, dk=8, dks=8, dv=8, window=None,
        sink=False),
    "full_group_6_sink": dict(
        W=8, group=6, hk=2, rows=2, dk=8, dks=8, dv=8, window=None,
        sink=True),
    "window_under_the_piece_group_8_sink": dict(
        W=16, group=8, hk=2, rows=8, dk=6, dks=8, dv=4, window=6,
        sink=True),
    "window_under_the_piece_no_sink": dict(
        W=16, group=2, hk=2, rows=2, dk=8, dks=8, dv=8, window=6,
        sink=False),
    "window_over_the_piece_group_6_sink": dict(
        W=8, group=6, hk=2, rows=2, dk=6, dks=8, dv=4, window=20,
        sink=True),
}
# where the two slots' pieces start: a prompt's first piece beside one
# behind a long context | both straddling the window's first block |
# the chain's end (positions past it are bucket padding)
STARTS = {"starts_a_prompt_and_long_context": (0, 40),
          "straddles": (5, 18),
          "runs_past_the_chain": (44, 54)}


@pytest.mark.parametrize("starts", list(STARTS))
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_walk_matches_the_oracle(geometry, starts):
    g = dict(GEOMETRIES[geometry])
    with_sink = g.pop("sink")
    *args, sink = _case(len(geometry) + len(starts), start=STARTS[starts],
                        poison=True, **g)
    got, want = _both(args, group=g["group"], window=g["window"],
                      scale=g["dk"] ** -0.5, sink=sink if with_sink else None)
    assert got.shape == (2, g["W"], g["hk"] * g["group"], g["dv"])
    assert np.isfinite(got).all()
    assert _rel(got, want) <= oracle_tol("chain_attention", "float32")


@pytest.mark.parametrize("tiles", [(8, 4), (32, 16), (64, 64),
                                   (ROW_TILE, KEY_TILE)])
@pytest.mark.parametrize("window", [None, 6])
def test_any_tiling_walks_the_same_chain(window, tiles):
    """Tiles under a block, over the window, and the chip's own (one
    tile of everything at this size)."""
    *args, sink = _case(3, W=16, group=4, hk=2, rows=4, dk=6, dks=8, dv=4,
                        window=window, start=(9, 37), poison=True)
    got, want = _both(args, tiles=tiles, group=4, window=window,
                      scale=6 ** -0.5, sink=sink)
    assert _rel(got, want) <= oracle_tol("chain_attention", "float32")


@pytest.mark.parametrize("window,with_sink", [(None, False), (None, True),
                                              (6, True)])
def test_rows_with_no_key_return_zeros(window, with_sink):
    """A dead slot's rows (``pos = -1``) beside a live slot's, and dead
    rows inside a live slot's tile: zeros, as the decode kernel's."""
    q, pk, pv, table, pos, sink = _case(11, W=16, group=2, hk=2, rows=2,
                                        dk=8, dks=8, dv=8, window=window,
                                        start=(0, 30))
    pos = np.asarray(pos).copy()
    pos[0] = -1
    pos[1, 3] = -1
    got, want = _both((q, pk, pv, table, jnp.asarray(pos)), group=2,
                      window=window, sink=sink if with_sink else None)
    assert not got[0].any() and not got[1, 3].any()
    assert got[1, 4].any()
    assert _rel(got, want) <= oracle_tol("chain_attention", "float32")


def test_bfloat16_operands_within_the_documented_tolerance():
    """The serving cells' dtype: scores out of bfloat16 operands, ``p``
    rounded once to bfloat16 for the one pass that weighs the values."""
    *args, sink = _case(5, W=16, group=8, hk=2, rows=8, dk=6, dks=8, dv=4,
                        window=6, start=(3, 41), dtype=jnp.bfloat16)
    got, want = _both(args, group=8, window=6, scale=6 ** -0.5, sink=sink)
    assert _rel(got, want) <= oracle_tol("chain_attention", "bfloat16")
    *args, sink = _case(5, W=16, group=8, hk=2, rows=8, dk=6, dks=8, dv=4,
                        window=None, start=(3, 41), dtype=jnp.bfloat16)
    got, want = _both(args, group=8, window=None, scale=6 ** -0.5)
    assert _rel(got, want) <= oracle_tol("chain_attention", "bfloat16")


def test_the_walk_reads_nothing_past_the_context_or_under_the_window():
    """Blocks the masks rule out for every row are poisoned one at a
    time in the POOL (not the table): the context's last block and the
    window's first still matter, the ones beyond them do not."""
    q, pk, pv, table, pos, sink = _case(2, W=8, group=2, hk=2, rows=2, dk=8,
                                        dks=8, dv=8, window=10, start=(20,),
                                        S=1)
    table = 1 + np.arange(NB, dtype=np.int32)[None]       # nothing released
    args = (q, pk, pv, jnp.asarray(table), pos)
    base, _ = _both(args, tiles=(8, 4), group=2, window=10, sink=sink)
    # rows 20..27 with a window of 10: keys 11..27, entries 2..6
    for entry, matters in ((1, False), (2, True), (6, True), (7, False),
                           (NB - 1, False)):
        poisoned = np.asarray(pv).copy()
        poisoned[table[0, entry]] = 1e4
        got, _ = _both((q, pk, jnp.asarray(poisoned), jnp.asarray(table),
                        pos), tiles=(8, 4), group=2, window=10, sink=sink)
        assert (np.abs(got - base).max() > 1.0) == matters, entry


def test_refusals_and_registration(monkeypatch):
    q, pk, pv, table, pos, _ = _case(1, W=8, group=1, hk=2, rows=2, dk=8,
                                     dks=8, dv=8, window=None, start=(0,),
                                     S=1)
    with pytest.raises(ValueError, match="latent plane keeps the dense"):
        chain_attention_pallas(q[..., 0, :], pk[:, :, 0], None, table, pos,
                               value_lanes=4, interpret=True)
    with pytest.raises(ValueError, match="query heads in groups"):
        chain_attention_pallas(q, pk, pv, table, pos, group=3,
                               interpret=True)
    # off the TPU the op class is the dense spelling
    assert resolve_name("chain_attention") == "xla_ref"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_name("chain_attention") == "pallas_tpu"
