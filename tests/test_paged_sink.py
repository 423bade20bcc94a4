"""The paged kernels with what a sink-window plane adds: a learned sink
logit a query head (mass, no value), a K array of more lanes than the V
array (and than the key: ``key_lanes``), a window's table whose entries
under the lower bound name blocks given back (the trash block), and the
dense spelling one K/V head at a time over the window's own entries.
Each spelling against a plain softmax over the gathered chain."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.kernels import paged_attention as pa  # noqa: E402

B, NB, HK, DK, DKS, DV = 4, 12, 2, 6, 8, 4


def _case(seed, S, W, group, window, released=True):
    """Pools, a table and positions: slot ``s``'s window of ``W`` rows
    ends at a position of its own; with ``released`` the entries under
    every row's lower bound are zeroed, as an engine that gave those
    blocks back leaves them."""
    rng = np.random.default_rng(seed)
    blocks = 1 + S * NB
    pk = rng.normal(size=(blocks, B, HK, DKS)).astype(np.float32)
    pk[..., DK:] = 0.0                       # the lanes past the key
    pv = rng.normal(size=(blocks, B, HK, DV)).astype(np.float32)
    table = 1 + np.arange(S * NB, dtype=np.int32).reshape(S, NB)
    last = rng.integers(W - 1, NB * B, S)
    pos = last[:, None] - (W - 1) + np.arange(W)[None]
    if released and window is not None:
        for s in range(S):
            table[s, :max(pos[s, 0] - window + 1, 0) // B] = 0
    q = rng.normal(size=(S, W, HK * group, DK)).astype(np.float32)
    sink = rng.normal(size=(HK * group,)).astype(np.float32) + 1.0
    return q, pk, pv, table, pos.astype(np.int32), sink


def _plain(q, pk, pv, table, pos, group, window, sink, scale):
    S, W, h, _ = q.shape
    out = np.zeros((S, W, h, DV), np.float64)
    for s in range(S):
        k = pk[table[s]].reshape(NB * B, HK, DKS)[..., :DK]
        v = pv[table[s]].reshape(NB * B, HK, DV)
        for w in range(W):
            lo = 0 if window is None else max(pos[s, w] - window + 1, 0)
            js = np.arange(lo, pos[s, w] + 1)
            for a in range(h):
                sc = k[js, a // group] @ q[s, w, a].astype(np.float64) * scale
                logits = np.concatenate([sc, [sink[a]]]) if sink is not None \
                    else sc
                p = np.exp(logits - logits.max())
                p /= p.sum()
                out[s, w, a] = p[:len(js)] @ v[js, a // group]
    return out


CASES = [("decode_full_sink", 1, 4, None, True),
         ("decode_window_sink", 1, 2, 6, True),
         ("decode_window_no_sink", 1, 2, 6, False),
         ("verify_window_sink", 3, 2, 5, True),
         ("piece_full_sink", 8, 4, None, True),
         ("piece_window_sink", 8, 2, 6, True),
         ("piece_window_no_sink", 8, 2, 6, False)]


# the by-head spelling is a dense window's; a streamed window's Mosaic
# loop at 2, 4 and 8 table entries an iteration too (the rule gives
# these chains of 12 entries one: ``entries_per_iteration``)
SPELLED = [(sp,) + c for c in CASES for sp in
           ("ref", "mosaic", "attend", "by_head")
           if sp != "by_head" or c[1] >= pa.DENSE_WINDOW]
SPELLED += [(f"mosaic_{g}_entries",) + c for c in CASES for g in (2, 4, 8)
            if c[1] < pa.DENSE_WINDOW]


@pytest.mark.parametrize("spelling,name,W,group,window,with_sink", SPELLED)
def test_sink_and_value_lanes_against_a_plain_softmax(
        spelling, name, W, group, window, with_sink, monkeypatch):
    q, pk, pv, table, pos, sink = _case(len(name), 3, W, group, window)
    scale = DK ** -0.5
    want = _plain(q, pk, pv, table, pos, group, window,
                  sink if with_sink else None, scale)
    how = dict(group=group, window=window,
               sink=jnp.asarray(sink) if with_sink else None)
    if spelling == "by_head":
        monkeypatch.setattr(pa, "DENSE_SCORE_BYTES", 0)
    if spelling.endswith("_entries"):
        monkeypatch.setattr(pa, "entries_per_iteration",
                            lambda *a: int(spelling.split("_")[1]))
    if spelling in ("attend", "by_head"):
        # the caller's form: the key's own lanes, no scale stated
        got = pa.attend(jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
                        jnp.asarray(table), jnp.asarray(pos), **how)
    else:
        qs = np.pad(q, ((0, 0),) * 3 + ((0, DKS - DK),))
        call = (pa.paged_attention_ref if spelling == "ref" else
                lambda *a, **kw: pa.paged_attention_pallas(
                    *a, interpret=True, **kw))
        got = call(jnp.asarray(qs), jnp.asarray(pk), jnp.asarray(pv),
                   jnp.asarray(table), jnp.asarray(pos), scale=scale, **how)
    assert got.shape == (3, W, HK * group, DV)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_the_scan_of_blocks_starts_at_the_sink():
    """``block_step`` under the chain's length: the online softmax's
    carry starts at the sink and every step rescales it."""
    q, pk, pv, table, pos, sink = _case(5, 2, 1, 2, None)
    qs = np.pad(q, ((0, 0),) * 3 + ((0, DKS - DK),))
    want = _plain(q, pk, pv, table, pos, 2, None, sink, DK ** -0.5)
    got = pa.paged_attention_ref(
        jnp.asarray(qs), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(table), jnp.asarray(pos), block_step=2, group=2,
        scale=DK ** -0.5, sink=jnp.asarray(sink))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_a_row_with_no_key_returns_zeros_beside_a_sink():
    """A dead slot's rows (``pos = -1``): the sink holds all the mass
    and adds no value."""
    q, pk, pv, table, pos, sink = _case(7, 2, 1, 2, 6)
    pos[0] = -1
    qs = jnp.asarray(np.pad(q, ((0, 0),) * 3 + ((0, DKS - DK),)))
    for call in (pa.paged_attention_ref,
                 lambda *a, **kw: pa.paged_attention_pallas(
                     *a, interpret=True, **kw)):
        got = np.asarray(call(qs, jnp.asarray(pk), jnp.asarray(pv),
                              jnp.asarray(table), jnp.asarray(pos), group=2,
                              window=6, sink=jnp.asarray(sink)))
        assert not got[0].any() and got[1].any()


def test_write_pads_the_heads_and_the_lanes_with_zeros():
    pool = jnp.ones((3, B, 4, DKS), jnp.float32)
    rows = jnp.full((2, HK, DK), 2.0, jnp.float32)
    out = np.asarray(pa.write(pool, jnp.array([1, 2]), jnp.array([0, 3]),
                              rows))
    assert (out[1, 0, :HK, :DK] == 2).all()
    assert (out[2, 3, :HK, :DK] == 2).all()
    assert not out[1, 0, HK:].any() and not out[1, 0, :, DK:].any()
    assert (out[0] == 1).all() and (out[1, 1] == 1).all()
    assert pa.key_lanes(192) == 256 and pa.key_lanes(128) == 128


def test_a_sink_is_refused_on_a_latent_plane():
    with pytest.raises(ValueError, match="latent plane has no sink"):
        pa.paged_attention_ref(
            jnp.zeros((1, 1, 2, 8)), jnp.zeros((3, B, 8)), None,
            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1, 1), jnp.int32),
            value_lanes=4, sink=jnp.zeros((2,)))
