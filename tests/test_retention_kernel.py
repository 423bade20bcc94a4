"""Power retention's kernels (``kernels/retention.py``) on the CPU at
small sizes: the features ``phi`` against the squared dot product, the
``xla_ref`` step and chunk against the QUADRATIC form across piece
boundaries, uneven piece widths and padded rows, the Mosaic kernels in
interpret mode against ``xla_ref``, a dead slot's state bit-equal
after a step, and ONE chunk call over 8 to 512 rows (its walk in row
tiles, a limit inside a tile, a piece that starts a prompt and never
reads the slot).  2 K/V heads of 3 query heads each throughout."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.kernels import retention as rt  # noqa: E402

H, HK = 6, 2


def quadratic(q, k, v, lg, eps=1e-6):
    """``y [T, h, d]`` of the layer's defining sum, in float64: query
    head ``a`` reads K/V head ``a // (h / kv)``."""
    q, k, v, lg = (np.asarray(x, np.float64) for x in (q, k, v, lg))
    T, h, d = q.shape
    group = h // k.shape[1]
    cum = np.cumsum(lg, axis=0)
    y = np.zeros((T, h, d))
    for a in range(h):
        j = a // group
        s = q[:, a] @ k[:, j].T / np.sqrt(d)
        w = s ** 2 * np.exp(cum[:, None, j] - cum[None, :, j]) * np.tril(
            np.ones((T, T)))
        y[:, a] = w @ v[:, j] / (w.sum(1, keepdims=True) + eps)
    return y


def rows(rng, T, d):
    return (rng.normal(size=(T, H, d)).astype(np.float32),
            rng.normal(size=(T, HK, d)).astype(np.float32),
            rng.normal(size=(T, HK, d)).astype(np.float32),
            np.log(rng.uniform(0.8, 0.999, (T, HK))).astype(np.float32))


@pytest.mark.parametrize("d", [8, 16, 128])
def test_phi_is_the_squared_dot_product(d):
    rng = np.random.default_rng(d)
    q = rng.normal(size=(7, d)).astype(np.float32)
    k = rng.normal(size=(7, d)).astype(np.float32)
    pq, pk = np.asarray(rt.phi(q)), np.asarray(rt.phi(k))
    assert pq.shape == (7, d // 2 + 1, d)
    assert rt.stored_rows(d) == (d // 2 + 1) * d
    assert rt.published_rows(d) == d * (d + 1) // 2
    want = (q.astype(np.float64) * k).sum(-1) ** 2 / d
    got = np.einsum("nra,nra->n", pq.astype(np.float64), pk)
    assert np.abs(got - want).max() <= 4e-6 * np.abs(want).max()
    # the layout stores the last diagonal's d / 2 pairs twice
    assert rt.stored_rows(d) - rt.published_rows(d) == d // 2
    assert (rt.published_rows(128), rt.stored_rows(128)) == (8256, 8320)


def _serve(step, chunk, d, T=40, slots=3, slot=1,
           pieces=((16, 16), (8, 5), (16, 11))):
    """The rows of one sequence through ``chunk`` (pieces of ``(width,
    real rows)``) and then ``step``, in slot ``slot`` of ``slots``, the
    other slots dead and holding a state of their own: ``(y [T, h, d],
    S, z, the state as it began)``."""
    rng = np.random.default_rng(3)
    q, k, v, lg = rows(rng, T, d)
    S = jnp.asarray(rng.normal(size=(slots, HK, rt.stored_rows(d), d)),
                    jnp.float32)
    z = jnp.asarray(rng.normal(size=(slots, HK, rt.stored_rows(d))),
                    jnp.float32)
    began = np.asarray(S), np.asarray(z)
    ys, at = [], 0
    for width, n in pieces:
        def pad(x):
            # padding rows hold what a bucket's tail would: anything
            return jnp.asarray(np.concatenate(
                [x[at:at + n], np.full((width - n,) + x.shape[1:], 3.0,
                                       np.float32)]))
        y, S, z = chunk(S, z, jnp.int32(slot), at == 0, pad(q), pad(k),
                        pad(v), pad(lg), jnp.arange(width) < n)
        ys.append(np.asarray(y)[:n])
        at += n
    valid = jnp.arange(slots) == slot
    while at < T:
        def every(x):
            return jnp.asarray(np.stack(
                [x[at] if s == slot else x[at] * 0 + 2.0
                 for s in range(slots)]))
        y, S, z = step(S, z, every(q), every(k), every(v), every(lg), valid)
        assert not np.asarray(y)[np.arange(slots) != slot].any()
        ys.append(np.asarray(y)[slot:slot + 1])
        at += 1
    return (np.concatenate(ys), np.asarray(S), np.asarray(z), began,
            quadratic(q, k, v, lg))


@pytest.mark.parametrize("d", [8, 16])
def test_xla_ref_is_the_quadratic_form_across_pieces_and_steps(d):
    got, S, z, began, want = _serve(rt.retention_step_ref,
                                    rt.retention_chunk_ref, d)
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
    # the slots that served nothing hold what they held, to the bit
    for s in (0, 2):
        assert np.array_equal(S[s], began[0][s])
        assert np.array_equal(z[s], began[1][s])
    assert not np.array_equal(S[1], began[0][1])


@pytest.mark.parametrize("d", [8, 16])
def test_mosaic_kernels_in_interpret_mode_agree_with_xla_ref(d):
    def step(*a):
        return rt.retention_step_pallas(*a, interpret=True)

    def chunk(*a):
        return rt.retention_chunk_pallas(*a, interpret=True)

    got, S, z, began, want = _serve(step, chunk, d)
    ref, S_ref, z_ref, _, _ = _serve(rt.retention_step_ref,
                                     rt.retention_chunk_ref, d)
    # the chunk kernel multiplies float32 as two bfloat16 pieces each
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    assert np.abs(got - ref).max() <= 1e-4 * np.abs(ref).max()
    assert np.abs(S - S_ref).max() <= 1e-4 * np.abs(S_ref).max()
    assert np.abs(z - z_ref).max() <= 1e-4 * np.abs(z_ref).max()
    # a dead slot's state is never read and never written: bit-equal
    for s in (0, 2):
        assert np.array_equal(S[s], began[0][s])
        assert np.array_equal(z[s], began[1][s])


def test_step_of_several_live_slots_visits_each_and_no_other():
    d, slots = 16, 5
    rng = np.random.default_rng(5)
    q, k, v, lg = rows(rng, slots, d)
    S = jnp.asarray(rng.normal(size=(slots, HK, rt.stored_rows(d), d)),
                    jnp.float32)
    z = jnp.asarray(rng.normal(size=(slots, HK, rt.stored_rows(d))) + 4.0,
                    jnp.float32)
    valid = jnp.asarray([True, False, True, True, False])
    y, Sn, zn = rt.retention_step_pallas(
        S, z, *(jnp.asarray(x) for x in (q, k, v, lg)), valid,
        interpret=True)
    yr, Sr, zr = rt.retention_step_ref(
        S, z, *(jnp.asarray(x) for x in (q, k, v, lg)), valid)
    assert np.allclose(y, yr, rtol=1e-5, atol=1e-5)
    assert np.allclose(Sn, Sr, rtol=1e-6, atol=1e-6)
    assert np.allclose(zn, zr, rtol=1e-6, atol=1e-6)
    for s in (1, 4):
        assert np.array_equal(np.asarray(Sn)[s], np.asarray(S)[s])
        assert np.array_equal(np.asarray(zn)[s], np.asarray(z)[s])
        assert not np.asarray(y)[s].any()
    # no slot live: nothing is visited at all
    _, S0, z0 = rt.retention_step_pallas(
        S, z, *(jnp.asarray(x) for x in (q, k, v, lg)),
        jnp.zeros(slots, bool), interpret=True)
    assert np.array_equal(S0, S) and np.array_equal(z0, z)


def test_a_fresh_piece_starts_from_zeros_whatever_the_slot_held():
    d = 8
    rng = np.random.default_rng(9)
    q, k, v, lg = (jnp.asarray(x) for x in rows(rng, 8, d))
    valid = jnp.arange(8) < 8
    shape = (2, HK, rt.stored_rows(d), d)
    dirty = (jnp.full(shape, 5.0), jnp.full(shape[:-1], 5.0))
    clean = (jnp.zeros(shape), jnp.zeros(shape[:-1]))
    for chunk in (rt.retention_chunk_ref,
                  lambda *a: rt.retention_chunk_pallas(*a, interpret=True)):
        a = chunk(*dirty, jnp.int32(1), True, q, k, v, lg, valid)
        b = chunk(*clean, jnp.int32(1), False, q, k, v, lg, valid)
        assert np.array_equal(a[0], b[0])
        assert np.array_equal(np.asarray(a[1])[1], np.asarray(b[1])[1])


def _mosaic_chunk(*a):
    return rt.retention_chunk_pallas(*a, interpret=True)


def _one_call(C, real, fresh, d=8, before=24, slots=3, slot=2):
    """A sequence of ``before + real`` rows: the first ``before`` through
    ``xla_ref`` (none for a ``fresh`` call), then ONE call of ``C`` rows
    of which ``real`` are within the limit, through the Mosaic kernel in
    interpret mode and through ``xla_ref`` -> (both results, the
    quadratic form's rows of the call, the state before it)."""
    before = 0 if fresh else before
    rng = np.random.default_rng(C + real)
    q, k, v, lg = rows(rng, before + C, d)
    S = jnp.asarray(rng.normal(size=(slots, HK, rt.stored_rows(d), d)),
                    jnp.float32)
    z = jnp.asarray(rng.normal(size=(slots, HK, rt.stored_rows(d))),
                    jnp.float32)
    if before:
        _, S, z = rt.retention_chunk_ref(
            S, z, jnp.int32(slot), True,
            *(jnp.asarray(x[:before]) for x in (q, k, v, lg)),
            jnp.arange(before) < before)
    args = (jnp.int32(slot), fresh) + tuple(
        jnp.asarray(x[before:]) for x in (q, k, v, lg)) + (
        jnp.arange(C) < real,)
    want = quadratic(*(x[:before + real] for x in (q, k, v, lg)))[before:]
    return (_mosaic_chunk(S, z, *args), rt.retention_chunk_ref(S, z, *args),
            want, (np.asarray(S), np.asarray(z)))


@pytest.mark.parametrize("fresh", [False, True])
@pytest.mark.parametrize("C,real", [(8, 8), (32, 32), (128, 128), (256, 256),
                                    (512, 512), (512, 300)])
def test_one_chunk_call_walks_its_rows_in_tiles(C, real, fresh):
    """ONE call of up to ``CALL_ROWS`` rows (four row tiles at 512; a
    limit of 300 ends inside the third) against ``xla_ref`` and the
    quadratic form, continuing a prompt and starting one."""
    assert rt.chunk_rows(C) == [C] and rt.CHUNK_ROWS == 128
    (y, S, z), (yr, Sr, zr), want, (S0, z0) = _one_call(C, real, fresh)
    y, yr = np.asarray(y)[:real], np.asarray(yr)[:real]
    assert np.abs(y - want).max() <= 1e-4 * np.abs(want).max()
    assert np.abs(y - yr).max() <= 1e-4 * np.abs(yr).max()
    assert np.abs(S - Sr).max() <= 1e-4 * np.abs(Sr).max()
    assert np.abs(z - zr).max() <= 1e-4 * np.abs(zr).max()
    # the other slots hold what they held, to the bit
    for s in (0, 1):
        assert np.array_equal(np.asarray(S)[s], S0[s])
        assert np.array_equal(np.asarray(z)[s], z0[s])


@pytest.mark.parametrize("C,real", [(8, 8), (128, 128), (512, 300)])
def test_a_fresh_call_leaves_the_same_state_whatever_the_slot_held(C, real):
    """A call that starts a prompt skips the read of the state: what the
    slot held (here: infinities and NaNs too) reaches neither the rows
    nor the state it leaves."""
    d = 8
    rng = np.random.default_rng(C)
    q, k, v, lg = (jnp.asarray(x) for x in rows(rng, C, d))
    valid = jnp.arange(C) < real
    shape = (2, HK, rt.stored_rows(d), d)
    dirty = np.full(shape, 5.0, np.float32)
    dirty[:, :, ::3] = np.inf
    dirty[:, :, 1::3] = np.nan
    (y, S, z), (yc, Sc, zc) = (
        _mosaic_chunk(jnp.asarray(held), jnp.asarray(held[..., 0]),
                      jnp.int32(1), True, q, k, v, lg, valid)
        for held in (dirty, np.zeros(shape, np.float32)))
    assert np.array_equal(np.asarray(y)[:real], np.asarray(yc)[:real])
    assert np.array_equal(np.asarray(S)[1], np.asarray(Sc)[1])
    assert np.array_equal(np.asarray(z)[1], np.asarray(zc)[1])
    assert np.isfinite(np.asarray(S)[1]).all()
    # and the slot beside it holds its infinities still
    assert np.array_equal(np.asarray(S)[0], dirty[0], equal_nan=True)


@pytest.mark.parametrize("fresh", [False, True])
def test_one_512_row_call_equals_four_threaded_128_row_calls(fresh):
    """What a 512-row window was before PR 44: four consecutive calls of
    one row tile each, the state threaded through."""
    d, C = 8, 512
    rng = np.random.default_rng(12)
    q, k, v, lg = (jnp.asarray(x) for x in rows(rng, C, d))
    S = jnp.asarray(rng.normal(size=(2, HK, rt.stored_rows(d), d)),
                    jnp.float32)
    z = jnp.asarray(rng.normal(size=(2, HK, rt.stored_rows(d))) + 4.0,
                    jnp.float32)
    valid = jnp.arange(C) < 300
    y, S1, z1 = _mosaic_chunk(S, z, jnp.int32(0), fresh, q, k, v, lg, valid)
    ys, S4, z4 = [], S, z
    for i in range(4):
        cut = slice(128 * i, 128 * (i + 1))
        yi, S4, z4 = _mosaic_chunk(S4, z4, jnp.int32(0), fresh and i == 0,
                                   q[cut], k[cut], v[cut], lg[cut],
                                   valid[cut])
        ys.append(yi)
    y4 = np.concatenate(ys)[:300]
    assert np.abs(np.asarray(y)[:300] - y4).max() <= 1e-4 * np.abs(y4).max()
    assert np.abs(S1 - S4).max() <= 1e-4 * np.abs(S4).max()
    assert np.abs(z1 - z4).max() <= 1e-4 * np.abs(z4).max()


def test_retention_resolves_to_the_oracle_off_the_tpu():
    from paddle_tpu.kernels import registered_op_classes, resolve_name

    assert "retention" in registered_op_classes()
    assert resolve_name("retention") == "xla_ref"
