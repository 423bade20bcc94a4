"""Mamba-2's step and chunked form (``kernels/ssm.py``): the backends
agree and leave dead slots alone; the chunked form is the scan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import oracle_tol


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
