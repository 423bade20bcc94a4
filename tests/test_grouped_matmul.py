"""The grouped matrix product (``kernels/grouped_matmul.py``): both
backends against the truth over empty groups, rows of no group and groups
across row tiles, with the matrices held transposed, and the work items
the Mosaic kernel walks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import get_kernel, oracle_tol, resolve_name


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
