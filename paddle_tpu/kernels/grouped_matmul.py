"""Grouped matrix product: rows sorted by group, one matrix a group.

What a routed FFN on the serving path multiplies
(``serving/arch.py``, ``GatedMoE``): the rows a step routed to the
experts held here, gathered so that the rows of one expert lie together,
times that expert's matrix.  How many rows an expert gets is data; the
shapes are not::

    call(lhs, rhs, group_sizes, interpret=None, block_m=None,
         block_n=None, transpose_rhs=False) -> out

    lhs          [m, k]      rows, those of group 0 first, then group 1 ...
    rhs          [g, k, n]   one matrix a group (``[g, n, k]`` with
                             ``transpose_rhs``: the product is ``lhs @
                             rhs[i].T``)
    group_sizes  [g] int32   rows of each group; ``sum <= m``
    out          [m, n]      ``lhs[rows of group i] @ rhs[i]`` in
                             ``lhs.dtype`` (float32 accumulation); the rows
                             past ``sum(group_sizes)`` belong to no group
                             and come back ZERO

``transpose_rhs`` is for a matrix whose ``n`` is not a whole number of
128-lane tiles (1,856 is 14.5): the device lays such an array out with
its two minor axes swapped, so that the axis of whole tiles is minor and
nothing is padded, and a kernel that wants it row-major is handed a COPY
of it at every call (157.5 MB a layer at 16 experts of ``[2688, 1856]``:
a compile for a described v5e shows it).  Held as ``[g, n, k]`` it is
row-major as it lies, unpadded, and a panel is ``[block_n, k]``, read
with the contraction on both operands' lanes, as ``q k^T`` is.

There is no capacity: a group may hold every row or none, and a group
with no row costs nothing in the Mosaic kernel (its matrix is never
read).  Inference only (no VJP).

Backends:

* ``xla_ref``: a ``lax.scan`` over the groups, each step the whole
  ``lhs`` times one matrix, kept where the row is the group's.  ``g``
  times the work and every matrix read: the numerics oracle, and what a
  CPU process runs.
* ``pallas_tpu``: the work items are the (group, row tile) pairs that
  hold a row, in group order, found on the device from ``group_sizes``
  and handed to the kernel as scalar-prefetch arguments; grid ``(n
  tiles, work items)`` with the second bound DYNAMIC (the number of
  items this call has), ``k`` whole (``k`` need not be whole lane tiles,
  nor ``n`` of matrices held transposed: ``_block_n``).  An item multiplies its row tile by
  its group's ``[k, block_n]`` panel and stores the rows that are the
  group's; a row tile that several groups share is visited once a group,
  consecutively, so its output block stays in VMEM between the visits.
  At most ``m / block_m + g - 1`` items, each reading one panel: the
  matrices of groups with no row are never fetched.  The layout of the
  work items is that of the grouped matmul of MegaBlocks
  (arXiv:2211.15841) as ``jax.experimental.pallas.ops.tpu.megablox``
  spells it, without its ``k`` loop and its sharded groups.  Registered
  available on a TPU only, as the paged kernel is; the oracle suite runs
  its logic on the CPU with ``interpret=True``.

The two differ by the order of one float32 sum over ``k``
(``ORACLE_TOL["grouped_matmul", ...]``).
"""

import jax
import jax.numpy as jnp

from .paged_attention import _tpu_available
from .registry import register_kernel, resolve

__all__ = ["grouped_matmul", "grouped_matmul_ref", "grouped_matmul_pallas",
           "work_items", "BLOCK_M", "PANEL_BYTES"]

# rows a work item multiplies: one MXU pass holds them, and a decode
# step's real rows (some 25 of 384) lie in the first tile
BLOCK_M = 128
# the widest [k, block_n] panel of a group's matrix one item reads; two
# are in flight (3 MiB each at k = 3072 in bfloat16, block_n 512)
PANEL_BYTES = 4 << 20


def grouped_matmul(lhs, rhs, group_sizes, transpose_rhs=False):
    """The product through whatever the registry resolves for
    ``grouped_matmul``: the one call the serving step makes."""
    return resolve("grouped_matmul").impl.call(
        lhs, rhs, group_sizes, transpose_rhs=transpose_rhs)


def _in_a_group(out, group_sizes):
    row = jnp.arange(out.shape[0], dtype=jnp.int32)
    return jnp.where((row < jnp.sum(group_sizes))[:, None], out, 0)


# -- xla_ref -----------------------------------------------------------------

def grouped_matmul_ref(lhs, rhs, group_sizes, interpret=None, block_m=None,
                       block_n=None, transpose_rhs=False):
    """The oracle spelling: every group's matrix times every row, kept
    where the row is the group's.  ``interpret`` and the blocks are
    accepted for signature parity and ignored."""
    del interpret, block_m, block_n
    m = lhs.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    row = jnp.arange(m, dtype=jnp.int32)[:, None]

    def one(acc, group):
        w, start, end = group
        y = jnp.matmul(lhs, w.T if transpose_rhs else w,
                       preferred_element_type=jnp.float32)
        return jnp.where((row >= start) & (row < end), y, acc), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros((m, rhs.shape[1 if transpose_rhs else 2]),
                       jnp.float32),
        (rhs, ends - group_sizes, ends))
    return acc.astype(lhs.dtype)


# -- pallas_tpu --------------------------------------------------------------

def work_items(group_sizes, m, block_m):
    """``(group_of [L], tile_of [L], n_items, offsets [g + 1])`` of the
    (group, row tile) pairs that hold a row, in group order; ``L = m /
    block_m + g - 1`` bounds their number (entries past ``n_items`` name
    a valid group and tile and are never run)."""
    g = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // block_m
    tiles = jnp.where(sizes > 0, (ends - 1) // block_m - first + 1, 0)
    n_tiles = m // block_m
    length = n_tiles + g - 1
    group_of = jnp.repeat(jnp.arange(g, dtype=jnp.int32), tiles,
                          total_repeat_length=length)
    item_start = jnp.cumsum(tiles) - tiles
    tile_of = (first[group_of] + jnp.arange(length, dtype=jnp.int32)
               - item_start[group_of])
    return (group_of, jnp.clip(tile_of, 0, n_tiles - 1), jnp.sum(tiles),
            jnp.concatenate([starts[:1] * 0, ends]))


def _block_n(k, n, itemsize, overhang=False):
    """The width of a group's panel: the widest multiple of 128 that
    divides ``n`` and keeps a ``[k, block_n]`` panel within
    ``PANEL_BYTES``; ``n`` itself where no multiple of 128 divides it (a
    block equal to the array).  With ``overhang`` (matrices held
    transposed, whose ``n`` is a major axis: 1,856 is 14.5 lane tiles)
    such an ``n`` takes instead the multiple of 128 within
    ``PANEL_BYTES`` that covers it with the fewest lanes past it, the
    widest of those: the last panel overhangs the matrix, Mosaic reads
    what is there and stores what fits, and the weights in HBM are never
    padded (a column of the product is a row of the panel, so what the
    overhang holds reaches no column that is stored)."""
    fits = [b for b in range(128, n + 1, 128)
            if k * b * itemsize <= PANEL_BYTES]
    whole = [b for b in fits if n % b == 0]
    if whole:
        return max(whole)
    if not overhang or n % 128 == 0 or not fits:
        return n
    return max(fits, key=lambda b: (-b * -(-n // b), b))


def grouped_matmul_pallas(lhs, rhs, group_sizes, interpret=None,
                          block_m=None, block_n=None, transpose_rhs=False):
    """The Mosaic kernel (module docstring)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    g, n = rhs.shape[0], rhs.shape[1 if transpose_rhs else 2]
    # a row tile is a whole number of (16, 128) bfloat16 tiles
    tm = min(block_m or BLOCK_M, -(-m // 16) * 16)
    tn = block_n or _block_n(k, n, rhs.dtype.itemsize,
                              overhang=transpose_rhs)
    pad = (-m) % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    group_of, tile_of, n_items, offsets = work_items(group_sizes, m + pad,
                                                     tm)

    def kernel(group_of, tile_of, offsets, lhs_ref, rhs_ref, out_ref):
        i = pl.program_id(1)
        grp = group_of[i]
        row = tile_of[i] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        mine = (row >= offsets[grp]) & (row < offsets[grp + 1])
        if transpose_rhs:
            acc = jax.lax.dot_general(
                lhs_ref[...], rhs_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                          preferred_element_type=jnp.float32)
        # rows of the tile that are another group's keep what that
        # group's visit stored (the block stays in VMEM between them)
        out_ref[...] = jnp.where(
            mine, acc, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(-(-n // tn), n_items),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, grp, tile, off:
                             (tile[i], 0)),
                pl.BlockSpec((None, tn, k), lambda j, i, grp, tile, off:
                             (grp[i], j, 0)) if transpose_rhs else
                pl.BlockSpec((None, k, tn), lambda j, i, grp, tile, off:
                             (grp[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, grp, tile, off:
                                   (tile[i], j))),
        out_shape=jax.ShapeDtypeStruct((m + pad, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=bool(interpret),
        name="grouped_matmul",
    )(group_of, tile_of, offsets, lhs, rhs)
    # a tile no item visited, and the rows past the last group in the
    # last tile visited, hold whatever the buffer held
    return _in_a_group(out[:m], group_sizes)


# -- registration ------------------------------------------------------------

class _GroupedXlaRef:
    call = staticmethod(grouped_matmul_ref)


class _GroupedPallasTpu:
    call = staticmethod(grouped_matmul_pallas)


register_kernel("grouped_matmul", "xla_ref", _GroupedXlaRef)
register_kernel("grouped_matmul", "pallas_tpu", _GroupedPallasTpu,
                available=_tpu_available)
