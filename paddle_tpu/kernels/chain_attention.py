"""A WIDE window's attention through the block table: a blocked
online-softmax walk over the slot's chain, the scores never outside VMEM.

``kernels.paged_attention.attend`` sends a prefill piece here (a window
of ``DENSE_WINDOW`` rows or more over a K/V plane whose dense scores
would pass ``CHAIN_SCORE_BYTES``); the calling convention is the paged op
class's, argument for argument (``paged_attention``'s module docstring:
``group``, ``window``, ``scale``, ``out_dtype``, a ``sink``, a K array of
more lanes than the V array, the rows ``pool_rows`` added).

Backends:

* ``xla_ref`` — the dense spelling, what ``attend`` lowered to before
  this op class existed and lowers to off the TPU still
  (``paged_attention.dense_window``): the chain gathered once, float32
  scores over ALL of its ``NB x B`` positions through HBM, one K/V head
  at a time past ``DENSE_SCORE_BYTES``.  The numerics reference.
* ``pallas_tpu`` — ``chain_attention_pallas`` (HLO name
  ``chain_attention``; the readers of the DECODE kernel find its calls
  by ``paged_attention`` and must not find this one).  XLA gathers the
  table's entries ONCE into head-major arrays ``k [S, hk, T, dk]``, ``v
  [S, hk, T, dv]`` over the ``hk`` heads the plane really has (a full
  plane its whole chain, 41 MB at ``mimo25.long_reason``'s 13,312
  positions; a plane with a lower bound the ``(W + window - 2) // B + 2``
  entries some row can see, an entry under the bound redirected to the
  first live one so that a block given back is never read), and a flash
  walk runs over them: grid ``(S, hk, query tiles, key tiles)``, the key
  tiles innermost, ``(m, l, acc)`` float32 in VMEM scratch.  A K/V
  group is folded into ROWS as ``_fold_group`` does (row ``w * group +
  g`` of K/V head ``j`` is query head ``j * group + g`` at ``pos[s,
  w]``), so a query tile is ``ROW_TILE`` folded rows of one K/V head.
  For each query tile the wrapper states, as scalar-prefetch integers,
  the key tiles that hold a key some row of it may attend (from the
  tile of ``min pos - window + 1``, 0 without a window, to the tile of
  ``max pos``) and, among them, the tiles EVERY row attends whole; the
  others are neither fetched (the index map names the nearest live
  tile, which is the one already held) nor computed, and only the
  tiles the diagonal or the lower bound crosses are masked.

Numerics are the flash kernels': scores float32 out of the operands'
own dtype (bfloat16 in the serving cells: exact products, float32 sums;
``HIGHEST`` for a float32 pool), ``NEG_INF`` masking, ``(m, l, acc)``
float32, one normalization at the end with the ``l == 0 -> 1`` guard.
``p`` is weighed in ONE MXU pass after one rounding to the V array's
dtype (for a float32 pool at ``HIGHEST``).  That is the accuracy of the
spelling it replaces ON THE CHIP: there ``p x v`` is a float32 ``einsum``
at the default precision, which rounds both operands to bfloat16 for one
pass.  A ``sink`` is where the softmax STARTS (``m = sink``, ``l = 1``,
``acc = 0``).  A row with ``pos < 0`` attends nothing and returns zeros,
as the decode kernel's does (the dense spelling returns the mean of the
values there; nobody reads such a row).  The backends differ within
``ORACLE_TOL["chain_attention", ...]``.
"""

import functools

import jax
import jax.numpy as jnp

from ..ops.pallas_attention import LSE_LANES, VMEM_LIMIT_BYTES
from . import paged_attention as _paged
from .registry import register_kernel
from .xla_ref import NEG_INF

__all__ = ["KEY_TILE", "ROW_TILE", "chain_attention_pallas"]

# Folded query rows and key positions a tile.  Measured alone on the chip
# at mimo25.long_reason's planes (512 rows x group 16 a K/V head over
# 13,312 positions, behind 3,500 | 9,000 positions; my chip runs, PR 47,
# benchmarks/RESULTS.md has the table), the kernel's own microseconds:
# 1024 x 256 2,017 | 4,825 (call); 1024 x 512 665 | 1,542; 1024 x 1024
# 686 | 1,488; 2048 x 1024 669 | 1,430; 4096 x 1024 653 | 1,401; 1024 x
# 2048 705 | 1,717.  A step costs some 1.5 us a thousand rows whatever
# its keys (the state's read-modify-write, the row maxima) and a skipped
# grid step 0.35 us, so few wide steps win until a tile's scores crowd
# the scoped VMEM (4096 x 1024 holds 45 MB of them for 2% more).  The
# window-128 plane (8 K/V heads x 4,096 folded rows over 672 gathered
# positions) is ONE masked key tile of 768: 191 us at 2048 rows, 205 at
# 2048 x 256, 275 at 1024 x 128 (less work in more steps).
ROW_TILE = 2048
KEY_TILE = 1024


def _tile(n, cap, align):
    """The largest divisor of ``n`` that is at most ``cap`` and a
    multiple of ``align``; ``n`` itself where there is none."""
    for t in range(min(n, cap), 0, -1):
        if n % t == 0 and t % align == 0:
            return t
    return n


def _gathered(pool_k, pool_v, table, pos, hk, window, n, tk):
    """The slot's chain as head-major arrays ``k [S, hk, T, dk]``, ``v
    [S, hk, T, dv]`` and ``base [S]``, the position of their first key;
    ``T`` a multiple of ``tk`` that holds ``n`` table entries.  Without a
    window the whole chain; with one the ``n`` entries a row of the window
    can see (``paged_attention.window_entries``; the rows ascend), an
    entry under the lowest bound named as the first live one (its keys
    lie under every row's bound: masked, and finite) and an entry past
    the chain's end as the last (positions no row reaches)."""
    S, NB = table.shape
    B = pool_k.shape[1]
    T = -(-n * B // tk) * tk
    e = jnp.arange(-(-T // B), dtype=jnp.int32)[None]            # [1, n']
    low = jnp.zeros((S, 1), jnp.int32)
    if window is not None:
        low = (jnp.maximum(pos[:, :1] - window + 1, 0) // B).astype(jnp.int32)
    first = jnp.clip(low, 0, max(NB - n, 0))
    idx = jnp.clip(first + e, jnp.minimum(low, NB - 1), NB - 1)
    tbl = jnp.take_along_axis(table.astype(jnp.int32), idx, axis=1)

    def heads(pool):
        got = pool[tbl][:, :, :, :hk]                    # [S, n', B, hk, d]
        got = got.reshape(S, -1, hk, pool.shape[-1])[:, :T]
        return got.transpose(0, 2, 1, 3)                 # [S, hk, T, d]

    return heads(pool_k), heads(pool_v), first[:, 0] * B


def _tile_bounds(at, base, window, tq, tk, nk, end):
    """What each query tile walks: ``[5, S * nq]`` int32, rows ``lo``,
    ``hi`` (the key tiles ``lo .. hi`` hold a key some row of the tile
    may attend; ``hi < lo``: none), ``flo``, ``fhi`` (the tiles ``flo ..
    fhi`` EVERY row attends whole: no mask) and ``base``.  ``at [S, N]``
    are the folded rows' positions, ``end`` the chain's capacity (no key
    lies at or past it)."""
    S, N = at.shape
    rows = at.reshape(S, N // tq, tq)
    big = jnp.iinfo(jnp.int32).max
    live = rows >= 0
    top = jnp.max(rows, axis=-1)                                  # [S, nq]
    bottom = jnp.min(jnp.where(live, rows, big), axis=-1)
    least = jnp.min(rows, axis=-1)
    rel = base[:, None]
    hi = jnp.where(
        top >= 0, jnp.minimum((jnp.minimum(top, end - 1) - rel) // tk,
                              nk - 1), -1)
    fhi = (jnp.minimum(least, end - 1) + 1 - rel) // tk - 1
    if window is None:
        lo, flo = jnp.zeros_like(hi), jnp.zeros_like(hi)
    else:
        lo = jnp.clip((jnp.maximum(bottom - window + 1, 0) - rel) // tk,
                      0, nk - 1)
        flo = -((rel - (top - window + 1)) // tk)        # the ceiling
    lo = jnp.where(top >= 0, lo, 0)
    return jnp.stack([lo, hi, flo, fhi, jnp.broadcast_to(rel, hi.shape)]
                     ).reshape(5, -1).astype(jnp.int32)


def _kernel(bounds, q_ref, k_ref, v_ref, at_ref, *rest, scale, window, group,
            nq, nk, tk, end, with_sink):
    """One (slot, K/V head, query tile, key tile) grid step.  ``m`` is
    kept lane-replicated ``[tq, LSE_LANES]`` as the flash kernels keep
    it; ``l`` is kept as LANE-WISE partial sums (a row's sum is the sum
    of its lanes, taken once at the end), so a step makes one cross-lane
    reduction a row, for the maximum, and none for the sum."""
    import jax.experimental.pallas as pl

    if with_sink:
        sink_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    iq = pl.program_id(2)
    cell = pl.program_id(0) * nq + iq
    kb = pl.program_id(3)
    lo, hi, flo, fhi = (bounds[i, cell] for i in range(4))
    tq, dv = acc_scr.shape
    first_lane = jax.lax.broadcasted_iota(jnp.int32, (tq, LSE_LANES), 1) == 0

    @pl.when(kb == 0)
    def _init():
        if with_sink:
            # the sink: a key every row has already seen, with no value;
            # row r of the tile is query head (iq * tq + r) % group of
            # the K/V head's group
            g_of = jax.lax.rem(iq * tq + jax.lax.broadcasted_iota(
                jnp.int32, (tq, LSE_LANES), 0), group)
            m0 = jnp.broadcast_to(sink_ref[0, :1], (tq, LSE_LANES))
            for g in range(1, group):
                m0 = jnp.where(g_of == g, sink_ref[0, g:g + 1], m0)
            m_scr[...] = m0
            l_scr[...] = first_lane.astype(jnp.float32)
        else:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(masked):
        k, v = k_ref[0, 0], v_ref[0, 0]
        dt = jnp.promote_types(q_ref.dtype, k.dtype)
        s = _paged._matmul(q_ref[0, 0].astype(dt), k.astype(dt),
                           ((1,), (1,))) * scale                  # [tq, tk]
        if masked:
            # key column c is position off + c: kept from a row's lower
            # bound up to its own position (and the chain's last)
            off = bounds[4, cell] + kb * tk
            at = at_ref[0]                                        # [tq, 1]
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = col <= jnp.minimum(at, end - 1) - off
            if window is not None:
                keep &= col > at - window - off
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[...]
        m2 = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m2)
        peak = m2[:, :1]
        if masked:
            # a key a row does not keep weighs EXACTLY zero, also while
            # the row has seen none (exp(NEG_INF - NEG_INF) would be 1)
            peak = jnp.where(peak == NEG_INF, 0.0, peak)
        p = jnp.exp(s - peak)
        if tk % LSE_LANES:
            part = jnp.where(first_lane,
                             jnp.sum(p, axis=-1, keepdims=True), 0.0)
        else:
            part = p[:, :LSE_LANES]
            for c in range(1, tk // LSE_LANES):
                part = part + p[:, c * LSE_LANES:(c + 1) * LSE_LANES]
        l_scr[...] = l_scr[...] * alpha + part
        acc_scr[...] = (
            acc_scr[...] * (alpha if dv == LSE_LANES else alpha[:, :1])
            + _paged._matmul(p.astype(v.dtype), v, ((1,), (0,))))
        m_scr[...] = m2

    live = (kb >= lo) & (kb <= hi)
    full = (kb >= flo) & (kb <= fhi)
    pl.when(live & full)(lambda: tile(False))
    pl.when(live & jnp.logical_not(full))(lambda: tile(True))

    @pl.when(kb == nk - 1)
    def _finish():
        l = jnp.sum(l_scr[...], axis=-1, keepdims=True)
        o_ref[0, 0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def chain_attention_pallas(q, pool_k, pool_v, table, pos, block_step=None,
                           interpret=None, group=1, window=None, scale=None,
                           out_dtype=None, value_lanes=None, sink=None,
                           row_tile=None, key_tile=None):
    """The walk of the module docstring: ``q [S, W, hk * group, dk]``,
    ``pos [S, W]`` -> ``[S, W, hk * group, dv]`` in ``out_dtype``.
    ``block_step`` is accepted for signature parity and ignored;
    ``row_tile`` and ``key_tile`` (``ROW_TILE``, ``KEY_TILE``) are for
    the tests and the walk.  One inner ``jit`` for all the planes of a
    stack that share a geometry: the program that holds them lowers the
    kernel once a geometry, not once a layer."""
    del block_step
    if pool_v is None or value_lanes is not None:
        raise ValueError("chain_attention: a latent plane keeps the dense "
                         "spelling (kernels.paged_attention.attend)")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    h, dk = q.shape[2:]
    if h % group or h // group > pool_k.shape[2]:
        raise ValueError(f"chain_attention: {h} query heads in groups of "
                         f"{group} over a pool of {pool_k.shape[2]} K/V rows")
    return _chain_call(
        q, pool_k, pool_v, table, pos, sink, group=int(group), window=window,
        scale=1.0 / float(dk) ** 0.5 if scale is None else float(scale),
        out_dtype=jnp.dtype(q.dtype if out_dtype is None else out_dtype).name,
        interpret=bool(interpret), row_tile=row_tile or ROW_TILE,
        key_tile=key_tile)


@functools.partial(jax.jit, static_argnames=(
    "group", "window", "scale", "out_dtype", "interpret", "row_tile",
    "key_tile"))
def _chain_call(q, pool_k, pool_v, table, pos, sink, *, group, window, scale,
                out_dtype, interpret, row_tile, key_tile):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, W, h, dk = q.shape
    hk, N = h // group, W * group
    B, NB, dv = pool_k.shape[1], table.shape[1], pool_v.shape[-1]
    tq = _tile(N, row_tile, 8)
    n = _paged.window_entries(NB, B, W, window)
    tk = key_tile or min(KEY_TILE, -(-n * B // 128) * 128)
    k, v, base = _gathered(pool_k, pool_v, table, pos, hk, window, n, tk)
    nq, nk = N // tq, k.shape[2] // tk
    # the rows of K/V head j: row w * group + g is query head
    # j * group + g at pos[s, w]
    qh = q.reshape(S, W, hk, group, dk).transpose(0, 2, 1, 3, 4)
    qh = qh.reshape(S, hk, N, dk)
    at = jnp.repeat(pos.astype(jnp.int32), group, axis=1)
    bounds = _tile_bounds(at, base, window, tq, tk, nk, NB * B)

    def kv_at(s, j, iq, kb, bounds):
        cell = s * nq + iq
        lo = bounds[0, cell]
        return (s, j, jnp.clip(kb, lo, jnp.maximum(bounds[1, cell], lo)), 0)

    def row_at(s, j, iq, kb, bounds):
        return (s, j, iq, 0)

    in_specs = [pl.BlockSpec((1, 1, tq, dk), row_at),
                pl.BlockSpec((1, 1, tk, dk), kv_at),
                pl.BlockSpec((1, 1, tk, dv), kv_at),
                pl.BlockSpec((1, tq, 1), lambda s, j, iq, kb, b: (s, iq, 0))]
    args = [qh, k, v, at[:, :, None]]
    if sink is not None:
        # [hk, group, lanes]: head j's group of logits, lane-replicated
        in_specs.append(pl.BlockSpec(
            (1, group, LSE_LANES), lambda s, j, iq, kb, b: (j, 0, 0)))
        args.append(jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(hk, group, 1),
            (hk, group, LSE_LANES)))
    ctx = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, group=group,
                          nq=nq, nk=nk, tk=tk, end=NB * B,
                          with_sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, hk, nq, nk), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, tq, dv), row_at),
            scratch_shapes=[pltpu.VMEM((tq, LSE_LANES), jnp.float32),
                            pltpu.VMEM((tq, LSE_LANES), jnp.float32),
                            pltpu.VMEM((tq, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, hk, N, dv), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="chain_attention",
    )(bounds, *args)
    ctx = ctx.reshape(S, hk, W, group, dv).transpose(0, 2, 1, 3, 4)
    return ctx.reshape(S, W, h, dv)


# -- registration ------------------------------------------------------------

class _ChainXlaRef:
    call = staticmethod(_paged.dense_window)


class _ChainPallasTpu:
    call = staticmethod(chain_attention_pallas)


register_kernel("chain_attention", "xla_ref", _ChainXlaRef)
register_kernel("chain_attention", "pallas_tpu", _ChainPallasTpu,
                available=_paged._tpu_available)
