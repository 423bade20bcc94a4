"""Power retention: a mixer whose memory of the context is a STATE of
fixed size, not a K/V plane (arXiv:2507.04239, degree 2).

For a K/V head with query heads ``i`` of its group, rows ``s <= t``::

    a[t, s] = exp(sum_{r=s+1..t} lg_r) * (q_t[i] . k_s / sqrt(d)) ** 2
    y_t[i]  = sum_s a[t, s] v_s / (sum_s a[t, s] + eps)

which is linear in the context through the features ``phi`` (``phi(q) .
phi(k) = (q . k) ** 2 / d`` exactly): a K/V head holds ``S_t = g_t
S_{t-1} + phi(k_t) v_t^T`` and ``z_t = g_t z_{t-1} + phi(k_t)``, ``g =
exp(lg)``, and ``y_t[i] = phi(q_t[i])^T S_t / (phi(q_t[i])^T z_t +
eps)``.

**The features and the state's layout.**  ``phi(u)`` is the upper
triangle of ``u u^T`` laid out by CYCLIC DIAGONALS, so that it is made
from the ``d`` lanes of ``u`` by lane rotations alone: block ``r`` (``r =
0 .. d / 2``) is ``c_r u * roll(u, r) / sqrt(d)``, ``c`` 1 on the
diagonal, ``sqrt 2`` for ``0 < r < d / 2`` (every unordered pair at
cyclic distance ``r`` once) and 1 at ``r = d / 2`` (every such pair
twice).  That is ``d / 2 + 1`` blocks of ``d`` lanes, ``stored_rows(d)``
= 8,320 features at ``d`` 128 where the triangle has
``published_rows(d)`` = 8,256: the last block holds each of its 64 pairs
twice.  The state arrays are the engine's per-slot ones::

    S  [slots, kv_heads, stored_rows, d]   float32, row r * d + j, lane a:
                                           sum_s decay v_s[j] phi(k_s)[r, a]
    z  [slots, kv_heads, stored_rows]      float32, entry r * d + a

Two calls, each through the registry (``pallas_tpu`` on a TPU,
``xla_ref`` elsewhere: the same mathematics in ``jnp``), and each
returning the state arrays WHOLE, updated in place when the caller
donates them:

* ``step(S, z, q, k, v, lg, valid)``: one row a slot.  The Mosaic kernel
  (HLO name ``retention_step``) runs a grid over the LIVE slots only
  (their ids a scalar-prefetch argument, the grid's bound the number of
  them): a slot's state streams through VMEM in tiles of ``TILE_BLOCKS``
  feature blocks, each tile decayed, given its rank-one update, weighed
  by the group's query features and written back to where it came from.
  A dead slot's state is never read and never written.
* ``chunk(S, z, slot, fresh, q, k, v, lg, valid)``: a piece of up to
  ``CALL_ROWS`` rows of ONE slot in ONE call (HLO name
  ``retention_chunk``): rows attend each other in the quadratic form,
  the state before the piece through ``phi(q)``, and the piece leaves the
  state advanced.  Rows that are not ``valid`` (bucket padding, a suffix
  of the piece) advance nothing; ``fresh`` starts from zeros whatever the
  slot held, and never reads it.
  The kernel walks the call's rows in TILES of ``CHUNK_ROWS`` inside one
  grid step a K/V head: the head's state is loaded once and written
  once; its two bfloat16 pieces, the blocks' coefficients ``c_r`` folded
  in, are made once into VMEM; a tile's scores run against the piece's
  rows up to its own diagonal; its read of the state accumulates in VMEM
  over groups of ``TILE_BLOCKS`` feature blocks laid side by side (one
  MXU contraction of ``TILE_BLOCKS * d`` a group); the decay from the
  piece's start multiplies the ROWS of that product, so ``phi(q)`` is
  made from the rows as they came (of two bfloat16 rows it is exactly
  two bfloat16 pieces); the normaliser's read is ``q^T Z q`` with ``Z
  [d, d]`` gathered from ``z`` before the call; the state is advanced
  once, by one contraction over all the piece's rows.
  ``chunk_rows(width)`` names the calls of a window (one; a window wider
  than any rung of the prefill ladder is consecutive calls, the state
  threaded through, which ``serving/batched_decode._Cache.advance``
  makes).

Inference only (no VJP).
"""

import functools
import math

import jax
import jax.numpy as jnp

from .paged_attention import _tpu_available
from .registry import register_kernel, resolve

__all__ = ["step", "chunk", "phi", "stored_rows", "published_rows",
           "feature_blocks", "retention_step_ref", "retention_chunk_ref",
           "retention_step_pallas", "retention_chunk_pallas", "TILE_BLOCKS",
           "CHUNK_ROWS", "CALL_ROWS", "chunk_rows"]

# feature blocks ([d, d] float32 tiles of a K/V head's state) a grid step
# of the step kernel streams: 13 of 65 at d 128 is 852 kB in and as much
# out, both double-buffered
TILE_BLOCKS = 13
# a K/V head's whole state (4.26 MB at d 128) is one block of the chunk
# kernel, in and out and double-buffered: more than Mosaic's 16 MiB
# default scoped VMEM
_CHUNK_VMEM_BYTES = 64 << 20
# the rows of a TILE of the chunk kernel's walk over its call's rows: a
# tile's scores ``[G * CHUNK_ROWS, rows up to its diagonal]`` and its
# features are what the kernel holds in VMEM beside the state
CHUNK_ROWS = 128
# the rows of the widest ``chunk`` call, the widest rung of the prefill
# ladder (``serving.batched_decode.PREFILL_PIECE``): a call's decays
# ``[CALL_ROWS, CALL_ROWS]`` float32 sit in VMEM twice
CALL_ROWS = 512
_HIGHEST = jax.lax.Precision.HIGHEST


def feature_blocks(d):
    return d // 2 + 1


def stored_rows(d):
    """Features the layout stores a K/V head: ``(d / 2 + 1) * d``."""
    return feature_blocks(d) * d


def published_rows(d):
    """Features of the upper triangle: ``d (d + 1) / 2``."""
    return d * (d + 1) // 2


def _coefs(d):
    return [(1.0 if r in (0, d // 2) else math.sqrt(2.0)) / math.sqrt(d)
            for r in range(feature_blocks(d))]


def phi(u):
    """``[..., d] -> [..., d / 2 + 1, d]`` float32 (module docstring)."""
    u = u.astype(jnp.float32)
    d = u.shape[-1]
    if d % 2:
        raise ValueError(f"phi needs an even width, got {d}")
    rolled = jnp.stack([jnp.roll(u, r, axis=-1)
                        for r in range(feature_blocks(d))], axis=-2)
    coefs = jnp.asarray(_coefs(d), jnp.float32)
    return coefs[:, None] * u[..., None, :] * rolled


def chunk_rows(width):
    """The rows of the ``chunk`` CALLS that advance a state over a window
    of ``width`` rows: ONE up to ``CALL_ROWS`` (the kernel walks its rows
    in tiles of ``CHUNK_ROWS`` itself), whole calls of that and then the
    rest for a window wider than any rung."""
    full, rest = divmod(int(width), CALL_ROWS)
    return [CALL_ROWS] * full + ([rest] if rest else [])


def step(S, z, q, k, v, lg, valid, eps=1e-6):
    """One row a slot: ``q [N, h, d]``, ``k``/``v [N, kv, d]``, ``lg [N,
    kv]`` float32, ``valid [N]`` bool -> ``(y [N, h, d] float32, S',
    z')``; a slot that is not valid keeps its state and reads zeros."""
    return resolve("retention").impl.step(S, z, q, k, v, lg, valid, eps=eps)


def chunk(S, z, slot, fresh, q, k, v, lg, valid, eps=1e-6):
    """A piece of ONE slot: ``q [C, h, d]``, ``k``/``v [C, kv, d]``, ``lg
    [C, kv]``, ``valid [C]`` bool (a prefix of the rows), ``slot`` and
    ``fresh`` scalars -> ``(y [C, h, d] float32, S', z')``."""
    return resolve("retention").impl.chunk(S, z, slot, fresh, q, k, v, lg,
                                           valid, eps=eps)


# -- xla_ref -----------------------------------------------------------------

def retention_step_ref(S, z, q, k, v, lg, valid, eps=1e-6):
    f32 = jnp.float32
    N, hk, _, d = S.shape
    R, G = feature_blocks(d), q.shape[1] // hk
    S5, z4 = S.reshape(N, hk, R, d, d), z.reshape(N, hk, R, d)
    g = jnp.exp(lg.astype(f32))
    pk = phi(k)                                              # [N, hk, R, d]
    Sn = (g[..., None, None, None] * S5
          + v.astype(f32)[:, :, None, :, None] * pk[:, :, :, None, :])
    zn = g[..., None, None] * z4 + pk
    pq = phi(q.reshape(N, hk, G, d))                         # [N, hk, G, R, d]
    num = jnp.einsum("nkgra,nkrva->nkgv", pq, Sn, precision=_HIGHEST)
    den = jnp.einsum("nkgra,nkra->nkg", pq, zn, precision=_HIGHEST)
    y = (num / (den[..., None] + eps)).reshape(N, hk * G, d)
    live = valid.reshape(N, 1, 1, 1, 1)
    return (jnp.where(valid[:, None, None], y, 0.0),
            jnp.where(live, Sn, S5).reshape(S.shape),
            jnp.where(live[..., 0], zn, z4).reshape(z.shape))


def _piece_decays(lg, valid):
    """What a piece's gates give, float32: ``cum [C, kv]`` (the log decay
    from the piece's start through row ``t``; a row that is not valid
    adds nothing) and ``dec [kv, C, C]`` (``exp(cum_t - cum_s)`` for ``s
    <= t``, else 0)."""
    lg = jnp.where(valid[:, None], lg.astype(jnp.float32), 0.0)
    cum = jnp.cumsum(lg, axis=0)
    C = lg.shape[0]
    causal = jnp.tril(jnp.ones((C, C), bool))
    diff = cum.T[:, :, None] - cum.T[:, None, :]             # [kv, t, s]
    return cum, jnp.exp(jnp.where(causal[None], diff, -jnp.inf))


def retention_chunk_ref(S, z, slot, fresh, q, k, v, lg, valid, eps=1e-6):
    f32 = jnp.float32
    N, hk, _, d = S.shape
    C, R, G = q.shape[0], feature_blocks(d), q.shape[1] // hk
    keep = jnp.where(fresh, 0.0, 1.0).astype(f32)
    S0 = jax.lax.dynamic_index_in_dim(S, slot, 0, False).reshape(
        hk, R, d, d) * keep
    z0 = jax.lax.dynamic_index_in_dim(z, slot, 0, False).reshape(
        hk, R, d) * keep
    cum, dec = _piece_decays(lg, valid)
    k32 = jnp.where(valid[:, None, None], k.astype(f32), 0.0)
    v32 = v.astype(f32)
    qg = q.reshape(C, hk, G, d).astype(f32)
    # the piece's rows attend each other in the quadratic form
    s = jnp.einsum("tkgd,skd->kgts", qg, k32, precision=_HIGHEST)
    a = s * s * (1.0 / d) * dec[:, None]
    num = jnp.einsum("kgts,skv->tkgv", a, v32, precision=_HIGHEST)
    den = jnp.moveaxis(jnp.sum(a, axis=-1), -1, 0)            # [C, hk, G]
    # and the state before the piece through phi(q)
    pq = phi(qg)                                          # [C, hk, G, R, d]
    e = jnp.exp(cum)                                          # [C, hk]
    num = num + e[..., None, None] * jnp.einsum(
        "tkgra,krva->tkgv", pq, S0, precision=_HIGHEST)
    den = den + e[..., None] * jnp.einsum(
        "tkgra,kra->tkg", pq, z0, precision=_HIGHEST)
    y = (num / (den[..., None] + eps)).reshape(C, hk * G, d)
    # the piece leaves the state advanced
    w = jnp.exp(cum[-1][None] - cum)                          # [C, hk]
    pk = phi(k32)                                             # [C, hk, R, d]
    gc = jnp.exp(cum[-1])
    Sn = gc[:, None, None, None] * S0 + jnp.einsum(
        "skv,skra->krva", w[..., None] * v32, pk, precision=_HIGHEST)
    zn = gc[:, None, None] * z0 + jnp.einsum(
        "sk,skra->kra", w, pk, precision=_HIGHEST)
    return (y,
            jax.lax.dynamic_update_index_in_dim(
                S, Sn.reshape(S.shape[1:]), slot, 0),
            jax.lax.dynamic_update_index_in_dim(
                z, zn.reshape(z.shape[1:]), slot, 0))


# -- pallas_tpu --------------------------------------------------------------

def _tile_blocks(R):
    """The widest divisor of ``R`` within ``TILE_BLOCKS``."""
    return max(b for b in range(1, min(R, TILE_BLOCKS) + 1) if R % b == 0)


def _interpret(interpret):
    return (jax.default_backend() != "tpu") if interpret is None \
        else bool(interpret)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _pieces(x, n):
    """``x`` as ``n`` bfloat16 pieces that sum to it to ``8 n`` bits."""
    out = []
    for _ in range(n):
        out.append(x.astype(jnp.bfloat16))
        x = x - out[-1].astype(jnp.float32)
    return tuple(out)


def _halves(x):
    """``x`` as bfloat16 pieces that sum to it to 16 bits (one piece for
    an array that is bfloat16 already)."""
    return (x,) if x.dtype == jnp.bfloat16 else _pieces(x, 2)


def _dot_split(ap, bp, dims, order):
    """The product of two operands given as bfloat16 pieces, largest
    first: the pieces' products whose ranks sum under ``order``, one MXU
    pass each."""
    out = None
    for i, a in enumerate(ap):
        for j, b in enumerate(bp):
            if i + j < order:
                out = _dot(a, b, dims) if out is None \
                    else out + _dot(a, b, dims)
    return out


def _dot_hl(a, b, dims):
    """A float32 product on the MXU at 16 bits of each operand: the
    pieces' products but the smallest (three bfloat16 passes for two
    float32 operands)."""
    return _dot_split(_halves(a), _halves(b), dims, 2)


def retention_step_pallas(S, z, q, k, v, lg, valid, eps=1e-6,
                          interpret=None):
    """The Mosaic step kernel (module docstring)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    N, hk, _, d = S.shape
    R, G = feature_blocks(d), q.shape[1] // hk
    if G + 1 > 8 or d % 8:
        raise ValueError(f"retention_step: {G} query heads a K/V head (at "
                         f"most 7) of {d} lanes (a multiple of 8)")
    TB = _tile_blocks(R)
    T = R // TB
    coefs = _coefs(d)
    # the live slots first: the grid visits that many
    order = jnp.argsort(jnp.logical_not(valid), stable=True).astype(
        jnp.int32)
    n_live = jnp.sum(valid, dtype=jnp.int32)
    # one [8, d] tile a (slot, K/V head): the key row, then the group's
    # query rows
    u = jnp.concatenate(
        [k.astype(f32)[:, :, None], q.astype(f32).reshape(N, hk, G, d),
         jnp.zeros((N, hk, 7 - G, d), f32)], axis=2)
    vrow = v.astype(f32)[:, :, None]                          # [N, hk, 1, d]
    grow = jnp.broadcast_to(jnp.exp(lg.astype(f32))[:, :, None, None],
                            (N, hk, 1, d))
    z4 = z.reshape(N, hk, R, d)

    def kernel(order_ref, u_ref, v_ref, g_ref, s_ref, z_ref,
               y_ref, so_ref, zo_ref, phi_ref, vb_ref, acc_ref):
        t = pl.program_id(1) % T
        gate = g_ref[...]                                     # [1, d]

        @pl.when(t == 0)
        def _():
            uu = u_ref[...]                                   # [8, d]
            for r in range(R):
                ph = coefs[r] * uu * (pltpu.roll(uu, r, 1) if r else uu)
                phi_ref[r] = ph
                zo_ref[r:r + 1, :] = (gate * z_ref[r:r + 1, :]
                                      + ph[0:1, :])
            # v down the sublanes: [j, a] = v[j]
            vb_ref[...] = jnp.broadcast_to(v_ref[...], (d, d)).T
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def tile(first):
            def rows8(gi, carry):
                j0 = pl.multiple_of(gi * 8, 8)
                vb = vb_ref[pl.ds(j0, 8), :]                  # [8, d]
                accs = [jnp.zeros((8, d), f32)] * G
                for rr in range(TB):
                    ph = phi_ref[first + rr]                  # [8, d]
                    at = pl.ds(pl.multiple_of(rr * d + gi * 8, 8), 8)
                    new = gate * s_ref[at, :] + vb * ph[0:1, :]
                    so_ref[at, :] = new
                    accs = [acc + new * ph[i + 1:i + 2, :]
                            for i, acc in enumerate(accs)]
                for i in range(G):
                    acc_ref[i, pl.ds(j0, 8), :] += accs[i]
                return carry

            jax.lax.fori_loop(0, d // 8, rows8, 0)

        for tt in range(T):
            pl.when(t == tt)(functools.partial(tile, tt * TB))

        @pl.when(t == T - 1)
        def _():
            dacc = jnp.zeros((8, d), f32)
            for r in range(R):
                dacc = dacc + phi_ref[r] * zo_ref[r:r + 1, :]
            # every lane of row i + 1: phi(q_i) . z
            den = jax.lax.dot_general(
                dacc, jnp.ones((d, d), f32), (((1,), (0,)), ((), ())),
                precision=_HIGHEST, preferred_element_type=f32)
            y_ref[...] = jnp.zeros_like(y_ref)
            for i in range(G):
                # sum over the lanes of acc[i], the value's index on lanes
                num = jax.lax.dot_general(
                    jnp.ones((8, d), f32), acc_ref[i],
                    (((1,), (1,)), ((), ())), precision=_HIGHEST,
                    preferred_element_type=f32)
                y_ref[i + 1:i + 2, :] = num[0:1, :] / (
                    den[i + 1:i + 2, :] + eps)

    def at(i, order):
        return order[i // T]

    row = lambda rows: pl.BlockSpec(                          # noqa: E731
        (None, None, rows, d), lambda j, i, order: (at(i, order), j, 0, 0))
    tile_spec = pl.BlockSpec(
        (None, None, TB * d, d),
        lambda j, i, order: (at(i, order), j, i % T, 0))
    y, Sn, zn = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(hk, n_live * T),
            in_specs=[row(8), row(1), row(1), tile_spec, row(R)],
            out_specs=[row(8), tile_spec, row(R)],
            scratch_shapes=[pltpu.VMEM((R, 8, d), f32),
                            pltpu.VMEM((d, d), f32),
                            pltpu.VMEM((G, d, d), f32)]),
        out_shape=[jax.ShapeDtypeStruct((N, hk, 8, d), f32),
                   jax.ShapeDtypeStruct(S.shape, f32),
                   jax.ShapeDtypeStruct(z4.shape, f32)],
        # operands count the scalar-prefetch argument
        input_output_aliases={4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(interpret),
        name="retention_step",
    )(order, u, vrow, grow, S, z4)
    y = y[:, :, 1:G + 1].reshape(N, hk * G, d)
    # a dead slot's rows were never visited
    return (jnp.where(valid[:, None, None], y, 0.0), Sn,
            zn.reshape(z.shape))


def _row_tile(C):
    """The rows of a tile of the chunk kernel's walk over a call of ``C``
    rows: the widest multiple of 8 within ``CHUNK_ROWS`` that divides
    it."""
    return max(t for t in range(8, min(C, CHUNK_ROWS) + 1, 8) if C % t == 0)


def retention_chunk_pallas(S, z, slot, fresh, q, k, v, lg, valid, eps=1e-6,
                           interpret=None):
    """The Mosaic chunk kernel (module docstring).  One inner ``jit`` for
    all the layers of a stack: the program that holds them lowers the
    kernel once, not once a layer."""
    return _chunk_call(S, z, slot, fresh, q, k, v, lg, valid, eps=float(eps),
                       interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _chunk_call(S, z, slot, fresh, q, k, v, lg, valid, *, eps, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32, bf16 = jnp.float32, jnp.bfloat16
    N, hk, _, d = S.shape
    C, R, G = q.shape[0], feature_blocks(d), q.shape[1] // hk
    if C % 8 or d % 8:
        raise ValueError(f"retention_chunk: {C} rows of {d} lanes (both "
                         f"multiples of 8)")
    T = _row_tile(C)
    nT, GT = C // T, G * T
    U = _tile_blocks(R)
    nG = R // U
    inv_d = 1.0 / d
    norm = 1.0 / math.sqrt(d)
    cum, dec = _piece_decays(lg, valid)
    kz = jnp.where(valid[:, None, None], k, jnp.zeros_like(k))
    # a row tile's rows of a K/V head's group together, tile-major and
    # then head-major: [hk, nT * G * T, d]
    qt = jnp.transpose(q.reshape(nT, T, hk, G, d), (2, 0, 3, 1, 4)).reshape(
        hk, G * C, d)
    kb, vb = jnp.moveaxis(kz, 0, 1), jnp.moveaxis(v, 0, 1)    # [hk, C, d]
    # the decay from the piece's start through a row: what the state
    # before the piece is read through, a factor of the ROW
    e = jnp.exp(cum).T[:, :, None]                            # [hk, C, 1]
    w = jnp.exp(cum[-1][None] - cum)                          # [C, hk]
    # (w v)^T over the rows, then w itself: one product advances S and z
    vdT = jnp.concatenate(
        [jnp.moveaxis(w[..., None] * v.astype(f32), 0, 2),
         w.T[:, None, :], jnp.zeros((hk, 7, C), f32)], axis=1)
    gc = jnp.broadcast_to(jnp.exp(cum[-1])[:, None, None], (hk, 8, d))
    z4 = z.reshape(N, hk, R, d)
    slot = jnp.asarray(slot, jnp.int32)
    # phi(q) . z as q^T Z q: Z[b, a] holds c_r z[r, a] at r = (a - b) mod
    # d, the cyclic distance the features pair the lanes a and b at
    lane = jnp.arange(d)
    dist = (lane[None, :] - lane[:, None]) % d
    z0 = jax.lax.dynamic_index_in_dim(z4, slot, 0, False)     # [hk, R, d]
    zq = jnp.where(dist < R, (jnp.asarray(_coefs(d), f32)[:, None] * z0)[
        :, jnp.minimum(dist, R - 1), lane[None, :]], 0.0)     # [hk, d, d]
    head = jnp.stack([slot, jnp.asarray(fresh, jnp.int32)])
    exact = None if q.dtype == bf16 else _HIGHEST
    nt = (((1,), (1,)), ((), ()))

    def kernel(head_ref, qt_ref, kb_ref, vb_ref, dec_ref, e_ref, vdT_ref,
               gc_ref, zq_ref, s_ref, z_ref, y_ref, so_ref, zo_ref,
               qf_ref, acc_ref, den_ref, sh_ref, sl_ref, zs_ref):
        cont = head_ref[1] == 0
        gate = gc_ref[0:1, :]                                 # [1, d]

        def coef(r):
            return jnp.where((r == 0) | (r == R - 1), norm,
                             norm * math.sqrt(2.0))

        def feats(x, g):
            """Group ``g``'s ``U`` feature blocks of the float32 rows
            ``x`` less their coefficients, side by side on the lanes, as
            bfloat16 pieces (both of them exact where the rows were
            bfloat16: a product of two has 16 bits)."""
            hi, lo = zip(*(_pieces(x * pltpu.roll(x, g * U + i, 1), 2)
                           for i in range(U)))
            return jnp.concatenate(hi, axis=1), jnp.concatenate(lo, axis=1)

        # the piece's rows attend each other in the quadratic form, a
        # tile up to its own diagonal
        for i in range(nT):
            rows, W = slice(i * GT, (i + 1) * GT), (i + 1) * T
            s = jax.lax.dot_general(
                qt_ref[rows, :], kb_ref[0:W, :], nt, precision=exact,
                preferred_element_type=f32)                   # [G T, W]
            a = (s * s * inv_d).reshape(G, T, W) * dec_ref[
                i * T:(i + 1) * T, 0:W][None]
            a = a.reshape(GT, W)
            y_ref[rows, :] = _dot_hl(a, vb_ref[0:W, :], ((1,), (0,)))
            den_ref[rows, :] = jnp.sum(a, axis=1, keepdims=True)

        @pl.when(cont)
        def _():
            # the state before the piece, read ONCE: its bfloat16 pieces
            # with the blocks' coefficients, a group's blocks side by
            # side on the lanes
            def split(g, carry):
                for i in range(U):
                    r = g * U + i
                    lanes = slice(i * d, (i + 1) * d)
                    sh_ref[g, :, lanes], sl_ref[g, :, lanes] = _pieces(
                        coef(r) * s_ref[pl.ds(pl.multiple_of(r * d, d), d),
                                        :], 2)
                return carry

            jax.lax.fori_loop(0, nG, split, 0)
            qf_ref[...] = qt_ref[...].astype(f32)
            acc_ref[...] = jnp.zeros_like(acc_ref)

            def read(n, carry):
                rows, g = pl.ds(pl.multiple_of(n // nG * GT, 8), GT), n % nG
                ph, pw = feats(qf_ref[rows, :], g)
                sh, sl = sh_ref[g], sl_ref[g]                 # [j, U a]
                acc_ref[rows, :] += (_dot(ph, sh, nt[0]) + _dot(pw, sh, nt[0])
                                     + _dot(ph, sl, nt[0]))
                return carry

            jax.lax.fori_loop(0, nT * nG, read, 0)
            zp = _pieces(zq_ref[...], 3)
            for i in range(nT):
                rows = slice(i * GT, (i + 1) * GT)
                # a tile's rows are its heads' copies of the same T rows
                et = jnp.concatenate([e_ref[i * T:(i + 1) * T, :]] * G,
                                     axis=0)
                u = _dot_split(_halves(qt_ref[rows, :]), zp,
                               ((1,), (0,)), 3)
                y_ref[rows, :] += et * acc_ref[rows, :]
                den_ref[rows, :] += et * jnp.sum(
                    qf_ref[rows, :] * u, axis=1, keepdims=True)

        y_ref[...] = y_ref[...] / (den_ref[...] + eps)

        # the state advanced ONCE over all the piece's rows
        kf = kb_ref[...].astype(f32)
        vp = _halves(vdT_ref[...])

        def advance(held):
            def group(g, carry):
                kp = feats(kf, g)
                upd = _dot_split(vp, kp, ((1,), (0,)), 2)     # [d + 8, U a]
                for i in range(U):
                    r = g * U + i
                    at = pl.ds(pl.multiple_of(r * d, d), d)
                    new = coef(r) * upd[:, i * d:(i + 1) * d]
                    if held:
                        so_ref[at, :] = gate * s_ref[at, :] + new[:d]
                        zs_ref[r, 1:2, :] = (gate * zs_ref[r, 0:1, :]
                                             + new[d:d + 1])
                    else:
                        so_ref[at, :] = new[:d]
                        zs_ref[r, 1:2, :] = new[d:d + 1]
                return carry

            if held:
                for r in range(R):
                    zs_ref[r, 0:1, :] = z_ref[r:r + 1, :]
            jax.lax.fori_loop(0, nG, group, 0)

        pl.when(cont)(functools.partial(advance, True))
        # a piece that starts a prompt never reads what the slot held
        pl.when(jnp.logical_not(cont))(functools.partial(advance, False))
        for r in range(R):
            zo_ref[r:r + 1, :] = zs_ref[r, 1:2, :]

    rows = lambda n, lanes: pl.BlockSpec(                     # noqa: E731
        (None, n, lanes), lambda j, head: (j, 0, 0))
    state = lambda n: pl.BlockSpec(                           # noqa: E731
        (None, None, n, d), lambda j, head: (head[0], j, 0, 0))
    y, Sn, zn = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(hk,),
            in_specs=[rows(G * C, d), rows(C, d), rows(C, d), rows(C, C),
                      rows(C, 1), rows(d + 8, C), rows(8, d), rows(d, d),
                      state(R * d), state(R)],
            out_specs=[rows(G * C, d), state(R * d), state(R)],
            # the rows in float32, their read of the state and their
            # normaliser; the state's two pieces; z's rows before | after
            scratch_shapes=[pltpu.VMEM((G * C, d), f32),
                            pltpu.VMEM((G * C, d), f32),
                            pltpu.VMEM((G * C, 1), f32),
                            pltpu.VMEM((nG, d, U * d), bf16),
                            pltpu.VMEM((nG, d, U * d), bf16),
                            pltpu.VMEM((R, 8, d), f32)]),
        out_shape=[jax.ShapeDtypeStruct((hk, G * C, d), f32),
                   jax.ShapeDtypeStruct(S.shape, f32),
                   jax.ShapeDtypeStruct(z4.shape, f32)],
        input_output_aliases={9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_CHUNK_VMEM_BYTES),
        interpret=interpret,
        name="retention_chunk",
    )(head, qt, kb, vb, dec, e, vdT, gc, zq, S, z4)
    y = jnp.transpose(y.reshape(hk, nT, G, T, d), (1, 3, 0, 2, 4)).reshape(
        C, hk * G, d)
    return y, Sn, zn.reshape(z.shape)


# -- registration ------------------------------------------------------------

class _RetentionXlaRef:
    step = staticmethod(retention_step_ref)
    chunk = staticmethod(retention_chunk_ref)


class _RetentionPallasTpu:
    step = staticmethod(retention_step_pallas)
    chunk = staticmethod(retention_chunk_pallas)


register_kernel("retention", "xla_ref", _RetentionXlaRef)
register_kernel("retention", "pallas_tpu", _RetentionPallasTpu,
                available=_tpu_available)
