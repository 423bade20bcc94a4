"""The gated delta rule with a decay a CHANNEL (Kimi Delta Attention,
arXiv:2510.26692), advanced in place for the slots that are live.

A layer's rows arrive as ``q, k, v [..., H D]`` (the three projections,
before their convolution), ``g [..., H D]`` float32 (the LOG decay of
every key lane, ``<= 0``) and ``beta [..., H]`` float32 (the write
strength, in ``(0, 2)`` where the architecture allows a reflection).  A
causal depthwise convolution of ``taps`` taps and no bias runs over the
``3 H D`` channels (a slot keeps its last ``taps - 1`` rows, the TAILS),
then SiLU; a head's query and key are normed to length 1 (``eps``
1e-6), the query scaled by ``D ** -0.5``.  With ``alpha_t = exp(g_t)`` a
head's state ``S [D, D]`` (key lanes down, value lanes across), float32::

    Sb  = Diag(alpha_t) S_{t-1}          what the state still holds
    u_t = v_t - Sb^T k_t                 what it does NOT hold of k_t yet
    S_t = Sb + beta_t k_t u_t^T
    o_t = S_t^T q_t

The rank-one term depends on the decayed state itself, so neither
``kernels/ssm.py`` nor ``kernels/retention.py`` (``S <- decay S + rank
one``) computes it; ``I - beta k k^T`` reflects where ``beta > 1``.

Two calls, each through the registry (``pallas_tpu`` on a TPU, ``xla_ref``
elsewhere) and each returning the state arrays WHOLE, updated in place
when the caller donates them; the signatures are what
``serving/batched_decode._Cache.advance`` hands any in-place recurrence:

* ``step(S, tail, q, k, v, g, beta, valid, **layer)``: one row a slot.
  The Mosaic kernel (HLO name ``delta_step``) runs a grid over the LIVE
  slots only (their ids a scalar-prefetch argument, the grid's bound
  their number) by blocks of ``_HEAD_BLOCK`` heads: a block of a slot's
  state streams through VMEM ONCE (two reductions down the key lanes
  give ``Sb^T k`` and ``Sb^T q``; ``o = Sb^T q + beta (q . k) u``), is
  written back where it came from, and a dead slot's state and tails are
  never read and never written.  At the HBM peak a slot's layer is 2 x
  4 MiB: 10.2 us on a v5e.  The convolution, the norms and ``exp(g)`` are
  rows of a few thousand lanes and stay XLA's.
* ``chunk(S, tail, slot, fresh, q, k, v, g, beta, valid, **layer)``: a
  piece of ONE slot in ONE call, the state loaded once and written once
  (``fresh`` starts from zeros whatever the slot held), the rows walked
  in tiles of ``TILE`` by the WY form (HLO scope ``delta_chunk``).
  Inside a tile the rows' corrections solve a unit lower-triangular
  system, with ``G`` the running sum of ``g`` from the tile's start::

      (I + tril(Kt Kh^T, -1) Diag(beta)) U = V - Kt S_0,
      Kt_i = k_i * exp(G_i),  (Kt Kh^T)_ij = sum_c k_ic k_jc exp(G_ic - G_jc)

  **No ``exp`` of a positive number is taken**: ``exp(-G_j)`` alone
  overflows float32 after 55 rows of the init's strongest decay (e^-1.6 a
  row).  Inside a sub-block of ``SUB`` rows the ratios are formed pair by
  pair as ``exp(G_i - G_j)``, ``i >= j``; between sub-blocks through the
  row BEFORE the later one, ``exp(G_i - G_ref) exp(G_ref - G_j)`` with
  ``G_i <= G_ref <= G_j``: both factors are at most 1, and one that
  underflows (a true ratio under e^-87) is the zero it stands for.  The
  system is solved by forward substitution in the sub-blocks and a block
  substitution across them; the tile's outputs and the state's update are
  four products a head against the carried state.  XLA einsums in float32
  at ``HIGHEST`` on both backends: a tile's state traffic is 8 MiB a
  layer, 80 us of a 512-row piece, which is all a Mosaic kernel that kept
  the state in VMEM could save (``benchmarks/delta_walk.py`` times the
  call on the chip).  Rows that are not ``valid`` (a suffix of the piece)
  advance nothing.

``layer`` is ``conv_w [3 H D, taps]`` and ``heads``.  ``delta_scan_ref``
is the recurrence row by row, what both are tested against.  Inference
only (no VJP).
"""

import jax
import jax.numpy as jnp

from .paged_attention import _tpu_available
from .registry import register_kernel, resolve

__all__ = ["step", "chunk", "chunk_rows", "state_shapes", "delta_step_ref",
           "delta_step_pallas", "delta_chunk", "delta_scan_ref",
           "CHUNK_SCOPE", "TILE", "SUB"]

CHUNK_SCOPE = "delta_chunk"
# rows a WY tile solves together, and the rows of the sub-blocks inside
# which decay ratios are formed pair by pair (module docstring)
TILE, SUB = 64, 16
NORM_EPS = 1e-6
# heads of one slot a grid step of the step kernel streams: 8 x 64 KiB
# of state in, as much out, double-buffered
_HEAD_BLOCK = 8
_STEP_VMEM_BYTES = 32 << 20
_HIGHEST = jax.lax.Precision.HIGHEST


def _ein(spec, *ops):
    return jnp.einsum(spec, *ops, precision=_HIGHEST)


def state_shapes(heads, head_dim, taps):
    """``(S shape, tail shape)`` of what ONE slot holds of a layer."""
    return ((heads, head_dim, head_dim), (taps - 1, 3 * heads * head_dim))


def chunk_rows(width):
    """The rows of the ``chunk`` calls of a window: one, whatever its
    width (the chunked form walks ``TILE`` rows at a time itself)."""
    return [int(width)]


def step(S, tail, q, k, v, g, beta, valid, **layer):
    """One row a slot: ``q, k, v, g [slots, H D]``, ``beta [slots, H]``,
    ``valid [slots]`` bool -> ``(o [slots, H D] float32, S', tail')``; a
    slot that is not valid keeps its state and tails and reads zeros."""
    return resolve("delta_rule").impl.step(S, tail, q, k, v, g, beta, valid,
                                           **layer)


def chunk(S, tail, slot, fresh, q, k, v, g, beta, valid, **layer):
    """A piece of ONE slot: ``q, k, v, g [W, H D]``, ``beta [W, H]``,
    ``valid [W]`` bool (a prefix of the rows), ``slot`` and ``fresh``
    scalars -> ``(o [W, H D] float32, S', tail')``."""
    return resolve("delta_rule").impl.chunk(S, tail, slot, fresh, q, k, v, g,
                                            beta, valid, **layer)


# -- what both calls share ---------------------------------------------------

def _conv(rows, conv_w):
    """``silu(conv)`` float32 ``[..., W, C]`` of ``rows [..., taps - 1 +
    W, C]`` (the tails, then the call's rows); no bias."""
    f32 = jnp.float32
    taps = conv_w.shape[1]
    W = rows.shape[-2] - taps + 1
    cw = conv_w.astype(f32)
    return jax.nn.silu(sum(
        rows[..., j:j + W, :].astype(f32) * cw[:, j] for j in range(taps)))


def _heads(a, heads):
    """float32 ``q, k, v [..., H, D]`` of the convolved rows ``a [..., 3
    H D]``: q and k of length 1, q scaled by ``D ** -0.5``."""
    lead, C = a.shape[:-1], a.shape[-1] // 3
    D = C // heads
    q, k, v = (a[..., i * C:(i + 1) * C].reshape(*lead, heads, D)
               for i in range(3))
    unit = lambda x: x * jax.lax.rsqrt(                        # noqa: E731
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + NORM_EPS)
    return unit(q) * D ** -0.5, unit(k), v


def _step_rows(tail, q, k, v, conv_w, heads):
    """A decode step's rows: ``(rows [n, taps, C], q, k, v [n, H, D])``,
    ``rows`` the tails with the step's row behind them."""
    row = jnp.concatenate([q, k, v], axis=-1).astype(tail.dtype)
    rows = jnp.concatenate([tail, row[:, None]], axis=1)
    return (rows,) + _heads(_conv(rows, conv_w)[:, 0], heads)


# -- xla_ref -----------------------------------------------------------------

def delta_step_ref(S, tail, q, k, v, g, beta, valid, *, conv_w, heads):
    f32 = jnp.float32
    n = S.shape[0]
    rows, qh, kh, vh = _step_rows(tail, q, k, v, conv_w, heads)
    Sb = jnp.exp(g.astype(f32)).reshape(kh.shape)[..., None] * S
    u = vh - _ein("nhkv,nhk->nhv", Sb, kh)
    new = Sb + (beta.astype(f32)[..., None] * kh)[..., None] * u[..., None, :]
    o = _ein("nhkv,nhk->nhv", new, qh)
    live = valid[:, None, None]
    return (jnp.where(valid[:, None], o.reshape(n, -1), 0.0),
            jnp.where(live[..., None], new, S),
            jnp.where(live, rows[:, 1:], tail))


def delta_scan_ref(S0, q, k, v, g, beta):
    """The recurrence row by row, the equations letter for letter: ``S0
    [H, D, D]``, ``q, k, v, g [W, H, D]`` (q and k as they enter the
    rule: normed and scaled), ``beta [W, H]`` -> ``(o [W, H, D], S_W)``."""
    def one(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        Sb = jnp.exp(g_t)[..., None] * S
        u = v_t - jnp.sum(Sb * k_t[..., None], axis=-2)
        S = Sb + (b_t[:, None] * k_t)[..., None] * u[:, None, :]
        return S, jnp.sum(S * q_t[..., None], axis=-2)

    S, o = jax.lax.scan(one, S0, (q, k, v, g, beta))
    return o, S


def _tril_blocks(x, y, G, strict):
    """``sum_c x_ic y_jc exp(G_ic - G_jc)`` for ``j <= i`` (``j < i``
    where ``strict``), zero above: ``[..., C, C]`` of ``x, y, G [..., C,
    K]``, ``G`` the running log decay.  Pair by pair inside a sub-block
    of ``SUB`` rows; between sub-blocks through the row before the later
    one (module docstring: no exponent is positive)."""
    *lead, C, K = G.shape
    sub = min(SUB, C)
    nb = C // sub
    blocks = lambda a: a.reshape(*lead, nb, sub, K)            # noqa: E731
    xs, ys, Gs = blocks(x), blocks(y), blocks(G)
    at = jnp.arange(sub)
    keep = (at[:, None] > at[None, :]) if strict else (
        at[:, None] >= at[None, :])
    pair = jnp.where(keep[..., None], Gs[..., :, None, :]
                     - Gs[..., None, :, :], -jnp.inf)
    diag = jnp.sum(xs[..., :, None, :] * ys[..., None, :, :] * jnp.exp(pair),
                   axis=-1)                               # [.., nb, sub, sub]
    rows = []
    for i in range(nb):
        parts = []
        if i:
            ref = Gs[..., i - 1, -1:, :]                      # [..., 1, K]
            before = i * sub
            parts.append(_ein(
                "...tk,...jk->...tj", xs[..., i, :, :]
                * jnp.exp(Gs[..., i, :, :] - ref),
                y[..., :before, :] * jnp.exp(ref - G[..., :before, :])))
        parts.append(diag[..., i, :, :])
        if i < nb - 1:
            parts.append(jnp.zeros((*lead, sub, C - (i + 1) * sub),
                                   jnp.float32))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _unit_lower_inverse(A):
    """``(I + A)^-1`` of strictly lower-triangular ``A [..., C, C]``:
    forward substitution inside the ``SUB``-row diagonal blocks (every
    block of every tile and head at once, ``SUB - 1`` steps), then a
    block substitution down the block rows."""
    *lead, C, _ = A.shape
    sub = min(SUB, C)
    nb = C // sub
    at = jnp.arange(nb)
    D = A.reshape(*lead, nb, sub, nb, sub)[..., at, :, at, :]
    D = jnp.moveaxis(D, 0, -3)                            # [.., nb, sub, sub]
    T = jnp.broadcast_to(jnp.eye(sub, dtype=A.dtype), D.shape)
    for i in range(1, sub):
        T = T.at[..., i, :].add(-_ein("...j,...jk->...k", D[..., i, :i],
                                     T[..., :i, :]))
    full = T[..., 0, :, :]
    for i in range(1, nb):
        n = i * sub
        left = -_ein("...ab,...bc,...cd->...ad", T[..., i, :, :],
                    A[..., n:n + sub, :n], full)
        full = jnp.concatenate([
            jnp.concatenate([full, jnp.zeros((*lead, n, sub), A.dtype)], -1),
            jnp.concatenate([left, T[..., i, :, :]], -1)], axis=-2)
    return full


def _wy(S0, q, k, v, g, beta, tile):
    """The chunked form over ``W = c tile`` rows of one slot: ``S0 [H, D,
    D]``, ``q, k, v, g [W, H, D]``, ``beta [W, H]`` (``g`` and ``beta``
    zero on rows that advance nothing) -> ``(o [W, H, D], S_W)``."""
    W, H, D = q.shape
    c = W // tile
    tiles = lambda a: jnp.moveaxis(                            # noqa: E731
        a.reshape(c, tile, H, -1), 2, 1)                  # [c, H, tile, .]
    q, k, v, g = tiles(q), tiles(k), tiles(v), tiles(g)
    b = tiles(beta)[..., 0]                               # [c, H, tile]
    G = jnp.cumsum(g, axis=-2)
    A = _tril_blocks(k, k, G, strict=True) * b[..., None, :]
    Aq = _tril_blocks(q, k, G, strict=False)
    T = _unit_lower_inverse(A)
    e = jnp.exp(G)
    Uv = _ein("chts,chsv->chtv", T, v)
    Wk = _ein("chts,chsk->chtk", T, k * e)
    end = G[..., -1:, :]
    k_end = k * jnp.exp(end - G)

    def one(S, xs):
        Uv, Wk, Aq, qe, k_end, decay, b = xs
        Ub = b[..., None] * (Uv - _ein("htk,hkv->htv", Wk, S))
        o = _ein("htk,hkv->htv", qe, S) + _ein("hts,hsv->htv", Aq, Ub)
        return (decay[..., None] * S + _ein("htk,htv->hkv", k_end, Ub)), o

    S, o = jax.lax.scan(one, S0, (Uv, Wk, Aq, q * e, k_end,
                                  jnp.exp(end[..., 0, :]), b))
    return jnp.moveaxis(o, 1, 2).reshape(W, H, D), S


def delta_chunk(S, tail, slot, fresh, q, k, v, g, beta, valid, *, conv_w,
                heads):
    """The chunked form (module docstring), both backends'."""
    f32 = jnp.float32
    W, taps = q.shape[0], conv_w.shape[1]
    with jax.named_scope(CHUNK_SCOPE):
        keep = jnp.where(fresh, 0, 1)
        t0 = jax.lax.dynamic_index_in_dim(tail, slot, 0, False)
        t0 = t0 * keep.astype(tail.dtype)
        S0 = jax.lax.dynamic_index_in_dim(S, slot, 0, False) * keep.astype(
            f32)
        rows = jnp.concatenate(
            [t0, jnp.concatenate([q, k, v], axis=-1).astype(tail.dtype)],
            axis=0)
        qh, kh, vh = _heads(_conv(rows, conv_w), heads)
        # a row that is not real advances nothing: exp(0) = 1, beta = 0
        real = valid[:, None]
        gh = jnp.where(real, g.astype(f32), 0.0).reshape(kh.shape)
        bh = jnp.where(real, beta.astype(f32), 0.0)
        tile = min(TILE, -(-W // SUB) * SUB)
        pad = (-W) % tile
        if pad:
            qh, kh, vh, gh, bh = (
                jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                for a in (qh, kh, vh, gh, bh))
        o, Sn = _wy(S0, qh, kh, vh, gh, bh, tile)
        # the tails the NEXT call sees end at the last real row (real rows
        # are a prefix of the piece)
        n_real = jnp.sum(valid, dtype=jnp.int32)
        tn = jax.lax.dynamic_slice_in_dim(rows, n_real, taps - 1, axis=0)
        return (o[:W].reshape(W, -1),
                jax.lax.dynamic_update_index_in_dim(S, Sn, slot, 0),
                jax.lax.dynamic_update_index_in_dim(tail, tn, slot, 0))


# -- pallas_tpu --------------------------------------------------------------

def _interpret(interpret):
    return (jax.default_backend() != "tpu") if interpret is None \
        else bool(interpret)


def delta_step_pallas(S, tail, q, k, v, g, beta, valid, *, conv_w, heads,
                      interpret=None):
    """The Mosaic step kernel (module docstring)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    n, H, K, V = S.shape
    hb = min(_HEAD_BLOCK, H)
    if H % hb:
        raise ValueError(f"delta_step: {H} heads are not whole blocks of "
                         f"{hb}")
    J = H // hb
    # the rows: convolution, norms and the decay are XLA's
    rows, qh, kh, vh = _step_rows(tail, q, k, v, conv_w, heads)
    alpha = jnp.exp(g.astype(f32)).reshape(n, H, K)
    b = beta.astype(f32)
    # what multiplies a state's ROW runs down the sublanes, a head a lane:
    # [n, J, K, 4 hb] = alpha k | alpha q | beta k | alpha
    col = lambda a: jnp.swapaxes(a.reshape(n, J, hb, K), -1, -2)  # noqa: E731
    cols = jnp.concatenate([col(alpha * kh), col(alpha * qh),
                            col(b[..., None] * kh), col(alpha)], axis=-1)
    # beta (q . k), a head, along the lanes of its row
    qk = jnp.broadcast_to((b * jnp.sum(qh * kh, axis=-1))[..., None],
                          (n, H, V)).reshape(n, J, hb, V)
    # the live slots first: the grid visits that many
    order = jnp.argsort(jnp.logical_not(valid), stable=True).astype(
        jnp.int32)
    n_live = jnp.sum(valid, dtype=jnp.int32)

    def kernel(order_ref, cols_ref, v_ref, qk_ref, s_ref, o_ref, so_ref):
        for i in range(hb):
            down = lambda j: jnp.broadcast_to(                 # noqa: E731
                cols_ref[:, j * hb + i:j * hb + i + 1], (K, V))
            state = s_ref[i]
            u = v_ref[i:i + 1, :] - jnp.sum(down(0) * state, axis=0,
                                            keepdims=True)
            o_ref[i:i + 1, :] = (
                jnp.sum(down(1) * state, axis=0, keepdims=True)
                + qk_ref[i:i + 1, :] * u)
            so_ref[i] = down(3) * state + down(2) * jnp.broadcast_to(
                u, (K, V))

    block = lambda *shape: pl.BlockSpec(                       # noqa: E731
        (None, *shape),
        lambda i, j, order: (order[i], j) + (0,) * (len(shape) - 1))
    o, Sn = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_live, J),
            in_specs=[block(None, K, 4 * hb), block(None, hb, V),
                      block(None, hb, V), block(hb, K, V)],
            out_specs=[block(None, hb, V), block(hb, K, V)]),
        out_shape=[jax.ShapeDtypeStruct((n, J, hb, V), f32),
                   jax.ShapeDtypeStruct(S.shape, f32)],
        # operands count the scalar-prefetch argument
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_STEP_VMEM_BYTES),
        interpret=_interpret(interpret),
        name="delta_step",
    )(order, cols, vh.reshape(n, J, hb, V), qk, S)
    # a dead slot's rows were never visited
    return (jnp.where(valid[:, None], o.reshape(n, -1), 0.0), Sn,
            jnp.where(valid[:, None, None], rows[:, 1:], tail))


# -- registration ------------------------------------------------------------

class _DeltaXlaRef:
    step = staticmethod(delta_step_ref)
    chunk = staticmethod(delta_chunk)


class _DeltaPallasTpu:
    step = staticmethod(delta_step_pallas)
    chunk = staticmethod(delta_chunk)


register_kernel("delta_rule", "xla_ref", _DeltaXlaRef)
register_kernel("delta_rule", "pallas_tpu", _DeltaPallasTpu,
                available=_tpu_available)
