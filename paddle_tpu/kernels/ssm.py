"""Mamba-2's recurrence: a state MATRIX a head under ONE scalar decay
(arXiv:2405.21060), advanced in place for the slots that are live.

A layer's rows arrive as ``xbc [..., H P + 2 G N]`` (the inner rows, then
the ``G`` groups' ``B`` and ``C``) and ``dt [..., H]``.  A causal
depthwise convolution of ``taps`` taps runs over ``xbc``'s channels (a
slot keeps its last ``taps - 1`` rows, the TAILS), then SiLU; ``delta =
softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` are scalars a head, in
float32, and head ``h`` reads group ``h // (H / G)``::

    S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t      [H, P, N]
    y_t = S_t C_t + D x_t                                    [H, P]

**The state's layout.**  ``S[h, p, n]`` lies at ``[h // hp, n, (h % hp)
P + p]`` of a ``[H / hp, N, hp P]`` float32 array, ``hp = min(128 // P, H
/ G)`` heads side by side on the lanes (two at ``P`` 64: ``[32, 128,
128]`` for the published 64 heads, 2 MiB a layer a slot).  The lanes then
carry ``(head, p)`` exactly as a row's ``x`` and ``y`` do, the decay and
``delta x`` are rows, ``B`` and ``C`` run down the sublanes, and ``S C``
is a sum over sublanes: no lane reduction, no transpose, no lane left
empty.  ``state_shapes`` says what a slot holds; ``unpack`` gives ``[...,
H, P, N]``.

Two calls, each through the registry (``pallas_tpu`` on a TPU, ``xla_ref``
elsewhere) and each returning the state arrays WHOLE, updated in place
when the caller donates them; the signatures are what
``serving/batched_decode._Cache.advance`` hands any in-place recurrence:

* ``step(S, tail, xbc, dt, valid, **layer)``: one row a slot.  The
  Mosaic kernel (HLO name ``ssm_step``) runs a grid over the LIVE slots
  only (their ids a scalar-prefetch argument, the grid's bound their
  number): a slot's state streams through VMEM, is decayed, given its
  rank-one update, read through ``C`` and written back where it came
  from.  A dead slot's state and tails are never read and never
  written.  The convolution, ``delta`` and ``D x`` are rows of a few
  thousand lanes and stay XLA's.
* ``chunk(S, tail, slot, fresh, xbc, dt, valid, **layer)``: a piece of
  ONE slot in the CHUNKED form at ``chunk_size`` rows: within a chunk the
  quadratic form ``(C B^T . L) X`` with ``L`` the decay mask, across
  chunks the state (HLO scope ``ssm_chunk``).  XLA einsums at float32
  accuracy on both backends (``benchmarks/ssm_walk.py`` times them on the
  chip).  Rows that are not ``valid`` (a suffix of the piece) advance
  nothing; ``fresh`` starts from a zero state and zero tails whatever
  the slot held.

``layer`` is ``conv_w [C, taps]``, ``conv_b [C]``, ``dt_bias``,
``A_log``, ``D`` (each ``[H]``), ``heads``, ``groups`` and
``chunk_size``.  A layer with NO convolution passes ``conv_w=None``
(``conv_b`` is then not read): its rows enter the recurrence as they
are, with no activation, and its tails are ``[slots, 0, C]``
(``state_shapes(.., taps=1)``).  That is how a recurrence of CONSTANT
decay rides these two calls (Lightning Attention-2,
``serving.arch.SparseLightning``: ``x = v``, ``B = k``, ``C = q``, a
group a head, ``dt`` zeros under ``dt_bias = log(e - 1)`` so that
``delta`` is 1, ``A_log`` the log of the head's slope, ``D`` zeros).  ``ssm_scan_ref`` is the recurrence row by row, what
both are tested against.  Inference only (no VJP).
"""

import jax
import jax.numpy as jnp

from .paged_attention import _tpu_available
from .registry import register_kernel, resolve

__all__ = ["step", "chunk", "chunk_rows", "heads_per_row", "state_shapes",
           "pack", "unpack", "ssm_step_ref", "ssm_step_pallas",
           "ssm_chunk", "ssm_scan_ref", "CHUNK_SCOPE"]

# a slot's whole state (2 MiB) is one block of the step kernel, in and
# out and double-buffered
_STEP_VMEM_BYTES = 32 << 20
CHUNK_SCOPE = "ssm_chunk"
_HIGHEST = jax.lax.Precision.HIGHEST


def heads_per_row(heads, head_dim, groups):
    """Heads that share a lane row of the state: as many as 128 lanes
    hold, all of one group."""
    hp = max(1, min(128 // head_dim, heads // groups))
    if heads % groups or (heads // groups) % hp:
        raise ValueError(f"ssm: {heads} heads in {groups} groups of "
                         f"{head_dim} lanes do not pack {hp} a row")
    return hp


def state_shapes(heads, head_dim, groups, state, taps):
    """``(S shape, tail shape)`` of what ONE slot holds of a layer."""
    hp = heads_per_row(heads, head_dim, groups)
    return ((heads // hp, state, hp * head_dim),
            (taps - 1, heads * head_dim + 2 * groups * state))


def pack(S, groups):
    """``[..., H, P, N] -> [..., H / hp, N, hp P]`` (module docstring)."""
    *lead, H, P, N = S.shape
    hp = heads_per_row(H, P, groups)
    S = S.reshape(*lead, H // hp, hp, P, N)
    return jnp.moveaxis(S, -1, -3).reshape(*lead, H // hp, N, hp * P)


def unpack(S, heads, groups):
    """``[..., H / hp, N, hp P] -> [..., H, P, N]``."""
    *lead, R, N, L = S.shape
    hp = heads // R
    S = S.reshape(*lead, R, N, hp, L // hp)
    return jnp.moveaxis(S, -3, -1).reshape(*lead, heads, L // hp, N)


def chunk_rows(width):
    """The rows of the ``chunk`` calls of a window: one, whatever its
    width (the chunked form walks ``chunk_size`` rows at a time itself)."""
    return [int(width)]


def step(S, tail, xbc, dt, valid, **layer):
    """One row a slot: ``xbc [slots, C]``, ``dt [slots, H]``, ``valid
    [slots]`` bool -> ``(y [slots, H P] float32, S', tail')``; a slot that
    is not valid keeps its state and tails and reads zeros."""
    return resolve("ssm").impl.step(S, tail, xbc, dt, valid, **layer)


def chunk(S, tail, slot, fresh, xbc, dt, valid, **layer):
    """A piece of ONE slot: ``xbc [W, C]``, ``dt [W, H]``, ``valid [W]``
    bool (a prefix of the rows), ``slot`` and ``fresh`` scalars -> ``(y
    [W, H P] float32, S', tail')``."""
    return resolve("ssm").impl.chunk(S, tail, slot, fresh, xbc, dt, valid,
                                     **layer)


# -- what both calls share ---------------------------------------------------

def _taps(conv_w):
    """Taps of the layer's convolution; 1 for a layer that has none."""
    return 1 if conv_w is None else conv_w.shape[1]


def _conv(rows, conv_w, conv_b):
    """``silu(conv + b)`` float32 ``[..., W, C]`` of ``rows [..., taps - 1
    + W, C]`` (the tails, then the call's rows); the rows as they are,
    with no activation, for a layer with NO convolution (``conv_w``
    ``None``: its tails hold no row)."""
    f32 = jnp.float32
    if conv_w is None:
        return rows.astype(f32)
    taps = conv_w.shape[1]
    W = rows.shape[-2] - taps + 1
    cw = conv_w.astype(f32)
    acc = conv_b.astype(f32) + sum(
        rows[..., k:k + W, :].astype(f32) * cw[:, k] for k in range(taps))
    return jax.nn.silu(acc)


def _parts(a, dt, dt_bias, A_log, heads, groups, inner):
    """float32 ``x [..., H, P]``, ``B``, ``C [..., G, N]``, ``delta [...,
    H]`` and ``A [H]`` of the convolved rows ``a`` and the rows' ``dt``."""
    f32 = jnp.float32
    lead = a.shape[:-1]
    N = (a.shape[-1] - inner) // (2 * groups)
    x = a[..., :inner].reshape(*lead, heads, inner // heads)
    B = a[..., inner:inner + groups * N].reshape(*lead, groups, N)
    C = a[..., inner + groups * N:].reshape(*lead, groups, N)
    delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    return x, B, C, delta, -jnp.exp(A_log.astype(f32))


def _inner(S):
    """Lanes of a row's ``x``: ``H P``, from the packed state's shape."""
    return S.shape[-3] * S.shape[-1]


def _step_rows(S, tail, xbc, dt, conv_w, conv_b, dt_bias, A_log, heads,
               groups):
    """A decode step's rows: ``(rows, x, B, C, delta, A)``, ``rows [n,
    taps, C]`` the tails with the step's row behind them."""
    rows = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)], axis=1)
    a = _conv(rows, conv_w, conv_b)[:, 0]
    return (rows,) + _parts(a, dt, dt_bias, A_log, heads, groups, _inner(S))


# -- xla_ref -----------------------------------------------------------------

def ssm_step_ref(S, tail, xbc, dt, valid, *, conv_w, conv_b, dt_bias, A_log,
                 D, heads, groups, chunk_size=None):
    del chunk_size
    f32 = jnp.float32
    rows, x, B, C, delta, A = _step_rows(S, tail, xbc, dt, conv_w, conv_b,
                                         dt_bias, A_log, heads, groups)
    per = heads // groups
    Bh, Ch = jnp.repeat(B, per, axis=1), jnp.repeat(C, per, axis=1)
    S4 = unpack(S, heads, groups)                            # [n, H, P, N]
    new = (jnp.exp(delta * A)[..., None, None] * S4
           + (delta[..., None] * x)[..., None] * Bh[:, :, None, :])
    y = jnp.sum(new * Ch[:, :, None, :], axis=-1) + D.astype(f32)[:, None] * x
    live = valid[:, None, None]
    return (jnp.where(valid[:, None], y.reshape(y.shape[0], -1), 0.0),
            jnp.where(live[..., None], pack(new, groups), S),
            jnp.where(live, rows[:, 1:], tail))


def ssm_scan_ref(S0, x, B, C, delta, A, D):
    """The recurrence row by row, the equations letter for letter:
    ``S0 [H, P, N]``, ``x [W, H, P]``, ``B``/``C [W, H, N]`` (a head's
    group's), ``delta [W, H]``, ``A``/``D [H]`` -> ``(y [W, H, P], S_W)``."""
    def one(S, row):
        x_t, B_t, C_t, d_t = row
        S = (jnp.exp(d_t * A)[:, None, None] * S
             + (d_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.sum(S * C_t[:, None, :], axis=-1) + D[:, None] * x_t

    S, y = jax.lax.scan(one, S0, (x, B, C, delta))
    return y, S


def _chunked(S0, x, B, C, delta, A, Q):
    """The chunked form over ``W = c Q`` rows of one slot: ``S0 [H, P,
    N]``, ``x [W, H, P]``, ``B``/``C [W, G, N]``, ``delta [W, H]`` (zero
    on rows that advance nothing) -> ``(y [W, H, P] less D x, S_W)``."""
    W, H, P = x.shape
    G = B.shape[1]
    c, per = W // Q, H // G
    ein = lambda spec, *ops: jnp.einsum(spec, *ops, precision=_HIGHEST)
    a = (delta * A).reshape(c, Q, H)
    cum = jnp.cumsum(a, axis=1)                              # [c, Q, H]
    xd = (delta[..., None] * x).reshape(c, Q, G, per, P)
    Bc, Cc = B.reshape(c, Q, G, -1), C.reshape(c, Q, G, -1)
    # within a chunk: (C B^T . L) X, L[t, s] = exp(cum_t - cum_s), s <= t
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    diff = cum[:, :, None, :] - cum[:, None, :, :]           # [c, t, s, H]
    L = jnp.exp(jnp.where(causal[None, :, :, None], diff, -jnp.inf))
    L = L.reshape(c, Q, Q, G, per)
    cb = ein("ctgn,csgn->ctsg", Cc, Bc)
    y = ein("ctsgk,csgkp->ctgkp", L * cb[..., None], xd)
    # what a chunk adds to the state, decayed to the chunk's end
    w = jnp.exp(cum[:, -1:, :] - cum).reshape(c, Q, G, per)
    add = ein("csgk,csgkp,csgn->cgkpn", w, xd, Bc)           # [c, G, per, P, N]
    gate = jnp.exp(cum[:, -1, :]).reshape(c, G, per)
    # across chunks: the state
    S, before = S0.reshape(G, per, P, -1), []
    for i in range(c):
        before.append(S)
        S = gate[i][..., None, None] * S + add[i]
    e = jnp.exp(cum).reshape(c, Q, G, per)
    y = y + e[..., None] * ein("ctgn,cgkpn->ctgkp", Cc, jnp.stack(before))
    return y.reshape(W, H, P), S.reshape(S0.shape)


def ssm_chunk(S, tail, slot, fresh, xbc, dt, valid, *, conv_w, conv_b,
              dt_bias, A_log, D, heads, groups, chunk_size=128):
    """The chunked form (module docstring), both backends'."""
    f32 = jnp.float32
    W, taps = xbc.shape[0], _taps(conv_w)
    inner = _inner(S)
    with jax.named_scope(CHUNK_SCOPE):
        keep = jnp.where(fresh, 0, 1)
        t0 = jax.lax.dynamic_index_in_dim(tail, slot, 0, False)
        t0 = t0 * keep.astype(tail.dtype)
        S0 = unpack(jax.lax.dynamic_index_in_dim(S, slot, 0, False),
                    heads, groups) * keep.astype(f32)
        rows = jnp.concatenate([t0, xbc.astype(tail.dtype)], axis=0)
        a = _conv(rows, conv_w, conv_b)
        x, B, C, delta, A = _parts(a, dt, dt_bias, A_log, heads, groups,
                                   inner)
        # a row that is not real advances nothing: exp(0 A) = 1, 0 x B = 0
        delta = jnp.where(valid[:, None], delta, 0.0)
        Q = min(int(chunk_size), W)
        pad = (-W) % Q
        if pad:
            x, B, C, delta = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                              for v in (x, B, C, delta))
        y, Sn = _chunked(S0, x, B, C, delta, A, Q)
        y = y[:W] + D.astype(f32)[:, None] * x[:W]
        # the tails the NEXT call sees end at the last real row (real rows
        # are a prefix of the piece)
        n_real = jnp.sum(valid, dtype=jnp.int32)
        tn = jax.lax.dynamic_slice_in_dim(rows, n_real, taps - 1, axis=0)
        return (y.reshape(W, -1),
                jax.lax.dynamic_update_index_in_dim(
                    S, pack(Sn, groups), slot, 0),
                jax.lax.dynamic_update_index_in_dim(tail, tn, slot, 0))


# -- pallas_tpu --------------------------------------------------------------

def _interpret(interpret):
    return (jax.default_backend() != "tpu") if interpret is None \
        else bool(interpret)


def ssm_step_pallas(S, tail, xbc, dt, valid, *, conv_w, conv_b, dt_bias,
                    A_log, D, heads, groups, chunk_size=None,
                    interpret=None):
    """The Mosaic step kernel (module docstring)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del chunk_size
    f32 = jnp.float32
    n, R, N, L = S.shape
    inner, G = R * L, groups
    P = inner // heads
    rows_a_group = R // G
    # the rows: convolution, delta and the decay are XLA's
    rows, x, B, C, delta, A = _step_rows(S, tail, xbc, dt, conv_w, conv_b,
                                         dt_bias, A_log, heads, G)
    lanes = lambda v: jnp.broadcast_to(                       # noqa: E731
        v[..., None], (n, heads, P)).reshape(n, R, L)
    dx = (delta[..., None] * x).reshape(n, R, L)
    dec = lanes(jnp.exp(delta * A))
    # B and C down the sublanes, a group a lane: [n, N, 2 G]
    bc = jnp.moveaxis(jnp.concatenate([B, C], axis=1), 1, 2)
    # the live slots first: the grid visits that many
    order = jnp.argsort(jnp.logical_not(valid), stable=True).astype(
        jnp.int32)
    n_live = jnp.sum(valid, dtype=jnp.int32)

    def kernel(order_ref, dx_ref, dec_ref, bc_ref, s_ref, y_ref, so_ref):
        for g in range(G):
            Bb = jnp.broadcast_to(bc_ref[:, g:g + 1], (N, L))
            Cb = jnp.broadcast_to(bc_ref[:, G + g:G + g + 1], (N, L))
            for r in range(g * rows_a_group, (g + 1) * rows_a_group):
                new = (dec_ref[r:r + 1, :] * s_ref[r]
                       + Bb * dx_ref[r:r + 1, :])
                so_ref[r] = new
                y_ref[r:r + 1, :] = jnp.sum(new * Cb, axis=0, keepdims=True)

    row = lambda *shape: pl.BlockSpec(                        # noqa: E731
        (None, *shape), lambda i, order: (order[i],) + (0,) * len(shape))
    y, Sn = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_live,),
            in_specs=[row(R, L), row(R, L), row(N, 2 * G), row(R, N, L)],
            out_specs=[row(R, L), row(R, N, L)]),
        out_shape=[jax.ShapeDtypeStruct((n, R, L), f32),
                   jax.ShapeDtypeStruct(S.shape, f32)],
        # operands count the scalar-prefetch argument
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_STEP_VMEM_BYTES),
        interpret=_interpret(interpret),
        name="ssm_step",
    )(order, dx, dec, bc, S)
    y = y.reshape(n, heads, P) + D.astype(f32)[:, None] * x
    # a dead slot's rows were never visited
    return (jnp.where(valid[:, None], y.reshape(n, -1), 0.0), Sn,
            jnp.where(valid[:, None, None], rows[:, 1:], tail))


# -- registration ------------------------------------------------------------

class _SsmXlaRef:
    step = staticmethod(ssm_step_ref)
    chunk = staticmethod(ssm_chunk)


class _SsmPallasTpu:
    step = staticmethod(ssm_step_pallas)
    chunk = staticmethod(ssm_chunk)


register_kernel("ssm", "xla_ref", _SsmXlaRef)
register_kernel("ssm", "pallas_tpu", _SsmPallasTpu, available=_tpu_available)
