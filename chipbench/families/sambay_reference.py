"""The benchmark's copy of ``paddle_tpu/models/sambay_reference.py`` (the
plain reference of the decoder-hybrid-decoder stack with differential
attention: SambaY, arXiv:2507.06607, and arXiv:2410.05258;
``modeling_phi4flash.py`` beside the published ``config.json``), kept
here so that the comparison which decides ``correct`` rests on nothing
the program can change.  It imports nothing of the program;
``chipbench/tests/test_sambay_family.py`` holds the two files to the same
text below this docstring, and the program's copy carries the equations
and every departure in its own.

Straightforward ``jax.numpy``: float32, ``highest`` matmul precision, no
cache, no kernel, no batching tricks, a sequential scan over positions;
one jitted layer function at a time, the head in blocks of vocabulary
rows.  The switches of ``forward`` each leave one line of the
mathematics out: they show what the check bites on, never the model.
"""

import functools
import math

import jax
import jax.numpy as jnp

_MLP_KEYS = ("ln1.scale", "ln1.bias", "ln2.scale", "ln2.bias", "ffn_gu.w",
             "ffn_down.w")
_ATT_KEYS = ("att_out.w", "att_out.b", "att_lambda_q1.w", "att_lambda_k1.w",
             "att_lambda_q2.w", "att_lambda_k2.w", "att_subln.scale")
_MIX_KEYS = {
    "mamba": ("ssm_in.w", "ssm_conv.w", "ssm_conv.b", "ssm_x.w", "ssm_dt.w",
              "ssm_dt.b", "ssm_A_log.w", "ssm_D.w", "ssm_out.w"),
    "window": ("att_qkv.w", "att_qkv.b") + _ATT_KEYS,
    "full": ("att_qkv.w", "att_qkv.b") + _ATT_KEYS,
    "cross": ("att_q.w", "att_q.b") + _ATT_KEYS,
    "gmu": ("gmu_in.w", "gmu_out.w"),
}
HEAD_BLOCK = 32768  # vocabulary rows one head call multiplies


def layer_kinds(n_layer):
    """The mixer of each layer, by index."""
    half = n_layer // 2
    kinds = []
    for i in range(n_layer):
        if i % 2 == 0:
            kinds.append("mamba" if i <= half else "gmu")
        elif i < half:
            kinds.append("window")
        else:
            kinds.append("full" if i == half + 1 else "cross")
    return kinds


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _mamba(h, w, d_state, dt_rank):
    """h [b, t, d] -> (out [b, t, d], y [b, t, n]): one position after
    the other, from zero state and zero rows before the first."""
    n, taps = w["ssm_conv.w"].shape
    az = h @ w["ssm_in.w"]
    a, z = az[..., :n], az[..., n:]
    t = a.shape[1]
    padded = jnp.pad(a, ((0, 0), (taps - 1, 0), (0, 0)))
    a = w["ssm_conv.b"] + sum(padded[:, k:k + t] * w["ssm_conv.w"][:, k]
                              for k in range(taps))
    a = jax.nn.silu(a)
    dbc = a @ w["ssm_x.w"]
    delta = jax.nn.softplus(dbc[..., :dt_rank] @ w["ssm_dt.w"]
                            + w["ssm_dt.b"])
    B = dbc[..., dt_rank:dt_rank + d_state]
    C = dbc[..., dt_rank + d_state:]
    A = -jnp.exp(w["ssm_A_log.w"])                              # [n, s]

    def position(s, row):
        d_t, a_t, b_t, c_t = row
        s = (jnp.exp(d_t[..., None] * A) * s
             + (d_t * a_t)[..., None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    s0 = jnp.zeros((h.shape[0], n, d_state), jnp.float32)
    _, y = jax.lax.scan(position, s0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (delta, a, B, C)))
    y = jnp.moveaxis(y, 0, 1) + w["ssm_D.w"] * a
    return (y * jax.nn.silu(z)) @ w["ssm_out.w"], y


def _differential(q, k, v, w, lam0, window, eps, lambda_term):
    """q [b, t, H, dh], k and v [b, t, G, dh] -> [b, t, H * dh]."""
    b, t, H, dh = q.shape
    G = k.shape[2]
    q = q.reshape(b, t, H // 2, 2, dh)
    # pair a reads K/V pair a // (H / G)
    k = jnp.repeat(k.reshape(b, t, G // 2, 2, dh), H // G, axis=2)
    v = jnp.repeat(v.reshape(b, t, G // 2, 2 * dh), H // G, axis=2)
    at = jnp.arange(t)
    mask = at[None, :] <= at[:, None]
    if window is not None:
        mask &= at[None, :] > at[:, None] - window
    s = jnp.einsum("bqaxd,bkaxd->baxqk", q, k) / jnp.sqrt(float(dh))
    A = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    lam = (jnp.exp(jnp.sum(w["att_lambda_q1.w"] * w["att_lambda_k1.w"]))
           - jnp.exp(jnp.sum(w["att_lambda_q2.w"] * w["att_lambda_k2.w"]))
           + lam0)
    A = A[:, :, 0] - lam * A[:, :, 1] if lambda_term else A[:, :, 0]
    o = jnp.einsum("baqk,bkad->bqad", A, v)                     # [b,t,H/2,2dh]
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps)
    o = o * w["att_subln.scale"] * (1.0 - lam0)
    return o.reshape(b, t, H * dh) @ w["att_out.w"] + w["att_out.b"]


@functools.partial(jax.jit, static_argnames=(
    "kind", "n_head", "kv_heads", "window", "d_state", "dt_rank", "eps",
    "lambda_term"))
def _layer(x, w, memory, kv, lam0, kind, n_head, kv_heads, window, d_state,
           dt_rank, eps, lambda_term):
    """x [b, t, d] float32 -> (x', y, kv'): ``y`` the Mamba scan output
    (else ``memory`` passed through), ``kv'`` this layer's own K and V
    (else ``kv`` passed through); ``lam0`` the layer's ``lambda_0``, an
    argument so that one compile serves every layer of a kind."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, t, d = x.shape
    dh = d // n_head
    with jax.default_matmul_precision("highest"):
        h = _ln(x, w["ln1.scale"], w["ln1.bias"], eps)
        if kind == "mamba":
            mix, memory = _mamba(h, w, d_state, dt_rank)
        elif kind == "gmu":
            mix = (memory * jax.nn.silu(h @ w["gmu_in.w"])) @ w["gmu_out.w"]
        else:
            if kind == "cross":
                q = h @ w["att_q.w"] + w["att_q.b"]
            else:
                qkv = h @ w["att_qkv.w"] + w["att_qkv.b"]
                q = qkv[..., :d]
                kv = tuple(part.reshape(b, t, kv_heads, dh) for part in (
                    qkv[..., d:d + kv_heads * dh],
                    qkv[..., d + kv_heads * dh:]))
            mix = _differential(q.reshape(b, t, n_head, dh), kv[0], kv[1], w,
                                lam0, window if kind == "window" else None,
                                eps, lambda_term)
        x = x + mix
        gu = _ln(x, w["ln2.scale"], w["ln2.bias"], eps) @ w["ffn_gu.w"]
        f = gu.shape[-1] // 2
        x = x + (gu[..., f:] * jax.nn.silu(gu[..., :f])) @ w["ffn_down.w"]
    return x, memory, kv


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, scale, bias, eps):
    return _ln(x, scale.astype(jnp.float32), bias.astype(jnp.float32), eps)


@jax.jit
def _head(x, rows):
    with jax.default_matmul_precision("highest"):
        return x @ rows.astype(jnp.float32).T


def forward(params, tokens, n_layer, n_head, kv_heads, window, d_state=16,
            dt_rank=None, eps=1e-5, lambda_term=True, memory=True,
            windowed=True):
    """Next-token logits [b, t, V] float32 for tokens [b, t]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    table = params["tok_emb.w"]
    x = table[tokens].astype(jnp.float32)
    dt_rank = dt_rank or -(-x.shape[-1] // 16)
    half = n_layer // 2
    m = kv = None
    for i, kind in enumerate(layer_kinds(n_layer)):
        w = {k: params[f"block{i}_{k}"] for k in _MLP_KEYS + _MIX_KEYS[kind]}
        x, y, kv_i = _layer(
            x, w, m, kv, 0.8 - 0.6 * math.exp(-0.3 * i), kind=kind,
            n_head=n_head, kv_heads=kv_heads,
            window=window if windowed else None, d_state=d_state,
            dt_rank=dt_rank, eps=eps, lambda_term=lambda_term)
        if i == half:
            m = y if memory else jnp.ones_like(y)
        if i == half + 1:
            kv = kv_i
    x = _final_norm(x, params["ln_f.scale"], params["ln_f.bias"], eps=eps)
    return jnp.concatenate(
        [_head(x, table[r:r + HEAD_BLOCK])
         for r in range(0, table.shape[0], HEAD_BLOCK)], axis=-1)
