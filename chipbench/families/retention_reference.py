"""The benchmark's copy of ``paddle_tpu/models/retention_reference.py``
(the plain reference of the ``brumby`` layout: power retention,
arXiv:2507.04239, ``modeling_brumby.py`` beside the published
``config.json``), kept here so that the comparison which decides
``correct`` rests on nothing the program can change.  It imports nothing
of the program; ``chipbench/tests/test_retention_family.py`` holds the
two files to the same text below this docstring, and the program's copy
carries the equations and every departure in its own.

Straightforward ``jax.numpy``: the QUADRATIC form (no features, no state,
no chunk, no cache, no kernel), float32, ``highest`` matmul precision,
one jitted function a sub-layer, each sub-layer's matrices upcast when
its turn comes (the 9.7 GB of bfloat16 weights stay where they are on
the device during the check), the scores one K/V head at a time in
blocks of query rows, the FFN in blocks of rows, the head in blocks of
vocabulary rows.  The switches of ``forward`` each leave one line of the
mathematics out or change it: they show what the check bites on, never
the model.
"""

import functools

import jax
import jax.numpy as jnp

_MIX_KEYS = ("norm1.scale", "att_q.w", "att_k.w", "att_v.w",
             "att_qnorm.scale", "att_knorm.scale", "att_gate.w",
             "att_gate.b", "att_out.w")
_FFN_KEYS = ("norm2.scale", "ffn_gate.w", "ffn_up.w", "ffn_down.w")
HEAD_BLOCK = 32768   # vocabulary rows one head call multiplies
QUERY_BLOCK = 1024   # query rows one call scores against every key
FFN_BLOCK = 2048     # rows one FFN call multiplies


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [b, t, h, dh] at positions 0 .. t - 1."""
    t, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv        # [t, dh/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "kv_heads", "theta", "degree", "eps", "norm_eps", "gate",
    "normaliser", "rotary", "piece", "grouped"))
def _mixer(x, w, n_head, kv_heads, theta, degree, eps, norm_eps, gate,
           normaliser, rotary, piece, grouped):
    """x [b, t, d] float32 -> x + Retention(RMS_1(x)), the quadratic
    form."""
    w = _f32(w)
    b, t, _ = x.shape
    group = n_head // kv_heads
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["norm1.scale"], norm_eps)
        q = (h @ w["att_q.w"]).reshape(b, t, n_head, -1)
        k = (h @ w["att_k.w"]).reshape(b, t, kv_heads, -1)
        v = (h @ w["att_v.w"]).reshape(b, t, kv_heads, -1)
        dh = q.shape[-1]
        q = _rms(q, w["att_qnorm.scale"], norm_eps)
        k = _rms(k, w["att_knorm.scale"], norm_eps)
        if rotary:
            q, k = _rope(q, theta), _rope(k, theta)
        lg = jax.nn.log_sigmoid(h @ w["att_gate.w"] + w["att_gate.b"])
        if not gate:
            lg = jnp.zeros_like(lg)
        cum = jnp.cumsum(lg, axis=1)                              # [b, t, kv]
        if grouped:
            # query head i reads K/V head i // group
            qg = q.reshape(b, t, kv_heads, group, dh)
        else:
            # the switch: query head i reads K/V head i % kv_heads
            qg = jnp.moveaxis(q.reshape(b, t, group, kv_heads, dh), 2, 3)
        block = min(QUERY_BLOCK, t)
        if t % block:
            raise ValueError(f"{t} positions are not a multiple of the "
                             f"query block {block}")
        at = jnp.arange(t)

        def rows(j, first):
            qj = jax.lax.dynamic_slice_in_dim(qg, first, block, 1)[:, :, j]
            cq = jax.lax.dynamic_slice_in_dim(cum, first, block, 1)[..., j]
            s = jnp.einsum("bqgd,bkd->bgqk", qj, k[:, :, j]) * dh ** -0.5
            tq = first + jnp.arange(block)
            seen = at[None, :] <= tq[:, None]
            if piece:
                # the switch: nothing is carried across a piece boundary
                seen = seen & (at[None, :] // piece == tq[:, None] // piece)
            decay = jnp.exp(jnp.where(
                seen[None], cq[:, :, None] - cum[:, None, :, j], -jnp.inf))
            a = s ** degree * decay[:, None]
            num = jnp.einsum("bgqk,bkd->bqgd", a, v[:, :, j])
            if normaliser:
                den = jnp.sum(a, axis=-1).transpose(0, 2, 1)
                num = num / (den[..., None] + eps)
            return num                                    # [b, q, g, dh]

        firsts = jnp.arange(0, t, block)
        y = jnp.stack([
            jnp.moveaxis(jax.lax.map(functools.partial(rows, j), firsts),
                         0, 1).reshape(b, t, group, dh)
            for j in range(kv_heads)], axis=2)            # [b, t, kv, g, dh]
        if not grouped:
            y = jnp.moveaxis(y, 2, 3)
        return x + y.reshape(b, t, n_head * dh) @ w["att_out.w"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _ffn(x, w, eps):
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["norm2.scale"], eps)
        return x + (jax.nn.silu(h @ w["ffn_gate.w"])
                    * (h @ w["ffn_up.w"])) @ w["ffn_down.w"]


@jax.jit
def _head(x, columns):
    with jax.default_matmul_precision("highest"):
        return x @ columns.astype(jnp.float32)


def trunk(params, tokens, n_layer, n_head, kv_heads, theta, eps=1e-6,
          norm_eps=1e-6, degree=2, gate=True, normaliser=True, rotary=True,
          piece=None, grouped=True):
    """The residual [b, t, d] float32 after the last layer, for tokens
    [b, t].  The switches: ``gate=False`` (``lg`` 0), ``normaliser=False``,
    ``degree=1``, ``rotary=False``, ``piece=n`` (the state zeroed at every
    ``n``-th position: a row sees its own piece only), ``grouped=False``
    (query head ``i`` reads K/V head ``i % kv_heads``)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["tok_emb.w"][tokens].astype(jnp.float32)
    t = x.shape[1]
    for i in range(n_layer):
        w = lambda name: params[f"block{i}_{name}"]              # noqa: E731
        x = _mixer(x, {k: w(k) for k in _MIX_KEYS}, n_head=n_head,
                   kv_heads=kv_heads, theta=float(theta), degree=degree,
                   eps=eps, norm_eps=norm_eps, gate=gate,
                   normaliser=normaliser, rotary=rotary, piece=piece,
                   grouped=grouped)
        ffn = {k: w(k) for k in _FFN_KEYS}
        x = jnp.concatenate([_ffn(x[:, r:r + FFN_BLOCK], ffn, eps=norm_eps)
                             for r in range(0, t, FFN_BLOCK)], axis=1)
    return x


def head_blocks(params, x, norm_eps=1e-6):
    """The logits of rows ``x [b, t, d]``, a block of ``HEAD_BLOCK``
    vocabulary rows at a time (an iterator of ``[b, t, <= HEAD_BLOCK]``)."""
    x = _rms(x, params["norm_f.scale"].astype(jnp.float32), norm_eps)
    head = params["lm_head.w"]
    for r in range(0, head.shape[1], HEAD_BLOCK):
        yield _head(x, head[:, r:r + HEAD_BLOCK])


def forward(params, tokens, *layout, norm_eps=1e-6, **switches):
    """Next-token logits [b, t, V] float32 for tokens [b, t]; the
    arguments are ``trunk``'s."""
    x = trunk(params, tokens, *layout, norm_eps=norm_eps, **switches)
    return jnp.concatenate(list(head_blocks(params, x, norm_eps)), axis=-1)
