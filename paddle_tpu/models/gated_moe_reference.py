"""The plain reference of the ``afmoe`` layout (Arcee Trinity; catalog
row ``Trinity-Large-Preview``, ``config.json`` and ``modeling_afmoe.py``
beside it on the hub): what ``serving/arch.py``'s ``GatedMoE`` has to
compute, written down with no cache, no kernel and no batching trick.

RMS is RMSNorm with a gain, eps 1e-5, statistics in float32::

    x = E[token] * sqrt(d)                                   # mup_enabled
    for each layer i:
        x = x + RMS_post_a( Attn_i( RMS_pre_a(x) ) )         # sandwich norms
        x = x + RMS_post_f( FFN_i ( RMS_pre_f(x) ) )
    logits = RMS_f(x) W_head                                 # untied, no bias

``Attn_i(h)``: ``q = RMS_q(heads(h W_q))``, ``k = RMS_k(kv_heads(h
W_k))`` (over the lanes of a head, one gain of ``head_dim`` each), ``v =
kv_heads(h W_v)``.  A WINDOW layer rotates q and k (theta 10000, the
halves convention) and a query at position p sees keys ``p - window < j
<= p``; a FULL layer has NO positional encoding and is causal.  Query
head ``a`` reads K/V head ``a // (heads / kv_heads)``; scores scaled by
``1 / sqrt(head_dim)``; ``o = (ctx * sigmoid(h W_g)) W_o``, the gate
lane by lane on the joined heads before the output projection.

``FFN_i(h)``, a dense layer (``i < dense_layers``): ``(silu(h W_gate) *
(h W_up)) W_down``.  A routed layer, for one row::

    s   = sigmoid(float32(h W_r))                  # over ALL experts
    sel = top_k(s + b)                             # b selects only
    w_j = s[sel_j] / (sum_j s[sel_j] + 1e-20) * route_scale
    y   = Shared(h) + sum_{j : sel_j in held} w_j Expert_{sel_j}(h)

``held`` is the share ``experts = (first, count)`` of the router's
experts whose matrices ``params`` hold (stacked, ``experts_*.w [count,
..]``): the weights are normalised over all ``top_k`` selected experts
whether held or not, and what the absent ones would add is LEFT OUT; at
``(0, router width)`` this is the uncut model.  No capacity, no group
limit, no dropped token.

What the published configuration has no key for (the configuration file
of the benchmark lists each under ``assumed``): rotary on window layers
only; the gate's place and width; the q/k norms before the rotation; the
four norms of a layer; ``+ 1e-20``; routing in float32; no multiplier on
the logits; the window counting the query itself.

Straightforward ``jax.numpy``: float32, ``highest`` matmul precision, a
loop over the held experts with a mask, each expert's matrices upcast
when its turn comes (the parameters may stay in bfloat16 on the device),
the head in blocks of vocabulary rows.  The switches of ``forward`` each
leave one line of the mathematics out or move it: they show what a
comparison with this reference bites on, never the model.
"""

import functools

import jax
import jax.numpy as jnp

_ATT_KEYS = ("norm1.scale", "att_q.w", "att_k.w", "att_v.w", "att_gate.w",
             "att_qnorm.scale", "att_knorm.scale", "att_out.w",
             "norm2.scale")
_DENSE_KEYS = ("norm3.scale", "ffn_gate.w", "ffn_up.w", "ffn_down.w",
               "norm4.scale")
_ROUTE_KEYS = ("norm3.scale", "router.w", "router.bias")
HEAD_BLOCK = 32768  # vocabulary rows one head call multiplies


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [b, t, h, dh] at positions 0 .. t - 1: lane ``i`` pairs with
    lane ``i + dh / 2``."""
    t, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv       # [t, dh/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    half = dh // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "kv_heads", "window", "rotary", "gate", "eps", "theta"))
def _attention(x, w, n_head, kv_heads, window, rotary, gate, eps, theta):
    """x [b, t, d] float32 -> x + RMS_post(Attn(RMS_pre(x)))."""
    w = _f32(w)
    b, t, _ = x.shape
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["norm1.scale"], eps)
        q = (h @ w["att_q.w"]).reshape(b, t, n_head, -1)
        k = (h @ w["att_k.w"]).reshape(b, t, kv_heads, -1)
        v = (h @ w["att_v.w"]).reshape(b, t, kv_heads, -1)
        q = _rms(q, w["att_qnorm.scale"], eps)
        k = _rms(k, w["att_knorm.scale"], eps)
        if rotary:
            q, k = _rope(q, theta), _rope(k, theta)
        at = jnp.arange(t)
        mask = at[None, :] <= at[:, None]
        if window is not None:
            mask &= at[None, :] > at[:, None] - window

        def one(head):
            """One K/V head and the query heads that read it."""
            qh, kh, vh = head                     # [b, t, g, dh], [b, t, dh]
            s = jnp.einsum("bqgd,bkd->bgqk", qh, kh) / jnp.sqrt(
                float(qh.shape[-1]))
            a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            return jnp.einsum("bgqk,bkd->bqgd", a, vh)

        # query head a reads K/V head a // (heads / kv_heads); one K/V
        # head at a time, so that a long context's scores fit
        q = q.reshape(b, t, kv_heads, n_head // kv_heads, -1)
        ctx = jax.lax.map(one, tuple(jnp.moveaxis(x, 2, 0)
                                     for x in (q, k, v)))
        ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, t, -1)
        if gate:
            ctx = ctx * jax.nn.sigmoid(h @ w["att_gate.w"])
        return x + _rms(ctx @ w["att_out.w"], w["norm2.scale"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, w, eps):
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["norm3.scale"], eps)
        y = (jax.nn.silu(h @ w["ffn_gate.w"]) * (h @ w["ffn_up.w"])) \
            @ w["ffn_down.w"]
        return x + _rms(y, w["norm4.scale"], eps)


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "norm",
                                             "eps"))
def _route(x, w, top_k, scale, norm, eps):
    """x [b, t, d] -> (h, sel [b, t, top_k], weight [b, t, top_k], s):
    ``s [b, t, width]`` are the scores of all the experts."""
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["norm3.scale"], eps)
        s = jax.nn.sigmoid(h @ w["router.w"])
    _, sel = jax.lax.top_k(s + w["router.bias"], top_k)
    weight = jnp.take_along_axis(s, sel, axis=-1)
    if norm:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True)
                           + 1e-20) * scale
    return h, sel, weight, s


@functools.partial(jax.jit, static_argnames=("first", "count"))
def _margin(s, bias, sel, first, count):
    """How far each row's selection is from one that differs in a HELD
    expert: the least of (a held selected expert's ``s + b`` above the
    best one left out) and (the worst selected one's above a held expert
    left out), over the sigmoid's slope ``s (1 - s)`` at the worst
    selected one, which to first order makes it a distance in the
    router's OUTPUT, where rounding acts; ``inf`` where no such pair
    is."""
    at = jnp.arange(s.shape[-1])
    chosen = jnp.any(sel[..., None] == at, axis=-2)           # [b, t, width]
    held = (at >= first) & (at < first + count)
    c, inf = s + bias, jnp.inf
    last = jnp.argmin(jnp.where(chosen, c, inf), axis=-1)[..., None]
    worst_in = jnp.take_along_axis(c, last, axis=-1)[..., 0]
    at_last = jnp.take_along_axis(s, last, axis=-1)[..., 0]
    best_out = jnp.max(jnp.where(chosen, -inf, c), axis=-1)
    leave = jnp.min(jnp.where(chosen & held, c, inf), axis=-1) - best_out
    enter = worst_in - jnp.max(jnp.where(~chosen & held, c, -inf), axis=-1)
    return jnp.minimum(leave, enter) / (at_last * (1.0 - at_last))


@jax.jit
def _expert(h, gate, up, down):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(h @ gate.astype(jnp.float32))
                * (h @ up.astype(jnp.float32))) @ down.astype(jnp.float32)


@jax.jit
def _add_expert(y, h, sel, weight, expert, gate, up, down):
    """y + (the weight each row gave ``expert``, 0 where it did not
    select it) x Expert(h): the mask over the rows."""
    mine = jnp.sum(jnp.where(sel == expert, weight, 0.0), axis=-1)
    return y + mine[..., None] * _expert(h, gate, up, down)


@functools.partial(jax.jit, static_argnames=("eps",))
def _close(x, y, scale, eps):
    return x + _rms(y, scale.astype(jnp.float32), eps)


@jax.jit
def _head(x, columns):
    with jax.default_matmul_precision("highest"):
        return x @ columns.astype(jnp.float32)


def routed_ffn(params, i, x, top_k, experts, route_scale, eps=1e-5,
               routed=True, shared=True, route_norm=True, seen=None,
               ties=None):
    """Layer ``i``'s ``FFN(RMS_pre_f(x))`` before the closing norm: the
    shared expert (``shared``) and the held experts' parts (``routed``)
    for the share ``experts = (first, count)``.  ``seen`` (a list)
    receives the layer's selections ``[b, t, top_k]``; ``ties`` (a list)
    how far ``[b, t]`` each row's selection is from one that differs in a
    held expert (``_margin``)."""
    w = lambda name: params[f"block{i}_{name}"]
    first, count = experts
    h, sel, weight, s = _route(x, {k: w(k) for k in _ROUTE_KEYS},
                               top_k=top_k, scale=route_scale,
                               norm=route_norm, eps=eps)
    if seen is not None:
        seen.append(sel)
    if ties is not None:
        ties.append(_margin(s, w("router.bias").astype(jnp.float32), sel,
                            first=first, count=count))
    y = jnp.zeros_like(h)
    if shared:
        y = _expert(h, w("shared_gate.w"), w("shared_up.w"),
                    w("shared_down.w"))
    if routed:
        for e in range(count):
            y = _add_expert(y, h, sel, weight, first + e,
                            w("experts_gate.w")[e], w("experts_up.w")[e],
                            w("experts_down.w")[e])
    return y


def trunk(params, tokens, layer_types, n_head, kv_heads, window,
          dense_layers, top_k, experts, route_scale, eps=1e-5,
          rope_theta=10000.0, routed=True, route_norm=True,
          attention_gate=True, rotary_on="window", windowed=True,
          seen=None, ties=None, before_routing=None):
    """The residual [b, t, d] float32 after the last layer, for tokens
    [b, t].  ``before_routing(i, x)`` is called with the residual that
    routed layer ``i`` is about to route (whoever seeds the weights
    settles ``expert_bias`` there, layer by layer)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    table = params["tok_emb.w"]
    x = table[tokens].astype(jnp.float32) * jnp.sqrt(float(table.shape[1]))
    for i, kind in enumerate(layer_types):
        w = lambda name: params[f"block{i}_{name}"]
        x = _attention(
            x, {k: w(k) for k in _ATT_KEYS}, n_head=n_head,
            kv_heads=kv_heads,
            window=window if kind == "window" and windowed else None,
            rotary=kind == rotary_on, gate=attention_gate, eps=eps,
            theta=float(rope_theta))
        if i < dense_layers:
            x = _dense_ffn(x, {k: w(k) for k in _DENSE_KEYS}, eps=eps)
        else:
            if before_routing is not None:
                before_routing(i, x)
            y = routed_ffn(params, i, x, top_k, experts, route_scale, eps,
                           routed=routed, route_norm=route_norm, seen=seen,
                           ties=ties)
            x = _close(x, y, w("norm4.scale"), eps=eps)
    return x


def forward(params, tokens, *layout, eps=1e-5, **switches):
    """Next-token logits [b, t, V] float32 for tokens [b, t]; the
    arguments are ``trunk``'s, whose ``seen`` and ``ties`` (lists)
    receive each routed layer's selections and how nearly they were
    others (``routed_ffn``)."""
    x = trunk(params, tokens, *layout, eps=eps, **switches)
    x = _rms(x, params["norm_f.scale"].astype(jnp.float32), eps)
    head = params["lm_head.w"]
    return jnp.concatenate(
        [_head(x, head[:, r:r + HEAD_BLOCK])
         for r in range(0, head.shape[1], HEAD_BLOCK)], axis=-1)
