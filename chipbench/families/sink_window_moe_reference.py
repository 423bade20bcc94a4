"""The benchmark's copy of ``paddle_tpu/models/sink_window_moe_reference.py``
(the plain reference of the ``mimo_v2`` layout: Xiaomi MiMo-V2.5's
language model, ``config.json`` on the hub), kept here so that the
comparison which decides ``correct`` rests on nothing the program can
change.  It imports nothing of the program;
``chipbench/tests/test_sink_window_moe_family.py`` holds the two files to
the same text below this docstring, and the program's copy carries the
equations and every departure in its own.

Straightforward ``jax.numpy``: float32, ``highest`` matmul precision, no
cache, no kernel, no batching tricks; one jitted function a sub-layer,
attention one K/V head at a time in blocks of query rows, a loop over
the held experts with a mask, each expert's matrices upcast when its
turn comes (the 6.4 GiB of bfloat16 weights stay where they are on the
device during the check), the head in blocks of vocabulary rows.  The
ASSUMED points (the configuration file lists them): the sink as one
extra logit a query head dropped after the softmax; the value scale on v
before the weighing; rotary on the first 64 of the 192 lanes, halves
convention; the window counting the query itself; ``attention_chunk_size``
read as nothing beside ``sliding_window``.  The switches of ``trunk``
each leave one line of the mathematics out or move it: they show what
the check bites on, never the model.
"""

import functools

import jax
import jax.numpy as jnp

_ATT_KEYS = ("norm1.scale", "att_qkv.w", "att_out.w")
_DENSE_KEYS = ("norm2.scale", "ffn_gate.w", "ffn_up.w", "ffn_down.w")
_ROUTE_KEYS = ("norm2.scale", "router.w", "router.bias")
HEAD_BLOCK = 32768  # vocabulary rows one head call multiplies
QUERY_BLOCK = 512   # query rows whose scores are made at once


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta, lanes):
    """x [b, t, h, dh] at positions 0 .. t - 1, rotated in its FIRST
    ``lanes`` lanes (lane ``i`` pairs with lane ``i + lanes / 2``), the
    others untouched."""
    t = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, lanes, 2, dtype=jnp.float32) / lanes)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv    # [t, lanes/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    r, half = x[..., :lanes], lanes // 2
    rot = jnp.concatenate([-r[..., half:], r[..., :half]], axis=-1)
    return jnp.concatenate(
        [r * jnp.cos(ang) + rot * jnp.sin(ang), x[..., lanes:]], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "kv_heads", "head_dim", "window", "rotary_lanes", "theta",
    "value_scale", "eps"))
def _attention(x, w, sink, n_head, kv_heads, head_dim, window, rotary_lanes,
               theta, value_scale, eps):
    """x [b, t, d] float32 -> x + Attn(RMS(x)); ``sink [n_head]`` or
    ``None``; ``window`` ``None`` for a layer attended whole."""
    w = _f32(w)
    b, t, _ = x.shape
    group = n_head // kv_heads
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["norm1.scale"], eps)
        qkv = h @ w["att_qkv.w"]
        nq, nk = n_head * head_dim, kv_heads * head_dim
        q = qkv[..., :nq].reshape(b, t, n_head, head_dim)
        k = qkv[..., nq:nq + nk].reshape(b, t, kv_heads, head_dim)
        v = qkv[..., nq + nk:].reshape(b, t, kv_heads, -1) * value_scale
        if rotary_lanes:
            q, k = (_rope(q, theta, rotary_lanes),
                    _rope(k, theta, rotary_lanes))
        # in blocks of query rows, so that a long context's scores fit
        blocks = -(-t // QUERY_BLOCK)
        pad = blocks * QUERY_BLOCK - t
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q = q.reshape(b, blocks, QUERY_BLOCK, kv_heads, group, head_dim)
        at_k = jnp.arange(t)
        sk = (None if sink is None
              else sink.astype(jnp.float32).reshape(kv_heads, group))

        def one_head(head):
            """One K/V head and the query heads that read it."""
            qh, kh, vh, sh = head    # [b, blocks, Q, g, dh], [b, t, .], [g]

            def one_block(block):
                qb, i = block                           # [b, Q, g, dh]
                at_q = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
                mask = at_k[None, :] <= at_q[:, None]
                if window is not None:
                    mask &= at_k[None, :] > at_q[:, None] - window
                s = jnp.einsum("bqgd,bkd->bgqk", qb, kh) / jnp.sqrt(
                    float(head_dim))
                s = jnp.where(mask, s, -jnp.inf)
                if sh is None:
                    a = jax.nn.softmax(s, axis=-1)
                else:
                    # one more logit a query head: it takes mass and is
                    # dropped after the softmax (it has no value row)
                    sb = jnp.broadcast_to(sh[None, :, None, None],
                                          (*s.shape[:-1], 1))
                    a = jax.nn.softmax(jnp.concatenate([s, sb], axis=-1),
                                       axis=-1)[..., :-1]
                return jnp.einsum("bgqk,bkd->bqgd", a, vh)

            return jax.lax.map(one_block, (jnp.moveaxis(qh, 1, 0),
                                           jnp.arange(blocks)))

        # query head a reads K/V head a // group; one K/V head at a time
        heads = (jnp.moveaxis(q, 3, 0), jnp.moveaxis(k, 2, 0),
                 jnp.moveaxis(v, 2, 0))
        ctx = jax.lax.map(one_head, heads + (sk,))
        # [kv, blocks, b, Q, g, dv] -> [b, t, n_head * dv]
        ctx = jnp.moveaxis(ctx, (0, 1), (3, 1)).reshape(
            b, blocks * QUERY_BLOCK, -1)[:, :t]
        return x + ctx @ w["att_out.w"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, w, eps):
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["norm2.scale"], eps)
        return x + (jax.nn.silu(h @ w["ffn_gate.w"])
                    * (h @ w["ffn_up.w"])) @ w["ffn_down.w"]


@functools.partial(jax.jit, static_argnames=("top_k", "norm", "bias", "eps"))
def _route(x, w, top_k, norm, bias, eps):
    """x [b, t, d] -> (h, sel [b, t, top_k], weight [b, t, top_k], s):
    ``s [b, t, width]`` are the scores of all the experts."""
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["norm2.scale"], eps)
        s = jax.nn.sigmoid(h @ w["router.w"])
    _, sel = jax.lax.top_k(s + w["router.bias"] if bias else s, top_k)
    weight = jnp.take_along_axis(s, sel, axis=-1)
    if norm:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
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
def _add_expert(y, h, sel, weight, expert, gate, up, down):
    """y + (the weight each row gave ``expert``, 0 where it did not
    select it) x Expert(h): the mask over the rows."""
    with jax.default_matmul_precision("highest"):
        out = (jax.nn.silu(h @ gate.astype(jnp.float32))
               * (h @ up.astype(jnp.float32))) @ down.astype(jnp.float32)
    mine = jnp.sum(jnp.where(sel == expert, weight, 0.0), axis=-1)
    return y + mine[..., None] * out


@jax.jit
def _head(x, columns):
    with jax.default_matmul_precision("highest"):
        return x @ columns.astype(jnp.float32)


def routed_ffn(params, i, x, top_k, experts, eps=1e-5, norm_topk=True,
               route_bias=True, seen=None, ties=None):
    """Layer ``i``'s routed ``FFN(RMS(x))``: the held experts' parts for
    the share ``experts = (first, count)``; there is no shared expert.
    ``seen`` (a list) receives the layer's selections ``[b, t, top_k]``;
    ``ties`` (a list) how far ``[b, t]`` each row's selection is from
    one that differs in a held expert (``_margin``)."""
    w = lambda name: params[f"block{i}_{name}"]
    first, count = experts
    h, sel, weight, s = _route(x, {k: w(k) for k in _ROUTE_KEYS},
                               top_k=top_k, norm=norm_topk, bias=route_bias,
                               eps=eps)
    if seen is not None:
        seen.append(sel)
    if ties is not None:
        ties.append(_margin(s, w("router.bias").astype(jnp.float32), sel,
                            first=first, count=count))
    y = jnp.zeros_like(h)
    for e in range(count):
        y = _add_expert(y, h, sel, weight, first + e,
                        w("experts_gate.w")[e], w("experts_up.w")[e],
                        w("experts_down.w")[e])
    return y


def trunk(params, tokens, layer_types, n_head, kv_heads, window_kv_heads,
          head_dim, window, rotary_lanes, dense_layers, top_k, experts,
          value_scale=1.0, eps=1e-5, rope_theta=1e7, window_rope_theta=1e4,
          norm_topk=True, route_bias=True, sink=True, windowed=True,
          seen=None, ties=None, before_routing=None):
    """The residual [b, t, d] float32 after the last layer, for tokens
    [b, t].  ``before_routing(i, x)`` is called with the residual that
    routed layer ``i`` is about to route (whoever seeds the weights
    settles the router's bias there, layer by layer).  The switches
    (``sink``, ``windowed``, ``norm_topk``, ``route_bias``, and the
    values passed for ``value_scale``, ``rotary_lanes``, ``window`` and
    ``window_rope_theta``) each leave one line of the mathematics out or
    move it: they show what a check bites on, never the model."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["tok_emb.w"][tokens].astype(jnp.float32)
    for i, kind in enumerate(layer_types):
        w = lambda name: params[f"block{i}_{name}"]
        win = kind == "window"
        x = _attention(
            x, {k: w(k) for k in _ATT_KEYS},
            w("att_sink.b") if win and sink else None, n_head=n_head,
            kv_heads=window_kv_heads if win else kv_heads,
            head_dim=head_dim, window=window if win and windowed else None,
            rotary_lanes=rotary_lanes,
            theta=float(window_rope_theta if win else rope_theta),
            value_scale=float(value_scale), eps=eps)
        if i < dense_layers:
            x = _dense_ffn(x, {k: w(k) for k in _DENSE_KEYS}, eps=eps)
        else:
            if before_routing is not None:
                before_routing(i, x)
            x = x + routed_ffn(params, i, x, top_k, experts, eps,
                               norm_topk=norm_topk, route_bias=route_bias,
                               seen=seen, ties=ties)
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


def logits(params, tokens, cfg):
    """The reference's logits for a configuration ``cfg``: the keys of
    the published ``config.json`` as the benchmark's configuration file
    holds them (``n_routed_experts`` the experts HELD, ``experts_first``
    the first of them)."""
    return forward(params, tokens, *layout(cfg),
                   **settings(cfg))


def layout(cfg):
    """``trunk``'s positional arguments after the tokens, from the
    published keys."""
    types = tuple("window" if kind else "full"
                  for kind in cfg["hybrid_layer_pattern"])
    return (types, cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["swa_num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"],
            int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
            cfg["moe_layer_freq"].index(1) if 1 in cfg["moe_layer_freq"]
            else len(types),
            cfg["num_experts_per_tok"],
            (cfg.get("experts_first", 0), cfg["n_routed_experts"]))


def settings(cfg):
    """``trunk``'s keyword arguments that the published keys state."""
    return dict(value_scale=cfg["attention_value_scale"],
                eps=cfg["layernorm_epsilon"],
                rope_theta=float(cfg["rope_theta"]),
                window_rope_theta=float(cfg["swa_rope_theta"]),
                norm_topk=bool(cfg["norm_topk_prob"]),
                sink=bool(cfg["add_swa_attention_sink_bias"]))
