"""The plain reference of the ``deepseek_v2`` layout (DeepSeek-V2,
arXiv:2405.04434; catalog row ``DeepSeek-V2-Lite``, ``config.json`` and
``modeling_deepseek.py`` beside it on the hub): what ``serving/arch.py``'s
``LatentMoE`` has to compute, written down with no cache, no kernel, no
absorbed product and no batching trick.

RMS is RMSNorm with a gain, eps 1e-6, statistics in float32; no bias
anywhere::

    x = E[token]                                             # no multiplier
    for each layer i:
        x = x + Attn( RMS_a(x) )                             # pre-norm only
        x = x + FFN_i( RMS_f(x) )
    logits = RMS_out(x) W_head                               # untied

``Attn(h)`` at position p, ``n_head`` heads (the NON-absorbed form: the
per-head keys and values of the whole context are made from the latent)::

    [q_nope_a | q_pe_a] = head_a(h W_q)          # nope_dim | rope_dim lanes
    [c' | k_pe']        = h W_kva                # rank | rope_dim
    c    = RMS_kv(c')                            # a gain of ``rank``
    k_pe = rope_p(k_pe')                         # ONE key, all heads share it
    q_pe_a = rope_p(q_pe_a)
    [k_nope_a | v_a]    = head_a(c W_kvb)        # nope_dim | v_dim a head
    s_a(p, j) = (q_nope_a . k_nope_a(j) + q_pe_a . k_pe(j)) sigma,  j <= p
    o_a = sum_j softmax_j(s_a)_j v_a(j);   Attn = concat_a(o_a) W_o

Rotary positions are YaRN's (``yarn_inv_freq``: frequency ``i`` of the
``rope_dim / 2`` is ``theta ** (-2 i / rope_dim)`` kept where it turns
more than ``beta_fast`` times over the ``original`` positions, divided
by ``factor`` where fewer than ``beta_slow`` times, blended linearly
between the bounds), in the halves convention (lane ``i`` pairs with
lane ``i + rope_dim / 2``); cos and sin are multiplied by ``mscale(factor,
mscale) / mscale(factor, mscale_all_dim)`` and ``sigma = (nope_dim +
rope_dim) ** -0.5 * mscale(factor, mscale_all_dim) ** 2`` with
``mscale(s, m) = 0.1 m ln s + 1``.

``FFN_i(h)``, a dense layer (``i < dense_layers``): ``(silu(h W_gate) *
(h W_up)) W_down``.  A routed layer, for one row::

    s   = softmax(float32(h W_r))                  # over ALL experts, no bias
    sel = top_k(s)                                 # greedy, no group limit
    w_j = s[sel_j] * route_scale                   # NOT renormalised
    y   = Shared(h) + sum_{j : sel_j in held} w_j Expert_{sel_j}(h)

``held`` is the share ``experts = (first, count)`` of the router's
experts whose matrices ``params`` hold (stacked, ``experts_*.w [count,
..]``); what the absent ones would add is LEFT OUT; at ``(0, router
width)`` this is the uncut model.  The shared experts are ONE gated MLP.
No capacity, no dropped token.

What the published configuration has no key for (the configuration file
of the benchmark lists each under ``assumed``): the halves convention
(the model card's interleaved pairs are a fixed permutation of seeded
columns); routing in float32; the YaRN bounds as derived; eps of
``RMS_kv``; which experts are held.

Straightforward ``jax.numpy``: float32, ``highest`` matmul precision, a
loop over the held experts with a mask, each expert's matrices upcast
when its turn comes (the parameters may stay in bfloat16 on the device),
attention one head at a time and in blocks of query rows, the head in
blocks of vocabulary rows.  The switches of ``forward`` each leave one
line of the mathematics out or change it: they show what a comparison
with this reference bites on, never the model.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_ATT_KEYS = ("norm1.scale", "att_q.w", "att_kva.w", "att_kvnorm.scale",
             "att_kvb.w", "att_out.w")
_DENSE_KEYS = ("norm2.scale", "ffn_gate.w", "ffn_up.w", "ffn_down.w")
_ROUTE_KEYS = ("norm2.scale", "router.w")
HEAD_BLOCK = 32768   # vocabulary rows one head call multiplies
QUERY_BLOCK = 1024   # query rows one attention call scores


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(lanes, theta, factor, original, beta_fast, beta_slow,
                  blend=True):
    """The ``lanes / 2`` rotary frequencies; ``blend=False`` is plain
    ``theta`` (a switch)."""
    extra = theta ** (-np.arange(0, lanes, 2, dtype=np.float64) / lanes)
    if factor <= 1 or not blend:
        return extra.astype(np.float32)

    def bound(beta):
        return lanes * math.log(original / (beta * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(bound(beta_fast)), 0)
    hi = min(math.ceil(bound(beta_slow)), lanes - 1)
    ramp = np.clip((np.arange(lanes // 2) - lo)
                   / (hi - lo if hi > lo else 0.001), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def _rope(x, inv_freq, gain):
    """x [b, t, h, dh] at positions 0 .. t - 1: lane ``i`` pairs with
    lane ``i + dh / 2``."""
    t, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq   # [t, dh/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * (jnp.cos(ang) * gain) + rot * (jnp.sin(ang) * gain)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "rank", "nope_dim", "v_dim", "sigma", "gain", "eps",
    "rotary_key", "kv_norm"))
def _attention(x, w, inv_freq, n_head, rank, nope_dim, v_dim, sigma, gain,
               eps, rotary_key, kv_norm):
    """x [b, t, d] float32 -> x + Attn(RMS_a(x)), the per-head form."""
    w = _f32(w)
    b, t, _ = x.shape
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["norm1.scale"], eps)
        q = (h @ w["att_q.w"]).reshape(b, t, n_head, -1)
        q_nope, q_pe = q[..., :nope_dim], q[..., nope_dim:]
        kva = h @ w["att_kva.w"]
        c, k_pe = kva[..., :rank], kva[..., None, rank:]
        if kv_norm:
            c = _rms(c, w["att_kvnorm.scale"], eps)
        q_pe, k_pe = _rope(q_pe, inv_freq, gain), _rope(k_pe, inv_freq, gain)
        kv = (c @ w["att_kvb.w"]).reshape(b, t, n_head, nope_dim + v_dim)
        k_nope, v = kv[..., :nope_dim], kv[..., nope_dim:]
        at = jnp.arange(t)

        def one(head):
            """One head, its query rows in blocks."""
            qn, qp, kn, vh = head                              # [b, t, .]
            out = []
            for r in range(0, t, QUERY_BLOCK):
                rows = slice(r, min(r + QUERY_BLOCK, t))
                s = jnp.einsum("bqd,bkd->bqk", qn[:, rows], kn)
                if rotary_key:
                    s = s + jnp.einsum("bqd,bkd->bqk", qp[:, rows],
                                       k_pe[:, :, 0])
                mask = at[None, :] <= at[rows, None]
                a = jax.nn.softmax(jnp.where(mask, s * sigma, -jnp.inf),
                                   axis=-1)
                out.append(jnp.einsum("bqk,bkd->bqd", a, vh))
            return jnp.concatenate(out, axis=1)

        ctx = jax.lax.map(one, tuple(jnp.moveaxis(m, 2, 0)
                                     for m in (q_nope, q_pe, k_nope, v)))
        ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, t, -1)
        return x + ctx @ w["att_out.w"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, w, eps):
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["norm2.scale"], eps)
        return x + (jax.nn.silu(h @ w["ffn_gate.w"])
                    * (h @ w["ffn_up.w"])) @ w["ffn_down.w"]


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "norm",
                                             "eps"))
def _route(x, w, top_k, scale, norm, eps):
    """x [b, t, d] -> (h, sel [b, t, top_k], weight [b, t, top_k], s):
    ``s [b, t, width]`` are the scores of all the experts.  ``norm``
    renormalises the selected weights (a switch: the model does not)."""
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["norm2.scale"], eps)
        s = jax.nn.softmax(h @ w["router.w"], axis=-1)
    weight, sel = jax.lax.top_k(s, top_k)
    if norm:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return h, sel, weight * scale, s


@functools.partial(jax.jit, static_argnames=("first", "count"))
def _margin(s, sel, first, count):
    """How far each row's selection is from one that differs in a HELD
    expert: the least of (a held selected expert's score above the best
    one left out) and (the worst selected one's above a held expert left
    out), over the worst selected score, which makes it a RELATIVE
    distance, a distance between the router's outputs (logits), where
    rounding acts; ``inf`` where no such pair is."""
    at = jnp.arange(s.shape[-1])
    chosen = jnp.any(sel[..., None] == at, axis=-2)           # [b, t, width]
    held = (at >= first) & (at < first + count)
    inf = jnp.inf
    worst_in = jnp.min(jnp.where(chosen, s, inf), axis=-1)
    best_out = jnp.max(jnp.where(chosen, -inf, s), axis=-1)
    leave = jnp.min(jnp.where(chosen & held, s, inf), axis=-1) - best_out
    enter = worst_in - jnp.max(jnp.where(~chosen & held, s, -inf), axis=-1)
    return jnp.minimum(leave, enter) / worst_in


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


@jax.jit
def _head(x, columns):
    with jax.default_matmul_precision("highest"):
        return x @ columns.astype(jnp.float32)


def routed_ffn(params, i, x, top_k, experts, route_scale, eps=1e-6,
               routed=True, shared=True, route_norm=False, seen=None,
               ties=None):
    """Layer ``i``'s ``FFN(RMS_f(x))``: the shared MLP (``shared``) and
    the held experts' parts (``routed``) for the share ``experts =
    (first, count)``.  ``seen`` (a list) receives the layer's selections
    ``[b, t, top_k]``; ``ties`` (a list) how far ``[b, t]`` each row's
    selection is from one that differs in a held expert (``_margin``)."""
    w = lambda name: params[f"block{i}_{name}"]
    first, count = experts
    h, sel, weight, s = _route(x, {k: w(k) for k in _ROUTE_KEYS},
                               top_k=top_k, scale=route_scale,
                               norm=route_norm, eps=eps)
    if seen is not None:
        seen.append(sel)
    if ties is not None:
        ties.append(_margin(s, sel, first=first, count=count))
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


def trunk(params, tokens, n_layer, n_head, rank, nope_dim, rope_dim, v_dim,
          dense_layers, top_k, experts, route_scale, rope, eps=1e-6,
          routed=True, route_norm=False, rotary_key=True, kv_norm=True,
          mscale_in_scores=True, yarn_blend=True, seen=None, ties=None,
          before_routing=None):
    """The residual [b, t, d] float32 after the last layer, for tokens
    [b, t].  ``rope = (theta, factor, original, beta_fast, beta_slow,
    mscale, mscale_all_dim)``.  ``before_routing(i, x)`` is called with
    the residual that routed layer ``i`` is about to route."""
    theta, factor, original, beta_fast, beta_slow, m, m_all = rope
    inv_freq = jnp.asarray(yarn_inv_freq(rope_dim, float(theta), factor,
                                         original, beta_fast, beta_slow,
                                         blend=yarn_blend))
    sigma = (nope_dim + rope_dim) ** -0.5
    if mscale_in_scores:
        sigma *= yarn_mscale(factor, m_all) ** 2
    gain = yarn_mscale(factor, m) / yarn_mscale(factor, m_all)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["tok_emb.w"][tokens].astype(jnp.float32)
    for i in range(n_layer):
        w = lambda name: params[f"block{i}_{name}"]
        x = _attention(
            x, {k: w(k) for k in _ATT_KEYS}, inv_freq, n_head=n_head,
            rank=rank, nope_dim=nope_dim, v_dim=v_dim, sigma=float(sigma),
            gain=float(gain), eps=eps, rotary_key=rotary_key,
            kv_norm=kv_norm)
        if i < dense_layers:
            x = _dense_ffn(x, {k: w(k) for k in _DENSE_KEYS}, eps=eps)
        else:
            if before_routing is not None:
                before_routing(i, x)
            x = x + routed_ffn(params, i, x, top_k, experts, route_scale,
                               eps, routed=routed, route_norm=route_norm,
                               seen=seen, ties=ties)
    return x


def forward(params, tokens, *layout, eps=1e-6, **switches):
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
