"""The plain reference of the ``dots3_note`` layout's language model
(catalog row ``dots3-note-prev``; its keys are DeepSeek-V3.2's, letter
for letter, plus one ``swa_*`` copy of the attention keys): what
``serving/arch.py``'s ``SparseLatentMoE`` has to compute, written down
with no cache, no kernel, no absorbed product and no batching trick.

RMS is RMSNorm with a gain, eps 1e-5, statistics in float32; no bias but
the index key's LayerNorm; head untied, table not scaled::

    x = E[token]
    for each layer i:   x = x + Attn_i( RMS(x) );   x = x + FFN_i( RMS(x) )
    logits = RMS_out(x) W_head

``Attn(a)`` at position t, by the layer's type (``layer_types[i]``): a
FULL layer has ``H`` heads of nope | rope | v lanes, a query latent of
``q_rank`` and a K/V latent of ``rank``; a SLIDING layer has its own
``H``, lanes and ranks (the ``swa_*`` keys) and attends key ``j`` iff
``t - window < j <= t``.  Both, the PER-HEAD form::

    c_q = r_q RMS_q(a W_qa);   [q_nope_h | q_pe_h] = head_h(c_q W_qb)
    [c' | k'] = a W_kva;  c = r_kv RMS_kv(c');  k_pe = rope(k')  # one key
    [k_nope_h | v_h] = head_h(c W_kvb);   q_pe_h = rope(q_pe_h)
    s_h(t, j) = (q_nope_h . k_nope_h(j) + q_pe_h . k_pe(j)) (nope + rope)^-1/2
    g = sigmoid(a W_g)                                  # one gate a head
    Attn = concat_h( g_h sum_j softmax_j(s_h)_j v_h(j) ) W_o

with ``r_q = (d / q_rank)^1/2`` and ``r_kv = (d / rank)^1/2`` (the lora
rescale).  A FULL layer's softmax runs over ``j in S_t`` only, the same
set for every head, which its INDEXER picks (``H_I`` heads of ``d_I``
lanes)::

    q^I_h = head_h(c_q W^I_qb);  k^I = LayerNorm(a W^I_k)   # gain and bias
    rope on the first ``rope`` lanes of both
    w = (a W^I_w) H_I^-1/2 d_I^-1/2
    I(t, j) = sum_h w_h(t) relu(q^I_h(t) . k^I(j)),  j <= t
    S_t = the index_topk positions of largest I(t, .)  (all, while t < topk)

Rotary positions are plain (no scaling), the halves convention (lane
``i`` pairs with lane ``i + rope / 2``), theta by layer type.

``FFN_i(m)``: a dense layer (``i < dense_layers``) ``(silu(m W_gate) *
(m W_up)) W_down``; a routed layer, for one row::

    s   = sigmoid(float32(m W_r));  sel = top_k(s + e_bias)   # bias selects
    w_j = s[sel_j] / (sum_j s[sel_j] + 1e-20) * route_scale
    y   = Shared(m) + sum_{j : sel_j in held} w_j Expert_{sel_j}(m)

``held`` is the share ``experts = (first, count)`` whose matrices
``params`` hold; what the absent ones would add is LEFT OUT; at ``(0,
router width)`` this is the uncut model.  No capacity, no dropped token.

What the published configuration has no key for (the benchmark's
configuration file lists each under ``assumed``, with its reason):
``apply_mla_qkv_lora_rescale`` read as LongCat-Flash's
``mla_scale_q_lora`` / ``mla_scale_kv_lora`` (``r_q``, ``r_kv`` above, on
the normed latents, the indexer reading the rescaled ``c_q``); the gate
as one sigmoid a head of the layer's normed input, before ``W_o``
(arXiv:2505.06708's headwise form); no group limit in the routing; the
halves rotary convention on the latent's and the indexer's lanes alike;
the window counting the query; the index LayerNorm's eps 1e-6; the index
key in the compute dtype and DeepSeek's Hadamard rotation left out (an
orthogonal map of ``q^I`` and ``k^I`` alike changes no dot product);
routing and index scores accumulated in float32.

Straightforward ``jax.numpy``: float32, ``highest`` matmul precision;
attention one head at a time (its keys and values made from the latent
when its turn comes, its part of ``W_o`` added to a running sum) in
blocks of query rows, the selection a boolean mask ``[t, t]`` made from
``top_k`` of the dense index scores in blocks of query rows, so that
34k positions fit beside the weights; a loop over the held experts with
a mask; the head in blocks of vocabulary rows.  The switches of
``forward`` each leave one line of the mathematics out or change it:
they show what a comparison with this reference bites on, never the
model.
"""

import functools

import jax
import jax.numpy as jnp

_LATENT_KEYS = ("norm1.scale", "att_qa.w", "att_qnorm.scale", "att_kva.w",
                "att_kvnorm.scale")
_HEAD_KEYS = ("att_qb.w", "att_kvb.w", "att_gate.w", "att_out.w")
_INDEX_KEYS = ("idx_qb.w", "idx_k.w", "idx_knorm.scale", "idx_knorm.bias",
               "idx_w.w")
_DENSE_KEYS = ("norm2.scale", "ffn_gate.w", "ffn_up.w", "ffn_down.w")
_ROUTE_KEYS = ("norm2.scale", "router.w", "router.bias")
HEAD_BLOCK = 32768   # vocabulary rows one head call multiplies
QUERY_BLOCK = 1024   # query rows one attention call scores
INDEX_BLOCK = 64     # query rows one call of the indexer scores
INDEX_EPS = 1e-6     # the index key's LayerNorm


def geometry(cfg, kind):
    """The attention geometry of a layer of ``kind`` (``"full"`` or
    ``"sliding"``) from the published keys: the plain ones, or their
    ``swa_`` copies."""
    pre = "swa_" if kind == "sliding" else ""
    return {"heads": cfg[pre + "num_attention_heads"],
            "q_rank": cfg[pre + "q_lora_rank"],
            "rank": cfg[pre + "kv_lora_rank"],
            "nope": cfg[pre + "qk_nope_head_dim"],
            "rope": cfg[pre + "qk_rope_head_dim"],
            "v": cfg[pre + "v_head_dim"],
            "theta": float(cfg[pre + "rope_theta"])}


def layout(cfg):
    """``forward``'s keyword arguments from a configuration with the
    published keys (``n_routed_experts`` the experts HELD, ``router_width``
    the router's, ``experts_first`` the first held)."""
    types = tuple("sliding" if k.startswith("sliding") else "full"
                  for k in cfg["layer_types"][:cfg["num_hidden_layers"]])
    return dict(
        layer_types=types, full=geometry(cfg, "full"),
        sliding=geometry(cfg, "sliding"), window=cfg["sliding_window_size"],
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"],
        dense_layers=cfg["first_k_dense_replace"],
        top_k=cfg["num_experts_per_tok"],
        experts=(cfg["experts_first"], cfg["n_routed_experts"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        eps=cfg["rms_norm_eps"])


def param_shapes(d, rows, f, e, router_width, held, layer_types, full,
                 sliding, index_heads, index_dim, dense_layers):
    """{name: shape} of every parameter of the layout (``held`` routed
    experts a routed layer, ``rows`` of table and head): the count of the
    published model is the sum of their products."""
    p = {"tok_emb.w": (rows, d), "lm_head.w": (d, rows),
         "norm_f.scale": (d,)}
    for i, kind in enumerate(layer_types):
        g = full if kind == "full" else sliding
        h = g["heads"]
        layer = {
            "norm1.scale": (d,), "norm2.scale": (d,),
            "att_qa.w": (d, g["q_rank"]), "att_qnorm.scale": (g["q_rank"],),
            "att_qb.w": (g["q_rank"], h * (g["nope"] + g["rope"])),
            "att_kva.w": (d, g["rank"] + g["rope"]),
            "att_kvnorm.scale": (g["rank"],),
            "att_kvb.w": (g["rank"], h * (g["nope"] + g["v"])),
            "att_gate.w": (d, h), "att_out.w": (h * g["v"], d)}
        if kind == "full":
            layer.update({
                "idx_qb.w": (g["q_rank"], index_heads * index_dim),
                "idx_k.w": (d, index_dim), "idx_knorm.scale": (index_dim,),
                "idx_knorm.bias": (index_dim,), "idx_w.w": (d, index_heads)})
        if i < dense_layers:
            layer.update({"ffn_gate.w": (d, f), "ffn_up.w": (d, f),
                          "ffn_down.w": (f, d)})
        else:
            layer.update({
                "router.w": (d, router_width), "router.bias": (router_width,),
                "shared_gate.w": (d, e), "shared_up.w": (d, e),
                "shared_down.w": (e, d), "experts_gate.w": (held, d, e),
                "experts_up.w": (held, d, e), "experts_down.w": (held, e, d)})
        p.update({f"block{i}_{k}": v for k, v in layer.items()})
    return p


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, theta, first=0):
    """x [t, ..., dh] at positions first .. first + t - 1: lane ``i``
    pairs with lane ``i + dh / 2``."""
    t, dh = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = (first + jnp.arange(t)).astype(jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1).reshape(
        (t,) + (1,) * (x.ndim - 2) + (dh,))
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _rope_lanes(x, lanes, theta, first=0):
    """Rotary on the first ``lanes`` lanes, the others as they are."""
    return jnp.concatenate([_rope(x[..., :lanes], theta, first),
                            x[..., lanes:]], axis=-1)


def _row_blocks(t, block):
    """(blocks, rows a block) that cover ``t`` rows."""
    rows = min(block, t)
    return -(-t // rows), rows


@functools.partial(jax.jit, static_argnames=(
    "rank", "theta", "eps", "rescale"))
def _latents(x, w, rank, theta, eps, rescale):
    """x [t, d] -> (a, c_q, c, k_pe): the normed input, the query latent,
    the K/V latent and the one rotary key."""
    w = _f32(w)
    d = x.shape[-1]
    with jax.default_matmul_precision("highest"):
        a = _rms(x, w["norm1.scale"], eps)
        c_q = _rms(a @ w["att_qa.w"], w["att_qnorm.scale"], eps)
        kva = a @ w["att_kva.w"]
        c = _rms(kva[..., :rank], w["att_kvnorm.scale"], eps)
        if rescale:
            c_q = c_q * (d / c_q.shape[-1]) ** 0.5
            c = c * (d / rank) ** 0.5
        return a, c_q, c, _rope(kva[..., rank:], theta)


@functools.partial(jax.jit, static_argnames=(
    "heads", "lanes", "rope", "topk", "theta", "relu", "rotary", "select"))
def _selection(a, c_q, w, heads, lanes, rope, topk, theta, relu, rotary,
               select):
    """The indexer: ``keep [R, t]`` bool, row ``t`` true at the
    positions of ``S_t``; ``R >= t`` covers the rows ``_attend``'s blocks
    of query rows slice (rows past ``t`` are padding)."""
    w = _f32(w)
    t = a.shape[0]
    at = jnp.arange(t)
    rows = min(INDEX_BLOCK, t)
    q_blocks, q_rows = _row_blocks(t, QUERY_BLOCK)
    blocks = -(-max(t, q_blocks * q_rows) // rows)
    if select == "all":
        return jnp.ones((blocks * rows, t), bool)
    if select == "first":
        return jnp.broadcast_to(at < topk, (blocks * rows, t))
    with jax.default_matmul_precision("highest"):
        keys = _layer_norm(a @ w["idx_k.w"], w["idx_knorm.scale"],
                           w["idx_knorm.bias"], INDEX_EPS)
        if rotary:
            keys = _rope_lanes(keys, rope, theta)
        pad = ((0, blocks * rows - t), (0, 0))
        c_q = jnp.pad(c_q, pad).reshape(blocks, rows, -1)
        a = jnp.pad(a, pad).reshape(blocks, rows, -1)

        def one(block):
            cb, ab, first = block
            q = (cb @ w["idx_qb.w"]).reshape(rows, heads, lanes)
            if rotary:
                q = _rope_lanes(q, rope, theta, first)
            weight = (ab @ w["idx_w.w"]) * (heads ** -0.5 * lanes ** -0.5)
            s = jnp.einsum("qhd,td->qht", q, keys)
            if relu:
                s = jax.nn.relu(s)
            score = jnp.sum(s * weight[..., None], axis=1)      # [rows, t]
            pos = first + jnp.arange(rows)
            score = jnp.where(at[None, :] <= pos[:, None], score, -jnp.inf)
            _, sel = jax.lax.top_k(score, min(topk, t))
            return jnp.zeros((rows, t), bool).at[
                jnp.arange(rows)[:, None], sel].set(True)

        keep = jax.lax.map(one, (c_q, a, jnp.arange(blocks) * rows))
    return keep.reshape(blocks * rows, t)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "v", "theta", "window", "gate"))
def _attend(x, a, c_q, c, k_pe, keep, w, heads, nope, v, theta, window,
            gate):
    """x + Attn, the per-head form: a scan over the heads, each adding
    its gated context through its rows of ``W_o``."""
    w = _f32(w)
    t, d = x.shape
    blocks, rows = _row_blocks(t, QUERY_BLOCK)
    pad = blocks * rows - t
    at = jnp.arange(t)
    with jax.default_matmul_precision("highest"):
        g = (jax.nn.sigmoid(a @ w["att_gate.w"]) if gate
             else jnp.ones((t, heads), jnp.float32))
        w_qb = jnp.moveaxis(w["att_qb.w"].reshape(c_q.shape[-1], heads, -1),
                            1, 0)
        w_kvb = jnp.moveaxis(w["att_kvb.w"].reshape(c.shape[-1], heads, -1),
                             1, 0)
        w_o = w["att_out.w"].reshape(heads, v, d)
        sigma = float(w_qb.shape[-1]) ** -0.5

        def one_head(y, head):
            qb, kvb, ob, gh = head
            q = c_q @ qb                                    # [t, nope | rope]
            q_pe = _rope(q[:, nope:], theta)
            kv = c @ kvb                                    # [t, nope | v]
            k_nope, vh = kv[:, :nope], kv[:, nope:]
            qs = jnp.pad(jnp.concatenate([q[:, :nope], q_pe], axis=-1),
                         ((0, pad), (0, 0))).reshape(blocks, rows, -1)
            keys = jnp.concatenate([k_nope, k_pe], axis=-1)

            def one_block(block):
                qr, first = block
                pos = first + jnp.arange(rows)
                mask = at[None, :] <= pos[:, None]
                if window is not None:
                    mask &= at[None, :] > pos[:, None] - window
                if keep is not None:
                    mask &= jax.lax.dynamic_slice_in_dim(keep, first, rows)
                s = jnp.where(mask, (qr @ keys.T) * sigma, -jnp.inf)
                return jax.nn.softmax(s, axis=-1) @ vh

            ctx = jax.lax.map(one_block, (qs, jnp.arange(blocks) * rows))
            ctx = ctx.reshape(blocks * rows, v)[:t]
            return y + (ctx * gh[:, None]) @ ob, None

        y, _ = jax.lax.scan(one_head, jnp.zeros_like(x),
                            (w_qb, w_kvb, w_o, g.T))
        return x + y


def attention(x, w, kind, g, window, index_heads, index_dim, index_topk,
              eps, rescale=True, gate=True, select="topk", index_relu=True,
              index_rope=True, theta=None):
    """One layer's ``x + Attn(RMS(x))`` over ``x [t, d]``; ``w`` gives the
    layer's parameters by name."""
    theta = g["theta"] if theta is None else float(theta)
    a, c_q, c, k_pe = _latents(
        x, {k: w(k) for k in _LATENT_KEYS}, rank=g["rank"], theta=theta,
        eps=eps, rescale=rescale)
    keep = None
    if kind == "full":
        keep = _selection(
            a, c_q, {k: w(k) for k in _INDEX_KEYS}, heads=index_heads,
            lanes=index_dim, rope=g["rope"], topk=index_topk, theta=theta,
            relu=index_relu, rotary=index_rope, select=select)
    return _attend(
        x, a, c_q, c, k_pe, keep, {k: w(k) for k in _HEAD_KEYS},
        heads=g["heads"], nope=g["nope"], v=g["v"], theta=theta,
        window=window if kind == "sliding" else None, gate=gate)


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
    """x [t, d] -> (h, sel [t, top_k], weight [t, top_k], s): ``s [t,
    width]`` are the scores of all the experts."""
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["norm2.scale"], eps)
        s = jax.nn.sigmoid(h @ w["router.w"])
    _, sel = jax.lax.top_k(s + w["router.bias"], top_k)
    weight = jnp.take_along_axis(s, sel, axis=-1)
    if norm:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return h, sel, weight * scale, s


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
    chosen = jnp.any(sel[..., None] == at, axis=-2)              # [t, width]
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


@jax.jit
def _head(x, columns):
    with jax.default_matmul_precision("highest"):
        return x @ columns.astype(jnp.float32)


def routed_ffn(params, i, x, top_k, experts, route_scale=1.0, eps=1e-5,
               routed=True, shared=True, route_norm=True, seen=None,
               ties=None):
    """Layer ``i``'s ``FFN(RMS(x))``: the shared expert (``shared``) and
    the held experts' parts (``routed``) for the share ``experts =
    (first, count)``.  ``seen`` (a list) receives the layer's selections
    ``[t, top_k]``; ``ties`` (a list) how far ``[t]`` each row's
    selection is from one that differs in a held expert (``_margin``)."""
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


def trunk(params, tokens, layer_types, full, sliding, window, index_heads,
          index_dim, index_topk, dense_layers, top_k, experts,
          route_scale=1.0, eps=1e-5, rescale=True, gate=True, select="topk",
          index_relu=True, index_rope=True, windowed=True,
          sliding_theta=None, route_norm=True, seen=None,
          before_routing=None, ties=None):
    """The residual ``[t, d]`` float32 after the last layer, for tokens
    ``[t]``.  ``before_routing(i, x)`` is called with the residual that
    routed layer ``i`` is about to route (whoever seeds the weights
    settles the router's bias there, layer by layer).  The switches each
    leave one line of the mathematics out or move it: ``select`` ``"all"``
    attends the whole chain in place of ``S_t`` and ``"first"`` the first
    ``index_topk`` positions; ``index_relu`` / ``index_rope`` the
    indexer's; ``windowed`` the sliding layers' lower bound;
    ``sliding_theta`` their rotary base; ``rescale`` the lora rescale;
    ``gate`` the gate a head; ``route_norm`` the selected weights' sum.
    ``ties`` (a list) receives each routed layer's margins ``[t]``
    (``_margin``): how far a row's expert selection is from one that
    differs in a held expert."""
    x = params["tok_emb.w"][jnp.asarray(tokens, jnp.int32)].astype(
        jnp.float32)
    for i, kind in enumerate(layer_types):
        w = lambda name: params[f"block{i}_{name}"]
        x = attention(
            x, w, kind, full if kind == "full" else sliding,
            window if windowed else None, index_heads, index_dim,
            index_topk, eps, rescale=rescale, gate=gate, select=select,
            index_relu=index_relu, index_rope=index_rope,
            theta=sliding_theta if kind == "sliding" else None)
        if i < dense_layers:
            x = _dense_ffn(x, {k: w(k) for k in _DENSE_KEYS}, eps=eps)
        else:
            if before_routing is not None:
                before_routing(i, x)
            x = x + routed_ffn(params, i, x, top_k, experts, route_scale,
                               eps, route_norm=route_norm, seen=seen,
                               ties=ties)
    return x


def forward(params, tokens, **how):
    """Float32 logits ``[b, t, V]`` of tokens ``[b, t]``, one sequence
    after the other; ``how`` is ``layout``'s dict and ``trunk``'s
    switches (``ties`` receives the margins of the LAST sequence's
    routed layers: the check compares one sequence a call)."""
    eps = how.get("eps", 1e-5)
    scale, head = params["norm_f.scale"].astype(jnp.float32), params[
        "lm_head.w"]
    out = []
    ties = how.get("ties")
    for row in jnp.asarray(tokens, jnp.int32):
        if ties:
            del ties[:]
        x = _rms(trunk(params, row, **how), scale, eps)
        out.append(jnp.concatenate(
            [_head(x, head[:, r:r + HEAD_BLOCK])
             for r in range(0, head.shape[1], HEAD_BLOCK)], axis=-1))
    return jnp.stack(out)
