"""The plain reference of the ``solar_open2`` layout (Upstage
Solar-Open2-250B: three layers of a gated delta rule with a decay a
channel, Kimi Delta Attention, arXiv:2510.26692, to one layer of gated
position-free grouped-query attention; every layer routed), kept here so
that the comparison which decides ``correct`` rests on nothing the
program can change.  It imports nothing of the program.

With ``a = RMS(x)`` (eps ``rms_norm_eps``, statistics in float32), every
layer is ``x <- x + Mix(a)``, ``x <- x + MoE(RMS(x))``; one RMSNorm before
the untied head; the table is not scaled; no rotary anywhere.

**A GQA layer** (``gqa_layers``): ``q = a W_q`` (H heads of D), ``k = a
W_k``, ``v = a W_v`` (H_kv heads), causal softmax over the whole context
at ``D ** -0.5``, query head ``h`` reading K/V head ``h // (H / H_kv)``;
``o = ctx * sigmoid(a W_g)`` lane by lane, ``y = o W_o``.

**A delta layer**: ``q~, k~, v~ = a W_q, a W_k, a W_v``; each passes a
causal depthwise convolution of ``taps`` taps a channel (no bias), then
SiLU; a head ``q = q^ / |q^| D ** -0.5``, ``k = k^ / |k^|`` (``x rsqrt(sum
x^2 + 1e-6)``).  The log decay a KEY LANE, float32: ``g_t = -exp(A_log_h)
softplus((a W_fa) W_fb + dt_bias)``, ``alpha_t = exp(g_t)``; the write
strength ``beta_t = 2 sigmoid(a W_b)``.  State ``S [D, D]`` a head::

    Sb = Diag(alpha_t) S_{t-1};  u_t = v_t - Sb^T k_t
    S_t = Sb + beta_t k_t u_t^T;  o_t = S_t^T q_t

then ``o = RMSNorm_D(o_t; w_o) * sigmoid((a W_ga) W_gb + b_g)``, ``y = o
W_o``.

**The FFN**: ``s = sigmoid(float32(u W_r))`` over all ``width`` experts;
top ``top_k`` of ``s + bias`` (the bias selects only); ``w = s[sel] / (sum
+ 1e-20)`` over all selected whether held or not, times ``scale``; expert
``e`` is ``(silu(u W_gate) * (u W_up)) W_down``; one shared expert of that
form added with weight 1.  THE CUT: only the experts ``first .. first +
count - 1`` are held; what the others would add is left out, here and in
the program alike.

What the published configuration has no key for (the configuration
file's ``assumed`` gives each its reason): the GQA gate lane by lane from
the normed input, before ``W_o``; no q/k norm on GQA layers; ``b_g`` the
only bias; the l2-norm's eps 1e-6 and the output norm's ``rms_norm_eps``;
the state in float32; sigmoid scores with a selecting bias; no group
limit; no multi-token-prediction head.

Straightforward ``jax.numpy``: float32, ``highest`` matmul precision, no
cache, no kernel, no chunked form: the delta rule is a ``lax.scan`` ONE
POSITION AT A TIME, attention is dense over the whole context a block of
query rows, the held experts are a loop with a mask (each expert's
matrices upcast when its turn comes), the head goes in blocks of rows to
the host.  A sequence is walked in blocks of ``ROWS`` rows (the state and
the convolution's last rows carried between them) so that 66,816
positions at the published widths fit beside 6.6 GB of weights.  The
switches of ``forward`` each leave one line of the mathematics out or
move it: they show what the check bites on, never the model.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

_DELTA_KEYS = ("norm1.scale", "delta_q.w", "delta_k.w", "delta_v.w",
               "delta_conv.w", "delta_fa.w", "delta_fb.w", "delta_dt.b",
               "delta_A_log.w", "delta_beta.w", "delta_ga.w", "delta_gb.w",
               "delta_gb.b", "delta_onorm.scale", "delta_out.w")
_ATT_KEYS = ("norm1.scale", "att_q.w", "att_k.w", "att_v.w", "att_gate.w",
             "att_out.w")
_ROUTE_KEYS = ("norm2.scale", "router.w", "router.bias")
ROWS = 4096        # rows of a sequence one call of a sub-layer computes
QUERY_ROWS = 512   # query rows one dense attention call scores
NORM_EPS = 1e-6    # of the l2-norm of a head's query and key


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "beta_scale", "decay", "delta_term", "l2norm"))
def _delta(x, w, S, tail, keep, heads, eps, beta_scale=2.0,
           decay="channel", delta_term=True, l2norm=True):
    """Rows ``x [n, d]`` float32 of ONE sequence -> ``(x + Delta(RMS(x)),
    S', tail')``: ``S [H, D, D]`` the state before the first row, ``tail
    [taps - 1, 3 H D]`` the convolution's rows before it, ``keep [n]`` 0
    where the state is FORGOTTEN before the row (a hit that lost it).
    ``beta_scale``: 2, or 1 where negative eigenvalues are not allowed;
    ``decay``: ``"channel"`` or ``"head"`` (a head's mean in place of a
    lane's); ``delta_term``: ``u = v - Sb^T k`` (else ``u = v``);
    ``l2norm``: q and k of length 1."""
    w = _f32(w)
    n = x.shape[0]
    D = w["delta_out.w"].shape[0] // heads
    taps = w["delta_conv.w"].shape[1]
    with jax.default_matmul_precision("highest"):
        a = _rms(x, w["norm1.scale"], eps)
        rows = jnp.concatenate(
            [tail, jnp.concatenate([a @ w["delta_q.w"], a @ w["delta_k.w"],
                                    a @ w["delta_v.w"]], axis=-1)], axis=0)
        # the causal depthwise convolution: row j sees rows j - taps + 1 .. j
        conv = jax.nn.silu(sum(rows[j:j + n] * w["delta_conv.w"][:, j]
                               for j in range(taps)))
        q, k, v = (conv[:, i * heads * D:(i + 1) * heads * D].reshape(
            n, heads, D) for i in range(3))
        if l2norm:
            unit = lambda r: r * jax.lax.rsqrt(                # noqa: E731
                jnp.sum(jnp.square(r), axis=-1, keepdims=True) + NORM_EPS)
            q, k = unit(q), unit(k)
        q = q * D ** -0.5
        g = -(jnp.exp(w["delta_A_log.w"])[:, None] * jax.nn.softplus(
            (a @ w["delta_fa.w"]) @ w["delta_fb.w"]
            + w["delta_dt.b"]).reshape(n, heads, D))
        if decay == "head":
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
        beta = beta_scale * jax.nn.sigmoid(a @ w["delta_beta.w"])

        def one(S, row):
            q_t, k_t, v_t, g_t, b_t, keep_t = row
            Sb = jnp.exp(g_t)[..., None] * (S * keep_t)
            u = v_t
            if delta_term:
                u = u - jnp.sum(Sb * k_t[..., None], axis=-2)
            S = Sb + (b_t[:, None] * k_t)[..., None] * u[:, None, :]
            return S, jnp.sum(S * q_t[..., None], axis=-2)

        S, o = jax.lax.scan(one, S, (q, k, v, g, beta, keep))
        o = _rms(o, w["delta_onorm.scale"], eps).reshape(n, heads * D)
        o = o * jax.nn.sigmoid((a @ w["delta_ga.w"]) @ w["delta_gb.w"]
                               + w["delta_gb.b"])
        return x + o @ w["delta_out.w"], S, rows[n:]


@functools.partial(jax.jit, static_argnames=("kv_heads", "eps"))
def _keys(x, w, kv_heads, eps):
    """``(K, V) [n, kv_heads, D]`` of rows ``x [n, d]``."""
    with jax.default_matmul_precision("highest"):
        a = _rms(x, w["norm1.scale"].astype(jnp.float32), eps)
        shape = (x.shape[0], kv_heads, -1)
        return ((a @ w["att_k.w"].astype(jnp.float32)).reshape(shape),
                (a @ w["att_v.w"].astype(jnp.float32)).reshape(shape))


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "gate"))
def _attention(x, w, K, V, first, n_head, eps, gate=True):
    """Query rows ``x [n, d]`` at positions ``first ..`` -> ``x +
    Attn(RMS(x))`` over the keys ``K, V [t, kv_heads, D]`` of the whole
    sequence, causal; dense scores a K/V head.  ``gate``: the sigmoid gate
    lane by lane."""
    w = _f32(w)
    n, (t, kv_heads, D) = x.shape[0], K.shape
    with jax.default_matmul_precision("highest"):
        a = _rms(x, w["norm1.scale"], eps)
        q = (a @ w["att_q.w"]).reshape(n, kv_heads, n_head // kv_heads, D)
        mask = jnp.arange(t)[None, :] <= first + jnp.arange(n)[:, None]

        def one(head):
            """One K/V head and the query heads that read it."""
            qh, kh, vh = head                      # [n, g, D], [t, D]
            s = jnp.einsum("qgd,kd->gqk", qh, kh) * D ** -0.5
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->qgd", p, vh)

        ctx = jax.lax.map(one, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(K, 1, 0),
                                jnp.moveaxis(V, 1, 0)))
        o = jnp.moveaxis(ctx, 0, 1).reshape(n, -1)
        if gate:
            o = o * jax.nn.sigmoid(a @ w["att_gate.w"])
        return x + o @ w["att_out.w"]


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "norm",
                                             "eps"))
def _route(x, w, top_k, scale, norm, eps):
    """x [n, d] -> (u, sel [n, top_k], weight [n, top_k], s): ``s [n,
    width]`` are the scores of all the experts."""
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        u = _rms(x, w["norm2.scale"], eps)
        s = jax.nn.sigmoid(u @ w["router.w"])
    _, sel = jax.lax.top_k(s + w["router.bias"], top_k)
    weight = jnp.take_along_axis(s, sel, axis=-1)
    if norm:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return u, sel, weight * scale, s


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
    chosen = jnp.any(sel[..., None] == at, axis=-2)              # [n, width]
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
def _expert(u, gate, up, down):
    """``(silu(u W_gate) * (u W_up)) W_down``."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        return (jax.nn.silu(u @ gate.astype(f32)) * (u @ up.astype(f32))
                ) @ down.astype(f32)


@jax.jit
def _add_expert(y, u, sel, weight, expert, gate, up, down):
    """y + (the weight each row gave ``expert``, 0 where it did not
    select it) x Expert(u): the mask over the rows."""
    mine = jnp.sum(jnp.where(sel == expert, weight, 0.0), axis=-1)
    return y + mine[..., None] * _expert(u, gate, up, down)


@jax.jit
def _head(x, scale, columns):
    with jax.default_matmul_precision("highest"):
        return x @ (scale[:, None] * columns.astype(jnp.float32))


def routed_ffn(params, i, x, top_k, experts, route_scale, eps=1e-5,
               routed=True, shared=True, route_norm=True, seen=None,
               ties=None):
    """Layer ``i``'s ``FFN(RMS(x))`` of rows ``x [n, d]``: the shared
    expert (``shared``) and the held experts' parts (``routed``) for the
    share ``experts = (first, count)``.  ``seen`` (a list) receives the
    selections ``[n, top_k]``; ``ties`` (a list) how far ``[n]`` each row's
    selection is from one that differs in a held expert (``_margin``)."""
    w = lambda name: params[f"block{i}_{name}"]                # noqa: E731
    first, count = experts
    u, sel, weight, s = _route(x, {k: w(k) for k in _ROUTE_KEYS},
                               top_k=top_k, scale=route_scale,
                               norm=route_norm, eps=eps)
    if seen is not None:
        seen.append(sel)
    if ties is not None:
        ties.append(_margin(s, w("router.bias").astype(jnp.float32), sel,
                            first=first, count=count))
    y = jnp.zeros_like(u)
    if shared:
        y = _expert(u, w("shared_gate.w"), w("shared_up.w"),
                    w("shared_down.w"))
    if routed:
        for e in range(count):
            y = _add_expert(y, u, sel, weight, first + e,
                            w("experts_gate.w")[e], w("experts_up.w")[e],
                            w("experts_down.w")[e])
    return y


def _blocks(t, cuts=()):
    """``[(first, end)]`` of the row blocks of a sequence of ``t`` rows:
    ``ROWS`` rows each, cut again at every position of ``cuts``."""
    edges = sorted({0, t, *range(0, t, ROWS), *(int(c) for c in cuts
                                                 if 0 < int(c) < t)})
    return list(zip(edges[:-1], edges[1:]))


def trunk(params, tokens, n_layer, gqa_layers, n_head, kv_heads,
          delta_heads, top_k, experts, route_scale, eps=1e-5, routed=True,
          shared=True, route_norm=True, gqa_gate=True, lost=(),
          inject=None, capture=None, seen=None, ties=None,
          before_routing=None, **delta):
    """The residual ``[t, d]`` float32 after the last layer, for ONE
    sequence of tokens ``[t]``.  ``lost`` (positions): the delta layers'
    state forgotten before each of them, as a prefix hit that started from
    zeros would; ``inject = (position, [S a delta layer])``: the state
    REPLACED before that position, as a hit that restored another
    snapshot would; ``capture = (position, list)``: the list receives each
    delta layer's state before that position.  ``before_routing(i, x)`` is
    called with the residual that layer ``i`` is about to route (whoever
    seeds the weights settles the router's bias there, layer by layer);
    ``delta`` are ``_delta``'s switches."""
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    x = params["tok_emb.w"][tokens].astype(jnp.float32)
    cuts = [given[0] for given in (inject, capture) if given is not None]
    blocks = _blocks(t, cuts)
    keep = np.ones((t,), np.float32)
    keep[[int(p) for p in lost if 0 <= int(p) < t]] = 0.0
    keep = jnp.asarray(keep)
    n_delta = 0
    for i in range(n_layer):
        w = lambda name: params[f"block{i}_{name}"]            # noqa: E731
        out = []
        if i in gqa_layers:
            wa = {k: w(k) for k in _ATT_KEYS}
            kv = [_keys(x[a:b], wa, kv_heads=kv_heads, eps=eps)
                  for a, b in blocks]
            K, V = (jnp.concatenate(m) for m in zip(*kv))
            for a in range(0, t, QUERY_ROWS):
                out.append(_attention(x[a:a + QUERY_ROWS], wa, K, V, a,
                                      n_head=n_head, eps=eps, gate=gqa_gate))
        else:
            wd = {k: w(k) for k in _DELTA_KEYS}
            D = wd["delta_out.w"].shape[0] // delta_heads
            S = jnp.zeros((delta_heads, D, D), jnp.float32)
            tail = jnp.zeros((wd["delta_conv.w"].shape[1] - 1,
                              3 * delta_heads * D), jnp.float32)
            for a, b in blocks:
                if capture is not None and a == capture[0]:
                    capture[1].append(S)
                if inject is not None and a == inject[0]:
                    S = inject[1][n_delta]
                y, S, tail = _delta(x[a:b], wd, S, tail, keep[a:b],
                                    heads=delta_heads, eps=eps, **delta)
                out.append(y)
            n_delta += 1
        x = jnp.concatenate(out)
        if before_routing is not None:
            before_routing(i, x)
        x = jnp.concatenate([
            x[a:b] + routed_ffn(
                params, i, x[a:b], top_k, experts, route_scale, eps,
                routed=routed, shared=shared, route_norm=route_norm,
                seen=seen, ties=ties)
            for a, b in blocks])
    return x


def forward(params, tokens, *layout, eps=1e-5, **switches):
    """Next-token logits ``[b, t, V]`` float32 (a NumPy array: the head's
    rows go to the host a block at a time) for tokens ``[b, t]``, one
    sequence after the other; the arguments are ``trunk``'s, whose
    ``seen`` and ``ties`` (lists) receive, a routed layer a block of rows,
    the selections and how nearly they were others (the LAST sequence's:
    the check compares one sequence a call)."""
    tokens = np.asarray(tokens)
    scale = params["norm_f.scale"].astype(jnp.float32)
    head = params["lm_head.w"]
    out = np.zeros(tokens.shape + (head.shape[1],), np.float32)
    for n, row in enumerate(tokens):
        for given in (switches.get("seen"), switches.get("ties")):
            if given:
                del given[:]
        x = trunk(params, row, *layout, eps=eps, **switches)
        for a, b in _blocks(row.shape[0]):
            out[n, a:b] = np.asarray(_head(_rms(x[a:b], 1.0, eps), scale,
                                           head))
    return out
