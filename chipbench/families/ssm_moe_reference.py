"""The benchmark's copy of ``paddle_tpu/models/ssm_moe_reference.py``
(the plain reference of the ``nemotron_h`` layout: NVIDIA Nemotron-H /
Nemotron 3 Nano, arXiv:2504.03624; Mamba-2, arXiv:2405.21060), kept here
so that the comparison which decides ``correct`` rests on nothing the
program can change.  It imports nothing of the program;
``chipbench/tests/test_ssm_moe_family.py`` holds the two files to the
same text below this docstring, and the program's copy carries the
equations and every departure in its own.

Straightforward ``jax.numpy``: float32, ``highest`` matmul precision, no
cache, no kernel, no batching tricks; one jitted function a sub-layer,
the recurrence a ``lax.scan`` over positions, a loop over the held
experts with a mask, each expert's matrices upcast when its turn comes
(the 10.5 GB of bfloat16 weights stay where they are on the device
during the check), the head in blocks of vocabulary rows.  The switches
of ``forward`` each leave one line of the mathematics out or move it:
they show what the check bites on, never the model.
"""

import functools

import jax
import jax.numpy as jnp

_M_KEYS = ("norm.scale", "ssm_in.w", "ssm_conv.w", "ssm_conv.b", "ssm_dt.b",
           "ssm_A_log.w", "ssm_D.w", "ssm_norm.scale", "ssm_out.w")
_ATT_KEYS = ("norm.scale", "att_qkv.w", "att_out.w")
_ROUTE_KEYS = ("norm.scale", "router.w", "router.bias")
HEAD_BLOCK = 32768  # vocabulary rows one head call multiplies


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "eps", "skip", "dt_bias", "gate_first", "group_norm",
    "tails_every", "state_every", "state_dtype"))
def _mamba(x, w, heads, groups, eps, skip=True, dt_bias=True,
           gate_first=True, group_norm=True, tails_every=0, state_every=0,
           state_dtype="float32", lost=None):
    """x [b, t, d] float32 -> x + Mamba2(RMS(x)).  ``skip``: the ``D x``
    term; ``dt_bias``: the bias under the softplus; ``gate_first``: the
    gate before the norm (else after it); ``group_norm``: a norm a group
    (else one over all the lanes); ``tails_every`` / ``state_every`` (a
    number of rows, 0 for never): the convolution's rows / the state
    forgotten at every multiple of it, as a piece boundary that lost
    them would; ``lost`` (positions, an int32 array): the state forgotten
    before each of them, as a hand-over from the prompt's pieces to the
    decode steps that lost it would; ``state_dtype``: what the state is
    rounded to after every position, as a cache that held it so would."""
    w = _f32(w)
    b, t, _ = x.shape
    inner = w["ssm_out.w"].shape[0]
    P = inner // heads
    taps = w["ssm_conv.w"].shape[1]
    N = (w["ssm_conv.w"].shape[0] - inner) // (2 * groups)
    at = jnp.arange(t)
    with jax.default_matmul_precision("highest"):
        u = _rms(x, w["norm.scale"], eps)
        zxd = u @ w["ssm_in.w"]
        z = zxd[..., :inner]
        xbc = zxd[..., inner:2 * inner + 2 * groups * N]
        dt = zxd[..., 2 * inner + 2 * groups * N:]
        # the causal depthwise convolution: row j sees rows j - taps + 1 .. j
        padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = w["ssm_conv.b"]
        for k in range(taps):
            rows = padded[:, k:k + t]
            if tails_every:
                back = taps - 1 - k
                seen = at - back >= at // tails_every * tails_every
                rows = jnp.where(seen[None, :, None], rows, 0.0)
            conv = conv + rows * w["ssm_conv.w"][:, k]
        a = jax.nn.silu(conv)
        xs = a[..., :inner].reshape(b, t, heads, P)
        per = heads // groups
        B = jnp.repeat(a[..., inner:inner + groups * N].reshape(
            b, t, groups, N), per, axis=2)
        C = jnp.repeat(a[..., inner + groups * N:].reshape(
            b, t, groups, N), per, axis=2)
        delta = jax.nn.softplus(dt + (w["ssm_dt.b"] if dt_bias else 0.0))
        A = -jnp.exp(w["ssm_A_log.w"])
        keep = jnp.ones((t,)) if not state_every else (
            at % state_every != 0).astype(jnp.float32)
        if lost is not None:
            keep = keep * jnp.all(at[:, None] != lost[None, :], axis=1)

        def one(S, row):
            x_t, B_t, C_t, d_t, k_t = row
            S = (jnp.exp(d_t * A)[..., None, None] * S * k_t
                 + (d_t[..., None] * x_t)[..., None] * B_t[..., None, :])
            S = S.astype(state_dtype).astype(jnp.float32)
            return S, jnp.sum(S * C_t[..., None, :], axis=-1)

        _, y = jax.lax.scan(
            one, jnp.zeros((b, heads, P, N), jnp.float32),
            tuple(jnp.moveaxis(v, 1, 0) for v in (xs, B, C, delta)) + (keep,))
        y = jnp.moveaxis(y, 0, 1)                            # [b, t, H, P]
        if skip:
            y = y + w["ssm_D.w"][:, None] * xs
        y = y.reshape(b, t, inner)
        gate = jax.nn.silu(z)
        if gate_first:
            y = y * gate
        lanes = inner // groups if group_norm else inner
        y = _rms(y.reshape(b, t, -1, lanes), 1.0, eps).reshape(b, t, inner)
        y = y * w["ssm_norm.scale"]
        if not gate_first:
            y = y * gate
        return x + y @ w["ssm_out.w"]


@functools.partial(jax.jit, static_argnames=("n_head", "kv_heads", "eps",
                                             "score_scaled"))
def _attention(x, w, n_head, kv_heads, eps, score_scaled=True):
    """x [b, t, d] float32 -> x + Attn(RMS(x)); no positional signal.
    ``score_scaled``: the scores' ``head_dim ** -0.5``."""
    w = _f32(w)
    b, t, _ = x.shape
    dh = w["att_out.w"].shape[0] // n_head
    with jax.default_matmul_precision("highest"):
        u = _rms(x, w["norm.scale"], eps)
        qkv = u @ w["att_qkv.w"]
        nq, nk = n_head * dh, kv_heads * dh
        q = qkv[..., :nq].reshape(b, t, kv_heads, n_head // kv_heads, dh)
        k = qkv[..., nq:nq + nk].reshape(b, t, kv_heads, dh)
        v = qkv[..., nq + nk:].reshape(b, t, kv_heads, dh)
        at = jnp.arange(t)
        mask = at[None, :] <= at[:, None]

        def one(head):
            """One K/V head and the query heads that read it."""
            qh, kh, vh = head                     # [b, t, g, dh], [b, t, dh]
            s = jnp.einsum("bqgd,bkd->bgqk", qh, kh)
            if score_scaled:
                s = s / jnp.sqrt(float(dh))
            a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            return jnp.einsum("bgqk,bkd->bqgd", a, vh)

        ctx = jax.lax.map(one, tuple(jnp.moveaxis(m, 2, 0)
                                     for m in (q, k, v)))
        ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, t, -1)
        return x + ctx @ w["att_out.w"]


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "norm",
                                             "eps"))
def _route(x, w, top_k, scale, norm, eps):
    """x [b, t, d] -> (u, sel [b, t, top_k], weight [b, t, top_k], s):
    ``s [b, t, width]`` are the scores of all the experts."""
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        u = _rms(x, w["norm.scale"], eps)
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


@functools.partial(jax.jit, static_argnames=("squared", "up_transposed"))
def _expert(u, up, down, squared=True, up_transposed=False):
    """``relu(u W_up) ** 2 W_down``; a routed expert's ``W_up`` is held
    transposed (``[e, d]``)."""
    with jax.default_matmul_precision("highest"):
        up = up.astype(jnp.float32)
        a = jax.nn.relu(u @ (up.T if up_transposed else up))
        return (jnp.square(a) if squared else a) @ down.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("squared",))
def _add_expert(y, u, sel, weight, expert, up, down, squared=True):
    """y + (the weight each row gave ``expert``, 0 where it did not
    select it) x Expert(u): the mask over the rows."""
    mine = jnp.sum(jnp.where(sel == expert, weight, 0.0), axis=-1)
    return y + mine[..., None] * _expert(u, up, down, squared=squared,
                                         up_transposed=True)


@jax.jit
def _head(x, columns):
    with jax.default_matmul_precision("highest"):
        return x @ columns.astype(jnp.float32)


def routed_ffn(params, i, x, top_k, experts, route_scale, eps=1e-5,
               routed=True, shared=True, route_norm=True, squared=True,
               seen=None, ties=None):
    """Layer ``i``'s ``FFN(RMS(x))``: the shared expert (``shared``) and
    the held experts' parts (``routed``) for the share ``experts =
    (first, count)``.  ``seen`` (a list) receives the layer's selections
    ``[b, t, top_k]``; ``ties`` (a list) how far ``[b, t]`` each row's
    selection is from one that differs in a held expert (``_margin``)."""
    w = lambda name: params[f"block{i}_{name}"]
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
        y = _expert(u, w("shared_up.w"), w("shared_down.w"), squared=squared)
    if routed:
        for e in range(count):
            y = _add_expert(y, u, sel, weight, first + e,
                            w("experts_up.w")[e], w("experts_down.w")[e],
                            squared=squared)
    return y


def trunk(params, tokens, pattern, n_head, kv_heads, ssm_heads, ssm_groups,
          top_k, experts, route_scale, eps=1e-5, routed=True, shared=True,
          route_norm=True, route_scaled=True, squared=True,
          score_scaled=True, seen=None, ties=None, before_routing=None,
          **mamba):
    """The residual [b, t, d] float32 after the last layer, for tokens
    [b, t].  ``before_routing(i, x)`` is called with the residual that
    routed layer ``i`` is about to route (whoever seeds the weights
    settles the router's bias there, layer by layer); ``mamba`` are
    ``_mamba``'s switches."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["tok_emb.w"][tokens].astype(jnp.float32)
    for i, kind in enumerate(pattern):
        w = lambda name: params[f"block{i}_{name}"]
        if kind == "M":
            x = _mamba(x, {k: w(k) for k in _M_KEYS}, heads=ssm_heads,
                       groups=ssm_groups, eps=eps, **mamba)
        elif kind == "*":
            x = _attention(x, {k: w(k) for k in _ATT_KEYS}, n_head=n_head,
                           kv_heads=kv_heads, eps=eps,
                           score_scaled=score_scaled)
        else:
            if before_routing is not None:
                before_routing(i, x)
            x = x + routed_ffn(
                params, i, x, top_k, experts,
                route_scale if route_scaled else 1.0, eps, routed=routed,
                shared=shared, route_norm=route_norm, squared=squared,
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
