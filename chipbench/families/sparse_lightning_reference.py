"""The plain reference of the ``minicpm_sala`` layout (openbmb
MiniCPM-SALA: block-sparse grouped-query attention, InfLLM-V2,
arXiv:2509.24663 / MiniCPM4, arXiv:2506.07900, in one layer of four;
Lightning Attention-2, arXiv:2401.04658, in the others), kept here so
that the comparison which decides ``correct`` rests on nothing the
program can change.  It imports nothing of the program.

**Stream.**  ``h0 = scale_emb E[id]``; a layer is ``h += r Mix(RMS(h))``,
``h += r FFN(RMS(h))`` with ``r = scale_depth / sqrt(L)`` (``L`` the
PUBLISHED depth), the FFN ``(silu(u W_g) * (u W_u)) W_d``; ``logits =
W_head (RMS(h) * head_scale)``, ``head_scale = dim_model_base /
hidden_size``; RMSNorm statistics in float32, eps ``rms_norm_eps``.

**An ``S`` layer** (``minicpm4``).  ``q = a W_q`` (H heads of D), ``k, v =
a W_k, a W_v`` (H_kv heads), NO rotary, scores at ``D ** -0.5``, causal;
the joined heads times ``sigmoid(a W_gate)``, then ``W_o``.  A query at
position ``t < dense_len`` attends every position ``<= t``.  From
``dense_len`` on, InfLLM-V2: ``Kc[j] = mean(k[stride j : stride j +
kernel])`` a K/V head; over the ``j`` whose window lies wholly at or
before ``t``, ``p = softmax(q_h . Kc / sqrt(D))`` a query head, summed
over the heads of the K/V group; a block of ``block`` positions scores
the maximum of ``p`` over the ``j`` whose window overlaps it; the group
attends the first ``init_blocks`` blocks, the ``window_blocks`` blocks
that end with the query's own, and the ``topk`` of largest score among
the others that lie before them, causally, ONE softmax over the union.

**An ``L`` layer** (``lightning-attn``).  ``q, k, v = a W_q, a W_k, a
W_v`` (H_l heads of D_l); RMSNorm a head on q and k (gains of D_l);
rotary (rotate-half over all D_l lanes, ``theta``) on q and k; a head's
state ``S [D_l, D_l]`` float32::

    S_t = exp(-s) S_{t-1} + k_t^T v_t;   o_t = (q_t D_l ** -0.5) S_t

with ``s`` the head's slope in this layer (``slopes``, whoever calls
states them); RMSNorm a head of the read (a gain of D_l) times
``sigmoid(a W_gate)`` lane by lane, then ``W_o``.

What the published configuration has no key for is listed, each with its
reason, under ``assumed`` in ``chipbench/configs/minicpm-sala.json`` and
in ``chipbench/SALA.md``: the sparse sizes (MiniCPM4's ``sparse_config``),
the forced blocks beside the ``topk`` (not among them), the maximum over
overlapping compressed rows, the switch at ``dense_len`` by the QUERY's
position, q/k norm on the ``L`` layers only, both gates lane by lane from
the normed input, the output norm a head, no SiLU on q, k, v, the slopes
``2 ** (-8 h / H)`` times the layer factor ``1 - l / (L - 1)``, the state
and the selection's scores in float32.

Straightforward ``jax.numpy``: float32, ``highest`` matmul precision, no
cache, no kernel, no chunked form: an ``L`` layer is a ``lax.scan`` ONE
POSITION AT A TIME, an ``S`` layer is dense scores over the whole context
under a MASK built from the selection equations, a block of query rows
at a time; the residual is kept as a list of blocks of ``ROWS`` rows so
that 132,352 positions at the published widths fit beside 5.6 GB of
weights; the head goes in blocks of rows to the host, from row
``rows_from`` on.  The switches of ``trunk`` each leave one line of the
mathematics out or move it: they show what the check bites on, never
the model.
"""

import functools
import mmap

import jax
import jax.numpy as jnp
import numpy as np

_LIN_KEYS = ("norm1.scale", "lin_q.w", "lin_k.w", "lin_v.w", "lin_gate.w",
             "lin_out.w", "lin_qnorm.scale", "lin_knorm.scale",
             "lin_onorm.scale")
_ATT_KEYS = ("norm1.scale", "att_q.w", "att_k.w", "att_v.w", "att_gate.w",
             "att_out.w")
_FFN_KEYS = ("norm2.scale", "ffn_gate.w", "ffn_up.w", "ffn_down.w")
ROWS = 2048        # rows of a sequence one call of a sub-layer computes
QUERY_ROWS = 64    # query rows one masked attention call scores


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rotate(x, pos, theta):
    """Rotate-half rotary of ``x [n, H, D]`` at positions ``pos [n]``:
    lane ``i`` pairs with lane ``i + D / 2``."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None, None] * inv          # [n, 1, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "theta", "scale", "rope", "gate", "decay"))
def _lightning(x, w, S, slopes, first, keep, heads, eps, theta, scale,
               rope=True, gate=True, decay=True):
    """Rows ``x [n, d]`` float32 of ONE sequence at positions ``first ..``
    -> ``(x + scale Lightning(RMS(x)), S')``: ``S [H, D, D]`` the state
    before the first row, ``slopes [H]``, ``keep [n]`` 0 where the state
    is FORGOTTEN before the row (a hit that lost it).  ``rope``,
    ``gate``, ``decay``: each leaves that line out."""
    w = _f32(w)
    n = x.shape[0]
    D = w["lin_out.w"].shape[0] // heads
    with jax.default_matmul_precision("highest"):
        a = _rms(x, w["norm1.scale"], eps)
        q, k, v = ((a @ w[f"lin_{m}.w"]).reshape(n, heads, D) for m in "qkv")
        q = _rms(q, w["lin_qnorm.scale"], eps)
        k = _rms(k, w["lin_knorm.scale"], eps)
        if rope:
            pos = first + jnp.arange(n)
            q, k = _rotate(q, pos, theta), _rotate(k, pos, theta)
        q = q * D ** -0.5
        lam = jnp.exp(-slopes)[:, None, None] if decay else 1.0

        def one(S, row):
            q_t, k_t, v_t, keep_t = row
            S = lam * (S * keep_t) + k_t[:, :, None] * v_t[:, None, :]
            return S, jnp.sum(q_t[:, :, None] * S, axis=1)

        S, o = jax.lax.scan(one, S, (q, k, v, keep))
        o = _rms(o, w["lin_onorm.scale"], eps).reshape(n, heads * D)
        if gate:
            o = o * jax.nn.sigmoid(a @ w["lin_gate.w"])
        return x + scale * (o @ w["lin_out.w"]), S


@functools.partial(jax.jit, static_argnames=("kv_heads", "eps", "theta",
                                             "rope"))
def _keys(x, w, first, kv_heads, eps, theta, rope=False):
    """``(K, V) [n, kv_heads, D]`` of rows ``x [n, d]``."""
    with jax.default_matmul_precision("highest"):
        a = _rms(x, w["norm1.scale"].astype(jnp.float32), eps)
        shape = (x.shape[0], kv_heads, -1)
        K = (a @ w["att_k.w"].astype(jnp.float32)).reshape(shape)
        if rope:
            K = _rotate(K, first + jnp.arange(x.shape[0]), theta)
        return K, (a @ w["att_v.w"].astype(jnp.float32)).reshape(shape)


def _compressed(K, kernel, stride, straddle=None):
    """``Kc [n_c, kv_heads, D]``: ``Kc[j] = mean(K[stride j : stride j +
    kernel])`` for every window that lies inside ``K [t, ..]``.
    ``straddle`` (a position): the rows whose window holds it and the
    position before it take the mean of a window whose part from it on
    is ZEROS, as a chain that served another tail would hold them."""
    t = K.shape[0]
    n_c = max((t - kernel) // stride + 1, 0)
    at = (np.arange(n_c)[:, None] * stride + np.arange(kernel)[None, :])
    rows = K[at.reshape(-1)].reshape(n_c, kernel, *K.shape[1:])
    if straddle is not None:
        cut = (at >= straddle) & (at[:, :1] < straddle)
        rows = jnp.where(jnp.asarray(cut)[:, :, None, None], 0.0, rows)
    return jnp.mean(rows, axis=1)


def _overlaps(n_blocks, n_c, kernel, stride, block):
    """``(idx [n_blocks, m], real)``: the compressed rows whose window
    overlaps each block of ``block`` positions (``real`` false where the
    row does not exist)."""
    b = np.arange(n_blocks)
    lo = -(-(block * b - kernel + 1) // stride)
    hi = (block * (b + 1) - 1) // stride
    m = int((hi - lo).max()) + 1
    idx = lo[:, None] + np.arange(m)[None, :]
    real = (idx >= 0) & (idx <= hi[:, None]) & (idx < n_c)
    return np.clip(idx, 0, max(n_c - 1, 0)), real


@functools.partial(jax.jit, static_argnames=(
    "n_head", "eps", "theta", "scale", "kernel", "stride", "block", "topk",
    "init_blocks", "window_blocks", "dense_len", "select", "group_sum",
    "gate", "rope"))
def _attention(x, w, K, V, Kc, first, n_head, eps, theta, scale, kernel,
               stride, block, topk, init_blocks, window_blocks, dense_len,
               select=True, group_sum=True, gate=True, rope=False):
    """Query rows ``x [n, d]`` at positions ``first ..`` -> ``(x + scale
    Attn(RMS(x)), gap [n])`` over the keys ``K, V [t, kv_heads, D]`` of
    the whole sequence and their compressed keys ``Kc``; ``gap`` is how
    far, relative to the ``topk``-th block's score, the next block lies
    under it (where those two score alike, the pair from its neighbours;
    the least over the K/V heads; ``inf`` for a row that selects
    nothing).  ``select`` false: every row attends densely;
    ``group_sum`` false: the selection takes the FIRST head's ``p`` in
    place of the group's sum; ``gate``, ``rope``: that line left out or
    put in."""
    w = _f32(w)
    n, (t, kv_heads, D) = x.shape[0], K.shape
    n_blocks = -(-t // block)
    n_c = Kc.shape[0]
    pos = first + jnp.arange(n)
    with jax.default_matmul_precision("highest"):
        a = _rms(x, w["norm1.scale"], eps)
        q = (a @ w["att_q.w"]).reshape(n, n_head, D)
        if rope:
            q = _rotate(q, pos, theta)
        q = q.reshape(n, kv_heads, n_head // kv_heads, D)
        causal = jnp.arange(t)[None, :] <= pos[:, None]
        sparse_row = pos >= dense_len
        idx, real = _overlaps(n_blocks, n_c, kernel, stride, block)
        b = jnp.arange(n_blocks)[None, :]
        own = (pos // block)[:, None]
        forced = (b < init_blocks) | ((b > own - window_blocks) & (b <= own))
        cand = ~forced & (b < own)

        def one(head):
            """One K/V head and the query heads that read it."""
            qh, kh, vh, kc = head              # [n, g, D], [t, D], [n_c, D]
            visible = causal
            gap = jnp.full((n,), jnp.inf)
            if select and n_c and n_blocks > init_blocks + window_blocks:
                whole = (jnp.arange(n_c)[None, :] * stride + kernel - 1
                         <= pos[:, None])                       # [n, n_c]
                sc = jnp.einsum("qgd,cd->gqc", qh, kc) * D ** -0.5
                p = jax.nn.softmax(jnp.where(whole, sc, -jnp.inf), axis=-1)
                p = jnp.where(whole, p, 0.0)
                p = jnp.sum(p, axis=0) if group_sum else p[0]
                score = jnp.max(jnp.where(real, p[:, idx], 0.0), axis=-1)
                score = jnp.where(cand, score, -1.0)
                top, at = jax.lax.top_k(score, min(topk + 2, n_blocks))
                chosen = jnp.any((at[:, :topk, None] == b[:, None, :])
                                 & (top[:, :topk, None] >= 0.0), axis=1)
                blocks = forced | chosen
                by_pos = jnp.repeat(blocks, block, axis=1)[:, :t]
                visible = jnp.where(sparse_row[:, None], causal & by_pos,
                                    causal)
                if 2 <= topk < n_blocks - 1:
                    # how far the selection is from another: the last
                    # block taken over the best one left out; where the
                    # two score ALIKE (neighbours whose maximum is the
                    # compressed row they share: the lower one is taken,
                    # here and in the program), the pair against the
                    # blocks on either side of it
                    rel = lambda hi, lo: (                       # noqa: E731
                        (hi - lo) / jnp.maximum(hi, 1e-30))
                    above, last = top[:, topk - 2], top[:, topk - 1]
                    nxt, below = top[:, topk], top[:, topk + 1]
                    near = jnp.where(last > nxt, rel(last, nxt), jnp.minimum(
                        rel(above, last), rel(nxt, jnp.maximum(below, 0.0))))
                    gap = jnp.where(sparse_row & (nxt >= 0.0), near, jnp.inf)
            s = jnp.einsum("qgd,kd->gqk", qh, kh) * D ** -0.5
            p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->qgd", p, vh), gap

        ctx, gap = jax.lax.map(one, (
            jnp.moveaxis(q, 1, 0), jnp.moveaxis(K, 1, 0),
            jnp.moveaxis(V, 1, 0), jnp.moveaxis(Kc, 1, 0)))
        o = jnp.moveaxis(ctx, 0, 1).reshape(n, -1)
        if gate:
            o = o * jax.nn.sigmoid(a @ w["att_gate.w"])
        return x + scale * (o @ w["att_out.w"]), jnp.min(gap, axis=0)


@functools.partial(jax.jit, static_argnames=("eps", "scale"))
def _ffn(x, w, eps, scale):
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        u = _rms(x, w["norm2.scale"], eps)
        return x + scale * ((jax.nn.silu(u @ w["ffn_gate.w"])
                             * (u @ w["ffn_up.w"])) @ w["ffn_down.w"])


@functools.partial(jax.jit, static_argnames=("eps", "head_scale"))
def _head(x, scale, columns, eps, head_scale):
    with jax.default_matmul_precision("highest"):
        return (_rms(x, scale, eps) * head_scale) @ columns.astype(
            jnp.float32)


def _blocks(t, cuts=(), rows=None):
    """``[(first, end)]`` of the row blocks of a sequence of ``t`` rows:
    ``rows`` (``ROWS``) rows each, cut again at every position of
    ``cuts``."""
    edges = sorted({0, t, *range(0, t, rows or ROWS),
                    *(int(c) for c in cuts if 0 < int(c) < t)})
    return list(zip(edges[:-1], edges[1:]))


def trunk(params, tokens, mixers, slopes, n_head, kv_heads, lin_heads,
          sparse, embed_scale=1.0, residual_scale=1.0, eps=1e-6,
          theta=10000.0, rows_from=0, residual=None, lost=(), inject=None,
          capture=None, ties=None, straddle=None, sparse_gate=True,
          lin_gate=True, sparse_rope=False, **how):
    """The residual after the last layer, for ONE sequence of tokens
    ``[t]``: a list of ``(first, end, rows [end - first, d] float32)``,
    the blocks a caller needs (those that end past ``rows_from``: the
    LAST layer computes no others).  ``mixers``: ``"S"`` or ``"L"`` a held
    layer; ``slopes``: ``[H_l]`` a held ``L`` layer; ``sparse``: the
    selection's sizes (``kernel``, ``stride``, ``block``, ``topk``,
    ``init_blocks``, ``window_blocks``, ``dense_len``).  ``residual [t,
    d]``: the stream that enters the first held layer, in place of the
    table's rows.  ``lost`` (positions): the ``L`` layers' state forgotten
    before each; ``inject = (position, [S a L layer])``: the state
    REPLACED before it; ``capture = (position, list)``: the list receives
    each ``L`` layer's state before it.  ``ties`` (a list) receives, an
    ``S`` layer a block of query rows, ``_attention``'s ``gap``.
    ``straddle``, ``sparse_gate``, ``lin_gate``, ``sparse_rope`` and
    ``how`` (``select``, ``group_sum``, ``decay``, ``lin_rope``) are the
    changed lines."""
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    cuts = [given[0] for given in (inject, capture) if given is not None]
    blocks = _blocks(t, cuts)
    if residual is None:
        xs = [embed_scale * params["tok_emb.w"][tokens[a:b]].astype(
            jnp.float32) for a, b in blocks]
    else:
        xs = [jnp.asarray(residual[a:b], jnp.float32) for a, b in blocks]
    keep = np.ones((t,), np.float32)
    keep[[int(p) for p in lost if 0 <= int(p) < t]] = 0.0
    keep = jnp.asarray(keep)
    lin = {k: how.pop(k) for k in ("decay",) if k in how}
    if "lin_rope" in how:
        lin["rope"] = how.pop("lin_rope")
    n_lin = 0
    for i, kind in enumerate(mixers):
        w = lambda name: params[f"block{i}_{name}"]            # noqa: E731
        last = i == len(mixers) - 1
        needed = [not last or b > rows_from for _, b in blocks]
        if kind == "S":
            wa = {k: w(k) for k in _ATT_KEYS}
            kv = [_keys(x, wa, a, kv_heads=kv_heads, eps=eps, theta=theta,
                        rope=sparse_rope)
                  for x, (a, _) in zip(xs, blocks)]
            K, V = (jnp.concatenate(m) for m in zip(*kv))
            Kc = _compressed(K, sparse["kernel"], sparse["stride"],
                             straddle)
            for n, (a, b) in enumerate(blocks):
                if not needed[n]:
                    continue
                out = []
                for qa in range(a, b, QUERY_ROWS):
                    y, gap = _attention(
                        xs[n][qa - a:qa - a + QUERY_ROWS], wa, K, V, Kc, qa,
                        n_head=n_head, eps=eps, theta=theta,
                        scale=residual_scale, gate=sparse_gate,
                        rope=sparse_rope, **sparse, **how)
                    out.append(y)
                    if ties is not None:
                        ties.append((i, qa, gap))
                xs[n] = jnp.concatenate(out)
        else:
            wl = {k: w(k) for k in _LIN_KEYS}
            D = wl["lin_out.w"].shape[0] // lin_heads
            S = jnp.zeros((lin_heads, D, D), jnp.float32)
            for n, (a, b) in enumerate(blocks):
                if capture is not None and a == capture[0]:
                    capture[1].append(S)
                if inject is not None and a == inject[0]:
                    S = inject[1][n_lin]
                xs[n], S = _lightning(
                    xs[n], wl, S, jnp.asarray(slopes[n_lin], jnp.float32),
                    a, keep[a:b], heads=lin_heads, eps=eps, theta=theta,
                    scale=residual_scale, gate=lin_gate, **lin)
            n_lin += 1
        wf = {k: w(k) for k in _FFN_KEYS}
        for n in range(len(blocks)):
            if needed[n]:
                xs[n] = _ffn(xs[n], wf, eps=eps, scale=residual_scale)
    return [(a, b, x) for (a, b), x in zip(blocks, xs) if b > rows_from]


_LARGE = {}        # shape -> [the one large array, the rows written]


def _zeros(shape, written):
    """Float32 zeros of ``shape`` on the host, ``written`` (rows of the
    second axis, a slice) about to be filled.  A large one is anonymous
    memory that is not reserved, so that only the rows written cost pages
    (132,352 rows of 73,448 logits would be 38.9 GB), and it is ONE array
    a shape for the life of the process, its written rows zeroed again:
    whoever asks a second time is done with the first."""
    n = int(np.prod(shape)) * 4
    if n < (1 << 30):
        return np.zeros(shape, np.float32)
    if shape not in _LARGE:
        flags = mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS | getattr(
            mmap, "MAP_NORESERVE", 0)
        _LARGE[shape] = [np.frombuffer(mmap.mmap(-1, n, flags=flags),
                                       np.float32).reshape(shape), None]
    out, before = _LARGE[shape]
    if before is not None:
        out[:, before] = 0.0
    _LARGE[shape][1] = written
    return out


def forward(params, tokens, *layout, head_scale=1.0, eps=1e-6, rows_from=0,
            **switches):
    """Next-token logits ``[b, t, V]`` float32 (a NumPy array: the head's
    rows go to the host a block at a time) for tokens ``[b, t]``, one
    sequence after the other; rows before ``rows_from`` are left zeros.
    The arguments are ``trunk``'s, whose ``ties`` (a list) receives the
    LAST sequence's (the check compares one sequence a call)."""
    tokens = np.asarray(tokens)
    scale = params["norm_f.scale"].astype(jnp.float32)
    head = params["lm_head.w"]
    out = _zeros(tokens.shape + (head.shape[1],),
                 slice(rows_from, tokens.shape[1]))
    for n, row in enumerate(tokens):
        if switches.get("ties"):
            del switches["ties"][:]
        for a, b, x in trunk(params, row, *layout, eps=eps,
                             rows_from=rows_from, **switches):
            a0 = max(a, rows_from)
            out[n, a0:b] = np.asarray(_head(
                x[a0 - a:], scale, head, eps=eps, head_scale=head_scale))
    return out
