"""What the serving engine reads of a model: one ``Architecture`` object.

The engine (``serving/engine.py``) and its compiled entry points
(``serving/batched_decode.py``) know blocks, tables, slots and windows;
what a model is made of they take from here:

* the attention geometry (``n_head``, ``head_dim``) and the number of
  K/V planes a cached token holds (``kv_planes = n_layer * passes``: a
  stack that runs ``passes`` times over the same weights caches its own
  K and V in every pass), which size the pool;
* the forward, written ONCE per architecture as a function of
  (parameters, rows, positions, a cache interface): ``embed`` makes the
  rows, ``stack`` runs the layers and ``head`` turns rows into float32
  logits.  ``stack`` is shape-agnostic in the leading axes of ``x`` (the
  decode step feeds ``[S, d]``, a window ``[S, W, d]``), so the decode
  chunk, prefill and the speculative verify window run the same lines.

The cache interface is one callable the entry points build::

    ctx, planes = attend(planes, layer, pass_idx, q, k, v)

It writes ``k`` and ``v`` (``[..., n_head, head_dim]``) into the plane of
``(pass_idx, layer)`` through the block table, attends everything that
plane holds up to each row's position, and returns the context in
``q``'s shape together with the updated planes.  ``pass_idx`` is the
Python integer 0 in a stack that runs once, and may be a traced scalar
inside a loop over passes.

Two architectures are here: ``Gpt2`` (the block of
``models/transformer.py``: pre-LayerNorm, learned absolute positions,
GELU FFN, biases; arithmetic and dtypes exactly those the engine always
served) and ``LoopedRmsRope`` (RMSNorm before AND after each sub-layer,
rotary positions, a gated SiLU FFN, no biases, the whole stack run
``passes`` times over the same weights with the final norm closing every
pass and an exit gate whose weights are held: the Ouro / LoopLM layout,
arXiv:2510.25741).  ``models/ouro_reference.py`` is the second one's
plain reference.
"""

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Architecture", "Gpt2", "LoopedRmsRope", "STACK_SCOPE"]

# the jax.named_scope every architecture's stack runs under (op_name
# metadata of the lowered program: stack vs. embedding, head and argmax)
STACK_SCOPE = "serving.stack_pass"


class Architecture:
    """The base: geometry, pool arithmetic and the checks every
    architecture shares.  A subclass gives ``embed``, ``stack``,
    ``head`` and ``check_params``."""

    name = "architecture"

    def __init__(self, n_layer, n_head, d_model, passes=1):
        if d_model % n_head:
            raise ValueError(f"d_model {d_model} % n_head {n_head} != 0")
        if n_layer < 1 or passes < 1:
            raise ValueError(f"n_layer {n_layer} and passes {passes} "
                             f"must be >= 1")
        self.n_layer, self.n_head = int(n_layer), int(n_head)
        self.d_model, self.passes = int(d_model), int(passes)

    @property
    def head_dim(self):
        return self.d_model // self.n_head

    @property
    def kv_planes(self):
        """K/V planes one cached token holds: a plane of its own for
        every (pass, layer)."""
        return self.n_layer * self.passes

    def kv_block_bytes(self, block_tokens, itemsize):
        """Bytes one block of one plane holds: K and V of
        ``block_tokens`` positions."""
        return 2 * block_tokens * self.d_model * itemsize

    def kv_bytes_per_token(self, itemsize):
        """K and V of one cached token across all its planes."""
        return 2 * self.kv_planes * self.d_model * itemsize

    def heads(self, x):
        """``[..., d] -> [..., n_head, head_dim]``."""
        return x.reshape(*x.shape[:-1], self.n_head, self.head_dim)

    # -- what a subclass answers ------------------------------------------
    def check_params(self, params, max_len):
        """Raise ``ValueError`` where ``params`` cannot serve ``max_len``
        positions under this architecture."""

    def embed(self, p, toks, pos):
        """Rows ``[..., d]`` of tokens ``toks [...]`` at (clipped)
        positions ``pos [...]``."""
        raise NotImplementedError

    def stack(self, p, x, pos, planes, attend):
        """All layers, all passes: ``(x', planes')``; ``x'`` is what
        ``head`` consumes."""
        raise NotImplementedError

    def head(self, p, x):
        """Float32 logits of rows ``x [..., d]``."""
        raise NotImplementedError


def _ln(x, scale, bias, eps):
    # statistics in f32 even under bf16 compute (mean/var cancellation) —
    # mirrors transformer.generate's ln exactly
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    xn = ((x32 - mu) / jnp.sqrt(var + eps)).astype(x.dtype)
    return xn * scale + bias


class Gpt2(Architecture):
    """The ``transformer.build`` block under its parameter names
    (``block{i}_ln1.scale`` ... ``lm_head.w``)."""

    name = "gpt2"

    def __init__(self, n_layer, n_head, d_model, eps=1e-5):
        super().__init__(n_layer, n_head, d_model)
        self.eps = eps

    def check_params(self, params, max_len):
        table_len = np.shape(params["pos_emb.w.w"])[0]
        if max_len > table_len:
            raise ValueError(
                f"max_len {max_len} exceeds the trained position-"
                f"embedding table ({table_len} positions)")

    def embed(self, p, toks, pos):
        return p["tok_emb.w"][toks] + p["pos_emb.w.w"][pos]

    def stack(self, p, x, pos, planes, attend):
        eps = self.eps
        for i in range(self.n_layer):
            w = lambda nm: p[f"block{i}_{nm}"]
            h = _ln(x, w("ln1.scale"), w("ln1.bias"), eps)
            q = h @ w("att_q.w") + w("att_q.b")
            k = h @ w("att_k.w") + w("att_k.b")
            v = h @ w("att_v.w") + w("att_v.b")
            ctx, planes = attend(planes, i, 0, self.heads(q),
                                 self.heads(k), self.heads(v))
            x = x + ctx.reshape(x.shape) @ w("att_out.w") + w("att_out.b")
            h2 = _ln(x, w("ln2.scale"), w("ln2.bias"), eps)
            # exact erf gelu, matching transformer.generate and the gelu op
            ff = jax.nn.gelu(h2 @ w("ffn1.w") + w("ffn1.b"),
                             approximate=False)
            x = x + ff @ w("ffn2.w") + w("ffn2.b")
        return x, planes

    def head(self, p, x):
        x = _ln(x, p["ln_f.scale"], p["ln_f.bias"], self.eps)
        return jnp.matmul(x, p["lm_head.w"],
                          preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    # x / sqrt(mean(x^2) + eps) * scale, statistics in f32 (the published
    # RMSNorm upcasts, normalizes, casts back, then scales)
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * scale


def _rope(x, cos, sin):
    """Rotate-half rotary position: dimension ``i`` pairs with
    ``i + head_dim / 2``.  ``x [..., h, dh]``, ``cos``/``sin``
    ``[..., 1, dh]`` float32; computed in float32, returned in
    ``x.dtype``."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos + rot * sin).astype(x.dtype)


class LoopedRmsRope(Architecture):
    """A stack of sandwich-normed RMSNorm / rotary / gated-SiLU blocks
    run ``passes`` times over the same weights.

    Parameter names: ``tok_emb.w [V, d]``, per layer
    ``block{i}_norm1.scale`` (before attention), ``att_q.w``, ``att_k.w``,
    ``att_v.w``, ``att_out.w`` (``[d, d]``, no biases),
    ``norm2.scale`` (after attention, before the residual add),
    ``norm3.scale`` (before the FFN), ``ffn_gate.w``, ``ffn_up.w``
    (``[d, f]``), ``ffn_down.w`` (``[f, d]``), ``norm4.scale`` (after the
    FFN); then ``norm_f.scale`` (closes EVERY pass and feeds the next),
    ``exit_gate.w [d, 1]`` and ``exit_gate.b [1]`` (held, not applied:
    at ``early_exit_threshold`` 1 every token leaves at the last pass)
    and ``lm_head.w [d, V]`` (not tied).

    The passes are ONE loop in the compiled program (``lax.fori_loop``
    with the planes in the carry): the lowered executables hold one copy
    of the ``n_layer`` bodies whatever ``passes`` is.
    """

    name = "looped_rms_rope"

    def __init__(self, n_layer, n_head, d_model, passes, eps=1e-6,
                 rope_theta=10000.0, early_exit_threshold=1.0):
        super().__init__(n_layer, n_head, d_model, passes)
        if early_exit_threshold < 1.0:
            raise ValueError(
                f"early_exit_threshold {early_exit_threshold} < 1: tokens "
                f"would leave the stack at different passes, and the "
                f"engine has no decode step in which slots leave the "
                f"stack at different passes (every slot runs all "
                f"{passes} passes); serve at threshold 1")
        if self.head_dim % 2:
            raise ValueError(f"rotary positions need an even head_dim, "
                             f"got {self.head_dim}")
        self.eps, self.rope_theta = eps, float(rope_theta)

    def check_params(self, params, max_len):
        missing = [k for k in ("norm_f.scale", "exit_gate.w", "exit_gate.b",
                               "lm_head.w", "tok_emb.w",
                               f"block{self.n_layer - 1}_norm4.scale")
                   if k not in params]
        if missing:
            raise ValueError(f"{self.name}: parameters lack "
                             f"{', '.join(missing)}")

    def embed(self, p, toks, pos):
        return p["tok_emb.w"][toks]

    def _angles(self, pos):
        dh = self.head_dim
        inv = 1.0 / (self.rope_theta ** (
            jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
        ang = pos.astype(jnp.float32)[..., None] * inv          # [..., dh/2]
        ang = jnp.concatenate([ang, ang], axis=-1)[..., None, :]
        return jnp.cos(ang), jnp.sin(ang)

    def one_pass(self, p, i_pass, x, rope, planes, attend):
        """The ``n_layer`` blocks and the closing norm, once."""
        eps = self.eps
        cos, sin = rope
        for i in range(self.n_layer):
            w = lambda nm: p[f"block{i}_{nm}"]
            a = _rms(x, w("norm1.scale"), eps)
            q = _rope(self.heads(a @ w("att_q.w")), cos, sin)
            k = _rope(self.heads(a @ w("att_k.w")), cos, sin)
            v = self.heads(a @ w("att_v.w"))
            ctx, planes = attend(planes, i, i_pass, q, k, v)
            x = x + _rms(ctx.reshape(x.shape) @ w("att_out.w"),
                         w("norm2.scale"), eps)
            m = _rms(x, w("norm3.scale"), eps)
            ff = jax.nn.silu(m @ w("ffn_gate.w")) * (m @ w("ffn_up.w"))
            x = x + _rms(ff @ w("ffn_down.w"), w("norm4.scale"), eps)
        return _rms(x, p["norm_f.scale"], eps), planes

    def stack(self, p, x, pos, planes, attend):
        rope = self._angles(pos)

        def one(i_pass, carry):
            x, planes = carry
            return self.one_pass(p, i_pass, x, rope, planes, attend)

        return jax.lax.fori_loop(0, self.passes, one, (x, planes))

    def head(self, p, x):
        # the final norm already closed the last pass
        return jnp.matmul(x, p["lm_head.w"],
                          preferred_element_type=jnp.float32)
