"""The plain reference of the looped RMSNorm / rotary / gated-SiLU stack
(Ouro, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741; ``modeling_ouro.py`` beside the published
``config.json``) in straightforward ``jax.numpy``: float32, ``highest``
matmul precision, no cache, no kernel, no batching tricks, and nothing
imported from the rest of ``paddle_tpu``.

With RMS(x; g) = x / sqrt(mean(x^2) + eps) * g::

    x = E[token]
    for p in 0 .. P-1:                  # the same L layers' weights every pass
      for l in 0 .. L-1:
        a = RMS(x; g1_l);  q, k, v = a Wq_l, a Wk_l, a Wv_l
        q, k = rope(q, pos), rope(k, pos)        # rotate-half, pairs (i, i + dh/2)
        o = softmax(q k^T / sqrt(dh), causal) v  # over THIS pass's k, v only
        x = x + RMS(o Wo_l; g2_l)                # a norm after the sub-layer too
        m = RMS(x; g3_l)
        x = x + RMS((silu(m Wg_l) * (m Wu_l)) Wd_l; g4_l)
      x = RMS(x; g_f)                            # closes EVERY pass, feeds the next
      lambda_p = sigmoid(x w_exit + b_exit)
    logits = x W_head                            # of the last pass

Departures from the published code: none is intended.  What the
published ``config.json`` has no key for and this file takes from
``modeling_ouro.py``: the two norms after the sub-layers
(``input_layernorm_2``, ``post_attention_layernorm_2``), the model's
final norm applied at the end of every pass, and the exit gate's bias.
The published code computes in the checkpoint's bfloat16; this
reference computes the same equations in float32, which is what a
reference is for.

It takes the weights under the serving engine's parameter names
(``serving/arch.py``, ``LoopedRmsRope``: the only thing it shares with
the program), whatever their dtype, and computes on their float32
values.  Layers run one jitted layer function at a time under Python
loops, so that the float32 copy of one layer (not of the model, and not
of ``passes`` models) is what the device holds beside the system under
test, and the compile is one layer's.

The keyword switches of ``logits`` (``norm_between_passes``,
``interleaved``; and ``passes`` itself) exist so that tests can show
that each of those choices is visible at the comparison's tolerance;
their defaults are the model.
"""

import functools

import jax
import jax.numpy as jnp

_LAYER_KEYS = ("norm1.scale", "att_q.w", "att_k.w", "att_v.w", "att_out.w",
               "norm2.scale", "norm3.scale", "ffn_gate.w", "ffn_up.w",
               "ffn_down.w", "norm4.scale")


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * g


def _rope(x, theta, interleaved):
    """x [b, t, h, dh] at positions 0 .. t-1."""
    t, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # [t, dh/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    if interleaved:
        # the OTHER pairing, (2i, 2i + 1): not the model's
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         axis=-1).reshape(x.shape)
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v):
    t, dh = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(dh))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "theta",
                                             "interleaved"))
def _layer(x, w, n_head, eps, theta, interleaved):
    """x [b, t, d] float32 -> x'; ``w`` the layer's 11 arrays.  The K
    and V attended are the ones this call computes: a plane of its own
    for every (pass, layer)."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, t, d = x.shape
    dh = d // n_head
    with jax.default_matmul_precision("highest"):
        a = _rms(x, w["norm1.scale"], eps)
        q = _rope((a @ w["att_q.w"]).reshape(b, t, n_head, dh), theta,
                  interleaved)
        k = _rope((a @ w["att_k.w"]).reshape(b, t, n_head, dh), theta,
                  interleaved)
        v = (a @ w["att_v.w"]).reshape(b, t, n_head, dh)
        o = _attention(q, k, v).reshape(b, t, d)
        x = x + _rms(o @ w["att_out.w"], w["norm2.scale"], eps)
        m = _rms(x, w["norm3.scale"], eps)
        ff = jax.nn.silu(m @ w["ffn_gate.w"]) * (m @ w["ffn_up.w"])
        return x + _rms(ff @ w["ffn_down.w"], w["norm4.scale"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _close_pass(x, g, w_exit, b_exit, eps):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, g.astype(jnp.float32), eps)
        gate = jax.nn.sigmoid(x @ w_exit.astype(jnp.float32)
                              + b_exit.astype(jnp.float32))
    return x, gate[..., 0]


@jax.jit
def _head(x, head):
    with jax.default_matmul_precision("highest"):
        return x @ head.astype(jnp.float32)


def exit_distribution(gates):
    """``gates [P, ...]`` (lambda_p per token) -> ``p [P, ...]``: the
    share of a token that leaves at pass p, lambda_p * prod_{j<p} (1 -
    lambda_j), the last pass taking the remainder."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    p = gates * before
    return p.at[-1].set(before[-1])


def exit_pass(gates, threshold):
    """The pass at which each token leaves: the first whose cumulative
    exit share reaches ``threshold``, the last pass at the latest."""
    cum = jnp.cumsum(exit_distribution(gates), axis=0)
    reached = cum >= threshold
    last = gates.shape[0] - 1
    return jnp.where(reached.any(axis=0), jnp.argmax(reached, axis=0), last)


def forward(params, tokens, n_layer, n_head, passes, eps=1e-6,
            rope_theta=1e6, norm_between_passes=True, interleaved=False):
    """(logits [b, t, V] float32, gates [passes, b, t]) for tokens [b, t]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["tok_emb.w"][tokens].astype(jnp.float32)
    gates = []
    for p in range(passes):
        for i in range(n_layer):
            w = {k: params[f"block{i}_{k}"] for k in _LAYER_KEYS}
            x = _layer(x, w, n_head=n_head, eps=eps, theta=rope_theta,
                       interleaved=interleaved)
        closed, gate = _close_pass(x, params["norm_f.scale"],
                                   params["exit_gate.w"],
                                   params["exit_gate.b"], eps=eps)
        if norm_between_passes or p == passes - 1:
            x = closed
        gates.append(gate)
    return _head(x, params["lm_head.w"]), jnp.stack(gates)


def logits(params, tokens, n_layer, n_head, passes, eps=1e-6,
           rope_theta=1e6, early_exit_threshold=1.0, **switches):
    """Next-token logits [b, t, V] float32.  Asserts what the engine
    relies on: at ``early_exit_threshold`` 1 every token leaves at the
    last pass (the exit distribution sums to 1 only there)."""
    out, gates = forward(params, tokens, n_layer, n_head, passes, eps,
                         rope_theta, **switches)
    if early_exit_threshold >= 1.0:
        leaves = exit_pass(gates, early_exit_threshold)
        assert bool((leaves == passes - 1).all()), (
            "at threshold 1 a token left before the last pass")
    return out
