"""The plain reference: the GPT-2 block of the Cerebras-GPT configurations
in straightforward ``jax.numpy``, float32, ``highest`` matmul precision.

No kernels, no cache, no batching tricks, nothing imported from the
program.  It follows the published description (pre-LayerNorm, learned
absolute positions, full multi-head causal attention, GELU FFN, biases)
with the two departures the configuration files list: the tables hold
``vocab_rows`` rows and the head is untied.  GELU is the exact erf form.

It takes the weights under the program's parameter names (the only thing
it shares with the program), whatever their dtype, and computes on their
float32 values.  Layers run one jitted block at a time, so that the
float32 copy of one layer (not of the model) is what the device holds
beside the system under test.
"""

import functools

import jax
import jax.numpy as jnp

_BLOCK_KEYS = ("ln1.scale", "ln1.bias", "att_q.w", "att_q.b", "att_k.w",
               "att_k.b", "att_v.w", "att_v.b", "att_out.w", "att_out.b",
               "ln2.scale", "ln2.bias", "ffn1.w", "ffn1.b", "ffn2.w",
               "ffn2.b")


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def _block(x, w, n_head, eps):
    """x [b, t, d] float32 -> [b, t, d]; ``w`` the block's 16 arrays."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, t, d = x.shape
    dh = d // n_head
    with jax.default_matmul_precision("highest"):
        h = _ln(x, w["ln1.scale"], w["ln1.bias"], eps)
        q = (h @ w["att_q.w"] + w["att_q.b"]).reshape(b, t, n_head, dh)
        k = (h @ w["att_k.w"] + w["att_k.b"]).reshape(b, t, n_head, dh)
        v = (h @ w["att_v.w"] + w["att_v.b"]).reshape(b, t, n_head, dh)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(dh))
        causal = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, t, d)
        x = x + ctx @ w["att_out.w"] + w["att_out.b"]
        h = _ln(x, w["ln2.scale"], w["ln2.bias"], eps)
        ff = jax.nn.gelu(h @ w["ffn1.w"] + w["ffn1.b"], approximate=False)
        return x + ff @ w["ffn2.w"] + w["ffn2.b"]


@jax.jit
def _embed(tokens, tok_emb, pos_emb):
    t = tokens.shape[-1]
    return (tok_emb[tokens].astype(jnp.float32)
            + pos_emb[:t].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x, scale, bias, head, eps):
    with jax.default_matmul_precision("highest"):
        h = _ln(x, scale.astype(jnp.float32), bias.astype(jnp.float32), eps)
        return h @ head.astype(jnp.float32)


def logits(params, tokens, n_layer, n_head, eps=1e-5):
    """Next-token logits [b, t, vocab_rows] float32 for tokens [b, t]."""
    x = _embed(jnp.asarray(tokens, jnp.int32), params["tok_emb.w"],
               params["pos_emb.w.w"])
    for i in range(n_layer):
        x = _block(x, {k: params[f"block{i}_{k}"] for k in _BLOCK_KEYS},
                   n_head=n_head, eps=eps)
    return _logits(x, params["ln_f.scale"], params["ln_f.bias"],
                   params["lm_head.w"], eps=eps)


@jax.jit
def _nll_sum(lg, labels):
    """Sum of next-token losses over labels >= 0, and their count."""
    valid = labels >= 0
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, lse - picked, 0.0)), jnp.sum(valid)


def greedy_loss(params, tokens, n_layer, n_head, eps=1e-5):
    """(labels, loss) for a batch [b, t]: the reference's own most likely
    next token at every position, and its mean cross-entropy on them.

    On labels that do not depend on the weights an error in the trunk
    moves each token's loss up or down alike and cancels in the mean
    (at initial weights the mean is ln V + d 0.02^2 / 2 for any trunk).
    On the reference's own maxima every error lowers the label's logit,
    so the mean rises by about max-logit * err^2 / 2, err being the
    relative error of the final hidden state (PERF.md, PR 24)."""
    labels, total = [], 0.0
    for row in tokens:
        lg = logits(params, row[None], n_layer, n_head, eps)
        top = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        total += float(_nll_sum(lg, top)[0])
        labels.append(top[0])
    labels = jnp.stack(labels)
    return labels, total / labels.size
