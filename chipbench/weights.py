"""Weights from ``--seed``, made on the device in one jitted call."""

from . import flops


def make_params(cfg, positions, seed):
    """The program's parameter dict, bf16, normal(0, 0.02) matrices, unit
    LayerNorm scales, zero biases; rows and columns of the vocabulary's
    padding are zero, as in a deployment that never trained them, so no
    padded id is ever the argmax and none is ever fed back."""
    import jax
    import jax.numpy as jnp

    d, f, n_layer = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    rows, vocab = flops.vocab_rows(cfg), cfg["vocab_size"]
    dtype = jnp.dtype(cfg["compute_dtype"])

    @jax.jit
    def init(key):
        keys = iter(jax.random.split(key, 6 * n_layer + 3))

        def normal(*shape):
            return 0.02 * jax.random.normal(next(keys), shape, dtype)

        live = (jnp.arange(rows) < vocab).astype(dtype)
        p = {"tok_emb.w": normal(rows, d) * live[:, None],
             "pos_emb.w.w": normal(positions, d),
             "lm_head.w": normal(d, rows) * live[None, :],
             "ln_f.scale": jnp.ones((d,), dtype),
             "ln_f.bias": jnp.zeros((d,), dtype)}
        for i in range(n_layer):
            b = f"block{i}_"
            for name, shape in (("att_q", (d, d)), ("att_k", (d, d)),
                                ("att_v", (d, d)), ("att_out", (d, d)),
                                ("ffn1", (d, f)), ("ffn2", (f, d))):
                p[b + name + ".w"] = normal(*shape)
                p[b + name + ".b"] = jnp.zeros((shape[1],), dtype)
            for ln in ("ln1", "ln2"):
                p[b + ln + ".scale"] = jnp.ones((d,), dtype)
                p[b + ln + ".bias"] = jnp.zeros((d,), dtype)
        return p

    # the key is an argument, so one executable serves every seed
    return init(jax.random.PRNGKey(abs(int(seed)) % (2 ** 31 - 1)))
