"""The SambaY family (Phi-4-mini-flash-reasoning; the decoder-hybrid-
decoder of arXiv:2507.06607 with differential attention,
arXiv:2410.05258): ``num_hidden_layers`` pre-LayerNorm layers, each a
mixer and a gated SiLU MLP; the mixer by layer index is Mamba-1 (even
layers up to the middle), window differential attention (odd layers
before it), ONE full differential attention layer, then gated memory
units (even) and cross differential attention that reads the full
layer's K and V (odd).  Configuration keys are those of the published
``config.json``; what it has no key for is under ``assumed`` in the
configuration file, the sizes among them under ``assumed_sizes``.

The program serves it through ``ServingEngine(params, arch=SambaY(...),
prefix_reuse=False)`` (``paddle_tpu/serving/arch.py``); the plain
reference is ``sambay_reference.py`` beside this file.  The family
serves only: there is no ``training_program`` (``transformer.build`` has
none of these layers), so a training cell is refused by the loader.
``families/__init__.py`` says what each function is for.
"""

# a checkout whose program has no such architecture cannot run this
# family: it fails here, when the family is loaded, before any weight is made
from paddle_tpu.serving.arch import SambaY

from . import sambay_reference


def _dims(cfg):
    d = cfg["hidden_size"]
    extra = cfg["assumed_sizes"]
    return {"d": d, "f": cfg["intermediate_size"],
            "n": extra["mamba_expand"] * d, "s": extra["mamba_d_state"],
            "taps": extra["mamba_d_conv"], "r": extra["mamba_dt_rank"],
            "dh": d // cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"]
            * (d // cfg["num_attention_heads"]),
            "rows": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


def _matrices(kind, z):
    """{name: (rows, columns)} of the matmul matrices of one layer."""
    d, f, n, r, s, kv = z["d"], z["f"], z["n"], z["r"], z["s"], z["kv"]
    mats = {"ffn_gu": (d, 2 * f), "ffn_down": (f, d)}
    if kind == "mamba":
        mats.update(ssm_in=(d, 2 * n), ssm_x=(n, r + 2 * s), ssm_dt=(r, n),
                    ssm_out=(n, d))
    elif kind == "gmu":
        mats.update(gmu_in=(d, n), gmu_out=(n, d))
    elif kind == "cross":
        mats.update(att_q=(d, d), att_out=(d, d))
    else:
        mats.update(att_qkv=(d, d + 2 * kv), att_out=(d, d))
    return mats


def make_params(cfg, positions, seed):
    """The engine's parameter dict (``SambaY``'s names), in the
    configuration's dtype, from ``--seed`` in one jitted call: normal(0,
    0.02) matrices, table and attention biases; unit LayerNorm and
    sub-layer-norm scales, zero LayerNorm biases; the four lambda
    vectors of a layer normal(0, 0.1); and Mamba's own for what is not a
    matrix (``A_log = log(1 .. d_state)`` in every channel, ``D = 1``,
    ``b_dt`` the inverse softplus of a log-uniform draw in [1e-3, 1e-1],
    conv taps and bias uniform(+-1/sqrt(taps))): with ``A_log`` drawn
    normal a random stack's state neither decays nor discriminates.  No
    positional table, so ``positions`` sizes nothing."""
    import jax
    import jax.numpy as jnp

    del positions
    z = _dims(cfg)
    dtype = jnp.dtype(cfg["compute_dtype"])
    kinds = sambay_reference.layer_kinds(z["layers"])

    @jax.jit
    def init(key):
        keys = iter(jax.random.split(key, 16 * z["layers"] + 1))

        def normal(*shape, scale=0.02):
            return (scale * jax.random.normal(next(keys), shape)).astype(
                dtype)

        d, n, dh = z["d"], z["n"], z["dh"]
        one, zero = jnp.ones((d,), dtype), jnp.zeros((d,), dtype)
        p = {"tok_emb.w": normal(z["rows"], d), "ln_f.scale": one,
             "ln_f.bias": zero}
        for i, kind in enumerate(kinds):
            b = f"block{i}_"
            for name, shape in _matrices(kind, z).items():
                p[b + name + ".w"] = normal(*shape)
            p.update({b + "ln1.scale": one, b + "ln1.bias": zero,
                      b + "ln2.scale": one, b + "ln2.bias": zero})
            if kind == "mamba":
                bound = z["taps"] ** -0.5
                dt = jnp.exp(jax.random.uniform(
                    next(keys), (n,), minval=jnp.log(1e-3),
                    maxval=jnp.log(1e-1)))
                p.update({
                    b + "ssm_conv.w": jax.random.uniform(
                        next(keys), (n, z["taps"]), minval=-bound,
                        maxval=bound).astype(dtype),
                    b + "ssm_conv.b": jax.random.uniform(
                        next(keys), (n,), minval=-bound,
                        maxval=bound).astype(dtype),
                    b + "ssm_dt.b": (dt + jnp.log(-jnp.expm1(-dt))).astype(
                        dtype),
                    b + "ssm_A_log.w": jnp.broadcast_to(jnp.log(jnp.arange(
                        1.0, z["s"] + 1)), (n, z["s"])).astype(dtype),
                    b + "ssm_D.w": jnp.ones((n,), dtype)})
            elif kind != "gmu":
                first = "att_q" if kind == "cross" else "att_qkv"
                p[b + first + ".b"] = normal(
                    p[b + first + ".w"].shape[1])
                p[b + "att_out.b"] = normal(d)
                for v in ("q1", "k1", "q2", "k2"):
                    p[b + f"att_lambda_{v}.w"] = normal(dh, scale=0.1)
                p[b + "att_subln.scale"] = jnp.ones((2 * dh,), dtype)
        return p

    # the key is an argument, so one executable serves every seed
    return init(jax.random.PRNGKey(abs(int(seed)) % (2 ** 31 - 1)))


def _arch(cfg):
    z = _dims(cfg)
    return SambaY(z["layers"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"], z["d"],
                  window=cfg["sliding_window"], d_inner=z["n"],
                  d_state=z["s"], conv_taps=z["taps"], dt_rank=z["r"],
                  eps=cfg["layer_norm_eps"])


def serving_engine(params, cfg, registry, geometry):
    import paddle_tpu as pt

    return pt.serving.ServingEngine(
        params, arch=_arch(cfg), registry=registry,
        compute_dtype=cfg["compute_dtype"], **geometry)


def logits(params, tokens, cfg, **switches):
    z = _dims(cfg)
    return sambay_reference.forward(
        params, tokens, z["layers"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["sliding_window"], d_state=z["s"],
        dt_rank=z["r"], eps=cfg["layer_norm_eps"], **switches)


def sizes(cfg):
    z = _dims(cfg)
    kinds = sambay_reference.layer_kinds(z["layers"])
    applied = sum(rows * cols for kind in kinds
                  for rows, cols in _matrices(kind, z).values())
    own = sum(kind in ("window", "full") for kind in kinds)
    return {
        "d_model": z["d"], "heads": cfg["num_attention_heads"],
        "head_dim": z["dh"], "vocab_rows": z["rows"],
        # every matrix of every layer once a token, and the table once as
        # the head (its rows are gathered at the bottom, not multiplied);
        # norms, biases, conv taps, A and D are O(width)
        "matmul_params": applied + z["d"] * z["rows"],
        # a plane for each attention layer with K and V of its own; the
        # cross layers attend the full layer's plane again
        "kv_planes": own, "attention_passes": own + kinds.count("cross"),
    }


def hybrid_sizes(cfg):
    """What the hybrid readers ask beside ``sizes`` (a sixth answer, as
    ``stack_passes`` is the looped family's): the K/V heads a plane
    holds, the window and how many planes have it, how often a token
    reads the one full plane, and the recurrent state a slot holds
    (float32 ``[inner, state]`` and ``taps - 1`` rows of the conv's input
    in the compute dtype, a Mamba layer)."""
    z = _dims(cfg)
    kinds = sambay_reference.layer_kinds(z["layers"])
    itemsize = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    return {
        "kv_heads": cfg["num_key_value_heads"],
        "window": cfg["sliding_window"],
        "window_planes": kinds.count("window"),
        "full_plane_reads": 1 + kinds.count("cross"),
        "state_layers": kinds.count("mamba"),
        "state_shape": (z["n"], z["s"]),
        "state_bytes_per_slot": kinds.count("mamba") * (
            z["n"] * z["s"] * 4 + (z["taps"] - 1) * z["n"] * itemsize),
    }
