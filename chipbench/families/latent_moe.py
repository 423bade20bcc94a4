"""The latent-attention mixture-of-experts family (DeepSeek-V2,
``model_type`` ``deepseek_v2``): pre-normed layers whose attention
caches ONE latent row a position (``kv_lora_rank`` values and the
``qk_rope_head_dim`` lanes of the one rotary key all heads share) and
reads it through queries absorbed into the latent's width, YaRN rotary
positions, and a dense or ROUTED gated SiLU FFN: ``n_shared_experts``
shared experts (one MLP) beside ``n_routed_experts`` routed ones,
softmax scores, ``num_experts_per_tok`` of them a token, the selected
weights not normalised.  Configuration keys are those of the published
``config.json``; ``n_routed_experts`` is what THIS chip holds of the
``router_width`` experts a routed layer has (experts ``experts_first ..
experts_first + n_routed_experts - 1``): the router keeps its published
width and a token is routed over all of them.  What the config has no
key for is under ``assumed`` in the configuration file.

The program serves it through ``ServingEngine(params,
arch=LatentMoE(...))`` (``paddle_tpu/serving/arch.py``); the plain
reference is ``latent_moe_reference.py`` beside this file (the per-head
form, no cache).  The family serves only.  ``families/__init__.py`` says
what each function is for; ``moe_sizes`` and ``latent_sizes`` are what
the readers of the routed layer and of the latent plane ask beside
``sizes`` (``chipbench/latent_bytes.py``).
"""

import sys

# a checkout whose program has no such architecture cannot run this
# family: it fails here, when the family is loaded, before any weight is made
from paddle_tpu.serving.arch import LatentMoE

from . import latent_moe_reference


def _dims(cfg):
    h = cfg["num_attention_heads"]
    return {"d": cfg["hidden_size"], "h": h, "rank": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "f": cfg["intermediate_size"],
            "e": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "held": cfg["n_routed_experts"], "width": cfg["router_width"],
            "rows": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"]}


def _matrices(routed, z):
    """{name: shape} of the matmul matrices of one layer."""
    d, h, e = z["d"], z["h"], z["e"]
    mats = {"att_q": (d, h * (z["nope"] + z["rope"])),
            "att_kva": (d, z["rank"] + z["rope"]),
            "att_kvb": (z["rank"], h * (z["nope"] + z["v"])),
            "att_out": (h * z["v"], d)}
    if routed:
        mats.update(router=(d, z["width"]), shared_gate=(d, z["shared"]),
                    shared_up=(d, z["shared"]), shared_down=(z["shared"], d),
                    experts_gate=(z["held"], d, e),
                    experts_up=(z["held"], d, e),
                    experts_down=(z["held"], e, d))
    else:
        mats.update(ffn_gate=(d, z["f"]), ffn_up=(d, z["f"]),
                    ffn_down=(z["f"], d))
    return mats


def make_params(cfg, positions, seed):
    """The engine's parameter dict (``LatentMoE``'s names), in the
    configuration's dtype, from ``--seed``: normal(0, ``initializer_range``)
    matrices (0.02 where the configuration has no such key, as the
    published one: the router's and the experts' among them), table and
    head, in one jitted call; unit gains on every norm.  A pre-normed stack needs no depth
    scale: every sub-layer reads a normed row, so the residual grows as
    the root of the sub-layers that added to it.  Rotary positions need
    no table, so ``positions`` sizes nothing."""
    import jax
    import jax.numpy as jnp

    del positions
    z = _dims(cfg)
    dtype = jnp.dtype(cfg["compute_dtype"])
    std = cfg.get("initializer_range", 0.02)

    @jax.jit
    def init(key):
        keys = iter(jax.random.split(key, 12 * z["layers"] + 2))

        def normal(*shape):
            return std * jax.random.normal(next(keys), shape, dtype)

        d = z["d"]
        p = {"tok_emb.w": normal(z["rows"], d),
             "lm_head.w": normal(d, z["rows"]),
             "norm_f.scale": jnp.ones((d,), dtype)}
        for i in range(z["layers"]):
            b = f"block{i}_"
            for name, shape in _matrices(i >= z["dense"], z).items():
                p[b + name + ".w"] = normal(*shape)
            p[b + "norm1.scale"] = jnp.ones((d,), dtype)
            p[b + "norm2.scale"] = jnp.ones((d,), dtype)
            p[b + "att_kvnorm.scale"] = jnp.ones((z["rank"],), dtype)
        return p

    # the key is an argument, so one executable serves every seed
    return init(jax.random.PRNGKey(abs(int(seed)) % (2 ** 31 - 1)))


def _rope(cfg):
    s = cfg["rope_scaling"]
    return (float(cfg["rope_theta"]), float(s["factor"]),
            s["original_max_position_embeddings"], float(s["beta_fast"]),
            float(s["beta_slow"]), s["mscale"], s["mscale_all_dim"])


def _layout(cfg):
    """The reference's positional arguments after the tokens."""
    z = _dims(cfg)
    return (z["layers"], z["h"], z["rank"], z["nope"], z["rope"], z["v"],
            z["dense"], cfg["num_experts_per_tok"],
            (cfg["experts_first"], z["held"]),
            float(cfg["routed_scaling_factor"]), _rope(cfg))


def _arch(cfg):
    z = _dims(cfg)
    theta, factor, original, fast, slow, m, m_all = _rope(cfg)
    return LatentMoE(
        z["layers"], z["h"], z["d"], rank=z["rank"], nope_dim=z["nope"],
        rope_dim=z["rope"], v_dim=z["v"], dense_layers=z["dense"],
        router_width=z["width"], top_k=cfg["num_experts_per_tok"],
        experts=(cfg["experts_first"], z["held"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        eps=cfg["rms_norm_eps"], rope_theta=theta, rope_factor=factor,
        rope_original=original, beta_fast=fast, beta_slow=slow, mscale=m,
        mscale_all_dim=m_all)


def serving_engine(params, cfg, registry, geometry):
    import paddle_tpu as pt

    return pt.serving.ServingEngine(
        params, arch=_arch(cfg), registry=registry,
        compute_dtype=cfg["compute_dtype"], **geometry)


def logits(params, tokens, cfg, ties=None, **switches):
    """The reference's logits ``[b, t, V]``, with the rows it cannot
    decide set to zero, as ``gated_moe.logits`` does it
    (``chipbench/MOE.md``): a row where, in any routed layer, the margin
    between the last selected expert and the best one left out, one of
    them HELD (``latent_moe_reference.routed_ffn``'s ``ties``), is under
    the configuration's ``check_undecided_margin`` comes back as zeros,
    which every token satisfies (gap 0).  How many were left out goes to
    standard error and to ``undecided`` below.  At a margin of 0 (the
    published configuration's: its sound runs' worst tokens do not lie
    at ties, the configuration file says) nothing is left out and no
    margin is computed.  ``ties`` (a list) receives the margins, a
    routed layer each."""
    if ties is None and cfg["check_undecided_margin"] > 0:
        ties = []
    out = latent_moe_reference.forward(
        params, tokens, *_layout(cfg), eps=cfg["rms_norm_eps"], ties=ties,
        **switches)
    if not ties:
        return out
    import jax.numpy as jnp

    left_out = jnp.min(jnp.stack(ties), axis=0) < cfg["check_undecided_margin"]
    undecided.append((int(left_out.sum()), left_out.size))
    print(f"chipbench: latent_moe: {undecided[-1][0]} of {undecided[-1][1]} "
          f"rows left out of the check as undecided (margin under "
          f"{cfg['check_undecided_margin']})", file=sys.stderr)
    return jnp.where(left_out[..., None], 0.0, out)


# (rows left out, rows) of each call of ``logits``, for whoever asks
undecided = []


def _outside_experts(z):
    """Matmul parameters a token is multiplied by outside the routed
    experts: every layer's attention, the dense FFN, each routed layer's
    router and shared MLP, and the head (the table's rows are
    gathered)."""
    total = z["d"] * z["rows"]
    for i in range(z["layers"]):
        total += sum(
            rows * cols for name, (*_, rows, cols) in _matrices(
                i >= z["dense"], z).items()
            if not name.startswith("experts_"))
    return total


def sizes(cfg):
    z = _dims(cfg)
    routed = z["layers"] - z["dense"]
    # a token selects num_experts_per_tok of router_width experts; the
    # held ones get held / width of them: 1.5 experts a routed layer here
    applied = cfg["num_experts_per_tok"] * z["held"] / z["width"]
    return {
        "d_model": z["d"], "heads": z["h"],
        "head_dim": z["nope"] + z["rope"], "vocab_rows": z["rows"],
        # what a token is multiplied by ON THIS CHIP, in expectation
        "matmul_params": int(_outside_experts(z)
                             + routed * applied * 3 * z["d"] * z["e"]),
        "kv_planes": z["layers"], "attention_passes": z["layers"],
    }


def moe_sizes(cfg):
    """What the readers of the routed layer ask: ``gated_moe.moe_sizes``'
    keys."""
    z = _dims(cfg)
    return {
        "moe_layers": z["layers"] - z["dense"], "experts_held": z["held"],
        "router_width": z["width"], "top_k": cfg["num_experts_per_tok"],
        "expert_params": 3 * z["d"] * z["e"],
        "expert_ops_per_row": 6 * z["d"] * z["e"],
        "outside_params": _outside_experts(z),
    }


def latent_sizes(cfg):
    """What the readers of the latent plane ask
    (``chipbench/latent_bytes.py``): the planes a token holds, the
    values one of them holds of a position (the latent and the rotary
    key, whatever the pool pads them to), the query heads that read each
    cached row, and the lanes of it that are the value."""
    z = _dims(cfg)
    return {"planes": z["layers"],
            "values_per_position": z["rank"] + z["rope"],
            "heads": z["h"], "value_lanes": z["rank"]}
