"""The sink-window mixture-of-experts family (Xiaomi MiMo-V2-Flash /
MiMo-V2.5's language model, ``model_type`` ``mimo_v2``): pre-normed
layers of grouped-query attention in two geometries (full layers of
``num_key_value_heads`` K/V heads, window layers of
``swa_num_key_value_heads`` with a learned sink), keys of ``head_dim``
lanes over values of ``v_head_dim``, rotary on part of a head at two
thetas, a scaled value, and a dense or ROUTED gated SiLU FFN with no
shared expert: sigmoid scores, ``num_experts_per_tok`` of them a token.
Configuration keys are those of the published ``config.json``;
``n_routed_experts`` is what THIS chip holds of the ``router_width``
experts a routed layer has (experts ``experts_first .. experts_first +
n_routed_experts - 1``): the router keeps its published width and a
token is routed over all of them.  What the config has no key for is
under ``assumed`` in the configuration file.

The program serves it through ``ServingEngine(params,
arch=SinkWindowMoE(...))`` (``paddle_tpu/serving/arch.py``); the plain
reference is ``sink_window_moe_reference.py`` beside this file.  The
family serves only.  ``families/__init__.py`` says what each function is
for; ``mixed_sizes`` is what ``chipbench/mixed_kv_bytes.py`` asks.
"""

import sys

# a checkout whose program has no such architecture cannot run this
# family: it fails here, when the family is loaded, before any weight is made
from paddle_tpu.serving.arch import SinkWindowMoE

from . import sink_window_moe_reference as reference
# the bias under which top k of s + bias loads every expert alike
from .gated_moe import _balanced_bias


def _dims(cfg):
    types = tuple("window" if kind else "full"
                  for kind in cfg["hybrid_layer_pattern"])
    freq = cfg["moe_layer_freq"]
    return {"d": cfg["hidden_size"], "dh": cfg["head_dim"],
            "dv": cfg["v_head_dim"], "h": cfg["num_attention_heads"],
            "kv": {"full": cfg["num_key_value_heads"],
                   "window": cfg["swa_num_key_value_heads"]},
            "f": cfg["intermediate_size"], "e": cfg["moe_intermediate_size"],
            "held": cfg["n_routed_experts"], "width": cfg["router_width"],
            "rows": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
            "dense": freq.index(1) if 1 in freq else len(freq),
            "types": types}


def _matrices(i, z):
    """{name: shape} of the matmul matrices of layer ``i``."""
    d, e, hk = z["d"], z["e"], z["kv"][z["types"][i]]
    mats = {"att_qkv": (d, z["h"] * z["dh"] + hk * (z["dh"] + z["dv"])),
            "att_out": (z["h"] * z["dv"], d)}
    if i >= z["dense"]:
        mats.update(router=(d, z["width"]),
                    experts_gate=(z["held"], d, e),
                    experts_up=(z["held"], d, e),
                    experts_down=(z["held"], e, d))
    else:
        mats.update(ffn_gate=(d, z["f"]), ffn_up=(d, z["f"]),
                    ffn_down=(z["f"], d))
    return mats


def make_params_unsettled(cfg, seed):
    """``make_params`` before the routers' biases are settled (zeros);
    also the tokens they are settled on."""
    import jax
    import jax.numpy as jnp

    z = _dims(cfg)
    dtype = jnp.dtype(cfg["compute_dtype"])
    lo, hi = cfg["sink_logit_range"]

    @jax.jit
    def init(key):
        keys = iter(jax.random.split(key, 8 * z["layers"] + 3))

        def normal(*shape):
            return 0.02 * jax.random.normal(next(keys), shape, dtype)

        d = z["d"]
        p = {"tok_emb.w": normal(z["rows"], d),
             "lm_head.w": normal(d, z["rows"]),
             "norm_f.scale": jnp.ones((d,), dtype)}
        for i, kind in enumerate(z["types"]):
            b = f"block{i}_"
            for name, shape in _matrices(i, z).items():
                p[b + name + ".w"] = normal(*shape)
            if i >= z["dense"]:
                p[b + "router.bias"] = jnp.zeros((z["width"],), dtype)
            if kind == "window":
                p[b + "att_sink.b"] = jax.random.uniform(
                    next(keys), (z["h"],), jnp.float32, lo, hi).astype(dtype)
            p[b + "norm1.scale"] = jnp.ones((d,), dtype)
            p[b + "norm2.scale"] = jnp.ones((d,), dtype)
        return p, jax.random.randint(
            next(keys), tuple(cfg["expert_bias_tokens"]), 0, z["rows"])

    # the key is an argument, so one executable serves every seed
    return init(jax.random.PRNGKey(abs(int(seed)) % (2 ** 31 - 1)))


def make_params(cfg, positions, seed):
    """The engine's parameter dict (``SinkWindowMoE``'s names), in the
    configuration's dtype, from ``--seed``: normal(0, 0.02) matrices (the
    router's and the experts' among them), table and head, unit gains on
    every norm (a pre-normed stack needs no depth scale), in one jitted
    call; each window layer's sink logits uniform over the
    configuration's ``sink_logit_range`` (``assumed.init`` says why); and
    each routed layer's ``router.bias`` as training leaves it: the bias
    that spreads the selections evenly over the router's experts
    (``_balance``, over the configuration's ``expert_bias_tokens``:
    sequences x their length).  Rotary positions need no table, so
    ``positions`` sizes nothing."""
    del positions
    params, tokens = make_params_unsettled(cfg, seed)
    return _balance(params, tokens, cfg)


def _balance(params, tokens, cfg):
    """``params`` with every routed layer's ``router.bias`` settled, one
    layer after the other, on the reference's own forward over ``tokens
    [n, t]`` (uniform ids from ``--seed``): a trained model of this family
    holds there what balanced its experts' load (``topk_method``
    ``noaux_tc``), and a seeded router with a zero bias gives THIS chip's
    16 a share of the pairs that moves with the seed (the
    ``gated_moe`` family measured 47.5-52.6% against a settled
    49.1-51.4%: PERF.md, PR 34)."""
    import jax

    z = _dims(cfg)
    settle = jax.jit(_balanced_bias, static_argnums=(1,))

    def before_routing(i, x):
        route = {k: params[f"block{i}_{k}"] for k in reference._ROUTE_KEYS}
        *_, s = reference._route(
            x, route, top_k=cfg["num_experts_per_tok"], norm=True, bias=True,
            eps=cfg["layernorm_epsilon"])
        params[f"block{i}_router.bias"] = settle(
            s.reshape(-1, z["width"]), cfg["num_experts_per_tok"]).astype(
                params[f"block{i}_router.bias"].dtype)

    reference.trunk(params, tokens, *reference.layout(cfg),
                    **reference.settings(cfg), before_routing=before_routing)
    return params


def _arch(cfg):
    z = _dims(cfg)
    return SinkWindowMoE(
        z["types"], z["h"], z["kv"]["full"], z["kv"]["window"], z["dh"],
        z["dv"], z["d"], window=cfg["sliding_window"],
        rotary_lanes=int(z["dh"] * cfg["partial_rotary_factor"]),
        dense_layers=z["dense"], router_width=z["width"],
        top_k=cfg["num_experts_per_tok"],
        experts=(cfg["experts_first"], z["held"]),
        value_scale=cfg["attention_value_scale"],
        norm_topk=cfg["norm_topk_prob"], eps=cfg["layernorm_epsilon"],
        rope_theta=cfg["rope_theta"],
        window_rope_theta=cfg["swa_rope_theta"])


def serving_engine(params, cfg, registry, geometry):
    import paddle_tpu as pt

    return pt.serving.ServingEngine(
        params, arch=_arch(cfg), registry=registry,
        compute_dtype=cfg["compute_dtype"], **geometry)


def logits(params, tokens, cfg, ties=None, **switches):
    """The reference's logits ``[b, t, V]``, with the rows it cannot
    decide set to zero: a row whose selection, in any routed layer, is
    within the configuration's ``check_undecided_margin`` of one that
    differs in a HELD expert (``sink_window_moe_reference._margin``)
    comes back as zeros, which every token satisfies (gap 0), as the
    ``gated_moe`` family's does (``chipbench/MOE.md``); at a margin of 0
    no row is left out.  How many were goes to standard error and to
    ``undecided`` below.  ``switches`` are ``trunk``'s."""
    margin = cfg.get("check_undecided_margin", 0.0)
    if margin and ties is None:
        ties = []
    how = dict(reference.settings(cfg), **switches)
    out = reference.forward(params, tokens, *reference.layout(cfg),
                            ties=ties, **how)
    if not ties or not margin:
        return out
    import jax.numpy as jnp

    left_out = jnp.min(jnp.stack(ties), axis=0) < margin
    undecided.append((int(left_out.sum()), left_out.size))
    print(f"chipbench: sink_window_moe: {undecided[-1][0]} of "
          f"{undecided[-1][1]} rows left out of the check as undecided "
          f"(margin under {margin})", file=sys.stderr)
    return jnp.where(left_out[..., None], 0.0, out)


# (rows left out, rows) of each call of ``logits``, for whoever asks
undecided = []


def _outside_experts(z):
    """Matmul parameters a token is multiplied by outside the routed
    experts: every layer's attention, the dense FFNs, each routed layer's
    router, and the head (the table's rows are gathered)."""
    total = z["d"] * z["rows"]
    for i in range(z["layers"]):
        total += sum(rows * cols
                     for name, (*_, rows, cols) in _matrices(i, z).items()
                     if not name.startswith("experts_"))
    return total


def sizes(cfg):
    z = _dims(cfg)
    routed = z["layers"] - z["dense"]
    # a token selects num_experts_per_tok of router_width experts; the
    # held ones get held / width of them: 0.5 experts a routed layer here
    applied = cfg["num_experts_per_tok"] * z["held"] / z["width"]
    return {
        "d_model": z["d"], "heads": z["h"], "head_dim": z["dh"],
        "vocab_rows": z["rows"],
        # what a token is multiplied by ON THIS CHIP, in expectation
        "matmul_params": int(_outside_experts(z)
                             + routed * applied * 3 * z["d"] * z["e"]),
        "kv_planes": z["layers"], "attention_passes": z["layers"],
    }


def moe_sizes(cfg):
    """What the readers of the routed layer ask: the routed layers, the
    experts held of the router's width, the experts a token selects, the
    parameters of ONE expert's three matrices, and the matmul parameters
    outside the routed experts (streamed once a decode step whatever the
    routing)."""
    z = _dims(cfg)
    return {
        "moe_layers": z["layers"] - z["dense"], "experts_held": z["held"],
        "router_width": z["width"], "top_k": cfg["num_experts_per_tok"],
        "expert_params": 3 * z["d"] * z["e"],
        "expert_ops_per_row": 6 * z["d"] * z["e"],
        "outside_params": _outside_experts(z),
    }


def mixed_sizes(cfg):
    """The planes by kind, at the PUBLISHED values whatever the pool
    stores (``mixed_kv_bytes.py``): how many planes of each kind, the K/V
    heads of one, the lanes of a key and of a value, the query heads, and
    the window."""
    z = _dims(cfg)
    return {
        "planes": {kind: z["types"].count(kind)
                   for kind in ("full", "window")},
        "kv_heads": dict(z["kv"]), "key_lanes": z["dh"],
        "value_lanes": z["dv"], "heads": z["h"],
        "window": cfg["sliding_window"],
    }
