"""The sparse latent-attention mixture-of-experts family (the language
model of ``dots3-note-prev``, ``model_type`` ``dots3_note``; its
attention keys are DeepSeek-V3.2's plus one ``swa_`` copy): pre-normed
layers of latent attention in TWO geometries by ``layer_types``.  A
``full_attention`` layer caches one row of ``kv_lora_rank +
qk_rope_head_dim`` values AND an index key of ``index_head_dim`` values a
position, and its ``num_attention_heads`` heads attend the
``index_topk`` cached positions its indexer (``index_n_heads`` heads)
scores highest; a ``sliding_attention`` layer caches one row of
``swa_kv_lora_rank + swa_qk_rope_head_dim`` values and attends the last
``sliding_window_size`` positions.  Every layer has a query latent
(``q_lora_rank``), the lora rescale and one sigmoid gate a head.  The
FFN is dense in the first ``first_k_dense_replace`` layers, then routed:
sigmoid scores, a bias that selects only, ``num_experts_per_tok`` of
``router_width``, the weights normalised, one shared expert beside them.
``n_routed_experts`` is what THIS chip holds of the router's experts
(``experts_first ..``).  What the config has no key for is under
``assumed`` in the configuration file.

The program serves it through ``ServingEngine(params,
arch=SparseLatentMoE(...))`` (``paddle_tpu/serving/arch.py``); the plain
reference is ``sparse_latent_moe_reference.py`` beside this file.  The
family serves only.  ``dsa_sizes`` is what the readers of its planes ask
beside ``sizes`` and ``moe_sizes`` (``chipbench/dsa_bytes.py``).
"""

import sys

# a checkout whose program has no such architecture cannot run this
# family: it fails here, when the family is loaded, before any weight is made
from paddle_tpu.serving.arch import SparseLatentMoE

from . import sparse_latent_moe_reference as reference
# the bias under which top k of s + bias loads every expert alike
from .gated_moe import _balanced_bias


def _shapes(cfg):
    how = reference.layout(cfg)
    return reference.param_shapes(
        cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"],
        cfg["moe_intermediate_size"], cfg["router_width"],
        cfg["n_routed_experts"], how["layer_types"], how["full"],
        how["sliding"], how["index_heads"], how["index_dim"],
        how["dense_layers"])


def make_params_unsettled(cfg, seed):
    """``make_params`` before the routers' biases are settled (zeros);
    also the tokens they are settled on."""
    import jax
    import jax.numpy as jnp

    shapes = _shapes(cfg)
    dtype = jnp.dtype(cfg["compute_dtype"])
    std = cfg["initializer_range"]
    # the table and the full layers' query up-projection have ranges of
    # their own (``assumed.init`` says why)
    own = {"tok_emb.w": cfg.get("embedding_range", std)}
    own.update({f"block{i}_att_qb.w": cfg.get("full_query_range", std)
                for i, kind in enumerate(reference.layout(cfg)["layer_types"])
                if kind == "full"})

    @jax.jit
    def init(key):
        keys = iter(jax.random.split(key, len(shapes) + 1))
        p = {}
        for name, shape in shapes.items():
            k = next(keys)
            if name.endswith(".scale"):
                p[name] = jnp.ones(shape, dtype)
            elif name.endswith(".bias"):
                p[name] = jnp.zeros(shape, dtype)
            else:
                p[name] = own.get(name, std) * jax.random.normal(
                    k, shape, dtype)
        return p, jax.random.randint(
            next(keys), (cfg["expert_bias_tokens"],), 0, cfg["vocab_size"])

    # the key is an argument, so one executable serves every seed
    return init(jax.random.PRNGKey(abs(int(seed)) % (2 ** 31 - 1)))


def make_params(cfg, positions, seed):
    """The engine's parameter dict (``SparseLatentMoE``'s names), in the
    configuration's dtype, from ``--seed``: normal(0,
    ``initializer_range``) matrices (routers, experts, indexer, table and
    head among them; the table at ``embedding_range`` and the full
    layers' ``att_qb.w`` at ``full_query_range``), unit gains, a zero
    index LayerNorm bias, in one jitted call; each routed layer's ``router.bias`` as training leaves
    it (``topk_method`` ``noaux_tc``): the bias that spreads the
    selections evenly over the router's experts (``_balance``, over the
    configuration's ``expert_bias_tokens``).  Rotary positions need no
    table, so ``positions`` sizes nothing."""
    del positions
    params, tokens = make_params_unsettled(cfg, seed)
    return _balance(params, tokens, cfg)


def _balance(params, tokens, cfg):
    """``params`` with every routed layer's ``router.bias`` settled, one
    layer after the other, on the reference's own forward over ``tokens
    [t]`` (ONE sequence of uniform ids from ``--seed``), as the
    ``gated_moe`` and ``sink_window_moe`` families do."""
    import jax

    how = reference.layout(cfg)
    settle = jax.jit(_balanced_bias, static_argnums=(1,))

    def before_routing(i, x):
        route = {k: params[f"block{i}_{k}"] for k in reference._ROUTE_KEYS}
        *_, s = reference._route(x, route, top_k=how["top_k"], scale=1.0,
                                 norm=True, eps=how["eps"])
        params[f"block{i}_router.bias"] = settle(s, how["top_k"]).astype(
            params[f"block{i}_router.bias"].dtype)

    reference.trunk(params, tokens, **how, before_routing=before_routing)
    return params


def _arch(cfg):
    how = reference.layout(cfg)
    return SparseLatentMoE(
        how["layer_types"], cfg["hidden_size"], how["full"], how["sliding"],
        window=how["window"], index_heads=how["index_heads"],
        index_dim=how["index_dim"], index_topk=how["index_topk"],
        dense_layers=how["dense_layers"], router_width=cfg["router_width"],
        top_k=how["top_k"], experts=how["experts"],
        route_scale=how["route_scale"], norm_topk=cfg["norm_topk_prob"],
        eps=how["eps"])


def serving_engine(params, cfg, registry, geometry):
    import paddle_tpu as pt

    return pt.serving.ServingEngine(
        params, arch=_arch(cfg), registry=registry,
        compute_dtype=cfg["compute_dtype"], **geometry)


def logits(params, tokens, cfg, ties=None, **switches):
    """The reference's logits ``[b, t, V]`` (``b`` is 1: the check
    compares one sequence a call), with the rows it cannot decide set to
    zero, as ``gated_moe.logits`` does it (``chipbench/MOE.md``): a row
    whose expert selection, in any routed layer, is within the
    configuration's ``check_undecided_margin`` of one that differs in a
    HELD expert (``sparse_latent_moe_reference._margin``) comes back as
    zeros, which every token satisfies (gap 0).  How many were left out
    goes to standard error and to ``undecided`` below.  At a margin of 0
    nothing is left out and no margin is computed.  ``ties`` (a list)
    receives the margins, a routed layer each; ``switches`` are
    ``sparse_latent_moe_reference.trunk``'s."""
    margin = cfg.get("check_undecided_margin", 0.0)
    if margin and ties is None:
        ties = []
    how = dict(reference.layout(cfg), **dict(
        {"route_norm": cfg["norm_topk_prob"]}, **switches))
    out = reference.forward(params, tokens, ties=ties, **how)
    if not ties or not margin:
        return out
    import jax.numpy as jnp

    left_out = jnp.min(jnp.stack(ties), axis=0) < margin          # [t]
    undecided.append((int(left_out.sum()), left_out.size))
    print(f"chipbench: sparse_latent_moe: {undecided[-1][0]} of "
          f"{undecided[-1][1]} rows left out of the check as undecided "
          f"(margin under {margin})", file=sys.stderr)
    return jnp.where(left_out[None, :, None], 0.0, out)


# (rows left out, rows) of each call of ``logits``, for whoever asks
undecided = []


def _outside_experts(cfg):
    """Matmul parameters a token is multiplied by outside the routed
    experts: every layer's attention and indexer, the dense FFN, each
    routed layer's router and shared expert, and the head (the table's
    rows are gathered)."""
    return sum(
        rows * cols for name, (*lead, rows, cols) in (
            (n, s) for n, s in _shapes(cfg).items() if len(s) == 2)
        if not lead and name != "tok_emb.w")


def sizes(cfg):
    how = reference.layout(cfg)
    g = how["full"]
    routed = len(how["layer_types"]) - how["dense_layers"]
    # a token selects num_experts_per_tok of router_width experts; the
    # held ones get held / width of them: 1 expert a routed layer here
    applied = how["top_k"] * cfg["n_routed_experts"] / cfg["router_width"]
    return {
        "d_model": cfg["hidden_size"], "heads": g["heads"],
        "head_dim": g["nope"] + g["rope"], "vocab_rows": cfg["vocab_size"],
        # what a token is multiplied by ON THIS CHIP, in expectation
        "matmul_params": int(_outside_experts(cfg) + routed * applied * 3
                             * cfg["hidden_size"]
                             * cfg["moe_intermediate_size"]),
        "kv_planes": len(how["layer_types"]),
        "attention_passes": len(how["layer_types"]),
    }


def moe_sizes(cfg):
    """What the readers of the routed layer ask: ``gated_moe.moe_sizes``'
    keys."""
    how = reference.layout(cfg)
    expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return {
        "moe_layers": len(how["layer_types"]) - how["dense_layers"],
        "experts_held": cfg["n_routed_experts"],
        "router_width": cfg["router_width"], "top_k": how["top_k"],
        "expert_params": expert, "expert_ops_per_row": 2 * expert,
        "outside_params": _outside_experts(cfg),
    }


def dsa_sizes(cfg):
    """What the readers of the planes ask (``chipbench/dsa_bytes.py``):
    the planes by kind, and of a plane of each kind the lanes the pool
    STORES of a position's row (the next multiple of 128 over ``rank +
    rope``), the lanes that are the value, the query heads; the index
    key's lanes and heads, ``index_topk`` and the window."""
    how = reference.layout(cfg)
    kinds = {}
    for kind in ("full", "sliding"):
        g = how[kind]
        kinds[kind] = {
            "planes": how["layer_types"].count(kind), "heads": g["heads"],
            "written": g["rank"] + g["rope"],
            "stored": -(-(g["rank"] + g["rope"]) // 128) * 128,
            "value_lanes": g["rank"]}
    return dict(kinds, index_lanes=how["index_dim"],
                index_heads=how["index_heads"],
                index_topk=how["index_topk"], window=how["window"])
