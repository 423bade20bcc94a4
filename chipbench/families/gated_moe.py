"""The gated-attention mixture-of-experts family (Arcee Trinity,
``model_type`` ``afmoe``): sandwich-normed layers of gated grouped-query
attention (rotary window layers and position-free full ones, heads wider
than ``hidden_size / num_attention_heads``) and a dense or ROUTED gated
SiLU FFN: a shared expert beside ``num_experts`` routed ones, sigmoid
scores, ``num_experts_per_tok`` of them a token.  Configuration keys are
those of the published ``config.json``; ``num_experts`` is what THIS
chip holds of the ``router_width`` experts a routed layer has (experts
``experts_first .. experts_first + num_experts - 1``): the router keeps
its published width and a token is routed over all of them.  What the
config has no key for is under ``assumed`` in the configuration file.

The program serves it through ``ServingEngine(params,
arch=GatedMoE(...))`` (``paddle_tpu/serving/arch.py``); the plain
reference is ``gated_moe_reference.py`` beside this file.  The family
serves only: there is no ``training_program`` (``transformer.build`` has
no routed layer), so a training cell is refused by the loader.
``families/__init__.py`` says what each function is for; ``moe_sizes`` is
what the readers of the routed layer ask beside ``sizes``
(``chipbench/moe_bytes.py``).
"""

import sys

# a checkout whose program has no such architecture cannot run this
# family: it fails here, when the family is loaded, before any weight is made
from paddle_tpu.serving.arch import GatedMoE

from . import gated_moe_reference

_KINDS = {"sliding_attention": "window", "full_attention": "full"}


def _dims(cfg):
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return {"d": d, "dh": dh, "q": cfg["num_attention_heads"] * dh,
            "kv": cfg["num_key_value_heads"] * dh,
            "f": cfg["intermediate_size"], "e": cfg["moe_intermediate_size"],
            "held": cfg["num_experts"], "width": cfg["router_width"],
            "rows": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
            "dense": cfg["num_dense_layers"],
            "types": tuple(_KINDS[t] for t in cfg["layer_types"])}


def _matrices(routed, z):
    """{name: shape} of the matmul matrices of one layer."""
    d, q, kv, e = z["d"], z["q"], z["kv"], z["e"]
    mats = {"att_q": (d, q), "att_k": (d, kv), "att_v": (d, kv),
            "att_gate": (d, q), "att_out": (q, d)}
    if routed:
        mats.update(router=(d, z["width"]), shared_gate=(d, e),
                    shared_up=(d, e), shared_down=(e, d),
                    experts_gate=(z["held"], d, e),
                    experts_up=(z["held"], d, e),
                    experts_down=(z["held"], e, d))
    else:
        mats.update(ffn_gate=(d, z["f"]), ffn_up=(d, z["f"]),
                    ffn_down=(z["f"], d))
    return mats


def make_params(cfg, positions, seed):
    """The engine's parameter dict (``GatedMoE``'s names), in the
    configuration's dtype, from ``--seed``: normal(0, 0.02) matrices (the
    router's and the experts' among them), table and head, in one jitted
    call; unit gains on the norms BEFORE a sub-layer, on the q/k norms
    and on the final norm; ``1 / sqrt(2 x layers held)`` on the two
    norms AFTER a sub-layer (the configuration file's ``assumed.init``
    says why); and each routed layer's ``expert_bias`` as training
    leaves it: the bias that spreads the selections evenly over the
    router's experts (``_balance``, over the configuration's
    ``expert_bias_tokens``: sequences x their length).  Rotary and
    position-free layers need no table, so ``positions`` sizes
    nothing."""
    import jax
    import jax.numpy as jnp

    del positions
    z = _dims(cfg)
    dtype = jnp.dtype(cfg["compute_dtype"])
    branch = (2 * z["layers"]) ** -0.5

    @jax.jit
    def init(key):
        keys = iter(jax.random.split(key, 12 * z["layers"] + 2))

        def normal(*shape):
            return 0.02 * jax.random.normal(next(keys), shape, dtype)

        d = z["d"]
        p = {"tok_emb.w": normal(z["rows"], d),
             "lm_head.w": normal(d, z["rows"]),
             "norm_f.scale": jnp.ones((d,), dtype)}
        for i in range(z["layers"]):
            b = f"block{i}_"
            routed = i >= z["dense"]
            for name, shape in _matrices(routed, z).items():
                p[b + name + ".w"] = normal(*shape)
            if routed:
                p[b + "router.bias"] = jnp.zeros((z["width"],), dtype)
            for name, gain in (("norm1", 1.0), ("norm2", branch),
                               ("norm3", 1.0), ("norm4", branch)):
                p[b + name + ".scale"] = jnp.full((d,), gain, dtype)
            for name in ("att_qnorm", "att_knorm"):
                p[b + name + ".scale"] = jnp.ones((z["dh"],), dtype)
        return p, jax.random.randint(
            next(keys), tuple(cfg["expert_bias_tokens"]), 0, z["rows"])

    # the key is an argument, so one executable serves every seed
    params, tokens = init(jax.random.PRNGKey(abs(int(seed)) % (2 ** 31 - 1)))
    return _balance(params, tokens, cfg)


def _balanced_bias(s, top_k, steps=32):
    """The bias ``[width]`` under which ``top_k`` of ``s + bias`` selects
    every expert equally often over the rows ``s [n, width]``: the
    fixed point a trained router's bias is moved towards (up where an
    expert is selected too seldom, down where too often), found by
    damped steps on the logarithm of the load."""
    import jax
    import jax.numpy as jnp

    n, width = s.shape
    target = n * top_k / width

    def step(_, bias):
        _, sel = jax.lax.top_k(s + bias, top_k)
        load = jnp.zeros((width,), jnp.float32).at[sel.reshape(-1)].add(1.0)
        bias = bias - 0.02 * jnp.log((load + 1.0) / (target + 1.0))
        return bias - jnp.mean(bias)

    return jax.lax.fori_loop(0, steps, step,
                             jnp.zeros((width,), jnp.float32))


def _balance(params, tokens, cfg):
    """``params`` with every routed layer's ``router.bias`` settled, one
    layer after the other, on the reference's own forward over ``tokens
    [n, t]`` (uniform ids from ``--seed``).

    A trained model of this family holds in ``expert_bias`` what
    balanced its experts' load; a seeded router with a zero bias loads
    them unevenly (an expert gets 0.5 to 1.9 times its share), and THIS
    chip's 32 then get 47.5% to 52.6% of the pairs by seed instead of
    the half a deployment's chip gets, which moved the decode step by
    the same 5% (my chip runs, PR 34)."""
    import jax

    z = _dims(cfg)
    settle = jax.jit(_balanced_bias, static_argnums=(1,))

    def before_routing(i, x):
        route = {k: params[f"block{i}_{k}"]
                 for k in gated_moe_reference._ROUTE_KEYS}
        *_, s = gated_moe_reference._route(
            x, route, top_k=cfg["num_experts_per_tok"],
            scale=cfg["route_scale"], norm=True, eps=cfg["rms_norm_eps"])
        params[f"block{i}_router.bias"] = settle(
            s.reshape(-1, z["width"]), cfg["num_experts_per_tok"]).astype(
                params[f"block{i}_router.bias"].dtype)

    gated_moe_reference.trunk(
        params, tokens, *_layout(cfg), eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), before_routing=before_routing)
    return params


def _layout(cfg):
    """The reference's positional arguments after the tokens."""
    z = _dims(cfg)
    return (z["types"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["sliding_window"], z["dense"],
            cfg["num_experts_per_tok"], (cfg["experts_first"], z["held"]),
            cfg["route_scale"])


def _arch(cfg):
    z = _dims(cfg)
    return GatedMoE(
        z["types"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
        z["dh"], z["d"], window=cfg["sliding_window"],
        dense_layers=z["dense"], router_width=z["width"],
        top_k=cfg["num_experts_per_tok"],
        experts=(cfg["experts_first"], z["held"]),
        route_scale=cfg["route_scale"], eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"])


def serving_engine(params, cfg, registry, geometry):
    import paddle_tpu as pt

    return pt.serving.ServingEngine(
        params, arch=_arch(cfg), registry=registry,
        compute_dtype=cfg["compute_dtype"], **geometry)


def logits(params, tokens, cfg, ties=None, **switches):
    """The reference's logits ``[b, t, V]``, with the rows it cannot
    decide set to zero.

    The model is discontinuous where a row's last selected expert and
    the best one left out score alike: which of the two a sound bfloat16
    program selects there is decided by its rounding, and a row that
    gained or lost a HELD expert computed another function of the same
    model (its logits move as far as a fault's do).  The runner's check
    takes the worst gap over all sampled tokens and has no way to leave
    one out, so this function does: a row where, in any routed layer,
    the margin between those two (``gated_moe_reference.routed_ffn``'s
    ``ties``) is under the configuration's ``check_undecided_margin``
    comes back as zeros, which every token satisfies (gap 0).  How many
    were left out goes to standard error and to ``undecided`` below; a
    row whose tie is between two experts held elsewhere is kept.
    ``ties`` (a list) receives the margins, a routed layer each."""
    ties = [] if ties is None else ties
    out = gated_moe_reference.forward(
        params, tokens, *_layout(cfg), eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), ties=ties, **switches)
    if not ties:
        return out
    import jax.numpy as jnp

    left_out = jnp.min(jnp.stack(ties), axis=0) < cfg["check_undecided_margin"]
    undecided.append((int(left_out.sum()), left_out.size))
    print(f"chipbench: gated_moe: {undecided[-1][0]} of {undecided[-1][1]} "
          f"rows left out of the check as undecided (margin under "
          f"{cfg['check_undecided_margin']})", file=sys.stderr)
    return jnp.where(left_out[..., None], 0.0, out)


# (rows left out, rows) of each call of ``logits``, for whoever asks
undecided = []


def _outside_experts(z):
    """Matmul parameters a token is multiplied by outside the routed
    experts: every layer's attention, the dense FFNs, each routed
    layer's router and shared expert, and the head (the table's rows are
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
    # held ones get held / width of them: 0.5 experts a routed layer here
    applied = cfg["num_experts_per_tok"] * z["held"] / z["width"]
    return {
        "d_model": z["d"], "heads": cfg["num_attention_heads"],
        "head_dim": z["dh"], "vocab_rows": z["rows"],
        # what a token is multiplied by ON THIS CHIP, in expectation
        "matmul_params": int(_outside_experts(z)
                             + routed * applied * 3 * z["d"] * z["e"]),
        "kv_planes": z["layers"], "attention_passes": z["layers"],
    }


def hybrid_sizes(cfg):
    """The K/V heads a plane holds, the window and how many planes have
    it, and how often a token reads a full plane (``hybrid_bytes.py``);
    no recurrent state."""
    z = _dims(cfg)
    return {
        "kv_heads": cfg["num_key_value_heads"],
        "window": cfg["sliding_window"],
        "window_planes": z["types"].count("window"),
        "full_plane_reads": z["types"].count("full"),
        "state_layers": 0, "state_bytes_per_slot": 0,
    }


def moe_sizes(cfg):
    """What the readers of the routed layer ask (``moe_bytes.py``): the
    routed layers, the experts held of the router's width, the experts a
    token selects, the parameters of ONE expert's three matrices, and
    the matmul parameters outside the routed experts (streamed once a
    decode step whatever the routing)."""
    z = _dims(cfg)
    return {
        "moe_layers": z["layers"] - z["dense"], "experts_held": z["held"],
        "router_width": z["width"], "top_k": cfg["num_experts_per_tok"],
        "expert_params": 3 * z["d"] * z["e"],
        "expert_ops_per_row": 6 * z["d"] * z["e"],
        "outside_params": _outside_experts(z),
    }
