"""The delta-rule mixture-of-experts family (Upstage Solar-Open2,
``model_type`` ``solar_open2``): layers of a gated delta rule with a
decay a channel (Kimi Delta Attention, arXiv:2510.26692) and, at
``gqa_layers``, of gated position-free grouped-query attention, every
layer followed by a routed gated-SiLU FFN beside one shared expert.
Configuration keys are those of the published ``config.json``;
``n_routed_experts`` is what THIS chip holds of the ``router_width``
experts a layer has (experts ``experts_first .. experts_first +
n_routed_experts - 1``): the router keeps its published width and a token
is routed over all of them.  What the config has no key for is under
``assumed`` in the configuration file.

The program serves it through ``ServingEngine(params,
arch=DeltaMoE(...))`` (``paddle_tpu/serving/arch.py``); the plain
reference is ``delta_moe_reference.py`` beside this file.  The family
serves only.  ``families/__init__.py`` says what each function is for;
``delta_sizes`` is what ``chipbench/delta_bytes.py`` asks beside
``sizes``.
"""

import sys

# a checkout whose program has no such architecture cannot run this
# family: it fails here, when the family is loaded, before any weight is made
from paddle_tpu.serving.arch import DeltaMoE

from . import delta_moe_reference as reference
# the bias that spreads the load and the head centred on the seeded rows
# are the settling ``nemotron-3-nano-30b-a3b`` is seeded with (PR 51)
from .ssm_moe import _balanced_bias, _centred_head


def _dims(cfg):
    lin = cfg["linear_attn_config"]
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
            "gqa": tuple(cfg["gqa_layers"]),
            "heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "H": lin["num_heads"], "D": lin["head_dim"],
            "taps": lin["short_conv_kernel_size"],
            # kda_use_full_proj false: the decay and the output gate are
            # two low-rank maps each, of the head's width
            "rank": lin["head_dim"],
            "e": cfg["moe_intermediate_size"],
            "held": cfg["n_routed_experts"], "width": cfg["router_width"],
            "top_k": cfg["num_experts_per_tok"], "rows": cfg["vocab_size"]}


def layer_shapes(kind, z):
    """``{name: shape}`` of one layer of ``kind`` (``"gqa"`` or
    ``"delta"``): its mixer, its norms and its routed FFN with
    ``z["held"]`` experts."""
    d, e = z["d"], z["e"]
    hd, kd = z["heads"] * z["dh"], z["kv"] * z["dh"]
    HD = z["H"] * z["D"]
    mixer = {"att_q.w": (d, hd), "att_k.w": (d, kd), "att_v.w": (d, kd),
             "att_gate.w": (d, hd), "att_out.w": (hd, d)} if kind == "gqa" \
        else {"delta_q.w": (d, HD), "delta_k.w": (d, HD),
              "delta_v.w": (d, HD), "delta_conv.w": (3 * HD, z["taps"]),
              "delta_fa.w": (d, z["rank"]), "delta_fb.w": (z["rank"], HD),
              "delta_dt.b": (HD,), "delta_A_log.w": (z["H"],),
              "delta_beta.w": (d, z["H"]), "delta_ga.w": (d, z["rank"]),
              "delta_gb.w": (z["rank"], HD), "delta_gb.b": (HD,),
              "delta_onorm.scale": (z["D"],), "delta_out.w": (HD, d)}
    return dict(mixer, **{
        "norm1.scale": (d,), "norm2.scale": (d,),
        "router.w": (d, z["width"]), "router.bias": (z["width"],),
        "shared_gate.w": (d, e), "shared_up.w": (d, e),
        "shared_down.w": (e, d),
        "experts_gate.w": (z["held"], d, e),
        "experts_up.w": (z["held"], d, e),
        "experts_down.w": (z["held"], e, d)})


def shapes(cfg):
    """``{parameter name: shape}`` of the whole configuration."""
    z = _dims(cfg)
    out = {"tok_emb.w": (z["rows"], z["d"]), "lm_head.w": (z["d"], z["rows"]),
           "norm_f.scale": (z["d"],)}
    for i in range(z["layers"]):
        kind = "gqa" if i in z["gqa"] else "delta"
        out.update({f"block{i}_{name}": shape
                    for name, shape in layer_shapes(kind, z).items()})
    return out


def parameters(cfg):
    """Parameters the configuration holds, counted from ``shapes``."""
    total = 0
    for shape in shapes(cfg).values():
        n = 1
        for s in shape:
            n *= s
        total += n
    return total


def make_params(cfg, positions, seed):
    """``make_params_unsettled`` with every layer's router bias settled
    and the head centred (``_balance``).  No layer needs a table of
    positions."""
    del positions
    return _balance(*make_params_unsettled(cfg, seed), cfg)


def make_params_unsettled(cfg, seed):
    """``(params, tokens)``: the engine's parameter dict (``DeltaMoE``'s
    names), in the configuration's dtype, from ``--seed``, in one jitted
    call (the configuration file's ``assumed.init``): normal(0, 0.02)
    matrices and head, a table of rows of RMS 1, unit gains, zero biases;
    a delta layer as the family's public code seeds it: the convolution
    uniform in +-0.5, ``A_log`` the log of uniform [1, 16], ``dt_bias`` the
    inverse softplus of a step log-uniform in [0.001, 0.1]; and the
    ``expert_bias_tokens`` uniform ids the biases are settled on."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(cfg["compute_dtype"])
    all_shapes = shapes(cfg)
    lo, hi = cfg["time_step_min"], cfg["time_step_max"]

    @jax.jit
    def init(key):
        keys = iter(jax.random.split(key, len(all_shapes) + 1))
        f32 = jnp.float32
        p = {}
        for name, shape in all_shapes.items():
            k = next(keys)
            kind = name.split("_", 1)[-1]
            if kind.endswith(".scale"):
                p[name] = jnp.ones(shape, dtype)
            elif kind in ("router.bias", "delta_gb.b"):
                p[name] = jnp.zeros(shape, dtype)
            elif kind == "delta_conv.w":
                p[name] = jax.random.uniform(k, shape, f32, -0.5, 0.5
                                             ).astype(dtype)
            elif kind == "delta_A_log.w":
                p[name] = jnp.log(jax.random.uniform(
                    k, shape, f32, 1.0, 16.0)).astype(dtype)
            elif kind == "delta_dt.b":
                step = jnp.exp(jax.random.uniform(
                    k, shape, f32, jnp.log(lo), jnp.log(hi)))
                p[name] = (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
            else:
                gain = 1.0 if name == "tok_emb.w" else 0.02
                p[name] = gain * jax.random.normal(k, shape, dtype)
        return p, jax.random.randint(
            next(keys), (cfg["expert_bias_tokens"],), 0, cfg["vocab_size"])

    # the key is an argument, so one executable serves every seed
    return init(jax.random.PRNGKey(abs(int(seed)) % (2 ** 31 - 1)))


def _balance(params, tokens, cfg):
    """``params`` with every layer's ``router.bias`` settled, one layer
    after the other, on the reference's own forward over ``tokens [n]``
    (ONE sequence of uniform ids from ``--seed``): a trained model of this
    family holds in the bias what balanced its experts' load, and a
    seeded router with a zero bias gives THIS chip's share of the experts
    a share of the rows that moves with the seed
    (``trinity-large-preview``'s way).  The same forward's last residual
    centres the head (``ssm_moe._centred_head``: its columns made
    orthogonal to the mean normed residual, ``nemotron-3-nano``'s
    lesson)."""
    import jax

    z = _dims(cfg)
    settle = jax.jit(_balanced_bias, static_argnums=(1,))

    def before_routing(i, x):
        route = {k: params[f"block{i}_{k}"] for k in reference._ROUTE_KEYS}
        *_, s = reference._route(x, route, top_k=z["top_k"], scale=1.0,
                                 norm=True, eps=cfg["rms_norm_eps"])
        params[f"block{i}_router.bias"] = settle(s, z["top_k"]).astype(
            params[f"block{i}_router.bias"].dtype)

    x = reference.trunk(params, tokens, *_layout(cfg),
                        eps=cfg["rms_norm_eps"],
                        before_routing=before_routing)
    params["lm_head.w"] = _centred_head(params["lm_head.w"], x,
                                        cfg["rms_norm_eps"])
    return params


def _layout(cfg):
    """The reference's positional arguments after the tokens."""
    z = _dims(cfg)
    return (z["layers"], z["gqa"], z["heads"], z["kv"], z["H"], z["top_k"],
            (cfg["experts_first"], z["held"]), cfg["routed_scaling_factor"])


def _arch(cfg):
    z = _dims(cfg)
    return DeltaMoE(
        z["layers"], z["gqa"], z["heads"], z["kv"], z["dh"], z["d"],
        delta_heads=z["H"], delta_head_dim=z["D"], conv_taps=z["taps"],
        router_width=z["width"], top_k=z["top_k"],
        experts=(cfg["experts_first"], z["held"]),
        route_scale=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"],
        beta_scale=2.0 if cfg["kda_allow_neg_eigval"] else 1.0,
        eps=cfg["rms_norm_eps"])


def serving_engine(params, cfg, registry, geometry):
    import paddle_tpu as pt

    return pt.serving.ServingEngine(
        params, arch=_arch(cfg), registry=registry,
        compute_dtype=cfg["compute_dtype"], **geometry)


def logits(params, tokens, cfg, ties=None, **switches):
    """The reference's logits ``[b, t, V]`` (a NumPy array; ``b`` is 1:
    the check compares one sequence a call), with the rows it cannot
    decide set to zero, as ``gated_moe.logits`` does it
    (``chipbench/MOE.md``): a row whose expert selection, in any layer, is
    within the configuration's ``check_undecided_margin`` of one that
    differs in a HELD expert (``delta_moe_reference._margin``) comes back
    as zeros, which every token satisfies (gap 0).  How many were left
    out goes to standard error and to ``undecided`` below.  At a margin
    of 0 nothing is left out and no margin is computed.  ``ties`` (a list)
    receives the margins, a layer a block of rows; ``switches`` are
    ``delta_moe_reference.trunk``'s."""
    import numpy as np

    margin = cfg.get("check_undecided_margin", 0.0)
    if margin and ties is None:
        ties = []
    how = dict({"route_norm": cfg["norm_topk_prob"],
                "beta_scale": 2.0 if cfg["kda_allow_neg_eigval"] else 1.0},
               **switches)
    out = reference.forward(params, tokens, *_layout(cfg),
                            eps=cfg["rms_norm_eps"], ties=ties, **how)
    _refuse_rows_not_computed(out, np.asarray(tokens))
    if not ties or not margin:
        return out
    layers = cfg["num_hidden_layers"]
    per = len(ties) // layers
    least = np.min(np.stack([
        np.concatenate([np.asarray(m) for m in ties[i * per:(i + 1) * per]])
        for i in range(layers)]), axis=0)                         # [t]
    left_out = least < margin
    undecided.append((int(left_out.sum()), left_out.size))
    print(f"chipbench: delta_moe: {undecided[-1][0]} of "
          f"{undecided[-1][1]} rows left out of the check as undecided "
          f"(margin under {margin})", file=sys.stderr)
    out[-1][left_out] = 0.0
    return out


def _refuse_rows_not_computed(out, tokens):
    """A row whose logits are not finite (a reference whose state
    diverged: the delta rule with the keys' l2-norm left out) was not
    computed, and must not pass: the runner's comparison takes ``max(worst,
    nan)`` for ``worst``.  Such a row becomes zeros with ``-1e30`` at the
    token that FOLLOWS it in ``tokens``, the one the check holds it to: a
    gap no limit admits."""
    import numpy as np

    for logits_b, tokens_b in zip(out, tokens):
        bad = np.flatnonzero(~np.isfinite(logits_b).all(axis=-1))
        if bad.size:
            logits_b[bad] = 0.0
            logits_b[bad, np.append(tokens_b[1:], 0)[bad]] = -1e30


# (rows left out, rows) of each call of ``logits``, for whoever asks
undecided = []


def _outside(kind, z):
    """Matmul parameters of one layer of ``kind`` outside its routed
    experts: every matrix a row is multiplied by whatever the routing."""
    return sum(shape[0] * shape[1]
               for name, shape in layer_shapes(kind, z).items()
               if len(shape) == 2 and name != "delta_conv.w")


def delta_sizes(cfg):
    """What ``chipbench/delta_bytes.py`` asks: the layers of each kind,
    the delta layers' geometry and what a slot holds of one, the share of
    the router's experts held, the parameters of ONE expert's three
    matrices, the matmul parameters outside the routed experts (streamed
    once a decode step whatever the routing), the bytes a cached position
    holds and the lanes of a GQA layer's query heads."""
    z = _dims(cfg)
    n_gqa = len(z["gqa"])
    n_delta = z["layers"] - n_gqa
    outside = z["d"] * z["rows"] + (n_gqa * _outside("gqa", z)
                                    + n_delta * _outside("delta", z))
    return {
        "delta_layers": n_delta, "gqa_layers": n_gqa,
        "moe_layers": z["layers"],
        "delta_heads": z["H"], "delta_head_dim": z["D"], "taps": z["taps"],
        "state_bytes": 4 * z["H"] * z["D"] * z["D"],
        "experts_held": z["held"], "router_width": z["width"],
        "top_k": z["top_k"], "d_model": z["d"], "expert_width": z["e"],
        "expert_params": 3 * z["d"] * z["e"],
        "expert_ops_per_row": 6 * z["d"] * z["e"],
        "outside_params": outside,
        "kv_heads": z["kv"], "head_dim": z["dh"],
        "kv_bytes_per_token": n_gqa * 2 * z["kv"] * z["dh"] * 2,
        "query_lanes": z["heads"] * z["dh"],
    }


def moe_sizes(cfg):
    """What the readers of the routed layer ask: ``gated_moe.moe_sizes``'
    keys."""
    more = delta_sizes(cfg)
    return {k: more[k] for k in (
        "moe_layers", "experts_held", "router_width", "top_k",
        "expert_params", "expert_ops_per_row", "outside_params")}


def sizes(cfg):
    z = _dims(cfg)
    more = delta_sizes(cfg)
    # a token selects top_k of router_width experts; the held ones get
    # held / width of them: 1 expert a layer here
    applied = z["top_k"] * z["held"] / z["width"]
    return {
        "d_model": z["d"], "heads": z["heads"], "head_dim": z["dh"],
        "vocab_rows": z["rows"],
        # what a token is multiplied by ON THIS CHIP, in expectation
        "matmul_params": int(more["outside_params"] + more["moe_layers"]
                             * applied * more["expert_params"]),
        "kv_planes": more["gqa_layers"],
        "attention_passes": more["gqa_layers"],
    }
