"""The state-space mixture-of-experts family (NVIDIA Nemotron-H /
Nemotron 3 Nano, ``model_type`` ``nemotron_h``): layers that are ONE
sub-layer each by ``hybrid_override_pattern`` (``M`` a Mamba-2 mixer,
``E`` a routed FFN of un-gated ``relu ** 2`` experts beside a shared one,
``*`` position-free grouped-query attention).  Configuration keys are
those of the published ``config.json``; ``n_routed_experts`` is what THIS
chip holds of the ``router_width`` experts a routed layer has (experts
``experts_first .. experts_first + n_routed_experts - 1``): the router
keeps its published width and a token is routed over all of them.  What
the config has no key for is under ``assumed`` in the configuration file.

The program serves it through ``ServingEngine(params,
arch=MambaMoE(...))`` (``paddle_tpu/serving/arch.py``); the plain
reference is ``ssm_moe_reference.py`` beside this file.  The family
serves only.  ``families/__init__.py`` says what each function is for;
``ssm_moe_sizes`` is what ``chipbench/ssm_moe_bytes.py`` asks beside
``sizes``.
"""

import sys

# a checkout whose program has no such architecture cannot run this
# family: it fails here, when the family is loaded, before any weight is made
from paddle_tpu.serving.arch import MambaMoE

from . import ssm_moe_reference


def _dims(cfg):
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return {"d": d, "dh": dh, "heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "H": H, "P": P, "G": G, "N": N,
            "inner": H * P, "conv": H * P + 2 * G * N,
            "taps": cfg["conv_kernel"], "e": cfg["moe_intermediate_size"],
            "s": cfg["moe_shared_expert_intermediate_size"],
            "held": cfg["n_routed_experts"], "width": cfg["router_width"],
            "top_k": cfg["num_experts_per_tok"], "rows": cfg["vocab_size"],
            "pattern": cfg["hybrid_override_pattern"]}


def _matrices(kind, z):
    """{name: shape} of the matmul matrices of one layer of ``kind``."""
    d = z["d"]
    if kind == "M":
        return {"ssm_in": (d, 2 * z["inner"] + 2 * z["G"] * z["N"] + z["H"]),
                "ssm_out": (z["inner"], d)}
    if kind == "*":
        return {"att_qkv": (d, (z["heads"] + 2 * z["kv"]) * z["dh"]),
                "att_out": (z["heads"] * z["dh"], d)}
    return {"router": (d, z["width"]), "shared_up": (d, z["s"]),
            "shared_down": (z["s"], d),
            # held transposed: an expert width that is not whole lane
            # tiles (serving/arch.py::routed_ffn)
            "experts_up": (z["held"], z["e"], d),
            "experts_down": (z["held"], z["e"], d)}


# the matrices that close a residual branch: scaled by 1 / sqrt(layers)
# (``rescale_prenorm_residual``)
_CLOSING = ("ssm_out", "att_out", "shared_down", "experts_down")
# the table's rows: RMS 1, what RMSNorm hands every layer (the
# configuration file's ``assumed.init`` says what rows of 0.02, of 3 and
# of 8 did to 52 seeded layers and to the check)
_TABLE_RMS = 1.0


def make_params(cfg, positions, seed):
    """``make_params_unsettled`` with every routed layer's bias settled
    (``_balance``).  No layer needs a table of positions."""
    del positions
    return _balance(*make_params_unsettled(cfg, seed), cfg)


def make_params_unsettled(cfg, seed):
    """``(params, tokens)``: the engine's parameter dict (``MambaMoE``'s names), in the
    configuration's dtype, from ``--seed``, in one jitted call: normal(0,
    0.02) matrices and head, a table of rows of RMS 1 (``_TABLE_RMS``),
    the matrices that close a branch over ``sqrt(layers)``
    (``rescale_prenorm_residual``); unit gains; a Mamba
    layer as the family seeds it (the configuration file's
    ``assumed.init``): the convolution uniform in ``+- taps ** -0.5``,
    ``dt_bias`` the inverse softplus of a step log-uniform in
    ``[time_step_min, time_step_max]``, ``A`` uniform in [1, 16], ``D``
    ones; each routed layer's bias zero; and the ``expert_bias_tokens``
    uniform ids the biases are settled on."""
    import jax
    import jax.numpy as jnp

    z = _dims(cfg)
    dtype = jnp.dtype(cfg["compute_dtype"])
    layers = len(z["pattern"])
    lo, hi = cfg["time_step_min"], cfg["time_step_max"]

    @jax.jit
    def init(key):
        keys = iter(jax.random.split(key, 8 * layers + 3))
        f32 = jnp.float32

        def normal(shape, gain=1.0):
            return gain * 0.02 * jax.random.normal(next(keys), shape, dtype)

        def uniform(shape, low, high):
            return jax.random.uniform(next(keys), shape, f32, low, high)

        d = z["d"]
        p = {"tok_emb.w": normal((z["rows"], d), _TABLE_RMS / 0.02),
             "lm_head.w": normal((d, z["rows"])),
             "norm_f.scale": jnp.ones((d,), dtype)}
        for i, kind in enumerate(z["pattern"]):
            b = f"block{i}_"
            p[b + "norm.scale"] = jnp.ones((d,), dtype)
            for name, shape in _matrices(kind, z).items():
                p[b + name + ".w"] = normal(
                    shape, layers ** -0.5 if name in _CLOSING else 1.0)
            if kind == "E":
                p[b + "router.bias"] = jnp.zeros((z["width"],), dtype)
            if kind == "M":
                bound = z["taps"] ** -0.5
                p[b + "ssm_conv.w"] = uniform(
                    (z["conv"], z["taps"]), -bound, bound).astype(dtype)
                p[b + "ssm_conv.b"] = uniform(
                    (z["conv"],), -bound, bound).astype(dtype)
                step = jnp.exp(uniform((z["H"],), jnp.log(lo), jnp.log(hi)))
                step = jnp.maximum(step, cfg["time_step_floor"])
                p[b + "ssm_dt.b"] = (
                    step + jnp.log(-jnp.expm1(-step))).astype(dtype)
                p[b + "ssm_A_log.w"] = jnp.log(
                    uniform((z["H"],), 1.0, 16.0)).astype(dtype)
                p[b + "ssm_D.w"] = jnp.ones((z["H"],), dtype)
                p[b + "ssm_norm.scale"] = jnp.ones((z["inner"],), dtype)
        return p, jax.random.randint(
            next(keys), tuple(cfg["expert_bias_tokens"]), 0, z["rows"])

    # the key is an argument, so one executable serves every seed
    return init(jax.random.PRNGKey(abs(int(seed)) % (2 ** 31 - 1)))


def _balanced_bias(s, top_k, steps=32):
    """The bias ``[width]`` under which ``top_k`` of ``s + bias`` selects
    every expert equally often over the rows ``s [n, width]``: the fixed
    point a trained router's bias is moved towards, found by damped
    steps on the logarithm of the load (``gated_moe``'s, PR 34)."""
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
    [n, t]`` (uniform ids from ``--seed``): a trained model of this
    family holds in the bias what balanced its experts' load, and a
    seeded router with a zero bias gives THIS chip's share of the experts
    a share of the rows that moves with the seed.  The same forward's
    last residual centres the head (``_centred_head``)."""
    import jax

    z = _dims(cfg)
    settle = jax.jit(_balanced_bias, static_argnums=(1,))

    def before_routing(i, x):
        route = {k: params[f"block{i}_{k}"]
                 for k in ssm_moe_reference._ROUTE_KEYS}
        *_, s = ssm_moe_reference._route(
            x, route, top_k=z["top_k"], scale=1.0, norm=True,
            eps=cfg["layer_norm_epsilon"])
        params[f"block{i}_router.bias"] = settle(
            s.reshape(-1, z["width"]), z["top_k"]).astype(
                params[f"block{i}_router.bias"].dtype)

    x = ssm_moe_reference.trunk(
        params, tokens, *_layout(cfg), eps=cfg["layer_norm_epsilon"],
        before_routing=before_routing)
    params["lm_head.w"] = _centred_head(params["lm_head.w"], x,
                                        cfg["layer_norm_epsilon"])
    return params


def _centred_head(head, x, eps):
    """``head [d, V]`` with its columns made orthogonal to the MEAN normed
    residual of the rows ``x [n, t, d]``: what every position shares then
    adds nothing to any token's logit, as under a trained head no token
    is every context's favourite (the configuration file's
    ``assumed.init``: a seeded head gives the stack's token-independent
    part a favourite, and greedy outputs end on it)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def centre(head, x):
        h = ssm_moe_reference._rms(x, 1.0, eps).reshape(-1, x.shape[-1])
        mean = jnp.mean(h, axis=0)
        unit = mean / jnp.linalg.norm(mean)
        w = head.astype(jnp.float32)
        return (w - unit[:, None] * (unit @ w)[None, :]).astype(head.dtype)

    return centre(head, x)


def _layout(cfg):
    """The reference's positional arguments after the tokens."""
    z = _dims(cfg)
    return (z["pattern"], z["heads"], z["kv"], z["H"], z["G"], z["top_k"],
            (cfg["experts_first"], z["held"]), cfg["routed_scaling_factor"])


def _arch(cfg):
    z = _dims(cfg)
    return MambaMoE(
        z["pattern"], z["heads"], z["kv"], z["dh"], z["d"],
        ssm_heads=z["H"], ssm_head_dim=z["P"], ssm_groups=z["G"],
        ssm_state=z["N"], conv_taps=z["taps"], router_width=z["width"],
        top_k=z["top_k"], experts=(cfg["experts_first"], z["held"]),
        route_scale=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"], chunk_size=cfg["chunk_size"],
        eps=cfg["layer_norm_epsilon"])


def serving_engine(params, cfg, registry, geometry):
    import paddle_tpu as pt

    return pt.serving.ServingEngine(
        params, arch=_arch(cfg), registry=registry,
        compute_dtype=cfg["compute_dtype"], **geometry)


def logits(params, tokens, cfg, ties=None, **switches):
    """The reference's logits ``[b, t, V]``, with the rows it cannot
    decide set to zero: a row where, in any routed layer, the margin
    between the last selected expert and the best one left out
    (``ssm_moe_reference._margin``: pairs that differ in a HELD expert
    only) is under the configuration's ``check_undecided_margin`` comes
    back as zeros, which every token satisfies (gap 0), as ``gated_moe``
    does and for its reason (``chipbench/MOE.md``, ``chipbench/SSM.md``).
    How many were left out goes to standard error and to ``undecided``
    below.  ``ties`` (a list) receives the margins, a routed layer
    each."""
    ties = [] if ties is None else ties
    out = ssm_moe_reference.forward(
        params, tokens, *_layout(cfg), eps=cfg["layer_norm_epsilon"],
        ties=ties, **switches)
    if not ties:
        return out
    import jax.numpy as jnp

    left_out = jnp.min(jnp.stack(ties), axis=0) < cfg["check_undecided_margin"]
    undecided.append((int(left_out.sum()), left_out.size))
    print(f"chipbench: ssm_moe: {undecided[-1][0]} of {undecided[-1][1]} "
          f"rows left out of the check as undecided (margin under "
          f"{cfg['check_undecided_margin']})", file=sys.stderr)
    return jnp.where(left_out[..., None], 0.0, out)


# (rows left out, rows) of each call of ``logits``, for whoever asks
undecided = []


def _outside(kind, z):
    """Matmul parameters of one layer of ``kind`` outside its routed
    experts."""
    return sum(rows * cols
               for name, (*_, rows, cols) in _matrices(kind, z).items()
               if not name.startswith("experts_"))


def ssm_moe_sizes(cfg):
    """What ``chipbench/ssm_moe_bytes.py`` asks: the layers of each kind,
    the mixer's geometry and what a slot holds of one Mamba layer, the
    share of the router's experts held, the parameters of ONE expert's
    two matrices, the matmul parameters outside the routed experts
    (streamed once a decode step whatever the routing), the bytes a
    cached position holds and the lanes of an attention layer's query
    heads."""
    z = _dims(cfg)
    count = {k: z["pattern"].count(k) for k in "ME*"}
    outside = z["d"] * z["rows"] + sum(
        count[k] * _outside(k, z) for k in "ME*")
    return {
        "ssm_layers": count["M"], "moe_layers": count["E"],
        "attention_layers": count["*"],
        "ssm_heads": z["H"], "ssm_head_dim": z["P"], "ssm_groups": z["G"],
        "ssm_state": z["N"], "conv_channels": z["conv"], "taps": z["taps"],
        "chunk_size": cfg["chunk_size"],
        "state_bytes": 4 * z["H"] * z["P"] * z["N"],
        "experts_held": z["held"], "router_width": z["width"],
        "top_k": z["top_k"], "d_model": z["d"], "expert_width": z["e"],
        "expert_params": 2 * z["d"] * z["e"],
        "expert_ops_per_row": 4 * z["d"] * z["e"],
        "outside_params": outside,
        "kv_bytes_per_token": count["*"] * 2 * z["kv"] * z["dh"] * 2,
        "query_lanes": z["heads"] * z["dh"],
    }


def sizes(cfg):
    z = _dims(cfg)
    more = ssm_moe_sizes(cfg)
    # a token selects top_k of router_width experts; the held ones get
    # held / width of them: 0.75 experts a routed layer here
    applied = z["top_k"] * z["held"] / z["width"]
    return {
        "d_model": z["d"], "heads": z["heads"], "head_dim": z["dh"],
        "vocab_rows": z["rows"],
        # what a token is multiplied by ON THIS CHIP, in expectation
        "matmul_params": int(more["outside_params"] + more["moe_layers"]
                             * applied * more["expert_params"]),
        "kv_planes": more["attention_layers"],
        "attention_passes": more["attention_layers"],
    }
