"""The block-sparse / lightning hybrid family (openbmb MiniCPM-SALA,
``model_type`` ``minicpm_sala``): ``minicpm4`` layers of grouped-query
attention under InfLLM-V2's block selection (arXiv:2509.24663; MiniCPM4,
arXiv:2506.07900) and ``lightning-attn`` layers of Lightning Attention-2
(arXiv:2401.04658), every layer followed by a dense gated-SiLU FFN, the
table, every residual branch and the head's input scaled by the model's
own constants.  Configuration keys are those of the published
``config.json``; ``mixer_types`` lists the layers THIS chip holds
(``first_layer`` is the published index of the first); what the config
has no key for is under ``assumed`` and ``sparse_config`` in the
configuration file.

The program serves it through ``ServingEngine(params,
arch=SparseLightning(...))`` (``paddle_tpu/serving/arch.py``); the plain
reference is ``sparse_lightning_reference.py`` beside this file.  The
family serves only.  ``families/__init__.py`` says what each function is
for; ``sala_sizes`` is what ``chipbench/sala_bytes.py`` asks beside
``sizes``.
"""

import sys

# a checkout whose program has no such architecture cannot run this
# family: it fails here, when the family is loaded, before any weight is made
from paddle_tpu.serving.arch import SparseLightning

from . import sparse_lightning_reference as reference

KINDS = {"minicpm4": "S", "lightning-attn": "L"}


def _dims(cfg):
    sp = cfg["sparse_config"]
    return {"d": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "mixers": "".join(KINDS[m] for m in cfg["mixer_types"]),
            "heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "H": cfg["lightning_nh"], "D": cfg["lightning_head_dim"],
            "rows": cfg["vocab_size"],
            "sparse": {"kernel": sp["kernel_size"],
                       "stride": sp["kernel_stride"],
                       "block": sp["block_size"], "topk": sp["topk"],
                       "init_blocks": sp["init_blocks"],
                       "window_blocks": sp["window_size"] // sp["block_size"],
                       "dense_len": sp["dense_len"]}}


def slopes(cfg):
    """``[H]`` a held ``L`` layer: Lightning Attention-2's ``2 ** (-8 h /
    H)``, ``h = 1 .. H``, times the layer factor ``1 - l / (L - 1) + 1e-5``
    with ``l`` the layer's PUBLISHED index and ``L`` the published depth
    (the configuration file's ``assumed.decay``)."""
    z = _dims(cfg)
    depth = cfg.get("published", cfg)["num_hidden_layers"]
    first = cfg.get("first_layer", 0)
    base = [2.0 ** (-8.0 * h / z["H"]) for h in range(1, z["H"] + 1)]
    return [[s * (1.0 - (first + i) / max(depth - 1, 1) + 1e-5) for s in base]
            for i, kind in enumerate(z["mixers"]) if kind == "L"]


def scales(cfg):
    """``(embed, residual, head)``: ``scale_emb``, ``scale_depth`` over
    the root of the PUBLISHED depth, ``dim_model_base / hidden_size``."""
    depth = cfg.get("published", cfg)["num_hidden_layers"]
    return (float(cfg["scale_emb"]), cfg["scale_depth"] / depth ** 0.5,
            cfg["dim_model_base"] / cfg["hidden_size"])


def layer_shapes(kind, z):
    """``{name: shape}`` of one layer of ``kind`` (``"S"`` or ``"L"``):
    its mixer, its norms and its FFN."""
    d, f = z["d"], z["f"]
    hd, kd, HD = z["heads"] * z["dh"], z["kv"] * z["dh"], z["H"] * z["D"]
    mixer = {"att_q.w": (d, hd), "att_k.w": (d, kd), "att_v.w": (d, kd),
             "att_gate.w": (d, hd), "att_out.w": (hd, d)} if kind == "S" \
        else {"lin_q.w": (d, HD), "lin_k.w": (d, HD), "lin_v.w": (d, HD),
              "lin_gate.w": (d, HD), "lin_out.w": (HD, d),
              "lin_qnorm.scale": (z["D"],), "lin_knorm.scale": (z["D"],),
              "lin_onorm.scale": (z["D"],)}
    return dict(mixer, **{
        "norm1.scale": (d,), "norm2.scale": (d,), "ffn_gate.w": (d, f),
        "ffn_up.w": (d, f), "ffn_down.w": (f, d)})


def shapes(cfg):
    """``{parameter name: shape}`` of the whole configuration."""
    z = _dims(cfg)
    out = {"tok_emb.w": (z["rows"], z["d"]), "lm_head.w": (z["d"], z["rows"]),
           "norm_f.scale": (z["d"],)}
    for i, kind in enumerate(z["mixers"]):
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
    """The engine's parameter dict (``SparseLightning``'s names), in the
    configuration's dtype, from ``--seed``, in one jitted call (the
    configuration file's ``assumed.init``): normal(0, 0.02) matrices and
    head, the ``S`` layers' ``W_q`` and ``W_k`` wider by
    ``sparse_qk_gain``, a table whose rows have RMS 1 AFTER ``scale_emb``,
    unit gains; then the head centred on the reference's own forward over
    ``centre_tokens`` uniform ids.  No layer needs a table of positions."""
    import jax
    import jax.numpy as jnp

    del positions
    dtype = jnp.dtype(cfg["compute_dtype"])
    all_shapes = shapes(cfg)
    qk = float(cfg.get("sparse_qk_gain", 1.0))

    @jax.jit
    def init(key):
        keys = iter(jax.random.split(key, len(all_shapes) + 1))
        p = {}
        for name, shape in all_shapes.items():
            k = next(keys)
            kind = name.split("_", 1)[-1]
            if kind.endswith(".scale"):
                p[name] = jnp.ones(shape, dtype)
                continue
            gain = (1.0 / cfg["scale_emb"] if name == "tok_emb.w"
                    else 0.02 * qk if kind in ("att_q.w", "att_k.w")
                    else 0.02)
            p[name] = (gain * jax.random.normal(k, shape, jnp.float32)
                       ).astype(dtype)
        return p, jax.random.randint(
            next(keys), (cfg["centre_tokens"],), 0, cfg["vocab_size"])

    # the key is an argument, so one executable serves every seed
    params, tokens = init(jax.random.PRNGKey(abs(int(seed)) % (2 ** 31 - 1)))
    x = jnp.concatenate([rows for *_, rows in reference.trunk(
        params, tokens, *_layout(cfg), **_how(cfg))])
    params["lm_head.w"] = _centred_head(params["lm_head.w"], x,
                                        cfg["rms_norm_eps"])
    return params


def _centred_head(head, x, eps):
    """``head [d, V]`` with its columns made orthogonal to the MEAN normed
    residual of the rows ``x [n, d]`` (``ssm_moe._centred_head``'s
    settling: what every position shares then adds nothing to any token's
    logit, and greedy outputs do not end on one favourite)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def centre(head, x):
        h = reference._rms(x, 1.0, eps)
        mean = jnp.mean(h, axis=0)
        unit = mean / jnp.linalg.norm(mean)
        w = head.astype(jnp.float32)
        return (w - unit[:, None] * (unit @ w)[None, :]).astype(head.dtype)

    return centre(head, x)


def _layout(cfg):
    """The reference's positional arguments after the tokens."""
    z = _dims(cfg)
    return (z["mixers"], slopes(cfg), z["heads"], z["kv"], z["H"],
            z["sparse"])


def _how(cfg):
    """The reference's keywords that the configuration decides."""
    embed, residual, _ = scales(cfg)
    return dict(embed_scale=embed, residual_scale=residual,
                eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]))


def _arch(cfg):
    z = _dims(cfg)
    embed, residual, head = scales(cfg)
    return SparseLightning(
        z["mixers"], z["heads"], z["kv"], z["dh"], z["d"],
        lin_heads=z["H"], lin_head_dim=z["D"], slopes=slopes(cfg),
        sparse=z["sparse"], embed_scale=embed, residual_scale=residual,
        head_scale=head, rope_theta=float(cfg["rope_theta"]),
        eps=cfg["rms_norm_eps"])


def serving_engine(params, cfg, registry, geometry):
    import paddle_tpu as pt

    return pt.serving.ServingEngine(
        params, arch=_arch(cfg), registry=registry,
        compute_dtype=cfg["compute_dtype"], **geometry)


def logits(params, tokens, cfg, ties=None, **switches):
    """The reference's logits ``[b, t, V]`` (a NumPy array; ``b`` is 1:
    the check compares one sequence a call), with the rows it cannot
    decide set to zero, as the routed families do it for experts
    (``chipbench/MOE.md``): a row whose ``topk``-th selected block, in any
    ``S`` layer and for either K/V head, scores within the
    configuration's ``check_undecided_margin`` (relative) of the best
    block left out comes back as zeros, which every token satisfies (gap
    0): the engine's bfloat16 rows may select the other, and a block
    swapped moves a logit as a fault does.  How many were left out goes
    to standard error and to ``undecided`` below.  At a margin of 0
    nothing is left out.  Rows before ``check_rows_from`` (where the
    sequence is longer than that) are not put through the head and stay
    zeros: the cell's shared documents are that long, every compared row
    lies past them, and 132,352 rows of 73,448 float32 logits would be
    38.9 GB.  ``ties`` (a list) receives ``(layer, first row, gap)`` a
    block of query rows; ``switches`` are
    ``sparse_lightning_reference.trunk``'s."""
    import numpy as np

    margin = cfg.get("check_undecided_margin", 0.0)
    if margin and ties is None:
        ties = []
    t = np.shape(tokens)[1]
    rows_from = cfg.get("check_rows_from", 0)
    rows_from = rows_from if t > rows_from else 0
    _, _, head_scale = scales(cfg)
    how = {**_how(cfg), "head_scale": head_scale, **switches}
    out = reference.forward(params, tokens, *_layout(cfg),
                            rows_from=rows_from, ties=ties, **how)
    if not ties or not margin:
        return out
    left_out = least_gaps(ties, t) < margin
    # the rows before rows_from are zeros already and nobody compares
    # them: writing them would touch the whole array's pages
    left_out[:rows_from] = False
    undecided.append((int(left_out[rows_from:].sum()), t - rows_from))
    print(f"chipbench: sparse_lightning: {undecided[-1][0]} of "
          f"{undecided[-1][1]} rows left out of the check as undecided "
          f"(the topk-th block within {margin} of the next)",
          file=sys.stderr)
    out[-1][left_out] = 0.0
    return out


def least_gaps(ties, rows):
    """``[rows]`` float32: each row's least ``gap`` over the ``(layer,
    first row, gap)`` entries the reference's ``ties`` list received
    (``inf`` for a row no sparse layer selected for)."""
    import numpy as np

    least = np.full((rows,), np.inf, np.float32)
    for _layer, first, gap in ties:
        gap = np.asarray(gap)
        at = slice(first, first + len(gap))
        least[at] = np.minimum(least[at], gap)
    return least


# the reference with ONE line changed, by name: the switches of
# ``sparse_lightning_reference.trunk`` and the selection's sizes.  ``HIT``
# stands for the position of the hit (a shared head's length), ``OTHER``
# for the lightning layers' states after ANOTHER head's tokens
CHECK_VARIANTS = {
    "selection_left_out": {"select": False},
    "topk_32": {"sparse_config": {"topk": 32}},
    "window_blocks_left_out": {"sparse_config": {"window_size": 64}},
    "init_block_left_out": {"sparse_config": {"init_blocks": 0}},
    "straddling_rows_from_the_shared_chain": {"straddle": "HIT"},
    "scores_not_summed_over_the_group": {"group_sum": False},
    "rotary_on_a_sparse_layer": {"sparse_rope": True},
    "states_zeroed_at_the_hit": {"lost": "HIT"},
    "another_documents_states_restored": {"inject": "OTHER"},
    "decay_left_out": {"decay": False},
    "lightning_rotary_left_out": {"lin_rope": False},
    "sparse_gate_left_out": {"sparse_gate": False},
    "lightning_gate_left_out": {"lin_gate": False},
    "scale_depth_left_out": {"residual_scale": 1.0},
    "head_scale_left_out": {"head_scale": 1.0},
}


def check_variants(cfg, heads):
    """What ``benchmarks/check_walk.py`` reads beside the sound
    reference: ``{name: resolve}``, ``resolve(params, prompt) ->
    (configuration, switches of logits())`` with ``HIT`` and ``OTHER``
    made the request's (``heads``: the traffic's shared heads, of which
    ``prompt`` starts with one) and a size's change made the
    configuration's."""
    import numpy as np

    hit = len(heads[0]) if len(heads) else 0

    def resolver(switches):
        def resolve(params, prompt):
            out, cfg_ = dict(switches), cfg
            sizes = out.pop("sparse_config", None)
            if sizes:
                cfg_ = dict(cfg, sparse_config=dict(cfg["sparse_config"],
                                                    **sizes))
            if out.get("lost") == "HIT":
                out["lost"] = (hit,)
            if out.get("straddle") == "HIT":
                out["straddle"] = hit
            if out.get("inject") == "OTHER":
                other = next(h for h in heads
                             if not np.array_equal(h, prompt[:hit]))
                states = []
                reference.trunk(
                    params, np.concatenate([other, prompt[hit:hit + 1]]),
                    *_layout(cfg_), rows_from=hit, capture=(hit, states),
                    **_how(cfg_))
                out["inject"] = (hit, states)
            return cfg_, out
        return resolve

    return {name: resolver(s) for name, s in CHECK_VARIANTS.items()}


# (rows left out, rows) of each call of ``logits``, for whoever asks
undecided = []


def sala_sizes(cfg):
    """What ``chipbench/sala_bytes.py`` asks: the layers of each kind,
    the selection's sizes, the geometry of both mixers, what a slot holds
    of an ``L`` layer, what a cached position and a compressed row hold,
    and the matmul parameters a decode step streams."""
    z = _dims(cfg)
    n_s, n_l = z["mixers"].count("S"), z["mixers"].count("L")
    matmul = z["d"] * z["rows"] + sum(
        shape[0] * shape[1]
        for kind in z["mixers"]
        for shape in layer_shapes(kind, z).values() if len(shape) == 2)
    return dict(
        z["sparse"], sparse_layers=n_s, lightning_layers=n_l,
        heads=z["heads"], kv_heads=z["kv"], head_dim=z["dh"],
        lin_heads=z["H"], lin_head_dim=z["D"],
        state_bytes=4 * z["H"] * z["D"] * z["D"],
        selected_blocks=(z["sparse"]["init_blocks"] + z["sparse"]["topk"]
                         + z["sparse"]["window_blocks"]),
        matmul_params=matmul, d_model=z["d"])


def sizes(cfg):
    z = _dims(cfg)
    more = sala_sizes(cfg)
    return {
        "d_model": z["d"], "heads": z["heads"], "head_dim": z["dh"],
        "vocab_rows": z["rows"], "matmul_params": more["matmul_params"],
        "kv_planes": more["sparse_layers"],
        "attention_passes": more["sparse_layers"],
    }
