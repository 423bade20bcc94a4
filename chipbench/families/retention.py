"""The power-retention family (Brumby-14B-Base, ``model_type``
``brumby``: Qwen3's pre-normed block with its attention replaced by
power retention, arXiv:2507.04239): every layer's mixer holds a STATE of
fixed size a slot a K/V head and no K/V plane; 40 query heads read the
state of their K/V head's group of five; per-head RMSNorm of q and k,
rotary positions over all lanes, one log-sigmoid gate a K/V head; a
gated SiLU FFN; an untied head.  Configuration keys are those of the
published ``config.json``; what it has no key for is under ``assumed``
in the configuration file.

The program serves it through ``ServingEngine(params,
arch=PowerRetention(...))`` (``paddle_tpu/serving/arch.py``); the plain
reference is ``retention_reference.py`` beside this file (the quadratic
form: no features, no state).  The family serves only.
``families/__init__.py`` says what each function is for;
``retention_sizes`` is what the readers of the state ask beside
``sizes`` (``chipbench/retention_bytes.py``).
"""

import numpy as np

# a checkout whose program has no such architecture cannot run this
# family: it fails here, when the family is loaded, before any weight is made
from paddle_tpu.serving.arch import PowerRetention

from . import retention_reference


def _dims(cfg):
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "f": cfg["intermediate_size"], "rows": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"]}


def _matrices(z):
    """{name: shape} of the matmul matrices of one layer."""
    d, dh = z["d"], z["dh"]
    return {"att_q": (d, z["h"] * dh), "att_k": (d, z["kv"] * dh),
            "att_v": (d, z["kv"] * dh), "att_gate": (d, z["kv"]),
            "att_out": (z["h"] * dh, d), "ffn_gate": (d, z["f"]),
            "ffn_up": (d, z["f"]), "ffn_down": (z["f"], d)}


def make_params(cfg, positions, seed):
    """The engine's parameter dict (``PowerRetention``'s names), in the
    configuration's dtype, from ``--seed``: normal(0, 0.02) matrices,
    table and head, unit gains, and the gate's bias ``log(H - 1)`` with
    the horizon ``H`` drawn log-uniformly between the configuration's
    ``gate_horizons`` a K/V head a layer (``sigmoid`` = 1 - 1 / H), all
    in one jitted call.  Rotary positions need no table, so
    ``positions`` sizes nothing."""
    import jax
    import jax.numpy as jnp

    del positions
    z = _dims(cfg)
    dtype = jnp.dtype(cfg["compute_dtype"])
    std = cfg.get("initializer_range", 0.02)
    lo, hi = (float(np.log(v)) for v in cfg["gate_horizons"])

    @jax.jit
    def init(key):
        keys = iter(jax.random.split(key, 10 * z["layers"] + 2))

        def normal(*shape):
            return std * jax.random.normal(next(keys), shape, dtype)

        d = z["d"]
        p = {"tok_emb.w": normal(z["rows"], d),
             "lm_head.w": normal(d, z["rows"]),
             "norm_f.scale": jnp.ones((d,), dtype)}
        for i in range(z["layers"]):
            b = f"block{i}_"
            for name, shape in _matrices(z).items():
                p[b + name + ".w"] = normal(*shape)
            horizon = jnp.exp(jax.random.uniform(
                next(keys), (z["kv"],), jnp.float32, lo, hi))
            p[b + "att_gate.b"] = jnp.log(horizon - 1.0).astype(dtype)
            for name, n in (("norm1", d), ("norm2", d),
                            ("att_qnorm", z["dh"]), ("att_knorm", z["dh"])):
                p[b + name + ".scale"] = jnp.ones((n,), dtype)
        return p

    # the key is an argument, so one executable serves every seed
    return init(jax.random.PRNGKey(abs(int(seed)) % (2 ** 31 - 1)))


def _layout(cfg):
    """The reference's positional arguments after the tokens."""
    z = _dims(cfg)
    return (z["layers"], z["h"], z["kv"], float(cfg["rope_theta"]))


def _arch(cfg):
    z = _dims(cfg)
    return PowerRetention(
        z["layers"], z["h"], z["kv"], z["d"], z["dh"], z["f"],
        degree=cfg["retention_degree"], eps=cfg["retention_eps"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"])


def serving_engine(params, cfg, registry, geometry):
    import paddle_tpu as pt

    return pt.serving.ServingEngine(
        params, arch=_arch(cfg), registry=registry,
        compute_dtype=cfg["compute_dtype"], **geometry)


# rows the reference computes are rounded up to this (one compile a
# length class, and ``retention_reference.QUERY_BLOCK`` divides it)
ROW_BLOCK = 1024


def logits(params, tokens, cfg, **switches):
    """The reference's logits ``[b, t, V]`` float32, a HOST array: the
    rows up to the last token that is not the padding's 0 (rounded up to
    ``ROW_BLOCK``; the mixer is causal, so what follows changes nothing
    before it) are computed, the head a block of vocabulary rows at a
    time and fetched block by block, and the rows past them are zeros:
    9,216 rows of 151,936 float32 logits are 5.6 GB, which the device
    does not hold beside the weights and the check does not read (it
    reads the rows that predict a request's own output)."""
    tokens = np.asarray(tokens)
    b, t = tokens.shape
    used = int(np.max(np.nonzero(tokens.any(axis=0))[0], initial=0)) + 1
    rows = min(t, -(-used // ROW_BLOCK) * ROW_BLOCK)
    x = retention_reference.trunk(
        params, tokens[:, :rows], *_layout(cfg), eps=cfg["retention_eps"],
        norm_eps=cfg["rms_norm_eps"],
        **dict({"degree": cfg["retention_degree"]}, **switches))
    out = np.zeros((b, t, cfg["vocab_size"]), np.float32)
    at = 0
    for block in retention_reference.head_blocks(params, x,
                                                 cfg["rms_norm_eps"]):
        out[:, :rows, at:at + block.shape[-1]] = np.asarray(block)
        at += block.shape[-1]
    return out


def sizes(cfg):
    z = _dims(cfg)
    layer = sum(rows * cols for rows, cols in _matrices(z).values())
    return {
        "d_model": z["d"], "heads": z["h"], "head_dim": z["dh"],
        "vocab_rows": z["rows"],
        # every layer's matrices and the head (the table's rows are
        # gathered): 10 x 330,342,400 + 777,912,320 here
        "matmul_params": z["layers"] * layer + z["d"] * z["rows"],
        "kv_planes": 0, "attention_passes": z["layers"],
        "state_bytes_per_slot": (z["layers"]
                                 * retention_sizes(cfg)["state_bytes"]),
    }


def retention_sizes(cfg):
    """What the readers of the state ask (``chipbench/retention_bytes.py``):
    the layers, the K/V heads that hold a state and the query heads that
    read it, the lanes of a head, the features a head's state holds (the
    upper triangle of its lanes: 8,256 at 128, whatever the layout
    stores) and the bytes of ONE slot's state in ONE layer (``S`` and
    ``z`` in the configuration's ``state_dtype``)."""
    z = _dims(cfg)
    rows = z["dh"] * (z["dh"] + 1) // 2
    itemsize = np.dtype(cfg["state_dtype"]).itemsize
    return {"layers": z["layers"], "kv_heads": z["kv"], "heads": z["h"],
            "head_dim": z["dh"], "state_rows": rows,
            "state_bytes": z["kv"] * (rows * z["dh"] + rows) * itemsize}
