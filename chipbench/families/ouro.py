"""The Ouro family (Ouro-1.4B / 2.6B, "LoopLM", arXiv:2510.25741): a
stack of ``num_hidden_layers`` blocks run ``total_ut_steps`` times over
the same weights, a K/V plane of its own for every (pass, layer);
RMSNorm before and after each sub-layer, rotary positions, a gated SiLU
FFN, no biases, the final norm closing every pass, an exit gate.
Configuration keys are those of the published ``config.json``.

The program serves it through ``ServingEngine(params,
arch=LoopedRmsRope(...))`` (``paddle_tpu/serving/arch.py``); the plain
reference is ``ouro_reference.py`` beside this file.  The family serves
only: there is no ``training_program`` (``transformer.build`` has no
such block, and the family's exit-weighted loss has constants the config
does not give), so a training cell is refused by the loader.
``families/__init__.py`` says what each function is for.
"""

# a checkout whose program has no architecture object cannot run this
# family: it fails here, when the family is loaded, before any weight is made
from paddle_tpu.serving.arch import LoopedRmsRope

from . import ouro_reference

_MATRICES = (("att_q", "d", "d"), ("att_k", "d", "d"), ("att_v", "d", "d"),
             ("att_out", "d", "d"), ("ffn_gate", "d", "f"),
             ("ffn_up", "d", "f"), ("ffn_down", "f", "d"))
# norms before a sub-layer have unit gain; those after it carry the
# residual branch's scale (make_params)
_NORMS = (("norm1", "unit"), ("norm2", "branch"), ("norm3", "unit"),
          ("norm4", "branch"))


def make_params(cfg, positions, seed):
    """The engine's parameter dict (``LoopedRmsRope``'s names), in the
    configuration's dtype: normal(0, 0.02) matrices and embedding, zero
    gate bias, unit scales on the norms BEFORE a sub-layer and on the
    final norm, and ``1 / sqrt(2 * layers * passes)`` on the two norms
    AFTER a sub-layer: the residual-branch scaling of GPT-2's and
    Megatron's initialisation, which a sandwich norm can carry only in
    its gain (it cancels any scaling of the projection in front of it).
    With unit gains there, 384 adds of unit-RMS vectors make a randomly
    initialised stack a chaotic map: the bfloat16 and the float32 forward
    of the SAME weights decorrelate (``PERF.md``, PR 28, has the
    readings), which no trained model does, and a comparison with the
    reference then measures the draw, not the program.  Rotary positions
    need no table, so ``positions`` sizes nothing."""
    import jax
    import jax.numpy as jnp

    del positions
    size = {"d": cfg["hidden_size"], "f": cfg["intermediate_size"]}
    d, rows, n_layer = size["d"], cfg["vocab_size"], cfg["num_hidden_layers"]
    dtype = jnp.dtype(cfg["compute_dtype"])
    branch = (2 * n_layer * cfg["total_ut_steps"]) ** -0.5

    @jax.jit
    def init(key):
        keys = iter(jax.random.split(key, len(_MATRICES) * n_layer + 3))

        def normal(*shape):
            return 0.02 * jax.random.normal(next(keys), shape, dtype)

        p = {"tok_emb.w": normal(rows, d), "lm_head.w": normal(d, rows),
             "norm_f.scale": jnp.ones((d,), dtype),
             "exit_gate.w": normal(d, 1),
             "exit_gate.b": jnp.zeros((1,), dtype)}
        for i in range(n_layer):
            for name, rows_, cols in _MATRICES:
                p[f"block{i}_{name}.w"] = normal(size[rows_], size[cols])
            for name, gain in _NORMS:
                p[f"block{i}_{name}.scale"] = jnp.full(
                    (d,), branch if gain == "branch" else 1.0, dtype)
        return p

    # the key is an argument, so one executable serves every seed
    return init(jax.random.PRNGKey(abs(int(seed)) % (2 ** 31 - 1)))


def _arch(cfg):
    return LoopedRmsRope(
        cfg["num_hidden_layers"], cfg["num_attention_heads"],
        cfg["hidden_size"], cfg["total_ut_steps"], eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"],
        early_exit_threshold=cfg["early_exit_threshold"])


def serving_engine(params, cfg, registry, geometry):
    import paddle_tpu as pt

    return pt.serving.ServingEngine(params, arch=_arch(cfg),
                                    registry=registry, **geometry)


def logits(params, tokens, cfg):
    return ouro_reference.logits(
        params, tokens, cfg["num_hidden_layers"], cfg["num_attention_heads"],
        cfg["total_ut_steps"], eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        early_exit_threshold=cfg["early_exit_threshold"])


def sizes(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    planes = cfg["num_hidden_layers"] * cfg["total_ut_steps"]
    return {
        "d_model": d, "heads": cfg["num_attention_heads"],
        "head_dim": cfg["head_dim"], "vocab_rows": cfg["vocab_size"],
        # four attention projections and three FFN matrices a layer,
        # applied once in every pass, and the head once; embedding rows
        # are gathered, norms and the gate are O(d)
        "matmul_params": planes * (4 * d * d + 3 * d * f)
        + d * cfg["vocab_size"],
        "kv_planes": planes, "attention_passes": planes,
    }


def stack_passes(cfg):
    """How often the stack runs over its weights: a sixth answer, which
    only a looped family gives (``loop.stack_busy_share`` takes 1 from a
    family without it)."""
    return cfg["total_ut_steps"]
