"""The GPT-2 family (the Cerebras-GPT configurations): pre-LayerNorm
blocks, learned absolute positions, full multi-head attention, GELU FFN,
biases; every layer runs once a token.  Configuration keys are those of
the published ``config.json``: ``n_embd``, ``n_layer``, ``n_head``,
``n_inner``, ``layer_norm_epsilon``.

The plain reference and the weights are ``chipbench/reference.py`` and
``chipbench/weights.py``, as they were; this file holds the two calls
into the program and the size arithmetic.  ``families/__init__.py`` says
what each function is for.
"""

from .. import reference, weights

make_params = weights.make_params


def serving_engine(params, cfg, registry, geometry):
    import paddle_tpu as pt

    return pt.serving.ServingEngine(
        params, cfg["n_layer"], cfg["n_head"], cfg["n_embd"],
        eps=cfg["layer_norm_epsilon"], registry=registry, **geometry)


def training_program(cfg, mix):
    from paddle_tpu.models import transformer

    outs = transformer.build(
        vocab_size=sizes(cfg)["vocab_rows"], n_layer=cfg["n_layer"],
        n_head=cfg["n_head"], d_model=cfg["n_embd"],
        d_ff=cfg["n_inner"], max_len=mix["seq_len"], dropout_rate=0.0,
        dtype=cfg["compute_dtype"], fused_head=True,
        learning_rate=mix["learning_rate"])
    return outs["avg_cost"]


def logits(params, tokens, cfg):
    return reference.logits(params, tokens, cfg["n_layer"], cfg["n_head"],
                            cfg["layer_norm_epsilon"])


def greedy_loss(params, tokens, cfg):
    return reference.greedy_loss(params, tokens, cfg["n_layer"],
                                 cfg["n_head"], cfg["layer_norm_epsilon"])


def sizes(cfg):
    d, f, n_layer = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    rows = int(cfg.get("changed", {}).get("vocab_rows", cfg["vocab_size"]))
    return {
        "d_model": d, "heads": cfg["n_head"],
        "head_dim": d // cfg["n_head"], "vocab_rows": rows,
        # the blocks' four attention projections and two FFN matrices,
        # and the head; embedding rows are gathered, not multiplied, and
        # biases and LayerNorms are O(d)
        "matmul_params": n_layer * (4 * d * d + 2 * d * f) + d * rows,
        "kv_planes": n_layer, "attention_passes": n_layer,
    }
