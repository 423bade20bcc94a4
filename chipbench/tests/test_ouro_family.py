"""The ``ouro`` family (``families/ouro.py``, ``ouro_reference.py``,
``configs/ouro-2.6b.json``): the sizes shape-only code reads, the byte
arithmetic the cell's geometry rests on, the reference held to the
program's copy, and the serving runner end to end on the CPU at a tiny
size of the family, with the check biting on one pass left out and on
weights of the next precision down."""

import json
import os

import numpy as np
import pytest

from chipbench import families, flops
from chipbench import run as bench_run

CFG = bench_run._read_json(bench_run.HERE, "configs", "ouro-2.6b.json")
MIX = bench_run._read_json(bench_run.HERE, "traffic", "reason_decode.json")
# the published layout at a width the CPU can run: 2 layers x 3 passes
TINY = {"name": "tiny-ouro", "family": "ouro", "head_dim": 128,
        "hidden_size": 256, "intermediate_size": 384,
        "num_attention_heads": 2, "num_hidden_layers": 2,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
        "rope_theta": 1000000, "total_ut_steps": 3,
        "early_exit_threshold": 1, "vocab_size": 256,
        "compute_dtype": "bfloat16", "changed": {}}
SERVE = {"runner": "serve", "chips": 1,
         "engine": {"max_len": 64, "max_slots": 4, "block_tokens": 8,
                    "cache_blocks": 8, "prefix_reuse": True},
         "rate_per_s": 6.0, "schedule_seed": 5,
         "shared_heads": {"count": 0, "tokens": 0, "zipf_s": 1.0},
         "prompt_tail": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                         "min": 2, "max": 20},
         "output": {"dist": "lognormal", "median": 10, "sigma": 0.4,
                    "min": 4, "max": 20},
         "drain_seconds": 60, "warmup_timeout_s": 300,
         "trace_seconds": 1.0,
         # at this size the right program's worst gap is under 0.01 over
         # seeds, fp8-rounded matrices give 0.07-0.19 and a pass left out
         # 0.2-0.3 (the maximum of 256 logits of deviation 0.3 lies 0.9
         # above a token drawn at random)
         "check": {"sample": 4, "logit_margin": 0.03}}


def test_sizes_and_bytes_of_the_published_configuration():
    size = families.sizes(CFG)
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert layer == 51_380_224
    assert size == {"d_model": 2048, "heads": 16, "head_dim": 128,
                    "vocab_rows": 49_152,
                    "matmul_params": 192 * layer + 2048 * 49_152,
                    "kv_planes": 192, "attention_passes": 192}
    assert families.of(CFG).stack_passes(CFG) == 4
    assert size["matmul_params"] == 9_965_666_304
    assert flops.kv_bytes_per_token(CFG) == 1536 * 1024     # 1.5 MiB
    # held: 48 layers once, embedding and head
    held = 48 * layer + 2 * 2048 * 49_152
    assert 2.66e9 < held < 2.67e9
    # the cell's pool: trash + 10 slots x 16 blocks + 16 cached, a block
    # 32 tokens in all 192 planes; with the weights under the chip
    eng = MIX["engine"]
    per_slot = -(-eng["max_len"] // eng["block_tokens"])
    blocks = 1 + eng["max_slots"] * per_slot + eng["cache_blocks"]
    pool = blocks * eng["block_tokens"] * flops.kv_bytes_per_token(CFG)
    assert blocks == 177 and round(pool / 2 ** 30, 2) == 8.30
    chip = flops.peaks("TPU v5 lite")["hbm_bytes"]
    assert 0.75 * chip < pool + 2 * held < 0.98 * chip
    # every request fits a slot
    assert (MIX["prompt_tail"]["max"] + MIX["output"]["max"]
            <= eng["max_len"])
    assert MIX["shared_heads"]["count"] == 0


def test_configuration_says_what_it_is():
    bench = bench_run._read_json(bench_run.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == [] and CFG["changed"] == {}
    assert CFG["source"].startswith(entry["source"])
    assert {"sandwich_norms", "norm_between_passes", "exit_gate",
            "init"} <= set(CFG["assumed"])
    assert len(CFG["layer_types"]) == CFG["num_hidden_layers"] == 48
    assert CFG["hidden_size"] == (CFG["num_attention_heads"]
                                  * CFG["head_dim"])


def test_the_family_serves_and_does_not_train():
    assert families.of(CFG, "serve").__name__ == "chipbench.families.ouro"
    with pytest.raises(SystemExit) as err:
        families.of(CFG, "train")
    assert "does not train" in str(err.value)
    assert "training_program" in str(err.value)


def test_reference_is_the_programs_copy_and_imports_nothing_of_it():
    def body(path):
        text = open(path).read()
        return text[text.index("import functools"):]

    mine = os.path.join(bench_run.HERE, "families", "ouro_reference.py")
    theirs = os.path.join(bench_run.ROOT, "paddle_tpu", "models",
                          "ouro_reference.py")
    assert body(mine) == body(theirs)
    assert "paddle_tpu" not in body(mine)
    assert "import" not in body(mine).replace(
        "import functools\n\nimport jax\nimport jax.numpy as jnp\n", "")


def _cell():
    bench = json.load(open(bench_run.ROOT + "/BENCHMARK.json"))
    return {"name": "tiny-ouro.serve", "chips": 1, "config": TINY,
            "traffic": SERVE, "end_to_end": bench["end_to_end"],
            "per_layer": [m for m in bench["per_layer"] if
                          "ouro2p6b.reason_decode" in m.get("workloads", [])]}


def test_serve_runner_rehearsal_and_what_fails_its_check(monkeypatch):
    from chipbench.runners import serve

    family = families.of(TINY, "serve")
    cell = _cell()
    result = serve.run(cell, seed=2 ** 31 + 28, seconds=1.5, tracer=None)
    facts = result["facts"]
    assert result["correct"], facts["worst_logit_margin"]
    assert result["attempted"] == 9 and result["failed"] == 0
    assert facts["compiled_in_window"] == 0
    assert facts["stats"].get("serving.prefix_hit_rate", 0.0) == 0.0
    # the readers that need no trace read these facts
    facts.update(config=TINY, traffic=SERVE, chips=1, trace=None,
                 trace_window_s=None,
                 peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    got = bench_run.layer_metrics(cell, facts)
    assert {"step.decode_ms", "step.decode_stream_roofline",
            "compile.seconds", "serve.ttft_p90_ms"} <= set(got)
    assert "sched.prefix_hit_share" not in got
    assert not any(k.startswith(("device.", "paged", "loop.")) for k in got)

    # one pass left out of the program: the reference runs all three
    right = family.serving_engine
    monkeypatch.setattr(
        family, "serving_engine", lambda params, cfg, reg, geometry: right(
            params, dict(cfg, total_ut_steps=cfg["total_ut_steps"] - 1),
            reg, geometry))
    wrong = serve.run(cell, seed=2 ** 31 + 28, seconds=1.0, tracer=None)
    assert not wrong["correct"]
    assert wrong["facts"]["worst_logit_margin"] > 4 * 0.03

    # the next precision down: the engine's matrices rounded to fp8
    import jax.numpy as jnp

    def fp8(params, cfg, reg, geometry):
        low = {k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                   if k.endswith(".w") else v) for k, v in params.items()}
        return right(low, cfg, reg, geometry)

    monkeypatch.setattr(family, "serving_engine", fp8)
    wrong = serve.run(cell, seed=2 ** 31 + 28, seconds=1.0, tracer=None)
    assert not wrong["correct"]
    assert wrong["facts"]["worst_logit_margin"] > 1.5 * 0.03


def test_weights_are_seeded_and_named_for_the_engine():
    from paddle_tpu.observability.metrics import MetricsRegistry

    family = families.of(TINY, "serve")
    a = family.make_params(TINY, 64, 2 ** 31 + 5)
    reg = MetricsRegistry()
    eng = family.serving_engine(a, TINY, reg, dict(SERVE["engine"]))
    assert reg.value("serving.kv_planes") == 6
    assert reg.value("serving.stack_passes") == 3
    assert reg.value("serving.kv_bytes_per_token") == (
        flops.kv_bytes_per_token(TINY))
    assert reg.value("serving.kv_pool_bytes") == (
        eng.kv_pool.num_blocks * 8 * flops.kv_bytes_per_token(TINY))
    b = family.make_params(TINY, 64, 2 ** 31 + 5)
    c = family.make_params(TINY, 64, 7)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["block1_ffn_up.w"], c["block1_ffn_up.w"])
    assert len(a) == 5 + 2 * 11
    assert a["block0_ffn_gate.w"].shape == (256, 384)
    assert a["exit_gate.w"].shape == (256, 1)
    assert str(a["lm_head.w"].dtype) == "bfloat16"
    # norms before a sub-layer at 1, after it at 1/sqrt(2 x 2 x 3)
    for name, want in (("norm1", 1.0), ("norm3", 1.0), ("norm_f", 1.0),
                       ("norm2", 12 ** -0.5), ("norm4", 12 ** -0.5)):
        key = name + ".scale" if name == "norm_f" else f"block1_{name}.scale"
        got = np.asarray(a[key], np.float32)
        assert got.min() == got.max() and abs(got[0] - want) < 2e-3, name
