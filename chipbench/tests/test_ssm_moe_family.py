"""The ``ssm_moe`` family (``families/ssm_moe.py``,
``ssm_moe_reference.py``, ``configs/nemotron-3-nano-30b-a3b.json``,
``ssm_moe_bytes.py`` and the six readers PR 51 brought): the sizes
shape-only code reads, the byte arithmetic the cell's geometry rests on,
the configuration against the catalog's keys, the reference held to the
program's copy, the counts of ``ssm_moe_bytes`` against hand counts, the
readers on hand-made facts (a hand-made trace among them), and the
serving runner end to end on the CPU at a tiny size of the family with
the check biting on each line of the mathematics left out."""

import json
import os

import numpy as np
import pytest

from chipbench import families, flops, ssm_moe_bytes
from chipbench import run as bench_run

CFG = bench_run._read_json(bench_run.HERE, "configs",
                           "nemotron-3-nano-30b-a3b.json")
MIX = bench_run._read_json(bench_run.HERE, "traffic", "chat_ssm.json")
PEAK = flops.peaks("TPU v5 lite")
CELL = "nemotron3n.chat_ssm"
GPT = bench_run._read_json(bench_run.HERE, "configs",
                           "cerebras-gpt-1.3b.json")
# the published layout at a width the CPU can run, with its oddness kept:
# an expert width that is not a multiple of 128, fewer groups than heads,
# 2 K/V heads; 4 of 16 experts held (4..7), top 3
TINY = {"name": "tiny-ssm-moe", "family": "ssm_moe", "hidden_size": 48,
        "head_dim": 16, "num_attention_heads": 8, "num_key_value_heads": 2,
        "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
        "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
        "moe_intermediate_size": 40,
        "moe_shared_expert_intermediate_size": 72, "n_routed_experts": 4,
        "router_width": 16, "experts_first": 4, "num_experts_per_tok": 3,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "layer_norm_epsilon": 1e-5, "hybrid_override_pattern": "MEM*EME",
        "time_step_min": 0.001, "time_step_max": 0.1,
        "time_step_floor": 1e-4, "vocab_size": 128,
        "compute_dtype": "bfloat16", "check_undecided_margin": 0.002,
        "expert_bias_tokens": [16, 32]}
SERVE = {"runner": "serve", "chips": 1,
         "engine": {"max_len": 64, "max_slots": 4, "block_tokens": 8,
                    "cache_blocks": 0, "prefix_reuse": False},
         "rate_per_s": 6.0, "schedule_seed": 5,
         "shared_heads": {"count": 0, "tokens": 0, "zipf_s": 1.0},
         "prompt_tail": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                         "min": 2, "max": 24},
         "output": {"dist": "lognormal", "median": 14, "sigma": 0.4,
                    "min": 6, "max": 28},
         "drain_seconds": 60, "warmup_timeout_s": 300,
         "trace_seconds": 1.0,
         "check": {"sample": 4, "logit_margin": 0.05}}
SEED = 2 ** 31 + 51

D = 2688
MAMBA = D * 10304 + 4096 * D            # in and out projections
ATT = D * 4608 + 4096 * D
EXPERT = 2 * D * 1856
ROUTED = D * 128 + 2 * D * 3712         # router and shared expert
OUTSIDE = 23 * MAMBA + 6 * ATT + 23 * ROUTED + D * 16384
STATE = 4 * 64 * 64 * 128


def test_sizes_and_bytes_of_the_configuration_as_it_is_run():
    assert (MAMBA, ATT, EXPERT, ROUTED) == (
        38_707_200, 23_396_352, 9_977_856, 20_299_776)
    size = families.sizes(CFG)
    assert size == {"d_model": D, "heads": 32, "head_dim": 128,
                    "vocab_rows": 16_384,
                    "matmul_params": OUTSIDE + int(23 * 0.75 * EXPERT),
                    "kv_planes": 6, "attention_passes": 6}
    more = families.of(CFG).ssm_moe_sizes(CFG)
    assert more == {
        "ssm_layers": 23, "moe_layers": 23, "attention_layers": 6,
        "ssm_heads": 64, "ssm_head_dim": 64, "ssm_groups": 8,
        "ssm_state": 128, "conv_channels": 6144, "taps": 4,
        "chunk_size": 128, "state_bytes": STATE, "experts_held": 16,
        "router_width": 128, "top_k": 6, "d_model": D, "expert_width": 1856,
        "expert_params": EXPERT, "expert_ops_per_row": 2 * EXPERT,
        "outside_params": OUTSIDE, "kv_bytes_per_token": 6144,
        "query_lanes": 4096}
    assert STATE == 2_097_152
    # everything held: matrices, the table, norms, convolutions, biases
    held = OUTSIDE + 23 * 16 * EXPERT + D * 16384
    small = (52 * D + D + 23 * (6144 * 5 + 3 * 64 + 4096) + 23 * 128)
    assert held + small == CFG["parameters_held"] == 5_258_420_544
    # a decode step streams at most 10.4 GB (every held expert touched):
    # 12.7 ms at the peak rate before any state or K/V
    most = 2 * (OUTSIDE + 23 * 16 * EXPERT)
    assert round(most / 1e9, 2) == 10.43
    assert round(most / PEAK["hbm_bytes_per_s"] * 1e3, 1) == 12.7
    # the cell's geometry: 40 slots of state, the pool as it is stored
    eng = MIX["engine"]
    per_slot = 23 * (STATE + 3 * 6144 * 2)
    assert per_slot == 49_082_368
    blocks = 1 + eng["max_slots"] * (eng["max_len"] // eng["block_tokens"])
    pool = blocks * eng["block_tokens"] * 24_576
    assert blocks == 3201 and round(pool / 1e9, 2) == 2.52
    chip = PEAK["hbm_bytes"]
    total = 2 * CFG["parameters_held"] + eng["max_slots"] * per_slot + pool
    assert 0.25 * chip < 2 * CFG["parameters_held"] < 0.7 * chip
    assert 0.85 * chip < total < 0.95 * chip
    assert (MIX["prompt_tail"]["max"] + MIX["output"]["max"]
            == eng["max_len"] == 2560)
    assert MIX["shared_heads"]["count"] == 0 and not eng["prefix_reuse"]
    assert (MIX["prompt_tail"]["median"], MIX["prompt_tail"]["sigma"],
            MIX["prompt_tail"]["min"]) == (256, 0.8, 32)
    assert (MIX["output"]["median"], MIX["output"]["sigma"],
            MIX["output"]["min"], MIX["output"]["max"]) == (384, 0.5, 64, 1024)


def test_configuration_holds_the_catalogs_keys_and_says_what_it_cut():
    bench = bench_run._read_json(bench_run.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "nemotron-3-nano-30b-a3b")
    assert entry["reduced"] == CFG["reduced"] == ["n_routed_experts",
                                                  "vocab_size"]
    assert CFG["source"].startswith(entry["source"])
    assert len(entry["source"]) <= 200
    # every number of the catalog's config under its key; none of the
    # widths is in the cut
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 52,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
        "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "sliding_window": None,
        "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True}
    assert {k: CFG[k] for k in published} == published
    assert (CFG["n_routed_experts"], CFG["vocab_size"]) == (16, 16384)
    assert CFG["published"]["n_routed_experts"] == CFG["router_width"] == 128
    assert CFG["published"]["vocab_size"] == 8 * CFG["vocab_size"]
    assert len(CFG["hybrid_override_pattern"]) == CFG["num_hidden_layers"]
    assert CFG["experts_first"] == 0 and "eight chips" in CFG["deployment"]
    assert {"rotary", "state_dtype", "groups", "inner_width", "mtp",
            "routing", "expert_bias", "norms", "expert_layout",
            "init"} <= set(CFG["assumed"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-nano-30b-a3b", "chat_ssm", 1)
    assert f"{MIX['rate_per_s']:g} req/s" in cell["why"]
    assert "eighth" in cell["why"]
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    # no reader that counts three matrices an expert, every matmul
    # parameter once a step, or K/V from planes x heads reports here
    assert not listed & {"moe.decode_stream_roofline",
                         "moe.expert_matmul_roofline",
                         "moe.untouched_expert_share",
                         "step.decode_stream_roofline",
                         "hybrid.decode_stream_roofline",
                         "hybrid.recurrent_busy_share",
                         "sched.prefix_hit_share"}
    mine = {"ssm_moe.decode_stream_roofline", "ssm.step_kernel_roofline",
            "ssm.chunk_kernel_roofline", "ssm_moe.expert_matmul_roofline",
            "ssm_moe.untouched_expert_share",
            "ssm_moe.paged_attention_roofline"}
    assert mine | {"tpot_p90_ms", "step.mixer_busy_share",
                   "paged.rows_per_update", "paged.skipped_entry_share",
                   "serve.ttft_p90_ms", "device.idle_share.serve",
                   "compile.seconds"} <= listed
    for name in mine:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "tpot_p90_ms"


def test_the_family_serves_and_does_not_train():
    assert families.of(CFG, "serve").__name__ == "chipbench.families.ssm_moe"
    with pytest.raises(SystemExit) as err:
        families.of(CFG, "train")
    assert "does not train" in str(err.value)


def test_reference_is_the_programs_copy_and_imports_nothing_of_it():
    def body(path):
        text = open(path).read()
        return text[text.index("import functools"):]

    mine = os.path.join(bench_run.HERE, "families", "ssm_moe_reference.py")
    theirs = os.path.join(bench_run.ROOT, "paddle_tpu", "models",
                          "ssm_moe_reference.py")
    assert body(mine) == body(theirs)
    assert "paddle_tpu" not in body(mine)
    assert "import" not in body(mine).replace(
        "import functools\n\nimport jax\nimport jax.numpy as jnp\n", "")


def _stats(decode=None, prefill=None):
    out = {}
    for phase, count in (("decode", decode), ("prefill", prefill)):
        for name, value in (count or {}).items():
            out[f"serving.moe_{name}{{phase={phase}}}"] = float(value)
    return out


# 1000 decode steps of 18 live slots: 13 pairs and 9 experts a layer
DECODE = {"rows": 1000 * 23 * 18, "assignments_held": 1000 * 23 * 13,
          "experts_touched": 1000 * 23 * 9, "expert_visits": 1000 * 23 * 16}
# 300 prefill pieces of 200 real rows: 150 pairs, 16 experts a layer
PREFILL = {"rows": 300 * 23 * 200, "assignments_held": 300 * 23 * 150,
           "experts_touched": 300 * 23 * 16, "expert_visits": 300 * 23 * 16}


def test_ssm_moe_bytes_against_hand_counts():
    from chipbench import moe_bytes

    assert ssm_moe_bytes.sizes(GPT) is None
    # a slot's step in one layer: 2 MiB read, 2 MiB written; decay,
    # update (2) and the read through C (2) a value
    assert ssm_moe_bytes.step(CFG) == (5 * 64 * 64 * 128, 2 * STATE)
    assert ssm_moe_bytes.least_seconds(
        *ssm_moe_bytes.step(CFG), PEAK) == pytest.approx(2 * STATE / 819e9)
    # a 512-row piece in one layer: four chunks of 128; a row scores the
    # rows of its chunk up to itself (8 x 128 lanes of C B^T, 64 x 64 of
    # X), is added to its chunk's state and reads the state before it
    ops, nbytes = ssm_moe_bytes.piece(CFG, 512)
    pairs = 512 * 129 // 2
    assert ops == 2 * pairs * (1024 + 4096) + 4 * 512 * 64 * 64 * 128
    assert nbytes == 2 * STATE + 512 * (6144 + 64) * 2 + 512 * 4096 * 4
    assert ssm_moe_bytes.piece(CFG, 8)[0] == (
        2 * 36 * 5120 + 4 * 8 * 64 * 64 * 128)
    count = moe_bytes.counts(_stats(DECODE), "decode")
    assert ssm_moe_bytes.steps(CFG, count) == 1000
    # a step of 18 live slots that attend 9000 positions and touch 207
    # (expert, layer) pairs
    assert ssm_moe_bytes.decode_step_bytes(CFG, 207, 18, 9000) == (
        2 * (OUTSIDE + 207 * EXPERT) + 18 * 23 * 2 * STATE + 9000 * 6144)
    # one layer's two products: memory-bound at a decode step's 13 pairs
    # over 9 experts, compute-bound past some 240 rows an expert
    read = 9 * 2 * EXPERT / 819e9
    assert ssm_moe_bytes.expert_call_seconds(CFG, 9, 13, PEAK) == (
        pytest.approx(read))
    assert ssm_moe_bytes.expert_call_seconds(CFG, 1, 512, PEAK) == (
        pytest.approx(2 * EXPERT * 512 / 197e12))


def _request(prompt_len, out, first=1.0, finish=2.0):
    return {"prompt_len": prompt_len, "prefix_hit": 0, "out": out,
            "prefill_t0": first - 0.1, "prefill_t1": first,
            "first": first, "finish": finish}


def test_decode_stream_roofline_on_hand_made_facts():
    reader = bench_run.load_reader("ssm_moe.decode_stream_roofline")
    # two requests decode 20 tokens each after their first, over the
    # 1000 steps the counters were summed over: 40 slot-steps
    requests = [_request(600, 21), _request(50, 21)]
    attended = sum((600 + i) + (50 + i) for i in range(1, 21))
    want = (2 * (OUTSIDE + 207 * EXPERT) + (40 * 23 * 2 * STATE
                                            + attended * 6144) / 1000) / 819e9
    facts = {"stats": dict(_stats(DECODE), **{
                 "serving.step_seconds": {"count": 250, "p50": 2 * want}}),
             "decode_chunk": 4, "peak": PEAK, "config": CFG,
             "requests": requests}
    assert reader.read(facts) == pytest.approx(50.0)
    # nothing to read: no histogram, no counters, no peak, another family
    assert reader.read(dict(facts, stats=_stats(DECODE))) is None
    assert reader.read(dict(facts, stats={"serving.step_seconds": {
        "count": 250, "p50": 0.01}})) is None
    assert reader.read({k: v for k, v in facts.items() if k != "peak"}) is None
    assert reader.read(dict(facts, config=GPT)) is None


def test_untouched_expert_share_on_hand_made_facts():
    reader = bench_run.load_reader("ssm_moe.untouched_expert_share")
    assert reader.read({"stats": _stats(DECODE, PREFILL)}) == pytest.approx(
        100 * (1 - 9 / 16))
    assert reader.read({"stats": _stats(None, PREFILL)}) is None
    assert reader.read({"stats": {}}) is None


GROUPED = ('%grouped_matmul.5 = bf16[256,1856]{1,0} custom-call(s32[17] %g, '
           's32[17] %t, s32[17] %o, bf16[256,2688] %x, bf16[16,1856,2688] '
           '%w), custom_call_target="tpu_custom_call"')
STEP = ('%ssm_step.3 = (f32[40,32,128]{2,1,0}, f32[40,32,128,128]{3,2,1,0}) '
        'custom-call(s32[40] %o, f32[40,32,128] %x, f32[40,32,128] %d, '
        'f32[40,128,16] %b, f32[40,32,128,128] %s), '
        'custom_call_target="tpu_custom_call"')
OTHER = ("%fusion.1 = f32[40,32,128] fusion(f32[40,32,128] %ssm_step.3), "
         "kind=kLoop")


def _trace(*ops, busy=1.0):
    return {"busy_s": busy, "ops": {
        f"op{i}": {"calls": calls, "seconds": s, "self": s,
                   "provenance": prov}
        for i, (prov, s, *rest) in enumerate(ops)
        for calls in [rest[0] if rest else 1]}}


def test_expert_matmul_roofline_holds_the_count_to_a_hand_made_trace():
    reader = bench_run.load_reader("ssm_moe.expert_matmul_roofline")
    assert reader.kernels(CFG, MIX) == {"grouped_matmul": (
        "%grouped_matmul", 'custom_call_target="tpu_custom_call"')}
    assert reader.CALLS_A_LAYER == 2
    # 4 decode steps and 2 prefill pieces of 23 routed layers, 2 calls a
    # layer-step
    trace = _trace((GROUPED, 0.30, 4 * 46), (GROUPED.replace(".5", ".9"),
                                            0.10, 2 * 46), (STEP, 0.2, 92))
    requests = [_request(300, 101, first=1.0, finish=2.0),
                dict(_request(400, 1, first=1.5, finish=None),
                     prefill_t0=1.2, prefill_t1=1.5)]
    stats = _stats(DECODE, PREFILL)
    in_window = {"decode": 100, "prefill": 400}
    # 100 positions at 18 rows a step against 400 at 200 rows a piece
    steps = {"decode": 100 / 18, "prefill": 2.0}
    share = {k: v / sum(steps.values()) for k, v in steps.items()}
    each = {"decode": 9 * 2 * EXPERT / 819e9,
            "prefill": 16 * 2 * EXPERT / 819e9}
    least = 138 * sum(share[k] * each[k] for k in share)
    assert reader.least_seconds(CFG, stats, in_window, 138, PEAK) == (
        pytest.approx(least))
    facts = {"trace": trace, "trace_span": (1.0, 2.0), "requests": requests,
             "stats": stats, "config": CFG, "peak": PEAK}
    assert reader.read(facts) == pytest.approx(100.0 * least / 0.40)
    assert reader.read(dict(facts, trace=_trace((STEP, 0.2)))) is None
    assert reader.read(dict(facts, stats={})) is None
    assert reader.read(dict(facts, config=GPT)) is None


def test_step_kernel_roofline_counts_the_spans_slot_steps(monkeypatch):
    import types

    from chipbench import trace_reduce

    reader = bench_run.load_reader("ssm.step_kernel_roofline")
    assert reader.kernels(CFG, MIX) == {"ssm_step": (
        "%ssm_step", 'custom_call_target="tpu_custom_call"')}

    def span(**stats):
        return types.SimpleNamespace(name="serving.decode_chunk",
                                     stats=list(stats.items()))

    profile = types.SimpleNamespace(planes=[types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(events=[
            span(active=18, steps=4, ssm_layers=23),
            span(active=20, steps=4, ssm_layers=23),
            span(active=3, steps=4)])])])       # another program's span
    monkeypatch.setattr(trace_reduce, "load", lambda path: profile)
    slot_steps = (18 + 20) * 4 * 23
    least = slot_steps * 2 * STATE / 819e9
    facts = {"trace": _trace((STEP, 2 * least, 184), (OTHER, 0.5)),
             "trace_path": "x", "config": CFG, "peak": PEAK}
    assert reader.read(facts) == pytest.approx(50.0)
    # only the calls NAMED after the kernel, not a fusion that reads it
    assert reader.read(dict(facts, trace=_trace((OTHER, 0.5)))) is None
    assert reader.read(dict(facts, config=GPT)) is None
    assert reader.read(dict(facts, trace=None)) is None


def test_chunk_roofline_takes_the_scopes_seconds(monkeypatch):
    import types

    from chipbench import trace_reduce

    reader = bench_run.load_reader("ssm.chunk_kernel_roofline")
    assert reader.widths(1024 + 32, 3) == [512, 512, 32]
    assert reader.widths(128, 1) == [128]

    def span(**stats):
        return types.SimpleNamespace(name="serving.prefill",
                                     stats=list(stats.items()))

    profile = types.SimpleNamespace(planes=[types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(events=[
            span(bucket=640, pieces=2, ssm_layers=23),
            span(bucket=32, pieces=1, ssm_layers=23)])])])
    monkeypatch.setattr(trace_reduce, "load", lambda path: profile)
    least = 23 * sum(ssm_moe_bytes.least_seconds(
        *ssm_moe_bytes.piece(CFG, w), PEAK) for w in (512, 128, 32))
    monkeypatch.setattr(reader, "scope_seconds", lambda facts: 4 * least)
    facts = {"trace": _trace((STEP, 0.2)), "trace_path": "x", "config": CFG,
             "peak": PEAK}
    assert reader.read(facts) == pytest.approx(25.0)
    monkeypatch.setattr(reader, "scope_seconds", lambda facts: None)
    assert reader.read(facts) is None
    assert reader.read(dict(facts, config=GPT)) is None


PAGED = ('%paged_attention.7 = bf16[40,16,8,128]{3,2,1,0} custom-call('
         's32[40] %n, bf16[40,16,8,128] %q, bf16[3201,32,8,128] %k), '
         'custom_call_target="tpu_custom_call"')


def test_paged_roofline_counts_what_is_cached_not_what_is_stored(
        monkeypatch):
    import types

    from chipbench import trace_reduce

    reader = bench_run.load_reader("ssm_moe.paged_attention_roofline")
    assert reader.kernels(CFG, MIX) == {"paged_attention": (
        "%paged_attention", 'custom_call_target="tpu_custom_call"')}
    # a decode position that attends n cached positions: 6,144 B of K and
    # V each over the six planes (24,576 are stored and read), 32 query
    # heads of 128 scored and weighted in each plane
    assert ssm_moe_bytes.attention(CFG, 1000) == (
        4 * 6 * 4096 * 1000, 1000 * 6144)
    # one request decodes 20 tokens after its first inside the window
    requests = [_request(600, 21)]
    named = bench_run.load_reader("paged_attention_named_roofline")
    contexts = named.decode_contexts(requests, 1.0, 2.0)
    assert len(contexts) == 20
    least = sum(contexts) * 6144 / 819e9
    facts = {"trace": _trace((PAGED, 4 * least, 120), (STEP, 0.2)),
             "trace_span": (1.0, 2.0), "requests": requests, "config": CFG,
             "peak": PEAK}
    assert reader.read(facts) == pytest.approx(25.0)
    # the program's own count of positions scales the requests'

    def span(**stats):
        return types.SimpleNamespace(name="serving.decode_chunk",
                                     stats=list(stats.items()))

    profile = types.SimpleNamespace(planes=[types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(events=[
            span(active=2, steps=4, ssm_layers=23),
            span(active=1, steps=2, ssm_layers=23)])])])
    monkeypatch.setattr(trace_reduce, "load", lambda path: profile)
    assert reader.read(dict(facts, trace_path="x")) == pytest.approx(12.5)
    # nothing to read: no call of that name, another family, no trace
    assert reader.read(dict(facts, trace=_trace((STEP, 0.2)))) is None
    assert reader.read(dict(facts, config=GPT)) is None
    assert reader.read(dict(facts, trace=None)) is None


def _cell():
    bench = json.load(open(bench_run.ROOT + "/BENCHMARK.json"))
    return {"name": "tiny-ssm-moe.serve", "chips": 1, "config": TINY,
            "traffic": SERVE, "end_to_end": bench["end_to_end"],
            "per_layer": [m for m in bench["per_layer"]
                          if CELL in m.get("workloads", [])]}


@pytest.fixture(scope="module")
def rehearsal():
    from chipbench.runners import serve

    cell = _cell()
    return cell, serve.run(cell, seed=SEED, seconds=1.5, tracer=None)


def test_serve_runner_rehearsal_and_what_the_readers_find(rehearsal):
    cell, result = rehearsal
    facts = result["facts"]
    assert result["correct"], facts["worst_logit_margin"]
    assert result["attempted"] == 9 and result["failed"] == 0
    assert facts["compiled_in_window"] == 0
    facts = dict(facts, config=TINY, traffic=SERVE, chips=1, trace=None,
                 trace_window_s=None,
                 peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    got = bench_run.layer_metrics(cell, facts)
    assert {"step.decode_ms", "ssm_moe.decode_stream_roofline",
            "ssm_moe.untouched_expert_share", "compile.seconds",
            "serve.ttft_p90_ms", "paged.skipped_entry_share",
            "paged.rows_per_update"} <= set(got)
    assert not any(k.startswith(("device.", "ssm.")) or
                   k in ("ssm_moe.expert_matmul_roofline",
                         "ssm_moe.paged_attention_roofline") for k in got)
    assert 0 < got["ssm_moe.decode_stream_roofline"]["value"] < 100
    assert 0 < got["ssm_moe.untouched_expert_share"]["value"] < 100
    assert got["paged.rows_per_update"]["value"] == 4.0
    assert facts["stats"]["serving.moe_rows{phase=decode}"] > 0


@pytest.mark.parametrize("switch", [
    {"skip": False}, {"dt_bias": False}, {"gate_first": False},
    {"group_norm": False}, {"squared": False}, {"route_scaled": False},
    {"tails_every": 8}, {"state_every": 8}], ids=lambda s: next(iter(s)))
def test_the_check_bites_on_each_line_left_out(rehearsal, switch):
    """The reference with one line left out or moved, against the sound
    engine's own tokens: each reads over the limit the sound run is held
    to (the margin's readings at the published widths are the traffic
    file's, from the chip)."""
    import jax.numpy as jnp

    _, result = rehearsal
    family = families.of(TINY, "serve")
    params = family.make_params(TINY, 64, SEED)
    # rows of 0.02: at 7 layers x 48 wide a unit table IS the logits, and
    # a line of a layer moves them by less than bfloat16 does
    params["tok_emb.w"] = params["tok_emb.w"] * (0.02 / family._TABLE_RMS)
    prompt = np.arange(3, 20, dtype=np.int32)
    eng = family.serving_engine(params, TINY, None,
                                dict(SERVE["engine"]))
    full, = eng.generate_many([prompt], max_new_tokens=40)
    padded = jnp.asarray(full)[None]
    sound = np.asarray(family.logits(params, padded, TINY))[0]
    other = np.asarray(family.logits(params, padded, TINY, **switch))[0]
    decided = np.abs(sound).sum(-1) > 0
    at = np.arange(len(prompt) - 1, len(full) - 1)
    at = at[decided[at] & (np.abs(other[at]).sum(-1) > 0)]
    gap = lambda lg: float(np.max(                               # noqa: E731
        lg[at].max(-1) - lg[at, np.asarray(full)[at + 1]]))
    assert gap(sound) <= SERVE["check"]["logit_margin"]
    # at this width the seeded logits are some 0.5 in size and a row
    # sees 57 positions: every line left out is another function of the
    # rows the engine generated (tests/test_ssm_moe.py has each at
    # weights that make it a gap of 0.01; the chip's readings at the
    # published widths are in chipbench/SSM.md)
    assert np.abs(other[at] - sound[at]).max() > 2e-3
    assert result["correct"]


def test_the_seeded_weights_are_the_familys_own():
    """``make_params``: a step inside ``[time_step_min, time_step_max]``
    under the softplus, ``A`` in -[1, 16], ``D`` ones, the closing
    matrices over ``sqrt(layers)``, the routed experts' up matrices held
    transposed, and a router bias that spreads the load."""
    import jax
    import jax.numpy as jnp

    family = families.of(TINY, "serve")
    params = family.make_params(TINY, 64, 5)
    for i in (0, 2, 5):
        step = np.asarray(jax.nn.softplus(
            params[f"block{i}_ssm_dt.b"].astype(jnp.float32)))
        assert (step > 0.0009).all() and (step < 0.11).all()
        A = np.exp(np.asarray(params[f"block{i}_ssm_A_log.w"], np.float32))
        assert (A > 0.98).all() and (A < 16.1).all()
        assert (np.asarray(params[f"block{i}_ssm_D.w"], np.float32) == 1).all()
    assert params["block1_experts_up.w"].shape == (4, 40, 48)
    assert params["block1_experts_down.w"].shape == (4, 40, 48)
    closing = float(jnp.std(params["block0_ssm_out.w"].astype(jnp.float32)))
    opening = float(jnp.std(params["block0_ssm_in.w"].astype(jnp.float32)))
    assert closing == pytest.approx(opening / 7 ** 0.5, rel=0.1)
    bias = [np.asarray(params[f"block{i}_router.bias"], np.float32)
            for i in (1, 4, 6)]
    assert all(b.any() and abs(b.mean()) < 1e-3 for b in bias)
