"""The ``latent_moe`` family (``families/latent_moe.py``,
``latent_moe_reference.py``, ``configs/deepseek-v2-lite.json``,
``traffic/doc_qa_8k.json``, ``latent_bytes.py`` and the readers
``mla.decode_stream_roofline``, ``mla.latent_attention_roofline`` and
``paged.shared_entry_share``): the sizes shape-only code reads, the byte
arithmetic the cell's geometry rests on, the reference held to the
program's copy, ``latent_bytes`` against hand counts, the program's
counters held to it, the readers on hand-made facts (a hand-made trace
among them, and "nothing to read: nothing returned"), and the serving
runner end to end on the CPU at a tiny size of the family WITH shared
heads and a prefix hit, the check biting on a weakened variant."""

import json
import os

import numpy as np
import pytest

from chipbench import families, flops, latent_bytes, moe_bytes
from chipbench import run as bench_run

CFG = bench_run._read_json(bench_run.HERE, "configs", "deepseek-v2-lite.json")
MIX = bench_run._read_json(bench_run.HERE, "traffic", "doc_qa_8k.json")
PEAK = flops.peaks("TPU v5 lite")
CELL = "dsv2lite.doc_qa_8k"
GPT = bench_run._read_json(bench_run.HERE, "configs",
                           "cerebras-gpt-1.3b.json")
TRINITY = bench_run._read_json(bench_run.HERE, "configs",
                               "trinity-large-preview.json")
# the published layout at a width the CPU can run: 4 heads of 16 | 8
# query lanes and 16 value lanes over a latent of 32, a dense layer and
# three routed ones, 4 of 16 experts held (4..7), top 3, YaRN factor 4
# over an original 16
TINY = {"name": "tiny-latent-moe", "family": "latent_moe", "hidden_size": 64,
        "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 24,
        "n_shared_experts": 2, "n_routed_experts": 4, "router_width": 16,
        "experts_first": 4, "num_experts_per_tok": 3,
        "num_hidden_layers": 4, "first_k_dense_replace": 1,
        "routed_scaling_factor": 1.0, "rms_norm_eps": 1e-6,
        "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                         "mscale": 0.707, "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 16,
                         "type": "yarn"},
        "vocab_size": 256, "compute_dtype": "bfloat16",
        # 1 / sqrt(width): at 0.02 a width of 64 gives scores so small
        # that attention is a plain mean and no line of it can be missed
        "initializer_range": 0.125, "check_undecided_margin": 0.0}
SERVE = {"runner": "serve", "chips": 1,
         "engine": {"max_len": 96, "max_slots": 4, "block_tokens": 8,
                    "cache_blocks": 24, "prefix_reuse": True},
         "rate_per_s": 6.0, "schedule_seed": 5,
         "shared_heads": {"count": 2, "tokens": 40, "zipf_s": 1.0},
         "prompt_tail": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                         "min": 2, "max": 24},
         "output": {"dist": "lognormal", "median": 14, "sigma": 0.4,
                    "min": 6, "max": 28},
         "drain_seconds": 60, "warmup_timeout_s": 300,
         "trace_seconds": 1.0,
         # at this size the right program's worst gap is 0.0 to 0.016 over
         # seeds; fp8 matrices read 0.27, the routed part left out 0.25,
         # the weights renormalised 0.30, mscale left out 0.36, the
         # latent's norm 1.05, plain theta 1.65, the rotary key 2.27
         "check": {"sample": 4, "logit_margin": 0.05}}
SEED = 2 ** 31 + 40

ATT = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
EXPERT = 3 * 2048 * 1408
ROUTED_OUTSIDE = ATT + 3 * 2048 * 2816 + 2048 * 64
OUTSIDE = (ATT + 3 * 2048 * 10944) + 26 * ROUTED_OUTSIDE + 2048 * 25600


def test_sizes_and_bytes_of_the_configuration_as_it_is_run():
    assert (ATT, EXPERT, ROUTED_OUTSIDE) == (13_762_560, 8_650_752,
                                             31_195_136)
    size = families.sizes(CFG)
    assert size == {"d_model": 2048, "heads": 16, "head_dim": 192,
                    "vocab_rows": 25_600,
                    "matmul_params": OUTSIDE + 26 * 3 * EXPERT // 2,
                    "kv_planes": 27, "attention_passes": 27}
    family = families.of(CFG)
    assert family.moe_sizes(CFG) == {
        "moe_layers": 26, "experts_held": 16, "router_width": 64, "top_k": 6,
        "expert_params": EXPERT, "expert_ops_per_row": 2 * EXPERT,
        "outside_params": OUTSIDE}
    assert family.latent_sizes(CFG) == {
        "planes": 27, "values_per_position": 576, "heads": 16,
        "value_lanes": 512}
    # K and V planes of heads are not what this model caches: the family
    # gives no hybrid_sizes, so moe_bytes and the readers built on it
    # refuse it (PERF.md, Open questions)
    assert not hasattr(family, "hybrid_sizes")
    with pytest.raises(SystemExit):
        moe_bytes.sizes(CFG)
    # everything held: the experts, what is outside them, the table
    held = OUTSIDE + 26 * 16 * EXPERT + 2048 * 25_600
    assert held == 4_595_646_464
    assert round(2 * held / 2 ** 30, 2) == 8.56
    # a decode step streams at most 9.09 GB of weights, 11.1 ms at the
    # peak rate: 1.89 GB outside the routed experts and the 416 held ones
    most = 2 * (OUTSIDE + 416 * EXPERT)
    assert round(most / 1e9, 2) == 9.09
    assert round(most / PEAK["hbm_bytes_per_s"] * 1e3, 1) == 11.1
    assert round(2 * OUTSIDE / 1e9, 2) == 1.89
    assert latent_bytes.position_bytes(CFG) == 1152
    # the cell's pool: trash + 12 slots x 288 blocks + 1152 cached, 27
    # planes of 32 x 640 lanes
    eng = MIX["engine"]
    per_slot = eng["max_len"] // eng["block_tokens"]
    blocks = 1 + eng["max_slots"] * per_slot + eng["cache_blocks"]
    block = 27 * 32 * 640 * 2
    assert (per_slot, blocks, block) == (288, 4609, 1_105_920)
    assert round(blocks * block / 2 ** 30, 2) == 4.75
    chip = PEAK["hbm_bytes"]
    assert 0.25 * chip < 2 * held < 0.6 * chip       # the floor, by weights
    assert 0.8 * chip < 2 * held + blocks * block < 0.9 * chip
    heads = MIX["shared_heads"]
    assert (heads["count"], heads["tokens"]) == (4, 8192)
    assert (heads["tokens"] + MIX["prompt_tail"]["max"]
            + MIX["output"]["max"]) <= eng["max_len"]
    assert eng["cache_blocks"] >= heads["count"] * (
        heads["tokens"] // eng["block_tokens"])
    assert eng["prefix_reuse"] and MIX["schedule_seed"] == 20260940
    # sixteen steps a chunk, so that what the host adds a chunk is a
    # fortieth of it and not a tenth (PERF.md, PR 40); the shortest
    # output still spans four chunks
    assert eng["decode_chunk"] == 16
    assert MIX["output"]["min"] >= 4 * eng["decode_chunk"]


def test_configuration_holds_the_catalogs_keys_and_says_what_it_cut():
    bench = bench_run._read_json(bench_run.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "deepseek-v2-lite")
    cut = ["n_routed_experts", "vocab_size"]
    assert entry["reduced"] == cut == CFG["reduced"]
    assert CFG["source"].startswith(entry["source"])
    assert len(entry["source"]) <= 200
    published = {
        "attention_bias": False, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10944, "kv_lora_rank": 512,
        "max_position_embeddings": 163840, "model_type": "deepseek_v2",
        "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 2, "norm_topk_prob": False,
        "num_attention_heads": 16, "num_experts_per_tok": 6,
        "num_hidden_layers": 27, "num_key_value_heads": 16,
        "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 0.707, "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 1,
        "scoring_func": "softmax", "seq_aux": True,
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "greedy", "v_head_dim": 128}
    assert {k: CFG[k] for k in published} == published
    assert (CFG["n_routed_experts"], CFG["vocab_size"]) == (16, 25600)
    assert CFG["published"]["n_routed_experts"] == CFG["router_width"] == 64
    assert CFG["published"]["vocab_size"] == 4 * CFG["vocab_size"]
    assert CFG["experts_first"] == 0 and "four chips" in CFG["deployment"]
    assert "all 27 layers" in CFG["deployment"]
    assert {"rotary_convention", "routing_precision", "yarn", "eps",
            "experts_held", "init", "routing_load"} <= set(CFG["assumed"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek-v2-lite", "doc_qa_8k", 1)
    assert f"{MIX['rate_per_s']:g} req/s" in cell["why"]
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    # no reader that counts the cache as K and V planes of heads reports
    # in the cell
    assert not listed & {"step.decode_stream_roofline",
                         "hybrid.decode_stream_roofline",
                         "moe.decode_stream_roofline",
                         "moe.expert_matmul_roofline",
                         "moe.untouched_expert_share",
                         "paged_attention_roofline",
                         "paged_attention_named_roofline",
                         "paged_attention_window_roofline"}
    assert {"mla.decode_stream_roofline", "mla.latent_attention_roofline",
            "paged.shared_entry_share", "sched.prefix_hit_share", "tpot_p90_ms", "serve_tokens_per_s",
            "paged.rows_per_update", "paged.skipped_entry_share",
            "serve.ttft_p90_ms", "device.idle_share.serve",
            "step.attention_busy_share"} <= listed
    for name in ("mla.decode_stream_roofline",
                 "mla.latent_attention_roofline", "paged.shared_entry_share"):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
        reader = bench_run.load_reader(name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.SOURCE,
                reader.MOVES) == (name, metric["unit"], metric["layer"],
                                  metric["source"], metric["moves"])


def test_the_family_serves_and_does_not_train():
    assert families.of(CFG, "serve").__name__ == (
        "chipbench.families.latent_moe")
    with pytest.raises(SystemExit) as err:
        families.of(CFG, "train")
    assert "does not train" in str(err.value)


def test_reference_is_the_programs_copy_and_imports_nothing_of_it():
    def body(path):
        text = open(path).read()
        return text[text.index("import functools"):]

    mine = os.path.join(bench_run.HERE, "families", "latent_moe_reference.py")
    theirs = os.path.join(bench_run.ROOT, "paddle_tpu", "models",
                          "latent_moe_reference.py")
    assert body(mine) == body(theirs)
    assert "paddle_tpu" not in body(mine)
    assert "import" not in body(mine).replace(
        "import functools\nimport math\n\nimport jax\nimport jax.numpy as "
        "jnp\nimport numpy as np\n", "")


def _stats(decode=None, **more):
    out = dict(more)
    for name, value in (decode or {}).items():
        out[f"serving.moe_{name}{{phase=decode}}"] = float(value)
    return out


# 1000 decode steps of 10 live slots: 9 experts of 16 a layer
DECODE = {"rows": 1000 * 26 * 10, "assignments_held": 1000 * 26 * 15,
          "experts_touched": 1000 * 26 * 9, "expert_visits": 1000 * 26 * 16}


def test_latent_bytes_against_hand_counts():
    assert latent_bytes.sizes(GPT) is None
    assert latent_bytes.sizes(TRINITY) is None
    size = latent_bytes.sizes(CFG)
    assert size["values_per_position"] == 576 and size["experts_held"] == 16
    # two decode positions at contexts 8300 and 9000: 27 planes of 576
    # values a position, 16 query rows of 576 + 512 lanes each
    ops, nbytes = latent_bytes.attended(CFG, [8300, 9000])
    assert nbytes == 27 * 17_300 * 1152
    assert ops == 2 * 16 * 1088 * 27 * 17_300
    # memory-bound: 0.657 ms of reading against 0.083 of multiplying
    assert latent_bytes.least_seconds(CFG, [8300, 9000], PEAK) == (
        pytest.approx(nbytes / 819e9))
    assert ops / 197e12 < nbytes / 819e9 / 7
    # ... whatever the pool stores: the count reads sizes, not shapes
    assert latent_bytes.position_bytes(CFG) == 576 * 2
    # the routing counters come through moe_bytes.counts, which asks no
    # sizes of the family
    count = moe_bytes.counts(_stats(DECODE), "decode")
    assert (count["touched"], count["visits"]) == (234_000, 416_000)
    # a step of two slots that touched 234 (expert, layer) pairs
    assert latent_bytes.decode_step_bytes(CFG, 234, [8300, 9000], 1) == (
        2 * (OUTSIDE + 234 * EXPERT) + nbytes)
    assert latent_bytes.decode_step_bytes(CFG, 234, [8300, 9000], 2) == (
        2 * (OUTSIDE + 234 * EXPERT) + nbytes / 2)


def _request(prompt_len, out, first=1.0, finish=2.0):
    return {"prompt_len": prompt_len, "prefix_hit": 0, "out": out,
            "prefill_t0": first - 0.1, "prefill_t1": first,
            "first": first, "finish": finish}


def test_decode_stream_roofline_on_hand_made_facts():
    reader = bench_run.load_reader("mla.decode_stream_roofline")
    least = 2 * (OUTSIDE + 234 * EXPERT) / 819e9          # 7.1 ms
    facts = {"stats": _stats(DECODE, **{
                 "serving.step_seconds": {"count": 250, "p50": 2 * least}}),
             "decode_chunk": 4, "peak": PEAK, "config": CFG,
             "requests": [_request(8, 1)]}               # nothing decoded
    assert reader.read(facts) == pytest.approx(50.0)
    # two requests decode 20 tokens each after their first: their latent
    # rows over the 1000 steps the counters were summed over
    facts["requests"] = [_request(8300, 21), _request(9000, 21)]
    rows = sum((8300 + i) + (9000 + i) for i in range(1, 21))
    want = least + 27 * rows * 1152 / 1000 / 819e9
    assert reader.read(facts) == pytest.approx(100 * want / (2 * least))
    # by counting touched experts and cached values only the share stays
    # at 100 for a step that ran AT the rate of its stream
    assert reader.read(dict(facts, stats=dict(
        facts["stats"], **{"serving.step_seconds": {
            "count": 250, "p50": want}}))) == pytest.approx(100.0)
    # nothing to read: no histogram, no counters, no peak, other families
    assert reader.read(dict(facts, stats=_stats(DECODE))) is None
    assert reader.read(dict(facts, stats={"serving.step_seconds": {
        "count": 250, "p50": 0.01}})) is None
    assert reader.read({k: v for k, v in facts.items() if k != "peak"}) is None
    assert reader.read(dict(facts, config=GPT)) is None
    assert reader.read(dict(facts, config=TRINITY)) is None


LATENT = ('%paged_latent_attention.7 = bf16[12,16,512]{2,1,0} custom-call('
          's32[12,288] %t, s32[12,1] %p, bf16[12,16,640] %q, '
          'bf16[4609,32,640] %k), custom_call_target="tpu_custom_call"')
PAGED = ('%paged_attention.7 = bf16[96,6,8,128]{3,2,1,0} custom-call(s32['
         '96,64] %t, s32[96,6] %p, bf16[96,6,8,128] %q, bf16[6145,32,8,128] '
         '%k, bf16[6145,32,8,128] %v), custom_call_target="tpu_custom_call"')
OTHER = ("%fusion.1 = bf16[12,16,512] fusion(bf16[4609,32,640] %k, "
         "bf16[12,16,512] %paged_latent_attention.7), kind=kOutput")


def _trace(*ops, busy=1.0):
    return {"busy_s": busy, "ops": {
        f"op{i}": {"calls": calls, "seconds": s, "self": s,
                   "provenance": prov}
        for i, (prov, s, *rest) in enumerate(ops)
        for calls in [rest[0] if rest else 1]}}


def test_latent_attention_roofline_holds_the_count_to_a_hand_made_trace():
    reader = bench_run.load_reader("mla.latent_attention_roofline")
    assert reader.kernels(CFG, MIX) == {"paged_latent_attention": (
        "%paged_latent_attention", 'custom_call_target="tpu_custom_call"')}
    trace = _trace((LATENT, 0.30, 2700), (PAGED, 0.2, 20), (OTHER, 0.5, 12))
    # only the calls NAMED after the kernel: not the K/V kernel, not a
    # fusion that reads the pool or the kernel's result
    assert reader.call_seconds(trace) == pytest.approx(0.30)
    assert reader.call_seconds(_trace((PAGED, 0.2), (OTHER, 0.5))) is None
    # the traced second holds one request decoding 100 tokens after a
    # prompt of 8300 (a prefix hit: nothing of it prefilled), and a
    # prompt prefilled inside it, which makes no call to the kernel
    requests = [dict(_request(8300, 101, first=1.0, finish=2.0),
                     prefix_hit=8288),
                dict(_request(200, 1, first=1.5, finish=None),
                     prefill_t0=1.2, prefill_t1=1.5)]
    rows = sum(8300 + i for i in range(1, 101))
    least = 27 * rows * 1152 / 819e9
    facts = {"trace": trace, "trace_span": (1.0, 2.0), "requests": requests,
             "config": CFG, "peak": PEAK}
    assert reader.read(facts) == pytest.approx(100.0 * least / 0.30)
    # a kernel that ran AT the rate of its rows reads 100
    assert reader.read(dict(facts, trace=_trace((LATENT, least, 2700)))) == (
        pytest.approx(100.0))
    # nothing to read: no trace, no named call, another family
    assert reader.read({"trace": None}) is None
    assert reader.read(dict(facts, trace=_trace((PAGED, 0.2)))) is None
    assert reader.read(dict(facts, config=TRINITY)) is None
    assert reader.read(dict(facts, config=GPT)) is None


def test_shared_entry_share_on_hand_made_facts():
    reader = bench_run.load_reader("paged.shared_entry_share")
    assert reader.read({"stats": {"serving.paged_entries_shared": 900.0,
                                  "serving.paged_entries_live": 1000.0}}) == (
        pytest.approx(90.0))
    assert reader.read({"stats": {"serving.paged_entries_shared": 0.0,
                                  "serving.paged_entries_live": 1000.0}}) == 0
    # a program without the counter, a run that decoded nothing
    assert reader.read({"stats": {"serving.paged_entries_live": 10.0}}) is None
    assert reader.read({"stats": {}}) is None


def test_untouched_expert_share_would_read_this_familys_counters():
    """The cell is not on the metric's list (``test_gated_moe_family``
    pins it to one cell), but the reader needs no sizes and reads the
    same counters: a ``benchmark`` PR can list it."""
    reader = bench_run.load_reader("moe.untouched_expert_share")
    stats = _stats(DECODE)
    assert reader.read({"stats": stats}) == pytest.approx(100 * (1 - 9 / 16))
    assert reader.read({"stats": {}}) is None


def _cell():
    bench = json.load(open(bench_run.ROOT + "/BENCHMARK.json"))
    return {"name": "tiny-latent-moe.serve", "chips": 1, "config": TINY,
            "traffic": SERVE, "end_to_end": bench["end_to_end"],
            "per_layer": [m for m in bench["per_layer"]
                          if CELL in m.get("workloads", [])]}


def test_serve_runner_rehearsal_with_shared_heads_and_the_counters():
    from chipbench.runners import serve

    cell = _cell()
    result = serve.run(cell, seed=SEED, seconds=1.5, tracer=None)
    facts = result["facts"]
    assert result["correct"], facts["worst_logit_margin"]
    assert result["attempted"] == 9 and result["failed"] == 0
    assert facts["compiled_in_window"] == 0
    # every prompt starts with a head the warm-up left in the trie
    assert all(r["prefix_hit"] >= 40 for r in facts["requests"])
    facts.update(config=TINY, traffic=SERVE, chips=1, trace=None,
                 trace_window_s=None,
                 peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    got = bench_run.layer_metrics(cell, facts)
    assert {"step.decode_ms", "mla.decode_stream_roofline",
            "paged.shared_entry_share", "sched.prefix_hit_share",
            "compile.seconds",
            "serve.ttft_p90_ms", "paged.skipped_entry_share",
            "paged.rows_per_update"} <= set(got)
    assert not any(k.startswith("device.") or "attention_roofline" in k
                   for k in got)
    assert 0 < got["mla.decode_stream_roofline"]["value"] < 100
    assert 0 < got["paged.shared_entry_share"]["value"] < 100
    assert got["sched.prefix_hit_share"]["value"] > 50
    assert got["paged.rows_per_update"]["value"] == 4.0
    stats = facts["stats"]
    assert stats["serving.paged_entries_shared"] <= (
        stats["serving.paged_entries_live"])
    # the decode positions the host counted are what latent_bytes counts
    # from the requests' own lengths, up to the steps a finished slot
    # rides out in its last chunk (at most decode_chunk - 1 a request)
    contexts = bench_run.load_reader(
        "hybrid.decode_stream_roofline").decode_contexts(facts["requests"])
    counted = stats["serving.latent_positions_read{phase=decode}"]
    _, nbytes = latent_bytes.attended(TINY, contexts)
    mine = nbytes / latent_bytes.position_bytes(TINY)
    most = 4 * 96 * (facts["decode_chunk"] - 1) * len(facts["requests"])
    assert mine <= counted <= mine + most
    assert stats["serving.latent_positions_read{phase=prefill}"] > 0


SWITCHES = {"rotary_key_left_out_of_the_scores": {"rotary_key": False},
            "latent_norm_left_out": {"kv_norm": False},
            "mscale_left_out_of_the_scores": {"mscale_in_scores": False},
            "routed_part_left_out": {"routed": False},
            "selected_weights_renormalised": {"route_norm": True},
            "yarn_blend_replaced_by_plain_theta": {"yarn_blend": False}}


@pytest.mark.parametrize("weakened", list(SWITCHES) + ["fp8_matrices"])
def test_what_fails_the_cells_check(weakened, monkeypatch):
    """The check bites: the reference with one line of the mathematics
    left out no longer rates the engine's tokens within the margin, and
    neither does the sound reference an engine whose matrices were
    rounded to fp8, the next precision down."""
    import jax.numpy as jnp

    from chipbench.runners import serve

    family = families.of(TINY, "serve")
    if weakened == "fp8_matrices":
        right = family.serving_engine

        def fp8(params, cfg, reg, geometry):
            low = {k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                       if k.endswith(".w") and v.ndim >= 2 else v)
                   for k, v in params.items()}
            return right(low, cfg, reg, geometry)

        monkeypatch.setattr(family, "serving_engine", fp8)
    else:
        right = family.logits
        monkeypatch.setattr(
            family, "logits", lambda params, tokens, cfg: right(
                params, tokens, cfg, **SWITCHES[weakened]))
    wrong = serve.run(_cell(), seed=SEED, seconds=1.0, tracer=None)
    assert not wrong["correct"]
    assert wrong["facts"]["worst_logit_margin"] > 2 * 0.05


def test_rows_the_reference_cannot_decide_are_left_out_and_counted():
    import jax.numpy as jnp

    family = families.of(TINY, "serve")
    params = family.make_params(TINY, 64, 11)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, TINY["vocab_size"], (2, 40)), jnp.int32)
    ties = []
    plain = np.asarray(family.logits(dict(params), tokens, TINY, ties=ties))
    least = np.min([np.asarray(m) for m in ties[:3]], axis=0)     # [2, 40]
    assert len(ties) == 3 and plain.any(-1).all() and (least > 0).all()
    assert family.undecided[-1] == (0, 80)
    margin = float(np.median(least))
    got = np.asarray(family.logits(params, tokens, dict(
        TINY, check_undecided_margin=margin)))
    out = least < margin
    assert 0 < out.sum() < 80 and family.undecided[-1] == (out.sum(), 80)
    assert not got[out].any() and np.array_equal(got[~out], plain[~out])
