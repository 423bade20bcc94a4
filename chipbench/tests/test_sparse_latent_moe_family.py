"""The ``sparse_latent_moe`` family (``families/sparse_latent_moe.py``,
``sparse_latent_moe_reference.py``, ``configs/dots3-note-prev.json``,
``traffic/doc_qa_32k.json``, ``dsa_bytes.py`` and the six ``dsa.*`` /
``swa_latent.*`` readers): the sizes shape-only code reads, the byte
arithmetic the cell's geometry rests on, the configuration held to the
catalog's keys, the reference held to the program's copy, ``dsa_bytes``
against hand counts, the readers on hand-made facts ("nothing to read:
nothing returned" among them), and the serving runner end to end on the
CPU at a tiny size of the family WITH shared heads longer than
``index_topk``, the check biting on every weakened variant."""

import json
import os

import numpy as np
import pytest

from chipbench import dsa_bytes, families, flops
from chipbench import run as bench_run

CFG = bench_run._read_json(bench_run.HERE, "configs", "dots3-note-prev.json")
MIX = bench_run._read_json(bench_run.HERE, "traffic", "doc_qa_32k.json")
PEAK = flops.peaks("TPU v5 lite")
CELL = "dots3np.doc_qa_32k"
GPT = bench_run._read_json(bench_run.HERE, "configs",
                           "cerebras-gpt-1.3b.json")
# the published layout at a width the CPU can run: full layers of 4 heads
# over a latent of 16 + 8 with a 3-head indexer that keeps 12 positions,
# sliding layers of 2 heads over a latent of 32 + 16 under a window of 9
# (rotary lanes wide and theta low, so that nine positions turn them)
TINY = {"name": "tiny-sparse-latent-moe", "family": "sparse_latent_moe",
        "hidden_size": 64, "num_hidden_layers": 5,
        "layer_types": ["full_attention", "full_attention",
                        "sliding_attention", "sliding_attention",
                        "sliding_attention"],
        "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
        "rope_theta": 80000000,
        "swa_num_attention_heads": 2, "swa_q_lora_rank": 16,
        "swa_kv_lora_rank": 32, "swa_qk_nope_head_dim": 4,
        "swa_qk_rope_head_dim": 16, "swa_v_head_dim": 8,
        "swa_rope_theta": 20, "sliding_window_size": 9,
        "index_n_heads": 3, "index_head_dim": 16, "index_topk": 12,
        "intermediate_size": 96, "moe_intermediate_size": 24,
        "n_shared_experts": 1, "n_routed_experts": 4, "router_width": 16,
        "experts_first": 4, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "routed_scaling_factor": 1,
        "norm_topk_prob": True, "rms_norm_eps": 1e-5, "vocab_size": 256,
        # float32: at this size a bfloat16 engine selects another one of
        # its 12 positions than the reference in most rows, each a twelfth
        # of a softmax (worst gap 0.86 where float32 reads 0.0 and
        # bfloat16 with index_topk 95, which never binds, 0.0 too); the
        # fp8 variant below is still refused
        "compute_dtype": "float32", "expert_bias_tokens": 64,
        # 1 / sqrt(width): at 0.02 a width of 64 gives scores so small
        # that attention is a plain mean and no line of it can be missed
        "initializer_range": 0.125}
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
         "check": {"sample": 4, "logit_margin": 0.08}}
SEED = 2 ** 31 + 55

FULL_ATT = 144_060_160
SLIDING_ATT = 90_845_184
EXPERT = 3 * 5120 * 1536
ROUTED = 33 * EXPERT + 5120 * 256 + 256
HELD = (FULL_ATT + 3 * 5120 * 13824 + FULL_ATT + ROUTED
        + 3 * (SLIDING_ATT + ROUTED) + 2 * 19008 * 5120 + 5120)


def test_sizes_and_bytes_of_the_configuration_as_it_is_run():
    assert (EXPERT, ROUTED, HELD) == (23_592_960, 779_878_656,
                                      4_087_154_176)
    family = families.of(CFG)
    shapes = family._shapes(CFG)
    assert sum(int(np.prod(s)) for s in shapes.values()) == HELD
    assert round(2 * HELD / 1e9, 2) == 8.17
    size = families.sizes(CFG)
    moe = family.moe_sizes(CFG)
    # every matrix outside the routed experts but the table: the norms,
    # the router's bias and the index LayerNorm's two vectors are no matrix
    vectors = (5120 + 5 * (2 * 5120 + 1024) + 2 * (512 + 256) + 3 * 1024
               + 4 * 256)
    outside = HELD - 4 * 32 * EXPERT - 19008 * 5120 - vectors
    assert moe == {"moe_layers": 4, "experts_held": 32, "router_width": 256,
                   "top_k": 8, "expert_params": EXPERT,
                   "expert_ops_per_row": 2 * EXPERT,
                   "outside_params": outside}
    assert round(2 * outside / 1e9, 2) == 1.94
    assert size == {"d_model": 5120, "heads": 128, "head_dim": 192,
                    "vocab_rows": 19008,
                    "matmul_params": outside + 4 * EXPERT,
                    "kv_planes": 5, "attention_passes": 5}
    assert family.dsa_sizes(CFG) == {
        "full": {"planes": 2, "heads": 128, "written": 576, "stored": 640,
                 "value_lanes": 512},
        "sliding": {"planes": 3, "heads": 64, "written": 1088,
                    "stored": 1152, "value_lanes": 1024},
        "index_lanes": 128, "index_heads": 64, "index_topk": 2048,
        "window": 513}
    assert not hasattr(family, "latent_sizes")      # mla.* refuses it
    # a cached token, written and stored
    assert 2 * (2 * (576 + 128) + 3 * 1088) == 9344
    block = 32 * 2 * (2 * (640 + 128) + 3 * 1152)
    eng = MIX["engine"]
    per_slot = eng["max_len"] // eng["block_tokens"]
    blocks = 1 + eng["max_slots"] * per_slot + eng["cache_blocks"]
    assert (per_slot, block) == (1064, 319_488)
    chip = PEAK["hbm_bytes"]
    assert 0.25 * chip < 2 * HELD < 0.6 * chip       # the floor, by weights
    assert 0.75 * chip < 2 * HELD + blocks * block < 0.85 * chip
    heads = MIX["shared_heads"]
    assert (heads["count"], heads["tokens"]) == (4, 32768)
    assert (heads["tokens"] + MIX["prompt_tail"]["max"]
            + MIX["output"]["max"]) == eng["max_len"]
    assert eng["cache_blocks"] >= heads["count"] * (
        heads["tokens"] // eng["block_tokens"])
    assert eng["prefix_reuse"] and MIX["schedule_seed"] == 20261055
    assert "decode_chunk" not in eng              # the engine's default
    assert (MIX["prompt_tail"]["min"], MIX["prompt_tail"]["median"],
            MIX["prompt_tail"]["max"]) == (32, 128, 512)
    assert (MIX["output"]["min"], MIX["output"]["median"],
            MIX["output"]["max"]) == (64, 256, 768)


def test_configuration_holds_the_catalogs_keys_and_says_what_it_cut():
    bench = bench_run._read_json(bench_run.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "dots3-note-prev")
    cut = ["num_hidden_layers", "layer_types", "n_routed_experts",
           "vocab_size"]
    assert entry["reduced"] == cut == CFG["reduced"]
    assert CFG["source"].startswith(entry["source"])
    published = {
        "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
        "attention_gate_type": "headwise", "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
        "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
        "kv_lora_rank": 512, "max_position_embeddings": 524288,
        "model_type": "dots3_note", "moe_intermediate_size": 1536,
        "moe_layer_freq": 1, "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_key_value_heads": 128, "q_lora_rank": 1024,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
        "routed_scaling_factor": 1, "scoring_func": "sigmoid",
        "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
        "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
        "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
        "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
        "swa_rope_theta": 50000, "swa_v_head_dim": 128,
        "tie_word_embeddings": False, "topk_method": "noaux_tc",
        "v_head_dim": 128}
    assert {k: CFG[k] for k in published} == published
    assert CFG["layer_types"] == ["full_attention"] * 2 + [
        "sliding_attention"] * 3
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"]) == (5, 32, 19008)
    pub = CFG["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (46, 256 == CFG["router_width"] and 256,
                                   8 * CFG["vocab_size"])
    assert "eight chips share each layer" in CFG["deployment"]
    assert "4,087,154,176" in CFG["reduced_why"]
    assert {"lora_rescale", "gate", "no_group_limit", "rotary_convention",
            "window", "index_layernorm", "index_key_dtype", "precision",
            "left_out", "init"} <= set(CFG["assumed"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dots3-note-prev", "doc_qa_32k", 1)
    assert f"{MIX['rate_per_s']:g} req/s" in cell["why"]
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    mine = {"dsa.decode_stream_roofline", "dsa.indexer_roofline",
            "dsa.sparse_attention_roofline", "swa_latent.attention_roofline",
            "dsa.select_busy_share", "dsa.attended_position_share"}
    assert mine | {"tpot_p90_ms", "sched.prefix_hit_share",
                   "paged.shared_entry_share", "step.attention_busy_share",
                   "step.unnamed_busy_share", "device.idle_share.serve",
                   "compile.seconds", "gen.late_ms_p90"} <= listed
    assert not listed & {"mla.decode_stream_roofline",
                         "mla.latent_attention_roofline",
                         "paged_attention_roofline",
                         "moe.decode_stream_roofline"}
    for name in mine:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
        reader = bench_run.load_reader(name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.SOURCE,
                reader.MOVES) == (name, metric["unit"], metric["layer"],
                                  metric["source"], metric["moves"])


def test_the_family_serves_and_does_not_train():
    assert families.of(CFG, "serve").__name__ == (
        "chipbench.families.sparse_latent_moe")
    with pytest.raises(SystemExit) as err:
        families.of(CFG, "train")
    assert "does not train" in str(err.value)


def test_reference_is_the_programs_copy_and_imports_nothing_of_it():
    def body(path):
        text = open(path).read()
        return text[text.index("import functools"):]

    mine = os.path.join(bench_run.HERE, "families",
                        "sparse_latent_moe_reference.py")
    theirs = os.path.join(bench_run.ROOT, "paddle_tpu", "models",
                          "sparse_latent_moe_reference.py")
    assert body(mine) == body(theirs)
    assert "paddle_tpu" not in body(mine)
    assert "import" not in body(mine).replace(
        "import functools\n\nimport jax\nimport jax.numpy as jnp\n", "")


def test_dsa_bytes_against_hand_counts():
    contexts = [100, 33000, 5000]
    assert dsa_bytes.sizes(GPT) is None
    assert dsa_bytes.index_call(CFG, contexts) == (
        2 * 64 * 128 * 38100, 38100 * 256)
    picked = 100 + 2048 + 2048
    assert dsa_bytes.sparse_call(CFG, contexts) == (
        2 * 128 * (576 + 512) * picked, picked * 1280)
    seen = 100 + 513 + 513
    assert dsa_bytes.window_call(CFG, contexts) == (
        2 * 64 * (1088 + 1024) * seen, seen * 2304)
    assert dsa_bytes.attention_bytes(CFG, contexts) == (
        2 * (38100 * 256 + picked * 1280) + 3 * seen * 2304)
    # ISSUE 55's arithmetic: 8 slots live at 33k read 135 MB of index
    # keys, 42 MB of selected rows and 28 MB of window rows a step
    eight = [33000] * 8
    assert round(2 * dsa_bytes.index_call(CFG, eight)[1] / 1e6) == 135
    assert round(2 * dsa_bytes.sparse_call(CFG, eight)[1] / 1e6) == 42
    assert round(3 * dsa_bytes.window_call(CFG, eight)[1] / 1e6) == 28
    assert dsa_bytes.least_seconds((197e12, 0), PEAK) == pytest.approx(1.0)
    step = dsa_bytes.decode_step_bytes(CFG, 28.0, eight, 1)
    outside = families.of(CFG).moe_sizes(CFG)["outside_params"]
    assert step == 2 * (outside + 28 * EXPERT) + dsa_bytes.attention_bytes(
        CFG, eight)


def _request(prompt_len, out, first=1.0, finish=2.0):
    return {"prompt_len": prompt_len, "out": out, "first": first,
            "finish": finish, "prefix_hit": 0, "due": 0.0, "submit": 0.0,
            "admit": 0.5, "prefill_t0": 0.5, "prefill_t1": 1.0, "bucket": 8}


def test_the_program_readers_on_hand_made_facts():
    share = bench_run.load_reader("dsa.attended_position_share")
    assert share.read({"stats": {}}) is None
    assert share.read({"stats": {
        "serving.index_positions_scored{phase=decode}": 66000.0,
        "serving.sparse_positions_attended{phase=decode}": 4096.0}}
    ) == pytest.approx(100 * 4096 / 66000)
    stream = bench_run.load_reader("dsa.decode_stream_roofline")
    steps = 100
    stats = {"serving.step_seconds": {"p50": 0.006, "count": 25},
             "serving.moe_rows{phase=decode}": 4.0 * steps * 8,
             "serving.moe_assignments_held{phase=decode}": 4.0 * steps * 8,
             "serving.moe_experts_touched{phase=decode}": 4.0 * steps * 7,
             "serving.moe_expert_visits{phase=decode}": 4.0 * steps * 32}
    requests = [_request(33000, 14)] * 8
    facts = {"stats": stats, "peak": PEAK, "config": CFG,
             "requests": requests}
    contexts = [33000 + i for i in range(1, 14)] * 8
    want = dsa_bytes.decode_step_bytes(CFG, 28.0, contexts, steps)
    assert stream.read(facts) == pytest.approx(
        100 * want / PEAK["hbm_bytes_per_s"] / 0.006)
    assert 0 < stream.read(facts) < 100
    assert stream.read(dict(facts, config=GPT)) is None
    assert stream.read(dict(facts, stats={})) is None
    # the device readers: no trace, nothing returned; no raise
    for name in ("dsa.indexer_roofline", "dsa.sparse_attention_roofline",
                 "swa_latent.attention_roofline", "dsa.select_busy_share"):
        reader = bench_run.load_reader(name)
        assert reader.read({"stats": {}, "config": CFG}) is None
        assert reader.read({"stats": {}, "config": GPT, "trace": {"ops": {}},
                            "trace_span": (0.0, 1.0), "requests": []}) is None


def test_the_device_readers_on_a_hand_made_join():
    """A recorded join stands in for the trace: 2 ms under the indexer's
    scope, 1 ms under the sparse attention's and 0.5 ms of selection in
    the decode module, of 10 ms busy."""
    helper = bench_run.load_reader("dsa.indexer_roofline")
    ops = {("jit_decode_chunk", "fusion.1"): (
               2e-3, "attn.core", "decode", "a/attn.core/paged_index_scores/x"),
           ("jit_decode_chunk", "sort.2"): (
               5e-4, "attn.core", "decode", "a/attn.core/index_select/sort"),
           ("jit_decode_chunk", "gather.3"): (
               1e-3, "attn.core", "decode",
               "a/attn.core/paged_sparse_latent_attention/gather"),
           ("jit_prefill", "fusion.9"): (
               4e-3, "attn.core", "prefill",
               "a/attn.core/paged_index_scores/x")}
    helper._ops["hand"] = (ops, 10e-3)
    requests = [_request(33000, 101, first=0.0, finish=10.0)] * 4
    facts = {"trace": {"ops": {}, "busy_s": 10e-3}, "trace_path": "hand",
             "trace_span": (2.0, 4.0), "requests": requests, "config": CFG,
             "peak": PEAK, "stats": {}}
    assert helper.scope_seconds(facts, "paged_index_scores") == 6e-3
    assert helper.scope_seconds(facts, "paged_index_scores",
                                "decode") == 2e-3
    contexts = bench_run.load_reader(
        "paged_attention_named_roofline").decode_contexts(requests, 2.0, 4.0)
    assert len(contexts) == 4 * 20
    # the spans' own count of positions is not in a hand-made profile
    helper_positions = bench_run.load_reader("swa.paged_attention_roofline")
    assert helper_positions.positions(dict(facts, trace_path=None)) is None
    facts["trace_path_for_spans"] = None
    import unittest.mock as mock

    with mock.patch.object(helper_positions, "positions", lambda f: None), \
            mock.patch.object(bench_run, "load_reader",
                              wraps=bench_run.load_reader) as load:
        load.side_effect = lambda n: (helper_positions if n ==
                                      "swa.paged_attention_roofline" else
                                      helper if n == "dsa.indexer_roofline"
                                      else mock.DEFAULT)
        got = helper.read(facts)
        sparse = bench_run.load_reader("dsa.sparse_attention_roofline")
        got_sparse = sparse.read(facts)
        select = bench_run.load_reader("dsa.select_busy_share").read(facts)
    want = 2 * dsa_bytes.least_seconds(
        dsa_bytes.index_call(CFG, contexts), PEAK)
    assert got == pytest.approx(100 * want / 2e-3)
    want = 2 * dsa_bytes.least_seconds(
        dsa_bytes.sparse_call(CFG, contexts), PEAK)
    assert got_sparse == pytest.approx(100 * want / 1e-3)
    assert select == pytest.approx(5.0)
    del helper._ops["hand"]


def _cell():
    bench = json.load(open(bench_run.ROOT + "/BENCHMARK.json"))
    return {"name": "tiny-sparse-latent-moe.serve", "chips": 1,
            "config": TINY, "traffic": SERVE,
            "end_to_end": bench["end_to_end"],
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
    # every prompt starts with a head the warm-up left in the trie, longer
    # than index_topk: its suffix selects rows inside the shared head
    assert all(r["prefix_hit"] >= 40 > 3 * TINY["index_topk"]
               for r in facts["requests"])
    facts.update(config=TINY, traffic=SERVE, chips=1, trace=None,
                 trace_window_s=None,
                 peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    got = bench_run.layer_metrics(cell, facts)
    assert {"step.decode_ms", "dsa.decode_stream_roofline",
            "dsa.attended_position_share", "paged.shared_entry_share",
            "sched.prefix_hit_share", "compile.seconds", "serve.ttft_p90_ms",
            "tail.tpot_ms", "serve.tpot_p90_ms"} <= set(got)
    assert not any(k.startswith("device.") or "attention_roofline" in k
                   or k in ("dsa.indexer_roofline", "dsa.select_busy_share")
                   for k in got)
    assert 0 < got["dsa.decode_stream_roofline"]["value"] < 100
    assert 10 < got["dsa.attended_position_share"]["value"] < 40
    assert got["sched.prefix_hit_share"]["value"] > 50
    stats = facts["stats"]
    # the decode positions the host counted are what dsa_bytes counts from
    # the requests' own lengths, up to the steps a finished slot rides out
    contexts = bench_run.load_reader(
        "hybrid.decode_stream_roofline").decode_contexts(facts["requests"])
    counted = stats["serving.index_positions_scored{phase=decode}"]
    mine = 2 * sum(contexts)
    most = 2 * 96 * (facts["decode_chunk"] - 1) * len(facts["requests"])
    assert mine <= counted <= mine + most
    assert stats["serving.latent_window_calls{phase=decode}"] > 0


SWITCHES = {
    "whole_chain_attended_in_place_of_the_selection": {"select": "all"},
    "relu_left_out_of_the_index_scores": {"index_relu": False},
    "indexer_rope_left_out": {"index_rope": False},
    "selection_taken_from_the_first_positions": {"select": "first"},
    "sliding_planes_attended_whole": {"windowed": False},
    "sliding_theta_replaced_by_the_full_layers": {"sliding_theta": 8e7},
    "lora_rescale_left_out": {"rescale": False},
    "gate_left_out": {"gate": False},
    "norm_topk_prob_left_out": {"route_norm": False}}


@pytest.mark.parametrize("weakened", list(SWITCHES) + ["fp8_matrices"])
def test_what_fails_the_cells_check(weakened, monkeypatch):
    """The check bites: the reference with one line of the mathematics
    left out or changed no longer rates the engine's tokens within the
    margin, and neither does the sound reference an engine whose matrices
    were rounded to fp8, the next precision down."""
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
    print(weakened, wrong["facts"]["worst_logit_margin"])
    assert not wrong["correct"]
    assert wrong["facts"]["worst_logit_margin"] > 1.5 * 0.08


def test_rows_the_reference_cannot_decide_are_left_out_and_counted():
    """``logits`` under a ``check_undecided_margin``: the rows whose
    expert selection, in any routed layer, is within the margin of one
    that differs in a held expert come back as zeros and are counted; at
    a margin of 0 no row is left out and no margin is computed."""
    import jax.numpy as jnp

    family = families.of(TINY, "serve")
    params = family.make_params(TINY, 64, 11)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, TINY["vocab_size"], (1, 40)), jnp.int32)
    ties = []
    plain = np.asarray(family.logits(dict(params), tokens, TINY, ties=ties))
    least = np.min([np.asarray(m) for m in ties], axis=0)          # [40]
    assert len(ties) == 4 and plain.any(-1).all() and (least > 0).all()
    margin = float(np.median(least))
    before = len(family.undecided)
    got = np.asarray(family.logits(params, tokens, dict(
        TINY, check_undecided_margin=margin)))
    left_out = least < margin
    assert family.undecided[-1] == (int(left_out.sum()), 40)
    assert len(family.undecided) == before + 1 and 0 < left_out.sum() < 40
    assert not got[0, left_out].any()
    assert np.array_equal(got[0, ~left_out], plain[0, ~left_out])
