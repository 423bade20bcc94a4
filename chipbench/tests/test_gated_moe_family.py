"""The ``gated_moe`` family (``families/gated_moe.py``,
``gated_moe_reference.py``, ``configs/trinity-large-preview.json``,
``moe_bytes.py`` and the three ``moe.*`` readers): the sizes shape-only
code reads, the byte arithmetic the cell's geometry rests on, the
reference held to the program's copy, the counts of ``moe_bytes``
against hand counts, the program's counters held to a count of the
reference's own selections, the readers on hand-made facts (a hand-made
trace among them), and the serving runner end to end on the CPU at a
tiny size of the family with the check biting on each line of the
mathematics left out."""

import json
import os

import numpy as np
import pytest

from chipbench import families, flops, hybrid_bytes, moe_bytes
from chipbench import run as bench_run

CFG = bench_run._read_json(bench_run.HERE, "configs",
                           "trinity-large-preview.json")
MIX = bench_run._read_json(bench_run.HERE, "traffic", "chat_moe.json")
PEAK = flops.peaks("TPU v5 lite")
CELL = "trinitylp.chat_moe"
GPT = bench_run._read_json(bench_run.HERE, "configs",
                           "cerebras-gpt-1.3b.json")
# the published layout at a width the CPU can run: heads of 32 where
# hidden / heads is 16, a dense layer and four routed ones (window x 3,
# full), 4 of 16 experts held (4..7), top 4
TINY = {"name": "tiny-gated-moe", "family": "gated_moe", "hidden_size": 64,
        "head_dim": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 128, "moe_intermediate_size": 48,
        "num_hidden_layers": 5, "num_dense_layers": 1,
        "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
        "num_experts": 4, "router_width": 16, "experts_first": 4,
        "num_experts_per_tok": 4, "route_scale": 2.448,
        "rms_norm_eps": 1e-5, "rope_theta": 10000, "sliding_window": 8,
        "vocab_size": 256, "compute_dtype": "bfloat16",
        "check_undecided_margin": 0.002, "expert_bias_tokens": [64, 32]}
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
         # at this size the right program's worst gap is 0.0 to 0.01 over
         # seeds (a flipped selection at the fourth score); the routed
         # part left out reads 0.125, the attention gate 0.07-0.12, rotary
         # moved 0.22-0.26, the window bound 0.51, fp8 matrices 0.08-0.09
         "check": {"sample": 4, "logit_margin": 0.03}}
SEED = 2 ** 31 + 34

ATT = 2 * 3072 * 6144 + 2 * 3072 * 1024 + 6144 * 3072   # q, gate, k, v, out
EXPERT = 3 * 3072 * 3072
OUTSIDE = (5 * ATT + 3 * 3072 * 12288 + 4 * (3072 * 256 + EXPERT)
           + 3072 * 25024)


def test_sizes_and_bytes_of_the_configuration_as_it_is_run():
    assert (ATT, EXPERT) == (62_914_560, 28_311_552)
    size = families.sizes(CFG)
    assert size == {"d_model": 3072, "heads": 48, "head_dim": 128,
                    "vocab_rows": 25_024,
                    "matmul_params": OUTSIDE + 4 * EXPERT // 2,
                    "kv_planes": 5, "attention_passes": 5}
    family = families.of(CFG)
    assert family.hybrid_sizes(CFG) == {
        "kv_heads": 8, "window": 4096, "window_planes": 4,
        "full_plane_reads": 1, "state_layers": 0, "state_bytes_per_slot": 0}
    assert family.moe_sizes(CFG) == {
        "moe_layers": 4, "experts_held": 32, "router_width": 256, "top_k": 4,
        "expert_params": EXPERT, "expert_ops_per_row": 2 * EXPERT,
        "outside_params": OUTSIDE}
    # everything held: the experts, what is outside them, the table
    held = OUTSIDE + 4 * 32 * EXPERT + 3072 * 25_024
    norms = 5 * (4 * 3072 + 2 * 128) + 3072 + 4 * 256
    assert held == 4_321_837_056 and held + norms == 4_321_903_872
    # a decode step streams at most 8.49 GB: 10.4 ms at the peak rate, of
    # which the 128 held experts are 8.85
    most = 2 * (OUTSIDE + 128 * EXPERT)
    assert round(most / 1e9, 2) == 8.49
    assert round(most / PEAK["hbm_bytes_per_s"] * 1e3, 1) == 10.4
    assert round(2 * 128 * EXPERT / PEAK["hbm_bytes_per_s"] * 1e3, 2) == 8.85
    assert hybrid_bytes.plane_token_bytes(CFG) == 4096
    assert hybrid_bytes.kv_bytes_per_token(CFG) == 20_480
    # the cell's pool: trash + 96 slots x 64 blocks of 32 positions, 5
    # planes x K and V x 64 KiB a block
    eng = MIX["engine"]
    blocks = 1 + eng["max_slots"] * (eng["max_len"] // eng["block_tokens"])
    pool = blocks * eng["block_tokens"] * 20_480
    assert blocks == 6145 and round(pool / 2 ** 30, 2) == 3.75
    chip = PEAK["hbm_bytes"]
    assert 0.25 * chip < 2 * held < 0.55 * chip      # the floor, by weights
    assert 0.7 * chip < 2 * held + pool < 0.8 * chip
    assert (MIX["prompt_tail"]["max"] + MIX["output"]["max"]
            <= eng["max_len"] < CFG["sliding_window"])
    assert MIX["shared_heads"]["count"] == 0 and not eng["prefix_reuse"]


def test_configuration_holds_the_catalogs_keys_and_says_what_it_cut():
    bench = bench_run._read_json(bench_run.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "trinity-large-preview")
    cut = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size"]
    assert entry["reduced"] == cut == CFG["reduced"]
    assert CFG["source"].startswith(entry["source"])
    assert len(entry["source"]) <= 200
    # the widths are the published ones: none of them is in the cut
    widths = {"head_dim": 128, "hidden_size": 3072,
              "intermediate_size": 12288, "moe_intermediate_size": 3072,
              "num_attention_heads": 48, "num_key_value_heads": 8,
              "num_experts_per_tok": 4, "sliding_window": 4096,
              "route_scale": 2.448, "num_shared_experts": 1,
              "rms_norm_eps": 1e-05, "rope_theta": 10000,
              "score_func": "sigmoid", "route_norm": True,
              "mup_enabled": True, "tie_word_embeddings": False,
              "n_group": 1, "topk_group": 1, "model_type": "afmoe",
              "max_position_embeddings": 262144}
    assert {k: CFG[k] for k in widths} == widths
    assert (CFG["num_hidden_layers"], CFG["num_dense_layers"],
            CFG["num_experts"], CFG["vocab_size"]) == (5, 1, 32, 25024)
    assert CFG["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert CFG["published"]["num_experts"] == CFG["router_width"] == 256
    assert CFG["published"]["vocab_size"] == 8 * CFG["vocab_size"]
    assert CFG["experts_first"] == 0 and "eight chips" in CFG["deployment"]
    assert {"rotary", "attention_gate", "qk_norm", "norms", "routing",
            "expert_bias", "logits", "window", "layers_kept",
            "init"} <= set(CFG["assumed"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-large-preview", "chat_moe", 1)
    assert f"{MIX['rate_per_s']:g} req/s" in cell["why"]
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    # no reader that counts every matmul parameter once a step, or K/V
    # from planes x heads, reports in the cell
    assert not listed & {"step.decode_stream_roofline",
                         "hybrid.decode_stream_roofline",
                         "hybrid.recurrent_busy_share",
                         "paged_attention_roofline",
                         "paged_attention_named_roofline",
                         "loop.stack_busy_share", "sched.prefix_hit_share"}
    assert {"moe.decode_stream_roofline", "moe.expert_matmul_roofline",
            "moe.untouched_expert_share", "tpot_p90_ms",
            "serve_tokens_per_s", "paged.rows_per_update",
            "paged.skipped_entry_share", "serve.ttft_p90_ms",
            "device.idle_share.serve"} <= listed
    for name in ("moe.decode_stream_roofline", "moe.expert_matmul_roofline",
                 "moe.untouched_expert_share"):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
        reader = bench_run.load_reader(name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.SOURCE,
                reader.MOVES) == (name, metric["unit"], metric["layer"],
                                  metric["source"], metric["moves"])


def test_the_family_serves_and_does_not_train():
    assert families.of(CFG, "serve").__name__ == (
        "chipbench.families.gated_moe")
    with pytest.raises(SystemExit) as err:
        families.of(CFG, "train")
    assert "does not train" in str(err.value)


def test_reference_is_the_programs_copy_and_imports_nothing_of_it():
    def body(path):
        text = open(path).read()
        return text[text.index("import functools"):]

    mine = os.path.join(bench_run.HERE, "families", "gated_moe_reference.py")
    theirs = os.path.join(bench_run.ROOT, "paddle_tpu", "models",
                          "gated_moe_reference.py")
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


# 1000 decode steps of 50 live slots: 25 pairs and 17 experts a layer
DECODE = {"rows": 1000 * 4 * 50, "assignments_held": 1000 * 4 * 25,
          "experts_touched": 1000 * 4 * 17, "expert_visits": 1000 * 4 * 32}
# 300 prefill pieces of 100 real rows: 50 pairs, 25 experts a layer
PREFILL = {"rows": 300 * 4 * 100, "assignments_held": 300 * 4 * 50,
           "experts_touched": 300 * 4 * 25, "expert_visits": 300 * 4 * 32}


def test_moe_bytes_against_hand_counts():
    assert moe_bytes.sizes(GPT) is None
    assert moe_bytes.expert_bytes(CFG) == 2 * EXPERT == 56_623_104
    count = moe_bytes.counts(_stats(DECODE), "decode")
    assert count == {"rows": 200_000, "assignments": 100_000,
                     "touched": 68_000, "visits": 128_000}
    assert moe_bytes.counts(_stats(DECODE), "prefill") is None
    assert moe_bytes.counts({}, "decode") is None
    assert moe_bytes.steps(CFG, count) == 1000
    assert moe_bytes.untouched_share(count) == pytest.approx(1 - 17 / 32)
    # a step of two slots at contexts 300 and 5000 that touched 68 (expert,
    # layer) pairs: what is outside the experts, 68 experts, and K/V with
    # the four window planes clipped to 4096
    kv = (4 * 300 + 300 + 4 * 4096 + 5000) * 4096
    assert moe_bytes.decode_step_bytes(CFG, 68, [300, 5000], 1) == (
        2 * (OUTSIDE + 68 * EXPERT) + kv)
    assert moe_bytes.decode_step_bytes(CFG, 68, [300, 5000], 2) == (
        2 * (OUTSIDE + 68 * EXPERT) + kv / 2)
    # one layer's grouped product: memory-bound at a decode step's 25
    # pairs over 17 experts, compute-bound only past 240 rows an expert
    read = 17 * 2 * EXPERT / 819e9
    assert moe_bytes.expert_call_seconds(CFG, 17, 25, PEAK) == (
        pytest.approx(read))
    assert 2 * EXPERT * 25 / 197e12 < read / 100
    assert moe_bytes.expert_call_seconds(CFG, 1, 512, PEAK) == (
        pytest.approx(2 * EXPERT * 512 / 197e12))


def _request(prompt_len, out, first=1.0, finish=2.0):
    return {"prompt_len": prompt_len, "prefix_hit": 0, "out": out,
            "prefill_t0": first - 0.1, "prefill_t1": first,
            "first": first, "finish": finish}


def test_decode_stream_roofline_on_hand_made_facts():
    reader = bench_run.load_reader("moe.decode_stream_roofline")
    least = 2 * (OUTSIDE + 68 * EXPERT) / 819e9          # 6.4 ms
    facts = {"stats": dict(_stats(DECODE), **{
                 "serving.step_seconds": {"count": 250, "p50": 2 * least}}),
             "decode_chunk": 4, "peak": PEAK, "config": CFG,
             "requests": [_request(8, 1)]}               # nothing decoded
    assert reader.read(facts) == pytest.approx(50.0)
    # two requests decode 20 tokens each after their first: their K/V
    # over the 1000 steps the counters were summed over
    facts["requests"] = [_request(600, 21), _request(50, 21)]
    kv = sum(5 * (600 + i) + 5 * (50 + i) for i in range(1, 21)) * 4096
    want = least + kv / 1000 / 819e9
    assert reader.read(facts) == pytest.approx(100 * want / (2 * least))
    # by counting touched experts only the share stays under 100 even for
    # a step that ran AT the rate of its stream
    assert reader.read(dict(facts, stats=dict(
        facts["stats"], **{"serving.step_seconds": {
            "count": 250, "p50": want}}))) == pytest.approx(100.0)
    # nothing to read: no histogram, no counters, no peak, another family
    assert reader.read(dict(facts, stats=_stats(DECODE))) is None
    assert reader.read(dict(facts, stats={"serving.step_seconds": {
        "count": 250, "p50": 0.01}})) is None
    assert reader.read({k: v for k, v in facts.items() if k != "peak"}) is None
    assert reader.read(dict(facts, config=GPT)) is None


def test_untouched_expert_share_on_hand_made_facts():
    reader = bench_run.load_reader("moe.untouched_expert_share")
    assert reader.read({"stats": _stats(DECODE, PREFILL)}) == pytest.approx(
        100 * (1 - 17 / 32))
    assert reader.read({"stats": _stats(None, PREFILL)}) is None
    assert reader.read({"stats": {}}) is None


GROUPED = ('%grouped_matmul.5 = bf16[384,3072]{1,0} custom-call(s32[34] %g, '
           's32[34] %t, s32[33] %o, bf16[384,3072] %x, bf16[32,3072,3072] '
           '%w), custom_call_target="tpu_custom_call"')
PIECE = GROUPED.replace("grouped_matmul.5", "grouped_matmul.9").replace(
    "384,3072", "512,3072")
PAGED = ('%paged_attention.7 = bf16[96,6,8,128]{3,2,1,0} custom-call(s32['
         '96,64] %t, s32[96,6] %p, bf16[96,6,8,128] %q, bf16[6145,32,8,128] '
         '%k, bf16[6145,32,8,128] %v), custom_call_target="tpu_custom_call"')
OTHER = ("%fusion.1 = bf16[384,3072] fusion(bf16[32,3072,3072] %w, "
         "bf16[384,3072] %grouped_matmul.5), kind=kOutput")


def _trace(*ops, busy=1.0):
    return {"busy_s": busy, "ops": {
        f"op{i}": {"calls": calls, "seconds": s, "self": s,
                   "provenance": prov}
        for i, (prov, s, *rest) in enumerate(ops)
        for calls in [rest[0] if rest else 1]}}


def test_expert_matmul_roofline_holds_the_count_to_a_hand_made_trace():
    reader = bench_run.load_reader("moe.expert_matmul_roofline")
    assert reader.kernels(CFG, MIX) == {"grouped_matmul": (
        "%grouped_matmul", 'custom_call_target="tpu_custom_call"')}
    # 4 decode steps and 2 prefill pieces of 4 routed layers, 3 calls a
    # layer-step: 48 and 24 calls
    trace = _trace((GROUPED, 0.30, 48), (PIECE, 0.10, 24), (PAGED, 0.2, 20),
                   (OTHER, 0.5, 12))
    # only the calls NAMED after the kernel: not the paged kernel, not a
    # fusion that reads the experts or the product's result
    assert reader.named_calls(trace) == (72, pytest.approx(0.40))
    assert reader.named_calls(_trace((PAGED, 0.2), (OTHER, 0.5))) is None
    # the traced second holds two requests decoding 100 tokens each and
    # one prompt of 200 tokens prefilled inside it
    requests = [_request(300, 101, first=1.0, finish=2.0),
                _request(400, 101, first=1.0, finish=2.0),
                dict(_request(200, 1, first=1.5, finish=None),
                     prefill_t0=1.2, prefill_t1=1.5)]
    in_window = reader.positions(requests, 1.0, 2.0)
    assert in_window == {"decode": 200, "prefill": 200}
    stats = _stats(DECODE, PREFILL)
    # by the requests' times: 200 positions at 50 rows a step are 4
    # decode steps, 200 at 100 rows a piece 2 pieces: two thirds and one
    # third of the 24 layer-steps the trace holds; a decode layer-step
    # reads 17 experts, a piece's 25
    least = (16 * 17 + 8 * 25) * 2 * EXPERT / 819e9
    assert reader.least_seconds(CFG, stats, in_window, 24, PEAK) == (
        pytest.approx(least))
    facts = {"trace": trace, "trace_span": (1.0, 2.0), "requests": requests,
             "stats": stats, "config": CFG, "peak": PEAK}
    assert reader.read(facts) == pytest.approx(100.0 * least / 0.40)
    # the number of layer-steps is the trace's, whatever the requests'
    # times say: half the calls, half the least time
    half = _trace((GROUPED, 0.30, 24), (PIECE, 0.10, 12))
    assert reader.read(dict(facts, trace=half)) == pytest.approx(
        50.0 * least / 0.40)
    # a compute-bound phase is counted by its operations: 2000 pairs a
    # layer-step over one expert
    dense = dict(PREFILL, assignments_held=300 * 4 * 2000,
                 experts_touched=300 * 4)
    assert reader.least_seconds(
        CFG, _stats(None, dense), {"decode": 0, "prefill": 100}, 4, PEAK
    ) == pytest.approx(4 * 2 * EXPERT * 2000 / 197e12)
    # nothing to read: no trace, no named call, no counters, another family
    assert reader.read({"trace": None}) is None
    assert reader.read(dict(facts, trace=_trace((PAGED, 0.2)))) is None
    assert reader.read(dict(facts, stats={})) is None
    assert reader.read(dict(facts, config=GPT)) is None


def test_window_roofline_reads_this_family_through_hybrid_sizes():
    reader = bench_run.load_reader("paged_attention_window_roofline")
    requests = [_request(1000, 101, first=1.0, finish=2.0)]
    facts = {"trace": _trace((PAGED, 0.004), (GROUPED, 0.5)),
             "trace_span": (1.0, 2.0), "requests": requests, "config": CFG,
             "peak": PEAK}
    nbytes = sum(5 * (1000 + i) for i in range(1, 101)) * 4096
    assert reader.read(facts) == pytest.approx(
        100.0 * (nbytes / 819e9) / 0.004)


def _cell():
    bench = json.load(open(bench_run.ROOT + "/BENCHMARK.json"))
    return {"name": "tiny-gated-moe.serve", "chips": 1, "config": TINY,
            "traffic": SERVE, "end_to_end": bench["end_to_end"],
            "per_layer": [m for m in bench["per_layer"]
                          if CELL in m.get("workloads", [])]}


def test_serve_runner_rehearsal_and_the_routing_counters():
    from chipbench.runners import serve

    cell = _cell()
    result = serve.run(cell, seed=SEED, seconds=1.5, tracer=None)
    facts = result["facts"]
    assert result["correct"], facts["worst_logit_margin"]
    assert result["attempted"] == 9 and result["failed"] == 0
    assert facts["compiled_in_window"] == 0
    facts.update(config=TINY, traffic=SERVE, chips=1, trace=None,
                 trace_window_s=None,
                 peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    got = bench_run.layer_metrics(cell, facts)
    assert {"step.decode_ms", "moe.decode_stream_roofline",
            "moe.untouched_expert_share", "compile.seconds",
            "serve.ttft_p90_ms", "paged.skipped_entry_share",
            "paged.rows_per_update"} <= set(got)
    assert not any(k.startswith(("device.", "paged_attention")) or
                   k == "moe.expert_matmul_roofline" for k in got)
    assert 0 < got["moe.decode_stream_roofline"]["value"] < 100
    assert 0 < got["moe.untouched_expert_share"]["value"] < 100
    assert got["paged.rows_per_update"]["value"] == 2.0
    stats = facts["stats"]
    for phase in moe_bytes.PHASES:
        count = moe_bytes.counts(stats, phase)
        # a row selects 4 of 16, a quarter of them held: one pair a row
        # and layer in expectation, never more than four
        assert 0.5 < count["assignments"] / count["rows"] < 1.5
        assert count["touched"] <= min(count["assignments"], count["visits"])
        assert moe_bytes.steps(TINY, count) == int(
            moe_bytes.steps(TINY, count))


def test_the_engines_counters_are_a_count_of_the_references_selections():
    """One request alone: what the compiled prefill pieces and decode
    chunks tallied is what ``moe_bytes`` would count from the reference's
    own selections at the same positions (float32, so that no selection
    flips)."""
    import jax.numpy as jnp
    from paddle_tpu.observability.metrics import MetricsRegistry

    cfg = dict(TINY, compute_dtype="float32")
    family = families.of(cfg, "serve")
    params = family.make_params(cfg, 64, 7)
    # spread the router's scores: at a width of 64 normal(0, 0.02) leaves
    # them all within 0.01 of one half
    params = {k: (v * 25 if k.endswith("router.w") else v)
              for k, v in params.items()}
    reg = MetricsRegistry()
    eng = family.serving_engine(params, cfg, reg, dict(SERVE["engine"]))
    prompt = np.arange(1, 12, dtype=np.int32)
    out, = eng.generate_many([prompt], max_new_tokens=13)
    seen = []
    family.logits(params, jnp.asarray(out)[None], cfg, seen=seen)
    sel = np.stack([np.asarray(s)[0] for s in seen])           # [L, t, k]
    held = (sel >= 4) & (sel < 8)

    def touched(lo, hi):
        return sum(len(np.unique(sel[l, lo:hi][held[l, lo:hi]]))
                   for l in range(4))

    # prefill: the 11 prompt tokens in one piece (a bucket of 16); decode:
    # three chunks of 4 steps from position 11 on (the first token comes
    # from prefill, the last is never fed back)
    stats = eng.stats()
    count = moe_bytes.counts(stats, "prefill")
    assert count == {"rows": 4 * 11, "assignments": held[:, :11].sum(),
                     "touched": touched(0, 11), "visits": 4 * 4}
    count = moe_bytes.counts(stats, "decode")
    assert count == {"rows": 4 * 12, "assignments": held[:, 11:23].sum(),
                     "touched": sum(touched(t, t + 1) for t in range(11, 23)),
                     "visits": 4 * 4 * 12}
    assert moe_bytes.steps(cfg, count) == 12
    assert stats["serving.moe_expert_bytes"] == (
        moe_bytes.expert_bytes(cfg, itemsize=4))
    assert stats["serving.moe_experts_held"] == 4
    assert stats["serving.moe_layers"] == 4


def test_the_seeded_expert_bias_balances_the_routers_load():
    """``make_params`` settles each routed layer's ``expert_bias`` on
    the reference's own forward: over fresh tokens every expert is then
    selected nearly equally often, where the same router with a zero
    bias loads them unevenly; the bias selects only (the weights of the
    selected experts still add up to ``route_scale``)."""
    import jax.numpy as jnp

    family = families.of(TINY, "serve")
    params = family.make_params(TINY, 64, 5)
    tokens = jnp.asarray(np.random.default_rng(9).integers(
        0, TINY["vocab_size"], (64, 32)), jnp.int32)

    def spread(p):
        seen = []
        family.logits(p, tokens, TINY, seen=seen)
        load = np.stack([np.bincount(np.asarray(s).ravel(), minlength=16)
                         for s in seen]) / (tokens.size * 4 / 16)
        return float(np.sqrt(np.mean(np.square(load - 1))))

    bias = [np.asarray(params[f"block{i}_router.bias"], np.float32)
            for i in range(1, 5)]
    assert all(b.any() and abs(b.mean()) < 1e-3 for b in bias)
    # root mean square of (an expert's selections over its even share)
    # - 1: 0.09 balanced (the rows of a sequence are not independent
    # draws), 0.22 for the same weights with the bias taken away
    even = spread(params)
    skewed = spread({k: (jnp.zeros_like(v) if k.endswith("router.bias")
                         else v) for k, v in params.items()})
    assert even < 0.13 and skewed > 0.17, (even, skewed)


def test_rows_the_reference_cannot_decide_are_left_out_and_counted():
    """``family.logits`` gives zeros (a gap of 0 for any token) for the
    rows whose selection, in any routed layer, is within the
    configuration's margin of one that differs in a held expert, the
    reference's logits for every other row, and says how many it left
    out."""
    import jax.numpy as jnp

    family = families.of(TINY, "serve")
    params = family.make_params(TINY, 64, 11)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, TINY["vocab_size"], (2, 40)), jnp.int32)
    ties = []
    plain = np.asarray(family.logits(dict(params), tokens, dict(
        TINY, check_undecided_margin=0.0), ties=ties))
    least = np.min([np.asarray(m) for m in ties[:4]], axis=0)     # [2, 40]
    assert len(ties) == 4 and plain.any(-1).all() and (least > 0).all()
    assert family.undecided[-1] == (0, 80)
    margin = float(np.median(least))
    got = np.asarray(family.logits(params, tokens, dict(
        TINY, check_undecided_margin=margin)))
    out = least < margin
    assert 0 < out.sum() < 80 and family.undecided[-1] == (out.sum(), 80)
    assert not got[out].any() and np.array_equal(got[~out], plain[~out])


SWITCHES = {"routed_part_left_out": {"routed": False},
            "attention_gate_left_out": {"attention_gate": False},
            "rotary_moved_to_the_full_layer": {"rotary_on": "full"},
            "window_bound_left_out": {"windowed": False}}


@pytest.mark.parametrize("weakened", list(SWITCHES) + ["fp8_matrices"])
def test_what_fails_the_cells_check(weakened, monkeypatch):
    """The check bites: the reference with one line of the mathematics
    left out or moved no longer rates the engine's tokens within the
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
    assert not wrong["correct"]
    assert wrong["facts"]["worst_logit_margin"] > 2 * 0.03
