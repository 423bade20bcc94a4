"""The ``sink_window_moe`` family (``families/sink_window_moe.py``,
``sink_window_moe_reference.py``, ``configs/mimo-v2.5.json``,
``mixed_kv_bytes.py`` and the readers ``swa.decode_stream_roofline``,
``swa.paged_attention_roofline`` and ``kv.window_held_share``): the sizes
shape-only code reads, the byte arithmetic the cell's geometry rests on,
the configuration held to the catalog's row, the reference held to the
program's copy, ``mixed_kv_bytes`` against hand counts, the readers on
hand-made facts (a hand-made trace among them), the seeded sink's share
of a window's mass at the published widths, and the serving runner end to
end on the CPU at a tiny size of the family with the check biting on
each line of the mathematics left out."""

import json
import os

import numpy as np
import pytest

from chipbench import families, flops, mixed_kv_bytes
from chipbench import run as bench_run

CFG = bench_run._read_json(bench_run.HERE, "configs", "mimo-v2.5.json")
MIX = bench_run._read_json(bench_run.HERE, "traffic", "long_reason.json")
PEAK = flops.peaks("TPU v5 lite")
CELL = "mimo25.long_reason"
GPT = bench_run._read_json(bench_run.HERE, "configs",
                           "cerebras-gpt-1.3b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the published layout at a width the CPU can run: keys of 24 lanes over
# values of 16, 8 of the 24 rotated, one K/V head on full planes and two
# on window planes, a dense layer and four routed ones, 4 of 16 experts
# held (4..7), top 4, a window of 8
TINY = {"name": "tiny-sink-window-moe", "family": "sink_window_moe",
        "hidden_size": 64, "head_dim": 24, "v_head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 1,
        "swa_num_key_value_heads": 2, "partial_rotary_factor": 0.334,
        "intermediate_size": 128, "moe_intermediate_size": 48,
        "num_hidden_layers": 5, "hybrid_layer_pattern": [0, 1, 1, 0, 1],
        "moe_layer_freq": [0, 1, 1, 1, 1], "n_routed_experts": 4,
        "router_width": 16, "experts_first": 4, "num_experts_per_tok": 4,
        "norm_topk_prob": True, "attention_value_scale": 0.707,
        "add_swa_attention_sink_bias": True, "layernorm_epsilon": 1e-5,
        "rope_theta": 10000000, "swa_rope_theta": 10000,
        "sliding_window": 8, "vocab_size": 256, "compute_dtype": "bfloat16",
        "sink_logit_range": [0.5, 2.0], "check_undecided_margin": 0.0,
        "expert_bias_tokens": [64, 32]}
SERVE = {"runner": "serve", "chips": 1,
         "engine": {"max_len": 64, "max_slots": 4, "block_tokens": 4,
                    "cache_blocks": 0, "prefix_reuse": False},
         "rate_per_s": 6.0, "schedule_seed": 5,
         "shared_heads": {"count": 0, "tokens": 0, "zipf_s": 1.0},
         "prompt_tail": {"dist": "lognormal", "median": 14, "sigma": 0.5,
                         "min": 4, "max": 28},
         "output": {"dist": "lognormal", "median": 16, "sigma": 0.4,
                    "min": 8, "max": 30},
         "drain_seconds": 60, "warmup_timeout_s": 300,
         "trace_seconds": 1.0,
         # at this size the sound program's worst gap is 0.0-0.0012 over
         # seeds; the variants of SWITCHES read 0.08-0.14 (sink), 0.11
         # (value scale), 0.15-0.28 (windows attended whole), 0.019-0.033
         # (norm_topk_prob), fp8 matrices more.  The second theta is not
         # among them: at a width of 64 matrices of 0.02 make scores near
         # zero, attention near uniform and the rotation invisible (0.001);
         # tests/test_sink_window_moe.py has it in float32 at weights of
         # 0.2, and the cell's check at the published width
         "check": {"sample": 4, "logit_margin": 0.006}}
SEED = 2 ** 31 + 46

FULL_ATT = 4096 * (64 * 192 + 4 * 320) + 8192 * 4096
WINDOW_ATT = 4096 * (64 * 192 + 8 * 320) + 8192 * 4096
EXPERT = 3 * 4096 * 2048
OUTSIDE = (2 * FULL_ATT + 5 * WINDOW_ATT + 3 * 4096 * 16384
           + 6 * 4096 * 256 + 4096 * 19072)


def test_sizes_and_bytes_of_the_configuration_as_it_is_run():
    assert (FULL_ATT, WINDOW_ATT, EXPERT) == (89_128_960, 94_371_840,
                                              25_165_824)
    size = families.sizes(CFG)
    assert size["matmul_params"] == OUTSIDE + 6 * 0.5 * EXPERT
    assert (size["d_model"], size["heads"], size["head_dim"],
            size["vocab_rows"], size["kv_planes"]) == (4096, 64, 192,
                                                       19072, 7)
    moe = families.of(CFG).moe_sizes(CFG)
    assert moe == {"moe_layers": 6, "experts_held": 16, "router_width": 256,
                   "top_k": 8, "expert_params": EXPERT,
                   "expert_ops_per_row": 2 * EXPERT,
                   "outside_params": OUTSIDE}
    held = OUTSIDE + 4096 * 19072 + 6 * 16 * EXPERT
    assert round(held / 1e6, 1) == 3429.9        # 6.86 GB of bf16
    mixed = families.of(CFG).mixed_sizes(CFG)
    assert mixed == {"planes": {"full": 2, "window": 5},
                     "kv_heads": {"full": 4, "window": 8}, "key_lanes": 192,
                     "value_lanes": 128, "heads": 64, "window": 128}
    assert mixed_kv_bytes.position_bytes(CFG, "full") == 2560
    assert mixed_kv_bytes.position_bytes(CFG, "window") == 5120
    # the cell's geometry: what fits ONLY because window planes hold
    # their window
    eng = MIX["engine"]
    slots, positions = eng["max_slots"], eng["max_len"]
    full = 2 * slots * positions * 2560
    windows = 5 * slots * 128 * 5120
    whole = 7 * slots * positions * 2560 + 5 * slots * positions * 2560
    assert round(full / 1e9, 2) == 1.64 and round(windows / 1e9, 2) == 0.08
    assert round(whole / 1e9, 1) == 9.8
    chip = 16 * 2 ** 30
    assert 2 * held + whole > 0.95 * chip         # no room for a piece
    assert 2 * held + full + windows < 0.55 * chip
    assert (MIX["prompt_tail"]["max"] + MIX["output"]["max"]
            == positions == 13312)
    assert MIX["shared_heads"]["count"] == 0 and not eng["prefix_reuse"]
    assert MIX["schedule_seed"] == 20260946 and eng["cache_blocks"] == 0


def test_configuration_holds_the_catalogs_keys_and_says_what_it_cut():
    bench = bench_run._read_json(bench_run.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "mimo-v2.5")
    cut = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "n_routed_experts", "vocab_size"]
    assert entry["reduced"] == cut == CFG["reduced"]
    assert CFG["source"] == entry["source"]
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == "MiMo-V2.5")
        assert CFG["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cut:
                assert CFG[key] == value, key
        assert CFG["hybrid_layer_pattern"] == row["config"][
            "hybrid_layer_pattern"][:7]
        assert CFG["moe_layer_freq"] == row["config"]["moe_layer_freq"][:7]
        assert CFG["published"]["num_hidden_layers"] == row["config"][
            "num_hidden_layers"]
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"]) == (7, 16, 19072)
    assert CFG["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert CFG["published"]["n_routed_experts"] == CFG["router_width"] == 256
    assert CFG["published"]["vocab_size"] == 8 * CFG["vocab_size"]
    assert CFG["experts_first"] == 0 and "sixteen chips" in CFG["deployment"]
    assert {"sink", "value_scale", "rotary", "window",
            "attention_chunk_size", "qk_norm", "norms", "routing",
            "left_out", "layers_kept", "init"} <= set(CFG["assumed"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mimo-v2.5", "long_reason", 1)
    assert f"{MIX['rate_per_s']:g} req/s" in cell["why"]
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    # no reader that counts every matmul parameter once a step, K/V from
    # planes x heads, or the routed layer through hybrid_sizes
    assert not listed & {"step.decode_stream_roofline",
                         "hybrid.decode_stream_roofline",
                         "moe.decode_stream_roofline",
                         "moe.expert_matmul_roofline",
                         "moe.untouched_expert_share",
                         "paged_attention_roofline",
                         "paged_attention_named_roofline",
                         "paged_attention_window_roofline",
                         "sched.prefix_hit_share"}
    # nor the tokens completed in the window: a request here lives a
    # quarter of it, and what the window's end cuts off moves with the
    # seed's routing by more than half that metric's bound (PERF.md,
    # PR 46); the generator's lateness moves that metric, so it goes too
    assert not listed & {"serve_tokens_per_s", "gen.late_ms_p90"}
    assert {"swa.decode_stream_roofline", "swa.paged_attention_roofline",
            "kv.window_held_share", "tpot_p90_ms",
            "paged.rows_per_update", "paged.skipped_entry_share",
            "sched.block_occupancy_peak", "serve.ttft_p90_ms",
            "device.idle_share.serve"} <= listed
    for name in ("swa.decode_stream_roofline", "swa.paged_attention_roofline",
                 "kv.window_held_share"):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
        reader = bench_run.load_reader(name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.SOURCE,
                reader.MOVES) == (name, metric["unit"], metric["layer"],
                                  metric["source"], metric["moves"])


def test_the_family_serves_and_does_not_train():
    assert families.of(CFG, "serve").__name__ == (
        "chipbench.families.sink_window_moe")
    with pytest.raises(SystemExit) as err:
        families.of(CFG, "train")
    assert "does not train" in str(err.value)


def test_reference_is_the_programs_copy_and_imports_nothing_of_it():
    def body(path):
        text = open(path).read()
        return text[text.index("import functools"):]

    mine = os.path.join(bench_run.HERE, "families",
                        "sink_window_moe_reference.py")
    theirs = os.path.join(bench_run.ROOT, "paddle_tpu", "models",
                          "sink_window_moe_reference.py")
    assert body(mine) == body(theirs)
    assert "paddle_tpu" not in body(mine)
    assert "import" not in body(mine).replace(
        "import functools\n\nimport jax\nimport jax.numpy as jnp\n", "")


def test_mixed_kv_bytes_against_hand_counts():
    assert mixed_kv_bytes.sizes(GPT) is None
    # one decode position at 6,000 attended tokens
    assert mixed_kv_bytes.attended(CFG, "full", 6000) == 6000
    assert mixed_kv_bytes.attended(CFG, "window", 6000) == 128
    assert mixed_kv_bytes.attended(CFG, "window", 50) == 50
    ops, nbytes = mixed_kv_bytes.paged_call(CFG, "full", 6000)
    assert (ops, nbytes) == (2 * 64 * 320 * 6000, 6000 * 2560)
    assert mixed_kv_bytes.kv_bytes(CFG, [6000]) == (
        2 * 6000 * 2560 + 5 * 128 * 5120) == 30_720_000 + 3_276_800
    # memory-bound on both kinds: the least seconds are the bytes'
    least = mixed_kv_bytes.least_seconds(CFG, [6000, 100], PEAK)
    want = (2 * (6000 + 100) * 2560 + 5 * (128 + 100) * 5120) \
        / PEAK["hbm_bytes_per_s"]
    assert abs(least - want) < 1e-12
    # a step of 10 live slots at 6,000 that touched 4.4 experts a layer
    step = mixed_kv_bytes.decode_step_bytes(CFG, 6 * 4.4, [6000] * 10, 1)
    assert step == 2 * (OUTSIDE + EXPERT * 6 * 4.4) + 10 * 33_996_800
    assert 3.5e9 < step < 3.6e9


def _stats(decode):
    return {f"serving.moe_{name}{{phase=decode}}": float(value)
            for name, value in decode.items()}


# 1000 decode steps of 10 live slots: 5 pairs and 4.4 experts a layer
DECODE = {"rows": 1000 * 6 * 10, "assignments_held": 1000 * 6 * 5,
          "experts_touched": 1000 * 6 * 4.4, "expert_visits": 1000 * 6 * 16}


def _requests(n, prompt, out):
    return [{"prompt_len": prompt, "out": out, "first": 1.0, "finish": 5.0,
             "prefix_hit": 0} for _ in range(n)]


def test_decode_stream_roofline_on_hand_made_facts():
    reader = bench_run.load_reader("swa.decode_stream_roofline")
    requests = _requests(10, 5000, 1001)       # 10 x 1000 decode positions
    stats = dict(_stats(DECODE), **{
        "serving.step_seconds": {"count": 250, "p50": 0.005}})
    facts = {"stats": stats, "peak": PEAK, "config": CFG,
             "requests": requests, "decode_chunk": 4}
    kv = sum(2 * (5000 + i) * 2560 + 5 * 128 * 5120
             for i in range(1, 1001)) * 10 / 1000
    want = (2 * (OUTSIDE + EXPERT * 6 * 4.4) + kv) \
        / PEAK["hbm_bytes_per_s"] / 0.005
    assert abs(reader.read(facts) - 100 * want) < 1e-9
    assert 80 < reader.read(facts) < 100
    assert reader.read(dict(facts, config=GPT)) is None
    assert reader.read(dict(facts, stats={
        "serving.step_seconds": stats["serving.step_seconds"]})) is None
    assert reader.read(dict(facts, stats=_stats(DECODE))) is None


def test_window_held_share_on_hand_made_facts():
    reader = bench_run.load_reader("kv.window_held_share")
    assert reader.read({"stats": {}}) is None
    assert reader.read({"stats": {"serving.window_blocks_held": 5000.0,
                                  "serving.window_blocks_whole": 188000.0}
                        }) == pytest.approx(100 * 5 / 188)
    assert reader.read({"stats": {"serving.window_blocks_whole": 10.0,
                                  "serving.window_blocks_held": 10.0}}) == 100


def test_paged_attention_roofline_holds_the_count_to_a_hand_made_trace(
        monkeypatch):
    reader = bench_run.load_reader("swa.paged_attention_roofline")
    call = ('%paged_attention.{n} = bf16[24,8,8,128] custom-call(...), '
            'custom_call_target="tpu_custom_call"')
    ops = {("m", f"paged_attention.{n}"): {
        "seconds": 0.001, "provenance": call.format(n=n)} for n in range(7)}
    ops[("m", "fusion.3")] = {"seconds": 0.5,
                              "provenance": "%fusion.3 = f32[] fusion()"}
    # 4 requests decoding all through a one-second window, 200 positions
    # each, contexts around 6,000
    requests = [{"prompt_len": 5900, "out": 201, "first": 0.0,
                 "finish": 1.0, "prefix_hit": 0, "prefill_t0": -1.0,
                 "prefill_t1": 0.0} for _ in range(4)]
    facts = {"trace": {"ops": ops}, "trace_span": (0.0, 1.0), "peak": PEAK,
             "config": CFG, "requests": requests}
    named = bench_run.load_reader("paged_attention_named_roofline")
    contexts = named.decode_contexts(requests, 0.0, 1.0)
    assert len(contexts) > 700 and min(contexts) > 5900
    least = sum(2 * n * 2560 + 5 * 128 * 5120 for n in contexts) \
        / PEAK["hbm_bytes_per_s"]
    got = reader.read(facts)
    assert got == pytest.approx(100 * least / 0.007)
    # the program's own spans hold the NUMBER of positions: 150 chunks of
    # 4 live slots and one step where the requests' times gave 800
    monkeypatch.setattr(reader, "chunks", lambda path: (
        [(4, 1)] * 150 if path == "a.xplane.pb" else []))
    assert reader.read(dict(facts, trace_path="a.xplane.pb")) == (
        pytest.approx(got * 600 / len(contexts)))
    assert reader.read(dict(facts, trace_path="b.xplane.pb")) == got
    assert reader.kernels(CFG, MIX) == named.kernels(CFG, MIX)
    assert reader.read(dict(facts, config=GPT)) is None
    assert reader.read(dict(facts, trace={"ops": {
        ("m", "fusion.3"): ops[("m", "fusion.3")]}})) is None
    assert reader.read({"trace": None}) is None


def test_the_seeded_sink_takes_its_share_of_a_windows_mass():
    """At the PUBLISHED widths, one window layer of seeded weights over
    256 positions: the sink logits of ``sink_logit_range`` take between a
    twentieth and three quarters of a full window's mass, head by head
    in the mean, which is what lets a check see the sink left out."""
    import jax
    import jax.numpy as jnp

    z = dict(d=4096, h=64, dh=192, hk=8, dv=128)
    key = jax.random.PRNGKey(46)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (256, z["d"]), jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
    wq = 0.02 * jax.random.normal(k2, (z["d"], z["h"] * z["dh"]))
    wk = 0.02 * jax.random.normal(k3, (z["d"], z["hk"] * z["dh"]))
    q = (x @ wq).reshape(256, z["h"], z["dh"])[128:]          # full windows
    k = (x @ wk).reshape(256, z["hk"], z["dh"])
    lo, hi = CFG["sink_logit_range"]
    for head, sink in ((0, lo), (9, hi), (63, (lo + hi) / 2)):
        s = q[:, head] @ k[:, head // 8].T / np.sqrt(192.0)   # [128, 256]
        at = np.arange(128, 256)[:, None]
        js = np.arange(256)[None]
        s = jnp.where((js <= at) & (js > at - 128), s, -jnp.inf)
        mass = jnp.exp(s).sum(-1)
        share = np.asarray(np.exp(sink) / (np.exp(sink) + mass))
        assert 0.05 < share.mean() < 0.75, (sink, share.mean())
        assert np.quantile(share, 0.1) > 0.03


def _cell():
    bench = bench_run._read_json(bench_run.ROOT, "BENCHMARK.json")
    return {"name": "tiny-sink-window-moe.serve", "chips": 1, "config": TINY,
            "traffic": SERVE, "end_to_end": bench["end_to_end"],
            "per_layer": [m for m in bench["per_layer"]
                          if CELL in m.get("workloads", [])]}


def test_serve_runner_rehearsal_and_what_the_readers_find():
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
    assert {"step.decode_ms", "swa.decode_stream_roofline",
            "kv.window_held_share", "compile.seconds", "serve.ttft_p90_ms",
            "paged.skipped_entry_share", "paged.rows_per_update",
            "sched.block_occupancy_peak",
            "step.prefill_rows_per_piece"} <= set(got)
    assert not any(k.startswith(("device.", "paged_attention")) or
                   k == "swa.paged_attention_roofline" for k in got)
    assert 0 < got["swa.decode_stream_roofline"]["value"] < 100
    assert 10 < got["kv.window_held_share"]["value"] < 90
    assert 0 < got["sched.block_occupancy_peak"]["value"] <= 100
    # two full planes of group 4 and three window planes of group 2, the
    # window planes' entries clipped to their window
    assert 2.0 < got["paged.rows_per_update"]["value"] < 4.0
    stats = facts["stats"]
    assert stats["serving.window_blocks_released"] > 0
    assert stats["serving.paged_entries_live{kind=full}"] > stats[
        "serving.paged_entries_live{kind=window}"] > 0


SWITCHES = {"sink_left_out": {"sink": False},
            "value_scale_left_out": {"value_scale": 1.0},
            "window_planes_attended_whole": {"windowed": False},
            "norm_topk_prob_left_out": {"norm_topk": False}}


def test_undecided_rows_come_back_as_zeros_and_are_counted():
    """``logits`` leaves out exactly the rows whose selection, in any
    routed layer, is within ``check_undecided_margin`` of one that differs
    in a held expert, says how many, and leaves the others as they were."""
    import jax.numpy as jnp

    family = families.of(TINY, "serve")
    params = family.make_params(TINY, 64, SEED)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, TINY["vocab_size"], (2, 40)), jnp.int32)
    ties = []
    plain = np.asarray(family.logits(dict(params), tokens, TINY, ties=ties))
    assert len(ties) == 4 and plain.any(-1).all()      # four routed layers
    least = np.min([np.asarray(m) for m in ties], axis=0)          # [2, 40]
    margin = float(np.median(least))
    got = np.asarray(family.logits(params, tokens, dict(
        TINY, check_undecided_margin=margin)))
    out = least < margin
    assert 0 < out.sum() < 80 and family.undecided[-1] == (out.sum(), 80)
    assert not got[out].any() and np.array_equal(got[~out], plain[~out])


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
    assert wrong["facts"]["worst_logit_margin"] > 2 * 0.006
