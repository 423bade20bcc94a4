"""The ``retention`` family (``families/retention.py``,
``retention_reference.py``, ``configs/brumby-14b-base.json``,
``traffic/doc_continue.json``, ``retention_bytes.py`` and the readers
``retention.decode_stream_roofline``, ``retention.step_kernel_roofline``
and ``retention.chunk_kernel_roofline``): the sizes shape-only code
reads, the byte arithmetic the cell's geometry rests on, the reference
held to the program's copy, ``retention_bytes`` against hand counts, the
readers on hand-made facts (a hand-made trace and a CPU profiler
session's spans among them, and "nothing to read: nothing returned"),
the cell's entries in ``BENCHMARK.json``, and the serving runner end to
end on the CPU at a tiny size of the family, the check biting on each
weakened variant."""

import json
import os

import numpy as np
import pytest

from chipbench import families, flops, retention_bytes
from chipbench import run as bench_run

CFG = bench_run._read_json(bench_run.HERE, "configs", "brumby-14b-base.json")
MIX = bench_run._read_json(bench_run.HERE, "traffic", "doc_continue.json")
PEAK = flops.peaks("TPU v5 lite")
CELL = "brumby14b.doc_continue"
GPT = bench_run._read_json(bench_run.HERE, "configs",
                           "cerebras-gpt-1.3b.json")
# the published layout at a width the CPU can run: 6 query heads over 2
# K/V heads of 16 lanes (136 features a head), three layers
TINY = {"name": "tiny-retention", "family": "retention", "hidden_size": 64,
        "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "num_hidden_layers": 3,
        "rms_norm_eps": 1e-6, "rope_theta": 1000000, "vocab_size": 256,
        "compute_dtype": "bfloat16", "state_dtype": "float32",
        "retention_degree": 2, "retention_eps": 1e-6,
        "gate_horizons": [4, 64],
        # 1 / sqrt(width): at 0.02 a width of 64 leaves every score and
        # every gate's data part near nothing, and no line can be missed
        "initializer_range": 0.125}
SERVE = {"runner": "serve", "chips": 1,
         "engine": {"max_len": 320, "max_slots": 4, "cache_blocks": 0,
                    "prefix_reuse": False},
         "rate_per_s": 5.0, "schedule_seed": 5,
         "shared_heads": {"count": 0, "tokens": 0, "zipf_s": 1.0},
         "prompt_tail": {"dist": "lognormal", "median": 100, "sigma": 0.7,
                         "min": 8, "max": 280},
         "output": {"dist": "lognormal", "median": 14, "sigma": 0.4,
                    "min": 6, "max": 28},
         "drain_seconds": 120, "warmup_timeout_s": 600,
         "trace_seconds": 1.0,
         "check": {"sample": 4, "logit_margin": 0.08}}
SEED = 2 ** 31 + 42

LAYER = (2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408 + 5120 * 8)
STATE = 8 * (8256 * 128 + 8256) * 4


def test_sizes_and_bytes_of_the_configuration_as_it_is_run():
    size = families.sizes(CFG)
    assert LAYER == 330_342_400 and STATE == 34_080_768
    assert size["matmul_params"] == 10 * LAYER + 5120 * 151936 == (
        4_081_336_320)
    assert size["kv_planes"] == 0 and size["attention_passes"] == 10
    assert size["state_bytes_per_slot"] == 10 * STATE == 340_807_680
    rs = retention_bytes.sizes(CFG)
    assert rs == {"layers": 10, "kv_heads": 8, "heads": 40, "head_dim": 128,
                  "state_rows": 8256, "state_bytes": STATE}
    assert retention_bytes.sizes(GPT) is None
    # the cell's geometry: 16 slots of state beside the weights
    held = 10 * (LAYER + 8 + 10_496) + 2 * 5120 * 151936 + 5120
    assert held == 4_859_358_800
    slots = MIX["engine"]["max_slots"]
    assert 12 <= slots <= 16
    assert (2 * held + slots * 10 * STATE) / 2 ** 30 < 15.75
    # the whole model, counted from the equations at the published depth
    assert 40 * (LAYER + 8 + 10_496) + 2 * 5120 * 151936 + 5120 == (
        14_769_945_920)


def test_configuration_holds_the_catalogs_keys_and_says_what_it_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Brumby-14B-Base")
    changed = {k for k, v in row["config"].items() if CFG.get(k, v) != v
               or k not in CFG}
    assert changed == {"num_hidden_layers"} == set(CFG["reduced"])
    assert CFG["published"]["num_hidden_layers"] == 40
    assert CFG["num_hidden_layers"] == 10
    bench = json.load(open(os.path.join(bench_run.ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == CFG["name"])
    assert entry["source"].startswith(row["source_url"])
    assert entry["reduced"] == CFG["reduced"]
    for key in ("degree", "gate", "normaliser", "qk_norm", "rotary",
                "state_dtype", "init"):
        assert key in CFG["assumed"]
    assert "four pipeline stages of ten layers" in CFG["deployment"]


def test_the_cells_entries_in_the_benchmark():
    bench = json.load(open(os.path.join(bench_run.ROOT, "BENCHMARK.json")))
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert len(bench["workloads"]) == 7
    cell = bench_run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == CFG
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tpot_p90_ms", "serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"retention.decode_stream_roofline",
            "retention.step_kernel_roofline",
            "retention.chunk_kernel_roofline", "step.decode_ms",
            "step.attention_busy_share", "device.idle_share.serve",
            "sched.decode_stall_share"} <= names
    # it has no pool: the metrics that read one do not list it
    assert not names & {"sched.block_occupancy_peak",
                        "paged.skipped_entry_share", "paged.rows_per_update",
                        "sched.prefix_hit_share"}
    for m in bench["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_p90_ms"
    assert MIX["engine"]["max_len"] == 9216
    assert not MIX["engine"]["prefix_reuse"]
    assert MIX["engine"]["cache_blocks"] == 0
    assert MIX["schedule_seed"] == 20260942
    assert MIX["prompt_tail"] == {"dist": "lognormal", "median": 2048,
                                  "sigma": 0.7, "min": 256, "max": 8192}
    assert MIX["output"] == {"dist": "lognormal", "median": 384,
                             "sigma": 0.5, "min": 128, "max": 1024}


def test_the_family_serves_and_does_not_train():
    family = families.of(CFG, "serve")
    assert family.__name__.endswith("retention")
    with pytest.raises(SystemExit, match="does not train"):
        families.of(CFG, "train")


def test_reference_is_the_programs_copy_and_imports_nothing_of_it():
    def body(path):
        text = open(path).read()
        return text[text.index('"""\n\nimport functools'):]

    mine = os.path.join(bench_run.HERE, "families", "retention_reference.py")
    theirs = os.path.join(bench_run.ROOT, "paddle_tpu", "models",
                          "retention_reference.py")
    assert body(mine) == body(theirs)
    assert "paddle_tpu" not in body(mine)


def test_retention_bytes_against_hand_counts():
    ops, nbytes = retention_bytes.step(CFG)
    assert nbytes == 2 * STATE
    values = 8 * 8256 * 129
    assert ops == 3 * values + 2 * 40 * 8256 * 129
    # 83 us a slot a layer at the HBM peak, far over its operations
    assert retention_bytes.least_seconds(ops, nbytes, PEAK) == (
        nbytes / 819e9)
    assert 83e-6 < nbytes / 819e9 < 84e-6
    ops, nbytes = retention_bytes.piece(CFG, 128)
    assert ops == (4 * 40 * 128 * 128 * 128 + 2 * 40 * 128 * 8256 * 129
                   + 2 * 8 * 128 * 8256 * 129)
    # 104 MFLOP a token a layer, as the issue counts it
    assert 101e6 < ops / 128 < 105e6
    assert nbytes == 2 * STATE + 128 * 128 * 2 * 96
    assert retention_bytes.decode_step_bytes(CFG, 4_081_336_320, 6) == (
        2 * 4_081_336_320 + 6 * 10 * 2 * STATE)


def _request(prompt_len, out, first=1.0, finish=2.0, t0=0.5):
    return {"prompt_len": prompt_len, "prefix_hit": 0, "out": out,
            "first": first, "finish": finish, "prefill_t0": t0,
            "prefill_t1": first}


def _trace(*ops, busy=1.0):
    return {"busy_s": busy, "ops": {
        f"op{i}": {"seconds": s, "provenance": text}
        for i, (text, s) in enumerate(ops)}}


def test_decode_stream_roofline_on_hand_made_facts():
    reader = bench_run.load_reader("retention.decode_stream_roofline")
    # 25 chunks of 4 steps at 6 slots live: 6 x 10 layers x 100 steps
    stats = {"serving.step_seconds": {"count": 25, "p50": 0.016},
             "serving.retention_slot_steps": 6000.0}
    facts = {"stats": stats, "peak": PEAK, "config": CFG, "decode_chunk": 4,
             "requests": []}
    want = 100 * (2 * 4_081_336_320 + 6 * 10 * 2 * STATE) / 819e9 / 0.016
    assert reader.read(facts) == pytest.approx(want)
    assert 75 < want < 100
    # nothing to read: a program with no such counter, another family
    assert reader.read(dict(facts, stats={
        "serving.step_seconds": stats["serving.step_seconds"]})) is None
    assert reader.read(dict(facts, config=GPT)) is None


def _spans(tmp_path, chunks, prefills):
    """A profiler session on the CPU that holds the driver's spans as the
    engine annotates them: the path of its ``.xplane.pb``."""
    import jax

    from chipbench import trace_reduce
    from paddle_tpu.observability import trace as program_trace

    tracer = program_trace.get_tracer()
    jax.profiler.start_trace(str(tmp_path))
    for active, steps in chunks:
        with tracer.span("serving.decode_chunk", cat="serving", steps=steps,
                         active=active, retention_layers=10,
                         attn_form="retention"):
            pass
    for bucket, pieces in prefills:
        with tracer.span("serving.prefill", cat="serving", bucket=bucket,
                         pieces=pieces, retention_layers=10,
                         attn_form="retention"):
            pass
    # a span of a program with no retention layer carries no such attribute
    with tracer.span("serving.decode_chunk", cat="serving", steps=4,
                     active=9):
        pass
    jax.profiler.stop_trace()
    return trace_reduce.find_xplane(str(tmp_path))


def test_kernel_rooflines_hold_the_counts_to_the_programs_spans(tmp_path):
    call = 'custom-call(...), custom_call_target="tpu_custom_call"'
    trace = _trace((f"%retention_step.3 = f32[16,8,8,128] {call}", 0.030),
                   (f"%retention_step.7 = f32[16,8,8,128] {call}", 0.020),
                   (f"%retention_chunk.2 = f32[8,640,128] {call}", 0.010),
                   ("%fusion.9 = bf16[16,5120] fusion(...)", 0.5))
    # two chunks of 4 steps at 6 and 5 live slots; one admission of 300
    # rows (128, 128, 64) and one of 8
    path = _spans(tmp_path, [(6, 4), (5, 4)], [(320, 3), (8, 1)])
    facts = {"trace": trace, "trace_path": path, "requests": [],
             "config": CFG, "peak": PEAK}
    step = bench_run.load_reader("retention.step_kernel_roofline")
    assert step.read(facts) == pytest.approx(
        100 * (6 + 5) * 4 * 10 * (2 * STATE / 819e9) / 0.050)
    chunk = bench_run.load_reader("retention.chunk_kernel_roofline")
    assert chunk.widths(320, 3) == [128, 128, 64]
    least = sum(retention_bytes.least_seconds(
        *retention_bytes.piece(CFG, w), PEAK) for w in (128, 128, 64, 8))
    assert chunk.read(facts) == pytest.approx(100 * 10 * least / 0.010)
    # nothing to read: no call of that name, another family, no trace, a
    # program whose spans carry no such attribute
    bare = dict(facts, trace=_trace(("%fusion.1 = f32[8]", 1.0)))
    assert step.read(bare) is None and chunk.read(bare) is None
    assert step.read(dict(facts, config=GPT)) is None
    assert chunk.read(dict(facts, trace=None)) is None
    assert step.spans(trace_reduce_load(path), "serving.decode_chunk",
                      "active", "steps") == [(6, 4), (5, 4), (9, 4)]
    assert step.spans(trace_reduce_load(path), "serving.decode_chunk",
                      "no_such_attribute") == []
    assert step.kernels(CFG, MIX) == {
        "retention_step": ("%retention_step", step.CALL)}


def trace_reduce_load(path):
    from chipbench import trace_reduce

    return trace_reduce.load(path)


def _cell():
    bench = json.load(open(os.path.join(bench_run.ROOT, "BENCHMARK.json")))
    return {"name": CELL, "chips": 1, "config": TINY, "traffic": SERVE,
            "end_to_end": bench["end_to_end"],
            "per_layer": [m for m in bench["per_layer"]
                          if CELL in m.get("workloads", [CELL])]}


def test_serve_runner_rehearsal_with_the_counters():
    from chipbench.runners import serve

    cell = _cell()
    result = serve.run(cell, seed=SEED, seconds=2.0, tracer=None)
    facts = result["facts"]
    assert result["correct"], facts["worst_logit_margin"]
    assert result["failed"] == 0 and result["attempted"] == 10
    assert facts["pool_blocks"] == 0 and facts["blocks_in_use_max"] == 0
    assert max(r["prompt_len"] for r in facts["requests"]) > 128
    facts.update(config=TINY, traffic=SERVE, chips=1, trace=None,
                 trace_window_s=None,
                 peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    got = bench_run.layer_metrics(cell, facts)
    assert {"step.decode_ms", "retention.decode_stream_roofline",
            "compile.seconds", "serve.ttft_p90_ms",
            "sched.slot_occupancy_peak", "step.prefill_ms_per_token",
            "sched.decode_stall_share"} <= set(got)
    assert not any(k.startswith(("device.", "paged.")) or "kernel" in k
                   for k in got)
    assert got["retention.decode_stream_roofline"]["value"] > 0
    stats = facts["stats"]
    # (the runner zeroes the registry after its warm pass, gauges too:
    # tests/test_retention_arch.py reads those off a fresh engine)
    assert not any(k.startswith("serving.paged_") for k in stats)
    # live slots x layers x steps: between the steps that emitted a token
    # and those plus what a finished slot rides out of its last chunk
    emitted = sum(r["out"] - 1 for r in facts["requests"])
    counted = stats["serving.retention_slot_steps"] / 3
    assert emitted <= counted <= emitted + len(facts["requests"]) * (
        facts["decode_chunk"] - 1)
    rows = sum(v for k, v in stats.items()
               if k.startswith("serving.retention_piece_rows"))
    assert rows == sum(r["bucket"] for r in facts["requests"])


SWITCHES = {"gate_left_out": {"gate": False},
            "normaliser_left_out": {"normaliser": False},
            "degree_one": {"degree": 1},
            "rotary_left_out": {"rotary": False},
            "state_zeroed_at_every_piece": {"piece": 128},
            "group_read_wrongly": {"grouped": False}}


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
    wrong = serve.run(_cell(), seed=SEED, seconds=2.0, tracer=None)
    assert not wrong["correct"]
    assert wrong["facts"]["worst_logit_margin"] > 2 * 0.08
