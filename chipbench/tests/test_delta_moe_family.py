"""The ``delta_moe`` family (``families/delta_moe.py``,
``delta_moe_reference.py``, ``configs/solar-open2-250b.json``,
``delta_bytes.py`` and the six readers PR 57 brought): the sizes
shape-only code reads, the byte arithmetic the cell's geometry rests on,
the configuration against the catalog's keys, the reference importing
nothing of the program, the counts of ``delta_bytes`` against hand
counts, the readers on hand-made facts (spans counted inside the traced
window's interval only, a share over 105 refused), and the serving
runner end to end on the CPU at a tiny size of the family, shared heads
served from state snapshots, with the check biting on each line of the
mathematics left out."""

import json
import os
import types

import numpy as np
import pytest

from chipbench import delta_bytes, families, flops, trace_reduce
from chipbench import run as bench_run

CFG = bench_run._read_json(bench_run.HERE, "configs", "solar-open2-250b.json")
MIX = bench_run._read_json(bench_run.HERE, "traffic", "doc_qa_64k.json")
PEAK = flops.peaks("TPU v5 lite")
CELL = "solaro2.doc_qa_64k"
GPT = bench_run._read_json(bench_run.HERE, "configs",
                           "cerebras-gpt-1.3b.json")
# the published layout at a width the CPU can run: one period G D D D, 2
# K/V heads under 4 query heads, 4 of 16 experts held (4..7), top 3
TINY = {"name": "tiny-delta-moe", "family": "delta_moe", "hidden_size": 64,
        "num_hidden_layers": 4, "gqa_layers": [0],
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                               "num_heads": 4, "num_kv_heads": None},
        "moe_intermediate_size": 40, "n_routed_experts": 4,
        "router_width": 16, "experts_first": 4, "num_experts_per_tok": 3,
        "routed_scaling_factor": 1, "norm_topk_prob": True,
        "kda_allow_neg_eigval": True, "rms_norm_eps": 1e-5,
        "time_step_min": 0.001, "time_step_max": 0.1, "vocab_size": 128,
        "compute_dtype": "bfloat16", "check_undecided_margin": 0.002,
        "expert_bias_tokens": 256}
# two shared heads of 64 tokens over blocks of 16: the warm-up's head + 8
# ends a piece of 32 ON the head's last block, and 192 cache blocks buy
# three snapshot rows
SERVE = {"runner": "serve", "chips": 1,
         "engine": {"max_len": 160, "max_slots": 4, "block_tokens": 16,
                    "cache_blocks": 192, "prefix_reuse": True},
         "rate_per_s": 6.0, "schedule_seed": 5,
         "shared_heads": {"count": 2, "tokens": 64, "zipf_s": 1.0},
         "prompt_tail": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                         "min": 2, "max": 24},
         "output": {"dist": "lognormal", "median": 14, "sigma": 0.4,
                    "min": 6, "max": 28},
         "drain_seconds": 60, "warmup_timeout_s": 300,
         "trace_seconds": 1.0,
         "check": {"sample": 4, "logit_margin": 0.1}}
SEED = 2 ** 31 + 57

D = 4096
GQA = 3 * D * 8192 + 2 * D * 1024
DELTA = 4 * D * 8192 + 2 * (D * 128 + 128 * 8192) + D * 64
EXPERT = 3 * D * 1280
ROUTED = D * 320 + EXPERT                 # router and shared expert
OUTSIDE = GQA + 3 * DELTA + 4 * ROUTED + D * 24576
STATE = 4 * 64 * 128 * 128


def test_sizes_and_bytes_of_the_configuration_as_it_is_run():
    assert (GQA, DELTA, EXPERT) == (109_051_904, 137_625_600, 15_728_640)
    assert OUTSIDE == 690_749_440
    assert families.sizes(CFG) == {
        "d_model": D, "heads": 64, "head_dim": 128, "vocab_rows": 24_576,
        "matmul_params": OUTSIDE + 4 * EXPERT, "kv_planes": 1,
        "attention_passes": 1}
    more = families.of(CFG).delta_sizes(CFG)
    assert more["state_bytes"] == STATE == 4_194_304
    assert (more["delta_layers"], more["gqa_layers"], more["moe_layers"],
            more["experts_held"], more["router_width"], more["top_k"]) == (
        3, 1, 4, 40, 320, 8)
    assert more["kv_bytes_per_token"] == 4096
    # the cell's geometry: 42,625 blocks of 131,072 B, 16 slots of
    # 13,025,280 B, 11 snapshot rows (an eighth of the cache's K/V bytes)
    geo = MIX["engine"]
    nb = geo["max_len"] // geo["block_tokens"]
    blocks = 1 + geo["max_slots"] * nb + geo["cache_blocks"]
    assert (nb, blocks) == (2088, 42_625)
    block_bytes = geo["block_tokens"] * more["kv_bytes_per_token"]
    assert blocks * block_bytes == 5_586_944_000
    slot = 3 * (STATE + 3 * 3 * 8192 * 2)
    assert slot == 13_025_280
    assert geo["cache_blocks"] * block_bytes // 8 // slot == 11
    assert 2 * CFG["parameters_held"] == 6_616_755_840
    assert MIX["shared_heads"]["tokens"] % 512 == 0
    assert (MIX["shared_heads"]["tokens"] + 8) % geo["block_tokens"]


def test_configuration_holds_the_catalogs_keys_and_says_what_it_cut():
    bench = bench_run._read_json(bench_run.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "solar-open2-250b")
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]
    assert CFG["source"] == entry["source"]
    # every number of the catalog's config under its key; none of the
    # widths is in the cut
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3, "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    assert {k: CFG[k] for k in published} == published
    assert CFG["published"] == {
        "num_hidden_layers": 48, "gqa_layers": list(range(0, 48, 4)),
        "n_routed_experts": 320, "vocab_size": 196608}
    assert (CFG["num_hidden_layers"], CFG["gqa_layers"],
            CFG["n_routed_experts"], CFG["vocab_size"]) == (4, [0], 40, 24576)
    assert CFG["router_width"] == 320 and CFG["experts_first"] == 0
    assert "eight chips" in CFG["deployment"] and "96" in CFG["deployment"]
    assert CFG["parameters_held"] == families.of(CFG).parameters(CFG)
    assert {"gqa_gate", "qk_norm", "biases", "norm_eps", "state_dtype",
            "routing", "expert_bias", "group_limit", "mtp", "gate_rank",
            "init"} <= set(CFG["assumed"])
    assert all(len(why) > 40 for why in CFG["assumed"].values())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b", "doc_qa_64k", 1)
    assert f"{MIX['rate_per_s']:g} req/s" in cell["why"]
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    mine = {"kda.step_kernel_roofline", "kda.chunk_kernel_roofline",
            "kda_moe.decode_stream_roofline",
            "kda_moe.paged_attention_roofline",
            "kda_moe.expert_matmul_roofline", "state.snapshot_hit_share"}
    assert mine | {"tpot_p90_ms", "serve_tokens_per_s",
                   "sched.prefix_hit_share", "paged.shared_entry_share",
                   "step.mixer_busy_share", "tail.tpot_ms",
                   "device.idle_share.serve", "compile.seconds"} <= listed
    # no reader of another family's bytes reports here
    assert not any(n.startswith(("dsa.", "ssm", "moe.", "mla.", "swa",
                                 "retention.", "hybrid.")) for n in listed)
    for name in mine:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "tpot_p90_ms"
    assert len(bench["workloads"]) == 11
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_the_family_serves_and_does_not_train():
    assert families.of(CFG, "serve").__name__ == (
        "chipbench.families.delta_moe")
    with pytest.raises(SystemExit) as err:
        families.of(CFG, "train")
    assert "does not train" in str(err.value)


def test_reference_imports_nothing_of_the_program():
    text = open(os.path.join(bench_run.HERE, "families",
                             "delta_moe_reference.py")).read()
    body = text[text.index("import functools"):]
    assert "paddle_tpu" not in body
    assert "import" not in body.replace(
        "import functools\n\nimport jax\nimport jax.numpy as jnp\n"
        "import numpy as np\n", "")
    assert "lax.scan(one, S," in body        # one position at a time


def test_delta_bytes_against_hand_counts():
    assert delta_bytes.sizes(GPT) is None
    assert delta_bytes.step(CFG) == (7 * 64 * 128 * 128, 2 * STATE)
    # 10.2 us a live slot a layer at 819 GB/s
    assert delta_bytes.least_seconds(*delta_bytes.step(CFG), PEAK) == (
        pytest.approx(10.24e-6, rel=0.01))
    ops, nbytes = delta_bytes.piece(CFG, 512)
    assert nbytes == 2 * STATE + 512 * (3 * 8192 * 2 + 8192 * 4 + 64 * 4
                                        + 8192 * 4)
    assert ops == 512 * 64 * (4 * 128 * 65 + 64 * 256 + 6 * 128 * 128
                              + 64 * 128)
    # a narrow piece is one short tile
    assert delta_bytes.piece(CFG, 8)[0] == 8 * 64 * (
        4 * 128 * 9 + 8 * 256 + 6 * 128 * 128 + 8 * 128)
    assert delta_bytes.attention(CFG, 65_536 + 300) == (
        4 * 8192 * 65_836, 65_836 * 4096)
    # one step, 3 slots live at 65,836 positions, 21 (expert, layer) pairs
    assert delta_bytes.decode_step_bytes(CFG, 21, 3, 3 * 65_836) == (
        2 * (OUTSIDE + 21 * EXPERT) + 3 * 3 * 2 * STATE
        + 3 * 65_836 * 4096)
    count = {"visits": 1000 * 4 * 40}
    assert delta_bytes.steps(CFG, count) == 1000
    assert delta_bytes.expert_call_seconds(CFG, 5, 8, PEAK) == (
        pytest.approx(5 * EXPERT * 2 / PEAK["hbm_bytes_per_s"]))
    assert delta_bytes.share("x", 104.9) == 104.9
    assert delta_bytes.share("x", 105.1) is None


def _trace(*ops):
    return {"ops": {f"op{i}": {"calls": 10, "seconds": s, "provenance": p}
                    for i, (p, s) in enumerate(ops)}}


STEP = ('%delta_step.3 = f32[16,8,8,128] custom-call(...), '
        'custom_call_target="tpu_custom_call"')


def test_step_kernel_roofline_counts_the_spans_inside_the_window(
        monkeypatch):
    reader = bench_run.load_reader("kda.step_kernel_roofline")
    least = delta_bytes.least_seconds(*delta_bytes.step(CFG), PEAK)

    def span(start, **stats):
        return types.SimpleNamespace(name="serving.decode_chunk",
                                     start_ns=start, duration_ns=5,
                                     stats=list(stats.items()))

    # two chunks inside [100, 200), one before it and one after: only the
    # two inside count (2 x 4 + 1 x 2 slot-steps of 3 layers)
    profile = types.SimpleNamespace(planes=[types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(events=[
            span(50, active=9, steps=4, delta_layers=3),
            span(100, active=2, steps=4, delta_layers=3),
            span(199, active=1, steps=2, delta_layers=3),
            span(200, active=9, steps=4, delta_layers=3)])])])
    monkeypatch.setattr(trace_reduce, "load", lambda path: profile)
    facts = {"config": CFG, "peak": PEAK, "trace_path": "x",
             "trace_interval": (100, 200),
             "trace": _trace((STEP, 30 * least * 4))}
    assert reader.read(facts) == pytest.approx(25.0)
    # a count that passes 105 is refused, not printed
    assert reader.read(dict(facts, trace=_trace((STEP, 30 * least / 1.2)))
                       ) is None
    # nothing to read: no call of that name, another family, no trace
    assert reader.read(dict(facts, trace=_trace(("%fusion.1 = ...", 1.0)))
                       ) is None
    assert reader.read(dict(facts, config=GPT)) is None
    assert reader.read(dict(facts, trace=None)) is None
    assert reader.kernels(CFG, MIX) == {
        "delta_step": ("%delta_step",
                       'custom_call_target="tpu_custom_call"')}


def test_chunk_roofline_takes_the_scopes_seconds_and_the_windows_pieces(
        monkeypatch):
    reader = bench_run.load_reader("kda.chunk_kernel_roofline")
    assert reader.widths(1032, 3) == [512, 512, 8]
    one = lambda w: delta_bytes.least_seconds(                 # noqa: E731
        *delta_bytes.piece(CFG, w), PEAK)

    def span(start, **stats):
        return types.SimpleNamespace(name="serving.prefill", start_ns=start,
                                     duration_ns=5,
                                     stats=list(stats.items()))

    profile = types.SimpleNamespace(planes=[types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(events=[
            span(10, bucket=512, pieces=1, delta_layers=3),
            span(150, bucket=128, pieces=1, delta_layers=3),
            span(160, bucket=640, pieces=2, delta_layers=3)])])])
    monkeypatch.setattr(trace_reduce, "load", lambda path: profile)
    least = 3 * (2 * one(128) + one(512))
    monkeypatch.setattr(reader, "scope_seconds", lambda facts: 10 * least)
    facts = {"config": CFG, "peak": PEAK, "trace_path": "x",
             "trace_interval": (100, 200), "trace": _trace()}
    assert reader.read(facts) == pytest.approx(10.0)
    monkeypatch.setattr(reader, "scope_seconds", lambda facts: None)
    assert reader.read(facts) is None


def test_snapshot_hit_share_and_decode_stream_on_hand_made_facts():
    reader = bench_run.load_reader("state.snapshot_hit_share")
    stats = {"serving.state_snapshot_hits": 10.0,
             "serving.prefix_hit_tokens": 10 * 65_536.0,
             "serving.prefill_real_tokens": 10 * 128.0}
    assert reader.read({"stats": stats}) == pytest.approx(
        100 * 65_536 / 65_664)
    # the parent has no such counter: nothing to read, no error
    assert reader.read({"stats": {"serving.prefix_hit_tokens": 5.0}}) is None

    stream = bench_run.load_reader("kda_moe.decode_stream_roofline")
    steps = 1000
    stats = {"serving.step_seconds": {"mean": 0.004, "p50": 0.002,
                                      "count": 250},
             "serving.moe_rows{phase=decode}": 3.0 * 4 * steps,
             "serving.moe_assignments_held{phase=decode}": 3.0 * 4 * steps,
             "serving.moe_experts_touched{phase=decode}": 12.0 * steps,
             "serving.moe_expert_visits{phase=decode}": 160.0 * steps}
    requests = [{"prompt_len": 65_600, "first": 0.1, "out": 1001}
                for _ in range(3)]
    want = delta_bytes.decode_step_bytes(
        CFG, 12, 3, sum(65_600 + i for i in range(1, 1001)) * 3 / steps)
    got = stream.read({"stats": stats, "peak": PEAK, "config": CFG,
                       "requests": requests})
    assert got == pytest.approx(
        100 * want / PEAK["hbm_bytes_per_s"] / 0.004)
    assert 0 < got < 100
    assert stream.read({"stats": stats, "peak": PEAK, "config": GPT,
                        "requests": requests}) is None


GROUPED = ('%grouped_matmul.7 = bf16[128,1280] custom-call(...), '
           'custom_call_target="tpu_custom_call"')


def test_expert_roofline_counts_the_windows_own_layer_steps(monkeypatch):
    """Decode chunks and admissions that START inside the window, each
    held to the run's mean layer-step of its phase; a chunk of fewer rows
    than the mean step to that share of it."""
    reader = bench_run.load_reader("kda_moe.expert_matmul_roofline")

    def span(name, start, **stats):
        return types.SimpleNamespace(name=name, start_ns=start,
                                     duration_ns=5,
                                     stats=list(stats.items()))

    profile = types.SimpleNamespace(planes=[types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(events=[
            span("serving.decode_chunk", 10, active=9, steps=4,
                 moe_layers=4),
            span("serving.decode_chunk", 110, active=4, steps=4,
                 moe_layers=4),
            span("serving.decode_chunk", 120, active=2, steps=4,
                 moe_layers=4),
            span("serving.prefill", 130, pieces=1, moe_layers=4),
            span("serving.prefill", 250, pieces=1, moe_layers=4)])])])
    monkeypatch.setattr(trace_reduce, "load", lambda path: profile)
    # the run: 1000 decode steps of 4 rows (4 pairs, 3.6 experts a
    # layer), 50 pieces of 128 rows (128 pairs, 38 experts a layer)
    stats = {"serving.moe_rows{phase=decode}": 4.0 * 4 * 1000,
             "serving.moe_assignments_held{phase=decode}": 4.0 * 4 * 1000,
             "serving.moe_experts_touched{phase=decode}": 3.6 * 4 * 1000,
             "serving.moe_expert_visits{phase=decode}": 160.0 * 1000,
             "serving.moe_rows{phase=prefill}": 128.0 * 4 * 50,
             "serving.moe_assignments_held{phase=prefill}": 128.0 * 4 * 50,
             "serving.moe_experts_touched{phase=prefill}": 38.0 * 4 * 50,
             "serving.moe_expert_visits{phase=prefill}": 160.0 * 50}
    one = lambda t, a: delta_bytes.expert_call_seconds(CFG, t, a, PEAK)  # noqa
    least = 16 * one(3.6, 4) + 16 * one(1.8, 2) + 4 * one(38, 128)
    facts = {"config": CFG, "peak": PEAK, "stats": stats, "trace_path": "x",
             "trace_interval": (100, 200),
             "trace": _trace((GROUPED, 2 * least))}
    assert reader.read(facts) == pytest.approx(50.0)
    assert reader.read(dict(facts, trace=_trace((GROUPED, least / 1.2)))
                       ) is None                       # over 105: refused
    assert reader.read(dict(facts, stats={})) is None
    assert reader.read(dict(facts, config=GPT)) is None


def _cell():
    bench = json.load(open(bench_run.ROOT + "/BENCHMARK.json"))
    return {"name": "tiny-delta-moe.serve", "chips": 1, "config": TINY,
            "traffic": SERVE, "end_to_end": bench["end_to_end"],
            "per_layer": [m for m in bench["per_layer"]
                          if CELL in m.get("workloads", [])]}


@pytest.fixture(scope="module")
def rehearsal():
    from chipbench.runners import serve
    from paddle_tpu.serving import batched_decode as bd

    cell = _cell()
    with pytest.MonkeyPatch.context() as patch:
        # pieces of 32: a head of 64 is two of them, as the cell's head of
        # 65,536 is 128 pieces of 512
        patch.setattr(bd, "PREFILL_PIECE", 32)
        return cell, serve.run(cell, seed=SEED, seconds=1.5, tracer=None)


def test_serve_runner_rehearsal_and_what_the_readers_find(rehearsal):
    cell, result = rehearsal
    facts = result["facts"]
    assert result["correct"], facts["worst_logit_margin"]
    assert result["attempted"] == 9 and result["failed"] == 0
    assert facts["compiled_in_window"] == 0
    stats = facts["stats"]
    # every request of the window started from its head's snapshot
    assert stats["serving.state_snapshot_hits"] == 9
    assert all(r["prefix_hit"] == 64 for r in facts["requests"])
    facts = dict(facts, config=TINY, traffic=SERVE, chips=1, trace=None,
                 trace_window_s=None,
                 peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    got = bench_run.layer_metrics(cell, facts)
    assert {"step.decode_ms", "kda_moe.decode_stream_roofline",
            "state.snapshot_hit_share", "sched.prefix_hit_share",
            "compile.seconds", "serve.ttft_p90_ms"} <= set(got)
    assert not any(k.startswith(("device.", "kda.")) or
                   k in ("kda_moe.expert_matmul_roofline",
                         "kda_moe.paged_attention_roofline") for k in got)
    assert 0 < got["kda_moe.decode_stream_roofline"]["value"] < 100
    assert got["state.snapshot_hit_share"]["value"] > 70
    assert got["state.snapshot_hit_share"]["value"] == pytest.approx(
        got["sched.prefix_hit_share"]["value"])


@pytest.mark.parametrize("switch", [
    {"beta_scale": 1.0}, {"decay": "head"}, {"delta_term": False},
    {"l2norm": False}, {"lost": (64,)}], ids=lambda s: next(iter(s)))
def test_the_check_bites_on_each_line_left_out(rehearsal, switch):
    """The reference with one line left out or moved, against the sound
    engine's own tokens after a hit at 64: another function of the rows
    the engine generated (``tests/test_delta_moe.py`` has each, and the G
    layer's gate and ``norm_topk_prob``, which at this width and matrices
    of 0.02 move nothing a bfloat16 run can see, at weights that make it
    a gap of 0.01; the
    chip's readings at the published widths are in ``chipbench/KDA.md``)."""
    from paddle_tpu.observability.metrics import MetricsRegistry
    from paddle_tpu.serving import batched_decode as bd

    _, result = rehearsal
    family = families.of(TINY, "serve")
    params = family.make_params(TINY, 160, SEED)
    rng = np.random.default_rng(3)
    head = rng.integers(0, 128, 64, dtype=np.int32)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(bd, "PREFILL_PIECE", 32)
        eng = family.serving_engine(params, TINY, MetricsRegistry(),
                                    dict(SERVE["engine"]))
        eng.generate_many([np.concatenate([head, head[:3]])],
                          max_new_tokens=2)
        prompt = np.concatenate([head, np.arange(3, 20, dtype=np.int32)])
        full, = eng.generate_many([prompt], max_new_tokens=40)
    assert eng.stats()["serving.state_snapshot_hits"] == 1
    padded = np.asarray(full)[None]
    sound = family.logits(params, padded, TINY)[0]
    other = family.logits(params, padded, TINY, **switch)[0]
    at = np.arange(len(prompt) - 1, len(full) - 1)
    at = at[(np.abs(sound[at]).sum(-1) > 0)
            & (np.abs(other[at]).sum(-1) > 0)]
    gap = lambda lg: float(np.max(                             # noqa: E731
        lg[at].max(-1) - lg[at, np.asarray(full)[at + 1]]))
    assert gap(sound) <= SERVE["check"]["logit_margin"]
    assert np.abs(other[at] - sound[at]).max() > 2e-3
    assert result["correct"]


def test_a_row_the_reference_could_not_compute_is_refused():
    """Not-finite logits never pass as a gap of NaN: the row refuses the
    token the check holds it to, whatever it is."""
    family = families.of(TINY, "serve")
    out = np.zeros((1, 5, 8), np.float32)
    out[0, 1, 3] = np.nan
    out[0, 4, :] = np.inf
    tokens = np.array([[1, 2, 6, 3, 4]])
    family._refuse_rows_not_computed(out, tokens)
    assert np.isfinite(out).all()
    at = out[0, [1, 4]]
    gap = at.max(-1) - at[np.arange(2), [6, 0]]
    assert (gap == 1e30).all() and not out[0, [0, 2, 3]].any()


def test_the_seeded_weights_are_the_familys_own():
    """``make_params``: a step inside ``[time_step_min, time_step_max]``
    under the softplus, ``A`` in [1, 16], the convolution inside +-0.5,
    a table of rows of RMS 1, a head centred on the seeded rows and a
    router bias that spreads the load."""
    import jax.numpy as jnp

    family = families.of(TINY, "serve")
    raw, tokens = family.make_params_unsettled(TINY, SEED)
    assert tokens.shape == (256,)
    f32 = lambda a: np.asarray(jnp.asarray(a, jnp.float32))    # noqa: E731
    step = np.log1p(np.exp(f32(raw["block1_delta_dt.b"])))
    assert 0.0009 < step.min() and step.max() < 0.11
    A = np.exp(f32(raw["block1_delta_A_log.w"]))
    assert 0.99 < A.min() and A.max() < 16.1
    assert np.abs(f32(raw["block2_delta_conv.w"])).max() <= 0.5
    assert not f32(raw["block3_router.bias"]).any()
    rms = np.sqrt(np.mean(np.square(f32(raw["tok_emb.w"]))))
    assert 0.9 < rms < 1.1
    params = family.make_params(TINY, 160, SEED)
    assert f32(params["block3_router.bias"]).any()
    assert set(params) == set(family.shapes(TINY))
    assert all(tuple(params[k].shape) == v
               for k, v in family.shapes(TINY).items())
