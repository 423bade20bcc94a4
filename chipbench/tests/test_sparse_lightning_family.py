"""The ``sparse_lightning`` family (``families/sparse_lightning.py``,
``sparse_lightning_reference.py``, ``configs/minicpm-sala.json``,
``sala_bytes.py`` and the six readers PR 61 brought): the sizes shape-only
code reads, the byte arithmetic the cell's geometry rests on, the
configuration against the catalog's keys, the reference importing nothing
of the program, the counts of ``sala_bytes`` against hand counts, the
readers on hand-made facts (spans counted inside the traced window's
interval only, a share over 105 refused), and the serving runner end to
end on the CPU at a tiny size of the family, shared heads served from
state snapshots past ``dense_len``, with the check biting on lines of the
mathematics left out."""

import json
import types

import numpy as np
import pytest

from chipbench import families, flops, sala_bytes, trace_reduce
from chipbench import run as bench_run

CFG = bench_run._read_json(bench_run.HERE, "configs", "minicpm-sala.json")
MIX = bench_run._read_json(bench_run.HERE, "traffic", "doc_qa_128k.json")
PEAK = flops.peaks("TPU v5 lite")
CELL = "sala9b.doc_qa_128k"
GPT = bench_run._read_json(bench_run.HERE, "configs",
                           "cerebras-gpt-1.3b.json")
# the published layout at a width the CPU can run: S L L S, 2 K/V heads
# under 4 query heads, blocks of 8 under compressed keys every 2 positions
TINY = {"name": "tiny-sala", "family": "sparse_lightning",
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "lightning_nh": 4, "lightning_head_dim": 16, "vocab_size": 128,
        "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                        "minicpm4"],
        "num_hidden_layers": 4, "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16,
        "compute_dtype": "bfloat16",
        "sparse_config": {"kernel_size": 4, "kernel_stride": 2,
                          "block_size": 8, "topk": 2, "init_blocks": 1,
                          "window_size": 16, "dense_len": 48},
        "sparse_qk_gain": 4.0, "centre_tokens": 64,
        "check_undecided_margin": 0.05}
# two shared heads of 64 tokens over blocks of 8: the warm-up's head + 8
# would end a piece ON a block boundary past the head, so min_bucket is 4
SERVE = {"runner": "serve", "chips": 1,
         "engine": {"max_len": 128, "max_slots": 4, "block_tokens": 8,
                    "cache_blocks": 128, "pool_blocks": 160,
                    "prefix_reuse": True, "min_bucket": 4},
         "rate_per_s": 6.0, "schedule_seed": 5,
         "shared_heads": {"count": 2, "tokens": 64, "zipf_s": 1.0},
         "prompt_tail": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                         "min": 2, "max": 24},
         "output": {"dist": "lognormal", "median": 14, "sigma": 0.4,
                    "min": 6, "max": 28},
         "drain_seconds": 60, "warmup_timeout_s": 300,
         "trace_seconds": 1.0,
         "check": {"sample": 4, "logit_margin": 0.02}}
SEED = 2 ** 31 + 61

D, F, V = 4096, 16384, 73448
S_LAYER = 3 * D * D + 2 * D * 256 + 3 * D * F
L_LAYER = 5 * D * D + 3 * D * F
STATE = 4 * 32 * 128 * 128


def test_sizes_and_bytes_of_the_configuration_as_it_is_run():
    assert (S_LAYER, L_LAYER) == (253_755_392, 285_212_672)
    matmul = 2 * S_LAYER + 6 * L_LAYER + D * V
    assert families.sizes(CFG) == {
        "d_model": D, "heads": 32, "head_dim": 128, "vocab_rows": V,
        "matmul_params": matmul, "kv_planes": 2, "attention_passes": 2}
    more = families.of(CFG).sala_sizes(CFG)
    assert more["state_bytes"] == STATE == 2_097_152
    assert (more["sparse_layers"], more["lightning_layers"],
            more["selected_blocks"], more["stride"], more["block"],
            more["dense_len"]) == (2, 6, 97, 16, 64, 8192)
    # the cell's geometry: 10,241 blocks of 528,384 B (K and V of two
    # planes in 8 pool rows, and 4 compressed rows of 512 B a plane), 32
    # slots of six states, 2,068 table entries a slot
    geo = MIX["engine"]
    assert geo["max_len"] == 131072 + 1280 == 2068 * geo["block_tokens"]
    assert sala_bytes.sizes(CFG)["block"] % geo["block_tokens"] == 0
    block = 2 * (2 * 64 * 8 * 128 * 2 + 4 * 256 * 2)
    assert block == 528_384
    assert (1 + geo["pool_blocks"]) * block == 5_411_180_544
    assert geo["max_slots"] * 6 * STATE == 402_653_184
    assert 2 * CFG["parameters_held"] == 5_641_089_536
    # four documents and 32 tails fit the stated pool beside the slack
    tails = geo["max_slots"] * (1280 // geo["block_tokens"] + 1)
    assert 4 * 2048 + tails <= geo["pool_blocks"]
    assert MIX["shared_heads"]["tokens"] % 512 == 0
    assert (MIX["shared_heads"]["tokens"] + 8) % geo["block_tokens"]


def test_configuration_holds_the_catalogs_keys_and_says_what_it_cut():
    bench = bench_run._read_json(bench_run.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "minicpm-sala")
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers",
                                                  "mixer_types"]
    assert CFG["source"] == entry["source"]
    published = {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "max_position_embeddings": 524288, "model_type": "minicpm_sala",
        "num_attention_heads": 32, "num_key_value_heads": 2,
        "qk_norm": True, "rand_init": False, "rms_norm_eps": 1e-06,
        "vocab_size": 73448, "rope_theta": 10000, "scale_emb": 12,
        "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 256,
        "tie_word_embeddings": False, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True}
    assert {k: CFG[k] for k in published} == published
    assert CFG["published"]["num_hidden_layers"] == 32
    assert len(CFG["published"]["mixer_types"]) == 32
    assert CFG["published"]["mixer_types"].count("minicpm4") == 8
    assert (CFG["num_hidden_layers"], CFG["first_layer"]) == (8, 9)
    assert CFG["mixer_types"] == CFG["published"]["mixer_types"][9:17]
    assert CFG["parameters_held"] == families.of(CFG).parameters(CFG)
    assert {"sparse_sizes", "forced_blocks", "pooling", "dense_switch",
            "qk_norm", "gates", "output_norm", "rotary", "activation",
            "decay", "state_dtype", "score_dtype", "init"} <= set(
                CFG["assumed"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "minicpm-sala", "doc_qa_128k", 1)
    assert len(cell["why"]) <= 200


def test_the_family_serves_and_does_not_train():
    family = families.of(CFG, "serve")
    assert family.__name__.endswith("sparse_lightning")
    with pytest.raises(SystemExit, match="does not train"):
        families.of(CFG, "train")


def test_reference_imports_nothing_of_the_program():
    source = open(bench_run.HERE
                  + "/families/sparse_lightning_reference.py").read()
    assert "paddle_tpu" not in source.split('"""', 2)[2]


def test_sala_bytes_against_hand_counts():
    assert sala_bytes.step(CFG) == (5 * 32 * 128 * 128, 2 * STATE)
    ops, nbytes = sala_bytes.piece(CFG, 512)
    assert nbytes == 2 * STATE + 512 * 3 * 4096 * 2 + 512 * 4096 * 4
    assert ops == 512 * 32 * (4 * 128 * 128 + 4 * 128 * 128)
    # two decode positions, one under dense_len: it selects nothing
    ctx = [131_200, 5_000]
    assert sala_bytes.select_call(CFG, ctx) == (
        2 * 32 * 128 * 8200, 8200 * 512)
    assert sala_bytes.sparse_call(CFG, ctx) == (
        4 * 32 * 128 * 97 * 64, 97 * 64 * 2 * 2 * 128 * 2)
    assert sala_bytes.sparse_call(CFG, ctx)[1] == 6_356_992
    step = sala_bytes.decode_step_bytes(CFG, 1.0, ctx, 2)
    assert step == pytest.approx(
        2 * families.sizes(CFG)["matmul_params"] + 6 * 2 * STATE
        + 2 * (8200 * 512 + 6_356_992) / 2)
    assert sala_bytes.share("x", 104.9) == 104.9
    assert sala_bytes.share("x", 105.1) is None
    assert sala_bytes.sizes(GPT) is None


_LOAD = bench_run.load_reader     # the test below stands in for one reader


def _trace(*ops):
    return {"ops": {f"op{i}": {"calls": 10, "seconds": s, "provenance": p}
                    for i, (p, s) in enumerate(ops)}}


STEP = ('%ssm_step.3 = f32[32,32,128,128] custom-call(...), '
        'custom_call_target="tpu_custom_call"')


def _spans(name, *events):
    return types.SimpleNamespace(planes=[types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(events=[
            types.SimpleNamespace(name=name, start_ns=start, duration_ns=5,
                                  stats=list(stats.items()))
            for start, stats in events])])])


def test_step_and_chunk_rooflines_count_the_spans_inside_the_window(
        monkeypatch):
    reader = bench_run.load_reader("lightning.step_kernel_roofline")
    least = sala_bytes.least_seconds(*sala_bytes.step(CFG), PEAK)
    how = dict(steps=4, lightning_layers=6)
    profile = _spans("serving.decode_chunk",
                     (50, dict(active=9, **how)),
                     (100, dict(active=2, **how)),
                     (199, dict(active=1, **how)),
                     (200, dict(active=9, **how)))
    monkeypatch.setattr(trace_reduce, "load", lambda path: profile)
    facts = {"config": CFG, "peak": PEAK, "trace_path": "x",
             "trace_interval": (100, 200),
             "trace": _trace((STEP, 72 * least * 4))}
    assert reader.read(facts) == pytest.approx(25.0)
    assert reader.read(dict(facts, trace=_trace((STEP, 72 * least / 1.2)))
                       ) is None
    assert reader.read(dict(facts, config=GPT)) is None
    assert reader.read(dict(facts, trace=None)) is None
    assert reader.kernels(CFG, MIX) == {
        "ssm_step": ("%ssm_step", 'custom_call_target="tpu_custom_call"')}

    reader = bench_run.load_reader("lightning.chunk_kernel_roofline")
    one = lambda w: sala_bytes.least_seconds(                  # noqa: E731
        *sala_bytes.piece(CFG, w), PEAK)
    profile = _spans("serving.prefill",
                     (10, dict(bucket=512, pieces=1, lightning_layers=6)),
                     (150, dict(bucket=128, pieces=1, lightning_layers=6)),
                     (160, dict(bucket=640, pieces=2, lightning_layers=6)))
    monkeypatch.setattr(trace_reduce, "load", lambda path: profile)
    least = 6 * (2 * one(128) + one(512))
    helper = bench_run.load_reader("dsa.indexer_roofline")
    monkeypatch.setattr(bench_run, "load_reader", lambda name: (
        types.SimpleNamespace(scope_seconds=lambda f, n, m=None: 10 * least)
        if name == "dsa.indexer_roofline" else _LOAD(name)))
    facts = {"config": CFG, "peak": PEAK, "trace_path": "x",
             "trace_interval": (100, 200), "trace": _trace()}
    assert reader.read(facts) == pytest.approx(10.0)
    assert helper.NAME == "dsa.indexer_roofline"


def test_attended_block_share_and_decode_stream_on_hand_made_facts():
    reader = bench_run.load_reader("sala.attended_block_share")
    stats = {"serving.sparse_blocks_selected{phase=decode}": 97.0 * 40,
             "serving.sparse_blocks_live{phase=decode}": 2060.0 * 40}
    assert reader.read({"stats": stats}) == pytest.approx(100 * 97 / 2060)
    assert reader.read({"stats": {}}) is None
    reader = bench_run.load_reader("sala.decode_stream_roofline")
    requests = [{"prompt_len": 131_200, "out": 5, "first": 1.0}]
    ctx = [131_200 + i for i in range(1, 5)]
    nbytes = sala_bytes.decode_step_bytes(CFG, 1.0, ctx, 4)
    facts = {"stats": {"serving.step_seconds": {
        "count": 1, "mean": 2 * nbytes / PEAK["hbm_bytes_per_s"]}},
        "peak": PEAK, "config": CFG, "requests": requests,
        "decode_chunk": 4}
    assert reader.read(facts) == pytest.approx(50.0)
    assert reader.read(dict(facts, config=GPT)) is None


def _cell():
    bench = json.load(open(bench_run.ROOT + "/BENCHMARK.json"))
    return {"name": "tiny-sala.serve", "chips": 1, "config": TINY,
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
        # 131,072 is 256 pieces of 512
        patch.setattr(bd, "PREFILL_PIECE", 32)
        return cell, serve.run(cell, seed=SEED, seconds=1.5, tracer=None)


def test_serve_runner_rehearsal_and_what_the_readers_find(rehearsal):
    cell, result = rehearsal
    facts = result["facts"]
    assert result["correct"], facts["worst_logit_margin"]
    assert result["attempted"] == 9 and result["failed"] == 0
    assert facts["compiled_in_window"] == 0
    stats = facts["stats"]
    # every request of the window started from its head's snapshot, and
    # every decode position selected its blocks
    assert stats["serving.state_snapshot_hits"] == 9
    assert all(r["prefix_hit"] == 64 for r in facts["requests"])
    assert stats["serving.sparse_calls{form=sparse,phase=decode}"] > 0
    assert "serving.sparse_calls{form=dense,phase=decode}" not in stats \
        or stats["serving.sparse_calls{form=dense,phase=decode}"] == 0
    facts = dict(facts, config=TINY, traffic=SERVE, chips=1, trace=None,
                 trace_window_s=None,
                 peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    got = bench_run.layer_metrics(cell, facts)
    assert {"step.decode_ms", "sala.decode_stream_roofline",
            "sala.attended_block_share", "state.snapshot_hit_share",
            "sched.prefix_hit_share", "compile.seconds",
            "serve.ttft_p90_ms"} <= set(got)
    assert not any(k.startswith(("device.", "lightning.")) or k in (
        "sala.block_select_roofline", "sala.sparse_attention_roofline")
        for k in got)
    assert 0 < got["sala.decode_stream_roofline"]["value"] < 100
    assert 0 < got["sala.attended_block_share"]["value"] < 100
    assert got["state.snapshot_hit_share"]["value"] > 70


@pytest.mark.parametrize("switch", [
    {"decay": False}, {"lin_rope": False}, {"lost": (64,)},
    {"lin_gate": False}, {"residual_scale": 1.0}],
    ids=lambda s: next(iter(s)))
def test_the_check_bites_on_each_line_left_out(rehearsal, switch):
    """The reference with one line left out or moved, against the sound
    engine's own tokens after a hit at 64: another function of the rows
    the engine generated (the selection left out and the sparse layers'
    gate move 4e-4 at this width, five blocks of at most thirteen and
    matrices of 0.02: nothing a bfloat16 run can see;
    ``tests/test_sparse_lightning.py`` holds the selection to the
    reference in float32, and the chip's readings at the published widths
    are in ``chipbench/SALA.md``)."""
    from paddle_tpu.observability.metrics import MetricsRegistry
    from paddle_tpu.serving import batched_decode as bd

    _, result = rehearsal
    family = families.of(TINY, "serve")
    params = family.make_params(TINY, 128, SEED)
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


def test_check_variants_resolve_to_the_requests_own_switches():
    """What ``benchmarks/check_walk.py`` reads: every changed line ISSUE
    61 lists, ``HIT`` and ``OTHER`` made the request's, a size's change
    made the configuration's, and each a different function of the rows
    past the hit than the sound reference."""
    family = families.of(TINY, "serve")
    params = family.make_params(TINY, 128, SEED)
    rng = np.random.default_rng(4)
    heads = [rng.integers(0, 128, 64, dtype=np.int32) for _ in range(2)]
    prompt = np.concatenate([heads[1], np.arange(5, 25, dtype=np.int32)])
    variants = family.check_variants(TINY, heads)
    assert list(variants) == list(family.CHECK_VARIANTS) and len(variants) == 15
    cfg, how = variants["topk_32"](params, prompt)
    assert cfg["sparse_config"]["topk"] == 32 and how == {}
    assert TINY["sparse_config"]["topk"] == 2
    assert variants["states_zeroed_at_the_hit"](params, prompt) == (
        TINY, {"lost": (64,)})
    assert variants["straddling_rows_from_the_shared_chain"](
        params, prompt)[1] == {"straddle": 64}
    _, how = variants["another_documents_states_restored"](params, prompt)
    at, states = how["inject"]
    assert at == 64 and len(states) == 2 and states[0].shape == (4, 16, 16)
    # the states are the OTHER head's: not what this prompt's head leaves
    own = []
    family.reference.trunk(params, prompt[:65], *family._layout(TINY),
                           rows_from=64, capture=(64, own),
                           **family._how(TINY))
    assert np.abs(np.asarray(states[0]) - np.asarray(own[0])).max() > 1e-3
    padded = prompt[None]
    open_cfg = dict(TINY, check_undecided_margin=0.0)
    sound = family.logits(params, padded, open_cfg)[0][64:]
    for name in ("another_documents_states_restored", "decay_left_out",
                 "scale_depth_left_out", "head_scale_left_out"):
        cfg, how = variants[name](params, prompt)
        other = family.logits(params, padded, dict(
            cfg, check_undecided_margin=0.0), **how)[0][64:]
        assert np.abs(other - sound).max() > 2e-3, name
