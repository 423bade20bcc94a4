"""Autotune engine tests (ISSUE 9): cache robustness (corrupt /
truncated / schema-version mismatch / stale kernel-geometry
fingerprint must each fall back to defaults and re-tune, never crash
or serve a wrong config), the candidate space + static pruning, and
the hot-path wiring."""

import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import tune
from paddle_tpu.tune import cache as tcache
from paddle_tpu.tune import space as tspace


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    """A fresh cache file path + singleton reset around each test (and
    a DIAG_W restore: the hot path may apply a tuned width)."""
    from paddle_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "DIAG_W", pa.DIAG_W)
    path = tmp_path / "tuned.json"
    monkeypatch.setenv("PADDLE_TPU_TUNE_CACHE", str(path))
    monkeypatch.setenv("PADDLE_TPU_TUNE", "cached")
    tune.reset_cache()
    yield path
    tune.reset_cache()


def _seed_entry(path, **overrides):
    """Write a VALID cache file with one flash entry, then apply
    overrides (None deletes a field)."""
    c = tcache.TuneCache(str(path))
    key = tspace.WorkloadKey("flash", 64, 32, 2, "float32", "cpu",
                             remat="-")
    c.put(key.s, {"block_q": 32, "block_k": 16, "diag_w": 16,
                  "packed": None})
    c.save()
    if overrides:
        data = json.loads(path.read_text())
        for k, v in overrides.items():
            if v is None:
                data.pop(k, None)
            else:
                data[k] = v
        path.write_text(json.dumps(data))
    tune.reset_cache()
    return key


# -- cache robustness (the satellite contract) ---------------------------

def test_cache_roundtrip(tmp_cache):
    key = _seed_entry(tmp_cache)
    got = tune.get_cache().get(key.s)
    assert got["config"]["block_q"] == 32
    assert tune.attention_config(64, 32, 2, "float32") == {
        "block_q": 32, "block_k": 16, "diag_w": 16, "packed": None}


def test_corrupt_cache_falls_back_to_defaults(tmp_cache):
    _seed_entry(tmp_cache)
    tmp_cache.write_bytes(b"\x00garbage not json{{{")
    tune.reset_cache()
    c = tune.get_cache()
    assert c.entries == {} and "unreadable" in c.stale_reason
    assert tune.attention_config(64, 32, 2, "float32") is None
    # re-tune rewrites a valid file over the garbage
    c.put("k", {"block_q": 8})
    c.save()
    tune.reset_cache()
    assert tune.get_cache().get("k")["config"]["block_q"] == 8


def test_truncated_cache_falls_back(tmp_cache):
    _seed_entry(tmp_cache)
    full = tmp_cache.read_text()
    tmp_cache.write_text(full[: len(full) // 2])
    tune.reset_cache()
    c = tune.get_cache()
    assert c.entries == {} and c.stale_reason is not None


def test_schema_version_mismatch_ignored(tmp_cache):
    key = _seed_entry(tmp_cache, schema_version=999)
    c = tune.get_cache()
    assert c.get(key.s) is None
    assert "schema_version" in c.stale_reason


def test_stale_fingerprint_retunes(tmp_cache, monkeypatch):
    """A cache written against a different kernel geometry is stale:
    entries are ignored (defaults apply) and the next save stamps the
    CURRENT fingerprint."""
    key = _seed_entry(tmp_cache)
    from paddle_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "LSE_LANES", 256)  # kernel geometry changed
    tune.reset_cache()
    c = tune.get_cache()
    assert c.get(key.s) is None
    assert "fingerprint" in c.stale_reason
    c.put(key.s, {"block_q": 64})
    c.save()
    tune.reset_cache()
    assert tune.get_cache().get(key.s)["config"]["block_q"] == 64
    # and the old-geometry process would in turn see THIS file as stale
    monkeypatch.undo()
    tune.reset_cache()
    assert tune.get_cache().get(key.s) is None


def test_non_object_entries_ignored(tmp_cache):
    _seed_entry(tmp_cache, entries={"bad": [1, 2], "worse": "x"})
    assert tune.get_cache().entries == {}


def test_kill_switch_skips_lookup(tmp_cache, monkeypatch):
    key = _seed_entry(tmp_cache)
    monkeypatch.setenv("PADDLE_TPU_TUNE", "0")
    assert tune.tune_mode() == "off"
    assert tune.attention_config(64, 32, 2, "float32") is None
    monkeypatch.setenv("PADDLE_TPU_TUNE", "cached")
    assert tune.attention_config(64, 32, 2, "float32") is not None
    assert key.s in tune.get_cache().entries


# -- workload key + candidate space + static pruning ---------------------

def test_workload_key_canonical_string():
    k = tspace.WorkloadKey("flash", 4096, 128, 6, np.dtype("float32"),
                           "tpu", remat="-")
    assert k.s == "op=flash|t=4096|dh=128|h=6|dt=float32|plat=tpu|remat=-"
    assert k == tspace.WorkloadKey("flash", 4096, 128, 6, "float32",
                                   "tpu", remat="-")
    assert tspace.WorkloadKey("flash", 4096, 128, 6, "bfloat16", "tpu",
                              remat="-") != k


def test_candidates_tile_exactly():
    for c in tspace.attention_candidates(4096, 128, 6):
        assert 4096 % c["block_q"] == 0 and 4096 % c["block_k"] == 0
        assert c["block_q"] % c["diag_w"] == 0 or \
            c["diag_w"] <= min(c["block_q"], c["block_k"])
    # toy t: blocks shrink to exact divisors instead of disappearing
    toys = tspace.attention_candidates(96, 32, 2, block_caps=(32, 64))
    assert toys and all(96 % c["block_q"] == 0 for c in toys)


def test_prune_static_roofline_and_vmem():
    cands = tspace.attention_candidates(4096, 128, 2,
                                        block_caps=(512, 1024, 4096))
    survivors, pruned = tspace.prune_static(4096, 128, 2, cands)
    assert survivors, "something must survive"
    assert all("roofline" in c for c in survivors)
    # a 4096x4096 block pair blows the VMEM budget and must be pruned
    vmem_pruned = [r for _, r in pruned if "vmem" in r]
    assert vmem_pruned, f"expected a vmem rejection, got {pruned}"


def test_hbm_model_ordering_matches_measured_reality():
    """The analytic bound must reproduce the measured t=16k facts:
    selective/offload at accum=1 exceed the 15.75 GiB chip (the round-5
    flagship's allocator failure), while accum2-no-remat, offload+accum2
    and bs6 full-remat fit (measured on the chip in round 5)."""
    G = 1 << 30
    est = lambda pol, acc: tspace.estimate_gpt_step_hbm(
        12, 768, 6, 32768, 16384, 6, policy=pol, accum=acc)
    assert est("selective", 1) > 15.75 * G
    assert est("offload", 1) > 15.75 * G
    assert est("none", 2) < 15.75 * G
    assert est("offload", 2) < 15.75 * G
    assert est("full", 1) < 15.75 * G
    # monotone in the levers
    assert est("offload", 2) < est("offload", 1)
    assert est("full", 1) < est("selective", 1) < est("none", 1)


def test_prune_static_hbm_budget_rejects_r05_config():
    demo = tune.flagship_static_demo()
    assert "gpt_t16k_rejected_r05_config" in demo
    assert demo["gpt_t16k_selected_policy"] in tspace.POLICY_ORDER
    sel_est = demo["gpt_t16k_selected_est_hbm_gib"]
    assert 0 < sel_est <= 0.85 * demo["gpt_t16k_budget_gib"]


# -- hot-path wiring -----------------------------------------------------

def _flash_op(program):
    for op in program.global_block().ops:
        if op.type in ("flash_attention_packed", "flash_attention"):
            return op
    return None


def _build_gpt(**kw):
    from paddle_tpu.models import transformer

    pt.core.unique_name.reset()
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        transformer.build(vocab_size=61, n_layer=2, n_head=2, d_model=64,
                          max_len=64, dropout_rate=0.0, dtype="float32",
                          **kw)
    return main_prog


def test_multi_head_attention_applies_tuned_geometry(tmp_cache):
    _seed_entry(tmp_cache)  # flash t=64 dh=32 h=2 float32 cpu
    main_prog = _build_gpt()
    op = _flash_op(main_prog)
    assert op.attrs.get("block_q") == 32 and op.attrs.get("block_k") == 16


def test_explicit_blocks_win_over_cache(tmp_cache):
    _seed_entry(tmp_cache)
    main_prog = _build_gpt(attn_block_q=8, attn_block_k=8)
    op = _flash_op(main_prog)
    assert op.attrs.get("block_q") == 8 and op.attrs.get("block_k") == 8


def test_kill_switch_builds_default_program(tmp_cache, monkeypatch):
    _seed_entry(tmp_cache)
    monkeypatch.setenv("PADDLE_TPU_TUNE", "0")
    op = _flash_op(_build_gpt())
    assert "block_q" not in op.attrs and "block_k" not in op.attrs


def test_forced_attention_config_context():
    with tune.forced_attention_config({"block_q": 16, "block_k": 16}):
        op = _flash_op(_build_gpt())
        assert op.attrs.get("block_q") == 16
    op = _flash_op(_build_gpt())
    assert op.attrs.get("block_q") != 16


def test_memory_optimize_auto_consults_cache(tmp_cache):
    """policy='auto' resolves the tuned winner; a miss (or winner
    'none') degrades sanely."""
    main_prog = _build_gpt()
    # miss -> selective segmentation applied
    segs = pt.memory_optimize(main_prog, policy="auto")
    assert segs and getattr(main_prog, "_offload", False) is False
    # seed a gpt_step winner with policy none -> program left unmarked
    c = tune.get_cache()
    key = tspace.WorkloadKey("gpt_step", 64, 32, 2, "float32", "cpu",
                             remat="auto")
    c.put(key.s, {"policy": "none", "accum": 1,
                  "block_q": 32, "block_k": 32})
    c.save()
    tune.reset_cache()
    main_prog = _build_gpt()
    assert pt.memory_optimize(main_prog, policy="auto") == []
    # and an offload winner sets the offload flag through the normal path
    c = tune.get_cache()
    c.put(key.s, {"policy": "offload", "accum": 1,
                  "block_q": 32, "block_k": 32})
    c.save()
    tune.reset_cache()
    main_prog = _build_gpt()
    pt.memory_optimize(main_prog, policy="auto")
    assert getattr(main_prog, "_offload", False) is True


def test_tune_stats_reaches_last_step_cost(tmp_cache):
    from paddle_tpu.observability import get_registry

    _seed_entry(tmp_cache)
    main_prog = _build_gpt()  # lookup hit increments the counter
    # a tiny real compile to fold stats into last_step_cost
    pt.core.unique_name.reset()
    mp, sp = pt.Program(), pt.Program()
    with pt.program_guard(mp, sp):
        from paddle_tpu import layers

        x = layers.data("x", shape=[4])
        y = layers.fc(x, 2)
        exe = pt.Executor()
        exe.run(sp)
        exe.run(mp, feed={"x": np.zeros((2, 4), np.float32)},
                fetch_list=[y])
    ts = exe.last_step_cost.get("tune")
    assert ts and ts["cache_hits"] >= 1


# -- cached mode never searches / search mode persists -------------------

def test_cached_mode_never_compiles_on_miss(tmp_cache):
    from paddle_tpu.observability import get_registry

    reg = get_registry()
    c0 = reg.value("executor.compile_count")
    rep = tune.tune_gpt_step(seq_len=64, n_layer=2, d_model=64, n_head=2,
                             vocab=61, batch=4, dtype="float32")
    assert rep["source"] == "miss" and rep["entry"] is None
    assert reg.value("executor.compile_count") == c0


def test_fingerprint_is_stable_and_geometry_sensitive(monkeypatch):
    f1 = tune.geometry_fingerprint()
    assert f1 == tune.geometry_fingerprint()
    from paddle_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "LSE_LANES", 256)
    assert tune.geometry_fingerprint() != f1
    monkeypatch.undo()
    # DIAG_W is a TUNABLE the cache stores — applying a tuned width
    # must NOT invalidate the cache that set it
    monkeypatch.setattr(pa, "DIAG_W", 512)
    assert tune.geometry_fingerprint() == f1


def test_tuned_diag_w_applied_and_env_pin_wins(tmp_cache, monkeypatch):
    """The winner's diag_w reaches the kernels (module global, set by
    the hot-path lookup); a PADDLE_TPU_DIAG_W env pin beats the cache."""
    from paddle_tpu.ops import pallas_attention as pa

    _seed_entry(tmp_cache)  # carries diag_w=16
    _build_gpt()
    assert pa.DIAG_W == 16
    monkeypatch.setattr(pa, "DIAG_W", 256)
    monkeypatch.setattr(pa, "_DIAG_W_ENV", 128)
    _build_gpt()
    assert pa.DIAG_W == 256  # env-pinned: the cache may not move it



# -- a measured search, end to end (toy GPT, real compiles) ----------------

_TOY = dict(seq_len=128, n_layer=3, d_model=64, n_head=2, vocab=61,
            batch=8, dtype="float32", fused_head=True)
_SEARCH = dict(steps=2, warmup=1, repeats=2, block_caps=(64,),
               diag_ws=(64,), accums=(1,), max_measure=8)


@pytest.fixture(scope="module")
def searched(tmp_path_factory):
    """ONE search over the remat policies of the toy GPT, its winner
    persisted to a cache file of this module's own; yields (report,
    cache path, MonkeyPatch) with PADDLE_TPU_TUNE=cached left set."""
    from paddle_tpu.ops import pallas_attention as pa

    path = tmp_path_factory.mktemp("tune") / "tuned.json"
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "DIAG_W", pa.DIAG_W)
        mp.setenv("PADDLE_TPU_TUNE_CACHE", str(path))
        mp.setenv("PADDLE_TPU_TUNE", "search")
        tune.reset_cache()
        rep = tune.tune_gpt_step(
            **_TOY, **_SEARCH,
            policies=("none", "selective", "compact", "full"))
        mp.setenv("PADDLE_TPU_TUNE", "cached")
        yield rep, path, mp
    tune.reset_cache()


def test_search_measures_and_its_winner_beats_the_worst(searched):
    rep, path, _mp = searched
    assert rep["source"] == "search" and rep["entry"] is not None
    measured = [m for m in rep["measured"] if m["verdict"] == "measured"]
    assert len(measured) >= 2
    meas = rep["entry"]["measured"]
    assert meas["median_s"] < meas["worst_median_s"]
    assert path.exists()


def test_second_invocation_is_a_cache_hit_that_compiles_nothing(searched):
    from paddle_tpu.observability import get_registry

    rep, _path, _mp = searched
    reg = get_registry()
    c0 = reg.value("executor.compile_count")
    h0 = reg.value("tune.cache_hits")
    again = tune.tune_gpt_step(**_TOY)
    assert again["source"] == "cache"
    assert again["entry"]["config"] == rep["entry"]["config"]
    assert reg.value("executor.compile_count") == c0
    assert reg.value("tune.cache_hits") > h0


def test_preflight_rejects_a_compiled_step_over_budget_before_it_runs():
    """The search's second gate after the static prune: a candidate
    whose COMPILED high-water exceeds the budget raises before a step
    executes (on the CPU every policy of the toy compiles to the same
    2 MB, under its analytic estimate, so no budget reaches this gate
    through ``tune_gpt_step``: the candidate is measured directly)."""
    from paddle_tpu.observability import get_registry
    from paddle_tpu.tune import search

    cand = {"block_q": 64, "block_k": 64, "policy": "none", "accum": 1}
    steps = get_registry().get("executor.run_seconds")
    n0 = steps.count if steps is not None else 0
    with pytest.raises(search.PreflightRejected, match="high-water"):
        search._measure_candidate(
            cand, **_TOY, steps=1, warmup=0, repeats=1, budget_bytes=4096,
            learning_rate=1e-3)
    steps = get_registry().get("executor.run_seconds")
    # the startup program ran; the training step did not
    assert (steps.count if steps is not None else 0) <= n0 + 1


def _toy_loss_bits(steps=3):
    """The toy GPT's loss trajectory under ``memory_optimize('auto')``
    as float bit patterns, and the executor that made it."""
    from paddle_tpu.models import transformer

    pt.core.unique_name.reset()
    main_prog, startup = pt.Program(), pt.Program()
    main_prog.random_seed = 7
    with pt.program_guard(main_prog, startup):
        outs = transformer.build(
            vocab_size=_TOY["vocab"], n_layer=_TOY["n_layer"],
            n_head=_TOY["n_head"], d_model=_TOY["d_model"],
            max_len=_TOY["seq_len"], dropout_rate=0.0,
            dtype=_TOY["dtype"], fused_head=True)
        pt.memory_optimize(main_prog, policy="auto")
    rng = np.random.default_rng(5)
    toks = rng.integers(0, _TOY["vocab"],
                        (_TOY["batch"], _TOY["seq_len"])).astype(np.int64)
    feed = {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}
    scope = pt.core.scope.Scope()
    with pt.core.scope.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        bits = [np.asarray(exe.run(main_prog, feed=feed,
                                   fetch_list=[outs["avg_cost"]],
                                   scope=scope)[0], np.float32).tobytes()
                for _ in range(steps)]
    return bits, exe


def test_kill_switch_trains_bit_exact_to_never_tuned(searched, tmp_path):
    """PADDLE_TPU_TUNE=0 over a POPULATED cache gives the loss bits of a
    run that never had a cache; the tuned run consults it."""
    _rep, path, mp = searched
    mp.setenv("PADDLE_TPU_TUNE", "0")
    try:
        off, exe_off = _toy_loss_bits()
    finally:
        mp.setenv("PADDLE_TPU_TUNE", "cached")
    assert (exe_off.last_step_cost.get("tune") or {}).get("mode") in (
        None, "off")
    mp.setenv("PADDLE_TPU_TUNE_CACHE", str(tmp_path / "none" / "tuned.json"))
    tune.reset_cache()
    try:
        never, _exe = _toy_loss_bits()
    finally:
        mp.setenv("PADDLE_TPU_TUNE_CACHE", str(path))
        tune.reset_cache()
    assert off == never
    _bits, exe_tuned = _toy_loss_bits(steps=1)
    assert exe_tuned.last_step_cost["tune"]["cache_hits"] > 0
