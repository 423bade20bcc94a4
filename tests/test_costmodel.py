"""Learned cost model tests (ISSUE 16): corpus ingestion edge cases
(truncated JSONL line, missing attribution fields, duplicate
(run_id, step) dedup — each CLASSIFIED, never a crash), the mixed-vintage workload-key regression (pre-PR-13 JSONL
without ``|kb=`` joins under ``backend="unknown"``), the cost-model
file's tune-cache robustness contract (corrupt / truncated / schema
mismatch -> analytic defaults + ``tune.costmodel_errors``), fitting on
synthetic rows (holdout improvement, hbm_scale clamping), the
``PADDLE_TPU_COSTMODEL=0`` kill switch's bit-exactness, and calibrated
static pruning (ordering preserved)."""

import json

import pytest

from paddle_tpu import tune
from paddle_tpu.observability import attribution as attr
from paddle_tpu.observability import get_registry
from paddle_tpu.observability.corpus import Corpus, workload_field
from paddle_tpu.tune import costmodel as cm
from paddle_tpu.tune import space as tspace

# a tiny hand-written HLO module — the deterministic attribution input
# of the bit-exactness checks (one dot, one fusion whose body op carries
# flops but no bytes, one reduce — three distinct op classes)
_TOY_HLO = """\
HloModule costmodel_toy

%fused_add (a: f32[64,64], b: f32[64,64]) -> f32[64,64] {
  %a = f32[64,64] parameter(0)
  %b = f32[64,64] parameter(1)
  ROOT %add.9 = f32[64,64] add(%a, %b)
}

ENTRY %main (p0: f32[64,64], p1: f32[64,64]) -> f32[64] {
  %p0 = f32[64,64] parameter(0)
  %p1 = f32[64,64] parameter(1)
  %dot.1 = f32[64,64] dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %fusion.2 = f32[64,64] fusion(%dot.1, %p1), kind=kLoop, calls=%fused_add
  ROOT %reduce.3 = f32[64] reduce(%fusion.2, %p1), dimensions={1}
}
"""


@pytest.fixture
def tmp_model(tmp_path, monkeypatch):
    """Scope the tune cache (and therefore the cost-model file, which
    lives next to it) to a tmp dir; reset both singletons around."""
    monkeypatch.setenv("PADDLE_TPU_TUNE_CACHE", str(tmp_path / "tuned.json"))
    monkeypatch.delenv("PADDLE_TPU_COSTMODEL_PATH", raising=False)
    monkeypatch.delenv("PADDLE_TPU_COSTMODEL", raising=False)
    tune.reset_cache()
    cm.reset_model()
    yield tmp_path / "costmodel.json"
    tune.reset_cache()
    cm.reset_model()


def _plant(path, platform, entry):
    """Write a valid fitted model file with one platform entry and drop
    the singleton so the next consult loads it."""
    m = cm.CostModel(str(path))
    m.platforms = {platform: dict(entry)}
    m.version = 1
    m.save()
    cm.reset_model()
    return m


_ENTRY = {
    "total": [1.0, 2.0, 3.5],
    "classes": {"dot": [1.5, 0.5, 0.01], "pallas": [2.0, 0.0, 0.0]},
    "train_rows": 9, "holdout_rows": 3,
    "holdout_err_pct": 4.2, "analytic_err_pct": 88.0,
    "hbm_scale": 1.0,
}


# -- corpus ingestion edge cases (the satellite contract) -----------------

def _write_jsonl(path, lines):
    path.write_text("\n".join(lines) + "\n")
    return path


def test_trainer_jsonl_classifies_rot(tmp_path):
    """One good step ingests; a truncated line, a non-object line, a
    step without wall_time and a step without attribution fields each
    classify into ``skipped`` — never a crash."""
    p = _write_jsonl(tmp_path / "run.jsonl", [
        json.dumps({"event": "run_meta", "run_id": "rid1",
                    "git_sha": "abc123"}),
        json.dumps({"event": "step", "step": 1, "wall_time": 0.5,
                    "attr_workload": "op=step|t=128|kb=pallas_tpu",
                    "attr_est_ms": 3.0, "attr_model_err_pct": -99.4,
                    "attr_classes": {"dot": [1e9, 2e8, 3, 2.5]}}),
        '{"event": "step", "step":',                 # truncated write
        json.dumps([1, 2]),                          # not an object
        json.dumps({"event": "step", "step": 3}),    # no wall_time
        json.dumps({"event": "step", "step": 4, "wall_time": 0.3}),
        json.dumps({"event": "pass", "pass_id": 0}),  # expected, not rot
    ])
    co = Corpus()
    assert co.ingest_trainer_jsonl(p) == 1
    row = co.rows[0]
    assert row["run_id"] == "rid1" and row["git_sha"] == "abc123"
    assert row["measured_ms"] == 500.0
    assert row["backend"] == "pallas_tpu"
    assert row["classes"]["dot"]["est_ms"] == 2.5
    reasons = [r for _s, r in co.skipped]
    assert any("truncated or non-JSON line" in r for r in reasons)
    assert any("not a JSON object" in r for r in reasons)
    assert any("no measured wall_time" in r for r in reasons)
    assert any("no attribution fields" in r for r in reasons)
    assert len(co.skipped) == 4  # the pass record is NOT rot


def test_duplicate_run_id_step_rows_dedup(tmp_path):
    """Re-ingesting the same file is idempotent: every row classifies
    as a duplicate, the corpus does not grow."""
    p = _write_jsonl(tmp_path / "run.jsonl", [
        json.dumps({"event": "run_meta", "run_id": "rid1"}),
        json.dumps({"event": "step", "step": 1, "wall_time": 0.5,
                    "attr_workload": "op=step|t=128|kb=pallas_tpu",
                    "attr_est_ms": 3.0}),
        json.dumps({"event": "step", "step": 2, "wall_time": 0.4,
                    "attr_workload": "op=step|t=128|kb=pallas_tpu",
                    "attr_est_ms": 3.0}),
    ])
    co = Corpus()
    assert co.ingest_trainer_jsonl(p) == 2
    assert co.ingest_trainer_jsonl(p) == 0
    assert len(co) == 2
    assert sum("duplicate (run_id, step)" in r
               for _s, r in co.skipped) == 2


def test_corpus_save_load_roundtrip(tmp_path):
    co = Corpus()
    assert co.add_row("unit", workload="op=step|t=64|kb=xla_ref",
                      measured_ms=7.5, est_ms=1.0, flops=2e9,
                      run_id="r1", step=1)
    assert not co.add_row("unit", measured_ms=0.0, est_ms=1.0)  # gate
    store = tmp_path / "corpus.jsonl"
    co.save_jsonl(store)
    fresh = Corpus()
    assert fresh.load_jsonl(store) == 1
    assert fresh.rows[0]["workload"] == "op=step|t=64|kb=xla_ref"
    # loading AGAIN dedups (append-only store, idempotent read-back)
    assert fresh.load_jsonl(store) == 0
    assert len(fresh) == 1


# -- mixed-vintage JSONL: the pre-PR-13 |kb= regression -------------------

def test_normalize_workload_key_backfills_backend():
    assert attr.normalize_workload_key(
        "op=step|t=128") == "op=step|t=128|kb=unknown"
    assert attr.normalize_workload_key(
        "op=step|t=128|kb=pallas_tpu") == "op=step|t=128|kb=pallas_tpu"
    assert attr.normalize_workload_key(None) is None
    assert attr.normalize_workload_key("") is None
    assert attr.normalize_workload_key("freeform") == "freeform"


def test_mixed_vintage_jsonl_joins_under_unknown_backend(tmp_path):
    """The regression fix: a pre-PR-13 step record (workload key with
    no ``|kb=`` token) must INGEST — backend backfilled to "unknown" —
    instead of being silently skipped next to new-vintage rows."""
    p = _write_jsonl(tmp_path / "mixed.jsonl", [
        json.dumps({"event": "run_meta", "run_id": "old"}),
        json.dumps({"event": "step", "step": 1, "wall_time": 0.2,
                    "attr_workload": "op=step|t=128|b=4|plat=cpu",
                    "attr_est_ms": 1.5}),
        json.dumps({"event": "step", "step": 2, "wall_time": 0.2,
                    "attr_workload":
                        "op=step|t=128|b=4|plat=cpu|kb=pallas_tpu",
                    "attr_est_ms": 1.5}),
    ])
    co = Corpus()
    assert co.ingest_trainer_jsonl(p) == 2
    old, new = co.rows
    assert old["workload"].endswith("|kb=unknown")
    assert old["backend"] == "unknown" and old["platform"] == "cpu"
    assert new["backend"] == "pallas_tpu"
    assert co.summary()["backends"] == {"unknown": 1, "pallas_tpu": 1}


def test_reconcile_carries_normalized_workload():
    rec = attr.reconcile({"est_ms_total": 2.0,
                          "workload": "op=step|t=64"}, 0.004)
    assert rec["workload"] == "op=step|t=64|kb=unknown"
    assert rec["measured_ms"] == 4.0 and rec["err_pct"] == -50.0


def test_workload_field_parses_tokens():
    k = "op=flash|t=512|kb=pallas_tpu|plat=cpu"
    assert workload_field(k, "kb") == "pallas_tpu"
    assert workload_field(k, "plat") == "cpu"
    assert workload_field(k, "missing") is None
    assert workload_field(None, "kb") is None


# -- cost-model file robustness (tune-cache contract) ---------------------

def _errors():
    return get_registry().value("tune.costmodel_errors")


def test_costmodel_corrupt_file_degrades_to_analytic(tmp_model):
    plat = cm.current_platform()
    _plant(tmp_model, plat, _ENTRY)
    assert cm.active_entry(plat) is not None
    tmp_model.write_bytes(b"\x00garbage not json{{{")
    cm.reset_model()
    before = _errors()
    assert cm.active_entry(plat) is None
    m = cm.get_model()
    assert m.platforms == {} and "unreadable" in m.stale_reason
    assert _errors() == before + 1
    assert cm.model_status(plat) == {"mode": "analytic"}
    # the next fit rewrites a valid file over the garbage
    _plant(tmp_model, plat, _ENTRY)
    assert cm.active_entry(plat) is not None


def test_costmodel_truncated_file_degrades(tmp_model):
    plat = cm.current_platform()
    _plant(tmp_model, plat, _ENTRY)
    full = tmp_model.read_text()
    tmp_model.write_text(full[: len(full) // 2])
    cm.reset_model()
    before = _errors()
    assert cm.active_entry(plat) is None
    assert cm.get_model().stale_reason is not None
    assert _errors() == before + 1


def test_costmodel_schema_mismatch_degrades(tmp_model):
    plat = cm.current_platform()
    _plant(tmp_model, plat, _ENTRY)
    data = json.loads(tmp_model.read_text())
    data["schema_version"] = 999
    tmp_model.write_text(json.dumps(data))
    cm.reset_model()
    before = _errors()
    assert cm.active_entry(plat) is None
    assert "schema_version" in cm.get_model().stale_reason
    assert _errors() == before + 1


def test_costmodel_kill_switch_env(tmp_model, monkeypatch):
    plat = cm.current_platform()
    _plant(tmp_model, plat, _ENTRY)
    assert cm.model_status(plat)["mode"] == "fitted"
    monkeypatch.setenv("PADDLE_TPU_COSTMODEL", "0")
    assert cm.active_entry(plat) is None
    assert cm.model_status(plat) == {"mode": "analytic"}
    assert cm.hbm_scale_for(plat) == 1.0


# -- fitting on synthetic rows --------------------------------------------

def _linear_rows(n, platform="testplat"):
    """Rows drawn from measured = 2*gflops + 1*gbytes + 5ms overhead,
    with the analytic est_ms recorded ~100x low (the CPU story)."""
    rows = []
    for i in range(1, n + 1):
        gf, gb = float(i), 0.5 * i
        measured = 2.0 * gf + 1.0 * gb + 5.0
        rows.append({
            "platform": platform, "workload": f"op=step|t={i}|kb=unknown",
            "measured_ms": measured, "est_ms": measured / 100.0,
            "flops": gf * 1e9, "bytes": gb * 1e9,
            "classes": {"dot": {"flops": gf * 1e9, "bytes": gb * 1e9,
                                "ops": 2, "est_ms": measured / 100.0}},
            "run_id": f"r{i}", "step": i, "source": "unit",
        })
    return rows


def test_fit_improves_on_analytic_holdout():
    plats = cm.fit_cost_model(_linear_rows(12))
    e = plats["testplat"]
    assert e["train_rows"] == 9 and e["holdout_rows"] == 3
    assert e["holdout_err_pct"] is not None
    assert e["analytic_err_pct"] is not None
    # the recorded analytic estimate is ~100x low -> ~99% error; the
    # fitted linear model must beat it decisively on held-out rows
    assert e["holdout_err_pct"] < e["analytic_err_pct"]
    assert e["analytic_err_pct"] > 90.0
    assert e["holdout_err_pct"] < 25.0


def test_fit_too_few_rows_stays_analytic():
    assert cm.fit_cost_model(_linear_rows(2)) == {}


def test_hbm_scale_clamped_to_conservative_band():
    """Measured/estimated HBM ratios calibrate the bound but only
    within [1.0, 2.0] — the prune may tighten, never relax."""
    for ratio, expect in ((3.0, 2.0), (0.5, 1.0), (1.4, 1.4)):
        rows = _linear_rows(12)
        for r in rows:
            r["hbm_est_bytes"] = 1e9
            r["hbm_high_water_bytes"] = ratio * 1e9
        e = cm.fit_cost_model(rows)["testplat"]
        assert e["hbm_scale"] == pytest.approx(expect)
    assert cm.fit_cost_model(_linear_rows(12))["testplat"][
        "hbm_scale"] == 1.0  # no hbm pairs -> neutral


def test_fit_and_save_roundtrip(tmp_model):
    m = cm.fit_and_save(_linear_rows(12))
    assert m.version == 1 and tmp_model.exists()
    e = cm.get_model().entry("testplat")
    assert e is not None and len(e["total"]) == 3
    # refit bumps the version (cross-run lineage)
    assert cm.fit_and_save(_linear_rows(12)).version == 2


def test_predictions_from_planted_entry():
    ms, comp, mem = cm.predict_class_ms(_ENTRY, "dot", 2e9, 4e9, 10)
    assert comp == pytest.approx(3.0) and mem == pytest.approx(2.0)
    assert ms == pytest.approx(3.0 + 2.0 + 0.1)
    # unknown class falls back to the total's a/b with no overhead
    ms2, c2, m2 = cm.predict_class_ms(_ENTRY, "mystery", 1e9, 1e9, 5)
    assert ms2 == pytest.approx(1.0 + 2.0)
    # sched cost = pallas-class flops term + the per-step constant
    assert cm.predict_sched_ms(_ENTRY, 3e9) == pytest.approx(
        2.0 * 3.0 + 3.5)


# -- consult points: bit-exactness + ordering -----------------------------

def test_attribute_hlo_kill_switch_bit_exact(tmp_model, monkeypatch):
    """With a fitted model on disk, PADDLE_TPU_COSTMODEL=0 must
    reproduce the no-model attribution byte-for-byte."""
    baseline = attr.attribute_hlo(_TOY_HLO)  # no model file yet
    plat = cm.current_platform()
    _plant(tmp_model, plat, _ENTRY)
    fitted = attr.attribute_hlo(_TOY_HLO)
    assert json.dumps(fitted, sort_keys=True) != json.dumps(
        baseline, sort_keys=True)  # the fit is actually consulted
    monkeypatch.setenv("PADDLE_TPU_COSTMODEL", "0")
    killed = attr.attribute_hlo(_TOY_HLO)
    assert json.dumps(killed, sort_keys=True) == json.dumps(
        baseline, sort_keys=True)


@pytest.mark.parametrize("rot", ["garbage", "truncated", "schema"])
def test_unloadable_model_file_leaves_attribution_as_without_one(
        tmp_model, rot):
    """A model file that cannot be loaded changes no estimate: the
    attribution table is the no-model one byte for byte."""
    baseline = json.dumps(attr.attribute_hlo(_TOY_HLO), sort_keys=True)
    plat = cm.current_platform()
    _plant(tmp_model, plat, _ENTRY)
    good = tmp_model.read_text()
    assert json.dumps(attr.attribute_hlo(_TOY_HLO),
                      sort_keys=True) != baseline
    tmp_model.write_text({
        "garbage": "{not json",
        "truncated": good[: len(good) // 2],
        "schema": json.dumps({"schema_version": 999, "platforms": {}}),
    }[rot])
    cm.reset_model()
    assert json.dumps(attr.attribute_hlo(_TOY_HLO),
                      sort_keys=True) == baseline
    assert cm.get_model().stale_reason is not None
    tmp_model.write_text(good)
    cm.reset_model()
    assert cm.model_status(plat)["mode"] == "fitted"


def test_estimate_gpt_step_hbm_scale_and_kill_switch(tmp_model,
                                                     monkeypatch):
    args = dict(n_layer=6, d_model=768, n_head=12, vocab=32000,
                seq_len=16384, batch=6, policy="offload", accum=1)
    base = tspace.estimate_gpt_step_hbm(**args)
    plat = cm.current_platform()
    _plant(tmp_model, plat, dict(_ENTRY, hbm_scale=1.5))
    assert tspace.estimate_gpt_step_hbm(**args) == int(base * 1.5)
    monkeypatch.setenv("PADDLE_TPU_COSTMODEL", "0")
    assert tspace.estimate_gpt_step_hbm(**args) == base  # bit-exact


def test_prune_static_calibrated_ordering(tmp_model):
    """The calibrated slack test must preserve the analytic verdicts'
    structure: the best candidate always survives, analytic survivors
    stay survivors (overhead only LOOSENS the ratio), and a
    zero-overhead fit reproduces the analytic prune verbatim with the
    'calibrated roofline' reason."""
    cands = [{"block_q": bq, "block_k": bk}
             for bq, bk in ((128, 128), (256, 256), (512, 512))]
    # slack below the 256/512-block candidates' ~1.20x scheduled-flop
    # ratio so the analytic prune actually rejects something
    kw = dict(seq_len=512, d_head=64, n_head=4, roofline_slack=1.1)
    base_surv, base_pruned = tspace.prune_static(candidates=cands, **kw)
    assert base_surv and any("roofline" in r for _c, r in base_pruned)
    plat = cm.current_platform()
    # zero per-step overhead: fitted ratio == flop ratio exactly
    _plant(tmp_model, plat, dict(
        _ENTRY, total=[1.0, 2.0, 0.0],
        classes={"pallas": [2.0, 0.0, 0.0]}))
    surv0, pruned0 = tspace.prune_static(candidates=cands, **kw)
    assert [c["block_q"] for c in surv0] == [
        c["block_q"] for c in base_surv]
    assert any("calibrated roofline" in r for _c, r in pruned0)
    # a large per-step overhead dilutes flop deltas: every analytic
    # survivor still survives (never a NEW rejection) and the best
    # candidate is unchanged
    _plant(tmp_model, plat, dict(
        _ENTRY, total=[1.0, 2.0, 1e6],
        classes={"pallas": [2.0, 0.0, 0.0]}))
    surv_loose, _ = tspace.prune_static(candidates=cands, **kw)
    loose_keys = {(c["block_q"], c["block_k"]) for c in surv_loose}
    assert {(c["block_q"], c["block_k"])
            for c in base_surv} <= loose_keys
    assert base_surv[0]["block_q"] == surv_loose[0]["block_q"]


# -- the loop closed on measured runs: reporter JSONL -> corpus -> fit ->
# the next compile consults it ---------------------------------------------

_TOY = dict(vocab=61, n_layer=3, n_head=2, d_model=64, batch=4)


class EndIteration:
    """What the trainer hands the reporter a step (it dispatches on the
    class NAME): the loop below makes the step stream itself, so the
    production MetricsReporter writes JSONL from measured walls and
    compiled cost dicts without a trainer around it."""

    def __init__(self, batch_id, cost, wall_time, step_cost, samples):
        self.pass_id, self.batch_id, self.cost = 0, batch_id, cost
        self.wall_time, self.step_cost = wall_time, step_cost
        self.samples, self.throughput = samples, samples / wall_time
        self.mfu = self.reader_wait = self.grad_norm = None


def _measured_run(seq_len, steps, jsonl_path, run_id):
    """Compile the toy GPT at ``seq_len`` and stream ``steps`` measured
    steps through a MetricsReporter; returns the last ``last_step_cost``."""
    import time

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import transformer
    from paddle_tpu.observability import MetricsReporter

    pt.core.unique_name.reset()
    main_prog, startup = pt.Program(), pt.Program()
    main_prog.random_seed = 7
    with pt.program_guard(main_prog, startup):
        outs = transformer.build(
            vocab_size=_TOY["vocab"], n_layer=_TOY["n_layer"],
            n_head=_TOY["n_head"], d_model=_TOY["d_model"],
            max_len=seq_len, dropout_rate=0.0, dtype="float32",
            fused_head=True)
        pt.memory_optimize(main_prog, policy="selective")
    rng = np.random.default_rng(seq_len)
    toks = rng.integers(0, _TOY["vocab"],
                        (_TOY["batch"], seq_len)).astype(np.int64)
    feed = {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}
    scope = pt.core.scope.Scope()
    reporter = MetricsReporter(log_every_n=0, jsonl_path=str(jsonl_path),
                               run_meta={"run_id": run_id})
    try:
        with pt.core.scope.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup, scope=scope)
            # the first step pays the compile outside the measured walls
            exe.run(main_prog, feed=feed, fetch_list=[outs["avg_cost"]],
                    scope=scope)
            for i in range(steps):
                t0 = time.perf_counter()
                loss = exe.run(main_prog, feed=feed,
                               fetch_list=[outs["avg_cost"]],
                               scope=scope)[0]
                reporter(EndIteration(
                    i, float(np.asarray(loss).ravel()[0]),
                    time.perf_counter() - t0, dict(exe.last_step_cost),
                    _TOY["batch"]))
            return dict(exe.last_step_cost)
    finally:
        reporter.close()


@pytest.fixture(scope="module")
def closed_loop(tmp_path_factory):
    """Two measured runs (t=128, t=64) -> corpus -> ``fit_and_save`` ->
    a third run compiled AFTER the fit; every file under one temporary
    directory, both singletons reset around it."""
    tmp = tmp_path_factory.mktemp("costmodel_loop")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PADDLE_TPU_TUNE_CACHE", str(tmp / "tuned.json"))
        mp.delenv("PADDLE_TPU_COSTMODEL_PATH", raising=False)
        mp.delenv("PADDLE_TPU_COSTMODEL", raising=False)
        tune.reset_cache()
        cm.reset_model()
        out = {"demo_base": tune.flagship_static_demo(), "mp": mp}
        out["cost_a"] = _measured_run(128, 6, tmp / "a.jsonl", "loop-a")
        _measured_run(64, 6, tmp / "b.jsonl", "loop-b")
        co = Corpus()
        out["ingested"] = (co.ingest_trainer_jsonl(tmp / "a.jsonl"),
                           co.ingest_trainer_jsonl(tmp / "b.jsonl"))
        out["corpus"] = co
        out["entry"] = cm.fit_and_save(co).entry(cm.current_platform())
        out["status"] = cm.model_status()
        out["cost_c"] = _measured_run(64, 2, tmp / "c.jsonl", "loop-c")
        out["rows_c"] = [json.loads(ln) for ln in
                         (tmp / "c.jsonl").read_text().splitlines()
                         if ln.strip()]
        yield out
    tune.reset_cache()
    cm.reset_model()


def test_reporter_jsonl_of_measured_runs_ingests_every_step(closed_loop):
    co = closed_loop["corpus"]
    assert closed_loop["ingested"] == (6, 6) and co.skipped == []
    assert {r["platform"] for r in co.rows} == {cm.current_platform()}
    assert {r["run_id"] for r in co.rows} == {"loop-a", "loop-b"}
    # before any fit a compile says so: the field is never absent
    assert closed_loop["cost_a"]["costmodel"] == {"mode": "analytic"}


def test_fit_on_measured_rows_beats_the_analytic_roofline(closed_loop):
    """Off the accelerator the analytic roofline is some 100x low; the
    fitted per-step constant must close it on the held-out rows."""
    e = closed_loop["entry"]
    assert e is not None and e["train_rows"] >= 8
    assert e["holdout_err_pct"] < e["analytic_err_pct"]
    st = closed_loop["status"]
    assert st["mode"] == "fitted" and st["train_rows"] == e["train_rows"]


def test_compile_after_the_fit_records_it_in_cost_and_jsonl(closed_loop):
    assert closed_loop["cost_c"]["costmodel"]["mode"] == "fitted"
    steps = [r for r in closed_loop["rows_c"] if r.get("event") == "step"]
    assert len(steps) == 2
    assert all(r["costmodel"]["mode"] == "fitted" for r in steps)


def test_flagship_prune_selects_the_same_schedule_under_the_fit(closed_loop):
    """``predict_sched_ms`` is monotone in the flops, so a fit may move
    estimates, never the order: the t=16k static prune still rejects the
    round-5 configuration and picks the schedule it picked without a
    model; with the kill switch the whole demonstration is the same."""
    base = closed_loop["demo_base"]
    fitted = tune.flagship_static_demo()
    assert fitted["gpt_t16k_rejected_r05_config"] is not None
    assert base["gpt_t16k_selected_policy"] is not None
    for k in ("gpt_t16k_selected_policy", "gpt_t16k_selected_accum",
              "gpt_t16k_selected_block_q", "gpt_t16k_selected_block_k"):
        assert fitted[k] == base[k], k
    mp = closed_loop["mp"]
    mp.setenv("PADDLE_TPU_COSTMODEL", "0")
    try:
        assert tune.flagship_static_demo() == base
        assert cm.model_status() == {"mode": "analytic"}
    finally:
        mp.delenv("PADDLE_TPU_COSTMODEL")
