"""Per-op attribution engine + crash flight recorder (ISSUE 11) —
HLO-walk table math on planted text, coverage on a real compiled GPT
step, roofline bound classification, flight-bundle dumps via the PR-8
injected faults, grad-norm telemetry, and serving goodput accounting."""

import json
import math
import os
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.models import transformer
from paddle_tpu.observability import attribution as attr
from paddle_tpu.observability import flight
from paddle_tpu.observability import metrics as _obs


# -- HLO walk on planted text ------------------------------------------------

_PLANTED_HLO = """\
HloModule planted

%fused_computation.1 (p0: f32[64,64]) -> f32[64,64] {
  %p0 = f32[64,64]{1,0} parameter(0)
  %exp.0 = f32[64,64]{1,0} exponential(f32[64,64]{1,0} %p0)
  ROOT %add.9 = f32[64,64]{1,0} add(f32[64,64]{1,0} %exp.0, f32[64,64]{1,0} %p0)
}

ENTRY %main (a: f32[64,32], b: f32[32,64]) -> f32[64,64] {
  %a = f32[64,32]{1,0} parameter(0)
  %b = f32[32,64]{1,0} parameter(1)
  %dot.1 = f32[64,64]{1,0} dot(f32[64,32]{1,0} %a, f32[32,64]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %fus.1 = f32[64,64]{1,0} fusion(f32[64,64]{1,0} %dot.1), kind=kLoop, calls=%fused_computation.1
  %ar.0 = f32[64,64]{1,0} all-reduce(f32[64,64]{1,0} %fus.1), replica_groups={}, to_apply=%sum
  %kern.0 = f32[64,64]{1,0} multiply(f32[64,64]{1,0} %ar.0, f32[64,64]{1,0} %ar.0), metadata={op_name="flash" source_file="/repo/paddle_tpu/ops/pallas_attention.py" source_line=1}
  ROOT %cp.0 = f32[64,64]{1,0} copy(f32[64,64]{1,0} %kern.0)
}
"""


def test_attribute_hlo_planted_table():
    att = attr.attribute_hlo(_PLANTED_HLO, peak_flops=1e12, hbm_bw=1e11)
    cls = att["classes"]
    # dot: 2 * 64*64 * 32 contraction width — exact
    assert cls["matmul"]["flops"] == 2 * 64 * 64 * 32
    # the fusion body's add counts flops (one per element) but NO bytes
    # (fusion intermediates never touch HBM); the exponential is a
    # transcendental — its own column, excluded from flops
    assert cls["elementwise"]["flops"] == 64 * 64  # body add only
    assert cls["elementwise"]["transcendentals"] == 64 * 64
    # the fusion op line carries the boundary bytes
    assert cls["elementwise"]["bytes"] == 2 * 64 * 64 * 4
    # collective classed by kind
    assert cls["collective.all-reduce"]["ops"] == 1
    assert cls["collective.all-reduce"]["bytes"] == 2 * 64 * 64 * 4
    # the multiply whose source_file is pallas_attention belongs to the
    # KERNEL, not to elementwise
    assert cls["pallas"]["ops"] == 1
    assert cls["pallas"]["flops"] == 64 * 64
    # shares sum to ~1 and every class has a bound verdict
    assert abs(sum(r["share"] for r in cls.values()) - 1.0) < 0.01
    assert all(r["bound"] in ("compute", "memory") for r in cls.values())


def test_roofline_bound_classification():
    # compute-heavy: flops/peak dominates bytes/bw
    hlo = """\
ENTRY %m (a: f32[512,512], b: f32[512,512]) -> f32[512,512] {
  %a = f32[512,512]{1,0} parameter(0)
  %b = f32[512,512]{1,0} parameter(1)
  ROOT %dot.1 = f32[512,512]{1,0} dot(f32[512,512]{1,0} %a, f32[512,512]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    att = attr.attribute_hlo(hlo, peak_flops=1e12, hbm_bw=1e12)
    assert att["classes"]["matmul"]["bound"] == "compute"
    # memory-heavy: same table against a slow-memory roofline flips
    att2 = attr.attribute_hlo(hlo, peak_flops=1e15, hbm_bw=1e9)
    assert att2["classes"]["matmul"]["bound"] == "memory"


def _small_gpt(policy="selective", n_layer=3, t=16, d=32, vocab=64):
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = 7
    with pt.program_guard(main, startup):
        outs = transformer.build(vocab_size=vocab, n_layer=n_layer,
                                 n_head=2, d_model=d, max_len=t,
                                 dropout_rate=0.0, dtype="float32")
    if policy:
        pt.memory_optimize(main, policy=policy)
    return main, startup, outs["avg_cost"]


@pytest.fixture
def gpt_compiled():
    main, startup, loss = _small_gpt()
    scope = pt.Scope()
    with pt.core.scope.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        rng = np.random.default_rng(5)
        toks = rng.integers(0, 64, (2, 16)).astype(np.int64)
        feed = {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}
        cost = exe.compile_only(main, feed=feed, fetch_list=[loss],
                                scope=scope)
    return exe, cost


def test_attribution_coverage_on_compiled_gpt(gpt_compiled):
    """The real compiled step leaves a table: a coverage against the
    executable's own cost-analysis flops, a ``matmul`` class and the
    workload key.  No bound on the coverage and no ``pallas`` class:
    under the installed toolchain the interpret-mode kernels leave no
    such class and the walk recognises 0.44 of the compiled text's flops
    and none of its dots'.  What the table is FOR is judged on
    hand-written HLO by the cases around this one (``ROADMAP.md`` Design
    ``five-recorders``)."""
    exe, cost = gpt_compiled
    att = exe.last_attribution
    assert att is not None
    assert att["coverage"] is not None and 0 < att["coverage"] <= 1.05
    assert "matmul" in att["classes"]
    assert att["workload"].startswith("op=step|t=16|")
    assert "remat=selective" in att["workload"]


def test_attribution_summary_in_cost_dict(gpt_compiled):
    exe, cost = gpt_compiled
    summ = cost.get("attribution")
    assert summ and summ["top"] and summ["coverage"] == \
        exe.last_attribution["coverage"]
    # top entries are [class, share, bound] sorted by estimated time
    assert all(len(e) == 3 for e in summ["top"])


def test_attribution_kill_switch(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_ATTR", "0")
    main, startup, loss = _small_gpt(policy=None, n_layer=2)
    scope = pt.Scope()
    with pt.core.scope.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        rng = np.random.default_rng(5)
        toks = rng.integers(0, 64, (2, 16)).astype(np.int64)
        feed = {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}
        cost = exe.compile_only(main, feed=feed, fetch_list=[loss],
                                scope=scope)
    assert exe.last_attribution is None
    assert "attribution" not in cost


def test_finalize_roofline_recomputes_shares_after_flop_patch():
    """The TPU path patches opaque-kernel flops AFTER the walk; the
    re-finalize must move est_ms/bound/share, or a flash slowdown
    would never show in the pallas share (review finding)."""
    hlo = """\
ENTRY %m (a: f32[64,64]) -> f32[64,64] {
  %a = f32[64,64]{1,0} parameter(0)
  %cc.0 = f32[64,64]{1,0} custom-call(f32[64,64]{1,0} %a), custom_call_target="tpu_custom_call"
  ROOT %add.0 = f32[64,64]{1,0} add(f32[64,64]{1,0} %cc.0, f32[64,64]{1,0} %a)
}
"""
    att = attr.attribute_hlo(hlo, peak_flops=1e9, hbm_bw=1e12)
    before = att["classes"]["pallas"]["share"]
    assert att["classes"]["pallas"]["bound"] == "memory"
    att["classes"]["pallas"]["flops"] = 10 ** 9  # a 1s kernel estimate
    attr._finalize_roofline(att)
    after = att["classes"]["pallas"]
    assert after["share"] > before and after["share"] > 0.9
    assert after["bound"] == "compute"
    assert att["hlo_flops_total"] >= 10 ** 9


def test_reconcile_error_pct():
    att = {"est_ms_total": 2.0}
    rec = attr.reconcile(att, 0.004)  # measured 4 ms
    assert rec["measured_ms"] == 4.0
    assert rec["err_pct"] == -50.0
    assert attr.reconcile(att, None) is None
    assert attr.reconcile({}, 0.01) is None


# -- flight recorder ---------------------------------------------------------

@pytest.fixture
def recorder(tmp_path):
    rec = flight.FlightRecorder(capacity=5, out_dir=str(tmp_path))
    old = flight.set_recorder(rec)
    yield rec
    flight.set_recorder(old)


def test_flight_ring_bounded_and_dump_loadable(recorder, tmp_path):
    for i in range(12):
        recorder.record_step(step=i, loss=float(i), grad_norm=0.5 * i)
    steps = recorder.steps()
    assert len(steps) == 5 and steps[0]["step"] == 7  # newest window
    path = recorder.dump("watchdog", age_s=1.5)
    assert path and os.path.exists(path)
    b = flight.load_bundle(path)
    assert b["reason"] == "watchdog"
    assert b["context"]["age_s"] == 1.5
    assert [s["step"] for s in b["steps"]] == [7, 8, 9, 10, 11]
    assert b["grad_norm_window"] == [0.5 * i for i in range(7, 12)]
    assert "metrics" in b and "spans" in b


def test_flight_kill_switch(recorder, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLIGHT", "0")
    recorder.record_step(step=1)
    assert recorder.steps() == []
    assert recorder.dump("watchdog") is None
    assert recorder.dumps == []


def test_flight_dump_cap(recorder):
    recorder.max_dumps = 2
    assert recorder.dump("watchdog") is not None
    assert recorder.dump("watchdog") is not None
    assert recorder.dump("watchdog") is None  # storm guard
    assert len(recorder.dumps) == 2


def test_classify_exception():
    assert flight.classify_exception(MemoryError("x")) == "oom"
    assert flight.classify_exception(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory")) == "oom"
    assert flight.classify_exception(
        FloatingPointError("NaN detected")) == "nan_trip"
    assert flight.classify_exception(
        ValueError("bad shape")) == "trainer_exception"
    # cause chains are walked
    try:
        try:
            raise RuntimeError("Failed to allocate 1G")
        except RuntimeError as inner:
            raise RuntimeError("error lowering op") from inner
    except RuntimeError as outer:
        assert flight.classify_exception(outer) == "oom"


def _tiny_trainer():
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[8])
        y = layers.data("y", shape=[1])
        h = layers.fc(x, 8, act="relu")
        loss = layers.reduce_mean(layers.square(layers.fc(h, 1) - y))
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        trainer = pt.trainer.Trainer(loss, [x, y])
    return main, trainer


def _reader(n=4, batch=4):
    rng = np.random.default_rng(0)

    def reader():
        for _ in range(n):
            yield [(rng.normal(size=(8,)).astype(np.float32),
                    rng.normal(size=(1,)).astype(np.float32))
                   for _ in range(batch)]

    return reader


def test_injected_nan_fault_dumps_flight_bundle(recorder, monkeypatch):
    """The PR-8 nan_grad injection point gates the flight recorder: the
    poisoned step's bundle carries the triggering step record and the
    grad-norm window."""
    from paddle_tpu.resilience import faults

    faults.reset()
    main, trainer = _tiny_trainer()
    monkeypatch.setenv("PADDLE_TPU_FAULT", "nan_grad:2")
    with pt.program_guard(main, pt.Program()):
        trainer.train(_reader(), num_passes=1)
    nan_dumps = [p for p in recorder.dumps if "nan_trip" in p]
    assert nan_dumps, recorder.dumps
    b = flight.load_bundle(nan_dumps[0])
    assert b["reason"] == "nan_trip"
    assert any(isinstance(s.get("loss"), float)
               and math.isnan(s["loss"]) for s in b["steps"])
    assert b["grad_norm_window"]
    # phase durations recorded per step
    assert all("phase_dispatch" in s for s in b["steps"])


def test_trainer_exception_dumps_flight_bundle(recorder, monkeypatch):
    """An exception escaping the train loop (the injected reader fault)
    dumps a classified bundle before propagating."""
    from paddle_tpu.resilience import faults

    faults.reset()
    main, trainer = _tiny_trainer()
    monkeypatch.setenv("PADDLE_TPU_FAULT", "reader_err:3")
    with pt.program_guard(main, pt.Program()):
        with pytest.raises(RuntimeError):
            trainer.train(_reader(), num_passes=1)
    assert any("trainer_exception" in p for p in recorder.dumps)
    b = flight.load_bundle(
        [p for p in recorder.dumps if "trainer_exception" in p][0])
    assert b["steps"]  # the pre-crash history survived


def test_watchdog_trip_dumps_flight_bundle(recorder):
    from paddle_tpu.resilience.watchdog import Watchdog

    wd = Watchdog(deadline=0.1, label="attr-test", interval=0.02)
    try:
        time.sleep(0.5)
    finally:
        wd.stop()
    wd_dumps = [p for p in recorder.dumps if "watchdog" in p]
    assert wd_dumps
    b = flight.load_bundle(wd_dumps[0])
    assert b["reason"] == "watchdog"
    assert b["context"]["label"] == "attr-test"


# -- training-dynamics telemetry ---------------------------------------------

def test_grad_norm_recorded_per_step(recorder):
    main, trainer = _tiny_trainer()
    seen = []

    def handler(ev):
        if type(ev).__name__ == "EndIteration":
            seen.append(ev.grad_norm)

    with pt.program_guard(main, pt.Program()):
        trainer.train(_reader(), num_passes=1, event_handler=handler)
    assert len(seen) == 4
    assert all(isinstance(g, float) and g > 0 for g in seen)
    assert _obs.get_registry().value("trainer.grad_norm") > 0
    # the flight ring carries the same stream
    assert all(s.get("grad_norm") for s in recorder.steps())


def test_grad_norm_kill_switch(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_GRADNORM", "0")
    main, trainer = _tiny_trainer()
    seen = []

    def handler(ev):
        if type(ev).__name__ == "EndIteration":
            seen.append(ev.grad_norm)

    with pt.program_guard(main, pt.Program()):
        trainer.train(_reader(n=2), num_passes=1, event_handler=handler)
    assert seen and all(g is None for g in seen)


def test_loss_zscore_in_jsonl(tmp_path):
    from paddle_tpu.observability import MetricsReporter, read_jsonl

    main, trainer = _tiny_trainer()
    path = str(tmp_path / "run.jsonl")
    reporter = MetricsReporter(log_every_n=0, jsonl_path=path)
    with pt.program_guard(main, pt.Program()):
        trainer.train(_reader(n=12), num_passes=1,
                      event_handler=reporter)
    reporter.close()
    recs = read_jsonl(path, event="step")
    assert len(recs) == 12
    assert all("grad_norm" in r and r["grad_norm"] > 0 for r in recs)
    # z-score appears once the window holds 8 samples
    assert any(r.get("loss_zscore") is not None for r in recs[8:])
    # attribution summary rides the same records
    assert any(r.get("attr_est_ms") for r in recs)
    assert any(r.get("attr_model_err_pct") is not None for r in recs)


# -- serving goodput (the engine-side accounting) ----------------------------

VOCAB, NL, NH, DM, T = 50, 2, 2, 32, 32


@pytest.fixture
def serving_params():
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        transformer.build(vocab_size=VOCAB, n_layer=NL, n_head=NH,
                          d_model=DM, max_len=T, dropout_rate=0.0,
                          dtype="float32")
        exe = pt.Executor()
        exe.run(startup)
        return transformer.extract_params(program=main)


def test_goodput_counts_only_slo_met_tokens(serving_params):
    from paddle_tpu.serving import ServingEngine

    _obs.get_registry().clear(prefix="serving.")
    eng = ServingEngine(serving_params, NL, NH, DM, max_len=T,
                        max_slots=4, decode_chunk=2, min_bucket=4,
                        ttft_slo_s=600.0, e2e_slo_s=600.0)
    prompts = [np.arange(1, 5, dtype=np.int32)] * 3
    outs = eng.generate_many(prompts, max_new_tokens=4)
    st = eng.stats()
    assert st.get("serving.slo_violations", 0) == 0
    assert st["serving.goodput_tok_s"] > 0
    # every request judged, all within budget
    assert len(outs) == 3
