"""End-to-end tracing engine (observability/trace.py) — span runtime
semantics, disabled-mode overhead path, Chrome-trace export, trainer
step-phase spans, serving request span trees, and the satellite
instrumentation (print_profiler JSONL fold-in, nan_guard trip
accounting, the run identity stamp)."""

import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import transformer
from paddle_tpu.observability import get_registry, runlog, trace
from paddle_tpu.observability.runlog import RunLog, read_jsonl
from paddle_tpu.serving import ServingEngine


@pytest.fixture
def tracer():
    """A private enabled tracer installed as the global one (trainer /
    serving call sites read the global), restored on exit."""
    t = trace.Tracer(enabled=True, registry=None)
    old = trace.set_tracer(t)
    yield t
    trace.set_tracer(old)


# -- span runtime -----------------------------------------------------------
def test_span_nesting_and_attributes():
    t = trace.Tracer(enabled=True, registry=None)
    with t.span("outer", cat="unit", a=1) as sp:
        sp.set(b="two")
        with t.span("inner", cat="unit"):
            pass
    t.instant("tick", cat="unit", n=3)
    outer = t.events(name="outer")[0]
    inner = t.events(name="inner")[0]
    # nesting is by ts containment within a tid (how Chrome renders it)
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"a": 1, "b": "two"}
    assert outer["cat"] == "unit"
    tick = t.events(name="tick")[0]
    assert tick["ph"] == "i" and tick["args"] == {"n": 3}


def test_disabled_mode_is_shared_null_context():
    t = trace.Tracer(enabled=False, registry=None)
    # near-zero overhead: the SAME reusable null context object, no
    # allocation, no event, no host_timer observation
    assert t.span("a") is t.span("b", cat="x", k=1)
    with t.span("a"):
        pass
    # the live-span API works verbatim when disabled: call sites using
    # `as s: s.set(...)` must not crash under PADDLE_TPU_TRACE=0
    with t.span("a") as s:
        assert s.set(batch=3) is s
    t.instant("i")
    t.add_span("r", 0.0, 1.0)
    assert t.events() == []


def test_env_flag_disables_global_tracer(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TRACE", "0")
    assert trace.Tracer().enabled is False
    monkeypatch.setenv("PADDLE_TPU_TRACE", "1")
    assert trace.Tracer().enabled is True


def test_span_durations_feed_host_timer_namespace():
    reg = get_registry()
    reg.clear(prefix="host_timer.trace_unit")
    t = trace.Tracer(enabled=True)  # default: global registry fold-in
    with t.span("trace_unit_phase"):
        pass
    with t.span("trace_unit_phase"):
        pass
    h = reg.get("host_timer.trace_unit_phase")
    assert h is not None and h.count == 2
    # one aggregation path: print_profiler renders the same histogram
    from paddle_tpu import profiler

    table = profiler.print_profiler()
    assert "trace_unit_phase" in table
    reg.clear(prefix="host_timer.trace_unit")


def test_timer_false_skips_host_timer_fold_in():
    """add_span(timer=False) records the timeline event but NOT the
    host_timer histogram — for lane spans that re-present intervals
    already observed elsewhere (the serving request tree), which would
    otherwise multi-count the same wall seconds in the aggregate."""
    reg = get_registry()
    reg.clear(prefix="host_timer.trace_unit")
    t = trace.Tracer(enabled=True)
    t.add_span("trace_unit_lane", 0.0, 0.5, lane="req 0", timer=False)
    assert len(t.events(name="trace_unit_lane")) == 1
    assert reg.get("host_timer.trace_unit_lane") is None
    reg.clear(prefix="host_timer.trace_unit")


def test_request_lane_spans_not_in_host_timer():
    """The per-request lane tree stays timeline-only: one decode chunk
    is shared by every live request, so folding serving.req.* into
    host_timer would count the same chunk wall time once per request."""
    reg = get_registry()
    reg.clear(prefix="host_timer.serving")
    eng = ServingEngine(_make_params(), 2, 2, 32, max_len=32,
                        max_slots=2, decode_chunk=2, min_bucket=4)
    t2 = trace.Tracer(enabled=True)  # global-registry fold-in
    old = trace.set_tracer(t2)
    try:
        eng.generate_many([np.arange(1, 4, dtype=np.int32)],
                          max_new_tokens=4)
    finally:
        trace.set_tracer(old)
    assert t2.events(name="serving.request")  # the tree was emitted
    assert reg.get("host_timer.serving.request") is None
    assert reg.get("host_timer.serving.req.decode_chunk") is None
    # the driver-thread operational span lands once, under its own name
    # in the engine's registry, and not again as a host_timer duplicate
    assert reg.get("host_timer.serving.decode_chunk") is None
    assert eng.stats()["serving.decode_chunk"]["count"] >= 1
    # nor does any other driver span (admit, prefill, fetch, emit, step):
    # their seconds are in the engine's serving.driver_seconds
    assert reg.snapshot(prefix="host_timer.serving") == {}
    assert sum(v for k, v in eng.stats().items()
               if k.startswith("serving.driver_seconds{")) > 0
    reg.clear(prefix="host_timer.serving")


def test_thread_ident_reuse_gets_fresh_tid():
    """tids are allocated per thread OBJECT, not per get_ident() value:
    CPython reuses idents after a thread exits, which would merge a
    later thread onto a dead thread's lane under its stale name."""
    import threading

    t = trace.Tracer(enabled=True, registry=None)
    tids = []

    def work(name):
        th = threading.Thread(
            target=lambda: t.add_span(name, 0.0, 0.001), name=name)
        th.start()
        th.join()

    work("w0")
    work("w1")  # likely the same ident as the dead w0
    e0 = t.events(name="w0")[0]
    e1 = t.events(name="w1")[0]
    assert e0["tid"] != e1["tid"]
    names = t.to_chrome_trace()["traceEvents"]
    lanes = {e["args"]["name"] for e in names
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"w0", "w1"} <= lanes


def test_event_buffer_bounded_drops_oldest():
    t = trace.Tracer(enabled=True, registry=None, max_events=8)
    for i in range(20):
        t.add_span(f"s{i}", 0.0, 0.001)
    assert len(t.events()) <= 8
    assert t.dropped > 0
    # the most recent event survives (flight recorder keeps the tail)
    assert t.events()[-1]["name"] == "s19"


def test_chrome_trace_export_required_fields(tmp_path):
    t = trace.Tracer(enabled=True, registry=None)
    with t.span("a", cat="unit"):
        pass
    t.add_span("lane", 0.0, 0.002, lane="virtual 0")
    t.instant("mark")
    path = str(tmp_path / "trace.json")
    n = t.save(path)
    assert n == 3
    obj = json.load(open(path))
    assert "traceEvents" in obj
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 2
    for e in xs:
        for k in ("ph", "ts", "dur", "pid", "tid", "name"):
            assert k in e, f"missing {k}: {e}"
    # virtual lane got a thread_name metadata record
    metas = [e for e in obj["traceEvents"] if e["ph"] == "M"]
    assert any(e["name"] == "thread_name"
               and e["args"]["name"] == "virtual 0" for e in metas)


# -- trainer instrumentation ------------------------------------------------
PHASES = ("trainer.reader_wait", "trainer.feed_h2d", "trainer.dispatch",
          "trainer.device_sync", "trainer.opt_boundary")


def _train_lenet(batches=3):
    from paddle_tpu.models import lenet

    model = lenet.build(learning_rate=0.01)
    trainer = pt.trainer.Trainer(model["avg_cost"], model["feed"])
    rng = np.random.default_rng(0)

    def reader():
        for _ in range(batches):
            yield [(rng.normal(size=(1, 28, 28)).astype(np.float32),
                    int(rng.integers(0, 10))) for _ in range(4)]

    trainer.train(reader, num_passes=1)


def test_trainer_step_emits_five_phase_spans(tracer):
    _train_lenet(batches=3)
    steps = tracer.events(name="trainer.step")
    assert len(steps) == 3
    for name in PHASES:
        evs = tracer.events(name=name)
        assert len(evs) == 3, f"{name}: {len(evs)} spans"
    # phases nest inside their step span (reader_wait legitimately sits
    # before the step window)
    for d in tracer.events(name="trainer.dispatch"):
        assert any(s["tid"] == d["tid"] and s["ts"] <= d["ts"]
                   and d["ts"] + d["dur"] <= s["ts"] + s["dur"] + 1e-3
                   for s in steps)
    # step spans carry pass/batch attribution
    assert {s["args"]["batch"] for s in steps} == {0, 1, 2}


def test_trainer_host_timer_aggregates_are_disjoint():
    """The phase timers are the host_timer.* aggregation; trainer.step
    (whose window IS the phases) and the old unfused-path train_batch
    (whose window was exactly feed_h2d+dispatch+device_sync) stay out —
    otherwise print_profiler's %-of-total counts every step's wall
    seconds two or three times over."""
    reg = get_registry()
    t = trace.Tracer(enabled=True)  # default: folds into the registry
    old = trace.set_tracer(t)
    try:
        reg.clear(prefix="host_timer.trainer")
        reg.clear(prefix="host_timer.train_batch")
        _train_lenet(batches=3)
        for name in PHASES:
            h = reg.get("host_timer." + name)
            assert h is not None and h.count == 3, name
        assert reg.get("host_timer.trainer.step") is None
        assert reg.get("host_timer.train_batch") is None
    finally:
        trace.set_tracer(old)
        reg.clear(prefix="host_timer.trainer")


# -- serving request span tree ----------------------------------------------
def _make_params(vocab=50, n_layer=2, n_head=2, d_model=32, max_len=32):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        transformer.build(vocab_size=vocab, n_layer=n_layer,
                          n_head=n_head, d_model=d_model, max_len=max_len,
                          dropout_rate=0.0, dtype="float32")
    exe = pt.Executor()
    exe.run(startup)
    return transformer.extract_params(program=main)


def test_serving_request_span_tree_sums_to_e2e(tracer):
    params = _make_params()
    eng = ServingEngine(params, 2, 2, 32, max_len=32, max_slots=2,
                        decode_chunk=2, min_bucket=4)
    # warm the SAME shapes the traced request will use (a length-5
    # prompt lands in the bucket-8 prefill, not the warmup-3 bucket-4
    # one) with disjoint tokens so the prefix cache cannot shortcut the
    # timed prefill — every AOT compile, including the one
    # ``fn.prepare`` pays between admission and the prefill window, is
    # spent here, outside the traced request
    eng.generate_many([np.arange(10, 15, dtype=np.int32)],
                      max_new_tokens=8)
    tracer.clear()
    req = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=8)
    eng.run_until_idle()
    root = tracer.events(name="serving.request")[0]
    assert root["args"]["rid"] == req.rid
    kids = [e for e in tracer.events(cat="serving")
            if e["name"].startswith("serving.req.")
            and e["tid"] == root["tid"]]
    names = {e["name"] for e in kids}
    assert names >= {"serving.req.queue", "serving.req.prefill",
                     "serving.req.decode_chunk", "serving.req.evict"}
    # children nest within the root; the tree is built from the request
    # handle's own timestamps, so containment is exact — only the wall
    # seconds BETWEEN spans (host scheduling, compile walls) vary by
    # host, and no assertion here depends on them
    for e in kids:
        assert e["ts"] >= root["ts"] - 1e-3
        assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1e-3
    # the phases tile the request in order: queue ends before prefill
    # starts, decode chunks follow prefill sorted and non-overlapping,
    # and the zero-duration evict marker closes the root window
    queue = next(e for e in kids if e["name"] == "serving.req.queue")
    prefill = next(e for e in kids if e["name"] == "serving.req.prefill")
    chunks = sorted((e for e in kids
                     if e["name"] == "serving.req.decode_chunk"),
                    key=lambda e: e["ts"])
    evict = next(e for e in kids if e["name"] == "serving.req.evict")
    assert queue["ts"] + queue["dur"] <= prefill["ts"] + 1e-3
    assert chunks and prefill["ts"] + prefill["dur"] <= chunks[0]["ts"] + 1e-3
    for a, b in zip(chunks, chunks[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-3
    # 7 post-prefill tokens at decode_chunk=2 -> 4 chunks
    assert len(chunks) == 4
    assert evict["dur"] == 0
    assert evict["ts"] == pytest.approx(root["ts"] + root["dur"], abs=1.0)
    # disjoint children can never exceed the root window they tile
    cover = sum(e["dur"] for e in kids)
    assert cover <= 1.001 * root["dur"]
    # root duration IS the request e2e (microseconds vs seconds) — two
    # views of the same submit->finish timestamps
    assert root["dur"] == pytest.approx(req.e2e * 1e6, rel=0.05)


def test_request_lanes_never_shared_by_overlapping_requests():
    """Chrome/Perfetto derive nesting purely from ts/dur containment
    within a tid, so two requests whose windows overlap must NEVER land
    on one lane (they would render as one false tree); a lane is reused
    only once its previous occupant finished before the next submit."""
    import types

    class R:
        def __init__(self, submit_t, finish_t):
            self.submit_t, self.finish_t = submit_t, finish_t

    eng = types.SimpleNamespace(_req_lane_ends=[])
    lane = ServingEngine._req_lane
    # finish order: B [1,2] emits before the long-lived A [0,10]
    assert lane(eng, R(1.0, 2.0)) == 0
    assert lane(eng, R(0.0, 10.0)) == 1   # overlaps B -> own lane
    assert lane(eng, R(3.0, 4.0)) == 0    # lane 0 free again -> reused
    assert lane(eng, R(5.0, 11.0)) == 0   # still free after reuse
    assert lane(eng, R(6.0, 7.0)) == 2    # 0 and 1 both busy -> new


def test_serving_ttft_decomposition(tracer):
    params = _make_params()
    eng = ServingEngine(params, 2, 2, 32, max_len=32, max_slots=2,
                        decode_chunk=2, min_bucket=4)
    # warm the bucket-8 prefill the length-5 prompt below will use
    # (disjoint tokens: a prefix hit would change the timed suffix) so
    # the ``fn.prepare`` compile wall — which lands between admission
    # and the prefill window, i.e. inside TTFT but outside both
    # decomposition terms — is paid here
    eng.generate_many([np.arange(10, 15, dtype=np.int32)],
                      max_new_tokens=4)
    reg = get_registry()
    for nm in ("serving.ttft_seconds", "serving.queue_wait"):
        reg.get(nm).reset()
    req = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    eng.run_until_idle()
    st = eng.stats()
    assert st["serving.queue_wait"]["count"] == 1
    assert st["serving.decode_chunk"]["count"] >= 1
    queue = st["serving.queue_wait"]["mean"]
    prefill = req.prefill_t1 - req.prefill_t0
    ttft = st["serving.ttft_seconds"]["mean"]
    # the histogram and the request handle observe the SAME
    # submit -> first-token window: identical up to float noise
    assert ttft == pytest.approx(req.ttft, rel=1e-6)
    # the decomposition: queue wait and prefill are disjoint
    # sub-windows of TTFT measured from the same clock, so their sum
    # can never exceed it; the residual (admission bookkeeping between
    # admit_t and prefill_t0) is host wall the engine deliberately
    # keeps OUT of both terms — bounding it would re-introduce the
    # compile/scheduler wall sensitivity this test had at seed
    assert queue >= 0 and prefill > 0
    assert queue + prefill <= ttft + 1e-6
    assert ttft <= req.e2e + 1e-6


# -- run identity -----------------------------------------------------------
def test_run_stamp_fields():
    s = runlog.run_stamp()
    assert s["schema_version"] == runlog.SCHEMA_VERSION == 1
    assert len(s["run_id"]) == 12
    # inside this checkout the sha resolves; elsewhere it may be None
    assert s["git_sha"] is None or len(s["git_sha"]) == 12
    assert s["run_id"] != runlog.run_stamp()["run_id"]
    assert pt.observability.run_stamp is runlog.run_stamp


# -- satellites -------------------------------------------------------------
def test_print_profiler_log_emits_profiler_event(tmp_path):
    from paddle_tpu import profiler

    profiler.reset_profiler()
    with profiler.timer("logged_phase"):
        pass
    p = str(tmp_path / "run.jsonl")
    with RunLog(p) as log:
        profiler.print_profiler(log=log)
    recs = read_jsonl(p, event="profiler")
    assert len(recs) == 1
    timers = {t["event"]: t for t in recs[0]["timers"]}
    assert timers["logged_phase"]["calls"] == 1
    assert timers["logged_phase"]["total"] >= 0
    assert "pct" in timers["logged_phase"]
    profiler.reset_profiler()


def test_nan_guard_trip_records_counter_and_instant(tracer):
    import jax.numpy as jnp

    from paddle_tpu import profiler

    reg = get_registry()
    c0 = reg.value("executor.nan_trips")
    with pytest.raises(FloatingPointError):
        with profiler.nan_guard():
            np.asarray(jnp.log(jnp.zeros(()) - 1.0))
    assert reg.value("executor.nan_trips") == c0 + 1
    trips = tracer.events(name="nan_guard_trip")
    assert len(trips) == 1 and trips[0]["ph"] == "i"


def test_executor_check_nan_inf_records_trip(tracer):
    from paddle_tpu import layers
    from paddle_tpu.flags import FLAGS

    reg = get_registry()
    c0 = reg.value("executor.nan_trips")
    x = layers.data("x", shape=[4])
    y = layers.log(x) if hasattr(layers, "log") else layers.sqrt(x)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    FLAGS.check_nan_inf = True
    try:
        with pytest.raises(FloatingPointError):
            exe.run(feed={"x": -np.ones((2, 4), np.float32)},
                    fetch_list=[y])
    finally:
        FLAGS.check_nan_inf = False
    assert reg.value("executor.nan_trips") == c0 + 1
    assert tracer.events(name="nan_guard_trip")
