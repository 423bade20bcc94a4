"""One span, one clock (observability/trace.py): a live span is also a
``jax.profiler.TraceAnnotation`` on the thread that did the work, its one
clock pair feeds the event buffer, a histogram and a self-seconds
counter, and the serving driver loop, ``Executor.run`` and the compile
cache report where their own time goes through it."""

import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import transformer
from paddle_tpu.observability import get_registry, trace
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.serving import ServingEngine

DRIVER_SPANS = {"serving.idle", "serving.step", "serving.admit",
                "serving.prefill", "serving.dispatch",
                "serving.decode_chunk", "serving.fetch", "serving.emit"}


@pytest.fixture(scope="module")
def params():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        transformer.build(vocab_size=50, n_layer=2, n_head=2, d_model=32,
                          max_len=64, dropout_rate=0.0, dtype="float32")
    exe = pt.Executor()
    exe.run(startup)
    return transformer.extract_params(program=main)


def _engine(params, **kw):
    reg = MetricsRegistry()
    eng = ServingEngine(params, 2, 2, 32, max_len=64, max_slots=3,
                        decode_chunk=4, min_bucket=8, registry=reg, **kw)
    return eng, reg


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 50, n, dtype=np.int32)


def _phases(stats):
    return {k: v for k, v in stats.items()
            if k.startswith("serving.driver_seconds{")}


def _profile(directory):
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(directory) + "/**/*.xplane.pb", recursive=True)
    return ProfileData.from_file(path)


def _host_lines(profile):
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                yield line


# -- the span primitive ------------------------------------------------------
def test_span_feeds_every_consumer_from_one_clock_pair():
    reg = MetricsRegistry()
    t = trace.Tracer(enabled=True, registry=None)
    with t.span("unit.outer", registry=reg, histogram="unit.outer_seconds",
                counter=("unit.self_seconds", {"phase": "outer"}),
                rid=7) as outer:
        time.sleep(0.01)
        with t.span("unit.inner", registry=reg,
                    counter=("unit.self_seconds", {"phase": "inner"})) as inner:
            time.sleep(0.02)
    (ev,) = t.events(name="unit.outer")
    assert ev["args"] == {"rid": 7}
    # the event, the histogram and the caller read the same pair
    assert ev["dur"] == pytest.approx(outer.seconds * 1e6)
    hist = reg.get("unit.outer_seconds")
    assert hist.count == 1 and hist.total == outer.seconds
    # a histogram of its own replaces the host_timer fold-in
    assert reg.get("host_timer.unit.outer") is None
    # self seconds: the parent's duration less its child's, to the float
    assert reg.value("unit.self_seconds", phase="inner") == inner.seconds
    assert reg.value("unit.self_seconds", phase="outer") == pytest.approx(
        outer.seconds - inner.seconds, abs=1e-9)
    assert (reg.value("unit.self_seconds", phase="inner")
            + reg.value("unit.self_seconds", phase="outer")
            == pytest.approx(outer.seconds, abs=1e-9))


def test_a_span_with_its_own_aggregate_has_no_host_timer_duplicate():
    reg = MetricsRegistry()
    t = trace.Tracer(enabled=True, registry=reg)  # host_timer fold-in on
    with t.span("unit.plain"):
        pass
    with t.span("unit.counted", counter=("unit.seconds", {"phase": "p"})):
        pass
    with t.span("unit.observed", histogram="unit.observed_seconds"):
        pass
    assert {e["name"] for e in t.events()} == {
        "unit.plain", "unit.counted", "unit.observed"}
    assert sorted(reg.snapshot(prefix="host_timer.")) == [
        "host_timer.unit.plain"]


def test_event_false_keeps_a_span_out_of_the_buffer_only(tmp_path):
    reg = MetricsRegistry()
    t = trace.Tracer(enabled=True, registry=reg, max_events=8)
    with t.span("unit.burst", timer=False):
        pass
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(50):  # would wrap the 8-event buffer
            with t.span("unit.wait", counter=("unit.seconds",
                                              {"phase": "wait"}),
                        event=False) as sp:
                time.sleep(0.0005)
    finally:
        jax.profiler.stop_trace()
    assert [e["name"] for e in t.events()] == ["unit.burst"]
    assert t.dropped == 0
    assert reg.value("unit.seconds", phase="wait") >= 50 * 0.0005
    assert sp.seconds > 0
    waits = [e for line in _host_lines(_profile(tmp_path))
             for e in line.events if e.name == "unit.wait"]
    assert len(waits) == 50  # still annotations in a profiler session
    # with nothing to feed, it is the shared null context
    assert t.span("unit.nothing", event=False) is t.span("unit.x", event=False)


def test_counters_do_not_depend_on_the_event_buffer():
    reg = MetricsRegistry()
    t = trace.Tracer(enabled=False, registry=None)
    with t.span("unit.phase", registry=reg, histogram="unit.seconds",
                counter=("unit.self_seconds", {"phase": "p"})) as sp:
        time.sleep(0.002)
    assert t.events() == []  # PADDLE_TPU_TRACE=0: no event buffer
    assert reg.get("unit.seconds").count == 1
    assert reg.value("unit.self_seconds", phase="p") == sp.seconds > 0
    # and a span that feeds nothing else stays the shared null context
    assert t.span("unit.plain") is t.span("unit.other", k=1)


def test_span_is_a_profiler_annotation_on_its_own_thread(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    t = trace.Tracer(enabled=False, registry=MetricsRegistry())

    def work():
        with t.span("unit.worker", histogram="unit.worker_seconds",
                    rid=3, bucket=8) as sp:
            sp.set(cache_hit=True)
            time.sleep(0.005)

    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with t.span("unit.main", histogram="unit.main_seconds"):
            th = threading.Thread(target=work)
            th.start()
            th.join()
    finally:
        jax.profiler.stop_trace()
    found = {}
    for line in _host_lines(_profile(tmp_path)):
        for e in line.events:
            if e.name.startswith("unit."):
                found[e.name] = (id(line), dict(e.stats), e.duration_ns)
    assert set(found) == {"unit.worker", "unit.main"}
    assert found["unit.worker"][0] != found["unit.main"][0]
    stats = found["unit.worker"][1]
    assert (stats["rid"], stats["bucket"]) == (3, 8)
    assert str(stats["cache_hit"]) in ("True", "1")
    assert found["unit.worker"][2] >= 5e6  # ns: it held the sleep


# -- the serving driver loop ---------------------------------------------------
def test_driver_spans_are_annotations_on_the_driver_thread(params, tmp_path):
    eng, _ = _engine(params)
    eng.generate_many([_prompt(0, 5)], max_new_tokens=6)  # compile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    eng.start()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        time.sleep(0.06)  # the driver idles: a whole ~20 ms idle span
        req = eng.submit(_prompt(1, 5), max_new_tokens=6)
        req.result(timeout=120)
        time.sleep(0.06)
    finally:
        jax.profiler.stop_trace()
        eng.stop()
    lines = [ln for ln in _host_lines(_profile(tmp_path))
             if any(e.name in DRIVER_SPANS for e in ln.events)]
    assert len(lines) == 1  # every driver span on the one driver thread
    events = [e for e in lines[0].events if e.name in DRIVER_SPANS]
    assert {e.name for e in events} == DRIVER_SPANS
    (prefill,) = [e for e in events if e.name == "serving.prefill"]
    attrs = dict(prefill.stats)
    assert attrs["rid"] == req.rid and attrs["bucket"] == 8
    assert attrs["prefix_hit"] == 0 and "slot" in attrs
    chunk = next(e for e in events if e.name == "serving.decode_chunk")
    assert dict(chunk.stats)["steps"] == 4
    assert dict(chunk.stats)["active"] == 1
    # a fetch lies inside the prefill, and one inside each chunk
    def inside(a, b):
        return (b.start_ns <= a.start_ns and a.start_ns + a.duration_ns
                <= b.start_ns + b.duration_ns)
    fetches = [e for e in events if e.name == "serving.fetch"]
    assert any(inside(f, prefill) for f in fetches)
    assert any(inside(f, chunk) for f in fetches)


def test_driver_phases_sum_to_the_driver_threads_wall(params):
    eng, reg = _engine(params)
    eng.generate_many([_prompt(0, 5), _prompt(1, 12)], max_new_tokens=8)
    eng.start()
    try:
        time.sleep(0.05)
        reg.reset(prefix="serving.")
        t0 = time.perf_counter()
        handles = []
        for i in range(6):
            handles.append(eng.submit(_prompt(10 + i, 5 + i),
                                      max_new_tokens=12))
            time.sleep(0.3)
        for h in handles:
            h.result(timeout=120)
        time.sleep(0.3)
    finally:
        eng.stop()  # joins the driver: its last span is closed
    wall = time.perf_counter() - t0
    stats = eng.stats()
    phases = _phases(stats)
    assert set(phases) == {
        "serving.driver_seconds{phase=idle}",
        "serving.driver_seconds{phase=loop}",
        "serving.driver_seconds{phase=admit}",
        "serving.driver_seconds{phase=prefill}",
        "serving.driver_seconds{phase=decode}",
        "serving.driver_seconds{of=prefill,phase=fetch}",
        "serving.driver_seconds{of=decode,phase=fetch}",
        "serving.driver_seconds{phase=emit}"}
    assert all(v > 0 for v in phases.values())
    # the phases lie inside the driver thread's life and do not overlap:
    # they cannot sum to more than the window around it (plus the one
    # span open at the reset, which began under 0.05 s before it); how
    # much of the window a loaded host gives the thread is not asserted
    assert sum(phases.values()) <= wall + 0.06
    assert 0 < stats["serving.stalled_seconds"] <= stats["serving.live_seconds"]
    # the chunk histogram is the collect spans' own durations: their
    # fetches' and their self seconds, which phase=decode holds beside the
    # dispatch spans'
    chunks = stats["serving.decode_chunk"]
    fetched = phases["serving.driver_seconds{of=decode,phase=fetch}"]
    assert fetched < chunks["sum"] < (
        fetched + phases["serving.driver_seconds{phase=decode}"])
    # what lies between the phase spans is small, and counted
    assert phases["serving.driver_seconds{phase=loop}"] < 0.1 * wall
    assert stats["serving.prefill_seconds"]["count"] == 6


def test_an_idle_engine_leaves_the_event_buffer_alone(params):
    eng, reg = _engine(params)
    folded = MetricsRegistry()
    t = trace.Tracer(enabled=True, registry=folded)
    old = trace.set_tracer(t)
    try:
        eng.generate_many([_prompt(0, 5)], max_new_tokens=6)
        burst = len(t.events())
        assert burst and t.events(name="serving.step")
        eng.start()
        time.sleep(0.15)  # several ~20 ms idle spans
        eng.stop()
    finally:
        trace.set_tracer(old)
    assert len(t.events()) == burst and t.dropped == 0
    assert t.events(name="serving.idle") == []
    assert reg.value("serving.driver_seconds", phase="idle") >= 0.1
    # and no span of the engine's is observed a second time as host_timer
    assert folded.snapshot(prefix="host_timer.") == {}


def test_a_speculative_round_says_what_it_committed(params):
    from paddle_tpu.serving import speculative as spec

    eng, _ = _engine(params, draft_params=spec.depth_draft(params, 1),
                     spec_k=3)
    t = trace.Tracer(enabled=True, registry=None)
    old = trace.set_tracer(t)
    try:
        eng.generate_many([_prompt(0, 5), _prompt(1, 7)], max_new_tokens=10)
    finally:
        trace.set_tracer(old)
    rounds = sorted(t.events(name="serving.spec_round"),
                    key=lambda e: e["ts"])
    emits = sorted(t.events(name="serving.emit"), key=lambda e: e["ts"])
    assert rounds and len(emits) == len(rounds)
    assert all(r["args"]["k"] == 3 and r["args"]["active"] >= 1
               for r in rounds)
    # each round's emit span carries what the round committed
    assert all(0 <= e["args"]["accepted"] < e["args"]["emitted"]
               for e in emits)
    # the first token of each request came from its prefill
    assert sum(e["args"]["emitted"] for e in emits) == 2 * (10 - 1)
    assert sum(e["args"]["accepted"] for e in emits) == eng._spec.accepted


class _Late:
    """A first token that takes its time to reach the host: what a slow
    prefill on the device looks like to the driver, which dispatches the
    pieces at once and waits in the fetch."""

    def __init__(self, value, seconds):
        self.value, self.seconds = value, seconds

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.seconds)
        return np.asarray(self.value)


def test_a_prefill_between_two_chunks_is_stalled_time(params):
    eng, reg = _engine(params)
    eng.generate_many([_prompt(0, 5), _prompt(1, 12)], max_new_tokens=8)
    t = trace.Tracer(enabled=True, registry=None)  # keeps Request.chunks
    old = trace.set_tracer(t)
    try:
        first = eng.submit(_prompt(2, 5), max_new_tokens=24)
        eng.step()  # admits `first`, sends two chunks, reads the first
        assert len(eng._chunks) == 1 and len(first.chunks) == 1
        before = eng.stats()
        bucket = eng.bucket_for(12)
        fast = eng._prefill_fn(bucket)

        def slow(*args):
            out = fast(*args)
            return out[:4] + (_Late(out[4], 0.25),) + out[5:]

        slow.prepare = fast.prepare
        eng._prefill_fns[bucket] = slow
        second = eng.submit(_prompt(3, 12), max_new_tokens=8)
        # the pieces go behind the chunk in flight, which is read while
        # they run; then the slow first token; then a chunk for both
        eng.step()
        after = eng.stats()
    finally:
        trace.set_tracer(old)
    prefill_wall = second.prefill_t1 - second.prefill_t0
    assert prefill_wall >= 0.25
    # the prefill's clock pair starts where the chunk in flight ended
    assert second.prefill_t0 == first.chunks[1][1]
    stalled = (after["serving.stalled_seconds"]
               - before["serving.stalled_seconds"])
    live = after["serving.live_seconds"] - before["serving.live_seconds"]
    # `first` waited through the whole prefill (and the bookkeeping around
    # it); `second` went from its first token straight into the chunk
    assert prefill_wall <= stalled <= prefill_wall + 0.1
    # back to back (the chunk in flight): nothing stalled, the pair starts
    # at the previous collect; across the admission: at the dispatch
    assert first.chunks[1][0] == first.chunks[0][1]
    assert first.chunks[2][0] >= second.first_token_t
    assert first.chunks[2] == second.chunks[0]
    pairs = first.chunks[1:] + second.chunks
    assert live == pytest.approx(
        stalled + sum(t1 - t0 for t0, t1 in pairs), rel=1e-6)
    eng.run_until_idle()
    assert first.done and second.done


# -- Executor.run ----------------------------------------------------------------
def test_executor_run_emits_three_child_spans_and_run_seconds():
    reg = get_registry()
    reg.clear(prefix="host_timer.executor")
    t = trace.Tracer(enabled=True)  # global-registry fold-in
    old = trace.set_tracer(t)
    try:
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = pt.layers.data(name="x", shape=[4], dtype="float32")
            y = pt.layers.fc(input=x, size=3)
        exe = pt.Executor()
        scope = pt.Scope()
        exe.run(startup, scope=scope)
        t.clear()
        hist = reg.histogram("executor.run_seconds")
        n0, total0 = hist.count, hist.total
        feed = {"x": np.ones((2, 4), np.float32)}
        for _ in range(2):
            exe.run(main, feed=feed, fetch_list=[y], scope=scope)
    finally:
        trace.set_tracer(old)
    runs = t.events(name="executor.run")
    assert len(runs) == 2 and hist.count == n0 + 2
    assert hist.total - total0 == pytest.approx(
        sum(r["dur"] for r in runs) * 1e-6)
    for run in runs:
        kids = [e for e in t.events(cat="executor")
                if e["name"] != "executor.run" and e["tid"] == run["tid"]
                and run["ts"] <= e["ts"]
                and e["ts"] + e["dur"] <= run["ts"] + run["dur"] + 1e-3]
        kids.sort(key=lambda e: e["ts"])
        assert [k["name"] for k in kids] == [
            "executor.prepare", "executor.dispatch", "executor.finish"]
        # the three parts lie inside the run (the filter above) and do
        # not overlap; how tightly they tile it is the host's business
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-3
        assert sum(k["dur"] for k in kids) <= run["dur"] + 3e-3
    dispatches = sorted(t.events(name="executor.dispatch"),
                        key=lambda e: e["ts"])
    assert [d["args"]["cache_hit"] for d in dispatches] == [False, True]
    # the parts are timeline-only: executor.run_seconds is the aggregate
    assert reg.snapshot(prefix="host_timer.executor") == {}


def test_jitted_step_has_one_name_for_every_program_version():
    names = set()
    for width in (3, 5):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = pt.layers.data(name="x", shape=[4], dtype="float32")
            y = pt.layers.fc(input=x, size=width)
        exe = pt.Executor()
        scope = pt.Scope()
        exe.run(startup, scope=scope)
        (program, scope, feed_names, fetch_names, feed_vals, state_names,
         state, _) = exe._prepare(main, {"x": np.ones((2, 4), np.float32)},
                                  [y], scope)
        jitted = exe._compile(program, feed_names, fetch_names, state_names)
        text = jitted.lower(state, *feed_vals).as_text()
        names.add(text.split("module @")[1].split()[0])
    assert names == {"jit_step"}


# -- the compile cache's listeners ---------------------------------------------
def test_compile_listeners_count_a_miss_then_a_hit(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    reg = get_registry()
    # importing the package registered the listeners, and the counters
    # exist before anything compiled
    for name in ("compile.cache_hits", "compile.cache_misses",
                 "compile.trace_seconds", "compile.lower_seconds",
                 "compile.backend_seconds", "compile.cache_load_seconds"):
        assert reg.get(name, kind="counter") is not None, name
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}

    def make():
        # a new function object each time (the same HLO): jit's own
        # in-memory cache misses, the persistent one is asked
        def fn(x):
            return jnp.tanh(x @ x) + 25.0

        return jax.jit(fn)

    x = jnp.ones((48, 48), jnp.float32)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        snaps = [reg.snapshot(prefix="compile.")]
        make().lower(x).compile()
        snaps.append(reg.snapshot(prefix="compile."))
        make().lower(x).compile()  # traced and lowered again, then loaded
        snaps.append(reg.snapshot(prefix="compile."))
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()

    def delta(name, i):
        return snaps[i + 1][name] - snaps[i][name]

    assert (delta("compile.cache_misses", 0),
            delta("compile.cache_hits", 0)) == (1, 0)
    assert (delta("compile.cache_misses", 1),
            delta("compile.cache_hits", 1)) == (0, 1)
    assert delta("compile.cache_load_seconds", 0) == 0
    assert delta("compile.cache_load_seconds", 1) > 0
    for i in (0, 1):
        for name in ("compile.trace_seconds", "compile.lower_seconds",
                     "compile.backend_seconds"):
            assert delta(name, i) > 0, (name, i)
