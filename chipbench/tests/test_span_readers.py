"""The six readers of the program's own spans and counters (PR 25) on
hand-made facts, and ``device.idle_host_held_share.serve`` on a small
trace recorded on a TPU v5e with the driver-loop spans in it
(``testdata/serve_spans_v5e.xplane.pb``: a 1-layer engine, 2 slots, three
requests in 0.12 s with idle time either side, the Python tracer off)."""

import os
import types

import pytest

from chipbench import run as bench_run
from chipbench import trace_reduce

SPANS = os.path.join(bench_run.HERE, "testdata", "serve_spans_v5e.xplane.pb")


def _reader(name):
    return bench_run.load_reader(name)


def _event(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=[])


def _profile(planes):
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=pname, lines=[
            types.SimpleNamespace(name=lname, events=events)
            for lname, events in lines.items()])
        for pname, lines in planes.items()])


def test_decode_stall_share_is_stalled_over_live():
    read = _reader("sched.decode_stall_share").read
    assert read({"stats": {"serving.stalled_seconds": 3.0,
                           "serving.live_seconds": 4.0}}) == 75.0
    assert read({"stats": {"serving.stalled_seconds": 0.0,
                           "serving.live_seconds": 2.0}}) == 0.0
    # a program without the counters, or a window with no decoding
    assert read({"stats": {}}) is None
    assert read({"stats": {"serving.live_seconds": 0.0}}) is None


def test_driver_prefill_share_counts_the_prefills_own_fetch():
    reader = _reader("sched.driver_prefill_share")
    stats = {"serving.driver_seconds{phase=idle}": 3.75,
             "serving.driver_seconds{phase=loop}": 0.25,
             "serving.driver_seconds{phase=admit}": 0.5,
             "serving.driver_seconds{phase=prefill}": 0.25,
             "serving.driver_seconds{of=prefill,phase=fetch}": 2.25,
             "serving.driver_seconds{phase=decode}": 0.5,
             "serving.driver_seconds{of=decode,phase=fetch}": 2.0,
             "serving.driver_seconds{phase=emit}": 0.5,
             "serving.prefill_seconds": {"count": 3, "sum": 2.5}}
    assert reader.phases(stats)["of=prefill,phase=fetch"] == 2.25
    assert reader.read({"stats": stats}) == 25.0
    # the counters run on through the drain (and a traced run's
    # stop_trace): the time after the window is not the window's
    assert reader.read({"stats": stats, "drain_s": 2.0}) == pytest.approx(
        100 * 2.5 / 8.0)
    assert reader.read({"stats": {}}) is None
    assert reader.read({"stats": {}, "drain_s": 2.0}) is None


def test_idle_host_held_share_by_hand():
    """Three operations with two gaps: the first gap lies under a working
    driver span (half of it under its nested fetch as well: counted
    once), the second under ``serving.idle``; another thread's event of
    the same span does not count."""
    reader = _reader("device.idle_host_held_share.serve")
    ops = [_event("%fusion.1 = f32[8] fusion(f32[8] %a)", 0, 1000),
           _event("%fusion.1 = f32[8] fusion(f32[8] %a)", 3000, 1000),
           _event("%fusion.1 = f32[8] fusion(f32[8] %a)", 9000, 1000)]
    driver = [_event("serving.step", 0, 2900),
              _event("serving.decode_chunk", 100, 2400),
              _event("serving.fetch", 1500, 1000),
              _event("serving.emit", 2500, 300),
              _event("serving.idle", 4000, 5000)]
    other = [_event("$serve.py:118 sleep_until", 0, 10000)]
    profile = _profile({
        "/device:TPU:0": {"XLA Ops": ops},
        "/host:CPU": {"python/1": driver, "python/2": other}})
    work, idle = reader.driver_spans(profile)
    assert work == [[0, 2900]] and idle == [[4000, 9000]]
    # gaps [1000, 3000] and [4000, 9000]; [1000, 2900] is under work
    assert reader.held_seconds(profile) == pytest.approx(1900e-9)
    assert reader.overlap_ns([(1000, 3000), (4000, 9000)], idle) == 5000
    # no driver span in the trace (the parent commit): nothing to read
    bare = _profile({"/device:TPU:0": {"XLA Ops": ops},
                     "/host:CPU": {"python/2": other}})
    assert reader.held_seconds(bare) is None
    assert reader.read({"trace": None}) is None
    assert reader.read({"trace": {"busy_s": 1.0}}) is None  # no trace_path


def test_idle_host_held_share_on_the_recorded_trace():
    reader = _reader("device.idle_host_held_share.serve")
    profile = trace_reduce.load(SPANS)
    summary = trace_reduce.reduce(profile)
    work, idle = reader.driver_spans(profile)
    # the driver thread's spans are in the device trace's file, and the
    # requests' prefills among them
    names = {e.name for plane in profile.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert set(reader.AT_WORK) | {reader.IDLE} <= names
    assert work and idle
    held = reader.held_seconds(profile)
    idle_s = summary["span_s"] - summary["busy_s"]
    assert 0 < held < idle_s
    # held and not held partition the idle time: what is not under a
    # working span is under serving.idle or between two spans
    chips = trace_reduce.chip_ops(profile)
    merged = trace_reduce.busy_union(list(chips.values())[0])
    gaps = [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]
    under_idle = reader.overlap_ns(gaps, idle) * 1e-9
    assert held + under_idle <= idle_s * (1 + 1e-9)
    assert held + under_idle >= 0.9 * idle_s
    facts = {"trace": summary, "trace_path": SPANS,
             "trace_window_s": summary["span_s"]}
    assert reader.read(facts) == pytest.approx(
        100.0 * held / summary["span_s"])
    # every Mosaic call of the serving path carries its kernel's name
    mosaic = [rec["provenance"] for rec in summary["ops"].values()
              if "tpu_custom_call" in rec["provenance"]]
    assert mosaic and all("paged_attention" in p.split(" = ")[0]
                          for p in mosaic)


def test_ce_head_busy_share_finds_the_named_calls():
    reader = _reader("ce_head.busy_share")
    call = ('custom-call(bf16[8,4] %x), custom_call_target='
            '"tpu_custom_call"')
    ops = {
        "%jvp_fused_ce_fwd_.13 custom-call (f32[8,1], f32[8,1])": {
            "calls": 4, "seconds": 0.02, "self": 0.02,
            "provenance": "%jvp_fused_ce_fwd_.13 = (f32[8,1]) " + call},
        "%transpose_jvp_fused_ce_dw__.27 custom-call bf16[4,16]": {
            "calls": 4, "seconds": 0.06, "self": 0.06,
            "provenance": "%transpose_jvp_fused_ce_dw__.27 = " + call},
        "%flash_fwd.3 custom-call (bf16[8,4])": {
            "calls": 4, "seconds": 0.1, "self": 0.1,
            "provenance": "%flash_fwd.3 = (bf16[8,4]) " + call},
        # reads a kernel's result: the name is among its operands only
        "%fusion.9 fusion f32[8]": {
            "calls": 4, "seconds": 0.03, "self": 0.03,
            "provenance": "%fusion.9 = f32[8] fusion(f32[8,1] "
                          "%jvp_fused_ce_fwd_.13), kind=kLoop"},
        "%while.1 while ()": {
            "calls": 1, "seconds": 0.5, "self": 0.19,
            "provenance": "%while.1 = () while()"}}
    facts = {"trace": {"busy_s": 0.4, "ops": ops}}
    assert reader.read(facts) == pytest.approx(20.0)
    assert reader.kernels({}, {}) == {"fused_ce": ("fused_ce_", reader.CALL)}
    # the parent's trace names them %transpose_jvp___.27: nothing to read
    unnamed = {k.replace("fused_ce_", ""): dict(
        v, provenance=v["provenance"].replace("fused_ce_", ""))
        for k, v in ops.items()}
    assert reader.read({"trace": {"busy_s": 0.4, "ops": unnamed}}) is None
    assert reader.read({"trace": None}) is None


def test_registry_readers_read_the_process_registry():
    from paddle_tpu.observability import get_registry

    reg = get_registry()
    run_ms = _reader("executor.run_host_ms")
    misses = _reader("compile.cache_misses")
    hist = reg.get("executor.run_seconds")
    if hist is not None:
        hist.reset()
        assert run_ms.read({}) is None  # nothing observed yet
    hist = reg.histogram("executor.run_seconds")
    for seconds in (0.004, 0.006, 0.005, 0.9, 0.005):  # one warm-up call
        hist.observe(seconds)
    assert run_ms.read({}) == pytest.approx(5.0)
    hist.reset()
    # the listeners are registered at import, so the counter is there
    # and reads 0 (not None) where nothing compiled
    counter = reg.get("compile.cache_misses")
    assert counter is not None
    before = misses.read({})
    assert before == counter.value
    counter.inc(2)
    assert misses.read({}) == before + 2
