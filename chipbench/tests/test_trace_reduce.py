"""The reducer on a small trace recorded on a TPU v5e (three calls of a
jitted matmul + tanh with 20 ms sleeps between them) and on hand-made
events: busy union, idle share, per-name sums, self time, named gaps."""

import os
import types

import pytest

from chipbench import run as bench_run
from chipbench import trace_reduce

TINY = os.path.join(bench_run.HERE, "testdata", "tiny_v5e.xplane.pb")


def _event(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _profile(planes):
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=pname, lines=[
            types.SimpleNamespace(name=lname, events=events)
            for lname, events in lines.items()])
        for pname, lines in planes.items()])


@pytest.fixture(scope="module")
def tiny():
    return trace_reduce.load(TINY)


def test_recorded_trace_busy_union_and_sums(tiny):
    summary = trace_reduce.reduce(tiny)
    chips = trace_reduce.chip_ops(tiny)
    assert list(chips) == ["/device:TPU:0"] and summary["chips"] == 1
    events = chips["/device:TPU:0"]
    # the union another way: no two of these events overlap in part, so
    # it is the sum over the events that no other event contains
    outer = [(s, e) for s, e, *_ in events
             if not any((s2 <= s and e <= e2) and (s2, e2) != (s, e)
                        for s2, e2, *_ in events)]
    assert summary["busy_s"] == pytest.approx(
        sum(e - s for s, e in outer) * 1e-9)
    assert summary["busy_s"] == pytest.approx(1.0393e-05, rel=1e-3)
    first, last = events[0][0], max(e[1] for e in events)
    assert summary["span_s"] == pytest.approx((last - first) * 1e-9)
    fusion = summary["ops"]["%fusion fusion bf16[512,512]"]
    assert fusion["calls"] == 3
    assert fusion["seconds"] == pytest.approx(1.0344e-05, rel=1e-3)
    assert sum(v["self"] for v in summary["ops"].values()) == (
        pytest.approx(summary["busy_s"]))
    assert summary["device_ops"][0][0] == "%fusion fusion bf16[512,512]"
    # idle share over the span: the chip slept with the host
    assert 1 - summary["busy_s"] / summary["span_s"] > 0.999
    assert summary["idle_gaps"][0][0] == "host:$time sleep"
    assert summary["idle_gaps"][0][1] == pytest.approx(
        summary["span_s"] - summary["busy_s"], rel=1e-3)


def test_matching_by_name_and_provenance(tiny):
    summary = trace_reduce.reduce(tiny)
    calls, seconds = trace_reduce.matching(summary, "fusion", "kOutput")
    assert calls == 3 and seconds == pytest.approx(1.0344e-05, rel=1e-3)
    assert trace_reduce.matching(summary, "no-such-kernel") == (0.0, 0.0)


def test_nested_and_overlapping_events_by_hand():
    ops = [_event("%while.1 = s32[] while(s32[] %x)", 0, 1000),
           _event("%fusion.2 = f32[8] fusion(f32[8] %a)", 100, 300),
           _event('%custom-call.3 = f32[8] custom-call(f32[8] %b), '
                  'custom_call_target="flash_fwd"', 400, 500),
           _event("%fusion.2 = f32[8] fusion(f32[8] %a)", 2000, 250)]
    host = [_event("$engine.py:1 _admit", 1100, 800),
            _event("thread", 0, 5000)]
    profile = _profile({
        "/device:TPU:0": {"XLA Ops": ops, "XLA Modules": [
            _event("jit_step", 0, 2250)]},
        "/device:TPU:1": {"XLA Ops": [_event(
            "%fusion.2 = f32[8] fusion(f32[8] %a)", 0, 500)]},
        "/device:TPU:0 SparseCore": {"XLA Ops": [_event("x", 0, 9999)]},
        "/host:CPU": {"python": host}})
    s = trace_reduce.reduce(profile)
    assert s["chips"] == 2
    # chip 0: [0, 1000) and [2000, 2250); chip 1: [0, 500); mean of both
    assert s["busy_s"] == pytest.approx((1250 + 500) / 2 * 1e-9)
    assert s["ops"]["%while.1 while s32[]"]["self"] == pytest.approx(
        (1000 - 300 - 500) / 2 * 1e-9)
    assert s["ops"]["%fusion.2 fusion f32[8]"]["calls"] == 1.5
    calls, seconds = trace_reduce.matching(s, "flash_fwd")
    assert calls == 0.5 and seconds == pytest.approx(250e-9)
    # the one gap, [1000, 2000), is named by the host event inside it
    assert s["idle_gaps"] == [["host:$engine.py:1 _admit",
                               pytest.approx(500e-9)]]


def test_no_chip_plane_gives_nothing():
    profile = _profile({"/host:CPU": {"python": [_event("f", 0, 10)]}})
    assert trace_reduce.reduce(profile) is None
