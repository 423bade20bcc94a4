"""The one-clock window of a traced run, under ``tests/`` so that the
driver's count guards it: the last ten cases of
``chipbench/tests/test_trace_reduce.py`` (PR 50) as they stand there,
on hand-made events.  A traced run's ``busy_s`` and ``window_s`` come
from ONE interval on ONE clock (the window is an event IN the trace),
which is what every device-trace reader stands on, PR 51's
``ssm.step_kernel_roofline``, ``ssm.chunk_kernel_roofline`` and
``ssm_moe.expert_matmul_roofline`` among them."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as bench_run  # noqa: E402
from chipbench import trace_reduce  # noqa: E402


def _event(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _profile(planes):
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=pname, lines=[
            types.SimpleNamespace(name=lname, events=events)
            for lname, events in lines.items()])
        for pname, lines in planes.items()])


# -- the traced window: one interval on the trace's clock -----------------

W0, W1 = 1000, 11000
WHILE = "%while.9 = s32[] while(s32[] %x)"
FUSION = "%fusion.2 = f32[8] fusion(f32[8] %a)"
EARLY = "%fusion.5 = f32[8] fusion(f32[8] %e)"
TAIL = "%fusion.7 = f32[8] fusion(f32[8] %t)"
GONE = "%copy.4 = f32[8] copy(f32[8] %c)"


def _windowed(window=((W0, W1),), driver=()):
    """Chip 0 is busy across both edges: an operation that starts before
    the window, a ``while`` that runs past its end with fusions nested in
    it (one cut by the edge, one wholly past it) and an operation wholly
    before the window.  Chip 1 leaves a gap at each edge."""
    chip0 = [_event(GONE, 100, 200),            # wholly before: absent
             _event(EARLY, 400, 1200),          # [400, 1600): 600 inside
             _event(FUSION, 2000, 3000),        # [2000, 5000)
             _event(WHILE, 6000, 6000),         # [6000, 12000): 5000 inside
             _event(FUSION, 6100, 2000),        # nested, whole
             _event(TAIL, 10900, 600),          # nested, 100 inside
             _event(GONE, 11500, 400)]          # nested, wholly past: absent
    chip1 = [_event(FUSION, 3000, 1000),        # gaps [1000, 3000) ...
             _event(FUSION, 9000, 500)]         # ... and [9500, 11000)
    host = [_event("$gen.py:1 sleep_until", 900, 2200),
            _event("thread", 0, 9200)]
    host += [_event(trace_reduce.WINDOW_EVENT, s, e - s) for s, e in window]
    return _profile({
        "/device:TPU:0": {"XLA Ops": chip0},
        "/device:TPU:1": {"XLA Ops": chip1},
        "/host:CPU": {"python": host,
                      "driver": [_event(n, s, d) for n, s, d in driver]}})


def test_the_window_is_read_from_the_host_plane():
    assert trace_reduce.window_interval(_windowed()) == (W0, W1)


@pytest.mark.parametrize("window", [(), ((W0, W1), (W0 + 5, W1)),
                                    ((W0, W0),)],
                         ids=["none", "two", "empty"])
def test_a_trace_without_one_window_raises_on_the_runners_path(window):
    with pytest.raises(trace_reduce.WindowMissing):
        bench_run.reduce_window(_windowed(window))


def test_busy_and_gaps_are_cut_to_the_window_and_sum_to_it():
    interval, s = bench_run.reduce_window(_windowed())
    assert interval == (W0, W1) and s["chips"] == 2
    # chip 0: [1000, 1600) [2000, 5000) [6000, 11000); chip 1: 1500
    assert s["busy_s"] == pytest.approx((600 + 3000 + 5000 + 1500) / 2 * 1e-9)
    assert s["window_s"] == pytest.approx((W1 - W0) * 1e-9)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["busy_s"] + s["idle_s"] == pytest.approx(s["window_s"],
                                                      rel=1e-12)
    assert sum(g for _, g in s["idle_gaps"]) == pytest.approx(s["idle_s"])
    # what the trace holds outside: 200 + 600 + 1000 on chip 0
    assert s["outside_s"] == pytest.approx(1800 / 2 * 1e-9)
    # the gaps at the edges are chip 1's alone: chip 0 is busy across both
    assert s["edge_gaps_s"] == pytest.approx([2000 / 2 * 1e-9,
                                              1500 / 2 * 1e-9])
    # chip 1's edge gaps are gaps like any other: the leading one is named
    # by the host event over it (as chip 0's [1600, 2000) is), the trailing
    # one by its neighbours and not by the window's own event
    gaps = dict(s["idle_gaps"])
    assert gaps["host:$gen.py:1 sleep_until"] == pytest.approx(
        (2000 + 400) / 2 * 1e-9)
    assert gaps["between %fusion.2 fusion f32[8] and end"] == pytest.approx(
        1500 / 2 * 1e-9)


def test_cut_operations_keep_their_part_inside_and_none_outside():
    _, s = bench_run.reduce_window(_windowed())
    ops = s["ops"]
    assert not any("%copy.4" in name for name in ops)
    early = ops["%fusion.5 fusion f32[8]"]
    assert early["calls"] == 0.5
    assert early["seconds"] == early["self"] == pytest.approx(600 / 2 * 1e-9)
    loop = ops["%while.9 while s32[]"]
    assert loop["seconds"] == pytest.approx(5000 / 2 * 1e-9)
    assert loop["self"] == pytest.approx((5000 - 2000 - 100) / 2 * 1e-9)
    assert ops["%fusion.7 fusion f32[8]"]["seconds"] == pytest.approx(
        100 / 2 * 1e-9)
    # fusion.2: whole on chip 0 (twice), and chip 1's two
    assert ops["%fusion.2 fusion f32[8]"]["calls"] == 2.0
    assert sum(v["self"] for v in ops.values()) == pytest.approx(s["busy_s"])
    assert sum(sec for _, sec in s["device_ops"]) == pytest.approx(s["busy_s"])


def test_a_chip_that_ran_nothing_inside_the_window_is_left_out():
    profile = _windowed()
    chips = trace_reduce.chip_ops(profile, (5200, 5900))
    assert list(chips) == []
    chips = trace_reduce.chip_ops(profile, (1000, 1500))
    assert list(chips) == ["/device:TPU:0"]
    assert chips["/device:TPU:0"] == [(1000, 1500, "%fusion.5 fusion f32[8]",
                                      EARLY)]
    assert trace_reduce.reduce(profile, interval=(5200, 5900)) is None


def test_reduced_whole_a_trace_sums_to_its_span():
    s = trace_reduce.reduce(_windowed())
    assert s["window_s"] == s["span_s"] and s["outside_s"] == 0.0
    assert s["edge_gaps_s"] == [0.0, 0.0]
    assert s["busy_s"] + s["idle_s"] == pytest.approx(s["span_s"])
    assert "%copy.4 copy f32[8]" in s["ops"]


def test_the_idle_the_host_held_is_a_part_of_the_idle():
    held = bench_run.load_reader("device.idle_host_held_share.serve")
    # the driver at work over everything: all the idle is held, no more
    profile = _windowed(driver=[("serving.step", 0, 20000)])
    interval, s = bench_run.reduce_window(profile)
    assert held.held_seconds(profile, interval) == pytest.approx(s["idle_s"])
    # at work over chip 1's leading gap and beyond the window's start:
    # [500, 2500) holds 1500 of [1000, 3000) and chip 0's [1600, 2000)
    profile = _windowed(driver=[("serving.step", 500, 2000),
                                ("serving.idle", 2500, 9000)])
    got = held.held_seconds(profile, interval)
    assert got == pytest.approx((1500 + 400) / 2 * 1e-9)
    assert got <= s["idle_s"]
    facts = {"trace": s, "trace_window_s": s["window_s"]}
    idle = trace_reduce.idle_share_percent(facts)
    assert idle == pytest.approx(100 * s["idle_s"] / s["window_s"])
    assert 100 * got / s["window_s"] <= idle


def test_the_tracer_opens_and_closes_the_window_inside_the_trace(
        monkeypatch, tmp_path):
    import jax

    order = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            order.append(("open", self.name))

        def __exit__(self, *exc):
            order.append(("close", self.name))

    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: order.append(("start_trace", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: order.append(("stop_trace",)))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tracer = bench_run.Tracer(str(tmp_path / "trace"))
    assert not tracer.running and not tracer.done
    tracer.start()
    assert tracer.running and order[-1] == ("open", trace_reduce.WINDOW_EVENT)
    tracer.stop()
    assert order == [("start_trace", str(tmp_path / "trace")),
                     ("open", trace_reduce.WINDOW_EVENT),
                     ("close", trace_reduce.WINDOW_EVENT),
                     ("stop_trace",)]
    assert tracer.done and not tracer.running and tracer.window_s > 0
