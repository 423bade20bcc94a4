"""``sched.chunk_ahead_share`` against hand-made counters, its entry in
``BENCHMARK.json``, and a program without the counter (nothing to read:
nothing returned)."""

import json
import os

import pytest

from chipbench import run as bench_run

NAME = "sched.chunk_ahead_share"


def test_the_share_is_ahead_over_all_chunks_sent():
    reader = bench_run.load_reader(NAME)
    stats = {"serving.chunks_dispatched{ahead=1}": 120.0,
             "serving.chunks_dispatched{ahead=0}": 30.0}
    assert reader.read({"stats": stats}) == pytest.approx(80.0)
    # every chunk behind another; none (an engine that never had two)
    assert reader.read({"stats": {
        "serving.chunks_dispatched{ahead=1}": 7.0}}) == 100.0
    assert reader.read({"stats": {
        "serving.chunks_dispatched{ahead=0}": 7.0}}) == 0.0


def test_a_program_without_the_counter_gives_nothing_to_read():
    reader = bench_run.load_reader(NAME)
    assert reader.read({"stats": {"serving.step_seconds": {"count": 3}}}) \
        is None


def test_its_entry_lists_the_serving_cells_and_moves_tpot():
    bench = json.load(open(os.path.join(bench_run.ROOT, "BENCHMARK.json")))
    reader = bench_run.load_reader(NAME)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert bench["per_layer"][-1] is entry       # an addition, at the end
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) \
        == (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE)
    assert entry["better"] == "higher" and reader.RUNNERS == ("serve",)
    tpot = next(m for m in bench["end_to_end"] if m["name"] == "tpot_p90_ms")
    assert entry["workloads"] == tpot["workloads"]
