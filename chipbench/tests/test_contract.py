"""BENCHMARK.json against the files it names, and the result line."""

import json
import os
import re

import pytest

from chipbench import run as bench_run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"] and 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names) and "setup_s" in names
    for n in names + CELLS + [c["name"] for c in BENCH["configs"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_loads_by_name(cell):
    loaded = bench_run.load_cell(cell)
    assert loaded["traffic"]["runner"] in ("train", "serve")
    assert loaded["traffic"]["chips"] == loaded["chips"]
    e2e = {m["name"] for m in loaded["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert loaded["per_layer"]
    for m in loaded["per_layer"]:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_each_per_layer_metric_has_its_reader(metric):
    reader = bench_run.load_reader(metric["name"])
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
    assert (reader.NAME, reader.LAYER, reader.UNIT, reader.MOVES,
            reader.SOURCE) == (metric["name"], metric["layer"],
                               metric["unit"], metric["moves"],
                               metric["source"])
    runners = {bench_run.load_cell(c)["traffic"]["runner"]
               for c in metric.get("workloads", CELLS)}
    assert runners <= set(reader.RUNNERS)
    # nothing to read: nothing returned
    empty = {"runner": reader.RUNNERS[0], "requests": [], "stats": {},
             "trace": None}
    if metric["source"] == "device_trace":
        assert reader.read(empty) is None


def test_result_line_keys():
    result = {"correct": True, "attempted": 3, "failed": 0}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 5}
    line = json.loads(bench_run.result_line(
        result, {"setup_s": {"value": 1.5, "unit": "s"}}, device))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device"]
    traced = json.loads(bench_run.result_line(
        result, {}, device, {"device_ops": [], "idle_gaps": []}))
    assert list(traced)[-1] == "breakdown"
