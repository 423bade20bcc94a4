"""``train.attn_proj_busy_share`` (PR 60) against the recorded one-layer
GPT step of ``testdata/scopes_v5e.*`` (the fixture of
``test_scope_readers.py``: a trace taken on a TPU v5e WITH the program's
map from HLO instruction to named scope), and its entry in
``BENCHMARK.json``."""

import json
import os

import pytest

from chipbench import run as bench_run
from chipbench import scope_join, trace_reduce
from paddle_tpu.observability import trace

NAME = "train.attn_proj_busy_share"
READER = bench_run.load_reader(NAME)
DATA = os.path.join(bench_run.HERE, "testdata")
XPLANE = os.path.join(DATA, "scopes_v5e.xplane.pb")


@pytest.fixture()
def facts():
    with open(os.path.join(DATA, "scopes_v5e.scopes.json")) as f:
        scopes = json.load(f)
    scope_join._joined.clear()
    summary = trace_reduce.reduce(trace_reduce.load(XPLANE))
    return {"runner": "train", "trace": summary, "trace_path": XPLANE,
            "device_scopes": scopes}


def test_it_is_the_attn_proj_kinds_share_of_the_join(facts):
    got = READER.read(facts)
    joined = scope_join.joined(facts)
    assert got is not None and 0 < got < 100
    assert got == pytest.approx(
        100.0 * joined["kinds"]["attn.proj"] / joined["total"])
    # every phase: the recorded train step has a forward and a backward,
    # and the session's serving steps project too
    by = joined["by"]
    assert by["attn.proj", "forward"] > 0 and by["attn.proj", "backward"] > 0
    assert got == pytest.approx(100.0 * sum(
        s for (kind, _phase), s in by.items() if kind == "attn.proj")
        / joined["total"])
    # beside the FFN's share, never in it
    ffn = bench_run.load_reader("train.ffn_busy_share").read(facts)
    assert got + ffn < 100


@pytest.mark.parametrize("without", ["map", "trace", "join"])
def test_nothing_to_read_is_none(facts, without, monkeypatch):
    if without == "map":
        facts = dict(facts, device_scopes=[])
    elif without == "trace":
        facts = {"runner": "train", "trace": None, "trace_path": None}
    else:   # a program older than the join (the parent of PR 36)
        monkeypatch.delattr(trace, "device_seconds_by_scope")
    assert READER.read(facts) is None


def test_the_entry_in_the_benchmark_is_the_readers():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metric = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert metric == {
        "name": NAME, "unit": READER.UNIT, "better": "lower",
        "source": READER.SOURCE, "layer": READER.LAYER,
        "moves": READER.MOVES, "workloads": ["cgpt590m.train_2k"]}
    assert bench["per_layer"][-1] == metric
    assert READER.RUNNERS == ("train",)
