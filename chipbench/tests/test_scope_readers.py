"""The ten readers of the device's seconds by sub-layer (PR 36) against a
small trace recorded on a TPU v5e WITH the program's map from HLO
instruction to named scope (``testdata/scopes_v5e.xplane.pb`` and
``scopes_v5e.scopes.json``: decode steps of one request through an
8-layer hybrid engine at width 256 and one train step of a 1-layer GPT
under ``selective`` remat, in one profiler session; the map is
``trace.device_scopes()`` of that process for the two modules the
session ran, as JSON; the session's ``/host:metadata`` plane, the
modules' HLO protos, was dropped whole to fit the size, nothing inside
another plane touched)."""

import json
import os

import pytest

from chipbench import run as bench_run
from chipbench import scope_join, trace_reduce
from paddle_tpu.observability import trace

DATA = os.path.join(bench_run.HERE, "testdata")
XPLANE = os.path.join(DATA, "scopes_v5e.xplane.pb")
SERVE = ("step.attention_busy_share", "step.ffn_busy_share",
         "step.mixer_busy_share", "step.head_busy_share",
         "step.glue_busy_share", "step.unnamed_busy_share")
TRAIN = ("train.optimizer_busy_share", "train.ffn_busy_share",
         "train.recompute_busy_share", "train.unnamed_busy_share")


@pytest.fixture(scope="module")
def scopes():
    with open(os.path.join(DATA, "scopes_v5e.scopes.json")) as f:
        return json.load(f)


@pytest.fixture()
def facts(scopes):
    scope_join._joined.clear()
    summary = trace_reduce.reduce(trace_reduce.load(XPLANE))
    return {"runner": "serve", "trace": summary, "trace_path": XPLANE,
            "device_scopes": scopes}


def _read(name, facts):
    return bench_run.load_reader(name).read(facts)


def test_the_six_serving_shares_sum_to_100(facts):
    got = {name: _read(name, facts) for name in SERVE}
    assert all(v is not None and 0 <= v <= 100 for v in got.values()), got
    # the recorded session also holds a train step: its optimizer is the
    # one kind no serving share counts
    adam = _read("train.optimizer_busy_share", facts)
    assert sum(got.values()) + adam == pytest.approx(100.0, abs=1e-6)
    # the hybrid engine shows every kind, and the join is nearly whole
    assert got["step.mixer_busy_share"] > 0
    assert got["step.attention_busy_share"] > 0
    assert got["step.unnamed_busy_share"] < 5.0
    # what the join saw is the trace's busy union
    joined = scope_join.joined(facts)
    assert joined["total"] == pytest.approx(facts["trace"]["busy_s"],
                                            rel=0.01)


def test_the_training_shares(facts):
    got = {name: _read(name, facts) for name in TRAIN}
    assert all(v is not None and 0 <= v <= 100 for v in got.values()), got
    assert got["train.optimizer_busy_share"] > 0
    assert got["train.recompute_busy_share"] > 0
    assert got["train.ffn_busy_share"] > 0
    by = scope_join.joined(facts)["by"]
    assert by["ffn", "backward"] > 0 and by["ffn", "forward"] > 0
    assert ("optimizer", "backward") not in by


def test_the_kernels_seconds_lie_inside_their_kinds(facts):
    """The cross-checks the cells are held to: the calls found from
    outside, by the kernel's name, are part of the kind's seconds."""
    kinds = scope_join.joined(facts)["kinds"]
    _, paged = trace_reduce.matching(facts["trace"], "%paged_attention")
    _, ce = trace_reduce.matching(facts["trace"], "fused_ce_",
                                  "tpu_custom_call")
    _, flash = trace_reduce.matching(facts["trace"], "%flash_")
    assert 0 < paged <= kinds["attn.core"]
    assert 0 < ce <= kinds["head"]
    assert 0 < paged + flash <= kinds["attn.core"] * (1 + 1e-9)


def test_a_program_without_the_map_gives_nothing(facts, monkeypatch):
    monkeypatch.delattr(trace, "device_seconds_by_scope")
    for name in SERVE + TRAIN:
        assert _read(name, facts) is None
    monkeypatch.undo()
    scope_join._joined.clear()
    # no executable registered in this process, and no recorded map
    assert _read("step.ffn_busy_share",
                 dict(facts, device_scopes=[])) is None
    # an untraced run
    assert _read("step.ffn_busy_share", {"runner": "serve", "trace": None,
                                         "trace_path": None}) is None


def test_an_executable_from_an_older_trees_cache_is_all_unnamed(facts,
                                                                scopes):
    """Scope names are not in the compile cache's key: an executable that
    a tree without the vocabulary wrote names nothing, and says so."""
    old = [dict(e, instructions={
        n: [None, s[1], s[2].split("/serving.stack_pass")[0], s[3], []]
        for n, s in e["instructions"].items()}) for e in scopes]
    stale = dict(facts, device_scopes=old)
    assert _read("step.unnamed_busy_share", stale) == pytest.approx(100.0)
    assert _read("step.glue_busy_share", stale) == 0.0


def test_the_vocabulary_is_the_programs(scopes):
    kinds = {s[0] for e in scopes for s in e["instructions"].values()}
    assert kinds - {None} <= set(trace.KINDS)
    assert {"mixer", "attn.core", "attn.proj", "ffn", "head", "cache",
            "embed", "norm", "optimizer"} <= kinds
    phases = {s[1] for e in scopes for s in e["instructions"].values()}
    assert phases - {None} == set(trace.PHASES)
