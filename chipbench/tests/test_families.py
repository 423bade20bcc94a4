"""The seam: what a model is made of is reached through one module per
family, found by file name (``chipbench/families/``), and nowhere else."""

import json
import os
import re
import sys
import textwrap

import pytest

from chipbench import families, flops
from chipbench import run as bench_run

with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_each_configuration_resolves_to_its_family(config):
    """The family answers every responsibility the runners of the
    configuration's cells use, and its sizes are whole numbers."""
    cfg = bench_run._read_json(bench_run.ROOT, config["file"])
    cells = [w["name"] for w in BENCH["workloads"]
             if w["config"] == config["name"]]
    assert cells, "a configuration no cell uses"
    for cell in cells:
        runner = bench_run.load_cell(cell)["traffic"]["runner"]
        family = families.of(cfg, runner)  # raises where one is left out
        for name in families.NEEDS[runner]:
            assert callable(getattr(family, name)), (cell, name)
    size = families.sizes(cfg)
    assert set(size) == {"d_model", "heads", "head_dim",
                         "vocab_rows", "matmul_params", "kv_planes",
                         "attention_passes"}
    assert all(isinstance(v, int) and v > 0 for v in size.values()), size
    assert size["vocab_rows"] >= cfg["vocab_size"]


GUARDED = [
    (re.compile(r"^\s*(from|import)\b.*\b(reference|weights)\b", re.M),
     "imports the gpt2 family's reference or weights"),
    (re.compile(r"transformer\.build\("), "calls transformer.build"),
    (re.compile(r"ServingEngine\("), "calls ServingEngine("),
    (re.compile(r"""["'](n_layer|n_embd|n_inner|n_head)["']"""),
     "reads a model size from a configuration"),
]
# the gpt2 family's own two files, kept where PR 24 put them
FAMILY_FILES = {"reference.py", "weights.py"}


def _sources():
    for folder, _, files in os.walk(bench_run.HERE):
        rel = os.path.relpath(folder, bench_run.HERE)
        if rel.split(os.sep)[0] in ("families", "tests", "__pycache__"):
            continue
        for name in files:
            if name.endswith(".py") and not (rel == "." and
                                             name in FAMILY_FILES):
                yield os.path.join(folder, name)


def test_nothing_outside_families_knows_the_model():
    seen = list(_sources())
    assert len(seen) > 25  # run.py, flops.py, the runners, every reader
    found = [f"{os.path.relpath(path, bench_run.HERE)} {what}"
             for path in seen for pattern, what in GUARDED
             if pattern.search(open(path).read())]
    assert not found, found


def test_the_guard_sees_what_it_guards():
    for text in ("from .. import device, reference, traffic\n",
                 "from chipbench import weights\n",
                 "outs = transformer.build(vocab_size=1)\n",
                 "eng = pt.serving.ServingEngine(params)\n",
                 'd = config["n_embd"]\n', "h = cfg['n_head']\n"):
        assert any(p.search(text) for p, _ in GUARDED), text


RECORDER = textwrap.dedent('''
    """A second family for the rehearsal: the gpt2 block, said to run
    PASSES times over the same weights, every call recorded."""

    from chipbench.families import gpt2

    PASSES = 4
    CALLS = []


    def _recorded(name):
        def call(*args, **kwargs):
            CALLS.append(name)
            return getattr(gpt2, name)(*args, **kwargs)
        return call


    make_params = _recorded("make_params")
    serving_engine = _recorded("serving_engine")
    training_program = _recorded("training_program")
    logits = _recorded("logits")
    greedy_loss = _recorded("greedy_loss")


    def sizes(cfg):
        CALLS.append("sizes")
        size = gpt2.sizes(cfg)
        head = size["d_model"] * size["vocab_rows"]
        return dict(size,
                    matmul_params=PASSES * (size["matmul_params"] - head)
                    + head,
                    kv_planes=PASSES * size["kv_planes"],
                    attention_passes=PASSES * size["attention_passes"])
''')


@pytest.fixture
def recorder(tmp_path, monkeypatch):
    """``families/recorder.py`` in a directory of its own, loaded through
    the loader's directory argument; forgotten again after the test."""
    (tmp_path / "recorder.py").write_text(RECORDER)
    monkeypatch.setitem(sys.modules, "chipbench.families.recorder", None)
    module = families.load("recorder", directory=str(tmp_path))
    assert module.__file__ == str(tmp_path / "recorder.py")
    assert families.load("recorder") is module
    return module


def test_a_family_in_new_files_runs_both_runners(recorder):
    """Both runners on the CPU at the rehearsal's tiny size, on a
    configuration of the second family: every responsibility is reached
    through it, and ``flops.py`` and the readers answer from its sizes."""
    from chipbench.runners import serve, train
    from chipbench.tests import test_rehearsal as tiny

    cfg = dict(tiny.TINY, name="tiny-looped", family="recorder")
    plain = tiny.TINY
    assert families.of(cfg) is recorder and "family" not in plain

    def cell(mix):
        return dict(tiny._cell(mix), config=cfg)

    result = train.run(cell(tiny.TRAIN), seed=2 ** 31 + 5, seconds=0.5,
                       tracer=None)
    assert result["correct"], result["facts"]["loss_err"]
    assert {"make_params", "training_program", "greedy_loss",
            "sizes"} <= set(recorder.CALLS)
    facts = dict(result["facts"], config=cfg, traffic=tiny.TRAIN, chips=1,
                 trace=None, peak={"bf16_flops_per_s": 1e12})
    mfu = bench_run.load_reader("train.mfu").read
    assert mfu(facts) == pytest.approx(
        mfu(dict(facts, config=plain))
        * flops.train_flops_per_token(cfg, 64)
        / flops.train_flops_per_token(plain, 64))

    del recorder.CALLS[:]
    result = serve.run(cell(tiny.SERVE), seed=7, seconds=1.0, tracer=None)
    assert result["correct"], result["facts"]["worst_logit_margin"]
    assert {"make_params", "serving_engine", "logits"} <= set(recorder.CALLS)

    # 2 layers x 4 passes: planes and applications, and the blocks'
    # parameters four times with the head once
    d, f, rows = 256, 512, 256
    block = 4 * d * d + 2 * d * f
    assert flops.matmul_params(plain) == 2 * block + d * rows
    assert flops.matmul_params(cfg) == 4 * 2 * block + d * rows
    assert flops.vocab_rows(cfg) == rows
    assert flops.kv_bytes_per_token(cfg) == 4 * flops.kv_bytes_per_token(
        plain) == 2 * 8 * d * 2
    assert flops.train_flops_per_token(cfg, 64) == (
        6 * flops.matmul_params(cfg) + 6 * 8 * d * 64)
    assert flops.paged_attention_live(cfg, [10, 30]) == (
        4 * 8 * d * 40, 40 * flops.kv_bytes_per_token(cfg))
    # the readers' needles come from the sizes too
    flash = bench_run.load_reader("flash_roofline")
    assert "f32[4,64,1]" in flash.kernels(cfg, tiny.TRAIN)["flash_fwd"]
    paged = bench_run.load_reader("paged_attention_roofline")
    assert "bf16[49,8,2,128]" in paged.kernels(
        cfg, tiny.SERVE)["paged_attention"]


def test_a_family_that_only_serves_says_so(tmp_path, monkeypatch):
    (tmp_path / "servesonly.py").write_text(
        "def make_params(cfg, positions, seed): pass\n"
        "def serving_engine(params, cfg, registry, geometry): pass\n"
        "def logits(params, tokens, cfg): pass\n"
        "def sizes(cfg): pass\n")
    monkeypatch.setitem(sys.modules, "chipbench.families.servesonly", None)
    families.load("servesonly", directory=str(tmp_path))
    cfg = {"name": "x", "family": "servesonly"}
    assert families.of(cfg, "serve")
    with pytest.raises(SystemExit) as err:
        families.of(cfg, "train")
    assert "does not train" in str(err.value)
    assert "training_program, greedy_loss" in str(err.value)
    with pytest.raises(SystemExit) as err:
        families.of({"name": "y", "family": "no-such-family"})
    assert "no family 'no-such-family'" in str(err.value)
