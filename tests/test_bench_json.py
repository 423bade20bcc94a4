"""bench.py output-contract tests (ISSUE 3 satellite): the flagship JSON
line must print — parseable, non-null value — even when a numeric gate
fails; gate failures land as "gate_<name>": "FAILED: ..." strings in
extra and only flip the rc."""

import json

import numpy as np
import pytest

import bench


class _FakeDev:
    platform = "tpu"


@pytest.fixture
def flagship_env(monkeypatch):
    """Pretend an accelerator exists and both flagships produce numbers,
    without running any real benchmark."""
    monkeypatch.setattr(bench, "detect_devices", lambda: [_FakeDev()])
    monkeypatch.setattr(bench, "bench_resnet",
                        lambda *a, **k: (100.0, 90.0, 110.0))
    monkeypatch.setattr(bench, "bench_gpt",
                        lambda *a, **k: (1000.0, 0.31, 900.0, 1100.0))
    monkeypatch.setenv("BENCH_MODELS", "resnet,gpt")
    monkeypatch.delenv("BENCH_SMOKE", raising=False)
    monkeypatch.delenv("BENCH_INFER", raising=False)


def _run_main(capsys):
    rc = bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1, out
    return rc, json.loads(out[0])


def test_flagship_line_survives_failing_gate(flagship_env, monkeypatch,
                                             capsys):
    """Inject a failing gate: the flagship JSON line still prints with a
    non-null value; the failure is a string in extra; rc is nonzero."""
    def boom():
        raise RuntimeError("injected gate failure")

    monkeypatch.setattr(bench, "_gate_flash", boom)
    monkeypatch.setattr(bench, "grad_numeric_gates", lambda: {"g": 1.0})
    monkeypatch.setattr(bench, "_gate_mem", lambda: {"m": 1.0})
    rc, row = _run_main(capsys)
    assert rc != 0
    assert row["metric"] == "resnet50_train_images_per_sec_per_chip"
    assert row["value"] == 100.0  # NOT zeroed out by the gate failure
    assert row["extra"]["gate_flash"].startswith("FAILED: RuntimeError")
    assert row["extra"]["g"] == 1.0  # later gates still ran
    assert row["extra"]["m"] == 1.0
    assert row["extra"]["gpt_mfu"] == 0.31


def test_every_gate_failing_still_prints_numbers(flagship_env, monkeypatch,
                                                 capsys):
    def boom(*a, **k):
        raise MemoryError("RESOURCE_EXHAUSTED: 144 MB remat temps")

    monkeypatch.setattr(bench, "_gate_flash", boom)
    monkeypatch.setattr(bench, "grad_numeric_gates", boom)
    monkeypatch.setattr(bench, "_gate_mem", boom)
    rc, row = _run_main(capsys)
    assert rc != 0
    assert row["value"] == 100.0
    for g in ("gate_flash", "gate_grad", "gate_mem"):
        assert row["extra"][g].startswith("FAILED: MemoryError")


def test_all_gates_passing_rc_zero(flagship_env, monkeypatch, capsys):
    monkeypatch.setattr(bench, "_gate_flash",
                        lambda: {"flash_max_rel_err": 1e-6})
    monkeypatch.setattr(bench, "grad_numeric_gates", lambda: {"g": 1.0})
    monkeypatch.setattr(bench, "_gate_mem", lambda: {"m": 1.0})
    rc, row = _run_main(capsys)
    assert rc == 0
    assert row["extra"]["flash_max_rel_err"] == 1e-6
    assert not [k for k in row["extra"] if k.startswith("gate_")]


def test_infer_rows_behind_env_guard(flagship_env, monkeypatch, capsys):
    """BENCH_INFER=1 folds the benchmarks/inference.py rows into extra;
    a failing row is isolated as a string like the gates."""
    monkeypatch.setattr(bench, "_gate_flash", lambda: {})
    monkeypatch.setattr(bench, "grad_numeric_gates", lambda: {})
    monkeypatch.setattr(bench, "_gate_mem", lambda: {})

    calls = []

    def fake_rows(extra):
        calls.append(True)
        extra["infer_resnet_bs16_img_s"] = 250.0
        extra["infer_capi"] = "FAILED: OSError: no libpaddle_tpu_capi"
        return ["capi"]

    monkeypatch.setattr(bench, "infer_rows", fake_rows)
    rc, row = _run_main(capsys)
    assert not calls  # guard off -> not invoked
    monkeypatch.setenv("BENCH_INFER", "1")
    rc, row = _run_main(capsys)
    assert calls
    assert rc != 0  # a failed row flips the rc like a failed gate
    assert row["extra"]["infer_resnet_bs16_img_s"] == 250.0
    assert row["extra"]["infer_capi"].startswith("FAILED:")


def test_smoke_fallback_when_no_accelerator(monkeypatch, capsys):
    """No accelerator: the CPU smoke row still prints one parseable JSON
    line (the pre-existing contract, kept)."""
    class _Cpu:
        platform = "cpu"

    monkeypatch.setattr(bench, "detect_devices", lambda: [_Cpu()])
    monkeypatch.setattr(bench, "bench_smoke", lambda: 42.0)
    rc = bench.main()
    row = json.loads(capsys.readouterr().out.strip())
    assert row["metric"] == "smoke_train_images_per_sec"
    assert row["value"] == 42.0
    assert rc == 0


# -- the repaired BENCH_r05 "always ship a row" contract (ISSUE 6) ----------

_OOM = ("RESOURCE_EXHAUSTED: Out of memory while trying to allocate\n"
        "  1. Size: 144.00M\n     Operator: op_name=\"jit(step)/pallas\"\n"
        "     Shape: bf16[6,16384,768]{2,1,0}\n")


def test_wrapped_oom_classifies_and_retries(monkeypatch):
    """An OOM raised at jit(step) compile time inside the gate/preflight
    path arrives wrapped (the Executor's op lowering re-raises as
    RuntimeError); the cause-chain walk must still classify it and fire
    the t/2 retry."""
    calls = []

    def fake_at(seq, n_chips, mesh_factory, steps, warmup, extra):
        calls.append(seq)
        if seq > 2048:
            try:
                raise MemoryError(_OOM)          # the root allocator error
            except MemoryError as root:
                raise RuntimeError(
                    "error lowering Op(flash_attention)") from root
        return 500.0, 0.2, 480.0, 520.0

    monkeypatch.setattr(bench, "_bench_gpt_at", fake_at)
    monkeypatch.setenv("BENCH_GPT_SEQ", "8192")
    extra = {}
    out = bench.bench_gpt(1, lambda *a: None, 5, 1, extra=extra)
    assert out[0] == 500.0
    assert calls == [8192, 4096, 2048]
    assert extra["gpt_seq_fallback"] == 2048
    # the gate string keeps the most recent failure (t=4096, the last
    # level that OOMed before the floor fit) and summarizes the CHAIN
    # MEMBER carrying the buffer table, not the "error lowering" wrapper
    assert extra["gate_flagship_gpt"].startswith(
        "FAILED: RESOURCE_EXHAUSTED at t=4096")
    assert "144.00M bf16[6,16384,768]" in extra["gate_flagship_gpt"]


def test_floor_oom_still_ships_row_with_gate(monkeypatch, capsys):
    """The BENCH_r05 regression: GPT OOMs at EVERY t down to the floor
    and ResNet fails too — the (smoke-fallback) row must still print,
    parseable, carrying gate_flagship_gpt and the retry trail.  Uses the
    REAL bench_gpt retry loop (only _bench_gpt_at is stubbed)."""
    calls = []

    def fake_at(seq, n_chips, mesh_factory, steps, warmup, extra):
        calls.append(seq)
        raise MemoryError(_OOM)

    def resnet_boom(*a, **k):
        raise RuntimeError("resnet also failed")

    monkeypatch.setattr(bench, "detect_devices", lambda: [_FakeDev()])
    monkeypatch.setattr(bench, "_bench_gpt_at", fake_at)
    monkeypatch.setattr(bench, "bench_resnet", resnet_boom)
    monkeypatch.setattr(bench, "bench_smoke", lambda: 33.0)
    monkeypatch.setattr(bench, "run_gates", lambda extra: [])
    monkeypatch.setenv("BENCH_MODELS", "resnet,gpt")
    monkeypatch.delenv("BENCH_SMOKE", raising=False)
    monkeypatch.delenv("BENCH_INFER", raising=False)
    monkeypatch.setenv("BENCH_GPT_SEQ", "8192")
    rc, row = _run_main(capsys)
    assert rc != 0
    assert calls == [8192, 4096, 2048]        # the retry trail ran
    assert row["value"] == 33.0               # a parseable row shipped
    assert row["extra"]["gate_flagship_gpt"].startswith(
        "FAILED: RESOURCE_EXHAUSTED at t=2048")
    assert "gpt" in row["extra"]["errors"]


def test_unexpected_exception_still_prints_row(flagship_env, monkeypatch,
                                               capsys):
    """An exception escaping the per-section isolation (the class that
    produced BENCH_r05's rc=1-with-no-row) degrades to the smoke row,
    never to a bare stack trace."""
    def boom(extra):
        raise RuntimeError("escaped the gate isolation")

    monkeypatch.setattr(bench, "run_gates", boom)
    monkeypatch.setattr(bench, "bench_smoke", lambda: 21.0)
    rc, row = _run_main(capsys)
    assert rc != 0
    assert row["value"] == 21.0
    assert "escaped the gate isolation" in \
        row["extra"]["errors"]["unexpected"]


def test_alloc_failure_cause_chain_and_spellings():
    try:
        raise MemoryError("RESOURCE_EXHAUSTED")
    except MemoryError as root:
        wrapped = RuntimeError("error lowering op")
        wrapped.__cause__ = root
    assert bench._is_alloc_failure(wrapped)
    assert bench._is_alloc_failure(
        RuntimeError("Allocation of 16.5G exceeds the memory capacity"))
    assert bench._is_alloc_failure(
        RuntimeError("Failed to allocate request for 144.0MiB"))
    assert not bench._is_alloc_failure(ValueError("shape mismatch"))
    # `raise X from None` suppresses the implicit context: a genuine
    # bug raised while an OOM was in flight must NOT classify (and be
    # silently retried) as an allocator failure
    try:
        try:
            raise MemoryError("RESOURCE_EXHAUSTED")
        except MemoryError:
            raise ValueError("real bug") from None
    except ValueError as suppressed:
        assert not bench._is_alloc_failure(suppressed)
