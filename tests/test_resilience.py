"""Elastic resilience engine (paddle_tpu/resilience/, ISSUE 8) —
retry/backoff, fault injection, watchdog supervision, the resumable
reader, full-state checkpoint discovery, the AsyncCheckpointer's
crashed-publish recovery branches, and trainer kill-and-resume
bit-exactness, in-process and across real SIGKILLs of child processes
(``tests/resilience_children.py``)."""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.observability import metrics as _obs
from paddle_tpu.resilience import checkpoint as rckpt
from paddle_tpu.resilience import faults as rfaults
from paddle_tpu.resilience import retry as rretry
from paddle_tpu.resilience.watchdog import Watchdog


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv(rfaults.ENV_VAR, raising=False)
    rfaults.reset()
    yield
    rfaults.reset()


# ------------------------------------------------------------------- retry
def test_retry_absorbs_transient_failures():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    before = _obs.get_registry().value("resilience.retries")
    assert rretry.retry_call(flaky, retries=4, sleep=lambda d: None) == "ok"
    assert len(calls) == 3
    assert _obs.get_registry().value("resilience.retries") == before + 2


def test_retry_gives_up_and_chains_last_error():
    def always():
        raise OSError("hard down")

    with pytest.raises(rretry.RetryError) as ei:
        rretry.retry_call(always, retries=2, sleep=lambda d: None)
    assert ei.value.attempts == 3
    assert isinstance(ei.value.last, OSError)
    # non-retryable exceptions propagate untouched, immediately
    with pytest.raises(ValueError):
        rretry.retry_call(lambda: (_ for _ in ()).throw(ValueError("x")),
                          retries=5, sleep=lambda d: None)


def test_backoff_schedule_jitter_bounds():
    bo = rretry.Backoff(base=0.1, factor=2.0, max_delay=2.0, jitter=0.0)
    assert [bo.delay(i) for i in range(6)] == [0.1, 0.2, 0.4, 0.8, 1.6, 2.0]
    jittered = rretry.Backoff(base=0.1, jitter=0.5)
    for i in range(8):
        d = jittered.delay(i)
        nominal = min(0.1 * 2.0 ** i, 2.0)
        assert 0.5 * nominal <= d <= 1.5 * nominal
    # bounded iteration
    assert len(list(rretry.Backoff(attempts=3))) == 3


# ------------------------------------------------------------------ faults
def test_fault_spec_parsing(monkeypatch):
    assert rfaults.spec() is None
    monkeypatch.setenv(rfaults.ENV_VAR, "io_error:3")
    sp = rfaults.spec()
    assert (sp.kind, sp.n, sp.point) == ("io_error", 3, "ckpt.write")
    monkeypatch.setenv(rfaults.ENV_VAR, "nope:1")
    with pytest.raises(ValueError):
        rfaults.spec()
    monkeypatch.setenv(rfaults.ENV_VAR, "sigkill:0")
    with pytest.raises(ValueError):
        rfaults.spec()


def test_fault_fires_only_on_nth_arrival(monkeypatch):
    monkeypatch.setenv(rfaults.ENV_VAR, "io_error:2")
    assert rfaults.maybe_fault("ckpt.write") is None  # arrival 1
    with pytest.raises(OSError):
        rfaults.maybe_fault("ckpt.write")             # arrival 2: fires
    assert rfaults.maybe_fault("ckpt.write") is None  # transient: once
    # other points never trip someone else's fault
    assert rfaults.maybe_fault("trainer.step") is None


def test_nan_and_reader_faults(monkeypatch):
    monkeypatch.setenv(rfaults.ENV_VAR, "nan_grad:1")
    assert rfaults.maybe_fault("trainer.step") == "nan"
    rfaults.reset()
    monkeypatch.setenv(rfaults.ENV_VAR, "reader_err:1")
    with pytest.raises(RuntimeError):
        rfaults.maybe_fault("reader.next")


def test_injected_io_error_absorbed_by_checkpoint_retry(tmp_path,
                                                        monkeypatch):
    """The ckpt.write fault point lives INSIDE the retried call: an
    injected transient OSError costs one retry, not the checkpoint."""
    from paddle_tpu.models import fit_a_line

    outs = fit_a_line.build(learning_rate=0.05)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    monkeypatch.setenv(rfaults.ENV_VAR, "io_error:1")
    before = _obs.get_registry().value("resilience.retries")
    ckpt = pt.io.AsyncCheckpointer()
    d = str(tmp_path / "ck")
    ckpt.save(d)
    ckpt.close()  # wait() inside raises if the write ultimately failed
    assert os.path.exists(os.path.join(d, "__manifest__.pkl"))
    assert _obs.get_registry().value("resilience.retries") >= before + 1


def test_injected_reader_fault_surfaces_from_train(tmp_path, monkeypatch):
    """PADDLE_TPU_FAULT=reader_err:N propagates out of Trainer.train as
    the input-pipeline exception it simulates."""
    losses = _small_model_and_losses(tmp_path, monkeypatch,
                                     fault="reader_err:3")
    assert losses["error"] is not None
    assert "injected reader exception" in str(losses["error"])
    assert len(losses["costs"]) == 2  # two steps before the fault


def test_injected_nan_poisons_step_cost(tmp_path, monkeypatch):
    losses = _small_model_and_losses(tmp_path, monkeypatch,
                                     fault="nan_grad:2")
    assert losses["error"] is None
    costs = losses["costs"]
    assert np.isnan(costs[1]) and not np.isnan(costs[0])


# ---------------------------------------------------------------- watchdog
def test_watchdog_trips_and_rearms():
    trips = []
    with Watchdog(0.05, label="t", on_trip=trips.append,
                  interval=0.01) as wd:
        deadline = time.monotonic() + 5.0
        while wd.trips < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert wd.trips == 1, "watchdog did not trip on a stalled loop"
        assert trips and trips[0] > 0.05
        wd.beat()  # recovery re-arms the edge
        deadline = time.monotonic() + 5.0
        while wd.trips < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert wd.trips == 2, "watchdog did not re-arm after a beat"
    reg = _obs.get_registry()
    assert reg.value("resilience.watchdog_trips", label="t") >= 2
    assert reg.value("resilience.watchdog_stalled", label="t") == 0.0


def test_watchdog_quiet_while_beating():
    with Watchdog(0.2, label="quiet", interval=0.02) as wd:
        for _ in range(10):
            time.sleep(0.02)
            wd.beat()
        assert wd.trips == 0


# --------------------------------------------------------- resumable reader
def test_resumable_reader_counts_and_fast_forwards():
    r = pt.reader.resumable(lambda: iter(range(10)))
    assert list(r()) == list(range(10))
    assert r.items == 10 and r.epochs == 1
    r.set_state({"items": 4})
    assert list(r()) == list(range(4, 10))
    assert r.items == 10  # position includes the fast-forwarded prefix
    # skip past the end is safe (empty remainder, no StopIteration leak)
    r.set_state({"items": 99})
    assert list(r()) == []


def test_resumable_reader_delegates_underlying_state():
    class FileLike:
        """Reader factory with its own O(1) cursor snapshot."""

        def __init__(self):
            self.pos = 0

        def state(self):
            return {"pos": self.pos}

        def set_state(self, st):
            self.pos = st["pos"]

        def __call__(self):
            for i in range(self.pos, 6):
                self.pos = i + 1
                yield i

    src = FileLike()
    r = pt.reader.resumable(src)
    it = iter(r())
    assert [next(it) for _ in range(2)] == [0, 1]
    st = r.state()
    assert st["items"] == 2 and st["underlying"] == {"pos": 2}
    src2 = FileLike()
    r2 = pt.reader.resumable(src2)
    r2.set_state(st)
    assert list(r2()) == [2, 3, 4, 5]  # no re-draw of the prefix
    assert r2.items == 6


# ------------------------------------------------- checkpoint manifest/dirs
def test_train_state_schema_roundtrip(tmp_path):
    d = tmp_path / "ck"
    d.mkdir()
    rckpt.save_train_state(str(d), {
        "global_step": 7, "pass_id": 1, "step_in_pass": 3,
        "rng_key": np.array([1, 2], np.uint32),
        "reader_state": {"items": 3},
    })
    st = rckpt.load_train_state(str(d))
    assert st["schema_version"] == rckpt.SCHEMA_VERSION
    assert st["global_step"] == 7
    np.testing.assert_array_equal(st["rng_key"], [1, 2])
    # a state from the FUTURE refuses to load
    rckpt.save_train_state(str(d), {"schema_version": 99})
    with pytest.raises(ValueError):
        rckpt.load_train_state(str(d))


def test_latest_checkpoint_skips_torn_dirs(tmp_path):
    """Discovery returns the newest LOADABLE step: torn dirs (missing
    markers / manifest) and bare .tmp leftovers are skipped."""
    import pickle

    root = tmp_path / "ckpt"

    def plant(step, complete=True, state=True):
        d = root / f"step_{step}"
        d.mkdir(parents=True)
        with open(d / "__manifest__.pkl", "wb") as f:
            pickle.dump({"__nprocs__": 1}, f)
        if complete:
            (d / "__done0__").write_text("ok")
        if state:
            rckpt.save_train_state(str(d), {"global_step": step})
        return d

    assert rckpt.latest_checkpoint(str(root)) is None
    plant(3)
    plant(6)
    plant(9, complete=False)          # writer killed before the marker
    (root / "step_12.tmp").mkdir()    # crashed mid-write leftover
    got = rckpt.latest_checkpoint(str(root))
    assert got == str(root / "step_6")
    # without a train-state sidecar the dir is complete but not resumable
    plant(15, state=False)
    assert rckpt.latest_checkpoint(str(root)) == str(root / "step_6")
    assert rckpt.latest_checkpoint(
        str(root), require_state=False) == str(root / "step_15")


def test_latest_checkpoint_honors_old_fallback(tmp_path):
    """A crash between the two publish renames leaves only step_N.old:
    discovery must still surface step_N (load_vars falls back)."""
    import pickle

    root = tmp_path / "ckpt"
    d = root / "step_5.old"
    d.mkdir(parents=True)
    with open(d / "__manifest__.pkl", "wb") as f:
        pickle.dump({"__nprocs__": 1}, f)
    (d / "__done0__").write_text("ok")
    rckpt.save_train_state(str(d), {"global_step": 5})
    assert rckpt.latest_checkpoint(str(root)) == str(root / "step_5")
    st = rckpt.load_train_state(str(root / "step_5"))
    assert st["global_step"] == 5


def test_prune_checkpoints_retention(tmp_path):
    root = tmp_path / "ckpt"
    for n in (3, 6, 9, 12):
        (root / f"step_{n}").mkdir(parents=True)
    (root / "step_3.tmp").mkdir()
    pruned = rckpt.prune_checkpoints(str(root), keep=2)
    left = sorted(os.listdir(root))
    assert left == ["step_12", "step_9"], left
    assert len(pruned) == 3  # step_3, step_3.tmp, step_6
    with pytest.raises(ValueError):
        rckpt.prune_checkpoints(str(root), keep=1)


# ------------------------------------- AsyncCheckpointer recovery branches
def _saved_params(program=None):
    program = program or pt.default_main_program()
    scope = pt.core.scope.global_scope()
    return {p.name: np.asarray(scope.get(p.name))
            for p in program.all_parameters()}


def _build_fit_a_line():
    from paddle_tpu.models import fit_a_line

    outs = fit_a_line.build(learning_rate=0.05)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    return exe, outs


def test_old_only_restore_branch(tmp_path):
    """ISSUE 8 satellite: load from a dir that exists ONLY as .old (the
    crash-between-renames window) — load_vars' fallback branch."""
    import shutil

    exe, _ = _build_fit_a_line()
    ckpt = pt.io.AsyncCheckpointer()
    d = str(tmp_path / "latest")
    ckpt.save(d, extra_state={"global_step": 1})
    ckpt.close()
    want = _saved_params()
    # simulate the torn window: published dir moved to .old, nothing at d
    shutil.move(d, d + ".old")
    scope = pt.core.scope.global_scope()
    for n, v in want.items():
        scope.update({n: np.zeros_like(v)})
    pt.io.load_persistables(exe, d)
    for n, v in want.items():
        np.testing.assert_array_equal(np.asarray(scope.get(n)), v)
    assert rckpt.load_train_state(d)["global_step"] == 1


def test_leftover_tmp_and_old_restored_before_write(tmp_path):
    """A crashed prior run's leftovers (.tmp garbage, .old-only good
    copy) are cleaned/recovered by the next save (io.py _write)."""
    exe, _ = _build_fit_a_line()
    d = str(tmp_path / "latest")
    # plant a stale .tmp (crashed mid-write last run) and an .old-only
    # good checkpoint (crashed mid-publish before that)
    os.makedirs(os.path.join(d + ".tmp", "junk"))
    ckpt = pt.io.AsyncCheckpointer()
    ckpt.save(d + ".old")  # a real snapshot parked at .old
    ckpt.wait()
    ckpt.save(d)
    ckpt.close()
    assert os.path.exists(os.path.join(d, "__manifest__.pkl"))
    assert not os.path.exists(d + ".tmp")
    assert not os.path.exists(d + ".old")
    pt.io.load_persistables(exe, d)  # loads clean


def test_raise_pending_surfaces_worker_errors(tmp_path, monkeypatch):
    """ISSUE 8 satellite: a failed background write surfaces on the NEXT
    save()/wait() — never silently."""
    _build_fit_a_line()
    ckpt = pt.io.AsyncCheckpointer()
    monkeypatch.setattr(
        pt.io.AsyncCheckpointer, "_write",
        staticmethod(lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("disk on fire"))))
    ckpt.save(str(tmp_path / "a"))
    ckpt._q.join()  # worker consumed the item and recorded its error
    # ...which the next save() surfaces synchronously
    with pytest.raises(RuntimeError, match="disk on fire"):
        ckpt.save(str(tmp_path / "b"))
    # the error swap is atomic: once raised it is consumed, and wait()
    # after the (never-queued) second save is clean
    ckpt.wait()
    ckpt.close()


def test_close_raises_pending_error(tmp_path, monkeypatch):
    _build_fit_a_line()
    ckpt = pt.io.AsyncCheckpointer()
    monkeypatch.setattr(
        pt.io.AsyncCheckpointer, "_write",
        staticmethod(lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("worker died"))))
    ckpt.save(str(tmp_path / "a"))
    with pytest.raises(RuntimeError, match="worker died"):
        ckpt.close()
    # the worker thread is shut down even though close() raised
    assert not ckpt._thread.is_alive()


def test_multiproc_snapshot_carries_sidecar_proc0_only(tmp_path,
                                                       monkeypatch):
    """The multi-process write path (tests/multihost_runner.py
    ckpt_mid_kill): process 0 writes the train-state sidecar + manifest,
    every process writes its own completion marker, and the checkpoint
    only counts as complete once ALL markers exist."""
    import paddle_tpu.io as io

    snap = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    d = str(tmp_path / "ck")
    state = {"global_step": 2, "rng_key": np.array([1, 2], np.uint32)}
    monkeypatch.setattr(io, "_multiproc_ids", lambda: (0, 2))
    io._write_snapshot(d, snap, extra_state=state)
    assert os.path.exists(os.path.join(d, rckpt.STATE_FILE))
    assert not rckpt.checkpoint_complete(d), \
        "complete before rank 1's marker"
    monkeypatch.setattr(io, "_multiproc_ids", lambda: (1, 2))
    io._write_snapshot(d, {}, extra_state=state)  # rank 1: markers only
    assert rckpt.checkpoint_complete(d)
    assert rckpt.load_train_state(d)["global_step"] == 2
    # write-once: re-saving into the published dir raises on both ranks
    with pytest.raises(ValueError, match="write-once"):
        io._write_snapshot(d, {}, extra_state=state)
    monkeypatch.setattr(io, "_multiproc_ids", lambda: (0, 2))
    with pytest.raises(ValueError, match="write-once"):
        io._write_snapshot(d, snap, extra_state=state)


# --------------------------------------------- trainer full-state resume
def _small_model_and_losses(tmp_path, monkeypatch, fault=None,
                            kill_after=None, resume=False,
                            steps_per_call=1, async_ckpt=True):
    """One Trainer.train run of a dropout model in a fresh scope: returns
    {"costs": [...], "error": exc_or_None, "trainer": tr}."""
    if fault:
        monkeypatch.setenv(rfaults.ENV_VAR, fault)
        rfaults.reset()
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = 11
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", shape=[5], dtype="float32")
        y = pt.layers.data("y", shape=[1], dtype="float32")
        h = pt.layers.fc(x, size=8, act="relu")
        h = pt.layers.dropout(h, 0.3)
        pred = pt.layers.fc(h, size=1)
        cost = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.Momentum(learning_rate=0.05,
                              momentum=0.9).minimize(cost)

    def reader():
        rng = np.random.default_rng(5)
        X = rng.normal(size=(32, 5)).astype(np.float32)
        Y = X.sum(axis=1, keepdims=True).astype(np.float32)
        for i in range(4):
            yield list(zip(X[i * 8:(i + 1) * 8], Y[i * 8:(i + 1) * 8]))

    costs = []

    class Stop(Exception):
        pass

    def handler(ev):
        if type(ev).__name__ == "EndIteration":
            costs.append(ev.cost)
            if kill_after is not None and len(costs) >= kill_after:
                raise Stop

    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    error = tr = None
    try:
        with pt.program_guard(main, startup):
            tr = pt.trainer.Trainer(cost, [x, y], main_program=main,
                                    startup_program=startup)
            try:
                tr.train(reader, num_passes=2, event_handler=handler,
                         checkpoint_dir=str(tmp_path / "ck"),
                         checkpoint_every_n_steps=3,
                         async_checkpoint=async_ckpt, resume=resume,
                         steps_per_call=steps_per_call)
            except Stop:
                pass
            except Exception as e:  # noqa: BLE001 — inspected by tests
                error = e
    finally:
        pt.core.scope._scope_stack.pop()
        if fault:
            monkeypatch.delenv(rfaults.ENV_VAR, raising=False)
            rfaults.reset()
    return {"costs": costs, "error": error, "trainer": tr}


def test_trainer_kill_and_resume_bit_exact(tmp_path, monkeypatch):
    """Full-state step checkpoints + resume reproduce the uninterrupted
    trajectory bit-for-bit: params, optimizer moments, RNG key (dropout
    masks!) and reader cursor all restored.  The SIGKILL variant on the
    8-device mesh is ``test_sigkilled_trainer_resumes_bit_exact``."""
    ref = _small_model_and_losses(tmp_path / "ref", monkeypatch)
    assert len(ref["costs"]) == 8 and ref["error"] is None
    part = _small_model_and_losses(tmp_path / "run", monkeypatch,
                                   kill_after=5)
    assert part["costs"] == ref["costs"][:5]
    res = _small_model_and_losses(tmp_path / "run", monkeypatch,
                                  resume=True)
    st = res["trainer"].last_resume
    assert st is not None and st["global_step"] == 3  # ckpt every 3 steps
    assert st["pass_id"] == 0 and st["step_in_pass"] == 3
    assert res["costs"] == ref["costs"][3:], \
        "resumed trajectory diverged from the uninterrupted run"
    assert _obs.get_registry().value("executor.resume_count") >= 1


def test_trainer_resume_cold_start_without_checkpoints(tmp_path,
                                                       monkeypatch):
    """resume=True over an empty checkpoint dir is a cold start, not an
    error (the first launch of an elastic job)."""
    out = _small_model_and_losses(tmp_path, monkeypatch, resume=True)
    assert out["error"] is None
    assert len(out["costs"]) == 8
    assert out["trainer"].last_resume is None


def test_trainer_fused_path_checkpoints_and_resumes(tmp_path,
                                                    monkeypatch):
    """checkpoint_every_n_steps also fires from the fused
    (steps_per_call>1) loop — at group boundaries — and the fused resume
    fast-forwards the reader correctly.  Fused grouping changes the
    device-call shape, so trajectories are compared fused-vs-fused."""
    ref = _small_model_and_losses(tmp_path / "ref", monkeypatch,
                                  steps_per_call=2)
    assert len(ref["costs"]) == 8 and ref["error"] is None
    part = _small_model_and_losses(tmp_path / "run", monkeypatch,
                                   kill_after=6, steps_per_call=2)
    ck = tmp_path / "run" / "ck"
    assert rckpt.latest_checkpoint(str(ck)) is not None
    res = _small_model_and_losses(tmp_path / "run", monkeypatch,
                                  resume=True, steps_per_call=2)
    st = res["trainer"].last_resume
    assert st is not None and st["global_step"] >= 3
    assert res["costs"] == ref["costs"][st["global_step"]:]


def test_injected_nan_poisons_fused_step_cost(tmp_path, monkeypatch):
    """nan_grad fires on the fused (steps_per_call>1) loop too — the
    poisoned batch inside the group, not the whole group."""
    out = _small_model_and_losses(tmp_path, monkeypatch,
                                  fault="nan_grad:3", steps_per_call=2)
    assert out["error"] is None
    costs = out["costs"]
    assert np.isnan(costs[2])
    assert not any(np.isnan(c) for c in costs[:2] + costs[3:])


def test_keep_checkpoints_validated_at_train_entry(tmp_path):
    """keep_checkpoints < 2 fails at train() entry, not 100 steps later
    when the first prune runs."""
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", shape=[2], dtype="float32")
        y = pt.layers.data("y", shape=[1], dtype="float32")
        cost = pt.layers.mean(pt.layers.square_error_cost(
            pt.layers.fc(x, size=1), y))
        pt.optimizer.SGD(learning_rate=0.1).minimize(cost)
        tr = pt.trainer.Trainer(cost, [x, y], main_program=main,
                                startup_program=startup)
        with pytest.raises(ValueError, match="keep_checkpoints"):
            tr.train(lambda: iter([]), checkpoint_dir=str(tmp_path),
                     checkpoint_every_n_steps=3, keep_checkpoints=1)


def test_trainer_checkpoint_delegates_reader_state(tmp_path, monkeypatch):
    """A resumable reader over a factory with its OWN state()/set_state()
    cursor: the step checkpoint snapshots the underlying cursor and the
    resume restores it WITHOUT re-drawing the consumed prefix — the
    non-replayable-stream case an item-count fast-forward cannot
    handle."""

    class Stream:
        """One-way batch stream: re-drawing consumed items is an error
        unless the cursor was restored through state()."""

        def __init__(self, draws):
            self.pos = 0
            self.draws = draws  # shared log of every batch handed out

        def state(self):
            return {"pos": self.pos}

        def set_state(self, st):
            self.pos = st["pos"]

        def __call__(self):
            rng = np.random.default_rng(5)
            X = rng.normal(size=(32, 5)).astype(np.float32)
            Y = X.sum(axis=1, keepdims=True).astype(np.float32)
            for i in range(self.pos, 4):
                self.pos = i + 1
                self.draws.append(i)
                yield list(zip(X[i * 8:(i + 1) * 8],
                               Y[i * 8:(i + 1) * 8]))

    def build_and_train(reader, resume):
        pt.core.unique_name.reset()
        main, startup = pt.Program(), pt.Program()
        main.random_seed = 11
        with pt.program_guard(main, startup):
            x = pt.layers.data("x", shape=[5], dtype="float32")
            y = pt.layers.data("y", shape=[1], dtype="float32")
            cost = pt.layers.mean(pt.layers.square_error_cost(
                pt.layers.fc(x, size=4), y))
            pt.optimizer.SGD(learning_rate=0.1).minimize(cost)
        costs = []

        class Stop(Exception):
            pass

        def handler(ev):
            if type(ev).__name__ == "EndIteration":
                costs.append(ev.cost)
                if not resume and len(costs) >= 3:
                    raise Stop

        scope = pt.Scope()
        pt.core.scope._scope_stack.append(scope)
        try:
            with pt.program_guard(main, startup):
                tr = pt.trainer.Trainer(cost, [x, y], main_program=main,
                                        startup_program=startup)
                try:
                    tr.train(reader, num_passes=1, event_handler=handler,
                             checkpoint_dir=str(tmp_path / "ck"),
                             checkpoint_every_n_steps=2,
                             async_checkpoint=False, resume=resume)
                except Stop:
                    pass
            return costs, tr
        finally:
            pt.core.scope._scope_stack.pop()

    draws = []
    r = pt.reader.resumable(Stream(draws))
    build_and_train(r, resume=False)  # killed after step 3, ckpt at 2
    st = rckpt.load_train_state(
        rckpt.latest_checkpoint(str(tmp_path / "ck")))
    assert st["reader_state"]["underlying"] == {"pos": 2}
    draws2 = []
    r2 = pt.reader.resumable(Stream(draws2))
    costs, tr = build_and_train(r2, resume=True)
    assert tr.last_resume["global_step"] == 2
    assert len(costs) == 2  # batches 2, 3
    assert draws2 == [2, 3], \
        f"resume re-drew consumed items: {draws2}"


def test_step_checkpoint_retention_and_telemetry(tmp_path, monkeypatch):
    """Step checkpoints prune to keep_checkpoints and record
    checkpoint.save_ms / checkpoint.bytes telemetry."""
    _small_model_and_losses(tmp_path, monkeypatch)
    ck = tmp_path / "ck"
    steps = sorted(n for n in os.listdir(ck) if n.startswith("step_"))
    assert steps == ["step_3", "step_6"], steps  # 8 steps, every 3, keep 3
    reg = _obs.get_registry()
    assert reg.value("checkpoint.saves") >= 2
    assert reg.value("checkpoint.last_bytes") > 0
    assert reg.value("checkpoint.last_save_ms") > 0
    h = reg.get("checkpoint.save_ms")
    assert h is not None and h.count >= 2


def test_reporter_jsonl_carries_resilience_fields(tmp_path, monkeypatch):
    """ISSUE 8 satellite: the trainer JSONL step records carry
    checkpoint_save_ms / checkpoint_bytes / resume_count so bench
    history can track checkpoint overhead."""
    import json

    from paddle_tpu.observability.reporter import MetricsReporter

    path = tmp_path / "run.jsonl"
    rep = MetricsReporter(log_every_n=0, jsonl_path=str(path))
    pt.core.unique_name.reset()
    from paddle_tpu.models import fit_a_line

    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            outs = fit_a_line.build(learning_rate=0.05)
            rng = np.random.default_rng(0)
            X = rng.normal(size=(16, 13)).astype(np.float32)
            Y = X.sum(axis=1, keepdims=True).astype(np.float32)
            tr = pt.trainer.Trainer(outs["avg_cost"], outs["feed"],
                                    main_program=main,
                                    startup_program=startup)
            # sync saves, so the save-at-step-2 telemetry is already in
            # the registry when step 3's JSONL record is written
            tr.train(lambda: iter([list(zip(X, Y))] * 4), num_passes=1,
                     event_handler=rep,
                     checkpoint_dir=str(tmp_path / "ck"),
                     checkpoint_every_n_steps=2, async_checkpoint=False)
        rep.close()
    finally:
        pt.core.scope._scope_stack.pop()
    steps = [json.loads(l) for l in open(path)
             if json.loads(l).get("event") == "step"]
    assert steps, "no step records"
    last = steps[-1]
    for k in ("checkpoint_save_ms", "checkpoint_bytes",
              "checkpoint_saves", "resume_count"):
        assert k in last, f"missing {k}: {sorted(last)}"
    assert last["checkpoint_saves"] >= 1
    assert last["checkpoint_bytes"] > 0


# ------------------------------------------- real kills, one process a mode

_CHILDREN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "resilience_children.py")


def _run_child(mode, workdir, fault=None):
    """One fresh process of ``resilience_children.py``: eight virtual CPU
    devices, one summation order (bit-exactness across processes), the
    fault armed through the environment.  Returns (rc, output)."""
    env = dict(os.environ)
    env.pop("PYTHONSAFEPATH", None)
    env.pop(rfaults.ENV_VAR, None)
    if fault:
        env[rfaults.ENV_VAR] = fault
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")
             and f != "--xla_cpu_multi_thread_eigen=false"]
    env["XLA_FLAGS"] = " ".join(flags + [
        "--xla_force_host_platform_device_count=8",
        "--xla_cpu_multi_thread_eigen=false"])
    env["OMP_NUM_THREADS"] = "1"
    repo = os.path.dirname(os.path.dirname(_CHILDREN))
    env["PYTHONPATH"] = os.pathsep.join(
        [repo] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, _CHILDREN, mode, str(workdir)], env=env,
        timeout=600, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    return proc.returncode, proc.stdout


def _losses(workdir, mode):
    with open(os.path.join(str(workdir), f"losses_{mode}.txt")) as f:
        return [line.strip() for line in f if line.strip()]


def _printed(out, key):
    vals = [ln.split()[1] for ln in out.splitlines()
            if ln.startswith(key + " ")]
    return vals[-1] if vals else None


def test_sigkilled_trainer_resumes_bit_exact(tmp_path):
    """A dp=8 trainer SIGKILLed mid-pass (no unwinding, the async
    checkpoint writer dead mid-queue) leaves a bit-exact prefix of the
    uninterrupted trajectory; the same command with ``resume=True``
    finds the latest LOADABLE step checkpoint and reproduces the rest of
    the trajectory bit for bit (losses compared as ``float.hex()``)."""
    import shutil

    import resilience_children as rc_

    rc, out = _run_child("ref", tmp_path)
    assert rc == 0, out
    ref = _losses(tmp_path, "ref")
    assert len(ref) == rc_.PASSES * rc_.STEPS_PER_PASS

    shutil.rmtree(tmp_path / "ckpt", ignore_errors=True)
    rc, out = _run_child("crash", tmp_path, fault=f"sigkill:{rc_.KILL_AT}")
    assert rc == -signal.SIGKILL, out
    crash = _losses(tmp_path, "crash")
    assert len(crash) == rc_.KILL_AT - 1
    assert crash == ref[:len(crash)]

    rc, out = _run_child("resume", tmp_path)
    assert rc == 0, out
    resumed_at = int(_printed(out, "RESUMED_AT"))
    assert rc_.CKPT_EVERY <= resumed_at < rc_.KILL_AT
    assert _losses(tmp_path, "resume") == ref[resumed_at:]


def test_crash_between_publish_renames_falls_back_to_old(tmp_path):
    """A writer killed BETWEEN the two renames of its second publish
    (exit code 23) leaves ``latest.old`` as the only complete copy; a
    fresh process loading ``latest`` gets the state of the last GOOD
    checkpoint, digest for digest, and its train-state sidecar."""
    rc, out = _run_child("ckptcrash", tmp_path, fault="ckpt_crash:2")
    assert rc == 23, out
    good = _printed(out, "CKPT1_DIGEST")
    assert good is not None
    latest = str(tmp_path / "latest")
    assert not os.path.exists(os.path.join(latest, "__manifest__.pkl"))
    assert os.path.exists(os.path.join(latest + ".old", "__manifest__.pkl"))

    rc, out = _run_child("ckptverify", tmp_path)
    assert rc == 0, out
    assert _printed(out, "RESTORED_DIGEST") == good
    assert _printed(out, "RESTORED_STEP") == "1"
