"""Static-analysis engine tests (ISSUE 6 tentpole).

Every seeded check fires on a small deliberately-broken Program with the
exact finding id and severity; the clean GPT benchmark program lints to
ZERO findings; strict mode raises; the memaudit compatibility shims
still answer; and the Executor folds compile-time findings into
``last_step_cost`` / the trainer JSONL.  CPU-only, nothing executes a
training step — the engine's whole point is static judgment
(docs/analysis.md).
"""

import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import analysis, layers
from paddle_tpu.models import transformer

# layer count must differ from batch (2), heads (2) AND b*h (4) so the
# leading-axis probes are unambiguous (the test_memory_engine convention)
N_LAYER = 5
T, D = 12, 32


def _small_gpt(policy=None, dtype="float32", n_layer=N_LAYER):
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = 7
    with pt.program_guard(main, startup):
        outs = transformer.build(vocab_size=29, n_layer=n_layer, n_head=2,
                                 d_model=D, max_len=T, dropout_rate=0.0,
                                 dtype=dtype)
    if policy:
        pt.memory_optimize(main, policy=policy)
    return main, startup, outs["avg_cost"]


def _feed(seed=3):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, 29, (2, T)).astype(np.int64)
    return {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}


# -- program-level checks ---------------------------------------------------

def _planted_program():
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.fc(x, 2, name="live")
        layers.fc(x, 3, name="deadfc")
        blk = main.global_block()
        blk.create_var(name="orphan", shape=(3,), dtype="float32")
        a = blk.create_var(name="a", shape=(-1, 4), dtype="float32")
        b = blk.create_var(name="b", shape=(-1, 8), dtype="float32")
        c = blk.create_var(name="c", shape=(-1, 4), dtype="float32")
        blk.append_op("elementwise_add", {"X": [a.name], "Y": [b.name]},
                      {"Out": [c.name]})
        blk.append_op("relu", {"X": [x.name]}, {"Out": [y.name]})
    return main, y


def test_dead_code_ops_and_vars():
    main, y = _planted_program()
    rep = analysis.lint(main, fetch_list=[y], levels=("program",))
    dead = rep.by_check("program.dead-code")
    assert dead and all(f.severity == "warning" for f in dead)
    msgs = " ".join(f.message for f in dead)
    assert "deadfc" in msgs          # the dead op chain
    assert "orphan" in msgs          # the orphan declaration
    assert all(f.level == "program" for f in dead)


def test_shape_dtype_mismatch_is_error():
    main, y = _planted_program()
    rep = analysis.lint(main, fetch_list=[y], levels=("program",))
    sd = rep.by_check("program.shape-dtype")
    assert len(sd) == 1 and sd[0].severity == "error"
    assert "4" in sd[0].message and "8" in sd[0].message


def test_read_before_write_is_error():
    main, y = _planted_program()
    rep = analysis.lint(main, fetch_list=[y], levels=("program",))
    rbw = rep.by_check("program.read-before-write")
    assert {f.severity for f in rbw} == {"error"}
    read = " ".join(f.message for f in rbw)
    assert "'a'" in read and "'b'" in read


def test_fetch_overwritten_warning():
    main, y = _planted_program()
    rep = analysis.lint(main, fetch_list=[y], levels=("program",))
    fo = rep.by_check("program.fetch-overwritten")
    assert len(fo) == 1 and fo[0].severity == "warning"
    assert "LAST write" in fo[0].message


def test_grad_reads_after_backward_marker_allowed():
    """Optimizer ops read <param>@GRAD which no op writes — the Executor
    injects them; the read-before-write check must not fire."""
    main, _startup, loss = _small_gpt()
    rep = analysis.lint(main, fetch_list=[loss], levels=("program",))
    assert rep.by_check("program.read-before-write") == []


def test_strict_mode_raises():
    main, y = _planted_program()
    with pytest.raises(analysis.AnalysisError) as ei:
        analysis.lint(main, fetch_list=[y], levels=("program",),
                      strict=True)
    assert "program.read-before-write" in str(ei.value)
    # warnings alone never raise
    main2, _s, loss = _small_gpt()
    analysis.lint(main2, fetch_list=[loss], levels=("program",),
                  strict=True)


# -- jaxpr-level checks -----------------------------------------------------

def test_scan_locality_fires_when_scan_engine_off(monkeypatch):
    main, _startup, loss = _small_gpt("selective")
    monkeypatch.setenv("PADDLE_TPU_SCAN_REMAT", "0")
    rep = analysis.lint(main, feed=_feed(), fetch_list=[loss],
                        levels=("jaxpr",), layer_count=N_LAYER)
    sl = rep.by_check("jaxpr.scan-locality")
    assert sl and sl[0].severity == "error"
    assert "outside" in " ".join(f.message for f in sl)


def test_scan_locality_clean_when_engine_on():
    main, _startup, loss = _small_gpt("selective")
    rep = analysis.lint(main, feed=_feed(), fetch_list=[loss],
                        levels=("jaxpr",), layer_count=N_LAYER)
    assert rep.by_check("jaxpr.scan-locality") == []


def test_bf16_accum_scan_carry():
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("xb", shape=[16, 8], dtype="bfloat16")
        init = layers.reduce_mean(x, dim=1)
        rnn = layers.StaticRNN(name="acc")
        with rnn.step():
            xt = rnn.step_input(x)
            acc = rnn.memory(init)
            new = acc + xt
            rnn.update_memory(acc, new)
            rnn.step_output(new)
        tot = layers.reduce_sum(rnn())
    rep = analysis.lint(main, fetch_list=[tot], levels=("jaxpr",))
    ba = rep.by_check("jaxpr.bf16-accum")
    assert len(ba) == 1 and ba[0].severity == "warning"
    assert "bfloat16 carry" in ba[0].message
    assert ba[0].data["scan_length"] == 16


def test_bf16_accum_quiet_on_f32_carry():
    """The same accumulator carried in f32 (the framework's own
    spelling) must not fire."""
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("xf", shape=[16, 8], dtype="float32")
        init = layers.reduce_mean(x, dim=1)
        rnn = layers.StaticRNN(name="acc")
        with rnn.step():
            xt = rnn.step_input(x)
            acc = rnn.memory(init)
            new = acc + xt
            rnn.update_memory(acc, new)
            rnn.step_output(new)
        tot = layers.reduce_sum(rnn())
    rep = analysis.lint(main, fetch_list=[tot], levels=("jaxpr",))
    assert rep.by_check("jaxpr.bf16-accum") == []


def test_tanh_gelu_reassociation_hazard():
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[16])
        h = x
        for i in range(4):
            h = layers.fc(h, 16, act="tanh", name=f"l{i}")
        loss = layers.reduce_mean(layers.fc(h, 1, name="head"))
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    pt.memory_optimize(main, policy="full")
    rep = analysis.lint(main, fetch_list=[loss], levels=("jaxpr",))
    tg = rep.by_check("jaxpr.tanh-gelu")
    assert len(tg) == 1 and tg[0].severity == "warning"
    assert "erf" in tg[0].hint


def test_kernel_residual_offload_degraded():
    """offload on a program with no uniform scan group silently degrades
    to selective — the lint surfaces it."""
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[16])
        h = layers.fc(x, 12, act="relu", name="a1")
        h = layers.fc(h, 6, act="sigmoid", name="b1")
        loss = layers.reduce_mean(layers.fc(h, 1, name="c1"))
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    pt.memory_optimize(main, policy="offload")
    rep = analysis.lint(main, fetch_list=[loss], levels=("jaxpr",))
    kr = rep.by_check("jaxpr.kernel-residual")
    assert kr and kr[0].severity == "warning"
    from paddle_tpu.analysis.jaxpr_tools import BLOCK_INPUT_TAG

    assert BLOCK_INPUT_TAG in kr[0].message


def test_kernel_residual_quiet_on_clean_offload():
    main, _startup, loss = _small_gpt("offload")
    rep = analysis.lint(main, feed=_feed(), fetch_list=[loss],
                        levels=("jaxpr",), layer_count=N_LAYER)
    assert rep.by_check("jaxpr.kernel-residual") == []


# -- hlo-level checks -------------------------------------------------------

def test_hbm_preflight_over_budget():
    main, _startup, loss = _small_gpt()
    rep = analysis.lint(main, feed=_feed(), fetch_list=[loss],
                        levels=("hlo",), hbm_budget=1)
    hp = rep.by_check("hlo.hbm-preflight")
    assert len(hp) == 1 and hp[0].severity == "error"
    assert hp[0].message.startswith("RESOURCE_EXHAUSTED (preflight)")
    assert hp[0].data["budget_bytes"] == 1


def test_preflight_hbm_helper():
    assert analysis.preflight_hbm(None, 100) == []
    assert analysis.preflight_hbm(50, None) == []
    assert analysis.preflight_hbm(50, 100) == []
    (f,) = analysis.preflight_hbm(200, 100, context="t=16384")
    assert f.check == "hlo.hbm-preflight" and f.severity == "error"
    assert "t=16384" in f.message


def test_donation_findings_pure():
    fire = analysis.donation_findings(
        {"argument_bytes": 5 << 20, "alias_bytes": 0}, True)
    assert [f.check for f in fire] == ["hlo.donation-alias"]
    assert fire[0].severity == "warning"
    # aliased, tiny, or donation-off: quiet
    assert analysis.donation_findings(
        {"argument_bytes": 5 << 20, "alias_bytes": 4 << 20}, True) == []
    assert analysis.donation_findings(
        {"argument_bytes": 1 << 10, "alias_bytes": 0}, True) == []
    assert analysis.donation_findings(
        {"argument_bytes": 5 << 20, "alias_bytes": 0}, False) == []


_INLOOP_HLO = """\
HloModule planted, entry_computation_layout={(f32[8])->f32[8]}

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %g = f32[8] get-tuple-element((s32[], f32[8]) %p), index=1
  %ar = f32[8] all-reduce(f32[8] %g), replica_groups={}, to_apply=%sum.2
}

%cond.3 (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
}

ENTRY %main.4 (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %w = (s32[], f32[8]) while((s32[], f32[8]) %t), condition=%cond.3, body=%body.1
  %out = f32[8] all-reduce(f32[8] %gte), replica_groups={}, to_apply=%sum.2
}
"""


def test_inloop_collective_error_and_expected():
    from paddle_tpu.analysis.hlo_tools import hlo_comm_report

    comm = hlo_comm_report(_INLOOP_HLO)
    assert comm["reduce_ops_in_loop"] == 1 and comm["reduce_ops"] == 2
    ctx = analysis.CheckContext(None).seed("comm", comm)
    from paddle_tpu.analysis.hlo_checks import inloop_collective

    fs = list(inloop_collective(ctx))
    assert [f.check for f in fs] == ["hlo.inloop-collective"]
    assert fs[0].severity == "error"
    # run_steps fuses steps into one loop: the expected in-loop reduce
    # must produce NO finding (not even the gather-class info)
    ctx2 = analysis.CheckContext(None, in_loop_expected=True)
    ctx2.seed("comm", comm)
    assert list(inloop_collective(ctx2)) == []
    # genuine gather-class in-loop collectives still report as info
    ctx3 = analysis.CheckContext(None, in_loop_expected=True)
    ctx3.seed("comm", dict(comm, collectives_in_loop=3))
    fs3 = list(inloop_collective(ctx3))
    assert [f.severity for f in fs3] == ["info"]


# -- the clean program ------------------------------------------------------

@pytest.mark.parametrize("policy", [None, "selective", "offload"])
def test_clean_gpt_zero_findings(policy):
    """The GPT benchmark program lints to ZERO findings at every level,
    under no policy and under the remat policies the flagship runs."""
    main, _startup, loss = _small_gpt(policy)
    rep = analysis.lint(main, feed=_feed(), fetch_list=[loss],
                        layer_count=N_LAYER)
    assert rep.findings == [], [repr(f) for f in rep.findings]


_EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")


@pytest.mark.parametrize("script", sorted(
    f for f in os.listdir(_EXAMPLES) if f.endswith(".py")))
def test_example_programs_lint_clean(script):
    """Every ``examples/`` script's ``build_program()`` (what ``python
    -m paddle_tpu --lint <script>`` loads) has no program-level error
    or warning."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "pt_example_" + script[:-3], os.path.join(_EXAMPLES, script))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    pt.core.unique_name.reset()
    main, _startup, fetch = mod.build_program()
    rep = analysis.lint(main, fetch_list=fetch, levels=("program",))
    assert not rep.errors and not rep.warnings, rep.ids()


# -- framework / registry ---------------------------------------------------

def test_registry_has_seeded_checks():
    ids = {s.id for s in analysis.registered_checks()}
    assert {
        "program.dead-code", "program.shape-dtype",
        "program.read-before-write", "program.fetch-overwritten",
        "jaxpr.scan-locality", "jaxpr.kernel-residual",
        "jaxpr.bf16-accum", "jaxpr.tanh-gelu",
        "hlo.inloop-collective", "hlo.donation-alias",
        "hlo.hbm-preflight",
    } <= ids
    by_level = {lvl: [s for s in analysis.registered_checks(lvl)]
                for lvl in analysis.LEVELS}
    assert all(by_level.values())
    with pytest.raises(ValueError):
        analysis.register_check("program.dead-code", "program")(
            lambda ctx: [])


def test_unknown_level_rejected():
    """A typo'd level must raise, not silently run zero checks and
    report success."""
    main, y = _planted_program()
    with pytest.raises(ValueError, match="porgram"):
        analysis.lint(main, fetch_list=[y], levels=("porgram",))


def test_report_api_and_serialization():
    main, y = _planted_program()
    rep = analysis.lint(main, fetch_list=[y], levels=("program",))
    assert not rep.ok and len(rep.errors) >= 1
    d = rep.to_dict()
    assert d["ok"] is False
    assert len(d["findings"]) == len(rep)
    assert "error" in rep.summary()
    f = rep.findings[0]
    assert set(f.to_dict()) >= {"check", "severity", "level", "location",
                                "message", "hint"}


def test_artifact_failure_reported_not_raised():
    """A program whose trace fails (read of a missing var) must not kill
    lint — jaxpr/hlo checks report one artifact-skip info finding."""
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        blk = main.global_block()
        out = blk.create_var(name="o", shape=(4,), dtype="float32")
        blk.append_op("relu", {"X": ["never_written"]},
                      {"Out": [out.name]})
    rep = analysis.lint(main, fetch_list=[out])
    assert rep.by_check("program.read-before-write")  # the root cause
    art = rep.by_check("analysis.artifact")
    assert art and all(f.severity == "info" for f in art)


# -- the retired memaudit shim surface --------------------------------------

def test_memaudit_shims_deleted():
    """The deprecated ``core/memaudit.py`` shim module is GONE (ISSUE 14
    satellite — PR 11 had already migrated every in-repo caller): the
    module neither exists on disk nor imports, and no in-repo file
    mentions it in an import statement.  The analysis package no longer
    re-exports its parity surface either — tools import from
    ``analysis.hlo_tools`` / ``analysis.jaxpr_tools`` directly."""
    import importlib
    import re

    with pytest.raises(ImportError):
        importlib.import_module("paddle_tpu.core.memaudit")
    root = os.path.dirname(os.path.dirname(os.path.abspath(
        pt.__file__)))
    assert not os.path.exists(os.path.join(
        root, "paddle_tpu", "core", "memaudit.py"))
    offenders = []
    for dirpath, _dirs, files in os.walk(root):
        if any(part in dirpath for part in
               ("__pycache__", ".git", "/.claude", ".venv", "venv",
                "site-packages", "node_modules", "/build")):
            continue
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            if fn == "test_analysis.py":
                continue  # this contract test
            src = open(path, "r", encoding="utf-8",
                       errors="ignore").read()
            if re.search(r"^\s*(from|import)\s+[\w.]*memaudit",
                         src, re.MULTILINE):
                offenders.append(os.path.relpath(path, root))
    assert not offenders, offenders
    # the memaudit-parity names no longer ride the package namespace
    for gone in ("hlo_comm_report", "comm_report",
                 "compiled_memory_stats", "jaxpr_report", "walk_report",
                 "KERNEL_RESIDUAL_TAG", "BLOCK_INPUT_TAG",
                 "REDUCE_COLLECTIVES", "shape_pattern"):
        assert not hasattr(analysis, gone), gone


def test_audit_program_entry_point():
    """``analysis.audit_program`` (the real PR-4 audit entry point, not
    a shim) keeps its contract after the shim deletion."""
    main, startup, loss = _small_gpt("selective")
    scope = pt.Scope()
    with pt.core.scope.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        rep = analysis.audit_program(main, _feed(), [loss], scope=scope,
                                     layer_count=N_LAYER,
                                     absent_shapes=[(N_LAYER, T, D)])
    assert rep["pallas_total"] > 0
    assert not rep["layer_stacked_pallas"]
    assert rep["temp_bytes"] > 0 and rep["hbm_high_water_bytes"] > 0
    assert all(v == 0 for v in rep["absent_shape_hits"].values())
    assert any("fallback" not in p for p in rep["scan_remat_plan"])


# -- executor / reporter fold-in --------------------------------------------

def test_executor_folds_findings_into_step_cost():
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.fc(x, 2, name="live")
        layers.fc(x, 3, name="deadfc")  # dead, but lowerable
    scope = pt.Scope()
    with pt.core.scope.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        exe.run(main, feed={"x": np.zeros((2, 4), np.float32)},
                fetch_list=[y], scope=scope)
    cost = exe.last_step_cost
    assert cost["lint_findings"] >= 1
    assert "program.dead-code" in cost.get("lint_checks", [])
    assert cost["lint_errors"] == 0


def test_executor_lint_kill_switch(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_LINT", "0")
    main, _startup, loss = _small_gpt()
    scope = pt.Scope()
    with pt.core.scope.scope_guard(scope):
        exe = pt.Executor()
        exe.run(_startup, scope=scope)
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    assert "lint_findings" not in exe.last_step_cost


def test_reporter_jsonl_carries_lint_fields(tmp_path):
    from paddle_tpu.observability import MetricsReporter, read_jsonl

    class EndIteration:
        pass

    ev = EndIteration()
    ev.pass_id, ev.batch_id, ev.cost, ev.metrics = 0, 0, 0.5, []
    ev.wall_time, ev.samples, ev.throughput = 0.01, 4, 400.0
    ev.mfu, ev.reader_wait = None, None
    ev.step_cost = {"cache_hit": False, "lint_findings": 2,
                    "lint_errors": 1,
                    "lint_checks": ["program.dead-code"]}
    path = str(tmp_path / "run.jsonl")
    rep = MetricsReporter(log_every_n=0, jsonl_path=path)
    rep(ev)
    rep.close()
    recs = [r for r in read_jsonl(path) if r.get("event") == "step"]
    assert recs[0]["lint_findings"] == 2
    assert recs[0]["lint_errors"] == 1
    assert recs[0]["lint_checks"] == ["program.dead-code"]
