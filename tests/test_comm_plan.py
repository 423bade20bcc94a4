"""The sharding & communication contract analyzer (ISSUE 14):
CommPlan extraction (replica-group parsing, mesh-axis recovery, loop
membership, phase classification, provenance), the declarative
CommContract API, ``comm_diff``, the new checks
(``hlo.comm-contract`` / ``hlo.accidental-reshard`` /
``hlo.axis-attribution`` / ``program.spec-conflict`` /
``jaxpr.constraint-placement``), the Executor fold-in
(``exe.last_comm_plan`` + ``last_step_cost["comm_plan"]``), and the
schema-versioned ``--lint --json`` output contract."""

import json
import os
import subprocess
import sys

import numpy as np
import jax
import pytest

import paddle_tpu as pt
from paddle_tpu import analysis, layers
from paddle_tpu.analysis.comm import (
    CommContract,
    CommOp,
    CommPlan,
    attach_comm_contract,
    comm_diff,
    extract_comm_plan,
    mesh_axis_groups,
)
from paddle_tpu.analysis.comm.plan import (
    _axes_for_groups,
    _parse_replica_groups,
)
from paddle_tpu.parallel import api as papi
from paddle_tpu.parallel import contracts as pcontracts
from paddle_tpu.parallel.mesh import make_mesh
from jax.sharding import PartitionSpec as P


# -- replica-group parsing --------------------------------------------------

def test_parse_replica_groups_explicit():
    assert _parse_replica_groups("{{0,1,2,3},{4,5,6,7}}") == [
        [0, 1, 2, 3], [4, 5, 6, 7]]
    assert _parse_replica_groups("{{0,4},{1,5},{2,6},{3,7}}") == [
        [0, 4], [1, 5], [2, 6], [3, 7]]
    assert _parse_replica_groups("{}") == []
    assert _parse_replica_groups(None) is None
    assert _parse_replica_groups("garbage") is None


def test_parse_replica_groups_iota():
    # [2,4]<=[8]: iota(8).reshape(2,4) — rows are groups
    assert _parse_replica_groups("[2,4]<=[8]") == [
        [0, 1, 2, 3], [4, 5, 6, 7]]
    # the transposed form: iota(8).reshape(2,4).T.reshape(4,2)
    assert _parse_replica_groups("[4,2]<=[2,4]T(1,0)") == [
        [0, 4], [1, 5], [2, 6], [3, 7]]
    assert _parse_replica_groups("[8]<=[8]") == [
        [0, 1, 2, 3, 4, 5, 6, 7]]


def test_mesh_axis_recovery():
    mesh = make_mesh({"dp": 2, "fsdp": 4})
    groups = mesh_axis_groups(mesh)
    assert set(groups) == {("dp",), ("fsdp",), ("dp", "fsdp")}
    # on the row-major 8-device mesh: fsdp varies within a dp row
    assert _axes_for_groups([[0, 1, 2, 3], [4, 5, 6, 7]], groups,
                            8) == ("fsdp",)
    assert _axes_for_groups([[0, 4], [1, 5], [2, 6], [3, 7]], groups,
                            8) == ("dp",)
    # one all-devices group = the full-axis subset; {} spells the same
    assert _axes_for_groups([[0, 1, 2, 3, 4, 5, 6, 7]], groups,
                            8) == ("dp", "fsdp")
    assert _axes_for_groups([], groups, 8) == ("dp", "fsdp")
    # a partition matching NO axis subset: GSPMD invented a resharding
    assert _axes_for_groups([[0, 1], [2, 3], [4, 5], [6, 7]], groups,
                            8) is None
    # size-1 groups = no communication, not an invention
    assert _axes_for_groups([[k] for k in range(8)], groups, 8) == ()


# -- extraction from planted HLO --------------------------------------------

_PLANTED_HLO = """\
HloModule planted, entry_computation_layout={(f32[8])->f32[8]}

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %g = f32[8] get-tuple-element((s32[], f32[8]) %p), index=1
  %ag = f32[8,4]{1,0} all-gather(f32[2,4]{1,0} %g), channel_id=1, replica_groups=[2,4]<=[8], dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(step)/jvp(while)/body/pt_pin[fsdp_gather:w0]/squeeze"}
  %ar = f32[8] all-reduce(f32[8] %g), channel_id=2, replica_groups=[2,4]<=[8], to_apply=%sum.2, metadata={op_name="jit(step)/transpose(jvp(while))/body/dot_general"}
}

%cond.3 (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
}

ENTRY %main.4 (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %w = (s32[], f32[8]) while((s32[], f32[8]) %t), condition=%cond.3, body=%body.1
  %out = f32[4096] all-reduce(f32[4096] %gte), channel_id=3, replica_groups=[4,2]<=[2,4]T(1,0), to_apply=%sum.2, metadata={op_name="jit(step)/pt_pin[grad_boundary:fc.w]/add"}
  %rs = f32[2048] reduce-scatter(f32[4096] %gte), channel_id=4, replica_groups={{0,1},{2,3},{4,5},{6,7}}, dimensions={0}, to_apply=%sum.2, metadata={op_name="jit(step)/pt_shard[h_act]/dot_general"}
}
"""


@pytest.fixture
def planted_plan():
    mesh = make_mesh({"dp": 2, "fsdp": 4})
    return extract_comm_plan(_PLANTED_HLO, mesh=mesh)


def test_extract_kinds_loop_phase(planted_plan):
    plan = planted_plan
    assert len(plan) == 4
    by_kind = {op.kind: op for op in plan}
    ag = by_kind["all-gather"]
    assert ag.in_loop and ag.phase == "fwd-scan"
    assert ag.axes == ("fsdp",)
    assert ag.provenance == {"site": "fsdp_gather:w0"}
    ar_loop = [op for op in plan
               if op.kind == "all-reduce" and op.in_loop][0]
    # the transpose( autodiff marker classifies the backward scan
    assert ar_loop.phase == "bwd-scan"
    ar_boundary = [op for op in plan
                   if op.kind == "all-reduce" and not op.in_loop][0]
    assert ar_boundary.phase == "boundary"
    assert ar_boundary.axes == ("dp",)
    assert ar_boundary.bytes == 4096 * 4
    assert ar_boundary.provenance == {"site": "grad_boundary:fc.w"}
    rs = by_kind["reduce-scatter"]
    # {{0,1},{2,3},...} matches no axis subset of the dp2 x fsdp4 mesh
    assert rs.axes is None
    assert rs.provenance == {"var": "h_act"}
    assert plan.unattributed() == [rs]


def test_plan_select_and_summary(planted_plan):
    plan = planted_plan
    assert len(plan.select(kind="reduce")) == 3
    assert len(plan.select(kind="reduce", in_loop=True)) == 1
    assert len(plan.select(kind="gather")) == 1
    # the in-loop all-gather AND the in-loop all-reduce both span fsdp
    assert len(plan.select(axis="fsdp")) == 2
    assert len(plan.select(phase="boundary")) == 2
    assert len(plan.select(provenance=r"^h_")) == 1
    rows = plan.summary()
    assert all(set(r) == {"kind", "axes", "phase", "in_loop", "count",
                          "bytes"} for r in rows)
    assert json.loads(json.dumps(plan.to_dict()))  # JSON-able


def test_phase_label_override():
    mesh = make_mesh({"dp": 2, "fsdp": 4})
    plan = extract_comm_plan(_PLANTED_HLO, mesh=mesh,
                             label="serving_prefill_b4")
    assert {op.phase for op in plan} == {"prefill"}


def test_extract_without_mesh_keeps_axes_unresolved():
    plan = extract_comm_plan(_PLANTED_HLO, mesh=None)
    assert len(plan) == 4
    assert all(op.axes is None for op in plan)
    assert plan.mesh_axes == {}


# -- contracts --------------------------------------------------------------

def _mini_plan():
    return CommPlan([
        CommOp("all-reduce", 1024, ("dp",), False, "boundary"),
        CommOp("all-gather", 2048, ("fsdp",), True, "fwd-scan",
               provenance={"site": "fsdp_gather:w"}),
        CommOp("all-gather", 512, ("dp",), True, "fwd-scan",
               provenance={"var": "h_act"}),
    ], mesh_axes={"dp": 2, "fsdp": 4})


def test_contract_expect_and_forbid():
    plan = _mini_plan()
    c = (CommContract("good")
         .expect(kind="reduce", axis="dp", count=1, in_loop=False)
         .expect(kind="all-gather", axis="fsdp", min_count=1,
                 in_loop=True)
         .forbid(kind="reduce", in_loop=True))
    assert c.check(plan) == []
    bad = CommContract("bad").expect(kind="reduce", axis="dp", count=3)
    (v,) = bad.check(plan)
    assert "expected exactly 3" in v["message"] and v["op_count"] == 1
    forb = CommContract("noloop").forbid(kind="gather", in_loop=True)
    (v2,) = forb.check(plan)
    assert v2["op_count"] == 2 and "forbidden" in v2["message"]
    with pytest.raises(ValueError):
        CommContract("x").expect(kind="no-such-kind")


def test_contract_forbid_reshard_and_covered():
    plan = _mini_plan()
    c = CommContract("no-act").forbid_reshard(r"^h_")
    (v,) = c.check(plan)
    assert "h_act" in v["message"]
    # pin-site provenance does not match a var pattern scoped to ^h_
    assert v["op_count"] == 1
    cov = (CommContract("cover")
           .expect(kind="all-gather", axis="fsdp", in_loop=True))
    assert {op.kind for op in cov.covered(plan)} == {"all-gather"}
    with pytest.raises(Exception):
        CommContract("x").forbid_reshard("(unclosed")


def test_attach_comm_contract_accumulates():
    prog = pt.Program()
    a = attach_comm_contract(prog, CommContract("a"))
    attach_comm_contract(prog, CommContract("b"))
    from paddle_tpu.analysis.comm import comm_contracts

    assert [c.name for c in comm_contracts(prog)] == ["a", "b"]
    assert a.name == "a"
    assert comm_contracts(None) == []


def test_canned_training_contracts():
    mesh = make_mesh({"dp": 2, "fsdp": 4})
    cs = pcontracts.training_step_contract(mesh, accum=True, fsdp=True)
    assert [c.name for c in cs] == ["one-boundary-reduce",
                                    "fsdp-scan-gathers"]
    plan = _mini_plan()
    assert all(c.check(plan) == [] for c in cs)
    # a plan with an in-loop reduce violates both
    bad = CommPlan(plan.ops + [
        CommOp("all-reduce", 64, ("dp",), True, "bwd-scan")],
        mesh_axes=plan.mesh_axes)
    assert any(c.check(bad) for c in cs)


def test_collective_with_done_operand_still_counted():
    """Async comm overlap produces values named %all-gather-done.N; a
    real collective CONSUMING one must still land in the plan (the
    -done op itself never parses — the regex requires '(' right after
    the kind)."""
    text = (
        "HloModule m\n\n"
        "ENTRY %main (a: f32[8]) -> f32[8] {\n"
        "  %ar.5 = f32[1024] all-reduce(f32[1024] %all-gather-done.3),"
        " channel_id=2, replica_groups={}, to_apply=%sum,"
        ' metadata={op_name="jit(step)/add"}\n'
        "  %d = (f32[8]) all-gather-done((f32[8]) %s), channel_id=3\n"
        "}\n")
    plan = extract_comm_plan(text)
    assert [op.kind for op in plan] == ["all-reduce"]
    assert plan.ops[0].bytes == 1024 * 4


def test_anchored_forbid_reshard_hits_multi_output_provenance():
    """A multi-output producer's pt_shard scope joins its annotated
    outputs with commas; an anchored pattern (^h_) must still fire on
    the second name."""
    plan = CommPlan([
        CommOp("all-gather", 64, ("dp",), True, "fwd-scan",
               provenance={"var": "a_out,h_act"})],
        mesh_axes={"dp": 8})
    assert len(plan.select(provenance=r"^h_")) == 1
    (v,) = CommContract("x").forbid_reshard(r"^h_").check(plan)
    assert "h_act" in v["message"] and "a_out" not in str(v["message"])


def test_comm_report_derivation_matches_hlo_comm_report():
    """``CommPlan.comm_report()`` (what the Executor's fold-in ships)
    is key-for-key identical to the legacy text parser on the same
    HLO — one parse serves both shapes."""
    from paddle_tpu.analysis.hlo_tools import hlo_comm_report

    mesh = make_mesh({"dp": 2, "fsdp": 4})
    derived = extract_comm_plan(_PLANTED_HLO, mesh=mesh).comm_report()
    assert derived == hlo_comm_report(_PLANTED_HLO)
    assert derived["reduce_ops_in_loop"] == 1
    assert derived["collectives_in_loop"] == 2
    assert extract_comm_plan("", mesh=mesh).comm_report()[
        "collective_count"] == 0


def test_fused_compiles_still_evaluate_forbid_reshard():
    """The in_loop_expected exemption drops loop/phase selectors but
    NOT forbid_reshard — provenance rules are loop-insensitive, and a
    forbidden activation reshard must not hide behind run_steps'
    fused-loop production path."""
    from paddle_tpu.analysis.comm.checks import comm_contract

    prog = pt.Program()
    c = (CommContract("mixed")
         .forbid(kind="reduce", in_loop=True)   # confounded by fusion
         .forbid_reshard(r"^h_"))               # loop-insensitive
    attach_comm_contract(prog, c)
    fused = CommPlan([
        CommOp("all-reduce", 64, ("dp",), True, "fwd-scan"),
        CommOp("all-gather", 64, ("dp",), True, "fwd-scan",
               provenance={"var": "h_act"}),
    ], mesh_axes={"dp": 8})
    mesh = make_mesh({"dp": 8})
    ctx = analysis.CheckContext(prog, mesh=mesh, in_loop_expected=True)
    ctx.seed("comm_plan", fused)
    fs = list(comm_contract(ctx))
    assert len(fs) == 1
    assert "h_act" in fs[0].message  # the reshard rule fired
    assert "forbidden reduce" not in fs[0].message


def test_contract_check_skips_fused_run_steps_compiles():
    """run_steps fuses N optimizer steps into ONE while loop — the
    boundary reduce is structurally in-loop there, so contract
    in_loop/phase selectors would false-fire.  The hlo.comm-contract
    check applies the same in_loop_expected exemption as
    hlo.inloop-collective."""
    from paddle_tpu.analysis.comm.checks import comm_contract

    prog = pt.Program()
    attach_comm_contract(
        prog, CommContract("c").forbid(kind="reduce", in_loop=True))
    fused = CommPlan([
        CommOp("all-reduce", 64, ("dp",), True, "fwd-scan")],
        mesh_axes={"dp": 8})
    mesh = make_mesh({"dp": 8})
    ctx = analysis.CheckContext(prog, mesh=mesh, in_loop_expected=True)
    ctx.seed("comm_plan", fused)
    assert list(comm_contract(ctx)) == []
    ctx2 = analysis.CheckContext(prog, mesh=mesh)
    ctx2.seed("comm_plan", fused)
    assert [f.check for f in comm_contract(ctx2)] == [
        "hlo.comm-contract"]


def test_constraint_placement_exempts_declared_pt_shard():
    """A shard_activation annotation on a var produced INSIDE a scanned
    layer group traces as an in-scan constraint under pt_shard[var] —
    a declared annotation, policed by the reshard/contract checks, not
    flagged as a rogue unblessed pin."""
    from paddle_tpu.models import transformer

    mesh = make_mesh({"dp": 2, "fsdp": 4})
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = 7
    with pt.program_guard(main, startup):
        outs = transformer.build(vocab_size=64, n_layer=3, n_head=2,
                                 d_model=32, max_len=16,
                                 dropout_rate=0.0, dtype="float32")
    pt.memory_optimize(main, policy="selective")
    papi.data_parallel(main, "dp", programs=(startup,))
    blk = main.global_block()
    act = blk.vars["block1_att_out.tmp_0"]
    papi.shard_activation(act, P(*([None] * (len(act.shape) - 1)),
                                 "fsdp"))
    toks = np.zeros((4, 16), np.int64)
    feed = {"tokens": toks, "labels": toks}
    rep = analysis.lint(main, feed=feed,
                        fetch_list=[outs["avg_cost"]], mesh=mesh,
                        levels=("jaxpr",))
    assert rep.by_check("jaxpr.constraint-placement") == []


# -- comm_diff --------------------------------------------------------------

def test_comm_diff_explains_moved_op():
    base = _mini_plan()
    moved = CommPlan(base.ops + [
        CommOp("all-reduce", 4096, ("fsdp",), True, "bwd-scan"),
        CommOp("all-reduce", 4096, ("fsdp",), True, "bwd-scan"),
    ], mesh_axes=base.mesh_axes)
    diff = comm_diff(base, moved, "good", "bad")
    assert not diff["same"]
    (c,) = diff["changed"]
    assert c["kind"] == "all-reduce" and c["axes"] == "fsdp"
    assert c["in_loop"] and c["count_a"] == 0 and c["count_b"] == 2
    assert "good -> bad" in diff["text"][0]
    assert comm_diff(base, base)["same"]


# -- program.spec-conflict --------------------------------------------------

def test_spec_conflict_flags_indivisible_dims():
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[6])
        y = layers.fc(x, 3, name="odd")
    blk = main.global_block()
    # 3 does not divide over fsdp=4: annotated on the [6, 3] weight's
    # output axis
    blk.vars["odd.w"].partition_spec = P(None, "fsdp")
    mesh = make_mesh({"dp": 2, "fsdp": 4})
    rep = analysis.lint(main, fetch_list=[y], mesh=mesh,
                        levels=("program",))
    sc = rep.by_check("program.spec-conflict")
    assert sc and sc[0].severity == "warning"
    assert sc[0].data["var"] == "odd.w"
    assert sc[0].data["product"] == 4
    # a genuinely divisible spec is quiet: 6 % dp=2 == 0
    blk.vars["odd.w"].partition_spec = P("dp", None)
    rep2 = analysis.lint(main, fetch_list=[y], mesh=mesh,
                         levels=("program",))
    assert rep2.by_check("program.spec-conflict") == []


def test_spec_conflict_fsdp_composition():
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[6])
        y = layers.fc(x, 3, name="f")
    blk = main.global_block()
    blk.vars["f.w"].fsdp_param = True  # [6, 3]: 6 % fsdp=4 != 0
    mesh = make_mesh({"dp": 2, "fsdp": 4})
    rep = analysis.lint(main, fetch_list=[y], mesh=mesh,
                        levels=("program",))
    sc = rep.by_check("program.spec-conflict")
    assert sc and "fsdp" in sc[0].message
    # without a mesh the check is silent
    rep2 = analysis.lint(main, fetch_list=[y], levels=("program",))
    assert rep2.by_check("program.spec-conflict") == []


# -- executor fold-in + end-to-end on the 8-device mesh ---------------------

def _tiny_net(mesh):
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[16])
        yv = layers.data("y", shape=[1])
        h = layers.fc(x, 32, act="relu", name="h1")
        loss = layers.reduce_mean(
            layers.square(layers.fc(h, 1, name="out") - yv))
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    papi.data_parallel(main, "dp", programs=(startup,))
    return main, startup, loss


def test_executor_folds_comm_plan():
    mesh = make_mesh({"dp": 8})
    main, startup, loss = _tiny_net(mesh)
    exe = pt.Executor(mesh=mesh)
    exe.run(startup)
    feed = {"x": np.zeros((8, 16), np.float32),
            "y": np.zeros((8, 1), np.float32)}
    exe.run(main, feed=feed, fetch_list=[loss])
    plan = exe.last_comm_plan
    assert plan is not None and len(plan) > 0
    # the dp gradient reduction sits at the boundary, attributed to dp
    reduces = plan.select(kind="reduce", in_loop=False)
    assert reduces and all(op.axes == ("dp",) for op in reduces)
    assert not plan.unattributed()
    rows = exe.last_step_cost.get("comm_plan")
    assert rows == plan.summary()
    # the canned contract holds on this step
    (c,) = pcontracts.training_step_contract(mesh)
    assert c.check(plan) == []


def test_contract_violation_surfaces_in_compile_lint():
    mesh = make_mesh({"dp": 8})
    main, startup, loss = _tiny_net(mesh)
    # a contract this step cannot satisfy: forbid the boundary reduce
    attach_comm_contract(
        main, CommContract("impossible").forbid(kind="reduce"))
    exe = pt.Executor(mesh=mesh)
    exe.run(startup)
    feed = {"x": np.zeros((8, 16), np.float32),
            "y": np.zeros((8, 1), np.float32)}
    exe.run(main, feed=feed, fetch_list=[loss])
    cost = exe.last_step_cost
    assert cost["lint_errors"] >= 1
    assert "hlo.comm-contract" in (cost.get("lint_checks") or [])


def test_shard_activation_provenance_and_reshard_check():
    mesh = make_mesh({"dp": 8})
    main, startup, loss = _tiny_net(mesh)
    blk = main.global_block()
    act = blk.vars["h1.tmp_1"]
    papi.shard_activation(act, P(None, "dp"))  # feature-shard: reshard
    attach_comm_contract(
        main, CommContract("no-activation-reshard").forbid_reshard(r"^h1"))
    feed = {"x": np.zeros((8, 16), np.float32),
            "y": np.zeros((8, 1), np.float32)}
    rep = analysis.lint(main, feed=feed, fetch_list=[loss], mesh=mesh,
                        levels=("hlo",))
    ar = rep.by_check("hlo.accidental-reshard")
    assert ar and ar[0].severity == "warning"
    assert ar[0].data["var"] == "h1.tmp_1"
    assert ar[0].data["op_count"] > 0
    # a forbid_reshard contract upgrades it to an error naming the var
    cc = [f for f in rep.by_check("hlo.comm-contract")
          if f.severity == "error"]
    assert cc and "h1.tmp_1" in cc[0].message
    # shard_activation refuses persistables and data feeds
    with pytest.raises(ValueError):
        papi.shard_activation(blk.vars["x"], P("dp"))
    with pytest.raises(ValueError):
        papi.shard_activation(blk.vars["h1.w"], P("dp", None))


def test_constraint_placement_quiet_on_clean_programs():
    """The blessed pt_pin sites (boundary grad pin, accum carry, fsdp
    pins) never fire the constraint-placement check on a clean
    accumulation step."""
    mesh = make_mesh({"dp": 8})
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[16])
        yv = layers.data("y", shape=[1])
        h = layers.fc(x, 32, act="relu", name="h1")
        loss = layers.reduce_mean(
            layers.square(layers.fc(h, 1, name="out") - yv))
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    pt.gradient_accumulation(main, 2)
    papi.data_parallel(main, "dp", programs=(startup,))
    feed = {"x": np.zeros((16, 16), np.float32),
            "y": np.zeros((16, 1), np.float32)}
    rep = analysis.lint(main, feed=feed, fetch_list=[loss], mesh=mesh,
                        levels=("jaxpr",))
    assert rep.by_check("jaxpr.constraint-placement") == []


# -- the GPT step on the dp=2 x fsdp=4 mesh: planted mis-spellings, the plan's
# fundamentals, and the clean sweep ------------------------------------------

_GPT = dict(vocab_size=128, n_layer=3, n_head=2, d_model=32, max_len=16,
            dropout_rate=0.0, dtype="float32", learning_rate=1e-2)
_GPT_ACCUM = 2
_COMM_CHECKS = (
    "hlo.comm-contract", "hlo.accidental-reshard", "hlo.axis-attribution",
    "hlo.inloop-collective", "jaxpr.constraint-placement",
    "program.spec-conflict",
)


def _gpt_fsdp(policy="selective"):
    """The PR-10 recipe in its order: remat, accumulation, dp, fsdp."""
    from paddle_tpu.models import transformer

    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = 7
    with pt.program_guard(main, startup):
        outs = transformer.build(**_GPT)
    pt.memory_optimize(main, policy=policy)
    pt.gradient_accumulation(main, _GPT_ACCUM)
    papi.data_parallel(main, "dp", programs=(startup,))
    papi.shard_fsdp(main, programs=(startup,))
    return main, startup, outs["avg_cost"]


def _gpt_feed():
    rng = np.random.default_rng(5)
    toks = rng.integers(0, _GPT["vocab_size"],
                        (2 * _GPT_ACCUM * 2, _GPT["max_len"])
                        ).astype(np.int64)
    lbls = np.roll(toks, -1, axis=1)
    lbls[:, -1] = -1
    return {"tokens": toks, "labels": lbls}


def _gpt_comm_plan(mesh):
    main, startup, loss = _gpt_fsdp()
    scope = pt.Scope()
    with pt.core.scope.scope_guard(scope):
        exe = pt.Executor(mesh=mesh)
        exe.run(startup, scope=scope)
        exe.compile_only(main, feed=_gpt_feed(), fetch_list=[loss],
                         scope=scope)
    return exe.last_comm_plan


def _fsdp_composed_carry(lead):
    return P(*([None] * lead + ["dp"]), "fsdp")


def test_symmetric_fsdp_pin_is_an_unblessed_in_scan_constraint(monkeypatch):
    """docs/parallel.md rule 2's wrong spelling: a plain
    ``with_sharding_constraint`` where the forward-only custom-vjp pin
    belongs transposes to itself, so the backward scan inherits it."""
    from paddle_tpu.core import executor as ex

    def symmetric_pin(sharding, site="fsdp"):
        return lambda x: jax.lax.with_sharding_constraint(x, sharding)

    monkeypatch.setattr(ex, "_fsdp_fwd_pin", symmetric_pin)
    main, _startup, loss = _gpt_fsdp()
    rep = analysis.lint(main, feed=_gpt_feed(), fetch_list=[loss],
                        mesh=make_mesh({"dp": 2, "fsdp": 4}),
                        levels=("jaxpr",))
    errs = [f for f in rep.by_check("jaxpr.constraint-placement")
            if f.severity == "error"]
    assert any("fsdp" in (f.data.get("axes") or ())
               and (f.data.get("scan_depth") or 0) >= 1 for f in errs), [
        (f.data.get("axes"), f.data.get("scan_depth")) for f in errs]


def test_fsdp_composed_accum_carry_strays_off_its_plain_dp_contract(
        monkeypatch):
    """Rule 3's wrong spelling: the accumulation carry pinned
    ``P('dp', 'fsdp')`` errors AT the blessed ``accum_carry`` site."""
    from paddle_tpu.core import executor as ex

    monkeypatch.setattr(ex, "_accum_carry_spec", _fsdp_composed_carry)
    main, _startup, loss = _gpt_fsdp()
    rep = analysis.lint(main, feed=_gpt_feed(), fetch_list=[loss],
                        mesh=make_mesh({"dp": 2, "fsdp": 4}),
                        levels=("jaxpr",))
    errs = [f for f in rep.by_check("jaxpr.constraint-placement")
            if f.severity == "error" and "accum_carry" in f.location]
    assert errs and "fsdp" in (errs[0].data.get("axes") or ())


def test_gpt_fsdp_plan_axes_phases_and_diff(monkeypatch):
    """What the plan recovers from the compiled step's replica groups:
    the weight gathers as in-loop ``all-gather@fsdp`` of the forward
    scan, the gradient reduction at the boundary over the gradient
    axes, no collective left without an axis; the clean spelling keeps
    ``zero3_grad_contract``; and ``comm_diff`` against
    ``PADDLE_TPU_FSDP=0`` names the gathers FSDP adds."""
    mesh = make_mesh({"dp": 2, "fsdp": 4})
    plan_on = _gpt_comm_plan(mesh)
    gathers = plan_on.select(kind="all-gather", axis="fsdp", in_loop=True)
    assert gathers and all(o.phase == "fwd-scan" for o in gathers)
    boundary = plan_on.select(kind="reduce", in_loop=False,
                              phase="boundary")
    assert any("dp" in (o.axes or ()) for o in boundary)
    assert all(o.axes and set(o.axes) <= {"dp", "fsdp"} for o in boundary)
    assert not plan_on.unattributed()
    assert pcontracts.zero3_grad_contract(mesh).check(plan_on) == []
    monkeypatch.setenv("PADDLE_TPU_FSDP", "0")
    diff = comm_diff(_gpt_comm_plan(mesh), plan_on, "FSDP=0", "FSDP=1")
    assert any(c["kind"] == "all-gather" and c["axes"] == "fsdp"
               and c["in_loop"] and c["count_b"] > c["count_a"]
               for c in diff["changed"]), diff["text"]


def test_in_loop_gradient_scatter_breaks_zero3_grad_contract(monkeypatch):
    """Rule 4's wrong spelling: the ZeRO-3 scatter composed onto the
    accumulation carry reduces every micro-batch's partial gradient
    INSIDE the scan; the contract's in-loop forbid fires on the
    compiled plan's TRAFFIC, whatever site made it."""
    from paddle_tpu.core import executor as ex

    monkeypatch.setattr(ex, "_accum_carry_spec", _fsdp_composed_carry)
    mesh = make_mesh({"dp": 2, "fsdp": 4})
    viol = pcontracts.zero3_grad_contract(mesh).check(_gpt_comm_plan(mesh))
    forbids = [v for v in viol if v["rule"]["rule"] == "forbid"
               and v["op_count"] > 0]
    assert forbids and all("in-loop" in o for o in forbids[0]["ops"])


@pytest.mark.parametrize("policy", ["selective", "compact", "full",
                                    "offload"])
def test_clean_gpt_comm_sweep(policy, monkeypatch):
    """Every spelling the switches reach (FSDP on/off x ZeRO on/off)
    under this remat policy lints to zero error-severity comm findings
    with the canned training contracts attached."""
    mesh = make_mesh({"dp": 2, "fsdp": 4})
    for fsdp in ("1", "0"):
        for zero in ("1", "0"):
            monkeypatch.setenv("PADDLE_TPU_FSDP", fsdp)
            monkeypatch.setenv("PADDLE_TPU_ZERO", zero)
            main, _startup, loss = _gpt_fsdp(policy)
            for c in pcontracts.training_step_contract(
                    mesh, accum=True, fsdp=fsdp == "1",
                    grad_rs=fsdp == "1"):
                attach_comm_contract(main, c)
            rep = analysis.lint(main, feed=_gpt_feed(), fetch_list=[loss],
                                mesh=mesh, levels=("jaxpr", "hlo"))
            bad = [f for f in rep if f.check in _COMM_CHECKS
                   and f.severity == "error"]
            assert not bad, (fsdp, zero, [repr(f) for f in bad])


# -- the schema-versioned --lint --json contract ----------------------------

def test_lint_json_schema_round_trip():
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.fc(x, 2, name="live")
        layers.fc(x, 3, name="dead")  # planted dead code
        blk = main.global_block()
        blk.create_var(name="orphan", shape=(3,), dtype="float32")
    rep = analysis.lint(main, fetch_list=[y], levels=("program",))
    assert len(rep) > 0
    obj = analysis.report_json(rep, levels=("program",))
    # stable top-level keys + per-finding keys (data always present)
    assert set(obj) == {"schema_version", "levels", "findings",
                        "counts", "ok"}
    assert obj["schema_version"] == analysis.LINT_JSON_SCHEMA_VERSION
    assert obj["levels"] == ["program"]
    keys = {"check", "severity", "level", "location", "message",
            "hint", "data"}
    assert all(set(f) == keys for f in obj["findings"])
    # sorted: severity rank desc, then check id / location / message
    ranks = [("error", "warning", "info").index(f["severity"])
             for f in obj["findings"]]
    assert ranks == sorted(ranks)
    for a, b in zip(obj["findings"], obj["findings"][1:]):
        if a["severity"] == b["severity"]:
            assert (a["check"], a["location"], a["message"]) <= (
                b["check"], b["location"], b["message"])
    # the round trip: serialize -> parse -> rebuild -> identical JSON
    wire = json.dumps(obj)
    rebuilt = analysis.report_from_json(json.loads(wire))
    assert analysis.report_json(rebuilt, levels=("program",)) == obj
    # newer schema versions refuse instead of misreading
    with pytest.raises(ValueError):
        analysis.report_from_json(
            {"schema_version": analysis.LINT_JSON_SCHEMA_VERSION + 1,
             "findings": []})


@pytest.mark.slow
def test_lint_json_cli_contract():
    """``python -m paddle_tpu --lint <config> --json`` emits exactly one
    JSON object honoring the schema contract (subprocess: the CLI is
    what CI consumers actually parse)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = os.path.join(repo, "examples", "train_mnist.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu", "--lint", cfg, "--json",
         "--levels", "program"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    obj = json.loads(proc.stdout)
    assert obj["schema_version"] == analysis.LINT_JSON_SCHEMA_VERSION
    assert obj["ok"] is True and obj["levels"] == ["program"]
    rebuilt = analysis.report_from_json(obj)
    assert analysis.report_json(
        rebuilt, levels=("program",)) == obj
