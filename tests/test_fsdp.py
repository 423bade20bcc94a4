"""FSDP / ZeRO-3 parameter sharding inside the scan-remat body
(docs/parallel.md "FSDP"): spec composition rules, structural tagging,
the sharding_report accounting, the in-loop-gather comm contract, the
recorded replication fallbacks, and bit-exactness vs the replicated
spelling on dp x fsdp (x tp) meshes — including an indivisible-shape
model that must take the fallback and still train bit-exact."""

import os

import numpy as np
import jax
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.models import transformer
from paddle_tpu.parallel import api as papi
from paddle_tpu.parallel.mesh import make_mesh

VOCAB, HEADS, SEQ = 64, 2, 16


def _mesh(axes):
    return make_mesh(axes, devices=jax.devices()[:8])


def _m_first_tagged(program):
    return sorted(n for n, v in program.global_block().vars.items()
                  if getattr(v, "fsdp_param", False))[0]


def _build_gpt(n_layer=3, d_model=64, accum=1, memopt=True,
               dropout=0.0, vocab=VOCAB):
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = 7
    with pt.program_guard(main, startup):
        outs = transformer.build(
            vocab_size=vocab, n_layer=n_layer, n_head=HEADS,
            d_model=d_model, max_len=SEQ, dropout_rate=dropout,
            dtype="float32", learning_rate=1e-2)
    if memopt:
        pt.memory_optimize(main, policy="selective")
    if accum > 1:
        pt.gradient_accumulation(main, accum)
    return main, startup, outs


def _gpt_feed(batch=16, seed=5, vocab=VOCAB):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, vocab, (batch, SEQ)).astype(np.int64)
    lbls = np.roll(toks, -1, axis=1)
    lbls[:, -1] = -1
    return {"tokens": toks, "labels": lbls}


def _train(mesh, fsdp_env, build_kwargs=None, steps=3, batch=16,
           dp_axis="dp", tp=False, grad_fetch=True, rs=None):
    """Train on ``mesh`` with PADDLE_TPU_FSDP=``fsdp_env`` (and, when
    ``rs`` is given, PADDLE_TPU_ZERO3_RS=``rs``); returns
    (losses, grads, params, cost, accum_plan, remat_plan, report, scope,
    main, tagged, comm_plan)."""
    os.environ["PADDLE_TPU_FSDP"] = fsdp_env
    if rs is not None:
        os.environ["PADDLE_TPU_ZERO3_RS"] = rs
    try:
        main, startup, outs = _build_gpt(**(build_kwargs or {}))
        if tp:
            for prog in (main, startup):
                papi.shard_parameters_by_rule(prog, transformer.tp_rules())
        if dp_axis:
            papi.data_parallel(main, dp_axis, programs=(startup,))
        tagged = papi.shard_fsdp(main, programs=(startup,))
        scope = pt.Scope()
        pt.core.scope._scope_stack.append(scope)
        try:
            exe = pt.Executor(mesh=mesh)
            exe.run(startup, scope=scope)
            fetch = [outs["avg_cost"]]
            if grad_fetch and tagged:
                fetch += [tagged[0] + "@GRAD", "lm_head.w@GRAD"]
            feed = _gpt_feed(batch=batch,
                             vocab=(build_kwargs or {}).get("vocab",
                                                            VOCAB))
            losses, grads = [], []
            for _ in range(steps):
                r = exe.run(main, feed=feed, fetch_list=fetch,
                            scope=scope)
                losses.append(np.asarray(r[0]))
                grads.append([np.asarray(g) for g in r[1:]])
            params = {v.name: np.asarray(scope.get(v.name))
                      for v in main.all_parameters()}
            return (losses, grads, params, dict(exe.last_step_cost),
                    exe.last_accum_plan,
                    list(getattr(exe, "last_remat_plan", []) or []),
                    papi.sharding_report(main, mesh), scope, main,
                    tagged, exe.last_comm_plan)
        finally:
            pt.core.scope._scope_stack.pop()
    finally:
        os.environ.pop("PADDLE_TPU_FSDP", None)
        if rs is not None:
            os.environ.pop("PADDLE_TPU_ZERO3_RS", None)


@pytest.fixture(scope="module")
def trained():
    """``_train`` by its arguments, each distinct run made once a
    module: the cases on one mesh compare pairs of spellings that have a
    run in common (the default spelling, FSDP on and the gradients
    reduce-scattered), so it is compiled and trained once for both."""
    runs = {}

    def get(axes, fsdp_env, **kw):
        key = repr((sorted(axes.items()), fsdp_env, sorted(kw.items())))
        if key not in runs:
            runs[key] = _train(_mesh(axes), fsdp_env, **kw)
        return runs[key]

    return get


DP_FSDP = ({"dp": 2, "fsdp": 4}, dict(build_kwargs={"accum": 4}, steps=3))
DP_FSDP_TP = ({"dp": 2, "fsdp": 2, "tp": 2},
              dict(build_kwargs={"accum": 4}, steps=3, tp=True))


# -- fsdp_spec_for rules ----------------------------------------------------
def test_fsdp_spec_for_rules(monkeypatch):
    """Leading-axis composition with tp, divisibility fallbacks with
    recorded reasons, the kill switch, and untagged vars."""
    main, _startup, _ = _build_gpt(memopt=False)
    mesh = _mesh({"dp": 2, "fsdp": 2, "tp": 2})
    block = main.global_block()
    w = block.vars["block0_ffn1.w"]          # [64, 256]
    assert papi.fsdp_spec_for(w, mesh, block) is None  # not tagged
    w.fsdp_param = True
    assert papi.fsdp_spec_for(w, mesh, block) == P("fsdp", None)

    # composes ON TOP of a tp spec: free leading axis gains fsdp...
    w.partition_spec = P(None, "tp")
    assert papi.fsdp_spec_for(w, mesh, block) == P("fsdp", "tp")
    # ...and a tp-sharded leading axis composes into a tuple entry
    w.partition_spec = P("tp", None)
    assert papi.fsdp_spec_for(w, mesh, block) == P(("tp", "fsdp"), None)
    # _spec_for resolves the composition ahead of the explicit spec
    assert papi._spec_for(w, mesh, block) == P(("tp", "fsdp"), None)

    # indivisible leading dim: fallback recorded with the reason
    odd = block.create_var(name="odd.w", shape=[31, 8],
                           dtype="float32", persistable=True)
    odd.fsdp_param = True
    reg = pt.observability.get_registry()
    before = reg.value("parallel.shard_fallbacks") or 0
    assert papi.fsdp_spec_for(odd, mesh, block) is None
    assert papi._spec_for(odd, mesh, block) == P()
    recs = block._shard_fallbacks
    assert ("odd.w", "fsdp") in recs
    assert "31" in recs[("odd.w", "fsdp")]
    assert (reg.value("parallel.shard_fallbacks") or 0) == before + 1
    # recording is idempotent per (var, axis)
    papi.fsdp_spec_for(odd, mesh, block)
    assert (reg.value("parallel.shard_fallbacks") or 0) == before + 1

    # kill switch and meshes without an fsdp axis resolve to None
    monkeypatch.setenv("PADDLE_TPU_FSDP", "0")
    assert papi.fsdp_spec_for(w, mesh, block) is None
    monkeypatch.delenv("PADDLE_TPU_FSDP")
    assert papi.fsdp_spec_for(w, _mesh({"dp": 8}), block) is None
    assert papi.fsdp_spec_for(w, None, block) is None


def test_zero_spec_inherits_fsdp_composition():
    """An FSDP weight's optimizer accumulators shard along with it (the
    ZeRO-3 state discipline), and the skipped-dp fallback of an
    indivisible accumulator is recorded."""
    main, _startup, _ = _build_gpt(memopt=False)
    mesh = _mesh({"dp": 2, "fsdp": 4})
    block = main.global_block()
    mom = next(n for n in sorted(block.vars) if n.endswith("_moment1")
               and "ffn1.w" in n)
    var = block.vars[mom]
    pvar = block._find_var(var.zero_param)
    pvar.fsdp_param = True
    spec = papi.zero_spec_for(var, mesh, block)
    assert spec == P("fsdp", None)  # inherited; leading axis taken
    # fsdp off -> plain ZeRO-1 dp shard on the free leading axis
    os.environ["PADDLE_TPU_FSDP"] = "0"
    try:
        assert papi.zero_spec_for(var, mesh, block) == P("dp", None)
    finally:
        os.environ.pop("PADDLE_TPU_FSDP", None)
    # indivisible accumulator: dp shard skipped, reason recorded
    odd = block.create_var(name="odd_m", shape=[7, 4], dtype="float32",
                           persistable=True)
    odd.zero_param = var.zero_param
    pvar.fsdp_param = False
    assert papi.zero_spec_for(odd, mesh, block) is None
    assert ("odd_m", "dp") in block._shard_fallbacks


def test_shard_fsdp_tags_per_layer_params():
    """The structural matcher tags the per-layer (scan-stacked) weights
    PLUS the prologue/epilogue 2-D tables (embedding table, positional
    table, LM head — the fully-sharded-everything discipline, their
    gathers live outside the scan) — on the startup program too."""
    main, startup, _ = _build_gpt(n_layer=3)
    tagged = papi.shard_fsdp(main, programs=(startup,))
    # 16 per-layer params per period + tok_emb.w/pos_emb.w.w/lm_head.w
    assert len(tagged) == 3 * 16 + 3
    # the period tiling may rotate (an LN pairs with the next block's
    # attention), so ln_f can legitimately ride the last scan
    # iteration — but embeddings and the LM head never repeat
    assert sum(t.startswith("block") for t in tagged) >= 3 * 14
    for name in ("tok_emb.w", "pos_emb.w.w", "lm_head.w"):
        assert name in tagged
        var = main.global_block()._find_var(name)
        assert var is not None and var.fsdp_param
        # prologue tables carry the (fsdp, tp) composition so a free
        # tp axis joins the leading-dim shard on tp meshes
        assert var.fsdp_axes == ("fsdp", "tp")
    svar = startup.global_block()._find_var(tagged[0])
    assert svar is not None and svar.fsdp_param
    # replicate() opts a var back out
    var = main.global_block().vars[tagged[0]]
    papi.replicate(var)
    assert not var.fsdp_param


def test_shard_fsdp_without_remat_segments():
    """No memory_optimize marks: shard_fsdp falls back to the
    detect_repeated_run tiling and still finds the layer weights (and
    the prologue tables, which never depended on the segments)."""
    main, startup, _ = _build_gpt(n_layer=2, memopt=False)
    tagged = papi.shard_fsdp(main, programs=(startup,))
    assert len(tagged) == 2 * 16 + 3
    assert all(t.startswith("block") for t in tagged
               if t not in ("tok_emb.w", "pos_emb.w.w", "lm_head.w"))


def test_shard_fsdp_empty_is_recorded(monkeypatch):
    """A no-op shard_fsdp (no repeated structure, or the scan engine
    killed) records a program-level fallback instead of returning []
    silently — the 'OOM waiting to happen' discipline."""
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[16])
        y = layers.data("y", shape=[1])
        pred = layers.fc(input=layers.fc(input=x, size=8, act="tanh"),
                         size=1)
        loss = layers.mean(layers.square(pred - y))
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    assert papi.shard_fsdp(main) == []
    recs = main.global_block()._shard_fallbacks
    assert ("<program>", "fsdp") in recs
    assert "repeated" in recs[("<program>", "fsdp")]

    # scan engine killed: the segments path also records, via the SAME
    # group derivation the executor runs (_scan_groups_for)
    gpt, _startup, _ = _build_gpt(n_layer=2)
    monkeypatch.setenv("PADDLE_TPU_SCAN_REMAT", "0")
    assert papi.shard_fsdp(gpt) == []
    recs = gpt.global_block()._shard_fallbacks
    assert ("<program>", "fsdp") in recs
    monkeypatch.delenv("PADDLE_TPU_SCAN_REMAT")
    assert papi.shard_fsdp(gpt)  # engine back on: tags apply


def test_sharding_report_accounting():
    """params/opt_state/grads sections with per-device bytes under the
    resolved specs; optimizer_state_report stays the opt_state view."""
    main, startup, _ = _build_gpt(n_layer=3)
    mesh = _mesh({"dp": 2, "fsdp": 4})
    papi.shard_fsdp(main, programs=(startup,))
    rep = papi.sharding_report(main, mesh)
    p = rep["params"]
    assert p["sharded_vars"] == 3 * 16 + 3
    assert p["per_device_bytes"] * 2 <= p["total_bytes"]
    assert p["replicated_per_device_bytes"] == p["total_bytes"]
    # grads account at the boundary pin's spec: under the default
    # reduce-scatter spelling (docs/parallel.md rule 4) each chip holds
    # only its shard of every fsdp-tagged gradient...
    assert rep["grads"]["per_device_bytes"] * 2 <= (
        rep["grads"]["total_bytes"])
    # ...and the kill switch restores the replicated-grad accounting
    os.environ["PADDLE_TPU_ZERO3_RS"] = "0"
    try:
        rep_rs0 = papi.sharding_report(main, mesh)
        assert rep_rs0["grads"]["per_device_bytes"] == (
            rep_rs0["grads"]["total_bytes"])
    finally:
        os.environ.pop("PADDLE_TPU_ZERO3_RS", None)
    assert rep["total_bytes"] == (
        p["total_bytes"] + rep["opt_state"]["total_bytes"]
        + rep["grads"]["total_bytes"])
    legacy = papi.optimizer_state_report(main, mesh)
    assert legacy["total_bytes"] == rep["opt_state"]["total_bytes"]
    assert legacy["per_device_bytes"] == (
        rep["opt_state"]["per_device_bytes"])
    # meshless: everything replicated
    rep1 = papi.sharding_report(main, None)
    assert rep1["per_device_bytes"] == rep1["total_bytes"]


# -- the tentpole: in-scan gathers, bit-exactness ---------------------------
def test_fsdp_bitexact_dp_fsdp_mesh(trained):
    """dp=2 x fsdp=4, scan-remat + accum=4 local mode: stacked layer
    weights sharded 4-way at rest, all-gathered INSIDE the scan loop,
    zero reduce-class collectives in loop bodies, and loss/grads/params
    bit-exact vs the PADDLE_TPU_FSDP=0 replicated spelling."""
    axes, kw = DP_FSDP
    l1, g1, p1, c1, plan1, remat1, rep1, scope1, _m, tagged, cp1 = (
        trained(axes, "1", rs="1", **kw))
    l0, g0, p0, c0, _plan0, remat0, rep0, _s0, _m0, _t0, _cp0 = (
        trained(axes, "0", **kw))

    assert [g for g in remat1 if g.get("fsdp")], remat1
    assert all(not g.get("fsdp") for g in remat0), remat0
    assert plan1["mode"] == "local"
    assert c1["reduce_ops_in_loop"] == 0
    gathers_in = c1["collectives_in_loop"] - c1["reduce_ops_in_loop"]
    assert gathers_in > 0
    # boundary discipline under the default reduce-scatter spelling
    # (docs/parallel.md rule 4): every reduce stays at the boundary
    # (above), and the tagged grads' full-volume all-reduce@dp is a
    # boundary reduce-scatter over fsdp.  How MANY instructions carry
    # them is the compiler's (the installed one combines the 51 tagged
    # grads' scatters into one), so no count is held; the step's
    # contracts and byte bounds are
    # test_fsdp_step_holds_its_comm_contracts_and_byte_bounds'
    assert cp1.select(kind="reduce-scatter", axis="fsdp", in_loop=False)

    assert rep1["params"]["per_device_bytes"] * 2 <= (
        rep1["params"]["total_bytes"])
    assert rep0["params"]["per_device_bytes"] == (
        rep0["params"]["total_bytes"])
    wsh = str(scope1.get(tagged[0]).sharding.spec)
    assert "fsdp" in wsh, wsh

    for a, b in zip(l1, l0):
        assert np.array_equal(a, b)
    for ga, gb in zip(g1, g0):
        for a, b in zip(ga, gb):
            assert np.array_equal(a, b)
    for k in p1:
        assert np.array_equal(p1[k], p0[k]), k
    reg = pt.observability.get_registry()
    assert (reg.value("executor.fsdp_groups") or 0) > 0


def test_fsdp_bitexact_dp_fsdp_tp_mesh(trained):
    """dp=2 x fsdp=2 x tp=2: the fsdp shard composes with the tp rules
    (qkv stay column-sharded, ffn2 row-shards over (tp, fsdp)) and the
    two spellings train alike to float32's last digits."""
    axes, kw = DP_FSDP_TP
    mesh = _mesh(axes)
    l1, g1, p1, c1, _plan1, remat1, rep1, _s1, main, tagged, _cp1 = (
        trained(axes, "1", rs="1", **kw))
    l0, g0, p0, _c0, _plan0, _r0, rep0, _s0, _m0, _t0, _cp0 = (
        trained(axes, "0", **kw))
    assert [g for g in remat1 if g.get("fsdp")], remat1
    block = main.global_block()
    ffn2 = block.vars["block0_ffn2.w"]
    assert papi._spec_for(ffn2, mesh, block) == P(("tp", "fsdp"), None)
    assert rep1["params"]["per_device_bytes"] < (
        rep0["params"]["per_device_bytes"])
    # under tp composition the row-sharded matmuls all-reduce over tp
    # inside the layer; fsdp changes the at-rest LAYOUT of their weight
    # operands and XLA's resulting fusion reassociates a handful of
    # gradient elements at the ulp level (~1e-8 abs) — which Adam's
    # rsqrt then amplifies without bound on near-zero-gradient elements
    # (the attention key biases have an IDENTICALLY-zero true gradient:
    # softmax shift invariance).  So tp x fsdp is "close, not
    # bit-identical, like any resharding" — the documented dp=N-vs-dp=1
    # precedent (docs/parallel.md) — while the pure dp x fsdp mesh
    # above is gated fully bit-exact.  The first step's loss already
    # differs by one float32 step (4.568965 against 4.5689654: the
    # gathered forward reduces in another order), so the whole
    # trajectory is held to a relative 2e-6.
    for a, b in zip(l1, l0):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=0)
    for ga, gb in zip(g1, g0):
        for a, b in zip(ga, gb):
            # atol admits the LM head's near-zero elements: the head is
            # now itself fsdp-sharded (fully-sharded prologue), so its
            # gradient picks up the same ulp-level reassociation
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=5e-7)
    for k in p1:
        if k.endswith("_att_k.b"):
            continue  # zero-true-gradient: trajectory is sign-of-noise
        np.testing.assert_allclose(p1[k], p0[k], rtol=1e-2, atol=1e-4,
                                   err_msg=k)


def test_fsdp_indivisible_fallback_bitexact():
    """fsdp=8 with d_model=36: the [36, .] weights cannot shard 8-way
    and must take the recorded replication fallback (the [144, 36]
    ffn2 still shards) — and training stays bit-exact vs replicated."""
    mesh = _mesh({"fsdp": 8})
    kw = dict(build_kwargs={"n_layer": 2, "d_model": 36}, steps=2,
              dp_axis=None, batch=8)
    l1, g1, p1, _c1, _plan1, remat1, rep1, _s1, main, tagged, _cp1 = (
        _train(mesh, "1", **kw))
    l0, g0, p0, *_ = _train(mesh, "0", **kw)
    block = main.global_block()
    recs = getattr(block, "_shard_fallbacks", {})
    assert any(axis == "fsdp" for (_n, axis) in recs), recs
    # the divisible ffn2 [144, 36] sharded; the [36, .] ones fell back
    assert papi.fsdp_spec_for(
        block.vars["block0_ffn2.w"], mesh, block) == P("fsdp", None)
    assert papi.fsdp_spec_for(
        block.vars["block0_ffn1.w"], mesh, block) is None
    assert rep1["params"]["per_device_bytes"] < (
        rep1["params"]["total_bytes"])
    for a, b in zip(l1, l0):
        assert np.array_equal(a, b)
    for ga, gb in zip(g1, g0):
        for a, b in zip(ga, gb):
            assert np.array_equal(a, b)
    for k in p1:
        assert np.array_equal(p1[k], p0[k]), k

    # the analysis check surfaces the fallbacks as info findings
    from paddle_tpu.analysis import lint

    report = lint(main, levels=("program",),
                  checks=("program.shard-fallback",))
    found = report.by_check("program.shard-fallback")
    assert found and all(f.severity == "info" for f in found)
    assert any("fsdp" in f.message for f in found)


# -- rule 4: the reduce-scatter gradient spelling ---------------------------
@pytest.mark.parametrize("case", ["dp_fsdp", "dp_fsdp_tp",
                                  "fsdp_only_indivisible"])
def test_zero3_rs_bitexact(case, trained):
    """The true-ZeRO-3 gradient spelling vs its PADDLE_TPU_ZERO3_RS=0
    replicated-grad reference, bit-exact across mesh geometries
    (docs/parallel.md rule 4):

    * dp x fsdp — one boundary reduce-scatter@fsdp per tagged grad,
      zero in-loop reduces (``zero3_grad_contract``), grad bytes/device
      below replicated;
    * dp x fsdp x tp — the scatter composes with the tp rules;
    * fsdp-only with an indivisible embedding (vocab=61, d_model=36) —
      no dp axis means no boundary reduce to scatter, so the spelling
      is INERT by design (a bare scatter constraint measurably drifts
      under ``reduce_each`` accumulation), the indivisible tables take
      the recorded replication fallback, and both spellings stay
      bit-exact trivially.
    """
    if case == "dp_fsdp":
        axes, kw = DP_FSDP
    elif case == "dp_fsdp_tp":
        axes, kw = DP_FSDP_TP
    else:
        axes = {"fsdp": 8}
        kw = dict(build_kwargs={"n_layer": 2, "d_model": 36,
                                "vocab": 61, "accum": 4},
                  steps=2, dp_axis=None, batch=8)
    mesh = _mesh(axes)
    l1, g1, p1, _c1, _pl1, _r1, rep1, _s1, main, tagged, cp1 = trained(
        axes, "1", rs="1", **kw)
    l0, g0, p0, _c0, _pl0, _r0, rep0, _s0, _m0, _t0, cp0 = trained(
        axes, "1", rs="0", **kw)

    # the kill switch restores the replicated-grad spelling exactly
    assert not cp0.select(kind="reduce-scatter")
    if case == "dp_fsdp_tp":
        # tp grads are naturally tp-sharded either way; the scatter
        # still shrinks the per-device gradient residency further
        assert rep1["grads"]["per_device_bytes"] < (
            rep0["grads"]["per_device_bytes"])
    else:
        assert rep0["grads"]["per_device_bytes"] == (
            rep0["grads"]["total_bytes"])

    if case == "fsdp_only_indivisible":
        # no dp axis: grad_rs_spec_for resolves None, both plans agree
        assert not cp1.select(kind="reduce-scatter")
        block = main.global_block()
        assert papi.grad_rs_spec_for(
            block.vars["block0_ffn2.w"], mesh, block) is None
        assert rep1["grads"]["per_device_bytes"] == (
            rep1["grads"]["total_bytes"])
        recs = getattr(block, "_shard_fallbacks", {})
        assert ("tok_emb.w", "fsdp") in recs
        assert ("lm_head.w", "fsdp") in recs
        from paddle_tpu.analysis import lint

        report = lint(main, levels=("program",),
                      checks=("program.shard-fallback",))
        found = report.by_check("program.shard-fallback")
        # (the finding list caps at MAX_FINDINGS and this model falls
        # back a lot, so assert the check fires rather than hunting the
        # prologue entries — recs above already names them)
        assert found and all(f.severity == "info" for f in found)
        assert any(f.data.get("axis") == "fsdp" for f in found)
    else:
        from paddle_tpu.parallel.contracts import zero3_grad_contract

        viol = zero3_grad_contract(mesh).check(cp1)
        assert not viol, viol
        rs_ops = cp1.select(kind="reduce-scatter", axis="fsdp",
                            in_loop=False)
        assert rs_ops
        # every boundary scatter carries the pt_pin[grad_rs_boundary:
        # <name>] provenance of a tagged grad whose spec resolved (the
        # installed compiler combines the grads' scatters into one
        # instruction that keeps ONE name, ``tok_emb.w``, so the plan is
        # held to naming nothing else, not to naming every one)
        block = main.global_block()
        sites = {s for op in rs_ops for s in op.provenance_names()
                 if s.startswith("grad_rs_boundary:")}
        expected = {f"grad_rs_boundary:{n}" for n in tagged
                    if papi.grad_rs_spec_for(
                        block._find_var(n), mesh, block) is not None}
        assert sites and sites <= expected
        assert rep1["grads"]["per_device_bytes"] < (
            rep1["grads"]["total_bytes"])

    for a, b in zip(l1, l0):
        assert np.array_equal(a, b)
    for ga, gb in zip(g1, g0):
        for a, b in zip(ga, gb):
            assert np.array_equal(a, b)
    for k in p1:
        assert np.array_equal(p1[k], p0[k]), k


def test_fsdp_step_holds_its_comm_contracts_and_byte_bounds(trained):
    """What the compiled dp=2 x fsdp=4 step's PLAN and placement say,
    apart from the bits (the bit-exactness tests above): the canned
    contracts (in-loop fsdp weight gathers, no in-loop reduce, one
    boundary gradient reduction) hold; the embedding, positional table
    and LM head are sharded with their optimizer state (bytes a device
    at most half the replicated figure); ``PADDLE_TPU_ZERO3_RS=0``
    leaves the gradients replicated with no reduce-scatter; and
    ``comm_diff`` names what the scatter moved."""
    from paddle_tpu.analysis.comm import comm_diff
    from paddle_tpu.parallel.contracts import (
        fsdp_scan_contract, one_boundary_reduce_contract)

    axes, kw = DP_FSDP
    mesh = _mesh(axes)
    *_, plan1, _remat1, rep1, _s1, _m1, _t1, cp1 = trained(
        axes, "1", rs="1", **kw)
    *_, rep0, _s0, _m0, _t0, cp0 = trained(axes, "1", rs="0", **kw)

    assert plan1["mode"] == "local"
    assert fsdp_scan_contract(mesh).check(cp1) == []
    assert one_boundary_reduce_contract(mesh).check(cp1) == []
    assert cp1.select(kind="all-gather", axis="fsdp", in_loop=True)

    prologue = ("tok_emb.w", "pos_emb.w.w", "lm_head.w")
    pvars, ovars = rep1["params"]["vars"], rep1["opt_state"]["vars"]
    held = [pvars[n] for n in prologue] + [
        v for n in prologue for o, v in ovars.items() if n in o]
    assert len(held) > len(prologue)  # the tables AND their moments
    assert 2 * sum(v["per_device_bytes"] for v in held) <= sum(
        v["bytes"] for v in held)

    assert not cp0.select(kind="reduce-scatter")
    assert rep0["grads"]["per_device_bytes"] == rep0["grads"]["total_bytes"]
    assert rep1["grads"]["per_device_bytes"] < rep1["grads"]["total_bytes"]
    d = comm_diff(cp0, cp1, name_a="replicated", name_b="zero3-rs")
    assert "reduce-scatter" in {c["kind"] for c in d["changed"]}
    ar_dp = [c for c in d["changed"] if c["kind"] == "all-reduce"
             and c["axes"] == "dp" and c["phase"] == "boundary"]
    assert ar_dp and ar_dp[0]["bytes_b"] < ar_dp[0]["bytes_a"], d["text"]


def test_grad_rs_spec_for_rules(monkeypatch):
    """Rule-4 spec resolution: needs the kill switch on, a mesh with
    both dp>1 and fsdp axes, and an fsdp-tagged divisible shape."""
    main, _startup, _ = _build_gpt(memopt=False)
    block = main.global_block()
    w = block.vars["block0_ffn1.w"]
    mesh = _mesh({"dp": 2, "fsdp": 4})
    assert papi.grad_rs_spec_for(w, mesh, block) is None  # untagged
    w.fsdp_param = True
    assert papi.grad_rs_spec_for(w, mesh, block) == P("fsdp", None)
    # the grad spec IS the parameter's composed fsdp spec
    assert papi.grad_rs_spec_for(w, mesh, block) == (
        papi.fsdp_spec_for(w, mesh, block))
    # kill switch
    monkeypatch.setenv("PADDLE_TPU_ZERO3_RS", "0")
    assert papi.grad_rs_spec_for(w, mesh, block) is None
    monkeypatch.delenv("PADDLE_TPU_ZERO3_RS")
    # a reduce-scatter needs a boundary reduce: no dp axis (or size-1
    # dp) resolves None even though the param itself shards
    assert papi.grad_rs_spec_for(w, _mesh({"fsdp": 8}), block) is None
    assert papi.fsdp_spec_for(w, _mesh({"fsdp": 8}), block) is not None
    # FSDP off entirely -> None (rides fsdp_spec_for's own gates)
    monkeypatch.setenv("PADDLE_TPU_FSDP", "0")
    assert papi.grad_rs_spec_for(w, mesh, block) is None


def test_fsdp_kill_switch_and_auto_policy(monkeypatch):
    """PADDLE_TPU_FSDP=0 and the tuner's program._fsdp=False both keep
    the scan body gather-free; schedule_candidates grows the fsdp
    dimension only when asked."""
    from paddle_tpu.tune import schedule_candidates

    base = schedule_candidates(SEQ, 16, HEADS)
    both = schedule_candidates(SEQ, 16, HEADS, fsdp_opts=(False, True))
    assert len(both) == 2 * len(base)
    assert "fsdp" not in base[0]
    assert {c["fsdp"] for c in both} == {False, True}

    mesh = _mesh({"dp": 2, "fsdp": 4})
    main, startup, outs = _build_gpt(n_layer=2)
    papi.data_parallel(main, "dp", programs=(startup,))
    main._fsdp = False  # the tuned gather-vs-replicate decision —
    # set (by memory_optimize(policy="auto")) BEFORE shard_fsdp, which
    # propagates it to the startup program so both resolve replicated
    papi.shard_fsdp(main, programs=(startup,))
    assert startup._fsdp is False
    # the opt-out reaches spec RESOLUTION too — a replicate schedule
    # measures truly replicated params, not a sharded-at-rest hybrid
    rep = papi.sharding_report(main, mesh)
    assert rep["params"]["per_device_bytes"] == (
        rep["params"]["total_bytes"])
    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe = pt.Executor(mesh=mesh)
        exe.run(startup, scope=scope)
        exe.run(main, feed=_gpt_feed(), fetch_list=[outs["avg_cost"]],
                scope=scope)
        assert all(not g.get("fsdp") for g in exe.last_remat_plan)
        # (no reduce_ops_in_loop check here: at accum=1 a dp mesh has
        # per-layer dp reductions in the backward scan with or without
        # fsdp — the local-accum configs are where that gate applies)
        w = scope.get(_m_first_tagged(main))
        assert "fsdp" not in str(w.sharding.spec)
    finally:
        pt.core.scope._scope_stack.pop()


def test_memory_optimize_auto_applies_tuned_fsdp(monkeypatch):
    """policy='auto' threads a tuned schedule's fsdp decision onto the
    program for the executor gate."""
    import paddle_tpu.memory_optimization_transpiler as mot

    main, _startup, _ = _build_gpt(n_layer=2, memopt=False)
    monkeypatch.setattr(
        "paddle_tpu.tune.program_schedule_config",
        lambda program: {"policy": "selective", "fsdp": False})
    pt.memory_optimize(main, policy="auto")
    assert main._fsdp is False
    assert main._remat_segments


def test_tune_search_persists_fsdp_dimension(tmp_path, monkeypatch):
    """The gather-vs-replicate dimension round-trips through the
    measured search: tune_gpt_step(fsdp_opts=...) candidates carry the
    key, _measure_candidate applies it as program._fsdp, the winner
    persists it, and memory_optimize(policy='auto') hands it back."""
    from paddle_tpu import tune

    monkeypatch.setenv("PADDLE_TPU_TUNE_CACHE",
                       str(tmp_path / "tuned.json"))
    monkeypatch.setenv("PADDLE_TPU_TUNE", "search")
    tune.reset_cache()
    try:
        rep = tune.tune_gpt_step(
            seq_len=16, n_layer=2, d_model=32, n_head=2, vocab=61,
            batch=4, dtype="float32", steps=1, warmup=0, repeats=1,
            block_caps=(16,), diag_ws=(16,), policies=("none",),
            accums=(1,), fsdp_opts=(False,), max_measure=2)
        assert rep["source"] == "search", rep
        assert rep["entry"]["config"]["fsdp"] is False

        pt.core.unique_name.reset()
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            transformer.build(vocab_size=61, n_layer=2, n_head=2,
                              d_model=32, max_len=16, dropout_rate=0.0,
                              dtype="float32", learning_rate=1e-2)
        pt.memory_optimize(main, policy="auto")
        assert main._fsdp is False
    finally:
        tune.reset_cache()


def test_tune_search_persists_grad_rs_dimension(tmp_path, monkeypatch):
    """The measured grad_rs dimension (boundary reduce-scatter vs
    replicated grads — a real volume-vs-gather tradeoff on fsdp meshes)
    crosses only with fsdp=True candidates, rides _measure_candidate
    through the PADDLE_TPU_ZERO3_RS pin, and the winner persists the
    key in the tune cache."""
    from paddle_tpu import tune
    from paddle_tpu.tune import schedule_candidates

    # grad_rs never crosses with replicate-schedule candidates
    cands = schedule_candidates(SEQ, 16, HEADS, fsdp_opts=(False, True),
                                grad_rs_opts=(False, True))
    assert all("grad_rs" not in c for c in cands if not c["fsdp"])
    assert ({c["grad_rs"] for c in cands if c["fsdp"]}
            == {False, True})

    monkeypatch.setenv("PADDLE_TPU_TUNE_CACHE",
                       str(tmp_path / "tuned.json"))
    monkeypatch.setenv("PADDLE_TPU_TUNE", "search")
    tune.reset_cache()
    try:
        rep = tune.tune_gpt_step(
            seq_len=16, n_layer=2, d_model=32, n_head=2, vocab=61,
            batch=4, dtype="float32", steps=1, warmup=0, repeats=1,
            block_caps=(16,), diag_ws=(16,), policies=("none",),
            accums=(1,), fsdp_opts=(True,), grad_rs_opts=(False,),
            max_measure=2)
        assert rep["source"] == "search", rep
        assert rep["entry"]["config"]["fsdp"] is True
        assert rep["entry"]["config"]["grad_rs"] is False
    finally:
        tune.reset_cache()
