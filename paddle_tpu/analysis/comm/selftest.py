"""``python -m paddle_tpu --sharding-selftest`` — the sharding &
communication contract analyzer's CI gate (tools/tier1.sh).

On the 8-device virtual CPU mesh (dp=2 x fsdp=4):

* **Planted contract violations** — the three wrong spellings of
  docs/parallel.md's constraint-placement rules, each with a measured
  historical failure mode, each caught with the right attribution:

  1. a SYMMETRIC fsdp pin (a plain ``with_sharding_constraint`` in
     place of the forward-only ``_fsdp_fwd_pin`` custom-vjp) —
     ``jaxpr.constraint-placement`` errors on the unblessed in-scan
     constraint over the fsdp axis;
  2. an FSDP-COMPOSED accumulation grad carry (the carry pinned
     ``P('dp', 'fsdp')`` instead of plain ``P('dp')``) — the same
     check errors on the marked ``accum_carry`` site straying off its
     plain-dp contract;
  3. a FORBIDDEN ACTIVATION RESHARD (``shard_activation`` feature-
     sharding an attention intermediate) — the CommPlan attributes the
     resulting gather/reduce traffic to the variable via its
     ``pt_shard[var]`` provenance, ``hlo.accidental-reshard`` warns,
     and a ``CommContract.forbid_reshard`` upgrades it to an
     ``hlo.comm-contract`` error naming the var;

  4. an IN-LOOP reduce-scatter (the ZeRO-3 gradient scatter mis-spelled
     onto the accumulation carry, scattering every microbatch's partial
     gradient inside the scan) — ``zero3_grad_contract``'s in-loop
     forbid fires on the compiled plan with the offending ops
     attributed as in-loop reduce traffic over ``fsdp``, while the
     SAME contract holds on the clean spelling's plan.

* **Plan fundamentals** — mesh-axis recovery from replica groups
  (in-loop ``all-gather@fsdp`` weight gathers, boundary reduce over
  ``dp``, zero axis-unattributed collectives) and ``comm_diff``
  explaining exactly which op moved between the FSDP and replicated
  spellings.

* **The clean sweep** — every ``memory_optimize`` policy x
  {FSDP on/off} x {ZeRO on/off} on the same mesh lints to ZERO
  error-severity comm findings with the training contracts attached.
"""

import os
import sys

# the comm-analysis check family whose error-severity findings the
# clean sweep must be free of
COMM_CHECKS = (
    "hlo.comm-contract", "hlo.accidental-reshard",
    "hlo.axis-attribution", "hlo.inloop-collective",
    "jaxpr.constraint-placement", "program.spec-conflict",
)

POLICIES = ("selective", "compact", "full", "offload")


def run_selftest():
    n = 8
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    import jax

    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < n or jax.devices()[0].platform != "cpu":
        # backend already initialized without the virtual mesh: re-exec
        # clean, ONCE (the multichip-selftest convention)
        if os.environ.get("_PT_SHARDING_SELFTEST_CHILD"):
            print(f"FAIL cannot provision {n} cpu devices "
                  f"(have {len(jax.devices())} "
                  f"{jax.devices()[0].platform!r})")
            return 1
        import subprocess

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["_PT_SHARDING_SELFTEST_CHILD"] = "1"
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu", "--sharding-selftest"],
            env=env, timeout=1800)
        return proc.returncode

    import numpy as np
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu import analysis
    from paddle_tpu.analysis.comm import (
        CommContract, attach_comm_contract, comm_diff)
    from paddle_tpu.core import executor as ex
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel import api as papi
    from paddle_tpu.parallel.contracts import training_step_contract
    from paddle_tpu.parallel.mesh import make_mesh

    failures = []

    def check(cond, what):
        (failures.append(what) if not cond else None)
        print(("ok   " if cond else "FAIL ") + what)

    mesh = make_mesh({"dp": 2, "fsdp": 4})
    cfg = dict(vocab_size=128, n_layer=3, n_head=2, d_model=32,
               max_len=16, dropout_rate=0.0, dtype="float32",
               learning_rate=1e-2)
    accum = 2
    rng = np.random.default_rng(5)
    toks = rng.integers(0, cfg["vocab_size"],
                        (2 * accum * 2, cfg["max_len"])).astype(np.int64)
    lbls = np.roll(toks, -1, axis=1)
    lbls[:, -1] = -1
    feed = {"tokens": toks, "labels": lbls}

    def build(policy="selective", with_accum=True, fsdp_tags=True):
        pt.core.unique_name.reset()
        main, startup = pt.Program(), pt.Program()
        main.random_seed = 7
        with pt.program_guard(main, startup):
            outs = transformer.build(**cfg)
        if policy:
            pt.memory_optimize(main, policy=policy)
        if with_accum:
            pt.gradient_accumulation(main, accum)
        papi.data_parallel(main, "dp", programs=(startup,))
        if fsdp_tags:
            papi.shard_fsdp(main, programs=(startup,))
        return main, startup, outs

    # ---- planted violation 1: the SYMMETRIC fsdp pin ------------------
    orig_pin = ex._fsdp_fwd_pin

    def symmetric_pin(sharding, site="fsdp"):
        # the wrong spelling: transposes to itself, so the backward
        # scan inherits the constraint (measured 19-49 in-loop
        # all-reduces) — and carries no pt_pin[...] blessing
        import jax as _jax

        def pin(x):
            return _jax.lax.with_sharding_constraint(x, sharding)

        return pin

    ex._fsdp_fwd_pin = symmetric_pin
    try:
        main, _startup, outs = build()
        rep = analysis.lint(main, feed=feed,
                            fetch_list=[outs["avg_cost"]], mesh=mesh,
                            levels=("jaxpr",))
        fs = [f for f in rep.by_check("jaxpr.constraint-placement")
              if f.severity == "error"]
        check(bool(fs), "planted symmetric fsdp pin: "
                        "jaxpr.constraint-placement errors")
        hit = [f for f in fs if "fsdp" in (f.data.get("axes") or ())
               and (f.data.get("scan_depth") or 0) >= 1]
        check(bool(hit),
              f"symmetric pin attributed to axis=fsdp INSIDE a scan "
              f"body ({[(f.data.get('axes'), f.data.get('scan_depth')) for f in fs][:3]})")
    finally:
        ex._fsdp_fwd_pin = orig_pin

    # ---- planted violation 2: the FSDP-COMPOSED accum grad carry ------
    orig_spec = ex._accum_carry_spec

    def composed_carry_spec(lead):
        return P(*([None] * lead + ["dp"]), "fsdp")

    ex._accum_carry_spec = composed_carry_spec
    try:
        main, _startup, outs = build()
        rep = analysis.lint(main, feed=feed,
                            fetch_list=[outs["avg_cost"]], mesh=mesh,
                            levels=("jaxpr",))
        fs = [f for f in rep.by_check("jaxpr.constraint-placement")
              if f.severity == "error"
              and "accum_carry" in f.location]
        check(bool(fs), "planted fsdp-composed grad carry: "
                        "jaxpr.constraint-placement errors")
        check(bool(fs) and "fsdp" in (fs[0].data.get("axes") or ()),
              f"carry violation attributed to the composed axis "
              f"({fs[0].data.get('axes') if fs else None} at "
              f"pt_pin[accum_carry])")
    finally:
        ex._accum_carry_spec = orig_spec

    # ---- planted violation 3: the FORBIDDEN activation reshard --------
    main, _startup, outs = build(with_accum=False, fsdp_tags=False)
    blk = main.global_block()
    act = blk.vars["block0_att_out.tmp_0"]
    papi.shard_activation(
        act, P(*([None] * (len(act.shape) - 1)), "fsdp"))
    attach_comm_contract(
        main, CommContract("no-activation-reshard")
        .forbid_reshard(r"^block0_att_out"))
    rep = analysis.lint(main, feed=feed, fetch_list=[outs["avg_cost"]],
                        mesh=mesh, levels=("hlo",))
    cc = [f for f in rep.by_check("hlo.comm-contract")
          if f.severity == "error"]
    check(bool(cc) and "block0_att_out.tmp_0" in cc[0].message,
          f"planted activation reshard: forbid_reshard contract "
          f"errors, attributed to the var "
          f"({cc[0].message[:80] if cc else 'no finding'}...)")
    ar = rep.by_check("hlo.accidental-reshard")
    check(bool(ar) and ar[0].data.get("var") == "block0_att_out.tmp_0"
          and ar[0].data.get("op_count", 0) > 0,
          f"accidental-reshard warns with var provenance + kind/loop "
          f"attribution ({ar[0].data.get('ops', [])[:2] if ar else []})")

    # ---- plan fundamentals: axes, phases, comm_diff -------------------
    def compile_plan(fsdp):
        os.environ["PADDLE_TPU_FSDP"] = fsdp
        try:
            main, startup, outs = build()
            scope = pt.Scope()
            pt.core.scope._scope_stack.append(scope)
            try:
                exe = pt.Executor(mesh=mesh)
                exe.run(startup, scope=scope)
                exe.compile_only(main, feed=feed,
                                 fetch_list=[outs["avg_cost"]],
                                 scope=scope)
                return exe.last_comm_plan
            finally:
                pt.core.scope._scope_stack.pop()
        finally:
            os.environ.pop("PADDLE_TPU_FSDP", None)

    plan_on = compile_plan("1")
    plan_off = compile_plan("0")
    gathers = plan_on.select(kind="all-gather", axis="fsdp",
                             in_loop=True)
    check(bool(gathers) and all(o.phase == "fwd-scan" for o in gathers),
          f"fsdp weight gathers recovered as all-gather@fsdp in the "
          f"forward scan ({len(gathers)} ops)")
    boundary = plan_on.select(kind="reduce", in_loop=False,
                              phase="boundary")
    # under rule 4 the boundary reduce set is: the per-grad
    # reduce-scatters (reduce over dp, scatter over fsdp), the
    # untagged grads' all-reduce@dp, and the scalar grad-norm partial
    # all-reduce@fsdp each scattered grad contributes — every op
    # attributed, nothing outside the gradient axes
    check(bool(boundary)
          and any("dp" in (o.axes or ()) for o in boundary)
          and all((o.axes or ())
                  and set(o.axes) <= {"dp", "fsdp"} for o in boundary),
          f"boundary gradient reduction recovered over the gradient "
          f"axes ({len(boundary)} reduce ops, "
          f"{len(plan_on.select(kind='reduce-scatter'))} canonicalized "
          f"reduce-scatters)")
    check(not plan_on.unattributed(),
          "every collective's replica groups match a mesh-axis subset")
    diff = comm_diff(plan_off, plan_on, "FSDP=0", "FSDP=1")
    moved = [c for c in diff["changed"]
             if c["kind"] == "all-gather" and c["axes"] == "fsdp"
             and c["in_loop"] and c["count_b"] > c["count_a"]]
    check(bool(moved),
          f"comm_diff explains the moved op: FSDP adds the in-loop "
          f"fsdp gathers ({diff['text'][:2]})")

    # ---- planted violation 4: the IN-LOOP reduce-scatter --------------
    from paddle_tpu.parallel.contracts import zero3_grad_contract

    check(not zero3_grad_contract(mesh).check(plan_on),
          "clean FSDP spelling: zero3_grad_contract holds (boundary "
          "reduce-scatter@fsdp, zero in-loop reduces)")
    # the mis-spelling: the ZeRO-3 scatter composed onto the accum
    # carry — every microbatch's partial gradient reduce-scattered
    # INSIDE the scan, the per-iteration traffic rule 4 exists to
    # forbid.  (The jaxpr check catches the stray carry SITE above;
    # this proves the comm layer catches the resulting TRAFFIC
    # independently, for spellings no blessed-site audit sees.)
    ex._accum_carry_spec = composed_carry_spec
    try:
        plan_bad = compile_plan("1")
    finally:
        ex._accum_carry_spec = orig_spec
    viol = zero3_grad_contract(mesh).check(plan_bad)
    bad_rs = [v for v in viol if v["rule"]["rule"] == "forbid"
              and v["op_count"] > 0]
    check(bool(bad_rs),
          f"planted in-loop scatter (fsdp-composed carry): "
          f"zero3_grad_contract forbids the in-loop reduce traffic "
          f"({bad_rs[0]['op_count'] if bad_rs else 0} ops, "
          f"{bad_rs[0]['bytes'] if bad_rs else 0}B)")
    check(bool(bad_rs) and all("fsdp" in o and "in-loop" in o
                               for o in bad_rs[0]["ops"]),
          f"violation attributed to in-loop reduce@fsdp "
          f"({bad_rs[0]['ops'][:2] if bad_rs else []})")

    # ---- the clean sweep: policies x FSDP x ZeRO ----------------------
    for policy in POLICIES:
        for fsdp in ("1", "0"):
            for zero in ("1", "0"):
                os.environ["PADDLE_TPU_FSDP"] = fsdp
                os.environ["PADDLE_TPU_ZERO"] = zero
                try:
                    main, _startup, outs = build(policy=policy)
                    for c in training_step_contract(
                            mesh, accum=True, fsdp=fsdp == "1",
                            grad_rs=fsdp == "1"):
                        attach_comm_contract(main, c)
                    rep = analysis.lint(
                        main, feed=feed,
                        fetch_list=[outs["avg_cost"]], mesh=mesh,
                        levels=("jaxpr", "hlo"))
                    bad = [f for f in rep
                           if f.check in COMM_CHECKS
                           and f.severity == "error"]
                    check(not bad,
                          f"clean GPT policy={policy} fsdp={fsdp} "
                          f"zero={zero}: zero error-severity comm "
                          f"findings ({[f.check for f in bad] or 'ok'})")
                finally:
                    os.environ.pop("PADDLE_TPU_FSDP", None)
                    os.environ.pop("PADDLE_TPU_ZERO", None)

    print("sharding selftest " + ("FAILED" if failures else "PASSED"))
    return 1 if failures else 0
