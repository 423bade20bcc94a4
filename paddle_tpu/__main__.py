"""Command-line entry: ``python -m paddle_tpu <command>``.

Reference: the ``paddle`` CLI (``paddle/scripts/submit_local.sh.in:4-13`` —
train | pserver | version | dump_config | merge_model; binaries
``paddle/trainer/TrainerMain.cpp``, ``pserver/ParameterServer2Main.cpp``,
Go ``go/cmd/{pserver,master}``).

Commands:
  train        drive a model-config script's training loop
  pserver      serve a parameter-server shard over RPC
  master       serve the elastic dataset task dispatcher over RPC
  version      print version / build info
  dump_config  print a config script's Program IR (or graphviz DOT)
  merge_model  bundle an exported inference dir into one tar archive
  bench        run the repo benchmark

A model-config script is a Python file defining ``build() -> dict`` (with
"feed" and "avg_cost" entries, like paddle_tpu.models.*.build) and
optionally ``train_reader()`` yielding samples — the v1 trainer-config
convention rebuilt on the fluid-style DSL."""

import argparse
import importlib.util
import os
import sys

__version__ = "0.1.0"


def _load_config(path):
    spec = importlib.util.spec_from_file_location("paddle_tpu_config", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _build(mod):
    import paddle_tpu as pt

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        outs = mod.build()
    return main, startup, outs


def cmd_version(args):
    import jax

    print(f"paddle_tpu {__version__}")
    print(f"jax {jax.__version__}; backend: {jax.default_backend()}; "
          f"devices: {len(jax.devices())}")
    from . import native

    print(f"native runtime: {'available' if native.available() else 'unavailable'}")
    return 0


def cmd_train(args):
    import numpy as np
    import paddle_tpu as pt

    mod = _load_config(args.config)
    main, startup, outs = _build(mod)
    if getattr(args, "job", "train") == "checkgrad":
        # --job=checkgrad (reference TrainerMain.cpp:54 ->
        # Trainer.cpp:303 checkGradient): finite-difference every
        # trainable parameter through the whole jitted step on ONE batch
        with pt.program_guard(main, startup):
            exe = pt.Executor()
            exe.run(startup)
            reader = getattr(mod, "train_reader", None)
            if reader is None:
                raise SystemExit("config must define train_reader()")
            try:
                batch = next(iter(pt.reader.batch(reader,
                                                  args.batch_size)()))
            except StopIteration:
                raise SystemExit(
                    f"train_reader yields fewer than --batch-size "
                    f"({args.batch_size}) samples; checkgrad needs one "
                    f"full batch")
            feeder = pt.DataFeeder(outs["feed"])
            ok, report = pt.check_gradients(
                feeder.feed(batch), outs["avg_cost"], program=main,
                verbose=True)
        for name, r in sorted(report.items()):
            print(f"{name}: max_rel_err={r['max_rel_err']:.3e} "
                  f"(checked {r['checked']} elements)")
        print("checkgrad " + ("PASSED" if ok else "FAILED"))
        return 0 if ok else 1
    with pt.program_guard(main, startup):
        trainer = pt.trainer.Trainer(
            outs["avg_cost"], outs["feed"],
            extra_fetch=[v for k, v in outs.items()
                         if k not in ("feed", "avg_cost")
                         and hasattr(v, "name")],
        )
        reader = getattr(mod, "train_reader", None)
        if reader is None:
            raise SystemExit("config must define train_reader()")
        batched = pt.reader.batch(reader, args.batch_size)

        def handler(ev):
            if isinstance(ev, pt.trainer.EndIteration):
                if args.log_period and ev.batch_id % args.log_period == 0:
                    print(f"pass {ev.pass_id} batch {ev.batch_id} "
                          f"cost {np.asarray(ev.cost).ravel()[0]:.6f}")
            elif isinstance(ev, pt.trainer.EndPass):
                print(f"pass {ev.pass_id} done")

        if args.run_log:
            reporter = pt.observability.MetricsReporter(
                log_every_n=0, jsonl_path=args.run_log)
            handler = reporter.chain(handler)
        try:
            trainer.train(batched, num_passes=args.num_passes,
                          event_handler=handler,
                          checkpoint_dir=args.checkpoint_dir)
        finally:
            if args.run_log:
                reporter.close()
    return 0


def cmd_pserver(args):
    from .distributed import rpc
    from .distributed.pserver import ParameterServer
    from .distributed.store import FileStore, InMemStore, register_service

    store = FileStore(args.store) if args.store else InMemStore()
    ps = ParameterServer(
        index=args.index, num_trainers=args.num_trainers, sync=not args.async_sgd,
        store=store, checkpoint_dir=args.checkpoint_dir,
        checkpoint_every_n_updates=args.checkpoint_every,
    )
    server = rpc.Server(ps, port=args.port).start()
    register_service(store, "pserver", server.endpoint)
    print(f"pserver {args.index} serving on {server.endpoint}", flush=True)
    try:
        import threading

        threading.Event().wait()
    except KeyboardInterrupt:
        server.stop()
    return 0


def cmd_master(args):
    import glob

    from .distributed import rpc
    from .distributed.master import MasterService
    from .distributed.store import FileStore, InMemStore, register_service

    store = FileStore(args.store) if args.store else InMemStore()
    svc = MasterService(store=store, chunks_per_task=args.chunks_per_task,
                        timeout_sec=args.timeout)
    if args.dataset:
        paths = sorted(p for pat in args.dataset for p in glob.glob(pat))
        svc.set_dataset(paths)
        print(f"dataset: {len(paths)} files, {len(svc.todo)} tasks")
    server = rpc.Server(svc, port=args.port).start()
    register_service(store, "master", server.endpoint)
    print(f"master serving on {server.endpoint}", flush=True)
    try:
        import threading

        threading.Event().wait()
    except KeyboardInterrupt:
        server.stop()
    return 0


def cmd_dump_config(args):
    mod = _load_config(args.config)
    main, startup, _ = _build(mod)
    if args.dot:
        from .net_drawer import draw_graph

        print(draw_graph(main))
    else:
        print(main.to_string())
        if args.startup:
            print("\n// ---- startup program ----")
            print(startup.to_string())
    return 0


def cmd_merge_model(args):
    """Bundle an exported inference-model dir (save_inference_model layout)
    into a single tar (MergeModel.cpp / merge_v2_model analog)."""
    import tarfile

    if not os.path.isdir(args.model_dir):
        raise SystemExit(f"not a directory: {args.model_dir}")
    with tarfile.open(args.output, "w") as tar:
        for name in sorted(os.listdir(args.model_dir)):
            tar.add(os.path.join(args.model_dir, name), arcname=name)
    print(f"wrote {args.output}")
    return 0


def cmd_bench(args):
    import runpy

    path = os.path.join(os.path.dirname(__file__), "..", "bench.py")
    if not os.path.exists(path):
        raise SystemExit(
            "bench.py not found next to the package — the bench command is "
            "only available from a source checkout")
    sys.argv = ["bench.py"]
    runpy.run_path(path, run_name="__main__")
    return 0


def cmd_metrics_selftest(args=None):
    """``python -m paddle_tpu --metrics-selftest``: exercise the
    observability registry end-to-end on CPU — counters/gauges/histograms,
    Prometheus exposition, JSONL round trip, and the Executor's
    compile-counter/cache-hit instrumentation on a real (tiny) program.
    Exits 0 on success; the CI smoke gate for the telemetry subsystem."""
    import tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.observability import (
        MetricsRegistry, RunLog, get_registry, read_jsonl)

    failures = []

    def check(cond, what):
        (failures.append(what) if not cond else None)
        print(("ok   " if cond else "FAIL ") + what)

    reg = MetricsRegistry()
    c = reg.counter("t.count")
    c.inc()
    c.inc(2)
    check(c.value == 3, "counter accumulates")
    g = reg.gauge("t.depth", shard="0")
    g.set(7)
    check(reg.value("t.depth", shard="0") == 7, "labeled gauge")
    h = reg.histogram("t.lat")
    for i in range(100):
        h.observe(i / 100.0)
    check(abs(h.percentile(50) - 0.49) < 0.05, "histogram percentile")
    text = reg.to_text()
    check("t_count 3" in text and 'shard="0"' in text,
          "prometheus exposition")
    reg.reset()
    check(c.value == 0 and h.count == 0, "reset zeroes metrics")

    with tempfile.NamedTemporaryFile(suffix=".jsonl", delete=False) as f:
        path = f.name
    with RunLog(path, mode="w") as log:
        log.log("step", cost=np.float32(1.5), batch_id=0)
        log.log("pass", pass_id=0)
    recs = read_jsonl(path)
    check(len(recs) == 2 and recs[0]["cost"] == 1.5, "jsonl round trip")
    os.unlink(path)

    # executor instrumentation on a real program
    greg = get_registry()
    c0 = greg.value("executor.compile_count")
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        from paddle_tpu import layers

        x = layers.data("x", shape=[4])
        y = layers.fc(x, 2)
        exe = pt.Executor()
        exe.run(startup)
        feed = {"x": np.zeros((2, 4), np.float32)}
        exe.run(main_prog, feed=feed, fetch_list=[y])
        check(greg.value("executor.compile_count") >= c0 + 2,
              "compile counter increments (startup + main)")
        check(exe.last_step_cost["cache_hit"] is False,
              "first run is a cache miss")
        check(exe.last_step_cost["flops"] is not None,
              "cost analysis reports flops")
        exe.run(main_prog, feed=feed, fetch_list=[y])
        check(exe.last_step_cost["cache_hit"] is True,
              "second run hits the jit cache")

    print("metrics selftest " + ("FAILED" if failures else "PASSED"))
    return 1 if failures else 0


def cmd_memory_selftest(args=None):
    """``python -m paddle_tpu --memory-selftest``: the no-accelerator
    backward-pass memory regression, run explicitly — for every
    ``memory_optimize`` policy (selective/compact/full/offload) on a
    small GPT, lower the full training step and assert the scan-locality
    invariants of docs/memory.md: every flash ``pallas_call`` sits
    inside a ``lax.scan`` body (none unrolled per layer — the BENCH_r05
    failure mode), no pallas operand/result carries a leading
    layer-count axis, the scan engine engaged without fallbacks, and
    ``memory_analysis()`` figures are reported.  Also pins offload ==
    selective loss bit-exactness.  Exits 0 on success; wired into
    tools/tier1.sh."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.analysis import audit_program
    from paddle_tpu.models import transformer

    failures = []

    def check(cond, what):
        (failures.append(what) if not cond else None)
        print(("ok   " if cond else "FAIL ") + what)

    n_layer, t, d = 5, 12, 32

    def build(policy):
        pt.core.unique_name.reset()
        main_prog, startup = pt.Program(), pt.Program()
        main_prog.random_seed = 7
        with pt.program_guard(main_prog, startup):
            outs = transformer.build(vocab_size=29, n_layer=n_layer,
                                     n_head=2, d_model=d, max_len=t,
                                     dropout_rate=0.0, dtype="float32")
        pt.memory_optimize(main_prog, policy=policy)
        return main_prog, startup, outs["avg_cost"]

    rng = np.random.default_rng(5)
    toks = rng.integers(0, 29, (2, t)).astype(np.int64)
    feed = {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}

    losses = {}
    for policy in ("selective", "compact", "full", "offload"):
        main_prog, startup, loss = build(policy)
        scope = pt.Scope()
        pt.core.scope._scope_stack.append(scope)
        try:
            exe = pt.Executor()
            exe.run(startup, scope=scope)
            rep = audit_program(main_prog, feed, [loss], scope=scope,
                                layer_count=n_layer,
                                absent_shapes=[(n_layer, t, d)])
            if policy in ("selective", "offload"):
                # only these two feed the bit-exactness check below —
                # skip the extra step compile for the other policies
                losses[policy] = np.asarray(
                    exe.run(main_prog, feed=feed, fetch_list=[loss],
                            scope=scope)[0])
        finally:
            pt.core.scope._scope_stack.pop()
        # a policy's segmentation may leave the FIRST layer outside the
        # uniform group (compact's period aligns at layer 2 here), so up
        # to one layer's worth of kernel calls (fwd + dq + dkv = 3) may
        # legitimately sit outside the scan — the failure mode is O(L)
        # unrolled calls (>= n_layer), not O(1)
        check(rep["pallas_total"] > rep["pallas_outside_scan"]
              and rep["pallas_outside_scan"] <= 3,
              f"{policy}: flash calls scan-local "
              f"({rep['pallas_outside_scan']}/{rep['pallas_total']} "
              f"outside)")
        check(not rep["layer_stacked_pallas"],
              f"{policy}: no layer-stacked pallas operand "
              f"{rep['layer_stacked_pallas'][:2]}")
        check(all(n == 0
                  for n in rep.get("absent_shape_hits", {}).values()),
              f"{policy}: BENCH_r05 shape [{n_layer},{t},{d}] absent "
              f"from optimized HLO")
        plan = rep["scan_remat_plan"]
        check(any("fallback" not in p for p in plan)
              and not any("fallback" in p for p in plan),
              f"{policy}: scan engine engaged without fallback ({plan})")
        check(rep.get("temp_bytes", 0) > 0
              and rep.get("hbm_high_water_bytes", 0) > 0,
              f"{policy}: memory_analysis figures "
              f"(temp {rep.get('temp_bytes')}, "
              f"high-water {rep.get('hbm_high_water_bytes')})")
    check(np.array_equal(losses["offload"], losses["selective"]),
          "offload loss bit-exact vs selective")

    print("memory selftest " + ("FAILED" if failures else "PASSED"))
    return 1 if failures else 0


def cmd_multichip_selftest(args=None):
    """``python -m paddle_tpu --multichip-selftest``: the multi-chip
    scaling invariants on an 8-device virtual CPU mesh, run explicitly —
    ZeRO-1 accumulator sharding present with per-device optimizer-state
    bytes <= replicated/4, the comm audit's one-cross-chip-gradient-
    reduction-per-optimizer-step contract under accum_steps=4
    (``reduce_ops_in_loop == 0`` on compiled HLO, accumulation plan in
    ``local`` mode), and loss/params BIT-EXACT vs the replicated
    (``PADDLE_TPU_ZERO=0``) spelling on the same mesh.  Exits 0 on
    success; wired into tools/tier1.sh (docs/parallel.md)."""
    n = 8
    # strip-and-replace the device-count flag (a pre-set lower count must
    # not survive — the dryrun_multichip convention)
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    import jax

    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < n or jax.devices()[0].platform != "cpu":
        # backend was already initialized without the virtual mesh (e.g.
        # called from a process holding a real chip): re-exec clean —
        # ONCE (the child sets the env above before its backend exists,
        # so a second level means something else is broken)
        if os.environ.get("_PT_MULTICHIP_SELFTEST_CHILD"):
            print(f"FAIL cannot provision {n} cpu devices "
                  f"(have {len(jax.devices())} "
                  f"{jax.devices()[0].platform!r})")
            return 1
        import subprocess

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["_PT_MULTICHIP_SELFTEST_CHILD"] = "1"
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu", "--multichip-selftest"],
            env=env, timeout=1800)
        return proc.returncode

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel import api as papi
    from paddle_tpu.parallel.mesh import make_mesh

    failures = []
    import time as _time

    gate_t0 = [_time.monotonic()]
    gate_times = []

    def check(cond, what):
        # per-gate wall time: everything since the previous gate (the
        # training/compile work this gate consumed — the first gate of
        # each shared-executable family carries its compiles) is
        # charged to it, so a regression in gate cost is visible in
        # the selftest output (the runtime-audit discipline)
        now = _time.monotonic()
        gate_times.append((what, now - gate_t0[0]))
        gate_t0[0] = now
        (failures.append(what) if not cond else None)
        print(("ok   " if cond else "FAIL ") + what
              + f"  [{gate_times[-1][1]:.1f}s]")

    cfg = dict(vocab_size=256, n_layer=2, n_head=2, d_model=64,
               max_len=32, dropout_rate=0.0, dtype="float32",
               learning_rate=1e-2)
    accum = 4
    mesh = make_mesh({"dp": n})
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg["vocab_size"], (4 * n, 32)).astype(np.int64)
    lbls = np.roll(toks, -1, axis=1)
    lbls[:, -1] = -1
    feed = {"tokens": toks, "labels": lbls}

    def train(zero):
        os.environ["PADDLE_TPU_ZERO"] = zero
        try:
            pt.core.unique_name.reset()
            main_prog, startup = pt.Program(), pt.Program()
            main_prog.random_seed = 7
            with pt.program_guard(main_prog, startup):
                outs = transformer.build(**cfg)
            pt.gradient_accumulation(main_prog, accum)
            papi.data_parallel(main_prog, "dp", programs=(startup,))
            scope = pt.Scope()
            pt.core.scope._scope_stack.append(scope)
            try:
                exe = pt.Executor(mesh=mesh)
                exe.run(startup, scope=scope)
                losses = [
                    np.asarray(exe.run(
                        main_prog, feed=feed,
                        fetch_list=[outs["avg_cost"]], scope=scope)[0])
                    for _ in range(2)
                ]
                params = {v.name: np.asarray(scope.get(v.name))
                          for v in main_prog.all_parameters()}
                moments = sorted(
                    v.name for v in main_prog.global_block().vars.values()
                    if v.name.endswith("_moment1"))
                sh = scope.get(moments[0]).sharding
                return (losses, params, dict(exe.last_step_cost),
                        exe.last_accum_plan,
                        papi.optimizer_state_report(main_prog, mesh), sh,
                        exe.last_comm_plan)
            finally:
                pt.core.scope._scope_stack.pop()
        finally:
            os.environ.pop("PADDLE_TPU_ZERO", None)

    from paddle_tpu.parallel.contracts import (
        fsdp_scan_contract, one_boundary_reduce_contract)

    (losses, params, cost, plan, rep, moment_sh,
     comm_plan) = train("1")
    check(rep["sharded_vars"] > 0
          and "dp" in str(getattr(moment_sh, "spec", "")),
          f"ZeRO-1 accumulators dp-sharded ({rep['sharded_vars']} vars, "
          f"moment spec {getattr(moment_sh, 'spec', None)})")
    check(rep["per_device_bytes"] * 4 <= rep["total_bytes"],
          f"optimizer-state bytes/device {rep['per_device_bytes']} <= "
          f"replicated {rep['total_bytes']} / 4")
    check((plan or {}).get("mode") == "local",
          f"accumulation plan is comm-aware local mode ({plan})")
    # the one-reduction-per-step + zero-in-loop-reduce invariants as a
    # declarative CommContract over the compiled step's CommPlan
    # (parallel/contracts.py) — the machine-checked spelling of
    # docs/parallel.md's comm audit
    viol = one_boundary_reduce_contract(mesh).check(comm_plan)
    check(not viol and len(comm_plan) > 0,
          f"CommContract one-boundary-reduce holds "
          f"({len(comm_plan)} collectives planned; "
          f"violations: {[v['message'] for v in viol] or 'none'})")
    (losses_r, params_r, _cost_r, _plan_r, rep_r, _sh_r,
     _cp_r) = train("0")
    check(rep_r["sharded_vars"] == 0
          and rep_r["per_device_bytes"] == rep_r["total_bytes"],
          "PADDLE_TPU_ZERO=0 replicates every accumulator")
    check(all(np.array_equal(a, b) for a, b in zip(losses, losses_r)),
          "ZeRO loss bit-exact vs replicated spelling")
    check(all(np.array_equal(params[k], params_r[k]) for k in params),
          "ZeRO updated params bit-exact vs replicated spelling")

    # ---- FSDP / ZeRO-3: parameter sharding inside the scan-remat body
    # (docs/parallel.md).  dp=2 x fsdp=4 on the same 8 devices; the
    # scan-stacked per-layer weights shard 4-way over fsdp at rest and
    # all-gather one layer at a time INSIDE the scan body; loss, grads
    # and params stay bit-exact vs PADDLE_TPU_FSDP=0 because compute is
    # replicated along fsdp either way — only weight placement moves.
    mesh_f = make_mesh({"dp": n // 4, "fsdp": 4})
    cfg_f = dict(cfg, n_layer=3)

    def train_fsdp(fsdp, rs="1"):
        os.environ["PADDLE_TPU_FSDP"] = fsdp
        os.environ["PADDLE_TPU_ZERO3_RS"] = rs
        try:
            pt.core.unique_name.reset()
            main_prog, startup = pt.Program(), pt.Program()
            main_prog.random_seed = 7
            with pt.program_guard(main_prog, startup):
                outs = transformer.build(**cfg_f)
            pt.memory_optimize(main_prog, policy="selective")
            pt.gradient_accumulation(main_prog, accum)
            papi.data_parallel(main_prog, "dp", programs=(startup,))
            tagged = papi.shard_fsdp(main_prog, programs=(startup,))
            scope = pt.Scope()
            pt.core.scope._scope_stack.append(scope)
            try:
                exe = pt.Executor(mesh=mesh_f)
                exe.run(startup, scope=scope)
                gfetch = [tagged[0] + "@GRAD", "lm_head.w@GRAD"]
                losses, grads = [], []
                for _ in range(5):
                    r = exe.run(main_prog, feed=feed,
                                fetch_list=[outs["avg_cost"]] + gfetch,
                                scope=scope)
                    losses.append(np.asarray(r[0]))
                    grads.append([np.asarray(g) for g in r[1:]])
                params = {v.name: np.asarray(scope.get(v.name))
                          for v in main_prog.all_parameters()}
                return (losses, grads, params,
                        dict(exe.last_step_cost), exe.last_accum_plan,
                        list(exe.last_remat_plan),
                        papi.sharding_report(main_prog, mesh_f),
                        str(getattr(scope.get(tagged[0]), "sharding",
                                    None)),
                        exe.last_comm_plan, tagged)
            finally:
                pt.core.scope._scope_stack.pop()
        finally:
            os.environ.pop("PADDLE_TPU_FSDP", None)
            os.environ.pop("PADDLE_TPU_ZERO3_RS", None)

    (losses_f, grads_f, params_f, cost_f, plan_f, remat_f, rep_f,
     wsh_f, comm_plan_f, tagged_f) = train_fsdp("1")
    scanned = [g for g in remat_f if g.get("fsdp")]
    check(bool(scanned) and scanned[0]["fsdp"] > 0,
          f"scan-remat group runs with fsdp-sharded stacked weights "
          f"({scanned[0].get('fsdp') if scanned else 0} xs sharded)")
    check("fsdp" in (wsh_f or ""),
          f"live layer weight is fsdp-sharded ({wsh_f})")
    pf, pt_ = (rep_f["params"]["per_device_bytes"],
               rep_f["params"]["total_bytes"])
    check(pf * 2 <= pt_,
          f"param bytes/device {pf} <= replicated {pt_} / 2 "
          f"(stacked scan weights sharded 4-way)")
    check((plan_f or {}).get("mode") == "local",
          f"fsdp accumulation plan stays comm-aware local ({plan_f})")
    # the FSDP comm audit as CommContracts: in-loop fsdp weight gathers
    # present (the design), zero in-loop reduce-class collectives, one
    # boundary gradient reduction — evaluated on the structured
    # CommPlan instead of scalar count arithmetic
    viol_f = (fsdp_scan_contract(mesh_f).check(comm_plan_f)
              + one_boundary_reduce_contract(mesh_f).check(comm_plan_f))
    fsdp_gathers = comm_plan_f.select(kind="all-gather", axis="fsdp",
                                      in_loop=True)
    check(not viol_f,
          f"fsdp CommContracts hold: {len(fsdp_gathers)} in-loop "
          f"fsdp weight gathers, zero in-loop reduces, boundary "
          f"reduce present (violations: "
          f"{[v['message'] for v in viol_f] or 'none'})")
    # ---- true ZeRO-3 gradient path (docs/parallel.md rule 4): the
    # rs=0 executable set below is compiled ONCE and shared by the
    # kill-switch, bit-exactness, reduce-set and comm_diff gates — the
    # rs=1 set above already served the sharding/contract/bytes gates
    # (the runtime-audit discipline: one compile per distinct config).
    from paddle_tpu.analysis.comm import comm_diff
    from paddle_tpu.parallel.contracts import zero3_grad_contract

    # (1) exactly one reduce-scatter@fsdp per fsdp-tagged grad at the
    # optimizer boundary, zero in-loop reduce-class collectives —
    # evaluated as a CommContract over the compiled step's CommPlan
    viol_rs = zero3_grad_contract(
        mesh_f, n_grads=len(tagged_f)).check(comm_plan_f)
    rs_ops = comm_plan_f.select(kind="reduce-scatter", axis="fsdp",
                                in_loop=False)
    rs_sites = {(op.provenance or {}).get("site", "").split(":", 1)[-1]
                for op in rs_ops}
    check(not viol_rs and rs_sites == set(tagged_f),
          f"zero3_grad_contract holds: {len(rs_ops)} boundary "
          f"reduce-scatter@fsdp, one per fsdp-tagged grad "
          f"({len(tagged_f)} tagged; violations: "
          f"{[v['message'] for v in viol_rs] or 'none'})")
    # (2) the prologue/epilogue is truly sharded: embedding table +
    # LM head param AND opt-state bytes/device at most
    # replicated/(fsdp_degree/2)
    prologue = [nm for nm in ("tok_emb.w", "pos_emb.w.w", "lm_head.w")
                if nm in rep_f["params"]["vars"]]
    pvars = rep_f["params"]["vars"]
    ovars = rep_f["opt_state"]["vars"]
    pro_total = (sum(pvars[nm]["bytes"] for nm in prologue)
                 + sum(v["bytes"] for nm in prologue
                       for o, v in ovars.items() if nm in o))
    pro_dev = (sum(pvars[nm]["per_device_bytes"] for nm in prologue)
               + sum(v["per_device_bytes"] for nm in prologue
                     for o, v in ovars.items() if nm in o))
    check(len(prologue) == 3 and pro_dev * 2 <= pro_total,
          f"embedding + LM head param/opt-state bytes/device {pro_dev} "
          f"<= replicated {pro_total} / (fsdp_degree/2)")
    (losses_r0, grads_r0, params_r0, cost_r0, _plan_r0, _remat_r0,
     rep_r0, _wsh_r0, comm_plan_r0, _tagged_r0) = train_fsdp("1",
                                                             rs="0")
    # (3) 5-step loss+grads+params bit-exact vs the replicated-grad
    # spelling (PADDLE_TPU_ZERO3_RS=0 restores it exactly)
    check(not comm_plan_r0.select(kind="reduce-scatter")
          and rep_r0["grads"]["per_device_bytes"]
          == rep_r0["grads"]["total_bytes"],
          "PADDLE_TPU_ZERO3_RS=0 restores the replicated-grad "
          "spelling (no reduce-scatter, grads replicated)")
    check(all(np.array_equal(a, b)
              for a, b in zip(losses_f, losses_r0)),
          "ZeRO-3 RS loss bit-exact vs replicated-grad spelling "
          "(5 steps)")
    check(all(np.array_equal(a, b)
              for ga, gb in zip(grads_f, grads_r0)
              for a, b in zip(ga, gb)),
          "ZeRO-3 RS grads bit-exact vs replicated-grad spelling "
          "(5 steps)")
    check(all(np.array_equal(params_f[k], params_r0[k])
              for k in params_f),
          "ZeRO-3 RS updated params bit-exact vs replicated-grad "
          "spelling")
    # (4) comm_diff explains the move: the full-volume boundary
    # all-reduce@dp bucket shrinks, reduce-scatter@fsdp appears
    d = comm_diff(comm_plan_r0, comm_plan_f, name_a="replicated",
                  name_b="zero3-rs")
    moved = {c["kind"] for c in d["changed"]}
    ar_dp = [c for c in d["changed"]
             if c["kind"] == "all-reduce" and c["axes"] == "dp"
             and c["phase"] == "boundary"]
    check("reduce-scatter" in moved and ar_dp
          and ar_dp[0]["bytes_b"] < ar_dp[0]["bytes_a"],
          "comm_diff names the moved collectives (reduce-scatter "
          "appears, boundary all-reduce@dp bytes shrink): "
          + "; ".join(d["text"][:4]))
    (losses_f0, grads_f0, params_f0, cost_f0, _plan_f0, _remat_f0,
     rep_f0, _wsh_f0, _cp_f0, _tagged_f0) = train_fsdp("0")
    check(rep_f0["params"]["per_device_bytes"]
          == rep_f0["params"]["total_bytes"],
          "PADDLE_TPU_FSDP=0 replicates every parameter")
    check(cost_r0.get("reduce_ops") == cost_f0.get("reduce_ops"),
          f"boundary reduce set unchanged by fsdp under the "
          f"replicated-grad spelling "
          f"({cost_r0.get('reduce_ops')} == {cost_f0.get('reduce_ops')} "
          f"— one gradient reduction per optimizer step)")
    check(all(np.array_equal(a, b)
              for a, b in zip(losses_f, losses_f0)),
          "FSDP loss bit-exact vs replicated spelling (5 steps)")
    check(all(np.array_equal(a, b)
              for ga, gb in zip(grads_f, grads_f0)
              for a, b in zip(ga, gb)),
          "FSDP grads bit-exact vs replicated spelling (5 steps)")
    check(all(np.array_equal(params_f[k], params_f0[k])
              for k in params_f),
          "FSDP updated params bit-exact vs replicated spelling")

    slow = sorted(gate_times, key=lambda t: -t[1])[:3]
    print("gate wall times: total "
          + f"{sum(t for _, t in gate_times):.1f}s; slowest: "
          + ", ".join(f"{w[:48]}={t:.1f}s" for w, t in slow))
    print("multichip selftest " + ("FAILED" if failures else "PASSED"))
    return 1 if failures else 0


def cmd_bench_history(argv):
    """``python -m paddle_tpu --bench-history [--dir D] [--threshold T]
    [--known-failures F]``: parse every ``BENCH_*.json`` /
    ``MULTICHIP_*.json`` artifact under the repo root (or ``--dir``)
    into one trajectory table (stderr), classify failed artifacts
    (rc!=0 / missing row keys — the BENCH_r05 class), flag metric
    regressions beyond ``--threshold`` (default 10%) vs best-so-far,
    and print ONE parseable JSON summary row on stdout.  Exits non-zero
    when any failure or regression is not acknowledged in the
    known-failures file (default ``tools/bench_known_failures.json``) —
    the tier-1 gate that keeps a rotted bench artifact from sitting
    silently on disk."""
    import json as _json

    p = argparse.ArgumentParser(prog="paddle_tpu --bench-history")
    p.add_argument("--dir", default=None,
                   help="artifact directory (default: the repo root "
                        "containing this package)")
    p.add_argument("--threshold", type=float, default=0.1,
                   help="regression threshold vs best-so-far (0.1 = "
                        "flag any metric >10%% below its best round)")
    p.add_argument("--known-failures", default=None,
                   help="JSON {artifact: reason} of acknowledged "
                        "failures/regressions (default: "
                        "<dir>/tools/bench_known_failures.json)")
    args = p.parse_args([a for a in argv if a != "--bench-history"])

    from .observability import bench_history as bh

    root = args.dir or os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    kf = args.known_failures
    if kf is None:
        cand = os.path.join(root, "tools", "bench_known_failures.json")
        kf = cand if os.path.exists(cand) else None
    known = {}
    if kf:
        with open(kf, "r", encoding="utf-8") as fh:
            known = _json.load(fh)
    summary, rows = bh.history(root, threshold=args.threshold,
                               known_failures=known)
    print(bh.format_table(rows), file=sys.stderr)
    for art, why in sorted(summary.get("resolved", {}).items()):
        print(f"RESOLVED: {art}: {why}", file=sys.stderr)
    for k in summary.get("stale_acks", []):
        print(f"WARNING: stale ack {k!r} in {kf or 'known-failures'}: "
              f"the acknowledged defect no longer exists — delete the "
              f"entry", file=sys.stderr)
    for r in summary["regressions"]:
        ack = (" (acknowledged)"
               if f"{r['artifact']}:{r['metric']}" in known else "")
        print(f"REGRESSION{ack}: {r['metric']} {r['value']:g} in "
              f"{r['artifact']} is {r['drop'] * 100:.1f}% below best "
              f"{r['best']:g} (round {r['best_round']})",
              file=sys.stderr)
    for key, moved in sorted(
            summary.get("regression_attribution", {}).items()):
        tops = "; ".join(
            f"{m['op_class']} share {m['share_best']} -> {m['share']}"
            for m in moved[:3])
        print(f"ATTRIBUTION: {key}: {tops}", file=sys.stderr)
    print(_json.dumps(summary))
    return 0 if summary["ok"] else 1


def cmd_trace_selftest(args=None):
    """``python -m paddle_tpu --trace-selftest``: the tracing engine's
    CI gate, CPU-only — span runtime semantics (nesting, disabled-mode
    shared null context, host_timer fold-in), a real trainer run
    emitting all five step-phase spans into a valid Chrome-trace file,
    a serving request span tree whose TTFT decomposition (queue wait +
    prefill compute) matches the recorded ``serving.ttft_seconds``
    observation within 10%, and the ``--bench-history`` gate exiting
    non-zero on a planted failed artifact + regression fixture while
    still emitting one parseable JSON summary row.  Wired into
    tools/tier1.sh."""
    import json as _json
    import subprocess
    import tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.observability import get_registry, trace

    failures = []

    def check(cond, what):
        (failures.append(what) if not cond else None)
        print(("ok   " if cond else "FAIL ") + what)

    # -- span runtime --------------------------------------------------
    t = trace.Tracer(enabled=True, registry=None)
    with t.span("outer", cat="t", k=1):
        with t.span("inner"):
            pass
    t.instant("mark")
    outer, inner = t.events(name="outer")[0], t.events(name="inner")[0]
    check(outer["ts"] <= inner["ts"] and
          inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3,
          "span nesting by ts containment")
    check(outer["args"] == {"k": 1}, "span attributes recorded")
    td = trace.Tracer(enabled=False)
    check(td.span("x") is td.span("y") and not td.events(),
          "disabled mode: shared null context, no events")
    t2 = trace.Tracer(enabled=True)
    with t2.span("trace_selftest_phase"):
        pass
    h = get_registry().get("host_timer.trace_selftest_phase")
    check(h is not None and h.count == 1,
          "span duration folds into host_timer.*")

    # -- trainer: five phase spans + chrome export ---------------------
    old = trace.set_tracer(trace.Tracer(enabled=True))
    try:
        from paddle_tpu.models import lenet

        pt.core.unique_name.reset()
        main_prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(main_prog, startup):
            model = lenet.build(learning_rate=0.01)
            trainer = pt.trainer.Trainer(model["avg_cost"], model["feed"])
            rng = np.random.default_rng(0)

            def reader():
                for _ in range(3):
                    yield [(rng.normal(size=(1, 28, 28)).astype(
                        np.float32), int(rng.integers(0, 10)))
                        for _ in range(4)]

            trainer.train(reader, num_passes=1)
        gt = trace.get_tracer()
        phases = ("trainer.reader_wait", "trainer.feed_h2d",
                  "trainer.dispatch", "trainer.device_sync",
                  "trainer.opt_boundary")
        for name in phases:
            check(len(gt.events(name=name)) == 3,
                  f"trainer emits {name} x3")
        steps = gt.events(name="trainer.step")
        check(len(steps) == 3, "trainer emits trainer.step x3")
        disp = gt.events(name="trainer.dispatch")
        nested = all(any(
            s["tid"] == d["tid"] and s["ts"] <= d["ts"] and
            d["ts"] + d["dur"] <= s["ts"] + s["dur"] + 1e-3
            for s in steps) for d in disp)
        check(nested, "phase spans nest inside trainer.step")

        # -- serving request span tree + TTFT decomposition ------------
        from paddle_tpu.models import transformer
        from paddle_tpu.serving import ServingEngine

        pt.core.unique_name.reset()
        mp, sp = pt.Program(), pt.Program()
        with pt.program_guard(mp, sp):
            transformer.build(vocab_size=64, n_layer=2, n_head=2,
                              d_model=64, max_len=32, dropout_rate=0.0,
                              is_test=True, dtype="float32")
            exe = pt.Executor()
            exe.run(sp)
            params = transformer.extract_params(program=mp)
        eng = ServingEngine(params, 2, 2, 64, max_len=32, max_slots=4,
                            decode_chunk=2, min_bucket=4)
        # warm: pay the prefill/decode compiles outside the measurement
        eng.generate_many([np.arange(1, 4, dtype=np.int32)],
                          max_new_tokens=2)
        reg = get_registry()
        for nm in ("serving.ttft_seconds", "serving.queue_wait"):
            reg.get(nm).reset()
        gt.clear()
        req = eng.submit(np.arange(1, 5, dtype=np.int32),
                         max_new_tokens=6)
        eng.run_until_idle()
        st = eng.stats()
        check(st["serving.ttft_seconds"]["count"] == 1
              and st["serving.queue_wait"]["count"] == 1,
              "one timed request observed")
        q = st["serving.queue_wait"]["mean"]
        pre = req.prefill_t1 - req.prefill_t0
        ttft = st["serving.ttft_seconds"]["mean"]
        check(abs((q + pre) - ttft) <= 0.10 * ttft,
              f"TTFT decomposition within 10% (queue {q * 1e3:.3f}ms + "
              f"prefill {pre * 1e3:.3f}ms vs ttft {ttft * 1e3:.3f}ms)")
        roots = gt.events(name="serving.request")
        check(len(roots) == 1, "request root span emitted")
        if roots:
            root = roots[0]
            kids = [e for e in gt.events(cat="serving")
                    if e["name"].startswith("serving.req.")
                    and e["tid"] == root["tid"]]
            cover = sum(e["dur"] for e in kids)
            check({e["name"] for e in kids} >= {
                "serving.req.queue", "serving.req.prefill",
                "serving.req.decode_chunk", "serving.req.evict"},
                "request span tree has queue/prefill/decode/evict")
            check(0.5 * root["dur"] <= cover <= 1.001 * root["dur"],
                  f"span tree covers the request "
                  f"({cover / root['dur'] * 100:.1f}% of e2e)")

        # -- chrome export of everything above -------------------------
        path = os.path.join(tempfile.mkdtemp(prefix="pt_trace_"),
                            "trace.json")
        # re-emit the trainer spans into the export (cleared above):
        # the file must carry BOTH the nested step phases and the
        # request lane, per the acceptance criteria
        for e in steps + disp:
            gt._push(e)
        n = gt.save(path)
        with open(path, "r", encoding="utf-8") as fh:
            obj = _json.load(fh)
        xs = [e for e in obj.get("traceEvents", []) if e.get("ph") == "X"]
        ok_fields = xs and all(
            all(k in e for k in ("ph", "ts", "dur", "pid", "tid", "name"))
            for e in xs)
        names = {e["name"] for e in xs}
        check(bool(ok_fields), f"chrome trace valid ({n} events, "
                               f"required ph/ts/dur/pid/tid/name fields)")
        check("trainer.step" in names and "serving.request" in names,
              "chrome trace carries trainer steps + serving request lane")
    finally:
        trace.set_tracer(old)

    # -- bench-history gate on a planted fixture -----------------------
    fixture = tempfile.mkdtemp(prefix="pt_benchhist_")
    rows = [
        ("BENCH_r01.json", {"n": 1, "rc": 0, "parsed": {
            "metric": "m", "value": 100.0, "unit": "u"}}),
        ("BENCH_r02.json", {"n": 2, "rc": 0, "parsed": {
            "metric": "m", "value": 42.0, "unit": "u"}}),  # regression
        ("BENCH_r03.json", {"n": 3, "rc": 1, "parsed": None}),  # failed
    ]
    for name, data in rows:
        with open(os.path.join(fixture, name), "w") as fh:
            _json.dump(data, fh)
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu", "--bench-history",
         "--dir", fixture],
        capture_output=True, text=True, timeout=600)
    check(proc.returncode != 0,
          f"--bench-history exits non-zero on the planted fixture "
          f"(rc={proc.returncode})")
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    summary = None
    if len(lines) == 1:
        try:
            summary = _json.loads(lines[0])
        except _json.JSONDecodeError:
            summary = None
    check(summary is not None, "one parseable JSON summary row")
    if summary:
        check("BENCH_r03.json" in summary["failed"],
              "planted failed artifact classified")
        check(any(r["artifact"] == "BENCH_r02.json"
                  for r in summary["regressions"]),
              "planted regression flagged")

    print("trace selftest " + ("FAILED" if failures else "PASSED"))
    return 1 if failures else 0


def cmd_lint(argv):
    """``python -m paddle_tpu --lint <config.py> [--strict] [--json]
    [--levels program,jaxpr,hlo]``: build a model-config script's
    Program and run the static-analysis engine over it — program-level
    IR checks, the traced-jaxpr checks, and the compiled-HLO checks
    (feeds and parameters are synthesized from declared shapes; no
    training step executes).  Prints one line per finding plus a
    summary; rc 1 when error-severity findings survive (rc 2 under
    --strict, where the AnalysisError message prints instead)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    p = argparse.ArgumentParser(prog="paddle_tpu --lint")
    p.add_argument("config",
                   help="model-config script: build() -> dict (the train "
                        "convention) or build_program() -> (main, "
                        "startup, fetch_list) (the examples/ convention)")
    p.add_argument("--strict", action="store_true",
                   help="raise on error-severity findings (rc 2)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="print the full report as one JSON object")
    p.add_argument("--levels", default="program,jaxpr,hlo",
                   help="comma-separated artifact levels to run")
    p.add_argument("--hbm-budget", type=int, default=None,
                   help="device memory budget in bytes for the "
                        "hlo.hbm-preflight check (defaults to the "
                        "device's reported limit; CPU reports none, so "
                        "pass the target chip's HBM to preflight a "
                        "capacity config off-accelerator)")
    args = p.parse_args([a for a in argv if a != "--lint"])

    import json as _json

    from paddle_tpu import analysis

    mod = _load_config(args.config)
    if hasattr(mod, "build"):
        main_prog, _startup, outs = _build(mod)
        fetch = [outs["avg_cost"]] if "avg_cost" in outs else []
        fetch += [v for k, v in outs.items()
                  if k not in ("feed", "avg_cost") and hasattr(v, "name")]
    elif hasattr(mod, "build_program"):
        main_prog, _startup, fetch = mod.build_program()
    else:
        raise SystemExit(
            f"{args.config}: defines neither build() nor "
            f"build_program(); see python -m paddle_tpu --lint --help")
    levels = tuple(s.strip() for s in args.levels.split(",") if s.strip())
    try:
        report = analysis.lint(main_prog, fetch_list=fetch, levels=levels,
                               strict=args.strict,
                               hbm_budget=args.hbm_budget)
    except analysis.AnalysisError as e:
        print(e)
        return 2
    if args.as_json:
        # the schema-versioned output contract (stable keys, findings
        # sorted by severity/id) — CI consumers pin on schema_version
        # and round-trip via analysis.report_from_json
        print(_json.dumps(analysis.report_json(report, levels=levels)))
    else:
        for f in report:
            print(repr(f))
            if f.hint:
                print(f"    hint: {f.hint}")
        print("lint: " + report.summary())
    return 0 if report.ok else 1


def cmd_lint_selftest(args=None):
    """``python -m paddle_tpu --lint-selftest``: the static-analysis
    engine's CI gate, CPU-only — plants one Program per defect class
    (dead var/op, shape-dtype mismatch, read-before-write, fetch
    overwrite, bf16 accumulation, tanh-in-scan, scan-locality loss,
    degraded offload, >HBM-budget temp, in-loop collective on a
    2-device virtual mesh) and asserts the exact finding ids; asserts
    ZERO findings on the clean GPT benchmark program under every remat
    policy; asserts strict mode raises; and lints every ``examples/``
    script's program.  Wired into tools/tier1.sh."""
    n = 2
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    import jax

    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < n or jax.devices()[0].platform != "cpu":
        # backend already initialized without the virtual mesh: re-exec
        # clean, ONCE (the multichip-selftest convention)
        if os.environ.get("_PT_LINT_SELFTEST_CHILD"):
            print(f"FAIL cannot provision {n} cpu devices "
                  f"(have {len(jax.devices())} "
                  f"{jax.devices()[0].platform!r})")
            return 1
        import subprocess

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["_PT_LINT_SELFTEST_CHILD"] = "1"
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu", "--lint-selftest"],
            env=env, timeout=1800)
        return proc.returncode

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import analysis, layers
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel import api as papi
    from paddle_tpu.parallel.mesh import make_mesh

    failures = []

    def check(cond, what):
        (failures.append(what) if not cond else None)
        print(("ok   " if cond else "FAIL ") + what)

    # -- planted Program-level defects ---------------------------------
    pt.core.unique_name.reset()
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        x = layers.data("x", shape=[4])
        y = layers.fc(x, 2, name="live")
        layers.fc(x, 3, name="deadfc")  # dead op chain
        blk = main_prog.global_block()
        blk.create_var(name="orphan", shape=(3,), dtype="float32")
        a = blk.create_var(name="a", shape=(-1, 4), dtype="float32")
        b = blk.create_var(name="b", shape=(-1, 8), dtype="float32")
        c = blk.create_var(name="c", shape=(-1, 4), dtype="float32")
        blk.append_op("elementwise_add", {"X": [a.name], "Y": [b.name]},
                      {"Out": [c.name]})
        blk.append_op("relu", {"X": [x.name]}, {"Out": [y.name]})
    rep = analysis.lint(main_prog, fetch_list=[y], levels=("program",))
    ids = set(rep.ids())
    check("program.dead-code" in ids, "planted dead var/op reported")
    check("program.shape-dtype" in ids,
          "planted shape mismatch reported")
    check("program.read-before-write" in ids,
          "planted read-before-write reported")
    check("program.fetch-overwritten" in ids,
          "planted fetch overwrite reported")
    try:
        analysis.lint(main_prog, fetch_list=[y], levels=("program",),
                      strict=True)
        check(False, "strict mode raises AnalysisError")
    except analysis.AnalysisError:
        check(True, "strict mode raises AnalysisError")

    # -- planted jaxpr-level defects -----------------------------------
    def small_gpt(policy, n_layer=5):
        pt.core.unique_name.reset()
        mp, sp = pt.Program(), pt.Program()
        mp.random_seed = 7
        with pt.program_guard(mp, sp):
            outs = transformer.build(
                vocab_size=29, n_layer=n_layer, n_head=2, d_model=32,
                max_len=12, dropout_rate=0.0, dtype="float32")
        if policy:
            pt.memory_optimize(mp, policy=policy)
        return mp, outs["avg_cost"]

    mp, loss = small_gpt("selective")
    os.environ["PADDLE_TPU_SCAN_REMAT"] = "0"
    try:
        rep = analysis.lint(mp, fetch_list=[loss], levels=("jaxpr",),
                            layer_count=5)
    finally:
        os.environ.pop("PADDLE_TPU_SCAN_REMAT", None)
    check("jaxpr.scan-locality" in rep.ids(),
          "unrolled kernel calls (scan engine off) reported")

    pt.core.unique_name.reset()
    mp, sp = pt.Program(), pt.Program()
    with pt.program_guard(mp, sp):
        xb = layers.data("xb", shape=[16, 8], dtype="bfloat16")
        init = layers.reduce_mean(xb, dim=1)
        rnn = layers.StaticRNN(name="acc")
        with rnn.step():
            xt = rnn.step_input(xb)
            acc = rnn.memory(init)
            new = acc + xt
            rnn.update_memory(acc, new)
            rnn.step_output(new)
        tot = layers.reduce_sum(rnn())
    rep = analysis.lint(mp, fetch_list=[tot], levels=("jaxpr",))
    check("jaxpr.bf16-accum" in rep.ids(),
          "bf16 scan-carry accumulation reported")

    pt.core.unique_name.reset()
    mp, sp = pt.Program(), pt.Program()
    with pt.program_guard(mp, sp):
        xv = layers.data("x", shape=[16])
        h = xv
        for i in range(4):
            h = layers.fc(h, 16, act="tanh", name=f"l{i}")
        loss2 = layers.reduce_mean(layers.fc(h, 1, name="head"))
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss2)
    pt.memory_optimize(mp, policy="full")
    rep = analysis.lint(mp, fetch_list=[loss2], levels=("jaxpr",))
    check("jaxpr.tanh-gelu" in rep.ids(),
          "tanh inside scanned remat body reported")

    pt.core.unique_name.reset()
    mp, sp = pt.Program(), pt.Program()
    with pt.program_guard(mp, sp):
        xv = layers.data("x", shape=[16])
        h = layers.fc(xv, 12, act="relu", name="a1")
        h = layers.fc(h, 6, act="sigmoid", name="b1")
        loss3 = layers.reduce_mean(layers.fc(h, 1, name="c1"))
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss3)
    pt.memory_optimize(mp, policy="offload")
    rep = analysis.lint(mp, fetch_list=[loss3], levels=("jaxpr",))
    check("jaxpr.kernel-residual" in rep.ids(),
          "offload degraded on non-uniform program reported")

    # -- planted HLO-level defects -------------------------------------
    mp, loss = small_gpt(None)
    rep = analysis.lint(mp, fetch_list=[loss], levels=("hlo",),
                        hbm_budget=1)
    check("hlo.hbm-preflight" in rep.ids()
          and rep.by_check("hlo.hbm-preflight")[0].severity == "error",
          ">HBM-budget compiled step reported (static preflight)")

    fs = analysis.donation_findings(
        {"argument_bytes": 5 << 20, "alias_bytes": 0}, True)
    check([f.check for f in fs] == ["hlo.donation-alias"]
          and not analysis.donation_findings(
              {"argument_bytes": 5 << 20, "alias_bytes": 4 << 20}, True),
          "donated-buffer aliasing audit")

    pt.core.unique_name.reset()
    mp, sp = pt.Program(), pt.Program()
    with pt.program_guard(mp, sp):
        xv = layers.data("x", shape=[16, 8])
        init = layers.reduce_mean(xv, dim=[0, 1])
        rnn = layers.StaticRNN(name="acc")
        with rnn.step():
            xt = rnn.step_input(xv)
            acc = rnn.memory(init)
            s = layers.reduce_sum(xt, dim=0)
            new = acc + s
            rnn.update_memory(acc, new)
            rnn.step_output(new)
        tot = layers.reduce_sum(rnn())
    papi.data_parallel(mp, "dp", programs=(sp,))
    mesh = make_mesh({"dp": n})
    rep = analysis.lint(mp, fetch_list=[tot], mesh=mesh, levels=("hlo",))
    inloop = rep.by_check("hlo.inloop-collective")
    check(bool(inloop) and inloop[0].severity == "error",
          "planted in-loop collective reported on the virtual mesh")

    # -- clean program: the GPT benchmark program, zero findings -------
    rng = np.random.default_rng(5)
    toks = rng.integers(0, 29, (2, 12)).astype(np.int64)
    feed = {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}
    for policy in (None, "selective", "offload"):
        mp, loss = small_gpt(policy)
        rep = analysis.lint(mp, feed=feed, fetch_list=[loss],
                            layer_count=5)
        check(len(rep) == 0,
              f"clean GPT program (policy={policy}) has zero findings "
              f"({rep.ids()})")

    # -- every examples/ script lints clean ----------------------------
    import glob

    ex_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples")
    scripts = sorted(glob.glob(os.path.join(ex_dir, "*.py")))
    check(bool(scripts), f"examples/ scripts found ({len(scripts)})")
    for path in scripts:
        name = os.path.basename(path)
        try:
            mod = _load_config(path)
            mp, sp, fetch = mod.build_program()
            rep = analysis.lint(mp, fetch_list=fetch,
                                levels=("program",))
            check(len(rep.errors) == 0 and len(rep.warnings) == 0,
                  f"examples/{name} lints clean ({rep.ids()})")
        except Exception as e:  # noqa: BLE001
            check(False, f"examples/{name} lint crashed: "
                         f"{type(e).__name__}: {e}")

    print("lint selftest " + ("FAILED" if failures else "PASSED"))
    return 1 if failures else 0


def cmd_attribution_selftest(args=None):
    """``python -m paddle_tpu --attribution-selftest``: the per-op
    attribution engine + crash flight recorder's CI gate, CPU-only —
    the compiled GPT flagship-family step's attribution table must
    cover >= 95% of the executable's own cost-analysis flops with sane
    classes/shares and a tune-style workload key; the roofline
    estimate-vs-measured step-time error is REPORTED (the corpus
    quality figure — on CPU the roofline constants are nominal, so the
    value is informational, its presence is the contract); an injected
    NaN fault (``PADDLE_TPU_FAULT=nan_grad``, the PR-8 injection point)
    and a tripped watchdog each produce a loadable flight bundle
    containing the triggering step records; and a planted two-round
    bench-history fixture's >10% regression is ATTRIBUTED to the op
    class whose share moved.  Wired into tools/tier1.sh
    (docs/observability.md)."""
    import math
    import tempfile
    import time

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.models import transformer
    from paddle_tpu.observability import attribution as attr
    from paddle_tpu.observability import bench_history as bh
    from paddle_tpu.observability import flight

    failures = []

    def check(cond, what):
        (failures.append(what) if not cond else None)
        print(("ok   " if cond else "FAIL ") + what)

    # -- attribution table on the GPT flagship config ------------------
    # the flagship model FAMILY (transformer.build: flash attention,
    # fused CE head, scan-remat under memory_optimize) at CPU-sized
    # dims; ATTR_SELFTEST_* envs restore the full flagship shape on
    # real hardware
    n_layer = int(os.environ.get("ATTR_SELFTEST_LAYERS", "4"))
    d_model = int(os.environ.get("ATTR_SELFTEST_DMODEL", "64"))
    n_head = int(os.environ.get("ATTR_SELFTEST_HEADS", "2"))
    seq = int(os.environ.get("ATTR_SELFTEST_SEQ", "128"))
    vocab = int(os.environ.get("ATTR_SELFTEST_VOCAB", "512"))
    pt.core.unique_name.reset()
    main_prog, startup = pt.Program(), pt.Program()
    main_prog.random_seed = 7
    with pt.program_guard(main_prog, startup):
        outs = transformer.build(
            vocab_size=vocab, n_layer=n_layer, n_head=n_head,
            d_model=d_model, max_len=seq, dropout_rate=0.0,
            dtype="float32")
    pt.memory_optimize(main_prog, policy="selective")
    exe = pt.Executor()
    exe.run(startup)
    rng = np.random.default_rng(5)
    toks = rng.integers(0, vocab, (2, seq)).astype(np.int64)
    feed = {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}
    cost = exe.compile_only(main_prog, feed=feed,
                            fetch_list=[outs["avg_cost"]])
    att = exe.last_attribution
    check(att is not None and att.get("classes"),
          "compile produced exe.last_attribution")
    cov = (att or {}).get("coverage")
    check(cov is not None and cov >= 0.95,
          f"attribution covers >= 95% of compiled flops "
          f"(coverage={cov})")
    classes = (att or {}).get("classes", {})
    check("matmul" in classes and "pallas" in classes,
          f"table carries matmul + pallas kernel classes "
          f"({sorted(classes)})")
    share_sum = sum(r.get("share") or 0 for r in classes.values())
    check(abs(share_sum - 1.0) < 0.02,
          f"class shares sum to 1 ({share_sum:.4f})")
    check(all(r.get("bound") in ("compute", "memory")
              for r in classes.values()),
          "every class classified compute- or memory-bound")
    wk = (att or {}).get("workload") or ""
    check(wk.startswith("op=step|") and "remat=selective" in wk,
          f"tune-style workload key ({wk})")
    summ = (cost or {}).get("attribution") or {}
    check(bool(summ.get("top")) and summ.get("coverage") == cov,
          "compact summary rides last_step_cost (trainer JSONL channel)")

    # -- estimated vs measured step time -------------------------------
    exe.run(main_prog, feed=feed, fetch_list=[outs["avg_cost"]])
    t0 = time.perf_counter()
    steps = 3
    for _ in range(steps):
        exe.run(main_prog, feed=feed, fetch_list=[outs["avg_cost"]])
    measured = (time.perf_counter() - t0) / steps
    rec = attr.reconcile(att, measured)
    check(rec is not None and math.isfinite(rec["err_pct"]),
          f"estimated-vs-measured step-time error reported "
          f"(est {rec['est_ms'] if rec else '?'} ms vs measured "
          f"{rec['measured_ms'] if rec else '?'} ms, "
          f"err {rec['err_pct'] if rec else '?'}%)")

    # -- flight recorder: injected NaN + watchdog trips ----------------
    tmpd = tempfile.mkdtemp(prefix="pt_flight_")
    old_rec = flight.set_recorder(flight.FlightRecorder(out_dir=tmpd))
    try:
        pt.core.unique_name.reset()
        mp2, sp2 = pt.Program(), pt.Program()
        with pt.program_guard(mp2, sp2):
            x = layers.data("x", shape=[8])
            yv = layers.data("y", shape=[1])
            h = layers.fc(x, 8, act="relu")
            loss2 = layers.reduce_mean(
                layers.square(layers.fc(h, 1) - yv))
            pt.optimizer.SGD(learning_rate=0.1).minimize(loss2)
            trainer = pt.trainer.Trainer(loss2, [x, yv])
            rng2 = np.random.default_rng(0)

            def reader():
                for _ in range(4):
                    yield [(rng2.normal(size=(8,)).astype(np.float32),
                            rng2.normal(size=(1,)).astype(np.float32))
                           for _ in range(4)]

            os.environ["PADDLE_TPU_FAULT"] = "nan_grad:3"
            try:
                trainer.train(reader, num_passes=1)
            finally:
                os.environ.pop("PADDLE_TPU_FAULT", None)
        rec_obj = flight.get_recorder()
        nan_dumps = [p for p in rec_obj.dumps if "nan_trip" in p]
        check(bool(nan_dumps),
              f"injected nan_grad fault dumped a flight bundle "
              f"({rec_obj.dumps})")
        if nan_dumps:
            b = flight.load_bundle(nan_dumps[0])
            steps_in = b.get("steps", [])
            trig = [s for s in steps_in
                    if isinstance(s.get("loss"), float)
                    and math.isnan(s["loss"])]
            check(bool(trig),
                  f"bundle contains the triggering (NaN-loss) step "
                  f"({len(steps_in)} step records)")
            check(bool(b.get("grad_norm_window")),
                  f"bundle carries the grad-norm window "
                  f"({len(b.get('grad_norm_window', []))} entries)")
            check(b.get("reason") == "nan_trip" and b.get("spans")
                  is not None and b.get("metrics") is not None,
                  "bundle carries reason/spans/metrics")

        from paddle_tpu.resilience.watchdog import Watchdog

        wd = Watchdog(deadline=0.15, label="attr-selftest")
        time.sleep(0.8)
        wd.stop()
        wd_dumps = [p for p in flight.get_recorder().dumps
                    if "watchdog" in p]
        check(bool(wd_dumps),
              "watchdog trip dumped a loadable flight bundle")
        if wd_dumps:
            b = flight.load_bundle(wd_dumps[0])
            check(b.get("reason") == "watchdog"
                  and b.get("context", {}).get("age_s") is not None,
                  "watchdog bundle carries the stall age")
    finally:
        flight.set_recorder(old_rec)

    # -- regression attribution on a planted two-round fixture ---------
    import json as _json

    fixture = tempfile.mkdtemp(prefix="pt_attr_hist_")

    def _att_extra(shares):
        return {"classes": {c: {"flops": 1, "bytes": 1, "est_ms": s,
                                "share": s, "bound": "memory"}
                            for c, s in shares.items()},
                "workload": "op=step|t=16384|dh=128|h=6|dt=bfloat16"
                            "|plat=tpu|remat=auto",
                "coverage": 0.99, "est_ms_total": 1.0}

    rows_fx = [
        ("BENCH_r01.json", {"n": 1, "rc": 0, "parsed": {
            "metric": "gpt_train_tokens_per_sec_per_chip",
            "value": 100.0, "unit": "tok/s",
            "extra": {"gpt_attribution": _att_extra(
                {"matmul": 0.6, "elementwise": 0.3,
                 "collective.all-reduce": 0.1})}}}),
        ("BENCH_r02.json", {"n": 2, "rc": 0, "parsed": {
            "metric": "gpt_train_tokens_per_sec_per_chip",
            "value": 42.0, "unit": "tok/s",
            "extra": {"gpt_attribution": _att_extra(
                {"matmul": 0.35, "elementwise": 0.25,
                 "collective.all-reduce": 0.4})}}}),
    ]
    for name, data in rows_fx:
        with open(os.path.join(fixture, name), "w") as fh:
            _json.dump(data, fh)
    summary, _rows = bh.history(fixture)
    regs = summary["regressions"]
    check(bool(regs), "planted >10% regression flagged")
    ra = summary.get("regression_attribution", {})
    key = ("BENCH_r02.json:gpt_train_tokens_per_sec_per_chip")
    moved = ra.get(key) or []
    check(bool(moved) and moved[0]["op_class"]
          == "collective.all-reduce",
          f"regression attributed to the op class whose share moved "
          f"({[m['op_class'] for m in moved]})")

    print("attribution selftest " + ("FAILED" if failures else "PASSED"))
    return 1 if failures else 0


def cmd_tune_selftest(args=None):
    """``python -m paddle_tpu --tune-selftest``: the autotune engine's
    CI gate, CPU-only — a miniature measured schedule search over a toy
    transformer (the HBM preflight rejects over-budget candidates from
    compiled cost analysis alone, the winner beats the worst measured
    candidate), a second invocation is a pure cache hit with zero
    recompiles, ``PADDLE_TPU_TUNE=0`` is bit-exact vs the untuned
    defaults, and the t=16k flagship static prune rejects the BENCH_r05
    config while selecting a schedule with headroom
    (docs/autotune.md).  Wired into tools/tier1.sh."""
    from .tune.selftest import run_selftest

    return run_selftest()


def cmd_costmodel_selftest(args=None):
    """``python -m paddle_tpu --costmodel-selftest``: the learned cost
    model's CI gate (docs/observability.md "Cost model calibration") —
    two real CPU-measured toy-GPT runs seed the measurement corpus
    through the production MetricsReporter JSONL path (plus a bench
    artifact and a classified non-object artifact), the fitted
    roofline's holdout error must STRICTLY improve on the analytic
    model's recorded error over the same held-out rows, the t=16k
    flagship static prune under the fitted model still rejects the
    known-OOM BENCH_r05 config and selects the same known-good
    schedule, a corrupt/truncated/schema-mismatched model file each
    degrades cleanly to the analytic defaults, and
    ``PADDLE_TPU_COSTMODEL=0`` reproduces the no-model estimates
    bit-exact.  Wired into tools/tier1.sh."""
    from .tune.costmodel_selftest import run_selftest

    return run_selftest()


def cmd_kernels_selftest(args=None):
    """``python -m paddle_tpu --kernels-selftest``: the multi-backend
    kernel registry's CI gate (docs/kernels.md) — registry resolution
    and override precedence on this host, oracle parity for every
    available backend (plus the Mosaic kernels force-run in
    interpret mode) against the pure-XLA reference within the
    documented ``ORACLE_TOL`` bounds (f32+bf16, causal/non-causal,
    d_head 64/128, grads through the custom-vjp, run-to-run
    bit-exactness), the ``PADDLE_TPU_KERNEL_BACKEND=xla_ref`` GPT
    trainer path with zero Pallas calls under every memory_optimize
    policy, and the interpret-mode-in-timed-run lint finding planted
    and detected.  Wired into tools/tier1.sh."""
    from .kernels.selftest import run_selftest

    return run_selftest()


def cmd_sharding_selftest(args=None):
    """``python -m paddle_tpu --sharding-selftest``: the sharding &
    communication contract analyzer's CI gate — three planted
    constraint-placement violations (a symmetric fsdp pin, an
    fsdp-composed accumulation grad carry, a forbidden activation
    reshard) each caught with the right kind/axis/loop attribution on
    the 8-device CPU mesh; CommPlan mesh-axis recovery + phase
    classification + ``comm_diff``; and the clean-GPT sweep (every
    memory_optimize policy x FSDP on/off x ZeRO on/off) reporting zero
    error-severity comm findings under the attached training
    contracts (docs/analysis.md "Communication contracts")."""
    from .analysis.comm.selftest import run_selftest

    return run_selftest()


def cmd_resilience_selftest(args=None):
    """``python -m paddle_tpu --resilience-selftest``: the elastic
    resilience engine's CI gate — a trainer subprocess on the 8-device
    virtual CPU mesh is SIGKILLed mid-pass via ``PADDLE_TPU_FAULT``,
    resumes from its latest loadable full-state checkpoint (params +
    optimizer moments + RNG key + reader cursor), and must reproduce
    the uninterrupted loss trajectory BIT-EXACT; a second child crashes
    DURING checkpoint publish (between the two renames) and the torn
    checkpoint must still load via the ``.old`` fallback, train-state
    sidecar included.  The parent spawns the jax children and never
    initializes a backend itself (docs/resilience.md)."""
    from .resilience.selftest import run_selftest

    return run_selftest()


def cmd_spec_selftest(args=None):
    """``python -m paddle_tpu --spec-selftest``: speculative decoding's
    CI gate, CPU-only — a depth-pruned draft engine emits TOKEN-EXACT
    output vs single-stream greedy (f32 + bf16, prefix reuse on/off); a
    self-draft run's acceptance rate near 1 proves the parallel verify
    window bit-consistent with the sequential decode step; an
    adversarial draft (different random init) still yields exact output
    with >= 1 committed token per round; propose/rollback leaves
    ``blocks_in_use`` at the plain engine's baseline (zero scratch
    leak); and ``PADDLE_TPU_SPEC=0`` with a draft passed is bit-exact
    with zero spec metrics (docs/serving.md "Speculative decoding").
    Wired into tools/tier1.sh."""
    from .serving.spec_selftest import run_selftest

    return run_selftest()


def main(argv=None):
    from .flags import init_flags

    argv = list(sys.argv[1:] if argv is None else argv)
    argv = init_flags(argv)
    if "--metrics-selftest" in argv:
        return cmd_metrics_selftest()
    if "--memory-selftest" in argv:
        return cmd_memory_selftest()
    if "--multichip-selftest" in argv:
        return cmd_multichip_selftest()
    if "--lint-selftest" in argv:
        return cmd_lint_selftest()
    if "--sharding-selftest" in argv:
        return cmd_sharding_selftest()
    if "--trace-selftest" in argv:
        return cmd_trace_selftest()
    if "--resilience-selftest" in argv:
        return cmd_resilience_selftest()
    if "--tune-selftest" in argv:
        return cmd_tune_selftest()
    if "--kernels-selftest" in argv:
        return cmd_kernels_selftest()
    if "--costmodel-selftest" in argv:
        return cmd_costmodel_selftest()
    if "--attribution-selftest" in argv:
        return cmd_attribution_selftest()
    if "--spec-selftest" in argv:
        return cmd_spec_selftest()
    if "--bench-history" in argv:
        return cmd_bench_history(argv)
    if "--lint" in argv:
        return cmd_lint(argv)

    p = argparse.ArgumentParser(prog="paddle_tpu")
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("train", help="train a model-config script")
    sp.add_argument("--job", choices=["train", "checkgrad"],
                    default="train",
                    help="checkgrad: finite-difference the whole model's "
                         "gradients on one batch instead of training")
    sp.add_argument("config")
    sp.add_argument("--batch-size", type=int, default=64)
    sp.add_argument("--num-passes", type=int, default=1)
    sp.add_argument("--log-period", type=int, default=10)
    sp.add_argument("--checkpoint-dir", default=None)
    sp.add_argument("--run-log", default=None,
                    help="write per-step telemetry JSONL (wall time, "
                         "throughput, MFU, compile counts) to this path")
    sp.set_defaults(fn=cmd_train)

    sp = sub.add_parser("pserver", help="run a parameter-server shard")
    sp.add_argument("--index", type=int, default=0)
    sp.add_argument("--port", type=int, default=0)
    sp.add_argument("--num-trainers", type=int, default=1)
    sp.add_argument("--async-sgd", action="store_true")
    sp.add_argument("--store", default=None,
                    help="FileStore root for discovery/checkpoint metadata")
    sp.add_argument("--checkpoint-dir", default=None)
    sp.add_argument("--checkpoint-every", type=int, default=0)
    sp.set_defaults(fn=cmd_pserver)

    sp = sub.add_parser("master", help="run the dataset task dispatcher")
    sp.add_argument("--port", type=int, default=0)
    sp.add_argument("--dataset", nargs="*", default=None,
                    help="recordio file globs")
    sp.add_argument("--chunks-per-task", type=int, default=1)
    sp.add_argument("--timeout", type=float, default=20.0)
    sp.add_argument("--store", default=None)
    sp.set_defaults(fn=cmd_master)

    sp = sub.add_parser("version")
    sp.set_defaults(fn=cmd_version)

    sp = sub.add_parser("dump_config", help="print a config's Program IR")
    sp.add_argument("config")
    sp.add_argument("--dot", action="store_true", help="graphviz output")
    sp.add_argument("--startup", action="store_true")
    sp.set_defaults(fn=cmd_dump_config)

    sp = sub.add_parser("merge_model")
    sp.add_argument("model_dir")
    sp.add_argument("output")
    sp.set_defaults(fn=cmd_merge_model)

    sp = sub.add_parser("bench")
    sp.set_defaults(fn=cmd_bench)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
