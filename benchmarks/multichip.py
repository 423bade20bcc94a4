"""Multi-chip scaling benchmark — the ZeRO-1 / comm-aware-accumulation
engine measured end to end on a device mesh.

Runs the transformer flagship (and, in full mode, ResNet and a dp x tp
mesh) at dp=1 and dp=N through the real Executor and reports per-device
step time, the compiled step's collective op counts/bytes (split by
loop membership — ``analysis.hlo_tools.comm_report``), optimizer-state bytes
per device under ZeRO-1 vs replicated, and weak-scaling efficiency.

Emits exactly ONE parseable JSON line on stdout (everything else goes to
stderr; failures land as ``"error"`` / ``"gate_<name>": "FAILED: ..."``
fields and the row still prints).  ``--smoke`` additionally GATES the
structural facts that are deterministic on the virtual CPU mesh:

* ``gate_zero_sharding``   — accumulator arrays really are dp-sharded
  (``optimizer_state_report`` + the live Adam moment's NamedSharding);
* ``gate_one_reduce_per_step`` — under ``--accum`` the compiled HLO has
  ZERO reduce-class collectives inside loop bodies and a non-empty
  boundary reduce set (one cross-chip gradient reduction per OPTIMIZER
  step, not per microbatch), with the executor's accumulation plan in
  ``local`` mode;
* ``gate_state_bytes``     — optimizer-state bytes/device <= replicated/4;
* ``gate_fsdp_param_sharding`` — on the dp x fsdp=4 mesh the scan-stacked
  per-layer weights shard at rest (``param_bytes_per_device`` <=
  replicated / (fsdp_degree/2)), the weight all-gathers sit INSIDE the
  scan-remat loop, and reduce-class collectives stay out of loop bodies
  (one gradient reduction per optimizer step, docs/parallel.md "FSDP");
* ``gate_zero3_grad_rs``    — under the default PADDLE_TPU_ZERO3_RS
  spelling ``grad_bytes_per_device`` sits STRICTLY below the replicated
  figure (and <= replicated / (fsdp_degree/2)) with a non-empty
  boundary reduce class — the true-ZeRO-3 reduce-scatter win
  (docs/parallel.md rule 4).  ``boundary_comm_bytes`` /
  ``grad_bytes_per_device`` ship in the row.

Step times on the virtual CPU mesh share host cores and are indicative
only; the gates are the contract.

Self-provisioning: run as a script with no initialized jax backend it
pins ``JAX_PLATFORMS=cpu`` and ``--xla_force_host_platform_device_count``
itself; from a process whose backend is already up with too few CPU
devices it re-execs into a clean subprocess (the dryrun_multichip
convention).

Usage:
    python benchmarks/multichip.py --smoke
    python benchmarks/multichip.py --devices 8 --steps 5 --accum 4
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _devices_ready(n):
    """True when this process already exposes >= n CPU devices."""
    if "jax" not in sys.modules:
        return False
    try:
        import jax
        from jax._src import xla_bridge

        if not xla_bridge._backends:
            return False
        devs = jax.devices()
        return len(devs) >= n and devs[0].platform == "cpu"
    except Exception:
        return False


def _backend_initialized():
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge._backends)
    except Exception:
        return False


def _provision_env(n):
    """Pin an n-device virtual CPU platform into THIS process's env —
    only valid before the jax backend initializes."""
    from paddle_tpu.parallel.api import enable_comm_overlap

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    enable_comm_overlap("cpu")  # PADDLE_TPU_COMM_OVERLAP knob (no-op here)
    import jax

    jax.config.update("jax_platforms", "cpu")


def _reexec(argv):
    """Fresh-subprocess fallback: the current backend cannot provide the
    mesh (e.g. one real accelerator chip).  Mirrors dryrun_multichip."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONSAFEPATH", None)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [here] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)] + argv,
        env=env, cwd=here, capture_output=True, text=True, timeout=1800)
    if proc.stdout:
        sys.stdout.write(proc.stdout)
    if proc.stderr:
        sys.stderr.write(proc.stderr)
    return proc.returncode


# ---------------------------------------------------------------------------
def _build_gpt(cfg, accum):
    import paddle_tpu as pt
    from paddle_tpu.models import transformer

    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = 7
    with pt.program_guard(main, startup):
        outs = transformer.build(
            vocab_size=cfg["vocab"], n_layer=cfg["n_layer"],
            n_head=cfg["n_head"], d_model=cfg["d_model"],
            max_len=cfg["seq"], dropout_rate=0.0, dtype="float32",
            learning_rate=1e-2)
    if accum > 1:
        pt.gradient_accumulation(main, accum)
    return main, startup, outs


def _timed(exe, prog, feed, fetch, scope, steps, warmup):
    for _ in range(warmup):
        exe.run(prog, feed=feed, fetch_list=fetch, scope=scope)
    t0 = time.perf_counter()
    cost = None
    for _ in range(steps):
        cost = exe.run(prog, feed=feed, fetch_list=fetch, scope=scope)
    dt = (time.perf_counter() - t0) / steps
    assert np.isfinite(np.asarray(cost[0])).all(), cost
    return dt * 1e3, float(np.asarray(cost[0]).reshape(-1)[0])


def _gpt_feed(cfg, batch, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg["vocab"], (batch, cfg["seq"])).astype(
        np.int64)
    lbls = np.roll(toks, -1, axis=1)
    lbls[:, -1] = -1
    return {"tokens": toks, "labels": lbls}


def _train_gpt(cfg, mesh, n_chips, accum, steps, warmup, tp_rules=False,
               fsdp=False):
    """One measured config; returns (step_ms, facts) where facts carries
    the compiled step's comm/accum/state accounting.  ``fsdp=True``
    additionally marks remat segments (the scan-remat body is where the
    in-loop weight gathers live) and tags the per-layer weights for
    fsdp sharding."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.parallel import api as papi

    main, startup, outs = _build_gpt(cfg, accum)
    if fsdp:
        pt.memory_optimize(main, policy="selective")
    if mesh is not None:
        papi.data_parallel(main, "dp", programs=(startup,))
        if tp_rules:
            from paddle_tpu.models import transformer

            for prog in (main, startup):
                papi.shard_parameters_by_rule(prog, transformer.tp_rules())
        if fsdp:
            papi.shard_fsdp(main, programs=(startup,))
    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe = pt.Executor(mesh=mesh)
        exe.run(startup, scope=scope)
        feed = _gpt_feed(cfg, cfg["per_dev_batch"] * n_chips)
        step_ms, cost = _timed(
            exe, main, feed, [outs["avg_cost"]], scope, steps, warmup)
        sc = exe.last_step_cost or {}
        facts = {
            "cost": round(cost, 6),
            "collective_op_kinds": sc.get("collective_op_kinds"),
            "collective_bytes": sc.get("collective_bytes"),
            "reduce_ops": sc.get("reduce_ops"),
            "reduce_bytes": sc.get("reduce_bytes"),
            "reduce_ops_in_loop": sc.get("reduce_ops_in_loop"),
            "collectives_in_loop": sc.get("collectives_in_loop"),
            "accum_plan": sc.get("accum_comm"),
            "compiled_peak_bytes": sc.get("compiled_peak_bytes"),
        }
        if fsdp:
            facts["remat_plan"] = list(
                getattr(exe, "last_remat_plan", []) or [])
        if mesh is not None:
            srep = papi.sharding_report(main, mesh)
            facts["param_bytes_replicated"] = (
                srep["params"]["total_bytes"])
            facts["param_bytes_per_device"] = (
                srep["params"]["per_device_bytes"])
            # true-ZeRO-3 comm facts (docs/parallel.md rule 4): each
            # chip receives only its grad shard, so grads/device drop
            # with fsdp_degree and the boundary reduce class runs at
            # shard volume instead of full parameter volume
            facts["grad_bytes_replicated"] = (
                srep["grads"]["total_bytes"])
            facts["grad_bytes_per_device"] = (
                srep["grads"]["per_device_bytes"])
            plan = getattr(exe, "last_comm_plan", None)
            if plan is not None:
                facts["boundary_comm_bytes"] = sum(
                    op.bytes for op in plan.select(kind="reduce",
                                                   in_loop=False))
            rep = srep["opt_state"]
            facts["opt_state_bytes_replicated"] = rep["total_bytes"]
            facts["opt_state_bytes_per_device"] = rep["per_device_bytes"]
            facts["opt_state_sharded_vars"] = rep["sharded_vars"]
            moments = sorted(
                n for n in (v.name for v in
                            main.global_block().vars.values())
                if n.endswith("_moment1"))
            if moments:
                arr = scope.get(moments[0])
                facts["moment_sharding"] = str(
                    getattr(arr, "sharding", None))
        return step_ms, facts
    finally:
        pt.core.scope._scope_stack.pop()


def _train_resnet(mesh, n_chips, steps, warmup):
    import paddle_tpu as pt
    from paddle_tpu.models import resnet
    from paddle_tpu.parallel import api as papi

    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        outs = resnet.build(depth=50, class_dim=16, image_shape=(3, 32, 32),
                            dtype="float32")
    if mesh is not None:
        papi.data_parallel(main, "dp", programs=(startup,))
    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe = pt.Executor(mesh=mesh)
        exe.run(startup, scope=scope)
        batch = 2 * n_chips
        rng = np.random.default_rng(0)
        feed = {
            "img": rng.random((batch, 3, 32, 32)).astype(np.float32),
            "label": rng.integers(0, 16, (batch, 1)).astype(np.int64),
        }
        step_ms, _cost = _timed(
            exe, main, feed, [outs["avg_cost"]], scope, steps, warmup)
        sc = exe.last_step_cost or {}
        facts = {"collective_op_kinds": sc.get("collective_op_kinds"),
                 "collective_bytes": sc.get("collective_bytes"),
                 "reduce_ops_in_loop": sc.get("reduce_ops_in_loop")}
        if mesh is not None:
            rep = papi.optimizer_state_report(main, mesh)
            facts["opt_state_bytes_replicated"] = rep["total_bytes"]
            facts["opt_state_bytes_per_device"] = rep["per_device_bytes"]
        return step_ms, facts
    finally:
        pt.core.scope._scope_stack.pop()


# ---------------------------------------------------------------------------
def run(row, devices=8, smoke=True, steps=None, warmup=None, accum=4,
        models=("transformer",)):
    """Fill ``row`` in place; returns the list of failed gate names."""
    import jax
    from paddle_tpu.parallel.mesh import make_mesh

    n = devices
    steps = steps or (2 if smoke else 5)
    warmup = warmup if warmup is not None else (1 if smoke else 2)
    cfg = ({"vocab": 256, "n_layer": 2, "n_head": 2, "d_model": 64,
            "seq": 32, "per_dev_batch": max(4, accum)}
           if smoke else
           {"vocab": 1024, "n_layer": 4, "n_head": 4, "d_model": 128,
            "seq": 64, "per_dev_batch": max(4, accum)})
    row.update(devices=n, accum=accum, steps=steps,
               model=f"gpt_l{cfg['n_layer']}_d{cfg['d_model']}"
                     f"_t{cfg['seq']}",
               per_device_batch=cfg["per_dev_batch"])
    failed = []

    def gate(name, fn):
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — isolation is the point
            row[f"gate_{name}"] = (
                "FAILED: " + " ".join(f"{type(e).__name__}: {e}"
                                      .split())[:300])
            failed.append(name)

    mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])

    if "transformer" in models:
        log(f"transformer dp=1 (accum={accum}) ...")
        t1, f1 = _train_gpt(cfg, None, 1, accum, steps, warmup)
        row["dp1_step_ms"] = round(t1, 1)
        log(f"transformer dp={n} ZeRO (accum={accum}) ...")
        tn, fn_ = _train_gpt(cfg, mesh, n, accum, steps, warmup)
        row["dp_step_ms"] = round(tn, 1)
        # weak scaling: global batch grows n x at constant per-device
        # batch, so perfect scaling keeps the step time flat
        row["scaling_efficiency"] = round(t1 / tn, 3) if tn else None
        row["dp1_cost"] = f1["cost"]
        # param_bytes_* are the FSDP gate's facts: param_bytes_per_device
        # is the sharded figure, so the dp-only run's (fully replicated)
        # values must never ship under the same metric name
        row.update({k: v for k, v in fn_.items()
                    if k not in ("cost", "param_bytes_per_device",
                                 "param_bytes_replicated",
                                 "grad_bytes_per_device",
                                 "grad_bytes_replicated",
                                 "boundary_comm_bytes",
                                 "remat_plan")})
        row["dp_cost"] = fn_["cost"]

        def _gate_zero():
            assert row.get("opt_state_sharded_vars", 0) > 0, row
            assert "'dp'" in (row.get("moment_sharding") or ""), (
                f"moment not dp-sharded: {row.get('moment_sharding')}")

        def _gate_one_reduce():
            plan = row.get("accum_plan") or {}
            assert plan.get("mode") == "local", plan
            assert row.get("reduce_ops_in_loop") == 0, row
            assert (row.get("reduce_ops") or 0) > 0, row

        def _gate_bytes():
            per = row.get("opt_state_bytes_per_device")
            total = row.get("opt_state_bytes_replicated")
            assert per and total and per * 4 <= total, (per, total)

        gate("zero_sharding", _gate_zero)
        if accum > 1:
            gate("one_reduce_per_step", _gate_one_reduce)
        gate("state_bytes", _gate_bytes)

        if n % 4 == 0:
            # FSDP / ZeRO-3: dp x fsdp=4 mesh, per-layer weights
            # sharded at rest, gathered one layer at a time inside the
            # scan-remat body (docs/parallel.md "FSDP")
            fsdp_deg = 4
            log(f"transformer dp={n // fsdp_deg} x fsdp={fsdp_deg} "
                f"(accum={accum}) ...")
            mesh_f = make_mesh({"dp": n // fsdp_deg, "fsdp": fsdp_deg},
                               devices=jax.devices()[:n])
            tfs, ffs = _train_gpt(cfg, mesh_f, n, accum, steps, warmup,
                                  fsdp=True)
            row["dp_fsdp_step_ms"] = round(tfs, 1)
            row["fsdp_degree"] = fsdp_deg
            row["param_bytes_per_device"] = ffs.get(
                "param_bytes_per_device")
            row["param_bytes_replicated"] = ffs.get(
                "param_bytes_replicated")
            row["fsdp_reduce_ops_in_loop"] = ffs.get(
                "reduce_ops_in_loop")
            row["fsdp_gathers_in_loop"] = (
                (ffs.get("collectives_in_loop") or 0)
                - (ffs.get("reduce_ops_in_loop") or 0))
            row["fsdp_groups"] = sum(
                1 for g in ffs.get("remat_plan", ()) if g.get("fsdp"))
            row["grad_bytes_per_device"] = ffs.get(
                "grad_bytes_per_device")
            row["grad_bytes_replicated"] = ffs.get(
                "grad_bytes_replicated")
            row["boundary_comm_bytes"] = ffs.get("boundary_comm_bytes")

            def _gate_fsdp():
                per = row.get("param_bytes_per_device")
                total = row.get("param_bytes_replicated")
                # the acceptance bound: <= replicated / (fsdp_degree/2)
                assert per and total and per * (fsdp_deg // 2) <= total, (
                    per, total)
                assert row["fsdp_groups"] > 0, ffs.get("remat_plan")
                assert row["fsdp_gathers_in_loop"] > 0, row
                if accum > 1:
                    assert row["fsdp_reduce_ops_in_loop"] == 0, row
                    plan = ffs.get("accum_plan") or {}
                    assert plan.get("mode") == "local", plan

            def _gate_grad_rs():
                # true ZeRO-3: reduce-scatter at the boundary means
                # grads/device sit STRICTLY below the replicated figure
                per = row.get("grad_bytes_per_device")
                total = row.get("grad_bytes_replicated")
                assert per and total and per < total, (per, total)
                assert per * (fsdp_deg // 2) <= total, (per, total)
                assert (row.get("boundary_comm_bytes") or 0) > 0, row

            gate("fsdp_param_sharding", _gate_fsdp)
            gate("zero3_grad_rs", _gate_grad_rs)

        if not smoke and n % 2 == 0:
            log(f"transformer dp={n // 2} x tp=2 ...")
            mesh_tp = make_mesh({"dp": n // 2, "tp": 2},
                                devices=jax.devices()[:n])
            ttp, ftp = _train_gpt(cfg, mesh_tp, n, accum, steps, warmup,
                                  tp_rules=True)
            row["dp_tp_step_ms"] = round(ttp, 1)
            row["dp_tp_reduce_ops_in_loop"] = ftp.get("reduce_ops_in_loop")
            row["dp_tp_collective_bytes"] = ftp.get("collective_bytes")

    if "resnet" in models and not smoke:
        log("resnet dp=1 ...")
        r1, _ = _train_resnet(None, 1, steps, warmup)
        log(f"resnet dp={n} ...")
        rn, rfacts = _train_resnet(mesh, n, steps, warmup)
        row["resnet_dp1_step_ms"] = round(r1, 1)
        row["resnet_dp_step_ms"] = round(rn, 1)
        row["resnet_scaling_efficiency"] = (
            round(r1 / rn, 3) if rn else None)
        row["resnet_opt_state_bytes_per_device"] = rfacts.get(
            "opt_state_bytes_per_device")
        row["resnet_opt_state_bytes_replicated"] = rfacts.get(
            "opt_state_bytes_replicated")
    return failed


def run_smoke(devices=8):
    """In-process smoke row (used by __graft_entry__.dryrun_multichip so
    its row carries scaling numbers, not just OK).  The caller
    guarantees >= ``devices`` CPU devices.  Always returns a row; gate
    failures are recorded in it."""
    row = {"metric": "multichip_scaling", "mode": "smoke"}
    try:
        run(row, devices=devices, smoke=True)
    except Exception as e:  # noqa: BLE001 — the row must still carry why
        row["error"] = f"{type(e).__name__}: {e}"[:300]
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config + structural gates (ZeRO sharding, "
                    "one reduce per optimizer step, state bytes/device)")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--accum", type=int, default=4)
    ap.add_argument("--models", default="transformer,resnet")
    args = ap.parse_args(argv)

    if not _devices_ready(args.devices):
        if _backend_initialized():
            return _reexec(list(argv if argv is not None
                                else sys.argv[1:]))
        _provision_env(args.devices)

    row = {"metric": "multichip_scaling",
           "mode": "smoke" if args.smoke else "full"}
    models = [m for m in args.models.split(",") if m]
    if args.smoke:
        models = ["transformer"]
    try:
        failed = run(row, devices=args.devices, smoke=args.smoke,
                     steps=args.steps, accum=args.accum, models=models)
    except Exception as e:  # noqa: BLE001 — the row must still print
        row["error"] = f"{type(e).__name__}: {e}"[:300]
        print(json.dumps(row))
        raise
    print(json.dumps(row))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
