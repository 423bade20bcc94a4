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

``python -m paddle_tpu --lint <config.py>`` runs the static-analysis
engine over a config script's Program (see ``cmd_lint``).

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


def main(argv=None):
    from .flags import init_flags

    argv = list(sys.argv[1:] if argv is None else argv)
    argv = init_flags(argv)
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

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
