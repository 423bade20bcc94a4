"""One process of a 2-process CPU "multi-host" run (spawned by
test_distributed.py::test_multihost_two_process_cpu).  Each process joins
the JAX coordination service via paddle_tpu.distributed.launch, forms a
GLOBAL mesh spanning both processes' devices, checks a cross-process
collective, and runs two data-parallel Executor training steps — the
CPU-scale analog of the reference's multi-node trainers
(paddle/scripts/cluster_train_v2, --trainer_id flags).

Checkpoint modes (argv[4] = mode, argv[5] = ckpt dir) exercise the
multi-host sharded save/restore path on a model whose fc weight is
PARTITIONED over a tp axis that spans both processes (np.asarray on such
an array throws — io._ShardedSnap per-process shard files are the fix):

* ``ckpt_ref``    — train 3 steps straight through, print final state;
* ``ckpt_save``   — train 1 step, save_persistables (each process writes
                    its shard file), barrier, train 2 more, print final;
* ``ckpt_resume`` — fresh processes: startup, load_persistables (each
                    process reads only ITS shard file), train 2 steps,
                    print final.  Must equal both runs above bit-for-bit.

The ``ckpt_resume_midpass`` family (ISSUE 8, ROADMAP item 4's gate at
multi-host scale) upgrades this to kill-and-resume with FULL state
(``io.save_checkpoint`` + the resilience train-state sidecar carrying
the RNG key and step counter):

* ``ckpt_mid_ref``    — 4 steps straight through, print final state;
* ``ckpt_mid_kill``   — 2 steps, full-state checkpoint (per-process
                        shard files + proc-0 train-state), barrier, then
                        SIGKILL OWN PID — both ranks die mid-pass, no
                        unwinding (the parent expects rc == -SIGKILL);
* ``ckpt_mid_resume`` — fresh processes restore persistables + train
                        state + RNG, run the remaining 2 steps, print
                        final.  Must equal ``ckpt_mid_ref`` bit-for-bit.
"""

import os
import sys


def _tp_model_and_exe(launch, pt, total):
    """fc model with the weight column-sharded over a tp axis that spans
    the two processes (device-order axis 0), data-parallel over dp."""
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import api as papi

    mesh = launch.global_mesh({"tp": 2, "dp": total // 2})
    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        x = pt.layers.data("x", shape=[8], dtype="float32")
        y = pt.layers.data("y", shape=[4], dtype="float32")
        h = pt.layers.fc(x, size=16, act="relu")
        pred = pt.layers.fc(h, size=4)
        cost = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.Momentum(learning_rate=0.05, momentum=0.9).minimize(
            cost)
    papi.data_parallel(main_p, "dp", programs=(startup,))
    papi.shard_parameters_by_rule(main_p, [(r"fc_0\.w", P(None, "tp"))])
    papi.shard_parameters_by_rule(startup, [(r"fc_0\.w", P(None, "tp"))])
    scope = pt.Scope()
    exe = pt.Executor(mesh=mesh)
    return main_p, startup, cost, scope, exe, mesh


def _state_digest(scope, names):
    """Order-stable digest of (possibly partitioned) state: dense parts
    via np.asarray, partitioned parts via the io snapshot helper."""
    import hashlib

    import numpy as np

    from paddle_tpu.io import _host_snapshot, _ShardedSnap

    h = hashlib.sha256()
    for n in names:
        snap = _host_snapshot(scope.get(n))
        if isinstance(snap, _ShardedSnap):
            for key, data in sorted(snap.shards.items()):
                h.update(str(key).encode())
                h.update(np.ascontiguousarray(data).tobytes())
        else:
            h.update(np.ascontiguousarray(snap).tobytes())
    return h.hexdigest()


def _ckpt_mode(mode, ckpt_dir, coordinator, nproc, pid):
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.distributed import launch

    launch.init_multihost(coordinator=coordinator, num_processes=nproc,
                          process_id=pid)
    total = jax.device_count()
    local = jax.local_device_count()
    main_p, startup, cost, scope, exe, mesh = _tp_model_and_exe(
        launch, pt, total)
    exe.run(startup, scope=scope)

    # the tp-sharded weight really is cross-process partitioned
    w = scope.get("fc_0.w")
    assert not w.is_fully_addressable and not w.is_fully_replicated, (
        w.sharding)
    print(f"[{pid}] fc_0.w sharding {w.sharding}", flush=True)

    # the batch shards over dp only, and dp here is WITHIN-process (tp is
    # the axis crossing processes) — so each process's local portion of
    # the global batch is the WHOLE batch: both processes must feed
    # identical data, or the two tp halves silently train on different
    # batches and replicated state diverges across ranks
    rng = np.random.RandomState(0)
    dp = total // 2
    xs = rng.randn(4 * dp, 8).astype(np.float32)
    ys = np.tile(xs.sum(axis=1, keepdims=True) * 0.1, (1, 4)).astype(
        np.float32)
    feed = {"x": xs, "y": ys}

    def step():
        (l,) = exe.run(main_p, feed=feed, fetch_list=[cost], scope=scope)
        return float(np.asarray(l))

    def barrier():
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("ckpt")

    pnames = sorted(p.name for p in main_p.all_parameters())
    if mode == "ckpt_mid_ref":
        for _ in range(4):
            loss = step()
    elif mode == "ckpt_mid_kill":
        import signal

        import paddle_tpu.io as io

        for _ in range(2):
            loss = step()
        with pt.core.scope.scope_guard(scope):
            io.save_checkpoint(exe, ckpt_dir, main_p, train_state={
                "global_step": 2, "pass_id": 0, "step_in_pass": 2,
                "rng_key": np.asarray(scope.get(pt.core.scope.RNG_VAR)),
            })
        barrier()  # every rank's shard files + markers are on disk
        print(f"MULTIHOST_KILL_READY {pid}", flush=True)
        os.kill(os.getpid(), signal.SIGKILL)
    elif mode == "ckpt_mid_resume":
        import paddle_tpu.io as io
        from paddle_tpu.resilience import checkpoint as rckpt

        with pt.core.scope.scope_guard(scope):
            io.load_persistables(exe, ckpt_dir, main_p)
        st = rckpt.load_train_state(ckpt_dir)
        assert st["global_step"] == 2, st
        scope.set(pt.core.scope.RNG_VAR,
                  jnp.asarray(np.asarray(st["rng_key"])))
        for _ in range(4 - st["global_step"]):
            loss = step()
    elif mode == "ckpt_ref":
        for _ in range(3):
            loss = step()
    elif mode == "ckpt_save":
        step()
        import paddle_tpu.io as io

        with pt.core.scope.scope_guard(scope):
            io.save_persistables(exe, ckpt_dir, main_p)
        barrier()
        for _ in range(2):
            loss = step()
    elif mode == "ckpt_resume":
        import paddle_tpu.io as io

        with pt.core.scope.scope_guard(scope):
            io.load_persistables(exe, ckpt_dir, main_p)
        for _ in range(2):
            loss = step()
    else:
        raise SystemExit(f"unknown mode {mode}")
    names = pnames
    if mode.startswith("ckpt_mid"):
        # the midpass gate digests EVERY persistable — momentum state
        # included, so a resume that lost optimizer moments cannot pass
        # on params alone
        names = sorted(
            v.name for v in main_p.global_block().vars.values()
            if v.persistable and scope.find_var(v.name) is not None)
    digest = _state_digest(scope, names)
    print(f"MULTIHOST_CKPT_OK {pid} loss={loss:.8f} state={digest}",
          flush=True)


def main():
    coordinator, nproc, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    mode = sys.argv[4] if len(sys.argv) > 4 else "train"
    if mode != "train":
        return _ckpt_mode(mode, sys.argv[5], coordinator, nproc, pid)

    import jax

    jax.config.update("jax_platforms", "cpu")

    from paddle_tpu.distributed import launch

    launch.init_multihost(coordinator=coordinator, num_processes=nproc,
                          process_id=pid)
    assert jax.process_count() == nproc, jax.process_count()
    local = jax.local_device_count()
    total = jax.device_count()
    assert total == nproc * local, (total, local)
    print(f"[{pid}] devices local={local} global={total}", flush=True)

    # cross-process collective over the global mesh
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = launch.global_mesh({"dp": total})

    @jax.jit
    def global_sum():
        def f():
            return jax.lax.psum(
                jnp.ones((), jnp.float32), "dp")

        return shard_map(f, mesh=mesh, in_specs=(), out_specs=P())()

    s = float(global_sum())
    assert s == float(total), s
    print(f"[{pid}] psum over dp = {s}", flush=True)

    # data-parallel Executor training: each process feeds its LOCAL batch
    # shard; the Executor assembles the global array over the dp mesh.
    import paddle_tpu as pt
    from paddle_tpu.parallel import api as papi

    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        x = pt.layers.data("x", shape=[8], dtype="float32")
        y = pt.layers.data("y", shape=[1], dtype="float32")
        pred = pt.layers.fc(x, size=1)
        cost = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.SGD(learning_rate=0.1).minimize(cost)
    papi.data_parallel(main_p, "dp", programs=(startup,))

    scope = pt.Scope()
    exe = pt.Executor(mesh=mesh)
    exe.run(startup, scope=scope)

    rng = np.random.RandomState(0)  # same seed: deterministic global data
    xs = rng.randn(4 * total, 8).astype(np.float32)
    ys = (xs.sum(axis=1, keepdims=True) * 0.1).astype(np.float32)
    lo = pid * 4 * local
    xs_local, ys_local = xs[lo:lo + 4 * local], ys[lo:lo + 4 * local]
    losses = []
    for _ in range(2):
        (l,) = exe.run(main_p, feed={"x": xs_local, "y": ys_local},
                       fetch_list=[cost], scope=scope)
        losses.append(float(np.asarray(l)))
    assert np.isfinite(losses).all(), losses
    assert losses[1] < losses[0], losses
    # params are replicated over the global mesh -> fully addressable here
    w = np.asarray(scope.get("fc_0.w"))
    print(f"MULTIHOST_OK {pid} loss={losses[1]:.8f} wsum={float(w.sum()):.8f}",
          flush=True)


if __name__ == "__main__":
    main()
