"""The child processes of test_resilience.py's SIGKILL tests (spawned by
``_run_child`` there, one fresh process a mode, on an 8-device virtual
CPU mesh with single-threaded eigen so every child sums in one order):

* ``ref``    — 2 passes x 8 steps of a dp=8 data-parallel fc+dropout
  model, full-state checkpoints every 3 steps; writes each step's loss
  as ``float.hex()`` (bit-exact text) to ``losses_ref.txt``;
* ``crash``  — the same run under ``PADDLE_TPU_FAULT=sigkill:11``: killed
  entering step 10 (0-based), mid-pass 1, the async checkpoint writer
  dead mid-queue, no atexit;
* ``resume`` — the same command with ``resume=True``: finds the latest
  LOADABLE checkpoint, restores params + optimizer moments + RNG key +
  reader cursor, prints ``RESUMED_AT <step>`` and goes on;
* ``ckptcrash`` — saves twice to one directory under
  ``PADDLE_TPU_FAULT=ckpt_crash:2``: the second publish dies BETWEEN its
  two renames (``os._exit``, exit code 23), leaving ``latest.old`` as
  the only good copy;
* ``ckptverify`` — loads ``latest`` anyway (the ``.old`` fallback) and
  prints the restored digest and step.
"""

import hashlib
import os
import sys

PASSES = 2
STEPS_PER_PASS = 8
CKPT_EVERY = 3
KILL_AT = 11  # 1-based arrival: SIGKILL entering 0-based step 10


def _build_model(pt):
    """dp=8 data-parallel fc+dropout regression: dropout makes the
    trajectory depend on the @RNG@ key chain, so a resume that failed to
    restore RNG state forks visibly."""
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = 11
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", shape=[13], dtype="float32")
        y = pt.layers.data("y", shape=[1], dtype="float32")
        h = pt.layers.fc(x, size=16, act="relu")
        h = pt.layers.dropout(h, 0.3)
        pred = pt.layers.fc(h, size=1)
        cost = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.Momentum(learning_rate=0.05,
                              momentum=0.9).minimize(cost)
    return main, startup, cost, x, y


def _make_reader(np):
    """Deterministic 8-batches-per-pass reader (seeded per call, so every
    pass and every process draws identical data)."""
    def reader():
        rng = np.random.default_rng(7)
        X = rng.normal(size=(STEPS_PER_PASS * 16, 13)).astype(np.float32)
        W = rng.normal(size=(13, 1)).astype(np.float32)
        Y = (X @ W).astype(np.float32)
        for i in range(STEPS_PER_PASS):
            lo = i * 16
            yield list(zip(X[lo:lo + 16], Y[lo:lo + 16]))

    return reader


def _state_digest(pt, scope, program):
    """Order-stable digest over every persistable in the scope —
    params AND optimizer moments, so a resume that lost momentum state
    cannot sneak past on params alone."""
    import numpy as np

    h = hashlib.sha256()
    names = sorted(v.name for v in program.global_block().vars.values()
                   if v.persistable and scope.find_var(v.name) is not None)
    for name in names:
        h.update(name.encode())
        h.update(np.ascontiguousarray(np.asarray(scope.get(name))).tobytes())
    return h.hexdigest()


def _child_train(mode, workdir):
    """ref / crash / resume trainer child (8-device dp mesh)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel import api as papi

    assert len(jax.devices()) >= 8, jax.devices()
    mesh = make_mesh({"dp": 8})
    main, startup, cost, x, y = _build_model(pt)
    papi.data_parallel(main, "dp", programs=(startup,))

    losses = open(os.path.join(workdir, f"losses_{mode}.txt"), "w")

    def handler(ev):
        if type(ev).__name__ == "EndIteration":
            # float.hex(): lossless text round-trip, so "bit-exact" is a
            # string comparison in the parent
            losses.write(float(ev.cost).hex() + "\n")
            losses.flush()
            os.fsync(losses.fileno())  # SIGKILL must not eat lines

    with pt.program_guard(main, startup):
        tr = pt.trainer.Trainer(cost, [x, y], main_program=main,
                                startup_program=startup, mesh=mesh)
        tr.train(_make_reader(np), num_passes=PASSES,
                 event_handler=handler,
                 checkpoint_dir=os.path.join(workdir, "ckpt"),
                 checkpoint_every_n_steps=CKPT_EVERY,
                 async_checkpoint=True,
                 resume=(mode == "resume"))
    losses.close()
    if mode == "resume":
        st = tr.last_resume or {}
        print(f"RESUMED_AT {int(st.get('global_step', 0))}", flush=True)
    print(f"CHILD_OK {mode}", flush=True)
    return 0


def _child_ckptcrash(workdir):
    """Save twice to ONE directory; the armed ckpt_crash fault kills the
    process between the second publish's two renames."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as pt

    main, startup, cost, x, y = _build_model(pt)
    feeder = pt.DataFeeder([x, y])
    rng = np.random.default_rng(3)
    X = rng.normal(size=(16, 13)).astype(np.float32)
    Y = (X @ rng.normal(size=(13, 1))).astype(np.float32)
    feed = feeder.feed(list(zip(X, Y)))
    with pt.program_guard(main, startup):
        exe = pt.Executor()
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[cost])
        ckpt = pt.io.AsyncCheckpointer()
        target = os.path.join(workdir, "latest")
        ckpt.save(target, main, extra_state={"global_step": 1})
        ckpt.wait()
        print(f"CKPT1_DIGEST "
              f"{_state_digest(pt, pt.global_scope(), main)}", flush=True)
        exe.run(main, feed=feed, fetch_list=[cost])
        # this save's publish hits the armed ckpt_crash fault: the
        # process dies between the renames, losses the new dir, and the
        # .old fallback must still be loadable
        ckpt.save(target, main, extra_state={"global_step": 2})
        ckpt.wait()
    print("CKPT2_PUBLISHED (fault did not fire?)", flush=True)
    return 1  # reaching here means the injected crash failed


def _child_ckptverify(workdir):
    """Load the torn-publish checkpoint (via .old fallback) and print
    the restored digest + train state."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as pt
    from paddle_tpu.resilience import checkpoint as rckpt

    main, startup, cost, x, y = _build_model(pt)
    with pt.program_guard(main, startup):
        exe = pt.Executor()
        exe.run(startup)
        target = os.path.join(workdir, "latest")
        pt.io.load_persistables(exe, target, main)
        st = rckpt.load_train_state(target)
        print(f"RESTORED_STEP {st['global_step']}", flush=True)
        print(f"RESTORED_DIGEST "
              f"{_state_digest(pt, pt.global_scope(), main)}", flush=True)
    return 0


if __name__ == "__main__":
    mode, workdir = sys.argv[1], sys.argv[2]
    if mode in ("ref", "crash", "resume"):
        sys.exit(_child_train(mode, workdir))
    sys.exit({"ckptcrash": _child_ckptcrash,
              "ckptverify": _child_ckptverify}[mode](workdir))
