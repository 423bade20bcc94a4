"""The training runner: ``Program`` -> ``Executor.run`` on seeded batches.

Builds the configuration's Program through its family module
(``chipbench/families/``) and applies the recipe the traffic file names
(remat policy, micro-batches, mesh axes), runs the startup program and
sets the weights from ``--seed``, checks the first loss against the
family's plain reference on the same weights and tokens (the labels of
that one step are the reference's own most likely tokens: its
``greedy_loss`` says why), warms up, and then trains for the window on a
new seeded batch fed from the host every step.
"""

import statistics
import time

import numpy as np

from .. import device, families, traffic


def _build(pt, family, cfg, mix, mesh):
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        avg_cost = family.training_program(cfg, mix)
        # the PR-10 recipe's order: remat first (the scan body is where
        # in-loop gathers live), then accumulation, then placement
        if mix["memory_optimize"] != "none":
            pt.memory_optimize(main, policy=mix["memory_optimize"])
        if mix["micro_steps"] > 1:
            pt.gradient_accumulation(main, mix["micro_steps"])
        if mesh is not None:
            if "dp" in mesh.shape:
                pt.parallel.data_parallel(main, "dp", programs=(startup,))
            if "fsdp" in mesh.shape:
                pt.parallel.shard_fsdp(main, programs=(startup,))
    return main, startup, avg_cost


def run(cell, seed, seconds, tracer):
    """One run of a training cell; see ``chipbench/run.py`` for the
    shape of what comes back."""
    import jax
    import paddle_tpu as pt

    cfg, mix = cell["config"], cell["traffic"]
    family = families.of(cfg, "train")
    devices = jax.devices()[:cell["chips"]]
    mesh = None
    if mix.get("mesh"):
        mesh = pt.parallel.make_mesh(dict(mix["mesh"]), devices=devices)
    main, startup, avg_cost = _build(pt, family, cfg, mix, mesh)
    scope = pt.Scope()
    exe = pt.Executor(mesh=mesh)
    compile_s = {}
    exe.run(startup, scope=scope)
    compile_s["startup"] = exe.last_step_cost["compile_seconds"]

    chain = traffic.BigramChain(cfg["vocab_size"], seed, **mix["data"])
    n_seq, seq_len = mix["sequences_per_step"], mix["seq_len"]

    def feed_of(step):
        # host arrays: the executor places them as its step expects
        tokens, labels = chain.batch(step, n_seq, seq_len)
        return {"tokens": tokens, "labels": labels}

    # the startup program seeds its initializers when it is BUILT, so a
    # seed of its own would make it a new executable for every --seed;
    # it runs as built (optimizer state, shapes, placement) and the
    # weights are then set from --seed, as a loaded checkpoint would be
    made = family.make_params(cfg, seq_len, seed)
    params = {}
    for p in main.all_parameters():
        old = scope.get(p.name)
        params[p.name] = jax.device_put(made.pop(p.name).astype(old.dtype),
                                        old.sharding)
        scope.set(p.name, params[p.name])
    if made:
        raise RuntimeError(f"weights the program does not hold: {set(made)}")
    del made, old

    # the reference on the first batch, from the initial weights, before
    # the first step overwrites them: its greedy labels are that step's
    # labels, its loss on them what the program's first loss is held to
    feed0 = feed_of(0)
    labels0, ref_loss = family.greedy_loss(params, feed0["tokens"], cfg)
    feed0["labels"] = np.asarray(labels0, feed0["labels"].dtype)
    if feed0["labels"].max() >= cfg["vocab_size"]:
        raise RuntimeError("the reference chose a padded id")
    del params, labels0

    cost = exe.compile_only(main, feed=feed0, fetch_list=[avg_cost],
                            scope=scope)
    compile_s["train_step"] = cost["compile_seconds"]

    def step(i):
        feed = feed_of(i) if i else feed0
        t0 = time.perf_counter()
        (loss,) = exe.run(main, feed=feed, fetch_list=[avg_cost],
                          scope=scope, return_numpy=False)
        return loss, time.perf_counter() - t0

    losses = []
    for i in range(mix["warmup_steps"]):
        losses.append(step(i)[0])
    jax.block_until_ready(losses)
    if not exe.last_step_cost["cache_hit"]:
        raise RuntimeError("a warm-up step compiled again")

    # -- the measured window: steps back to back, one in flight while the
    # host draws the next batch; every dispatched step is completed and
    # counted, and the clock stops when the last one is ready
    n_warm = len(losses)
    dispatch_s, done_t = [], []
    trace_from = mix.get("trace_from_step", 2)
    trace_to = trace_from + mix.get("trace_steps", 4)
    t_start = time.perf_counter()
    pending, stop, i = None, False, 0
    while not stop:
        if tracer is not None and i in (trace_from, trace_to):
            jax.block_until_ready(losses)
            tracer.stop() if i == trace_to else tracer.start()
        loss, d = step(n_warm + i)
        losses.append(loss)
        dispatch_s.append(d)
        if pending is not None:
            pending.block_until_ready()
            done_t.append(time.perf_counter())
            stop = done_t[-1] - t_start >= seconds
        pending, i = loss, i + 1
    pending.block_until_ready()
    done_t.append(time.perf_counter())
    if tracer is not None and tracer.running:
        tracer.stop()
    elapsed = done_t[-1] - t_start
    steps = len(done_t)

    losses = [float(np.asarray(x).reshape(())) for x in losses]
    finite = [bool(np.isfinite(x)) for x in losses]
    check = mix["check"]
    loss_err = abs(losses[0] - ref_loss)
    # losses[0] is on the reference's labels; the rest on the chain's
    fell = (statistics.median(losses[-5:]) < statistics.median(losses[1:6]))
    correct = all(finite) and loss_err <= check["loss_abs_tol"] and fell
    tokens_per_step = n_seq * seq_len
    rate = steps * tokens_per_step / elapsed / cell["chips"]
    peak = device.memory_peak(devices, cost.get("hbm_high_water_bytes"))
    return {
        "correct": bool(correct), "attempted": steps,
        "failed": finite[n_warm:].count(False),
        "window_start": t_start,
        "end_to_end": {"train_tokens_per_s": rate},
        "memory_peak_bytes": peak,
        "compared": [("loss_err", loss_err, check["loss_abs_tol"]),
                     ("loss_fell", fell, True)],
        "facts": {
            "runner": "train", "steps": steps, "elapsed_s": elapsed,
            "tokens_per_step": tokens_per_step,
            "dispatch_s": dispatch_s,
            "step_done_t": [t - t_start for t in done_t],
            "compile_seconds": compile_s, "losses": losses,
            "reference_loss": ref_loss, "loss_err": loss_err,
            "loss_fell": fell, "traced_steps": trace_to - trace_from,
            "hbm_high_water_bytes": cost.get("hbm_high_water_bytes"),
            "kernel_backends": cost.get("kernel_backends"),
        },
    }
