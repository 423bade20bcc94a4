"""Round-2 perf triage on the real chip.

Measures, for ResNet-50 bf16 train bs128:
  A. current bench path: Executor.run per step (host dispatch per step)
  B. raw jitted step called in a loop on device-resident args (no executor
     python overhead)
  C. Executor.run_steps fused lax.scan
  D. pure-JAX NCHW vs NHWC conv stack micro-benchmark (layout hypothesis)
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp


def _sync(out):
    # materialize bytes on the host to end the timed region
    leaves = jax.tree.leaves(out)
    return float(jnp.sum(leaves[-1].astype(jnp.float32).ravel()[0]))


def bench_loop(fn, args, steps=20, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / steps


def main():
    import paddle_tpu as pt
    from paddle_tpu.models import resnet

    batch = 128
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        outs = resnet.build(depth=50, class_dim=1000,
                            image_shape=(3, 224, 224), dtype="bfloat16")
    exe = pt.Executor()
    exe.run(startup)

    img = jax.device_put(jnp.asarray(
        np.random.rand(batch, 3, 224, 224), dtype=jnp.bfloat16))
    label = jax.device_put(jnp.asarray(
        np.random.randint(0, 1000, (batch, 1)), dtype=jnp.int32))
    feed = {"img": img, "label": label}
    fetch = [outs["avg_cost"]]

    # A: executor.run per step
    def run_once():
        return exe.run(main_prog, feed=feed, fetch_list=fetch,
                       return_numpy=False)[0]
    dt = bench_loop(lambda: run_once(), (), steps=20)
    print(f"A executor.run       : {dt*1e3:8.2f} ms/step  "
          f"{batch/dt:8.1f} img/s")

    # B: raw jitted step, no executor python in the loop
    scope = pt.core.scope.global_scope()
    state_names = tuple(sorted(
        v.name for v in main_prog.persistable_vars()
        if scope.find_var(v.name) is not None))
    step, _ = exe.lower(main_prog, ["img", "label"],
                        [outs["avg_cost"].name], state_names)
    jstep = jax.jit(step)
    state = {n: scope.get(n) for n in state_names}
    state[pt.core.scope.RNG_VAR] = scope.get(pt.core.scope.RNG_VAR)

    def raw_once(state):
        s2, f = jstep(state, img, label)
        return s2, f

    # keep state fixed (no donation) for timing simplicity
    for _ in range(3):
        s2, f = raw_once(state)
    _sync(f)
    t0 = time.perf_counter()
    s = state
    for _ in range(20):
        s, f = raw_once(s)
    _sync(f)
    dt = (time.perf_counter() - t0) / 20
    print(f"B raw jitted step    : {dt*1e3:8.2f} ms/step  "
          f"{batch/dt:8.1f} img/s")

    # C: run_steps fused scan (10 steps to bound memory of stacked feed)
    ksteps = 10
    imgs = jax.device_put(jnp.asarray(
        np.random.rand(ksteps, batch, 3, 224, 224), dtype=jnp.bfloat16))
    labels = jax.device_put(jnp.asarray(
        np.random.randint(0, 1000, (ksteps, batch, 1)), dtype=jnp.int32))
    sfeed = {"img": imgs, "label": labels}
    # warmup/compile
    exe.run_steps(main_prog, feed=sfeed, fetch_list=fetch, return_numpy=False)
    t0 = time.perf_counter()
    out = exe.run_steps(main_prog, feed=sfeed, fetch_list=fetch,
                        return_numpy=False)
    _sync(out)
    dt = (time.perf_counter() - t0) / ksteps
    print(f"C run_steps scan     : {dt*1e3:8.2f} ms/step  "
          f"{batch/dt:8.1f} img/s")


def conv_layout_micro():
    """D: NCHW vs NHWC bottleneck-ish conv stack, fwd+bwd."""
    batch = 128

    def make_stack(dn, x_shape, w_shapes):
        ws = [jnp.asarray(np.random.randn(*s) * 0.05, jnp.bfloat16)
              for s in w_shapes]
        x = jnp.asarray(np.random.rand(*x_shape), jnp.bfloat16)

        def f(ws, x):
            h = x
            for w in ws:
                h = jax.lax.conv_general_dilated(
                    h, w, (1, 1), "SAME", dimension_numbers=dn)
                h = jnp.maximum(h, 0)
            return jnp.sum(h.astype(jnp.float32))

        g = jax.jit(jax.grad(f))
        return g, ws, x

    C = 256
    n_layers = 8
    # NCHW / OIHW
    g1, ws1, x1 = make_stack(("NCHW", "OIHW", "NCHW"),
                             (batch, C, 28, 28),
                             [(C, C, 3, 3)] * n_layers)
    dt = bench_loop(g1, (ws1, x1), steps=10)
    print(f"D conv NCHW          : {dt*1e3:8.2f} ms/iter")
    # NHWC / HWIO
    g2, ws2, x2 = make_stack(("NHWC", "HWIO", "NHWC"),
                             (batch, 28, 28, C),
                             [(3, 3, C, C)] * n_layers)
    dt = bench_loop(g2, (ws2, x2), steps=10)
    print(f"D conv NHWC          : {dt*1e3:8.2f} ms/iter")


if __name__ == "__main__":
    print("devices:", jax.devices())
    main()
    conv_layout_micro()
