"""Data-parallel ResNet over a device mesh — one annotation replaces the
reference's MultiGradientMachine/parallel_do/NCCL stack.  Optimizer
state (the Momentum velocities here) shards automatically over the dp
axis — ZeRO-1, docs/parallel.md; ``PADDLE_TPU_ZERO=0`` replicates.

Runs on real chips, or on a virtual mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/train_data_parallel.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_program():
    """The example's training program (with the data-parallel batch
    annotations but no mesh/devices), built without running — the entry
    point ``python -m paddle_tpu --lint`` loads.  Returns
    (main_program, startup_program, fetch_list)."""
    import paddle_tpu as pt
    from paddle_tpu import parallel

    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        model = pt.models.resnet.build(depth=20, class_dim=10,
                                       image_shape=(3, 32, 32),
                                       learning_rate=0.05,
                                       dtype="float32")
    parallel.data_parallel(main_prog, "dp", programs=(startup,))
    return main_prog, startup, [model["avg_cost"], model["accuracy"]]


def main():
    import jax

    import paddle_tpu as pt
    from paddle_tpu import parallel

    n = len(jax.devices())
    mesh = parallel.make_mesh({"dp": n})
    print(f"mesh: {n} devices on axis 'dp'")

    model = pt.models.resnet.build(depth=20, class_dim=10,
                                   image_shape=(3, 32, 32),
                                   learning_rate=0.05, dtype="float32")
    parallel.data_parallel(pt.default_main_program(), "dp",
                           programs=(pt.default_startup_program(),))

    exe = pt.Executor(mesh=mesh)
    exe.run(pt.default_startup_program())

    rep = parallel.optimizer_state_report(pt.default_main_program(), mesh)
    print(f"optimizer state: {rep['total_bytes'] / 1e6:.2f} MB total, "
          f"{rep['per_device_bytes'] / 1e6:.2f} MB/device "
          f"({rep['sharded_vars']} ZeRO-sharded vars)")

    rng = np.random.default_rng(0)
    batch = 8 * n  # global batch; shards across dp automatically
    for step in range(10):
        img = rng.normal(size=(batch, 3, 32, 32)).astype(np.float32)
        lbl = rng.integers(0, 10, (batch, 1)).astype(np.int64)
        cost, acc = exe.run(feed={"img": img, "label": lbl},
                            fetch_list=[model["avg_cost"],
                                        model["accuracy"]])
        print(f"step {step} cost {float(np.asarray(cost).ravel()[0]):.4f} "
              f"acc {float(np.asarray(acc).ravel()[0]):.3f}")


if __name__ == "__main__":
    main()
