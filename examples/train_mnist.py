"""MNIST LeNet end to end: model zoo + Trainer events + async checkpoints
+ export + reload (the reference book chapter 2 workflow).

    python examples/train_mnist.py [--passes 3]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as pt


def build_program():
    """The example's training program, built without running — the
    entry point ``python -m paddle_tpu --lint`` loads.
    Returns (main_program, startup_program, fetch_list)."""
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        model = pt.models.lenet.build(learning_rate=0.001)
    return main_prog, startup, [model["avg_cost"], model["accuracy"]]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--out", default="mnist_model")
    ap.add_argument("--run-log", default=None,
                    help="write per-step telemetry (wall time, throughput, "
                         "MFU, compile counts) to this JSONL file")
    args = ap.parse_args()

    model = pt.models.lenet.build(learning_rate=0.001)
    feeder = pt.DataFeeder(model["feed"])

    def train_reader():
        for img, lbl in pt.dataset.mnist.train()():
            yield img.reshape(1, 28, 28), lbl

    def handler(e):
        if isinstance(e, pt.trainer.EndIteration) and e.batch_id % 50 == 0:
            acc = float(np.asarray(e.metrics[0]).ravel()[0])
            print(f"pass {e.pass_id} batch {e.batch_id} "
                  f"cost {e.cost:.4f} acc {acc:.3f}")

    tr = pt.trainer.Trainer(model["avg_cost"], model["feed"],
                            extra_fetch=[model["accuracy"]])
    # telemetry rides along with the user handler: step summaries every
    # 50 batches + (optionally) a JSONL run log for offline analysis
    reporter = pt.observability.MetricsReporter(
        log_every_n=50, jsonl_path=args.run_log)
    try:
        tr.train(pt.reader.batch(train_reader, args.batch_size),
                 num_passes=args.passes,
                 event_handler=reporter.chain(handler),
                 checkpoint_dir="mnist_ckpts", async_checkpoint=True)
    finally:
        reporter.close()

    pt.io.save_inference_model(args.out, ["img"], [model["prediction"]],
                               tr.exe)
    engine = pt.inference.InferenceEngine(args.out)
    sample = list(pt.reader.firstn(train_reader, 4)())
    probs = engine.run(feed={"img": np.stack([im for im, _ in sample])})
    pred = np.asarray(probs[0]).argmax(axis=1)
    print("reloaded model predictions:", pred.tolist(),
          "labels:", [int(l) for _, l in sample])


if __name__ == "__main__":
    main()
