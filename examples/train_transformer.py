"""Train a small GPT on synthetic data and decode with the KV cache —
the long-context flagship in ~40 lines.

    python examples/train_transformer.py [--steps 200]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as pt
from paddle_tpu.models import transformer


def build_program(vocab=64, seq=64):
    """The example's training program, built without running — the
    entry point ``python -m paddle_tpu --lint`` loads.
    Returns (main_program, startup_program, fetch_list)."""
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        outs = transformer.build(vocab_size=vocab, n_layer=2, n_head=4,
                                 d_model=128, max_len=seq,
                                 dropout_rate=0.0, learning_rate=3e-3,
                                 dtype="float32")
    return main_prog, startup, [outs["avg_cost"]]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    outs = transformer.build(vocab_size=args.vocab, n_layer=2, n_head=4,
                             d_model=128, max_len=args.seq,
                             dropout_rate=0.0, learning_rate=3e-3,
                             dtype="float32")
    exe = pt.Executor()
    exe.run(pt.default_startup_program())

    rng = np.random.default_rng(0)
    for step in range(args.steps):
        toks = rng.integers(0, args.vocab, (16, args.seq)).astype(np.int64)
        lbls = (toks + 1) % args.vocab  # learn "next token = tok + 1"
        (cost,) = exe.run(feed={"tokens": toks, "labels": lbls},
                          fetch_list=[outs["avg_cost"]])
        if step % 50 == 0:
            print(f"step {step} loss {float(np.asarray(cost).ravel()[0]):.4f}")

    params = transformer.extract_params()
    prompt = np.asarray([[5, 6, 7]], np.int64)
    tokens, _ = transformer.generate(params, prompt, max_len=16,
                                     n_layer=2, n_head=4, d_model=128)
    print("prompt [5, 6, 7] ->", np.asarray(tokens)[0].tolist())


if __name__ == "__main__":
    main()
