"""What the training check can see, at the published width: a relative
error in the final hidden state of the size bf16 leaves (some 2%) stays
inside the committed tolerance, one of fp8's class (6%) does not.  The
head is the real one (1536 x 50304, normal 0.02), the trunk's output is
drawn, and the statistic is the one the runner uses: the mean loss on
the reference's own most likely tokens (``reference.greedy_loss``)."""

import json
import os

import numpy as np

from chipbench import run as bench_run


def _read(*parts):
    with open(os.path.join(bench_run.HERE, *parts)) as f:
        return json.load(f)


def _greedy_loss_shift(d, rows, vocab, tokens, errors):
    rng = np.random.default_rng(0)
    head = (0.02 * rng.standard_normal((d, rows))).astype(np.float32)
    head[:, vocab:] = 0.0

    def unit(x):  # the final LayerNorm at unit scale, zero bias
        x = x - x.mean(-1, keepdims=True)
        return x / x.std(-1, keepdims=True)

    def loss(h, labels):
        z = h @ head
        top = z.max(-1, keepdims=True)
        lse = top[:, 0] + np.log(np.exp(z - top).sum(-1))
        return float((lse - z[np.arange(len(z)), labels]).mean())

    hidden = rng.standard_normal((tokens, d)).astype(np.float32)
    labels = (unit(hidden) @ head).argmax(-1)
    assert labels.max() < vocab
    base = loss(unit(hidden), labels)
    noise = rng.standard_normal(hidden.shape).astype(np.float32)
    return [loss(unit(hidden + e * noise), labels) - base for e in errors]


def test_tolerance_passes_bf16_and_fails_fp8_class_errors():
    cfg = _read("configs", "cerebras-gpt-590m.json")
    tol = _read("traffic", "train_2k.json")["check"]["loss_abs_tol"]
    small, large = _greedy_loss_shift(
        cfg["n_embd"], cfg["changed"]["vocab_rows"], cfg["vocab_size"],
        2048, (0.02, 0.06))
    assert 0 < small < tol / 2, (small, tol)
    assert large > 2 * tol, (large, tol)
