"""Benchmark-config model tests (reference: benchmark/paddle/image/
{alexnet,googlenet,smallnet_mnist_cifar}.py — SURVEY §6 baseline configs).
Tiny-shape trainings: loss finite and decreasing, like tests/test_book.py."""

import pytest

import numpy as np

from paddle_tpu.models import alexnet, googlenet, smallnet

from tiny import train_steps


def test_alexnet():
    outs = alexnet.build(class_dim=4, image_shape=(3, 96, 96),
                         learning_rate=0.01, dtype="float32")
    rng = np.random.default_rng(10)
    img = rng.normal(size=(4, 3, 96, 96)).astype(np.float32)
    label = rng.integers(0, 4, size=(4, 1)).astype(np.int64)
    train_steps(outs, {"img": img, "label": label}, steps=4,
                extra_fetch=[outs["accuracy"]])


@pytest.mark.slow
def test_googlenet():
    outs = googlenet.build(class_dim=4, image_shape=(3, 128, 128),
                           learning_rate=0.001, dtype="float32")
    rng = np.random.default_rng(11)
    img = rng.normal(size=(2, 3, 128, 128)).astype(np.float32)
    label = rng.integers(0, 4, size=(2, 1)).astype(np.int64)
    train_steps(outs, {"img": img, "label": label}, steps=4)


def test_smallnet():
    outs = smallnet.build(class_dim=10, learning_rate=0.002)
    rng = np.random.default_rng(12)
    img = rng.normal(size=(8, 3, 32, 32)).astype(np.float32)
    label = rng.integers(0, 10, size=(8, 1)).astype(np.int64)
    train_steps(outs, {"img": img, "label": label}, steps=5,
                extra_fetch=[outs["accuracy"]])
