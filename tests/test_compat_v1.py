"""v1 trainer_config_helpers name-compat shim (paddle_tpu/compat/v1.py;
reference: python/paddle/trainer_config_helpers/layers.py).  A v1-style
config should build a Program and train."""

import numpy as np

import paddle_tpu as pt
from paddle_tpu.compat import v1

from tiny import train_steps


def test_v1_smallnet_config_trains():
    """The reference benchmark/paddle/image/smallnet_mnist_cifar.py shape,
    written with v1 names."""
    net = v1.data_layer("data", size=3 * 32 * 32, height=32, width=32)
    label = v1.data_layer("label", size=1, is_label=True)
    net = v1.img_conv_layer(input=net, filter_size=5, num_filters=32,
                            stride=1, padding=2, act=v1.ReluActivation())
    net = v1.img_pool_layer(input=net, pool_size=3, stride=2, padding=1)
    net = v1.img_conv_layer(input=net, filter_size=5, num_filters=32,
                            stride=1, padding=2, act=v1.ReluActivation())
    net = v1.img_pool_layer(input=net, pool_size=3, stride=2, padding=1,
                            pool_type=v1.AvgPooling())
    net = v1.fc_layer(input=net, size=64, act=v1.ReluActivation())
    out = v1.fc_layer(input=net, size=10, act=v1.SoftmaxActivation())
    cost = v1.classification_cost(input=out, label=label)
    opt = v1.settings(batch_size=8, learning_rate=0.002,
                      learning_method=v1.MomentumOptimizer(0.9),
                      regularization=v1.L2Regularization(1e-4))
    opt.minimize(cost)

    rng = np.random.default_rng(0)
    img = rng.normal(size=(8, 3, 32, 32)).astype(np.float32)
    lbl = rng.integers(0, 10, (8, 1)).astype(np.int64)
    train_steps({"avg_cost": cost}, {"data": img, "label": lbl}, steps=5)


def test_v1_lstm_text_config_trains():
    """The benchmark/paddle/rnn/rnn.py shape with v1 names: embedding ->
    simple_lstm -> seq pooling -> fc."""
    words = v1.data_layer("words", size=50, dtype="int64", seq_len=12)
    label = v1.data_layer("label", size=1, is_label=True)
    emb = v1.embedding_layer(input=words, size=16)
    lstm = v1.simple_lstm(input=emb, size=16)
    pooled = v1.pooling_layer(input=lstm, pooling_type=v1.MaxPooling())
    out = v1.fc_layer(input=pooled, size=2, act=v1.SoftmaxActivation())
    cost = v1.classification_cost(input=out, label=label)
    v1.settings(learning_rate=0.05,
                learning_method=v1.AdamOptimizer()).minimize(cost)

    rng = np.random.default_rng(1)
    data = rng.integers(0, 50, (4, 12)).astype(np.int64)
    lens = np.full((4,), 12, np.int32)
    lbl = rng.integers(0, 2, (4, 1)).astype(np.int64)
    train_steps({"avg_cost": cost},
                {"words": data, "words@LENGTH": lens, "label": lbl},
                steps=5)


def test_v1_misc_layers():
    a = v1.data_layer("a", size=8)
    b = v1.data_layer("b", size=8)
    s = v1.addto_layer([a, b], act=v1.TanhActivation())
    c = v1.concat_layer([a, b])
    sim = v1.cos_sim(a, b)
    scaled = v1.slope_intercept_layer(a, slope=2.0, intercept=1.0)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    av = np.ones((2, 8), np.float32)
    bv = np.full((2, 8), 2.0, np.float32)
    sv, cv, simv, scv = exe.run(feed={"a": av, "b": bv},
                                fetch_list=[s, c, sim, scaled])
    assert np.allclose(sv, np.tanh(3.0))
    assert cv.shape == (2, 16)
    assert np.allclose(simv, 1.0, atol=1e-5)
    assert np.allclose(scv, 3.0)


def test_v1_inputs_outputs_bookkeeping():
    a = v1.data_layer("a", size=4)
    out = v1.fc_layer(input=a, size=2, act=v1.SoftmaxActivation())
    assert v1.inputs(a) == [a]
    assert v1.outputs(out) == [out]
