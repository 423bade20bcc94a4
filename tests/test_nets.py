"""Composite-network helpers (nets.py; reference fluid/nets.py + v2
trainer_config_helpers/networks.py)."""

import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers, nets

from tiny import train_steps


def test_img_conv_bn_pool_and_separable():
    img = layers.data("img", shape=[3, 16, 16])
    label = layers.data("label", shape=[1], dtype="int64")
    h = nets.img_conv_bn_pool(img, num_filters=8, filter_size=3,
                              pool_size=2, pool_stride=2, conv_padding=1)
    h = nets.img_separable_conv(h, num_channels=8, num_out_channels=16,
                                filter_size=3, padding=1, act="relu")
    out = layers.fc(h, 4, act="softmax")
    cost = layers.mean(layers.cross_entropy(out, label))
    pt.optimizer.Adam(learning_rate=0.01).minimize(cost)
    rng = np.random.default_rng(0)
    feed = {"img": rng.normal(size=(4, 3, 16, 16)).astype(np.float32),
            "label": rng.integers(0, 4, (4, 1)).astype(np.int64)}
    train_steps({"avg_cost": cost}, feed, steps=4)


def test_bidirectional_lstm_and_gru():
    words = layers.data("words", shape=[6], dtype="int64", lod_level=1)
    label = layers.data("label", shape=[1], dtype="int64")
    emb = layers.embedding(words, size=[30, 8])
    proj = layers.fc(emb, 16 * 4, num_flatten_dims=2)
    layers.link_sequence(proj, emb)
    bi = nets.bidirectional_lstm(proj, size=16)
    assert bi.shape[-1] == 32
    proj_g = layers.fc(emb, 12 * 3, num_flatten_dims=2)
    layers.link_sequence(proj_g, emb)
    big = nets.bidirectional_gru(proj_g, size=12)
    assert big.shape[-1] == 24
    pooled = layers.sequence_pool(bi, pool_type="max")
    pooled_g = layers.sequence_pool(big, pool_type="max")
    out = layers.fc([pooled, pooled_g], 2, act="softmax")
    cost = layers.mean(layers.cross_entropy(out, label))
    pt.optimizer.Adam(learning_rate=0.02).minimize(cost)
    rng = np.random.default_rng(1)
    data = rng.integers(0, 30, (4, 6)).astype(np.int64)
    lens = rng.integers(2, 7, (4,)).astype(np.int32)
    lbl = rng.integers(0, 2, (4, 1)).astype(np.int64)
    train_steps({"avg_cost": cost},
                {"words": data, "words@LENGTH": lens, "label": lbl}, steps=4)


def test_dot_product_attention_matches_numpy():
    q = layers.data("q", shape=[3, 8])
    k = layers.data("k", shape=[5, 8])
    v = layers.data("v", shape=[5, 8])
    out = nets.dot_product_attention(q, k, v)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.default_rng(2)
    qv = rng.normal(size=(2, 3, 8)).astype(np.float32)
    kv = rng.normal(size=(2, 5, 8)).astype(np.float32)
    vv = rng.normal(size=(2, 5, 8)).astype(np.float32)
    (ov,) = exe.run(feed={"q": qv, "k": kv, "v": vv}, fetch_list=[out])
    s = qv @ kv.transpose(0, 2, 1)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(ov), w @ vv, rtol=2e-4, atol=2e-5)
