"""End-to-end "book" acceptance tests (reference: fluid/tests/book/ — 12
model trainings that ARE the acceptance suite, SURVEY §4).  Each test builds
a model from paddle_tpu.models on tiny shapes, trains a few steps on
synthetic data, and asserts the loss goes down and stays finite."""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.models import (
    ctr_dnn,
    deep_speech2,
    fit_a_line,
    label_semantic_roles,
    lenet,
    recommender,
    resnet,
    seq2seq,
    text_classification,
    vgg,
    word2vec,
)
from tiny import train_steps


def ragged_int(batch, max_len, high, rng):
    """Padded int64 [batch, max_len] + lengths [batch]."""
    lens = rng.integers(2, max_len + 1, size=batch)
    data = np.zeros((batch, max_len), np.int64)
    for i, ln in enumerate(lens):
        data[i, :ln] = rng.integers(0, high, size=ln)
    return data, lens.astype(np.int32)


def test_fit_a_line():
    outs = fit_a_line.build(learning_rate=0.05)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 13)).astype(np.float32)
    w = rng.normal(size=(13, 1)).astype(np.float32)
    y = x @ w
    train_steps(outs, {"x": x, "y": y}, steps=8)


def test_recognize_digits_conv():
    outs = lenet.build(learning_rate=0.001)
    rng = np.random.default_rng(1)
    img = rng.normal(size=(8, 1, 28, 28)).astype(np.float32)
    label = rng.integers(0, 10, size=(8, 1)).astype(np.int64)
    train_steps(outs, {"img": img, "label": label}, steps=5,
                extra_fetch=[outs["accuracy"]])


@pytest.mark.slow
def test_image_classification_vgg():
    outs = vgg.build(depth=16, class_dim=4, image_shape=(3, 32, 32),
                     learning_rate=0.01)
    rng = np.random.default_rng(2)
    img = rng.normal(size=(4, 3, 32, 32)).astype(np.float32)
    label = rng.integers(0, 4, size=(4, 1)).astype(np.int64)
    train_steps(outs, {"img": img, "label": label}, steps=4)


def test_image_classification_resnet():
    outs = resnet.build(depth=20, class_dim=4, image_shape=(3, 32, 32),
                        learning_rate=0.05)
    rng = np.random.default_rng(3)
    img = rng.normal(size=(4, 3, 32, 32)).astype(np.float32)
    label = rng.integers(0, 4, size=(4, 1)).astype(np.int64)
    train_steps(outs, {"img": img, "label": label}, steps=4)


def test_word2vec():
    outs = word2vec.build(dict_size=50, embed_size=8, hidden_size=16,
                          learning_rate=0.1)
    rng = np.random.default_rng(4)
    feed = {
        f"word_{i}": rng.integers(0, 50, size=(16, 1)).astype(np.int64)
        for i in range(4)
    }
    feed["next_word"] = rng.integers(0, 50, size=(16, 1)).astype(np.int64)
    train_steps(outs, feed, steps=6)


def test_machine_translation_train():
    outs = seq2seq.build(src_dict_size=40, trg_dict_size=40, word_dim=8,
                         hidden_dim=16, max_len=6, learning_rate=0.01)
    rng = np.random.default_rng(5)
    src, src_len = ragged_int(4, 6, 40, rng)
    trg, trg_len = ragged_int(4, 6, 40, rng)
    trg_next = np.roll(trg, -1, axis=1)
    feed = {
        "src_word_id": src, "src_word_id@LENGTH": src_len,
        "target_language_word": trg, "target_language_word@LENGTH": trg_len,
        "target_language_next_word": trg_next,
        "target_language_next_word@LENGTH": trg_len,
    }
    train_steps(outs, feed, steps=4)


def test_machine_translation_decode():
    outs = seq2seq.build_decode(
        src_dict_size=40, trg_dict_size=40, word_dim=8, hidden_dim=16,
        max_len=6, beam_size=3, max_out_len=5, end_id=1,
    )
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.default_rng(6)
    src, src_len = ragged_int(2, 6, 40, rng)
    ids, parents, steps = exe.run(
        feed={"src_word_id": src, "src_word_id@LENGTH": src_len},
        fetch_list=[outs["ids_array"], outs["parents_array"], outs["steps"]],
    )
    n = int(np.asarray(steps).reshape(-1)[0])
    assert 1 <= n <= 5
    sentences = seq2seq.decode_sentences(ids, parents, steps, end_id=1)
    assert sentences.shape[0] == 2  # batch


def test_label_semantic_roles():
    outs = label_semantic_roles.build(
        word_dict_len=30, label_dict_len=5, pred_dict_len=8, max_len=6,
        word_dim=4, hidden_dim=8, depth=2, learning_rate=0.02,
    )
    rng = np.random.default_rng(7)
    feed = {}
    words, lens = ragged_int(3, 6, 30, rng)
    for n in ["word", "ctx_n2", "ctx_n1", "ctx_0", "ctx_p1", "ctx_p2"]:
        w, _ = ragged_int(3, 6, 30, rng)
        feed[n] = w
        feed[n + "@LENGTH"] = lens
    verb, _ = ragged_int(3, 6, 8, rng)
    feed["verb"], feed["verb@LENGTH"] = verb, lens
    mark, _ = ragged_int(3, 6, 2, rng)
    feed["mark"], feed["mark@LENGTH"] = mark, lens
    target, _ = ragged_int(3, 6, 5, rng)
    feed["target"], feed["target@LENGTH"] = target, lens
    train_steps(outs, feed, steps=4)


def test_understand_sentiment_stacked_lstm():
    outs = text_classification.build(
        dict_dim=40, class_dim=2, emb_dim=8, hid_dim=8, stacked_num=2,
        learning_rate=0.05, max_len=8,
    )
    rng = np.random.default_rng(8)
    words, lens = ragged_int(4, 8, 40, rng)
    label = rng.integers(0, 2, size=(4, 1)).astype(np.int64)
    feed = {"words": words, "words@LENGTH": lens, "label": label}
    train_steps(outs, feed, steps=4)


def test_recommender_system():
    outs = recommender.build(learning_rate=0.05, max_title_len=4,
                             max_cat_len=3)
    rng = np.random.default_rng(9)
    b = 4
    cat, cat_len = ragged_int(b, 3, 10, rng)
    title, title_len = ragged_int(b, 4, 50, rng)
    feed = {
        "user_id": rng.integers(0, 100, (b, 1)).astype(np.int64),
        "gender_id": rng.integers(0, 2, (b, 1)).astype(np.int64),
        "age_id": rng.integers(0, 7, (b, 1)).astype(np.int64),
        "job_id": rng.integers(0, 10, (b, 1)).astype(np.int64),
        "movie_id": rng.integers(0, 100, (b, 1)).astype(np.int64),
        "category_id": cat, "category_id@LENGTH": cat_len,
        "movie_title": title, "movie_title@LENGTH": title_len,
        "score": rng.uniform(1, 5, (b, 1)).astype(np.float32),
    }
    train_steps(outs, feed, steps=5)


def test_ctr_dnn():
    outs = ctr_dnn.build(sparse_feature_dim=100, num_slots=3,
                         embedding_size=4, dense_dim=5, hidden=(8, 4),
                         learning_rate=0.05)
    rng = np.random.default_rng(10)
    b = 8
    feed = {"dense_feature": rng.normal(size=(b, 5)).astype(np.float32),
            "click": rng.integers(0, 2, (b, 1)).astype(np.int64)}
    for i in range(3):
        feed[f"slot_{i}"] = rng.integers(0, 100, (b, 1)).astype(np.int64)
    train_steps(outs, feed, steps=5)


def test_deep_speech2_ctc():
    outs = deep_speech2.build(feat_dim=8, max_audio_len=12, max_label_len=6,
                              rnn_size=8, num_rnn_layers=1, vocab_size=5,
                              learning_rate=0.01)
    rng = np.random.default_rng(11)
    b = 2
    audio = rng.normal(size=(b, 12, 8)).astype(np.float32)
    audio_len = np.array([12, 9], np.int32)
    label, label_len = ragged_int(b, 6, 5, rng)
    feed = {"audio": audio, "audio@LENGTH": audio_len,
            "transcript": label, "transcript@LENGTH": label_len}
    train_steps(outs, feed, steps=4)


@pytest.mark.slow
def test_ssd_detection():
    """SSD family: multi-scale prior boxes + multibox_loss training, then
    detection_output inference recovers a planted box (the v1 SSD config
    family — MultiBoxLossLayer / DetectionOutputLayer / PriorBox)."""
    from paddle_tpu.models import ssd

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        model = ssd.build(num_classes=4, image_shape=(3, 64, 64), max_gt=8)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)
    imgs, gt_box, gt_label = ssd.synthetic_batch(16)
    feed = {"img": imgs, "gt_box": gt_box, "gt_label": gt_label}
    losses = [
        float(np.asarray(exe.run(main, feed=feed,
                                 fetch_list=[model["avg_cost"]],
                                 scope=scope)[0]).ravel()[0])
        for _ in range(12)
    ]
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0] * 0.8, losses[::4]

    # the same program carries the inference head (nondiff branch)
    (dets,) = exe.run(main, feed=feed,
                      fetch_list=[model["detections"]], scope=scope)
    dets = np.asarray(dets)
    assert dets.shape[0] == 16 and dets.shape[-1] == 6
