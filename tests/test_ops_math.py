"""Op tests for the math group — check_output vs numpy + check_grad
(analytic vs numeric), mirroring fluid's per-op test files (SURVEY §4)."""

import numpy as np
import pytest

from op_test import check_output, check_grad, run_op

rng = np.random.RandomState(42)


def test_elementwise_add_broadcast_axis():
    x = rng.randn(2, 3, 4).astype(np.float32)
    y = rng.randn(3).astype(np.float32)
    check_output(
        "elementwise_add", {"X": x, "Y": y},
        {"Out": x + y.reshape(1, 3, 1)}, attrs={"axis": 1},
    )


def test_elementwise_ops_trailing_broadcast():
    x = rng.randn(4, 5).astype(np.float32)
    y = rng.randn(5).astype(np.float32)
    check_output("elementwise_mul", {"X": x, "Y": y}, {"Out": x * y})
    check_output("elementwise_sub", {"X": x, "Y": y}, {"Out": x - y})
    check_output("elementwise_max", {"X": x, "Y": y}, {"Out": np.maximum(x, y)})


@pytest.mark.parametrize("op,ref", [
    ("elementwise_add", lambda x, y: x + y),
    ("elementwise_mul", lambda x, y: x * y),
    ("elementwise_div", lambda x, y: x / y),
])
def test_elementwise_grad(op, ref):
    x = rng.rand(3, 4).astype(np.float32) + 0.5
    y = rng.rand(3, 4).astype(np.float32) + 0.5
    check_grad(op, {"X": x, "Y": y}, "X")
    check_grad(op, {"X": x, "Y": y}, "Y")


def test_mul_flatten():
    x = rng.randn(2, 3, 4).astype(np.float32)
    y = rng.randn(12, 5).astype(np.float32)
    out = x.reshape(2, 12) @ y
    check_output(
        "mul", {"X": x, "Y": y}, {"Out": out.reshape(2, 5)},
        attrs={"x_num_col_dims": 1, "y_num_col_dims": 1},
    )
    check_grad("mul", {"X": x, "Y": y}, "X")
    check_grad("mul", {"X": x, "Y": y}, "Y")


# -- the product over the rows as they stand (ISSUE 60) ---------------------

def _value_and_grads(product, x, y, cols):
    import jax
    import jax.numpy as jnp

    def out(x, y):
        return product(x, y, cols, 1).reshape(x.shape[:cols] + y.shape[1:])

    def loss(x, y):
        return (out(x, y).astype(jnp.float32) ** 2).sum()

    got = (jax.jit(out)(x, y),) + jax.jit(jax.grad(loss, (0, 1)))(x, y)
    return [np.asarray(v.astype(jnp.float32)) for v in got]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(2, 6, 16), (2, 3, 4, 16), (1, 5, 16)],
                         ids=lambda s: "x".join(map(str, s)))
def test_mul_over_rows_is_the_flat_product(shape, dtype):
    """Rows that only fold take the product over X as it stands: the
    flat form's value and both gradients, to the bit on the CPU."""
    import jax.numpy as jnp

    from paddle_tpu.ops import math_ops

    x = jnp.asarray(rng.randn(*shape), dtype)
    y = jnp.asarray(rng.randn(shape[-1], 24) * 0.3, dtype)
    cols = len(shape) - 1
    assert math_ops.folds_rows_only(x, y, cols, 1)
    flat = _value_and_grads(math_ops._mul_flat, x, y, cols)
    whole = _value_and_grads(math_ops._mul, x, y, cols)
    for name, f, w in zip(("out", "dx", "dy"), flat, whole):
        np.testing.assert_array_equal(f, w, err_msg=name)


@pytest.mark.parametrize("x_shape,y_shape,cols,y_cols,flattens", [
    ((2, 6, 16), (16, 8), 2, 1, False),
    ((2, 3, 4, 16), (16, 8), 3, 1, False),
    ((6, 16), (16, 8), 1, 1, False),         # its own flattening
    ((2, 3, 4, 4), (48, 8), 1, 1, True),     # [N, C, H, W] into an fc
    ((2, 3, 4, 4), (16, 8), 2, 1, True),     # folds trailing dims too
    ((2, 6, 16), (4, 4, 8), 2, 2, True),     # a 3-D Y
], ids=["3d-rows", "4d-rows", "2d", "nchw", "4d-mid", "y3d"])
def test_which_products_flatten(x_shape, y_shape, cols, y_cols, flattens):
    """The rule is the shape's: the traced ``mul`` holds a reshape only
    where the flattening does more than fold X's leading dims."""
    import jax

    from paddle_tpu.ops import math_ops

    x = rng.randn(*x_shape).astype(np.float32)
    y = rng.randn(*y_shape).astype(np.float32)
    out = run_op("mul", {"X": x, "Y": y},
                 attrs={"x_num_col_dims": cols, "y_num_col_dims": y_cols})
    want = (x.reshape(int(np.prod(x_shape[:cols])), -1)
            @ y.reshape(int(np.prod(y_shape[:y_cols])), -1))
    np.testing.assert_allclose(
        np.asarray(out["Out"]),
        want.reshape(x_shape[:cols] + y_shape[y_cols:]), rtol=1e-5,
        atol=1e-5)
    jaxpr = jax.make_jaxpr(lambda x, y: math_ops.mul(
        x, y, x_num_col_dims=cols, y_num_col_dims=y_cols)["Out"])(x, y)
    assert any(e.primitive.name == "reshape"
               for e in jaxpr.eqns) == flattens, jaxpr
    assert math_ops.folds_rows_only(x, y, cols, y_cols) == (
        not flattens and x.ndim > 2)


def test_matmul_transpose():
    x = rng.randn(3, 4).astype(np.float32)
    y = rng.randn(5, 4).astype(np.float32)
    check_output(
        "matmul", {"X": x, "Y": y}, {"Out": x @ y.T},
        attrs={"transpose_Y": True}, atol=1e-4,
    )
    check_grad("matmul", {"X": x, "Y": y}, "X", attrs={"transpose_Y": True})


def test_sum_multiple_inputs():
    xs = [rng.randn(2, 3).astype(np.float32) for _ in range(3)]
    check_output("sum", {"X": xs}, {"Out": xs[0] + xs[1] + xs[2]})


def test_reduce_ops():
    x = rng.randn(2, 3, 4).astype(np.float32)
    check_output("reduce_sum", {"X": x}, {"Out": x.sum(1)}, attrs={"dim": 1})
    check_output(
        "reduce_mean", {"X": x}, {"Out": x.mean((0, 2), keepdims=True)},
        attrs={"dim": [0, 2], "keep_dim": True},
    )
    check_output("reduce_max", {"X": x}, {"Out": x.max()}, attrs={"reduce_all": True})
    check_grad("reduce_sum", {"X": x}, "X", attrs={"dim": 1})
    check_grad("reduce_mean", {"X": x}, "X", attrs={"dim": [0, 2]})


def test_scale_clip_sign():
    x = rng.randn(3, 3).astype(np.float32)
    check_output("scale", {"X": x}, {"Out": x * 2.0 + 1.0},
                 attrs={"scale": 2.0, "bias": 1.0})
    check_output("clip", {"X": x}, {"Out": np.clip(x, -0.5, 0.5)},
                 attrs={"min": -0.5, "max": 0.5})
    check_output("sign", {"X": x}, {"Out": np.sign(x)})


def test_clip_by_norm():
    x = (rng.randn(4, 4) * 10).astype(np.float32)
    norm = np.sqrt((x ** 2).sum())
    check_output("clip_by_norm", {"X": x}, {"Out": x * (1.0 / norm)},
                 attrs={"max_norm": 1.0}, atol=1e-4)


def test_cos_sim():
    x = rng.randn(4, 8).astype(np.float32)
    y = rng.randn(4, 8).astype(np.float32)
    expected = (x * y).sum(1) / (
        np.linalg.norm(x, axis=1) * np.linalg.norm(y, axis=1)
    )
    got = run_op("cos_sim", {"X": x, "Y": y})
    np.testing.assert_allclose(got["Out"].reshape(-1), expected, rtol=1e-4)
    check_grad("cos_sim", {"X": x, "Y": y}, "X", max_relative_error=1e-2)


def test_activations_match_numpy():
    x = rng.randn(3, 4).astype(np.float32)
    check_output("sigmoid", {"X": x}, {"Out": 1 / (1 + np.exp(-x))}, atol=1e-5)
    check_output("tanh", {"X": x}, {"Out": np.tanh(x)})
    check_output("relu", {"X": x}, {"Out": np.maximum(x, 0)})
    check_output("square", {"X": x}, {"Out": x * x})
    check_output("leaky_relu", {"X": x},
                 {"Out": np.where(x > 0, x, 0.02 * x)}, attrs={"alpha": 0.02})


@pytest.mark.parametrize("op", ["sigmoid", "tanh", "softplus", "swish", "elu"])
def test_activation_grads(op):
    x = rng.randn(3, 4).astype(np.float32)
    check_grad(op, {"X": x}, "X")


def test_softmax_and_grad():
    x = rng.randn(4, 7).astype(np.float32)
    e = np.exp(x - x.max(-1, keepdims=True))
    check_output("softmax", {"X": x}, {"Out": e / e.sum(-1, keepdims=True)}, atol=1e-5)
    check_grad("softmax", {"X": x}, "X",
               loss_weights=rng.rand(4, 7).astype(np.float32))


def test_l1_norm():
    x = rng.randn(3, 4).astype(np.float32)
    check_output("l1_norm", {"X": x}, {"Out": np.abs(x).sum().reshape(1)})
    check_grad("l1_norm", {"X": x + np.sign(x) * 0.1}, "X")


def test_bilinear_tensor_product():
    b, dx, dy, size = 3, 4, 5, 2
    x = rng.randn(b, dx).astype(np.float32)
    y = rng.randn(b, dy).astype(np.float32)
    w = rng.randn(size, dx, dy).astype(np.float32)
    bias = rng.randn(size).astype(np.float32)
    want = np.einsum("bj,ijk,bk->bi", x, w, y) + bias
    check_output("bilinear_tensor_product",
                 {"X": x, "Y": y, "Weight": w, "Bias": bias},
                 {"Out": want}, atol=1e-4, rtol=1e-4)
    check_grad("bilinear_tensor_product",
               {"X": x, "Y": y, "Weight": w, "Bias": bias}, "Weight")
    check_grad("bilinear_tensor_product",
               {"X": x, "Y": y, "Weight": w, "Bias": bias}, "X")


def test_prelu():
    x = rng.randn(3, 4).astype(np.float32)
    a = np.asarray([0.25], np.float32)
    check_output("prelu", {"X": x, "Alpha": a},
                 {"Out": np.where(x >= 0, x, 0.25 * x)})
    check_grad("prelu", {"X": x + np.sign(x) * 0.1, "Alpha": a}, "Alpha")


def test_error_clip():
    """ErrorClipByValue: forward unchanged, backward error clipped at the
    marked variable (reference fluid/clip.py:37)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt

    # op level: grad of sum(10*x) through error_clip is clipped to 0.1
    from paddle_tpu.core.registry import get_op_impl

    impl = get_op_impl("error_clip").fn

    def f(x):
        y = impl(X=x, max=0.1)["Out"]
        return jnp.sum(10.0 * y)

    g = jax.grad(f)(jnp.ones((3,)))
    np.testing.assert_allclose(np.asarray(g), 0.1)

    # program level: rewrite via error_clip_callback
    x = pt.layers.data("x", shape=[4])
    h = pt.layers.fc(x, 4, bias_attr=False, name="ec_fc")
    out = pt.layers.scale(h, scale=100.0)
    cost = pt.layers.reduce_sum(out)
    clipped = pt.clip.error_clip_callback(h, pt.clip.ErrorClipByValue(0.01))
    pt.optimizer.SGD(learning_rate=1.0).minimize(cost)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    scope = pt.core.scope.global_scope()
    w0 = np.asarray(scope.get("ec_fc.w")).copy()
    xv = np.ones((2, 4), np.float32)
    exe.run(feed={"x": xv}, fetch_list=[cost])
    w1 = np.asarray(scope.get("ec_fc.w"))
    # dL/dW = x^T @ err, err clipped to 0.01 per element, batch 2 -> 0.02;
    # unclipped would be 100 per element
    np.testing.assert_allclose(w0 - w1, 0.02 * np.ones_like(w0),
                               rtol=1e-5, atol=1e-6)


def test_error_clip_after_minimize_keeps_backward_split():
    """Inserting the error-clip op after minimize must shift the
    forward/backward boundary so the step still lowers correctly."""
    import paddle_tpu as pt

    x = pt.layers.data("x", shape=[4])
    h = pt.layers.fc(x, 4, bias_attr=False, name="ec2_fc")
    cost = pt.layers.reduce_sum(pt.layers.scale(h, scale=10.0))
    pt.optimizer.SGD(learning_rate=1.0).minimize(cost)
    pt.clip.error_clip_callback(h, pt.clip.ErrorClipByValue(0.01))
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    scope = pt.core.scope.global_scope()
    w0 = np.asarray(scope.get("ec2_fc.w")).copy()
    xv = np.ones((2, 4), np.float32)
    (c,) = exe.run(feed={"x": xv}, fetch_list=[cost])
    assert np.isfinite(c).all()
    w1 = np.asarray(scope.get("ec2_fc.w"))
    np.testing.assert_allclose(w0 - w1, 0.02 * np.ones_like(w0),
                               rtol=1e-5, atol=1e-6)
