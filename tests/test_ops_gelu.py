"""The ``gelu`` op by the width of its input: a bfloat16 or float16 input
takes one float32 ``erf`` and a closed-form derivative, rounded once; a
float32 or float64 input keeps ``jax.nn.gelu(approximate=False)`` to the
bit.  The 16-bit evaluation is held to the float64 function over EVERY
finite value of the format, and to the bit-exactness the scan-remat
engine needs of an op inside ``jax.checkpoint`` inside ``lax.scan``."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import activation_ops


def _op(x):
    return activation_ops.gelu(x)["Out"]


def _erfc_form(x):
    """What the op was until PR 41, in every dtype."""
    return jax.nn.gelu(x, approximate=False)


def _gradient(fn, values):
    return jax.grad(lambda v: fn(v).astype(jnp.float32).sum())(values)


def _float64(array):
    return np.asarray(array.astype(jnp.float32), np.float64)


@functools.lru_cache(maxsize=None)
def _every_finite(dtype):
    """(values, them as float64, x Phi(x), its derivative) over every
    finite value of a 16-bit format."""
    values = jnp.asarray(np.arange(65536, dtype=np.uint16)).view(dtype)
    with np.errstate(invalid="ignore"):
        x = _float64(values)
    finite = np.isfinite(x)
    values, x = values[finite], x[finite]
    cdf = 0.5 * np.vectorize(math.erfc)(-x * math.sqrt(0.5))
    slope = cdf + x * np.exp(-0.5 * x * x) / math.sqrt(2.0 * math.pi)
    return values, x, x * cdf, slope


SIXTEEN = [jnp.bfloat16, jnp.float16]
# the erfc form's worst absolute errors within |x| <= 4, forward and
# gradient (its argument is rounded to 16 bits before erfc): what the op
# may not exceed, as numbers and as the form itself evaluated here
ERFC_FORM_WORST = {jnp.bfloat16: (0.00982, 0.00793),
                   jnp.float16: (0.0016, 0.00101)}


@pytest.mark.parametrize("dtype", SIXTEEN, ids=lambda d: d.__name__)
def test_gelu_16bit_forward_against_float64(dtype):
    values, x, want, _ = _every_finite(dtype)
    out = _op(values)
    assert out.dtype == dtype
    got = _float64(out)
    near = np.abs(x) <= 4
    # relative error where the result is a normal number of the format
    normal = near & (np.abs(want) >= float(jnp.finfo(dtype).tiny))
    relative = np.abs(got - want)[normal] / np.abs(want[normal])
    assert relative.max() <= 0.005, x[normal][relative.argmax()]
    before = np.abs(_float64(_erfc_form(values)) - want)
    assert np.abs(got - want)[near].max() <= min(
        before[near].max(), ERFC_FORM_WORST[dtype][0])
    assert np.abs(got - want)[x < -4].max() <= 1e-6
    # half a step of the format beyond 4, where x Phi(x) is x
    far = x > 4
    assert (np.abs(got - want)[far] <= np.abs(x[far]) * 2.0 ** -8).all()


@pytest.mark.parametrize("dtype", SIXTEEN, ids=lambda d: d.__name__)
def test_gelu_16bit_gradient_against_float64(dtype):
    values, x, _, slope = _every_finite(dtype)
    grad = _gradient(_op, values)
    assert grad.dtype == dtype
    got = _float64(grad)
    near = np.abs(x) <= 4
    before = np.abs(_float64(_gradient(_erfc_form, values)) - slope)
    assert np.abs(got - slope)[near].max() <= min(
        before[near].max(), ERFC_FORM_WORST[dtype][1])
    assert np.abs(got - slope)[x < -4].max() <= 1e-6
    assert np.abs(got - slope)[x > 4].max() <= 2.0 ** -8


@pytest.mark.parametrize("dtype", SIXTEEN, ids=lambda d: d.__name__)
def test_gelu_16bit_far_tail_is_zero_and_finite(dtype):
    """float32's erf stops short of -1 on XLA:CPU (-1 + 1.8e-7), so
    0.5 * x * (1 + erf) alone returns -896 at -1e10 and -inf at -3.4e38."""
    values, x, _, _ = _every_finite(dtype)
    got, slope = _float64(_op(values)), _float64(_gradient(_op, values))
    assert np.isfinite(got).all() and np.isfinite(slope).all()
    far = x < -40
    assert far.sum() > 1000 and x[far].min() == float(jnp.finfo(dtype).min)
    assert not got[far].any() and not slope[far].any()


@pytest.mark.parametrize("dtype", SIXTEEN, ids=lambda d: d.__name__)
def test_gelu_16bit_nan_and_infinities_as_erfc_form(dtype):
    values = jnp.asarray([np.nan, np.inf, -np.inf], dtype)
    for fn in (lambda f: f(values), lambda f: _gradient(f, values)):
        np.testing.assert_array_equal(_float64(fn(_op)),
                                      _float64(fn(_erfc_form)))
    out = _float64(_op(values))
    assert np.isnan(out[0]) and out[1] == np.inf


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_gelu_wide_input_keeps_jax_bits(dtype):
    with jax.enable_x64(True):
        rng = np.random.RandomState(41)
        x = jnp.asarray(np.concatenate([
            rng.normal(size=4096) * 3.0, rng.uniform(-12.0, -4.0, 512),
            [0.0, -0.0, -40.0, 40.0, -1e10, np.inf, -np.inf, np.nan]]), dtype)
        assert x.dtype == np.dtype(dtype) and _op(x).dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(_op(x)),
                                      np.asarray(_erfc_form(x)))
        np.testing.assert_array_equal(
            np.asarray(jax.grad(lambda v: _op(v).sum())(x)),
            np.asarray(jax.grad(lambda v: _erfc_form(v).sum())(x)))


@pytest.mark.parametrize("with_bias", [False, True],
                         ids=["op_alone", "bias_add_and_op"])
def test_gelu_bf16_same_bits_scanned_and_unrolled(with_bias):
    """The scan-remat engine's contract on the op alone: inside a
    ``jax.checkpoint`` inside a ``lax.scan`` of three steps and unrolled,
    both compiled as the engine compiles them, the same outputs and the
    same input gradients to the bit; and with the bias add the
    `selective` policy puts in the same segment.  NOT covered, and not
    equal on XLA:CPU: a 16-bit elementwise consumer of the op's output
    in the same segment (``op(h) * b``: the compiler keeps excess
    precision across two 16-bit operations, and not alike scanned and
    unrolled); a whole bfloat16 FFN stack's gradients differ there with
    the erfc form as well (PERF.md section 7, PR 41)."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.normal(size=(64, 256)) * 2.0, jnp.bfloat16)
    biases = jnp.asarray(rng.normal(size=(3, 256)), jnp.bfloat16)
    weights = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)

    @jax.checkpoint
    def segment(h, b):
        return _op(h + b) if with_bias else _op(h)

    def scanned(x, biases):
        out, kept = jax.lax.scan(lambda h, b: (segment(h, b),) * 2, x, biases)
        return out, kept

    def unrolled(x, biases):
        kept = []
        for i in range(3):
            x = segment(x, biases[i])
            kept.append(x)
        return x, jnp.stack(kept)

    def loss(fn):
        def scalar(x, biases):
            out, kept = fn(x, biases)
            return ((out.astype(jnp.float32) * weights).sum()
                    + kept.astype(jnp.float32).sum())
        return scalar

    for a, b in zip(jax.jit(scanned)(x, biases), jax.jit(unrolled)(x, biases)):
        np.testing.assert_array_equal(_float64(a), _float64(b))
    grads = [jax.jit(jax.grad(loss(fn), argnums=(0, 1)))(x, biases)
             for fn in (scanned, unrolled)]
    for a, b in zip(*grads):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_array_equal(_float64(a), _float64(b))
    assert np.abs(_float64(grads[0][0])).max() > 0


def test_gelu_16bit_backward_keeps_the_input_alone():
    """The custom VJP's residual is the op's input (what the `selective`
    checkpoint saves already): nothing the size of the input but the
    input itself leaves the forward."""
    x = jnp.zeros((8, 128), jnp.bfloat16)
    _, vjp = jax.vjp(_op, x)
    kept = [leaf for leaf in jax.tree_util.tree_leaves(vjp)
            if getattr(leaf, "shape", None) == x.shape]
    assert len(kept) == 1 and kept[0].dtype == jnp.bfloat16
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda v: _op(v).astype(
        jnp.float32).sum()))(x))
    # one erf for the value, one for the slope, and no erfc
    assert "erfc" not in jaxpr and jaxpr.count(" erf ") == 2
