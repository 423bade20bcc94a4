"""Activation ops.

Reference: ``paddle/operators/activation_op.{cc,cu}`` — ~20 activations via
functor templates, each with a hand-written gradient functor.  Here each is
one jnp expression; gradients come from JAX AD and XLA fuses them into
neighbouring matmuls (the reference needed separate kernel launches).
"""

import jax
import jax.numpy as jnp

from ..core.registry import register_op


def _simple(name, fn):
    @register_op(name)
    def _op(X, **attrs):
        return {"Out": fn(X, **{k: v for k, v in attrs.items() if not k.startswith("_")})}

    _op.__name__ = name
    return _op


_simple("sigmoid", lambda X: jax.nn.sigmoid(X))
_simple("logsigmoid", lambda X: jax.nn.log_sigmoid(X))
_simple("exp", lambda X: jnp.exp(X))
_simple("relu", lambda X: jax.nn.relu(X))
_simple("tanh", lambda X: jnp.tanh(X))
_simple("tanh_shrink", lambda X: X - jnp.tanh(X))
_simple("sqrt", lambda X: jnp.sqrt(X))
_simple("abs", lambda X: jnp.abs(X))
_simple("ceil", lambda X: jnp.ceil(X))
_simple("floor", lambda X: jnp.floor(X))
_simple("round", lambda X: jnp.round(X))
_simple("reciprocal", lambda X: 1.0 / X)
_simple("log", lambda X: jnp.log(X))
_simple("square", lambda X: jnp.square(X))
_simple("softplus", lambda X: jax.nn.softplus(X))


# The exact GELU x * Phi(x), never the tanh approximation: tanh's
# backward is not reassociation-stable between unrolled and lax.scan
# execution on XLA:CPU (measured 1e-3-level grad drift), which would
# break the scan-remat engine's bit-exactness contract; this evaluation
# has no tanh and no reduction and is the same bits either way
# (tests/test_ops_gelu.py pins it).
_SQRT_HALF = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327
# below this Phi(x) is under 1e-9 and float32's 1 + erf holds no digit
# of it (XLA:CPU's erf stops at -1 + 1.8e-7, which would leak
# 0.5 * x * 1.8e-7: -896 at x = -1e10): Phi is zero there
_GELU_ZERO_BELOW = -6.0


def _normal_cdf(xf):
    cdf = 0.5 * (1.0 + jax.lax.erf(xf * jnp.float32(_SQRT_HALF)))
    return jnp.where(xf < _GELU_ZERO_BELOW, 0.0, cdf)


@jax.custom_vjp
def _gelu_16bit(x):
    """x * Phi(x) of a bfloat16/float16 ``x``: float32 arithmetic (the
    VPU has no other), ONE erf, one rounding at the end."""
    xf = x.astype(jnp.float32)
    return (xf * _normal_cdf(xf)).astype(x.dtype)


def _gelu_16bit_fwd(x):
    return _gelu_16bit(x), x


def _gelu_16bit_bwd(x, g):
    # d/dx x Phi(x) = Phi(x) + x phi(x): one erf and one exp, where AD
    # through erfc's branches evaluates both of them again
    xf = x.astype(jnp.float32)
    pdf = jnp.exp(-0.5 * xf * xf) * jnp.float32(_INV_SQRT_2PI)
    slope = _normal_cdf(xf) + xf * pdf
    return ((g.astype(jnp.float32) * slope).astype(x.dtype),)


_gelu_16bit.defvjp(_gelu_16bit_fwd, _gelu_16bit_bwd)


@register_op("gelu")
def gelu(X, **_):
    # one function, evaluated by what the input is: a 16-bit input is
    # widened to float32 by the compiler whatever is written, and
    # jax.nn.gelu's 0.5 * x * erfc(-x * sqrt_half) then costs both of
    # erfc's branches (74 wide operations an element on a TPU v5e
    # against 12, an erf among them) AFTER rounding its argument to 16
    # bits; at 32 bits and above erfc's relative accuracy in the far
    # tail is visible and kept, to the bit
    if X.dtype in (jnp.bfloat16, jnp.float16):
        return {"Out": _gelu_16bit(X)}
    return {"Out": jax.nn.gelu(X, approximate=False)}


_simple("softsign", lambda X: X / (1 + jnp.abs(X)))


@register_op("prelu")
def prelu(X, Alpha, **_):
    # reference prelu_op.cc:46: f(x) = alpha*x for x<0 else x; Alpha is a
    # learnable scalar (the reference op takes exactly one alpha; a
    # channel-wise variant would need explicit axis alignment, so reject
    # silently-misbroadcast shapes).
    if Alpha.size != 1:
        raise ValueError(
            f"prelu Alpha must be a single scalar, got shape {Alpha.shape}")
    return {"Out": jnp.where(X >= 0, X, Alpha.reshape(()) * X)}


@register_op("brelu")
def brelu(X, t_min=0.0, t_max=24.0, **_):
    return {"Out": jnp.clip(X, t_min, t_max)}


@register_op("leaky_relu")
def leaky_relu(X, alpha=0.02, **_):
    return {"Out": jnp.where(X > 0, X, alpha * X)}


@register_op("soft_relu")
def soft_relu(X, threshold=40.0, **_):
    t = jnp.clip(X, -threshold, threshold)
    return {"Out": jnp.log1p(jnp.exp(t))}


@register_op("elu")
def elu(X, alpha=1.0, **_):
    return {"Out": jax.nn.elu(X, alpha)}


@register_op("relu6")
def relu6(X, threshold=6.0, **_):
    return {"Out": jnp.clip(X, 0.0, threshold)}


@register_op("pow")
def pow_op(X, factor=1.0, **_):
    return {"Out": jnp.power(X, factor)}


@register_op("stanh")
def stanh(X, scale_a=2.0 / 3.0, scale_b=1.7159, **_):
    return {"Out": scale_b * jnp.tanh(scale_a * X)}


@register_op("hard_shrink")
def hard_shrink(X, threshold=0.5, **_):
    return {"Out": jnp.where(jnp.abs(X) > threshold, X, 0.0)}


@register_op("softshrink")
def softshrink(X, lambda_=0.5, **attrs):
    lam = attrs.get("lambda", lambda_)
    return {"Out": jnp.where(X > lam, X - lam, jnp.where(X < -lam, X + lam, 0.0))}


@register_op("thresholded_relu")
def thresholded_relu(X, threshold=1.0, **_):
    return {"Out": jnp.where(X > threshold, X, 0.0)}


@register_op("hard_sigmoid")
def hard_sigmoid(X, slope=0.2, offset=0.5, **_):
    return {"Out": jnp.clip(slope * X + offset, 0.0, 1.0)}


@register_op("swish")
def swish(X, beta=1.0, **_):
    return {"Out": X * jax.nn.sigmoid(beta * X)}


@register_op("softmax")
def softmax(X, **_):
    return {"Out": jax.nn.softmax(X, axis=-1)}


@register_op("log_softmax")
def log_softmax(X, **_):
    return {"Out": jax.nn.log_softmax(X, axis=-1)}
