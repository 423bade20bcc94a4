"""GEMM / elementwise / reduction ops.

Reference groups (SURVEY §2.2): ``mul_op``, ``matmul_op`` (cuBLAS via
``operators/math/math_function``), ``elementwise_*_op`` with the axis
broadcast rule (``elementwise_op_function.h``), ``reduce_op``, ``sum_op``,
``scale/sign/clip/cast/minus`` etc.  All become single jnp/lax calls that XLA
maps straight onto the MXU (dots) and VPU (elementwise) — matmuls
accumulate in float32 via ``preferred_element_type`` so bfloat16 inputs keep
MXU-native speed without losing accumulation precision.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op
from ..core.dtypes import convert_dtype


def _acc_type(x):
    if x.dtype in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return None


def _broadcast_y(X, Y, axis):
    """Reference broadcast rule (elementwise_op_function.h): Y's dims align
    with X's dims starting at ``axis`` (default -1 = align trailing)."""
    if Y.ndim == 0 or X.shape == Y.shape:
        return Y
    ax = axis if axis >= 0 else X.ndim - Y.ndim
    # trim trailing size-1 dims like the reference does
    yshape = list(Y.shape)
    while yshape and yshape[-1] == 1 and len(yshape) > X.ndim - ax:
        yshape.pop()
    newshape = [1] * X.ndim
    newshape[ax : ax + len(yshape)] = yshape
    return Y.reshape(newshape)


def _register_elementwise(name, fn):
    @register_op("elementwise_" + name)
    def _op(X, Y, axis=-1, **_):
        return {"Out": fn(X, _broadcast_y(X, Y, axis))}

    _op.__name__ = "elementwise_" + name
    return _op


_register_elementwise("add", jnp.add)
_register_elementwise("sub", jnp.subtract)
_register_elementwise("mul", jnp.multiply)
_register_elementwise("div", jnp.divide)
_register_elementwise("max", jnp.maximum)
_register_elementwise("min", jnp.minimum)
_register_elementwise("pow", jnp.power)


def folds_rows_only(X, Y, x_num_col_dims, y_num_col_dims):
    """Whether ``mul``'s flattening would only fold X's leading dims into
    rows: the contraction is then over X's last dim as X stands."""
    return (X.ndim > 2 and x_num_col_dims == X.ndim - 1
            and (Y.ndim, y_num_col_dims) == (2, 1))


def _dot(X, Y):
    out = jnp.dot(X, Y, preferred_element_type=_acc_type(X))
    return out if out.dtype == X.dtype else out.astype(X.dtype)


def _mul_flat(X, Y, x_num_col_dims, y_num_col_dims):
    """Flattening matmul (reference mul_op.cc): X collapses to 2-D at
    x_num_col_dims, Y at y_num_col_dims."""
    return _dot(X.reshape((int(np.prod(X.shape[:x_num_col_dims])), -1)),
                Y.reshape((int(np.prod(Y.shape[:y_num_col_dims])), -1)))


def _mul(X, Y, x_num_col_dims, y_num_col_dims):
    """``mul_op``'s product.  Rows that only fold (``folds_rows_only``)
    take it over X as it stands, forward and backward, whoever made X:
    the same contraction with no reshape on either side of it.  A
    reshape between a product and its neighbours un-fuses them on the
    chip: the bias add becomes a pass of its own, and a scan's saved
    stack is written by a second pass that adds the bias again
    (docs/memory.md; PERF.md section 6, PRs 54 and 60)."""
    if folds_rows_only(X, Y, x_num_col_dims, y_num_col_dims):
        return _dot(X, Y)
    return _mul_flat(X, Y, x_num_col_dims, y_num_col_dims)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mul_reading(X, Y, x_num_col_dims, y_num_col_dims):
    """``_mul`` whose left operand is READ.  The barrier makes the
    compiler materialise X once, where it is otherwise free to evaluate
    a cheap elementwise producer again inside the product's operand
    fusion (0.31 ms a [4096, 6144] GELU whatever it holds: PERF.md
    section 6, PR 54).  The barrier is on the forward's operand alone
    and the residuals are the operands as they came, so what a scan
    saves and every gradient keep their values."""
    return _mul(jax.lax.optimization_barrier(X), Y, x_num_col_dims,
                y_num_col_dims)


def _mul_reading_fwd(X, Y, x_num_col_dims, y_num_col_dims):
    return _mul_reading(X, Y, x_num_col_dims, y_num_col_dims), (X, Y)


def _mul_reading_bwd(x_num_col_dims, y_num_col_dims, operands, g):
    return jax.vjp(
        lambda X, Y: _mul(X, Y, x_num_col_dims, y_num_col_dims),
        *operands)[1](g)


_mul_reading.defvjp(_mul_reading_fwd, _mul_reading_bwd)


@register_op("mul")
def mul(X, Y, x_num_col_dims=1, y_num_col_dims=1, _reads_saved=False, **_):
    """``_mul``.  ``_reads_saved`` is the scan-remat engine's
    (``core/executor.py``): X is the output of a checkpointed
    sub-segment of the scanned body, and the product reads it."""
    product = _mul_reading if _reads_saved else _mul
    out = product(X, Y, x_num_col_dims, y_num_col_dims)
    return {"Out": out.reshape(
        X.shape[:x_num_col_dims] + Y.shape[y_num_col_dims:])}


@register_op("matmul")
def matmul(X, Y, transpose_X=False, transpose_Y=False, alpha=1.0, **_):
    x = jnp.swapaxes(X, -1, -2) if transpose_X and X.ndim >= 2 else X
    y = jnp.swapaxes(Y, -1, -2) if transpose_Y and Y.ndim >= 2 else Y
    out = jnp.matmul(x, y, preferred_element_type=_acc_type(x))
    if out.dtype != X.dtype:
        out = out.astype(X.dtype)
    if alpha != 1.0:
        out = out * alpha
    return {"Out": out}


@register_op("sum")
def sum_op(X, **_):
    xs = X if isinstance(X, (list, tuple)) else [X]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {"Out": out}


@register_op("scale")
def scale(X, scale=1.0, bias=0.0, bias_after_scale=True, **_):
    if bias_after_scale:
        return {"Out": X * scale + bias}
    return {"Out": (X + bias) * scale}


@register_op("minus")
def minus(X, Y, **_):
    return {"Out": X - Y}


@register_op("sign")
def sign(X, **_):
    return {"Out": jnp.sign(X)}


@register_op("clip")
def clip(X, min=-1.0, max=1.0, **_):
    return {"Out": jnp.clip(X, min, max)}


@register_op("clip_by_norm")
def clip_by_norm(X, max_norm=1.0, **_):
    norm = jnp.sqrt(jnp.sum(jnp.square(X.astype(jnp.float32))))
    factor = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12), 1.0)
    return {"Out": (X * factor.astype(X.dtype))}


@register_op("cast")
def cast(X, out_dtype="float32", **_):
    return {"Out": X.astype(convert_dtype(out_dtype))}


def _reduce(fn, X, dim, keep_dim, reduce_all):
    if reduce_all or dim is None:
        axis = None
    else:
        axis = tuple(dim) if isinstance(dim, (list, tuple)) else (dim,)
    return fn(X, axis=axis, keepdims=keep_dim)


def _register_reduce(name, fn):
    @register_op("reduce_" + name)
    def _op(X, dim=None, keep_dim=False, reduce_all=False, **_):
        return {"Out": _reduce(fn, X, dim, keep_dim, reduce_all)}

    return _op


_register_reduce("sum", jnp.sum)
_register_reduce("mean", jnp.mean)
_register_reduce("max", jnp.max)
_register_reduce("min", jnp.min)
_register_reduce("prod", jnp.prod)


@register_op("mean")
def mean(X, **_):
    return {"Out": jnp.mean(X).reshape(1)}


@register_op("squared_l2_norm")
def squared_l2_norm(X, **_):
    return {"Out": jnp.sum(jnp.square(X)).reshape(1)}


@register_op("l1_norm")
def l1_norm(X, **_):
    # reference l1_norm_op.h: Out = sum(|X|)
    return {"Out": jnp.sum(jnp.abs(X)).reshape(1)}


@register_op("bilinear_tensor_product")
def bilinear_tensor_product(X, Y, Weight, Bias=None, **_):
    # reference bilinear_tensor_product_op.h:30: out[b,i] =
    # x[b,:] @ W[i,:,:] @ y[b,:] (+ bias[i]); one einsum on the MXU
    # replaces the per-output-channel gemm loop.
    out = jnp.einsum("bj,ijk,bk->bi", X, Weight.astype(X.dtype), Y)
    if Bias is not None:
        out = out + Bias.astype(X.dtype)
    return {"Out": out}


@register_op("squared_l2_distance")
def squared_l2_distance(X, Y, **_):
    d = X - _broadcast_y(X, Y, -1)
    sub = d.reshape((d.shape[0], -1))
    return {"sub_result": sub, "Out": jnp.sum(jnp.square(sub), axis=1, keepdims=True)}


@register_op("cos_sim")
def cos_sim(X, Y, **_):
    # Y may have batch 1 (broadcast against all rows of X), cos_sim_op.cc
    if Y.shape[0] == 1 and X.shape[0] != 1:
        Y = jnp.broadcast_to(Y, X.shape)
    xn = jnp.sqrt(jnp.sum(jnp.square(X), axis=1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(jnp.square(Y), axis=1, keepdims=True))
    out = jnp.sum(X * Y, axis=1, keepdims=True) / jnp.maximum(xn * yn, 1e-12)
    return {"Out": out, "XNorm": xn, "YNorm": yn}


@register_op("dot")
def dot(X, Y, **_):
    return {"Out": jnp.sum(X * Y, axis=-1, keepdims=True)}


@register_op("norm")
def norm(X, Input=None, epsilon=1e-10, **_):
    # reference norm_op: l2-normalize across channel dim (NCHW dim 1),
    # optionally scaled by a learnable per-channel Scale input.
    sq = jnp.sum(jnp.square(X), axis=1, keepdims=True)
    out = X / jnp.sqrt(sq + epsilon)
    if Input is not None:
        out = out * Input.reshape((1, -1) + (1,) * (X.ndim - 2))
    return {"Out": out}


@register_op("maxout")
def maxout(X, groups=2, **_):
    n, c, h, w = X.shape
    return {"Out": jnp.max(X.reshape(n, c // groups, groups, h, w), axis=2)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _identity_clip_grad(x, lo, hi):
    return x


def _icg_fwd(x, lo, hi):
    return x, None


def _icg_bwd(lo, hi, _res, g):
    return (jnp.clip(g, lo, hi),)


_identity_clip_grad.defvjp(_icg_fwd, _icg_bwd)


@register_op("error_clip")
def error_clip(X, max=1.0, min=None, **_):
    # reference fluid/clip.py ErrorClipByValue: identity forward, the
    # BACKPROPAGATED error through this point is clipped to [min, max] —
    # realized as a custom-VJP identity (jax.grad sees the clipped
    # cotangent exactly where the reference's backward rewrite clipped).
    lo = -abs(float(max)) if min is None else float(min)
    return {"Out": _identity_clip_grad(X, lo, float(max))}


@register_op("selective_fc")
def selective_fc(X, W, Bias=None, Select=None, **_):
    """Selective fully-connected: compute only the selected output columns
    per sample — the reference's large-output-layer capability
    (``paddle/gserver/layers/SelectiveFcLayer.cpp:1``; weight stored
    transposed there too, one row per output neuron).

    X [b,d]; W [k,d] (row-major by output neuron); Bias [k];
    Select [b,s] int ids, entries < 0 are padding.  With Select, Out is
    [b,s] (padded positions 0); without, a plain full fc Out [b,k].
    """
    if Select is None:
        out = X @ W.T
        if Bias is not None:
            out = out + Bias.reshape(1, -1)
        return {"Out": out}
    sel = Select.astype(jnp.int32)
    valid = sel >= 0
    idx = jnp.maximum(sel, 0)
    rows = W[idx]  # [b, s, d]
    out = jnp.einsum("bsd,bd->bs", rows, X)
    if Bias is not None:
        out = out + Bias.reshape(-1)[idx]
    return {"Out": jnp.where(valid, out, 0.0)}
