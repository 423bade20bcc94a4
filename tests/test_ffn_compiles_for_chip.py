"""One FFN layer of ``cgpt590m.train_2k`` compiled for a TPU v5e that is
described and not attached, through the ``gelu`` op: how many wide
elementwise instructions the chip's compiler leaves in the fusions round
the two products and in the checkpointed backward.  The VPU has no
16-bit arithmetic, so every one of them is a float32 pass over
``[4096, 6144]``; ``jax.nn.gelu(approximate=False)`` arrives there as
both branches of ``erfc`` (74 a forward evaluation, 86 in the backward:
ISSUE 41), the op's float32 ``erf`` form as 12 and 19.  Nothing runs: a
compile that passes is no chip run.  One file, the topology inside a
fixture (one process may hold the TPU's library).

The last two cases compile the FFN as the STEP lowers it (rows
``[2, 2048, .]`` through ``mul``'s flattening, under ``lax.scan`` with
its backward) and count the forward scan body's ``erf`` instructions:
the step's compiler makes another choice than the plain layer's, and
the scan-remat engine's reading product (ISSUE 54) is held to it."""

import collections
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROWS, D_MODEL, D_FF = 4096, 1536, 6144


def _op(h):
    from paddle_tpu.ops import activation_ops

    return activation_ops.gelu(h)["Out"]


def _erfc_form(h):
    return jax.nn.gelu(h, approximate=False)


def _layer(act):
    """The FFN as the Program lowers it under `selective`: ``mul``, the
    bias add and the activation in one checkpointed segment, ``mul``."""
    from paddle_tpu.ops.math_ops import mul

    def ffn(x, w1, b1, w2, b2):
        h = mul(x, w1)["Out"]
        a = jax.checkpoint(lambda h, b1: act(h + b1))(h, b1)
        return mul(a, w2)["Out"] + b2

    return ffn


def _optimized_hlo(fn, one_chip):
    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    return jax.jit(fn).lower(
        arg(ROWS, D_MODEL), arg(D_MODEL, D_FF), arg(D_FF),
        arg(D_FF, D_MODEL), arg(D_MODEL)).compile().as_text()


_WIDE = re.compile(r"^\w+\[%d,%d\]" % (ROWS, D_FF))
# what moves or names a value and computes nothing
_NOT_ARITHMETIC = {"parameter", "broadcast", "constant", "bitcast", "copy",
                   "fusion", "convolution", "transpose", "reshape",
                   "get-tuple-element", "dynamic-slice",
                   "dynamic-update-slice"}


def wide_elementwise(hlo):
    """{computation: its elementwise instructions on ``[ROWS, D_FF]``}."""
    from paddle_tpu.analysis.hlo_tools import iter_instructions

    return collections.Counter(
        i.comp for i in iter_instructions(hlo)
        if _WIDE.match(i.shape) and i.opcode not in _NOT_ARITHMETIC)


def _grad(act):
    ffn = _layer(act)
    return jax.grad(lambda *a: ffn(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2, 3, 4))


def test_ffn_forward_through_the_op_is_lean(one_chip):
    hlo = _optimized_hlo(_layer(_op), one_chip)
    assert "erfc" not in hlo
    counts = wide_elementwise(hlo)
    # 13: the product's convert, the bias add, the op's 11 and one erf
    assert 0 < max(counts.values()) <= 45, counts


def test_ffn_backward_through_the_op_is_lean(one_chip):
    hlo = _optimized_hlo(_grad(_op), one_chip)
    assert "erfc" not in hlo
    counts = wide_elementwise(hlo)
    # the checkpointed backward (19: one erf, one exponential) is the
    # widest; the forward evaluations stay under the forward's bound
    *others, widest = sorted(counts.values())
    assert 12 <= widest <= 55 and max(others) <= 45, counts


def test_the_count_sees_both_branches_of_erfc(one_chip):
    """The same layer through ``jax.nn.gelu(approximate=False)``: what
    the op cost a 16-bit input until PR 41, and the proof that the count
    above does not pass for want of anything to count."""
    forward = wide_elementwise(_optimized_hlo(_layer(_erfc_form), one_chip))
    backward = wide_elementwise(_optimized_hlo(_grad(_erfc_form), one_chip))
    assert max(forward.values()) > 60, forward
    assert max(backward.values()) > 70, backward


# -- the FFN as the training step lowers it ---------------------------------

STEP_ROWS, STEP_LAYERS = (2, 2048), 3


def _step_ffn(reading):
    """``STEP_LAYERS`` FFN layers as `cgpt590m.train_2k`'s scanned body
    runs them: a checkpointed norm, ``mul``, bias add and GELU in one
    checkpointed segment, ``mul``, bias and residual, every op under the
    scope the executor gives it; ``reading`` lowers the two products
    as the scan-remat engine does since PR 54 (their ``X`` is the output
    of a checkpointed sub-segment).  Returns the gradient function."""
    from paddle_tpu.ops import activation_ops
    from paddle_tpu.ops.math_ops import mul

    def product(name, x, w):
        with jax.named_scope(f"ffn/mul:block0_{name}"):
            return mul(x, w, x_num_col_dims=2, _reads_saved=reading)["Out"]

    def norm(x):
        xf = x.astype(jnp.float32)
        xf = xf - xf.mean(-1, keepdims=True)
        return (xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True)
                                   + 1e-5)).astype(x.dtype)

    def act(h, b1):
        with jax.named_scope("ffn/gelu:block0_ffn1"):
            return activation_ops.gelu(h + b1)["Out"]

    def body(x, layer):
        w1, b1, w2, b2 = layer
        h = product("ffn1", jax.checkpoint(norm)(x), w1)
        y = product("ffn2", jax.checkpoint(act)(h, b1), w2)
        return x + (y + b2), None

    def loss(layers, x):
        return jax.lax.scan(body, x, layers)[0].astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1))


def _step_hlo(reading, one_chip):
    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    n = STEP_LAYERS
    layers = (arg(n, D_MODEL, D_FF), arg(n, D_FF), arg(n, D_FF, D_MODEL),
              arg(n, D_MODEL))
    return jax.jit(_step_ffn(reading)).lower(
        layers, arg(*STEP_ROWS, D_MODEL)).compile().as_text()


def forward_erfs(hlo):
    """(``erf`` instructions the forward fusion of ``ffn2``'s product
    reaches through its called computations, those the whole forward
    scan body reaches)."""
    from paddle_tpu.analysis.hlo_tools import (called_computations,
                                               iter_instructions)

    by_comp = collections.defaultdict(list)
    for i in iter_instructions(hlo):
        by_comp[i.comp].append(i)

    def erfs(instruction):
        n = int(instruction.opcode == "erf")
        for comp in called_computations(instruction.head):
            n += sum(erfs(j) for j in by_comp[comp])
        return n

    fused = {c for body in by_comp.values() for i in body
             if i.opcode == "fusion" for c in called_computations(i.head)}
    (ffn2,) = [i for body in by_comp.values() for i in body
               if i.opcode == "fusion" and i.comp not in fused
               and i.op_name.endswith("mul:block0_ffn2/dot_general")
               and "transpose(jvp(" not in i.op_name]
    return erfs(ffn2), sum(erfs(i) for i in by_comp[ffn2.comp])


def test_step_ffn_evaluates_gelu_once_in_the_forward(one_chip):
    under_ffn2, in_body = forward_erfs(_step_hlo(True, one_chip))
    # ONE evaluation, in ffn1's fusion with the stack writes: a second
    # one here is the stack-writing kLoop of ISSUE 54's row three
    assert (under_ffn2, in_body) == (0, 1)


def test_step_ffn_through_plain_mul_evaluates_it_twice(one_chip):
    """The parent's lowering, and the proof that the harness sees the
    STEP's choice: the product's operand side holds the second GELU."""
    under_ffn2, in_body = forward_erfs(_step_hlo(False, one_chip))
    assert (under_ffn2, in_body) == (1, 2)
