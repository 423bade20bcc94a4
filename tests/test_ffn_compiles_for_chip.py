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
the scan-remat engine's reading product (ISSUE 54) is held to it.  The
cases after them compile the step's ATTENTION half the same way and
count what the forward scan body holds round the q, k, v and out
products: ``mul`` takes a product over rows that only fold as they
stand (ISSUE 60), so each product's fusion holds its bias add and
writes its own saved stack; through the flat spelling the bias add is a
pass of its own and the stack write adds the bias again."""

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


def _bf16_on(one_chip):
    """``arg(*shape)``: a bfloat16 argument of that shape on the chip."""
    return lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                               sharding=one_chip)


def _optimized_hlo(fn, one_chip):
    arg = _bf16_on(one_chip)
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


def _product(form, x, w):
    """``x [2, 2048, .] @ w`` through ``mul``: ``"reading"`` as the
    scan-remat engine lowers a product whose ``X`` a checkpointed
    sub-segment made (PR 54), ``"whole"`` as ``mul`` lowers any product
    over rows that only fold (PR 60), ``"flat"`` through the flattening
    both took until then (``_mul_flat``: the parents' lowering)."""
    from paddle_tpu.ops import math_ops

    if form == "flat":
        return math_ops._mul_flat(x, w, 2, 1).reshape(
            x.shape[:2] + w.shape[1:])
    return math_ops.mul(x, w, x_num_col_dims=2,
                        _reads_saved=form == "reading")["Out"]


def _norm(x):
    xf = x.astype(jnp.float32)
    xf = xf - xf.mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True)
                               + 1e-5)).astype(x.dtype)


def _step_ffn(form):
    """``STEP_LAYERS`` FFN layers as `cgpt590m.train_2k`'s scanned body
    runs them: a checkpointed norm, ``mul``, bias add and GELU in one
    checkpointed segment, ``mul``, bias and residual, every op under the
    scope the executor gives it; ``form`` is ``_product``'s.  Returns
    the gradient function."""
    from paddle_tpu.ops import activation_ops

    def product(name, x, w):
        with jax.named_scope(f"ffn/mul:block0_{name}"):
            return _product(form, x, w)

    def act(h, b1):
        with jax.named_scope("ffn/gelu:block0_ffn1"):
            return activation_ops.gelu(h + b1)["Out"]

    def body(x, layer):
        w1, b1, w2, b2 = layer
        h = product("ffn1", jax.checkpoint(_norm)(x), w1)
        y = product("ffn2", jax.checkpoint(act)(h, b1), w2)
        return x + (y + b2), None

    def loss(layers, x):
        return jax.lax.scan(body, x, layers)[0].astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1))


def _step_hlo(form, one_chip):
    arg, n = _bf16_on(one_chip), STEP_LAYERS
    layers = (arg(n, D_MODEL, D_FF), arg(n, D_FF), arg(n, D_FF, D_MODEL),
              arg(n, D_MODEL))
    return jax.jit(_step_ffn(form)).lower(
        layers, arg(*STEP_ROWS, D_MODEL)).compile().as_text()


def _by_computation(hlo):
    """({computation: its instructions}, ``reached(instruction)``: every
    instruction it reaches through its called computations)."""
    from paddle_tpu.analysis.hlo_tools import (called_computations,
                                               iter_instructions)

    by_comp = collections.defaultdict(list)
    for i in iter_instructions(hlo):
        by_comp[i.comp].append(i)

    def reached(instruction):
        for comp in called_computations(instruction.head):
            for j in by_comp[comp]:
                yield j
                yield from reached(j)

    return by_comp, reached


def forward_erfs(hlo):
    """(``erf`` instructions the forward fusion of ``ffn2``'s product
    reaches through its called computations, those the whole forward
    scan body reaches)."""
    from paddle_tpu.analysis.hlo_tools import called_computations

    by_comp, reached = _by_computation(hlo)

    def erfs(instruction):
        return sum(j.opcode == "erf" for j in (instruction,
                                               *reached(instruction)))

    fused = {c for body in by_comp.values() for i in body
             if i.opcode == "fusion" for c in called_computations(i.head)}
    (ffn2,) = [i for body in by_comp.values() for i in body
               if i.opcode == "fusion" and i.comp not in fused
               and i.op_name.endswith("mul:block0_ffn2/dot_general")
               and "transpose(jvp(" not in i.op_name]
    return erfs(ffn2), sum(erfs(i) for i in by_comp[ffn2.comp])


def test_step_ffn_evaluates_gelu_once_in_the_forward(one_chip):
    under_ffn2, in_body = forward_erfs(_step_hlo("reading", one_chip))
    # ONE evaluation, in ffn1's fusion with the stack writes: a second
    # one here is the stack-writing kLoop of ISSUE 54's row three
    assert (under_ffn2, in_body) == (0, 1)


@pytest.mark.parametrize("form", ["flat", "whole"])
def test_step_ffn_through_plain_mul_evaluates_it_twice(form, one_chip):
    """PR 54's parent's lowering (the flat product, no barrier), and the
    proof that the harness sees the STEP's choice: the product's operand
    side holds the second GELU.  The product over the rows as they stand
    (plain ``mul`` since PR 60) does not cure that alone: the barrier is
    the reading form's own."""
    under_ffn2, in_body = forward_erfs(_step_hlo(form, one_chip))
    assert (under_ffn2, in_body) == (1, 2)


# -- the attention half as the training step lowers it ----------------------

N_HEAD = 12


def _step_attn(form):
    """``STEP_LAYERS`` attention halves as `cgpt590m.train_2k`'s scanned
    body runs them: a checkpointed LayerNorm, q, k, v (``mul`` and the
    bias add), the flash kernel the cell calls (a Mosaic call is what
    stands between the products there too), ``out`` (``mul`` and the
    bias add) and the residual, every op under the scope the executor
    gives it; ``form`` is ``_product``'s.  Returns the gradient
    function."""
    from paddle_tpu.ops import pallas_attention

    def project(name, x, w, b):
        with jax.named_scope(f"attn.proj/mul:block0_{name}"):
            y = _product(form, x, w)
        with jax.named_scope(f"attn.proj/elementwise_add:block0_{name}"):
            return y + b

    def norm(x):
        with jax.named_scope("norm/layer_norm:block0_ln1"):
            return _norm(x)

    def core(q, k, v):
        with jax.named_scope("attn.core/flash_attention_packed:attn_0"):
            return pallas_attention._pallas_flash_attention_packed(
                q, k, v, N_HEAD, causal=True, interpret=False)

    def body(x, layer):
        wq, bq, wk, bk, wv, bv, wo, bo = layer
        h = jax.checkpoint(norm)(x)
        ctx = core(project("att_q", h, wq, bq), project("att_k", h, wk, bk),
                   project("att_v", h, wv, bv))
        return x + project("att_out", ctx, wo, bo), None

    def loss(layers, x):
        return jax.lax.scan(body, x, layers)[0].astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1))


def _step_attn_hlo(form, one_chip):
    arg, n = _bf16_on(one_chip), STEP_LAYERS
    layers = (arg(n, D_MODEL, D_MODEL), arg(n, D_MODEL)) * 4  # q, k, v, out
    return jax.jit(_step_attn(form)).lower(
        layers, arg(*STEP_ROWS, D_MODEL)).compile().as_text()


_WIDE_ROWS = re.compile(r"^\w+\[(%d,%d|%d),%d\]" % (
    *STEP_ROWS, STEP_ROWS[0] * STEP_ROWS[1], D_MODEL))


def forward_projections(hlo):
    """What the forward scan body (the computation that holds
    ``flash_fwd``) does round the four projections, as counts of its
    instructions: (product fusions that hold their own bias add, product
    fusions that write a saved stack, wide fusions that hold a
    projection's bias add and no product: a pass of its own, fusions
    that write a saved stack and add a projection's bias on the way with
    no product: the producer evaluated again, wide ``copy``s)."""
    by_comp, reached = _by_computation(hlo)
    (body,) = {i.comp for instructions in by_comp.values()
               for i in instructions
               if i.opcode == "custom-call" and "flash_fwd" in i.op_name}

    counts = collections.Counter()
    for i in by_comp[body]:
        if i.opcode == "copy" and _WIDE_ROWS.match(i.shape):
            counts["copies"] += 1
        if i.opcode != "fusion":
            continue
        inside = list(reached(i))
        product = any(j.opcode == "convolution" and "attn.proj/mul:"
                      in j.op_name for j in inside)
        bias = any(j.opcode == "add" and "attn.proj/elementwise_add:"
                   in j.op_name for j in inside)
        stack = any(j.opcode == "dynamic-update-slice" for j in inside)
        counts["products_with_bias"] += product and bias
        counts["products_writing_a_stack"] += product and stack
        counts["bias_passes"] += bias and not product and not stack
        counts["stack_writes_adding_bias"] += bias and stack and not product
    return tuple(counts[k] for k in (
        "products_with_bias", "products_writing_a_stack", "bias_passes",
        "stack_writes_adding_bias", "copies"))


@pytest.mark.parametrize("form,want", [
    # q, k, v and out: ONE fusion each, the bias add in its epilogue;
    # q, k and v write their saved stacks from it (out's value is saved
    # by nobody here: the residual is the carry)
    ("whole", (4, 3, 0, 0, 0)),
    # the parent's: four bare products, the bias adds as passes of their
    # own (out's with the residual), and three stack writes that read
    # the bare product and ADD THE BIAS AGAIN
    ("flat", (0, 0, 4, 3, 0)),
])
def test_step_attention_projections_hold_their_bias_and_stack(form, want,
                                                              one_chip):
    assert forward_projections(_step_attn_hlo(form, one_chip)) == want
