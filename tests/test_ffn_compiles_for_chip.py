"""One FFN layer of ``cgpt590m.train_2k`` compiled for a TPU v5e that is
described and not attached, through the ``gelu`` op: how many wide
elementwise instructions the chip's compiler leaves in the fusions round
the two products and in the checkpointed backward.  The VPU has no
16-bit arithmetic, so every one of them is a float32 pass over
``[4096, 6144]``; ``jax.nn.gelu(approximate=False)`` arrives there as
both branches of ``erfc`` (74 a forward evaluation, 86 in the backward:
ISSUE 41), the op's float32 ``erf`` form as 12 and 19.  Nothing runs: a
compile that passes is no chip run.  One file, the topology inside a
fixture (one process may hold the TPU's library)."""

import collections
import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROWS, D_MODEL, D_FF = 4096, 1536, 6144


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


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
