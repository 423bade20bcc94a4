"""The FFN of ``cgpt590m.train_2k`` alone on the chip, one JSON line a
way of evaluating its GELU: device microseconds a layer-micro-batch of
the forward, and of the forward with its backward under the checkpoint
the ``selective`` policy wraps round the bias add and the activation
(the busy seconds of a profiler trace over the calls, and the same by
instruction: the two products' fusions and the GELU's backward are the
three the training step's ``breakdown`` names).  Three layers under one
``lax.scan``, because the scan decides what the compiler fuses where.

    chiprun -- python3 benchmarks/ffn_walk.py [--only present,op] \
        [--step-shapes] [--calls 20] [--out chiprun_out/ffn_walk.jsonl]

A layer-micro-batch is ``[4096, 1536] x [1536, 6144]``, the GELU on
``[4096, 6144]``, ``x [6144, 1536]``, bfloat16: 77.3 GFLOP a product,
0.392 ms at the chip's peak.  ``present`` is the expression the ``gelu``
op had until PR 41 (``jax.nn.gelu(approximate=False)``), ``op`` what the
registry's op does now, ``rational`` the op's formula with ``erf``
written out as the clamped rational polynomial XLA uses for float32
(what one ``erf`` instruction costs the chip against 25 plain
operations), ``op_once`` the op with its output behind an optimization
barrier (no second evaluation inside the second product: what a plain
barrier gets), ``op_read`` the op with the two products as the
scan-remat engine lowers them since PR 54 (``mul(_reads_saved=True)``:
the operand behind a barrier in the forward alone, taken over the rows
as they stand).  ``--step-shapes`` is the training step's geometry: rows
``[2, 2048, .]`` through ``mul`` and the scan inside a two-iteration
outer loop, where the compiler makes the step's choices and not the
plain walk's (with flat ``[4096, .]`` rows ``op_read`` and a barrier on
the flat operand are the same program).  Since PR 60 ``mul`` takes such
rows as they stand in EVERY way, so the other ways' step-shape lines of
``RESULTS.md`` (PR 54: through the flattening) are the parent's.  A last line holds the op's error on the chip over every
finite bfloat16 value against the float64 function.  Refuses unless JAX
finds a TPU: a number from a CPU run is no device metric.
"""

import argparse
import json
import math
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS, D_MODEL, D_FF = 4096, 1536, 6144
LAYERS = 3  # of one scan: the times are a layer's
OUTER = 2  # micro-batches of --step-shapes' outer loop
TOP = 9  # instructions a line names

# XLA's float32 erf (after Eigen): x P(x^2) / Q(x^2), x clamped to where
# the quotient rounds to +-1
_ERF_CLAMP = 3.7439211627767994
_ERF_P = (0.00022905065861350646, 0.0034082910107109506,
          0.050955695062380861, 0.18520832239976145, 1.128379143519084)
_ERF_Q = (-1.1791602954361697e-7, 0.000023547966471313185,
          0.0010179625278914885, 0.014070470171167667,
          0.11098505178285362, 0.49746925110067538, 1.0)


def _rational_gelu(x):
    """The op's formula and derivative (``ops/activation_ops.py``) with
    the polynomial above where the op calls ``lax.erf``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import activation_ops as op

    def poly(z, coefficients):
        acc = jnp.float32(coefficients[0])
        for c in coefficients[1:]:
            acc = acc * z + jnp.float32(c)
        return acc

    def cdf(xf):
        z = jnp.clip(xf * jnp.float32(op._SQRT_HALF), -_ERF_CLAMP, _ERF_CLAMP)
        z2 = z * z
        erf = z * poly(z2, _ERF_P) / poly(z2, _ERF_Q)
        return jnp.where(xf < op._GELU_ZERO_BELOW, 0.0, 0.5 * (1.0 + erf))

    @jax.custom_vjp
    def gelu(x):
        xf = x.astype(jnp.float32)
        return (xf * cdf(xf)).astype(x.dtype)

    def bwd(x, g):
        xf = x.astype(jnp.float32)
        pdf = jnp.exp(-0.5 * xf * xf) * jnp.float32(op._INV_SQRT_2PI)
        return ((g.astype(jnp.float32) * (cdf(xf) + xf * pdf)).astype(x.dtype),)

    gelu.defvjp(lambda x: (gelu(x), x), bwd)
    return gelu(x)


def ways():
    """{way: (activation, whether the products read their operand)}."""
    import jax

    from paddle_tpu.ops import activation_ops

    op = lambda h: activation_ops.gelu(h)["Out"]
    return {
        "present": (lambda h: jax.nn.gelu(h, approximate=False), False),
        "op": (op, False),
        "rational": (_rational_gelu, False),
        # the op, its output behind a barrier: the second product has to
        # READ the activations the first one's epilogue wrote, where the
        # compiler otherwise evaluates the GELU again on its operand side
        "op_once": (lambda h: jax.lax.optimization_barrier(op(h)), False),
        # what the scan-remat engine does since PR 54
        "op_read": (op, True),
    }


def stack(act, reading=False):
    """``LAYERS`` FFN layers under one ``lax.scan``, as the scan-remat
    engine runs a uniform Program: ``mul``, then bias add and activation
    in one checkpointed segment, then ``mul``, bias and the residual.
    The scan is what makes the compiler choose as it does in the
    training step: the saved activations are written into stacks, and
    the second product evaluates the GELU again from the first one's
    output rather than read a slice of the stack back."""
    import jax

    from paddle_tpu.ops.math_ops import mul

    def product(x, w):
        return mul(x, w, x_num_col_dims=x.ndim - 1,
                   _reads_saved=reading)["Out"]

    def body(x, layer):
        w1, b1, w2, b2 = layer
        h = product(x, w1)
        a = jax.checkpoint(lambda h, b1: act(h + b1))(h, b1)
        return x + (product(a, w2) + b2), None

    return lambda x, layers: jax.lax.scan(body, x, layers)[0]


def _timed(fn, args, calls, layers=LAYERS):
    """(busy microseconds a layer, {instruction: microseconds a layer})
    over ``calls`` calls of ``fn``, each ``layers`` layer-micro-batches,
    from the device's own clock."""
    import jax

    from chipbench import trace_reduce

    jax.block_until_ready(fn(*args))  # compile, warm
    with tempfile.TemporaryDirectory(prefix="ffn_walk") as td:
        with jax.profiler.trace(td):
            out = None
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        chips = trace_reduce.chip_ops(trace_reduce.load(td))
    events = max(chips.values(), key=len)
    busy = sum(end - start for start, end in trace_reduce.busy_union(events))
    by_name = {}
    for start, end, name, _ in events:
        if " while " not in name:  # a loop holds what is counted below
            by_name[name] = by_name.get(name, 0) + end - start
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    a_layer = 1e-3 / (calls * layers)
    return busy * a_layer, {name: ns * a_layer for name, ns in top}


def measure(name, way, calls, step_shapes=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(41)

    def bf16(shape, scale):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.bfloat16)

    # the cost of an elementwise pass does not depend on the values;
    # these keep the pre-activations of order one through the layers
    layers = (bf16((LAYERS, D_MODEL, D_FF), 0.03), bf16((LAYERS, D_FF), 0.1),
              bf16((LAYERS, D_FF, D_MODEL), 0.002), bf16((LAYERS, D_MODEL), 0.1))
    rows = (OUTER, 2, ROWS // 2) if step_shapes else (ROWS,)
    x, dy = bf16(rows + (D_MODEL,), 1.0), bf16(rows + (D_MODEL,), 1.0)
    ffn = stack(*way)

    def both(x, layers, ct):
        y, vjp = jax.vjp(ffn, x, layers)
        return y, vjp(ct)

    def looped(fn):
        # a micro-batch's forward and backward inside the loop, as the
        # accumulation loop has them
        return lambda x, layers, *ct: jax.lax.map(
            lambda rows: fn(rows[0], layers, *rows[1:]), (x, *ct))

    n = LAYERS
    if step_shapes:
        ffn, both, n = looped(ffn), looped(both), LAYERS * OUTER
    fwd_us, fwd_ops = _timed(jax.jit(ffn), (x, layers), calls, n)
    both_us, both_ops = _timed(jax.jit(both), (x, layers, dy), calls, n)
    return {"variant": name, "layers": LAYERS, "step_shapes": step_shapes,
            "fwd_us": fwd_us, "fwd_bwd_us": both_us, "fwd_ops_us": fwd_ops,
            "fwd_bwd_ops_us": both_ops}


def accuracy():
    """The op on this device over every finite bfloat16 value, forward
    and gradient, against the float64 function."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import activation_ops

    values = jnp.asarray(np.arange(65536, dtype=np.uint16)).view(jnp.bfloat16)
    x = np.asarray(values.astype(jnp.float32)).astype(np.float64)
    finite = np.isfinite(x)
    values, x = values[finite], x[finite]
    op = lambda v: activation_ops.gelu(v)["Out"]
    y = np.asarray(jax.jit(op)(values).astype(jnp.float32), np.float64)
    g = np.asarray(jax.jit(jax.grad(lambda v: op(v).astype(
        jnp.float32).sum()))(values).astype(jnp.float32), np.float64)
    cdf = 0.5 * np.vectorize(math.erfc)(-x * math.sqrt(0.5))
    want = x * cdf
    slope = cdf + x * np.exp(-0.5 * x * x) / math.sqrt(2 * math.pi)
    near, tail, far = np.abs(x) <= 4, x < -4, x < -40
    # relative error where the result is a normal number: below that a
    # 16-bit format has no digits to be right in
    normal = near & (np.abs(want) >= float(jnp.finfo(jnp.bfloat16).tiny))
    scaled = np.abs(y - want)[normal] / np.abs(want[normal])
    return {"variant": "op_accuracy_bfloat16",
            "worst_relative_within_4": float(scaled.max()),
            "worst_absolute_within_4": float(np.abs(y - want)[near].max()),
            "worst_gradient_absolute_within_4":
                float(np.abs(g - slope)[near].max()),
            "worst_absolute_below_minus_4": float(np.abs(y - want)[tail].max()),
            "worst_gradient_absolute_below_minus_4":
                float(np.abs(g - slope)[tail].max()),
            "nonzero_below_minus_40":
                int(np.count_nonzero(y[far]) + np.count_nonzero(g[far])),
            "nonfinite": int((~np.isfinite(y)).sum() + (~np.isfinite(g)).sum())}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default="",
                    help="comma-separated variants (default: all)")
    ap.add_argument("--step-shapes", action="store_true",
                    help="rows [2, 2048, .] and an outer loop over "
                         "micro-batches, as the training step has them")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/ffn_walk.jsonl")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"ffn_walk times the chip; JAX found {dev.platform}")
    acts = ways()
    names = [n for n in args.only.split(",") if n] or list(acts)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as f:
        lines = [measure(name, acts[name], args.calls, args.step_shapes)
                 for name in names]
        for line in lines + [accuracy()]:
            line["device"] = dev.device_kind
            text = json.dumps(line)
            print(text, flush=True)
            f.write(text + "\n")


if __name__ == "__main__":
    main()
