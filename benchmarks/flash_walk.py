"""The flash kernels alone on the chip, one JSON line a geometry: device
microseconds a call of the forward and of the backward (summed over the
Mosaic calls of a profiler trace, so the XLA reduce of the dq partials is
not in it) and each one's share of its roofline (``chipbench/flops.py``:
operations and bytes from the shapes, the chip's peaks from its table).

    chiprun -- python3 benchmarks/flash_walk.py [--only cell,noncausal] \
        [--calls 20] [--out chiprun_out/flash_walk.jsonl]

The first geometry is ``cgpt590m.train_2k``'s call (b 2, t 2048, h 12,
d 128, packed bf16, causal, blocks of 1024); the others move one thing
at a time (non-causal: every cell full; smaller blocks; another strip
height through ``apply_tuned_diag_w``) or are the other callers' layouts
(paired d 64, t 4096, the 4-D ``[b*h, t, d]`` layout).  Refuses unless
JAX finds a TPU: a number from a CPU run is no device metric.
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name -> what differs from the cell's call
CELL = dict(b=2, t=2048, h=12, d=128, layout="packed", causal=True,
            block_q=1024, block_k=1024, diag_w=None)
GEOMETRIES = {
    "cell": {},
    "noncausal": {"causal": False},
    "blocks_512": {"block_q": 512, "block_k": 512},
    "block_q_512": {"block_q": 512},
    "diag_w_128": {"diag_w": 128},
    "diag_w_512": {"diag_w": 512},
    "diag_w_1024": {"diag_w": 1024},
    "paired_d64": {"h": 24, "d": 64},
    "paired_d64_noncausal": {"h": 24, "d": 64, "causal": False},
    "t4096": {"b": 1, "t": 4096},
    "layout_4d": {"layout": "4d"},
}


def _mosaic_seconds(trace_dir):
    """(calls, seconds) of the Mosaic custom calls on the chip's
    operations line of the newest trace under ``trace_dir``."""
    from chipbench import trace_reduce

    calls, ns = 0, 0
    for events in trace_reduce.chip_ops(trace_reduce.load(trace_dir)).values():
        for start, end, _, hlo in events:
            if "tpu_custom_call" in hlo:
                calls += 1
                ns += end - start
    return calls, ns * 1e-9


def _timed(fn, args, calls):
    import jax

    jax.block_until_ready(fn(*args))  # compile, warm
    with tempfile.TemporaryDirectory(prefix="flash_walk") as td:
        with jax.profiler.trace(td):
            out = None
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        n, seconds = _mosaic_seconds(td)
    if n != calls:
        raise RuntimeError(f"{n} Mosaic calls in the trace, {calls} made")
    return 1e6 * seconds / n


def measure(name, calls, peak):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import flops
    from paddle_tpu.ops import pallas_attention as pa

    g = dict(CELL, **GEOMETRIES[name])
    b, t, h, d = g["b"], g["t"], g["h"], g["d"]
    was = pa.DIAG_W
    if g["diag_w"]:
        pa.apply_tuned_diag_w(g["diag_w"])
    kw = dict(causal=g["causal"], block_q=g["block_q"], block_k=g["block_k"],
              interpret=False)
    if g["layout"] == "packed":
        shape = (b, t, h * d)
        attend = lambda q, k, v: pa._pallas_flash_attention_packed(
            q, k, v, h, **kw)
    else:
        shape = (b, t, h, d)
        attend = lambda q, k, v: pa._pallas_flash_attention(q, k, v, **kw)
    rng = np.random.default_rng(31)
    q, k, v, do = (jnp.asarray(rng.normal(size=shape) * 0.5, jnp.bfloat16)
                   for _ in range(4))
    try:
        fwd_us = _timed(jax.jit(attend), (q, k, v), calls)
        _, vjp = jax.vjp(attend, q, k, v)
        bwd_us = _timed(jax.jit(lambda f, ct: f(ct)), (vjp, do), calls)
    finally:
        pa.DIAG_W = was
    # flops.py counts the causal triangle; every pair of the square is
    # t * t against t * (t + 1) / 2 of them
    square = 1.0 if g["causal"] else 2.0 * t / (t + 1)
    line = {"geometry": name, **g, "strip": pa._pick_block(
        pa._pick_block(t, g["block_q"]), g["diag_w"] or was),
        "fwd_us": fwd_us, "bwd_us": bwd_us}
    if g["causal"]:  # what the walk schedules, from the kernels' own logic
        walk = pa.causal_flash_flops(t, t, d, g["block_q"], g["block_k"],
                                     diag_w=line["strip"])
        line.update(scheduled_over_useful=walk[0] / walk[1],
                    updates_per_row=walk.updates_per_row,
                    branches_per_cell=walk.branches_per_cell)
    for label, count, us in (("fwd", flops.flash_fwd, fwd_us),
                             ("bwd", flops.flash_bwd, bwd_us)):
        ops, nbytes = count(b, h, d, t)
        least, bound = flops.roofline_seconds(ops * square, nbytes, peak)
        line[f"{label}_roofline_pct"] = 100.0 * least * 1e6 / us
        line[f"{label}_tflops"] = ops * square / us * 1e-6
        line["bound"] = bound
    return line


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default="",
                    help="comma-separated geometry names (default: all)")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/flash_walk.jsonl")
    args = ap.parse_args()

    import jax

    from chipbench import flops

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"flash_walk times the chip; JAX found {dev.platform}")
    peak = flops.peaks(dev.device_kind)
    names = [n for n in args.only.split(",") if n] or list(GEOMETRIES)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as f:
        for name in names:
            try:
                line = measure(name, args.calls, peak)
            except Exception as e:  # noqa: BLE001 - a geometry Mosaic refuses
                line = {"geometry": name, "error": repr(e)[:400]}
            line["device"] = dev.device_kind
            text = json.dumps(line)
            print(text, flush=True)
            f.write(text + "\n")


if __name__ == "__main__":
    main()
