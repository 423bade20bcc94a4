"""The grouped matrix product alone on the chip, one JSON line a geometry:
device microseconds a call (the Mosaic call of a profiler trace, as
``flash_walk`` takes them), microseconds a touched group, the call's
share of its roofline (``chipbench/moe_bytes.py``: the larger of reading
the touched groups' matrices and of multiplying the rows) and the worst
error against the ``xla_ref`` scan on the chip.

    chiprun -- python3 benchmarks/grouped_walk.py [--only decode_25_rows,piece_64_rows] \
        [--calls 20] [--block-n 512] [--out chiprun_out/grouped_walk.jsonl]

The geometries are the calls of ``trinitylp.chat_moe`` (32 experts of
3072 x 3072 held): a decode step's buffer of 96 x 4 rows with what 20 and
50 live slots route here, with no row at all and with every row; a
prefill piece's 128 x 4 with its 64 pairs and with every pair; the
narrowest piece.  ``--shape 16,2048,1408`` (groups, k, n) times the
``doc_*`` geometries instead, the calls of ``dsv2lite.doc_qa_8k`` (16
experts of 2048 x 1408 held, top 6: a decode step's 12 x 6 rows with
what 10 live slots route here, a prefill piece's 128 x 6): gate and up
at ``16,2048,1408``, whose 1408 only 128-wide panels divide, the down
product at ``16,1408,2048``.  Which groups get the rows is drawn from
``--seed``.
Refuses unless JAX finds a TPU: a number from a CPU run is no device
metric.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flash_walk import _timed  # noqa: E402 - device seconds of the Mosaic calls

GROUPS, K, N = 32, 3072, 3072
CONFIG = "trinity-large-preview"
# name -> rows of the buffer, rows that belong to a group, groups touched
GEOMETRIES = {
    "decode_10_rows": (384, 10, 9),
    "decode_25_rows": (384, 25, 17),
    "decode_48_rows": (384, 48, 25),
    "decode_no_row": (384, 0, 0),
    "decode_every_row": (384, 384, 32),
    "piece_64_rows": (512, 64, 28),
    "piece_every_row": (512, 512, 32),
    "narrow_piece_4_rows": (32, 4, 3),
}
# the same for 16 groups (--shape): 10 live slots send 1.5 rows a layer
DOC_GEOMETRIES = {
    "doc_decode_15_rows": (72, 15, 9),
    "doc_decode_every_row": (72, 72, 16),
    "doc_piece_192_rows": (768, 192, 16),
    "doc_piece_every_row": (768, 768, 16),
}


def measure(name, calls, block_n, seed, peak, rhs, cfg):
    """``cfg`` is the configuration whose sizes count the roofline, or
    ``None`` for a ``--shape``: then one matrix's bytes and operations
    are counted from ``rhs`` itself."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import moe_bytes
    from paddle_tpu.kernels.grouped_matmul import (grouped_matmul_pallas,
                                                   grouped_matmul_ref)

    m, real, touched = {**GEOMETRIES, **DOC_GEOMETRIES}[name]
    groups, k, n = rhs.shape
    rng = np.random.default_rng(seed)
    sizes = np.zeros(groups, np.int32)
    if touched:
        held = rng.choice(groups, touched, replace=False)
        sizes[held] = 1
        np.add.at(sizes, rng.choice(held, real - touched), 1)
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    gs = jnp.asarray(sizes)
    fn = jax.jit(lambda l, r, s: grouped_matmul_pallas(l, r, s,
                                                       block_n=block_n))
    us = _timed(fn, (lhs, rhs, gs), calls)
    err = float(jnp.max(jnp.abs(
        fn(lhs, rhs, gs).astype(jnp.float32)
        - grouped_matmul_ref(lhs, rhs, gs).astype(jnp.float32))))
    # one matrix of an expert's three: a third of a layer's least time
    if cfg is None:
        least = max(touched * k * n * 2 / peak["hbm_bytes_per_s"],
                    2 * k * n * real / peak["bf16_flops_per_s"])
    else:
        least = moe_bytes.expert_call_seconds(cfg, touched, real, peak) / 3
    return {"geometry": name, "rows": m, "rows_in_a_group": real,
            "groups_touched": touched, "k": k, "n": n, "block_n": block_n,
            "us_a_call": us,
            "us_a_touched_group": us / touched if touched else None,
            "roofline_pct": 100e6 * least / us, "worst_error": err}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default="")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--block-n", type=int, default=None)
    ap.add_argument("--shape", default="",
                    help="groups,k,n of the matrices (default: chat_moe's)")
    ap.add_argument("--seed", type=int, default=34)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print(f"grouped_walk: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    from chipbench import flops
    from chipbench import run as bench_run

    peak = flops.peaks(jax.devices()[0].device_kind)
    if args.shape:
        shape, cfg = tuple(int(v) for v in args.shape.split(",")), None
        geometries = DOC_GEOMETRIES
    else:
        shape, geometries = (GROUPS, K, N), GEOMETRIES
        cfg = bench_run._read_json(bench_run.HERE, "configs",
                                   CONFIG + ".json")
    rhs = jnp.asarray(np.random.default_rng(args.seed).normal(
        size=shape) * 0.02, jnp.bfloat16)
    names = [n for n in args.only.split(",") if n] or list(geometries)
    lines = []
    for name in names:
        lines.append(json.dumps(measure(name, args.calls, args.block_n,
                                        args.seed, peak, rhs, cfg)))
        print(lines[-1], flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
