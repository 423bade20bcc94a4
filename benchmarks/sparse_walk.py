"""The learned sparse attention of ``dots3np.doc_qa_32k``'s full planes
alone on the chip, and its sliding planes' windowed latent attention
beside it, one JSON line a geometry: device microseconds a call (the busy
union of the call's operations in a profiler trace) and of them those in
Mosaic calls, the call's share
of its roofline (``chipbench/dsa_bytes.py``: the STORED bytes it cannot
avoid against its operations), and what the same rows cost the DENSE
latent call over the whole chain, which the selection replaces.

    chiprun -- python3 benchmarks/sparse_walk.py \\
        [--only decode_10x33k,piece_512] [--live 2,4,6] [--calls 10] \\
        [--out chiprun_out/sparse_walk.jsonl]

The geometry is the cell's: ONE full plane (latent rows of 640 stored
lanes and index keys of 128 lanes, 15,249 blocks of 32) and one sliding
plane (1,152 lanes), chains of 1,064 entries; a decode step of 1, 2, 4,
5, 6, 8 and 10 slots at 33,000 positions (the call's line in its TABLE's
slots), prefill pieces of 32, 128 and 512 rows that end at 33,000, the
three steps of the sparse call each alone, and the sliding plane's decode
step and 512-row piece.  ``--live n[,n...]`` adds, for each ``n``, the
ten-slot decode step with ``n`` slots live (the others as the engine
leaves a released slot: a table row of zeros, ``pos`` -1; the live ones
spread over the table, not packed): the call's line in its LIVE slots,
which is the table's line before PR 56 and ``slots_run(n, 10)``'s after.
This walk is what decides whether the gather stays XLA's (PERF.md
section 6, PR 55) and which slot counts the call keeps (PR 56).
Refuses unless JAX finds a TPU: a number from a CPU run is no device
metric.
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NB, B, TOPK, CONTEXT = 1064, 32, 2048, 33000
BLOCKS = 1 + 10 * NB + 4608
# name -> (what, slots, rows)
GEOMETRIES = {
    "decode_1x33k": ("sparse", 1, 1), "decode_2x33k": ("sparse", 2, 1),
    "decode_4x33k": ("sparse", 4, 1), "decode_5x33k": ("sparse", 5, 1),
    "decode_6x33k": ("sparse", 6, 1),
    "decode_8x33k": ("sparse", 8, 1), "decode_10x33k": ("sparse", 10, 1),
    "piece_32": ("sparse", 1, 32), "piece_128": ("sparse", 1, 128),
    "piece_512": ("sparse", 1, 512),
    "indexer_alone_10x33k": ("scores", 10, 1),
    "select_alone_10x33k": ("select", 10, 1),
    "gathered_attention_alone_10x33k": ("attention", 10, 1),
    "indexer_alone_piece_512": ("scores", 1, 512),
    "select_alone_piece_512": ("select", 1, 512),
    "gathered_attention_alone_piece_512": ("attention", 1, 512),
    "dense_latent_10x33k": ("dense", 10, 1),
    "window_decode_10": ("window", 10, 1),
    "window_piece_512": ("window", 1, 512),
}


def _seconds(trace_dir):
    """(busy union of every operation, seconds of the Mosaic calls) of
    the newest trace under ``trace_dir``."""
    from chipbench import trace_reduce

    spans, mosaic = [], 0
    for events in trace_reduce.chip_ops(trace_reduce.load(trace_dir)).values():
        for start, end, name, hlo in events:
            spans.append((start, end))
            if "tpu_custom_call" in hlo:
                mosaic += end - start
    busy, at = 0, 0
    for start, end in sorted(spans):
        busy += max(0, end - max(start, at))
        at = max(at, end)
    return busy * 1e-9, mosaic * 1e-9


def _device_us(fn, args, calls):
    import jax

    jax.block_until_ready(fn(*args))  # compile, warm
    with tempfile.TemporaryDirectory(prefix="sparse_walk") as td:
        with jax.profiler.trace(td):
            out = None
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        busy, mosaic = _seconds(td)
    return 1e6 * busy / calls, 1e6 * mosaic / calls


def _config():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "dots3-note-prev.json")) as f:
        return json.load(f)


def measure(name, calls, seed, peak, live=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import dsa_bytes
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.kernels import sparse_attention as sp

    what, S, W = GEOMETRIES[name]
    cfg = _config()
    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    table = jnp.asarray(np.stack([
        rng.permutation(BLOCKS - 1)[:NB] + 1 for _ in range(S)]), jnp.int32)
    pos = jnp.asarray(
        CONTEXT - W + np.arange(W)[None] - 7 * np.arange(S)[:, None],
        jnp.int32)
    if live is not None:
        # the other slots as a released slot's rows are: zeros, pos -1
        dead = np.ones(S, bool)
        dead[np.sort(rng.permutation(S)[:live])] = False
        table = jnp.where(dead[:, None], 0, table)
        pos = jnp.where(dead[:, None], -1, pos)
        name = f"{name}_live{live}"
    contexts = [int(n) for n in (np.asarray(pos) + 1).reshape(-1) if n > 0]
    if what == "window":
        pool = jax.random.normal(k1, (BLOCKS, B, 1152), bf)
        q = jax.random.normal(k2, (S, W, 64, 1152), bf) * 0.05
        fn = jax.jit(lambda q, pool, t, p: pa.attend(
            q, pool, None, t, p, value_lanes=1024, scale=256 ** -0.5,
            window=513))
        args = (q, pool, table, pos)
        ops, nbytes = dsa_bytes.window_call(cfg, contexts)
    else:
        pool = jax.random.normal(k1, (BLOCKS, B, 640), bf)
        idx = jax.random.normal(k3, (BLOCKS, B, 128), bf)
        q = jax.random.normal(k2, (S, W, 128, 640), bf) * 0.05
        qi = jax.random.normal(k4, (S, W, 64, 128), bf)
        wi = jax.random.normal(k5, (S, W, 64), jnp.float32) * 0.01
        how = dict(value_lanes=512, scale=192 ** -0.5)
        if what == "sparse":
            fn = jax.jit(lambda *a: sp.sparse_attend(*a, topk=TOPK, **how))
            args = (q, pool, idx, table, pos, qi, wi)
            o1, b1 = dsa_bytes.index_call(cfg, contexts)
            o2, b2 = dsa_bytes.sparse_call(cfg, contexts)
            ops, nbytes = o1 + o2, b1 + b2
        elif what == "scores":
            fn = jax.jit(sp.index_scores)
            args = (qi, wi, idx, table, pos)
            ops, nbytes = dsa_bytes.index_call(cfg, contexts)
        elif what == "select":
            scores = jax.jit(sp.index_scores)(qi, wi, idx, table, pos)
            fn = jax.jit(lambda s: sp.select_positions(s, TOPK))
            args = (scores,)
            ops, nbytes = 0, int(scores.size) * 4 + S * W * TOPK * 4
        elif what == "attention":
            sel = jax.jit(lambda *a: sp.select_positions(
                sp.index_scores(*a), TOPK))(qi, wi, idx, table, pos)
            fn = jax.jit(lambda q, pool, t, p, s: sp.sparse_latent_attention(
                q, pool, t, p, s, **how))
            args = (q, pool, table, pos, sel)
            ops, nbytes = dsa_bytes.sparse_call(cfg, contexts)
        else:
            fn = jax.jit(lambda q, pool, t, p: pa.attend(
                q, pool, None, t, p, **how))
            args = (q, pool, table, pos)
            ops = 2 * 128 * (640 + 512) * sum(contexts)
            nbytes = 640 * 2 * sum(contexts)
    busy_us, mosaic_us = _device_us(fn, args, calls)
    least = max(nbytes / peak["hbm_bytes_per_s"],
                ops / peak["bf16_flops_per_s"])
    return {"geometry": name, "what": what, "slots": S,
            "live": S if live is None else live, "rows": W,
            "context": CONTEXT, "us_a_call": busy_us,
            "mosaic_us_a_call": mosaic_us, "ops": int(ops),
            "bytes": int(nbytes),
            "least_us": 1e6 * least,
            "roofline_pct": 100e6 * least / busy_us,
            "bound": ("hbm" if nbytes / peak["hbm_bytes_per_s"]
                      >= ops / peak["bf16_flops_per_s"] else "mxu")}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default="")
    ap.add_argument("--live", default="",
                    help="live slot counts of the ten-slot decode step, "
                         "each a line more (decode_10x33k_live<n>)")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=55)
    ap.add_argument("--out", default="chiprun_out/sparse_walk.jsonl")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"sparse_walk: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    from chipbench import flops

    peak = flops.peaks(jax.devices()[0].device_kind)
    lives = [int(n) for n in args.live.split(",") if n]
    names = [n for n in args.only.split(",") if n] or (
        [] if lives else list(GEOMETRIES))
    runs = [(n, None) for n in names] + [("decode_10x33k", n) for n in lives]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as out:
        for name, live in runs:
            line = json.dumps(measure(name, args.calls, args.seed, peak, live))
            print(line, flush=True)
            out.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
