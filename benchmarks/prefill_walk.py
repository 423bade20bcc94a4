"""One prefill piece of a serving cell's REAL stack alone on the chip, by
window width: one JSON line a (cell, width) with the host's milliseconds
a piece (pieces sent back to back, one ``block_until_ready`` at the
end), milliseconds a row, the device's seconds by sub-layer kind and its
largest operations (a profiler trace of the same pieces, joined to the
names the program gave its sub-layers), the seconds the executable took
to compile and its compiled HBM high-water (``memory_analysis``, what
the engine's ``serving.hbm_high_water_bytes`` gauge holds).

    chiprun -- python3 benchmarks/prefill_walk.py --cell brumby14b.doc_continue \
        [--widths 128,256,512,1024] [--context 2048] [--pieces 6] \
        [--spelling rule|dense|walk] [--out chiprun_out/prefill_walk.jsonl]

The engine is the cell's own (``chipbench``'s family module builds the
weights from ``--seed`` and the engine from the traffic file's
geometry): every layer, the head, the pool or the state at the cell's
size.  A piece of ``W`` real rows is sent for slot 0 at position
``--context``, so it attends a chain of that many cached positions (or
advances a state that old); the rows before it are whatever the pool
holds, which costs what real rows cost.  The width need not be a rung of
the engine's ladder: what a rung WOULD cost is the question.
``--spelling`` says how a wide window over a K/V plane attends: as
``kernels.paged_attention.attend``'s rule has it (the default), every one
``dense`` (the rule out of reach) or every one a ``walk`` of the chain
(the rule at zero); it is the module's constant that is set, before
anything compiles: the program has no such option.  One
process a cell (a second cell's weights do not fit beside the first's):
put the calls of several cells in one ``chiprun`` command.
Refuses unless JAX finds a TPU: a number from a CPU run is no device
metric.
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pieces(width, context, n, vocab, seed):
    """``n`` pieces of ``width`` real rows at position ``context``."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed + width)
    return [(width, jnp.asarray(rng.integers(0, vocab, width,
                                             dtype=np.int32)), context, width)
            for _ in range(n)]


def _send(eng, row, pieces):
    """The pieces through the engine's own dispatch; the donated arrays
    are the engine's again afterwards.  Returns the last first-token."""
    eng._pk, eng._pv, first = eng._run_pieces(
        eng._prefill_fn, eng._p, eng._pk, eng._pv, 0, row, pieces)
    return first


def _device(eng, row, pieces, top):
    """Seconds a piece by (kind) and the largest operations, from a trace
    of ``pieces``."""
    import jax

    from chipbench import trace_reduce
    from paddle_tpu.observability import trace

    with tempfile.TemporaryDirectory(prefix="prefill_walk") as td:
        with jax.profiler.trace(td):
            jax.block_until_ready(_send(eng, row, pieces))
        path = trace_reduce.find_xplane(td)
        summary = trace_reduce.reduce(trace_reduce.load(path), top=top)
        joined = trace.device_seconds_by_scope(path)
    n = len(pieces)
    kinds = {}
    for (_module, kind, _phase), s in joined["seconds"].items():
        kinds[kind or "outside"] = kinds.get(kind or "outside", 0.0) + s / n
    return {"busy_ms": 1e3 * summary["busy_s"] / n,
            "kind_ms": {k: round(1e3 * s, 4)
                        for k, s in sorted(kinds.items(),
                                           key=lambda kv: -kv[1])},
            "ops_ms": [[name, round(1e3 * s / n, 4)]
                       for name, s in summary["device_ops"]]}


def measure(eng, cell, width, args, vocab):
    import jax
    import jax.numpy as jnp
    import numpy as np

    row = np.zeros(eng.blocks_per_slot, np.int32)
    if eng.kv_pool is not None:
        # slot 0's chain: the pool's first blocks (nothing else is live)
        row[:] = 1 + np.arange(eng.blocks_per_slot)
    row = jnp.asarray(row)
    label = f"prefill_{width}"
    jax.block_until_ready(_send(eng, row, _pieces(
        width, args.context, 2, vocab, args.seed)))  # compile, warm
    pieces = _pieces(width, args.context, args.pieces, vocab, args.seed)
    t0 = time.perf_counter()
    jax.block_until_ready(_send(eng, row, pieces))
    ms = 1e3 * (time.perf_counter() - t0) / args.pieces
    stats = eng.stats()
    line = {"cell": cell, "width": width, "context": args.context,
            "spelling": args.spelling,
            "pieces": args.pieces, "ms_a_piece": round(ms, 4),
            "ms_a_row": round(ms / width, 6),
            "compile_s": round(eng.compile_seconds[label], 2),
            "hbm_high_water_bytes": int(stats.get(
                f"serving.hbm_high_water_bytes{{label={label}}}", 0)),
            "temp_bytes": int(stats.get(
                f"serving.temp_bytes{{label={label}}}", 0))}
    line.update(_device(eng, row, pieces, args.top))
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True,
                    help="a serving cell of BENCHMARK.json")
    ap.add_argument("--widths", default="128,256,512,1024")
    ap.add_argument("--context", type=int, default=2048)
    ap.add_argument("--pieces", type=int, default=6)
    ap.add_argument("--seed", type=int, default=43)
    ap.add_argument("--top", type=int, default=8)
    ap.add_argument("--spelling", default="rule",
                    choices=("rule", "dense", "walk"),
                    help="how a wide window over a K/V plane attends")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from chipbench import device, families
    from chipbench import run as bench_run
    from paddle_tpu.kernels import paged_attention
    from paddle_tpu.observability.metrics import MetricsRegistry

    if args.spelling != "rule":
        # read at trace time, like every constant of that module
        paged_attention.CHAIN_SCORE_BYTES = (
            0 if args.spelling == "walk" else float("inf"))
    if jax.default_backend() != "tpu":
        raise SystemExit("prefill_walk: no TPU here; a number from a CPU "
                         "run is no device metric")
    cell = bench_run.load_cell(args.cell)
    cfg, mix = cell["config"], cell["traffic"]
    geometry = dict(mix["engine"])
    family = families.of(cfg, "serve")
    params = family.make_params(cfg, geometry["max_len"], args.seed)
    eng = family.serving_engine(params, cfg, MetricsRegistry(), geometry)
    del params
    out = open(args.out, "a") if args.out else None
    for width in (int(w) for w in args.widths.split(",")):
        if args.context + width > eng.max_len:
            raise SystemExit(f"prefill_walk: {args.context} + {width} rows "
                             f"pass the cell's max_len {eng.max_len}")
        line = measure(eng, args.cell, width, args, cfg["vocab_size"])
        line["device"] = device.describe(
            jax.devices(), device.memory_peak(jax.devices()))
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    if out:
        out.close()


if __name__ == "__main__":
    main()
