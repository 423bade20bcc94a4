"""One serving cell at several offered rates, to find the rate it
sustains (the knee a cell's ``rate_per_s`` is seated under):

    chiprun -- python3 benchmarks/serve_sweep.py --workload <cell> \\
        --rates 2,3,4,5 [--seed 1] [--seconds 51]

Each rate is one run of ``chipbench/runners/serve.py`` in a process of
its own (a chip belongs to one process; this parent never touches JAX)
with the cell's traffic file as it is but for ``rate_per_s``; the
processes share the program's compile cache, so only the first compiles.
One JSON line a rate: the end-to-end metrics, what the check compared,
``drain_s``, the peak of live slots, the queue's wait and the step, from
the run's own facts.  A rate is sustained where the queue does not grow:
``ttft_p90_ms`` stays near a prefill piece, ``drain_s`` near the longest
output, and the live slots under ``max_slots``.
"""

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def one(workload, rate, seed, seconds):
    sys.path.insert(0, ROOT)
    import time

    t0 = time.perf_counter()
    from chipbench import run as bench_run
    from chipbench.runners import serve

    cell = bench_run.load_cell(workload)
    cell["traffic"]["rate_per_s"] = rate
    import paddle_tpu  # noqa: F401  (points JAX at the compile cache)

    result = serve.run(cell, seed, seconds, None)
    facts, stats = result["facts"], result["facts"]["stats"]
    pick = lambda name: (stats.get(name) or {})                 # noqa: E731
    print(json.dumps({
        "rate_per_s": rate, "seed": seed, "correct": result["correct"],
        "attempted": result["attempted"], "failed": result["failed"],
        **result["end_to_end"],
        "setup_s": result["window_start"] - t0,
        "compared": result["compared"], "drain_s": facts["drain_s"],
        "slots_active_max": facts["slots_active_max"],
        "slots_active_mean": facts["slots_active_mean"],
        "max_slots": facts["max_slots"],
        "step_p50_ms": 1e3 * (pick("serving.step_seconds").get("p50") or 0),
        "queue_wait_p50_ms": 1e3 * (
            pick("serving.queue_wait_seconds").get("p50") or 0),
        "compile_seconds": sum(facts["compile_seconds"].values()),
        "compiled_high_water_bytes": facts["compiled_high_water_bytes"],
        "memory_peak_bytes": result["memory_peak_bytes"],
    }), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--one", type=float, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.one is not None:
        return one(args.workload, args.one, args.seed, args.seconds)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        log = os.path.join(out, f"sweep_{args.workload}_{rate:g}.err")
        with open(log, "w") as err:
            done = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--workload",
                 args.workload, "--rates", "0", "--one", str(rate), "--seed",
                 str(args.seed + i), "--seconds", str(args.seconds)],
                stdout=subprocess.PIPE, stderr=err, text=True)
        line = done.stdout.strip().splitlines()[-1:] or [""]
        print(line[0] or json.dumps({"rate_per_s": rate,
                                     "exit": done.returncode}), flush=True)
        with open(os.path.join(out, f"sweep_{args.workload}.jsonl"), "a") as f:
            f.write(line[0] + "\n")


if __name__ == "__main__":
    main()
