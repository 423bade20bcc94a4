"""What a serving cell's check bites on, on the chip: ONE run of the
cell's engine (``chipbench/runners/serve.py``, the cell's own traffic and
rate), then the comparison that decides ``correct``
(``serve._check``, unchanged and called as the runner calls it) made
again and again on the SAME sampled requests, one JSON line a reading:

* ``sound``: the family's reference as it is: the run's own verdict;
* with ``--margins 0,0.001,...``, for a family whose ``logits()`` leaves
  rows out as undecided (``check_undecided_margin``; it takes ``ties=``
  and has ``least_gaps(ties, rows)``): ONE forward a request with no row
  left out, then ``serve._check`` again at every margin (the
  configuration's own among them: that one is the verdict) on the stored
  logits with the rows under the margin zeroed, as the family zeroes
  them: the worst gap and the share of rows kept at each, from which the
  margin and ``logit_margin`` are set;
* the reference with one line changed against the sound engine: the
  family's ``check_variants(cfg, heads)``, ``{name: resolve}`` with
  ``resolve(params, prompt) -> (configuration, switches of logits())``;
  each has to read over the traffic file's ``logit_margin``, or the
  family's notes say why it cannot be seen;
* with ``--fp8``: a second run whose ENGINE's matrices are rounded to
  float8_e4m3fn (the nearest precision below bfloat16) against the
  reference on the unrounded weights; it has to read over it too.

    chiprun --timeout 3000 -- python3 benchmarks/check_walk.py \\
        --cell sala9b.doc_qa_128k --seed 7 [--fp8] [--only topk_32] \\
        [--margins 0,0.001] [--seconds 51] [--rate 2.1] [--sample 3]

The three older walks (``ssm_check_walk.py``, ``dsa_check_walk.py``,
``delta_check_walk.py``) are this script's ancestors, one a family; a
family that states its ``check_variants`` needs none of its own.
Refuses unless JAX finds a TPU.
"""

import argparse
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="the window (default: BENCHMARK.json's run_seconds)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="requests a second (default: the traffic file's)")
    ap.add_argument("--sample", type=int, default=0,
                    help="requests sampled (default: the traffic file's)")
    ap.add_argument("--fp8", action="store_true")
    ap.add_argument("--only", default=None,
                    help="variants, comma-separated ('' for none)")
    ap.add_argument("--variant-sample", type=int, default=1,
                    help="requests each variant is read on")
    ap.add_argument("--margins", default="",
                    help="undecided margins to read the sound run at")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"check_walk: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2

    import jax.numpy as jnp

    import paddle_tpu  # noqa: F401
    from chipbench import families, traffic
    from chipbench import run as bench_run
    from chipbench.runners import serve

    cell = bench_run.load_cell(args.cell)
    mix, cfg = cell["traffic"], cell["config"]
    if args.sample:
        mix["check"]["sample"] = args.sample
    if args.rate:
        mix["rate_per_s"] = args.rate
    seconds = args.seconds or bench_run._read_json(
        ROOT, "BENCHMARK.json")["run_seconds"]
    limit = mix["check"]["logit_margin"]
    family = families.of(cfg, "serve")
    check = serve._check
    margins = [float(m) for m in args.margins.split(",") if m]
    heads = traffic.serve_schedule(mix, cfg["vocab_size"], args.seed,
                                   seconds)["heads"]
    variants = (family.check_variants(cfg, heads)
                if hasattr(family, "check_variants") else {})
    names = (list(variants) if args.only is None
             else [n for n in args.only.split(",") if n])

    def say(**line):
        print(json.dumps(dict(line, seed=args.seed, limit=limit)), flush=True)

    def reading(name, logits, cfg_, params, positions, sample):
        """``serve._check`` a request under ``logits`` in the family's
        place; the run's verdict is the worst of them."""
        t0 = time.perf_counter()
        fam = types.SimpleNamespace(logits=logits)
        by_request = [check(fam, cfg_, params, positions, [h], limit)
                      for h in sample]
        worst = max(w for _, w in by_request)
        say(reading=name, worst=worst,
            refused=not all(ok for ok, _ in by_request),
            worst_by_request=[round(w, 5) for _, w in by_request],
            rows=[len(h.result(timeout=0)) - len(h.prompt) for h in sample],
            seconds=round(time.perf_counter() - t0, 1))
        return all(ok for ok, _ in by_request), worst

    def by_margin(name, cfg_, params, positions, sample):
        """The worst gap and the share of rows kept at each of
        ``margins`` and at the configuration's own, which is the run's
        verdict: one forward a request with no row left out, then the
        stored logits with the rows under a margin zeroed."""
        own = cfg_.get("check_undecided_margin", 0.0)
        open_cfg = dict(cfg_, check_undecided_margin=0.0)
        steps = sorted({own, *margins})
        worst, ok = {m: 0.0 for m in steps}, True
        kept = {m: [0, 0] for m in steps}
        for h in sample:
            n_p, n = len(h.prompt), len(h.result(timeout=0))
            held = {}

            def forward(params, tokens, cfg__):
                ties = []
                held["lg"] = family.logits(params, tokens, cfg__, ties=ties)
                held["near"] = family.least_gaps(ties, positions)
                return held["lg"]

            check(types.SimpleNamespace(logits=forward), open_cfg, params,
                  positions, [h], limit)
            for m in steps:
                out = held["near"] < m
                out[:n_p - 1] = False
                out[n - 1:] = False
                held["lg"][-1][out] = 0.0
                fine, w = check(types.SimpleNamespace(
                    logits=lambda *_: held["lg"]), open_cfg, params,
                    positions, [h], limit)
                worst[m] = max(worst[m], w)
                ok = ok and (fine or m != own)
                kept[m][0] += int((~out[n_p - 1:n - 1]).sum())
                kept[m][1] += n - n_p
        say(reading=name, worst=worst[own], refused=not ok, margin=own,
            worst_by_margin={f"{m:g}": worst[m] for m in steps},
            kept_by_margin={f"{m:g}": round(kept[m][0] / max(kept[m][1], 1),
                                            4) for m in steps})
        return ok, worst[own]

    def sound(name, fam, cfg_, params, positions, sample):
        if margins:
            return by_margin(name, cfg_, params, positions, sample)
        return reading(name, fam.logits, cfg_, params, positions, sample)

    def readings(fam, cfg_, params, positions, sample, margin):
        verdict = sound("sound", fam, cfg_, params, positions, sample)
        for name in names:
            for h in sample[:args.variant_sample]:
                def changed(params, tokens, cfg__, name=name,
                            prompt=h.prompt):
                    use_cfg, how = variants[name](params, prompt)
                    return fam.logits(params, tokens, use_cfg, **how)
                reading(name, changed, cfg_, params, positions, [h])
        return verdict

    def run_line(name, result, t0):
        facts = result["facts"]
        say(reading=name, correct=result["correct"],
            failed=result["failed"], attempted=result["attempted"],
            end_to_end=result["end_to_end"],
            memory_peak_bytes=result["memory_peak_bytes"],
            setup_s=result["window_start"] - t0,
            rate_per_s=mix["rate_per_s"], seconds=seconds,
            drain_s=facts["drain_s"],
            slots_active_mean=facts["slots_active_mean"],
            slots_active_max=facts["slots_active_max"])

    serve._check = readings
    t0 = time.perf_counter()
    run_line("run", serve.run(cell, args.seed, seconds, None), t0)
    if not args.fp8:
        return 0

    # the engine's matrices through float8_e4m3fn, array by array where
    # they lie; once the engine is gone the check clears them and makes
    # the sound weights again from the seed, for the reference
    make = family.make_params

    def rounded(cfg_, positions, seed):
        params = make(cfg_, positions, seed)
        for k in list(params):
            if params[k].ndim >= 2:
                params[k] = params[k].astype(jnp.float8_e4m3fn).astype(
                    params[k].dtype)
        return params

    def fp8(fam, cfg_, params, positions, sample, margin):
        for k in list(params):
            del params[k]
        params.update(make(cfg_, positions, args.seed))
        return sound("engine_matrices_fp8", fam, cfg_, params, positions,
                     sample)

    family.make_params = rounded
    serve._check = fp8
    t0 = time.perf_counter()
    run_line("run_fp8", serve.run(cell, args.seed, seconds, None), t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
