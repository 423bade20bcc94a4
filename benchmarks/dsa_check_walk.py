"""What ``dots3np.doc_qa_32k``'s check bites on, on the chip: ONE run of
the cell's engine (``chipbench/runners/serve.py``, a window of
``--seconds``), then the comparison that decides ``correct`` made again
and again on the SAME sampled requests, one JSON line a reading:

* ``sound``: the family's reference as it is (the run's own verdict),
  with the ten largest gaps of the run, and every sampled token's gap
  beside its row's least margin to a tie on a held expert over the
  routed layers (``sparse_latent_moe_reference._margin``) as the worst
  gap over the rows at or past each of ``MARGINS`` with the share of rows
  kept (every reading, the variants' too);
  every pair goes to ``chiprun_out/dsa_check_pairs_<seed>.json``, from
  which ``check_undecided_margin`` and ``logit_margin`` are set
  (``chipbench/DSA.md``);
* the reference with one line changed against the sound engine
  (``families/sparse_latent_moe_reference.py``'s switches): the whole
  chain attended in place of ``S_t``; ``relu`` left out of ``I``; the
  indexer's rope left out; the selection taken from the first 2,048
  positions; the sliding planes attended whole; theta 50,000 replaced
  by 8e7 on the sliding planes; the lora rescale left out; the gate left
  out; ``norm_topk_prob`` left out; each has to read over the traffic
  file's ``logit_margin``;
* with ``--fp8``: a second run whose ENGINE's matrices are rounded to
  float8_e4m3fn (the nearest precision below the stated one) against the
  reference on the unrounded weights; it has to read over it too.

    chiprun -- python3 benchmarks/dsa_check_walk.py --seed 7 \\
        [--seconds 20] [--sample 2] [--fp8] [--only gate_left_out]

Refuses unless JAX finds a TPU.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "dots3np.doc_qa_32k"
MARGINS = (0.0, 0.01, 0.02, 0.03, 0.06, 0.1, 0.2)
VARIANTS = {
    "whole_chain_attended_in_place_of_the_selection": {"select": "all"},
    "relu_left_out_of_the_index_scores": {"index_relu": False},
    "indexer_rope_left_out": {"index_rope": False},
    "selection_taken_from_the_first_positions": {"select": "first"},
    "sliding_planes_attended_whole": {"windowed": False},
    "sliding_theta_replaced_by_the_full_layers": {"sliding_theta": 8e7},
    "lora_rescale_left_out": {"rescale": False},
    "gate_left_out": {"gate": False},
    "norm_topk_prob_left_out": {"route_norm": False},
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fp8", action="store_true")
    ap.add_argument("--sample", type=int, default=0,
                    help="requests sampled (default: the traffic file's)")
    ap.add_argument("--only", default=None,
                    help="variants, comma-separated ('' for none)")
    ap.add_argument("--variant-sample", type=int, default=1,
                    help="requests each variant is read on")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"dsa_check_walk: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2

    import time

    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu  # noqa: F401
    from chipbench import families
    from chipbench import run as bench_run
    from chipbench.runners import serve

    cell = bench_run.load_cell(CELL)
    if args.sample:
        cell["traffic"]["check"]["sample"] = args.sample
    cfg, limit = cell["config"], cell["traffic"]["check"]["logit_margin"]
    family = families.of(cfg, "serve")
    check = serve._check
    names = (list(VARIANTS) if args.only is None
             else [n for n in args.only.split(",") if n])

    def say(**line):
        print(json.dumps(dict(line, seed=args.seed, limit=limit)), flush=True)

    def read(name, fam, cfg_, params, positions, sample, **switches):
        """The worst gap a request and over the run under the reference
        with ``switches``; for ``sound`` the ten largest gaps too."""
        t0 = time.perf_counter()
        open_cfg = dict(cfg_, check_undecided_margin=0.0)
        gaps, margins = [], []
        for h in sample:
            full = h.result(timeout=0)
            n_p = len(h.prompt)
            padded = np.zeros((1, positions), np.int32)
            padded[0, :len(full)] = full
            ties = []
            lg = np.asarray(fam.logits(params, padded, open_cfg, ties=ties,
                                       **switches))[0]
            at = lg[n_p - 1:len(full) - 1]
            gaps.append(at.max(-1) - at[np.arange(len(at)), full[n_p:]])
            margins.append(np.min(np.stack([np.asarray(r) for r in ties]),
                                axis=0)[n_p - 1:len(full) - 1])
        gap = np.concatenate(gaps)
        more = {}
        if margins:
            margin = np.concatenate(margins)
            order = np.argsort(-gap)[:10]
            more = {"ten_largest": [[round(float(gap[i]), 4),
                                     round(float(margin[i]), 5)]
                                    for i in order],
                    "median": float(np.median(gap)),
                    "share_over_0.05": float((gap > 0.05).mean()),
                    "margin_quantiles": [float(np.quantile(margin, q))
                                         for q in (0.1, 0.25, 0.5, 0.75)],
                    "prompt_lens": [len(h.prompt) for h in sample]}
            for m in MARGINS:
                kept = margin >= m
                more[f"worst_at_{m:g}"] = (float(gap[kept].max())
                                           if kept.any() else None)
                more[f"kept_at_{m:g}"] = float(kept.mean())
            out = os.path.join(ROOT, "chiprun_out")
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(
                    out, f"dsa_check_pairs_{args.seed}_{name}.json"),
                    "w") as f:
                json.dump({"gap": gap.tolist(), "margin": margin.tolist()}, f)
        say(reading=name, rows=len(gap), worst=float(gap.max()),
            refused=bool(gap.max() > limit),
            worst_by_request=[round(float(g.max()), 4) for g in gaps],
            seconds=round(time.perf_counter() - t0, 1), **more)

    def readings(fam, cfg_, params, positions, sample, margin):
        verdict = check(fam, cfg_, params, positions, sample, margin)
        say(reading="the_runs_own", worst=verdict[1], refused=not verdict[0])
        read("sound", fam, cfg_, params, positions, sample)
        for name in names:
            read(name, fam, cfg_, params, positions,
                 sample[:args.variant_sample], **VARIANTS[name])
        return verdict

    serve._check = readings
    result = serve.run(cell, args.seed, args.seconds, None)
    say(reading="run", correct=result["correct"],
        failed=result["failed"], attempted=result["attempted"],
        tpot_p90_ms=result["end_to_end"]["tpot_p90_ms"],
        serve_tokens_per_s=result["end_to_end"]["serve_tokens_per_s"],
        memory_peak_bytes=result["memory_peak_bytes"],
        step_decode_ms=bench_run.load_reader(
            "step.decode_ms").read(result["facts"]))
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

    family.make_params = rounded

    def fp8(fam, cfg_, params, positions, sample, margin):
        for k in list(params):
            del params[k]
        params.update(make(cfg_, positions, args.seed))
        read("engine_matrices_fp8", fam, cfg_, params, positions, sample)
        return check(fam, cfg_, params, positions, sample, margin)

    serve._check = fp8
    serve.run(cell, args.seed, args.seconds, None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
