"""What ``solaro2.doc_qa_64k``'s check bites on, on the chip: ONE run of
the cell's engine (``chipbench/runners/serve.py``, a window of
``--seconds``; every request HITS a 65,536-token head and starts from its
state snapshot), then the comparison that decides ``correct`` made again
and again on the SAME sampled requests, one JSON line a reading:

* ``sound``: the family's reference as it is (the run's own verdict),
  with the ten largest gaps of the run, and every sampled token's gap
  beside its row's least margin to a tie on a held expert over the four
  routed layers (``delta_moe_reference._margin``) as the worst gap over
  the rows at or past each of ``MARGINS`` with the share of rows kept
  (every reading, the variants' too); every pair goes to
  ``chiprun_out/delta_check_pairs_<seed>_<reading>.json``, from which
  ``check_undecided_margin`` and ``logit_margin`` are set
  (``chipbench/KDA.md``);
* the reference with one line changed against the sound engine
  (``families/delta_moe_reference.py``'s switches): the delta layers'
  state ZEROED at the hit; ANOTHER head's state restored at the hit (the
  reference's own state after the other head's 65,536 tokens); ``beta``
  not doubled; the decay a head's mean in place of a lane's; the delta
  term left out (``u = v``); the l2-norm left out; the G layer's gate
  left out; ``norm_topk_prob`` left out; each has to read over the
  traffic file's ``logit_margin``;
* with ``--fp8``: a second run whose ENGINE's matrices are rounded to
  float8_e4m3fn (the nearest precision below the stated one) against the
  reference on the unrounded weights; it has to read over it too.

    chiprun -- python3 benchmarks/delta_check_walk.py --seed 7 \\
        [--seconds 20] [--sample 2] [--fp8] [--only gqa_gate_left_out]

Refuses unless JAX finds a TPU.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "solaro2.doc_qa_64k"
MARGINS = (0.0, 0.005, 0.01, 0.02, 0.03, 0.05, 0.1)
# ``HIT`` stands for the position of the hit (the shared head's length),
# ``OTHER`` for the delta layers' state after ANOTHER head's tokens
VARIANTS = {
    "state_zeroed_at_the_hit": {"lost": "HIT"},
    "another_heads_snapshot_restored": {"inject": "OTHER"},
    "beta_not_doubled": {"beta_scale": 1.0},
    "decay_a_heads_mean": {"decay": "head"},
    "delta_term_left_out": {"delta_term": False},
    "l2norm_left_out": {"l2norm": False},
    "gqa_gate_left_out": {"gqa_gate": False},
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
        print(f"delta_check_walk: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2

    import time

    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu  # noqa: F401
    from chipbench import families, traffic
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

    hit = cell["traffic"]["shared_heads"]["tokens"]
    heads = traffic.serve_schedule(cell["traffic"], cfg["vocab_size"],
                                   args.seed, args.seconds)["heads"]

    def resolve(switches, fam, cfg_, params, prompt):
        """``switches`` with ``HIT`` and ``OTHER`` made this request's."""
        out = dict(switches)
        if out.get("lost") == "HIT":
            out["lost"] = (hit,)
        if out.get("inject") == "OTHER":
            other = next(h for h in heads
                         if not np.array_equal(h, prompt[:hit]))
            states = []
            fam.reference.trunk(
                params, np.concatenate([other, prompt[hit:hit + 1]]),
                *fam._layout(cfg_), eps=cfg_["rms_norm_eps"],
                capture=(hit, states))
            out["inject"] = (hit, states)
        return out

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
            lg = np.asarray(fam.logits(
                params, padded, open_cfg, ties=ties,
                **resolve(switches, fam, cfg_, params, h.prompt)))[0]
            at = lg[n_p - 1:len(full) - 1]
            gaps.append(at.max(-1) - at[np.arange(len(at)), full[n_p:]])
            per = len(ties) // cfg_["num_hidden_layers"]   # blocks a layer
            margins.append(np.min(np.stack([
                np.concatenate([np.asarray(r) for r in ties[i:i + per]])
                for i in range(0, len(ties), per)]),
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
                    out, f"delta_check_pairs_{args.seed}_{name}.json"),
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
