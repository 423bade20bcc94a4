"""What ``nemotron3n.chat_ssm``'s check bites on, on the chip: ONE run of
the cell's engine (``chipbench/runners/serve.py``, a window of
``--seconds``), then the comparison that decides ``correct`` made again
and again on the SAME sampled requests, one JSON line a reading:

* ``sound``: the family's reference as it is (the run's own verdict);
* the reference with one line left out or moved against the sound
  engine (``families/ssm_moe_reference.py``'s switches): ``D x`` out,
  ``dt_bias`` out, the gate after the norm, one norm over all lanes,
  ``relu ** 2`` as ``relu``, the routed scale out, the convolution's
  rows zeroed at every piece boundary (512 rows), the state zeroed
  there, the state forgotten where a prompt's pieces hand over to its
  decode steps, the state rounded to bfloat16 at every position, the attention
  scores' ``head_dim ** -0.5`` out; each has to read over the traffic
  file's ``logit_margin``, or be named in ``chipbench/SSM.md``;
* with ``--fp8``: a second run whose ENGINE's matrices are rounded to
  float8_e4m3fn (the nearest precision below the stated one) against
  the reference on the unrounded weights; it has to read over it too;

Every reading is taken with NO row left out and printed as the worst gap
over the rows whose margin to a tie on a held expert is at or past each
of ``MARGINS``, with the share of rows kept; every sampled token's gap
and margin goes to ``chiprun_out/ssm_check_pairs_<seed>.json``, from
which ``check_undecided_margin`` and ``logit_margin`` are set
(``chipbench/SSM.md``).

    chiprun -- python3 benchmarks/ssm_check_walk.py --seed 7 \\
        [--seconds 20] [--fp8]

Refuses unless JAX finds a TPU.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "nemotron3n.chat_ssm"
PIECE = 512
MARGINS = (0.0, 0.005, 0.01, 0.015, 0.02, 0.03)
VARIANTS = {
    "D_x_out": {"skip": False}, "dt_bias_out": {"dt_bias": False},
    "gate_after_norm": {"gate_first": False},
    "one_norm_over_4096": {"group_norm": False},
    "relu2_as_relu": {"squared": False},
    "routed_scale_out": {"route_scaled": False},
    "tails_zeroed_at_piece_boundary": {"tails_every": PIECE},
    "state_zeroed_at_piece_boundary": {"state_every": PIECE},
    # ``lost`` is filled in a request: the position of its first decode step
    "state_lost_at_the_prompts_end": {"lost": None},
    "state_held_in_bfloat16": {"state_dtype": "bfloat16"},
    "attention_scale_out": {"score_scaled": False},
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fp8", action="store_true")
    ap.add_argument("--sample", type=int, default=0,
                    help="requests sampled (default: the traffic file's)")
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"ssm_check_walk: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2

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
    names = [n for n in args.only.split(",") if n] or list(VARIANTS)

    def say(**line):
        print(json.dumps(dict(line, seed=args.seed, limit=limit)), flush=True)

    pairs = {}
    out_dir = os.path.join(ROOT, "chiprun_out")

    def read(name, fam, cfg_, params, positions, sample, **switches):
        """Every sampled token's gap beside its row's margin to a tie on
        a held expert, under the reference with ``switches``; one line
        with the worst gap over the rows at or past each of MARGINS."""
        open_cfg = dict(cfg_, check_undecided_margin=0.0)
        gaps, margins = [], []
        for h in sample:
            full = h.result(timeout=0)
            n_p = len(h.prompt)
            padded = np.zeros((1, positions), np.int32)
            padded[0, :len(full)] = full
            ties = []
            if "lost" in switches:
                switches = dict(switches, lost=jnp.asarray([n_p], jnp.int32))
            lg = np.asarray(fam.logits(params, padded, open_cfg, ties=ties,
                                       **switches))[0]
            at = lg[n_p - 1:len(full) - 1]
            gaps.append(at.max(-1) - at[np.arange(len(at)), full[n_p:]])
            margins.append(np.asarray(jnp.min(jnp.stack(ties), axis=0))[
                0, n_p - 1:len(full) - 1])
        gap, tie = np.concatenate(gaps), np.concatenate(margins)
        pairs[name] = {"gap": gap.tolist(), "margin": tie.tolist()}
        say(reading=name, rows=len(gap),
            worst_by_request=[round(float(g.max()), 4) for g in gaps], **{
            f"worst_at_{m:g}": (float(gap[tie >= m].max())
                                if (tie >= m).any() else None)
            for m in MARGINS}, **{
            f"kept_at_{m:g}": float((tie >= m).mean()) for m in MARGINS})
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(
                out_dir, f"ssm_check_pairs_{args.seed}.json"), "w") as f:
            json.dump(pairs, f)

    def readings(fam, cfg_, params, positions, sample, margin):
        verdict = check(fam, cfg_, params, positions, sample, margin)
        say(reading="the_runs_own", worst=verdict[1], refused=not verdict[0],
            distinct_tokens_of_generated=[
                [len(set(h.result(timeout=0)[len(h.prompt):].tolist())),
                 len(h.result(timeout=0)) - len(h.prompt)] for h in sample])
        read("sound", fam, cfg_, params, positions, sample)
        for name in names:
            read(name, fam, cfg_, params, positions, sample, **VARIANTS[name])
        return verdict

    serve._check = readings
    result = serve.run(cell, args.seed, args.seconds, None)
    say(reading="run", correct=result["correct"],
        tpot_p90_ms=result["end_to_end"]["tpot_p90_ms"],
        untouched_expert_share=bench_run.load_reader(
            "ssm_moe.untouched_expert_share").read(result["facts"]),
        step_decode_ms=bench_run.load_reader(
            "step.decode_ms").read(result["facts"]))
    if not args.fp8:
        return 0

    # the engine's matrices through float8_e4m3fn, array by array where
    # they lie (two copies of 10.5 GB of weights do not fit the chip);
    # once the engine is gone the check clears them and makes the sound
    # weights again from the seed, for the reference
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
