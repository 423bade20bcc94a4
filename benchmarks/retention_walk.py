"""Power retention's two kernels alone on the chip, one JSON line a
geometry: device microseconds a call (the Mosaic call of a profiler
trace, as ``flash_walk`` takes them), the call's share of its roofline
(``chipbench/retention_bytes.py``: a live slot's state of one layer read
once and written once, 2 x 34,080,768 B, against the operations), the
worst error against the ``xla_ref`` form on the chip and, for the step,
whether a dead slot's state came back bit-equal.

    chiprun -- python3 benchmarks/retention_walk.py \
        [--only step_6_live,piece_128] [--calls 20] [--out chiprun_out/retention_walk.jsonl]

The geometry is ``brumby14b.doc_continue``'s: ONE layer's state of 16
slots (8 K/V heads of 8,320 stored rows of 128 lanes, float32: 545 MB),
40 query heads; a decode step with 1, 4, 6, 8, 12 and 16 slots live
(which ones is drawn from ``--seed``), a prefill piece of 32, 128, 256
and 512 rows that continues a prompt, one of 512 rows that STARTS one
(``fresh``: the state is never read), and pieces of 64 rows of which 40
and of 512 of which 300 are real.  A piece goes through ``serving/
batched_decode._Cache.advance``, which hands it to the kernel in the
calls ``kernels.retention.chunk_rows`` names (one since PR 44, four of
128 rows a 512-row piece before), ``us_a_piece`` their sum.  The state is
donated and threaded from call to call, as the engine does it.
Refuses unless JAX finds a TPU: a number from a CPU run is no device
metric.
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flash_walk import _mosaic_seconds  # noqa: E402 - Mosaic calls' seconds

CONFIG = "brumby-14b-base"
SLOTS = 16
# name -> ("step", live slots) or ("piece", rows, real rows[, fresh])
GEOMETRIES = {
    "step_1_live": ("step", 1), "step_4_live": ("step", 4),
    "step_6_live": ("step", 6), "step_8_live": ("step", 8),
    "step_12_live": ("step", 12), "step_16_live": ("step", 16),
    "piece_32": ("piece", 32, 32), "piece_128": ("piece", 128, 128),
    "piece_64_of_which_40": ("piece", 64, 40),
    "piece_256": ("piece", 256, 256), "piece_512": ("piece", 512, 512),
    "piece_512_fresh": ("piece", 512, 512, True),
    "piece_512_of_which_300": ("piece", 512, 300),
}


def _device_us(fn, state, args, calls, each=1):
    """Microseconds of the ``each`` Mosaic calls of one ``fn(S, z, *args)
    -> (y, S, z)``, over ``calls`` of them with the state threaded;
    returns it with the last outputs."""
    import jax

    y, *state = fn(*state, *args)  # compile, warm
    jax.block_until_ready(y)
    with tempfile.TemporaryDirectory(prefix="retention_walk") as td:
        with jax.profiler.trace(td):
            for _ in range(calls):
                y, *state = fn(*state, *args)
            jax.block_until_ready(y)
        n, seconds = _mosaic_seconds(td)
    if n != calls * each:
        raise RuntimeError(f"{n} Mosaic calls in the trace, {calls * each} "
                           f"made")
    return 1e6 * seconds / calls, y, state


def _piece(S, z, slot, fresh, q, k, v, lg, valid):
    """A piece as the engine hands it to the kernel: ``_Cache.advance``
    over a window of one slot, which starts a prompt where its first
    position is 0."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import retention
    from paddle_tpu.serving.batched_decode import _Cache

    at = jnp.arange(q.shape[0])[None]
    cache = _Cache(None, None, None, None, jnp.where(fresh, 0, 1) + at,
                   writable=valid[None], slot=slot)
    y, planes = cache.advance(((), (), ((S, z),)), 0, retention, q[None],
                              k[None], v[None], lg[None])
    return (y[0],) + planes[2][0]


def measure(name, calls, seed, peak, cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import retention_bytes
    from paddle_tpu.kernels import retention as rt

    size = retention_bytes.sizes(cfg)
    hk, h, d = size["kv_heads"], size["heads"], size["head_dim"]
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    # a state some thousand steps old: values of the order of the sums
    make = jax.jit(lambda k: (
        30.0 * jax.random.normal(k, (SLOTS, hk, rt.stored_rows(d), d),
                                 jnp.float32),
        100.0 + jax.random.uniform(k, (SLOTS, hk, rt.stored_rows(d)),
                                   jnp.float32)))
    kind, *shape = GEOMETRIES[name]

    def rows(n, heads):
        # unit RMS, as the per-head norm leaves q and k
        return jnp.asarray(rng.normal(size=(n, heads, d)), jnp.bfloat16)

    def gates(n):
        return jnp.asarray(np.log(rng.uniform(0.98, 0.9998, (n, hk))),
                           jnp.float32)

    out = {"geometry": name}
    if kind == "step":
        live = shape[0]
        valid = np.zeros(SLOTS, bool)
        valid[rng.choice(SLOTS, live, replace=False)] = True
        args = (rows(SLOTS, h), rows(SLOTS, hk), rows(SLOTS, hk),
                gates(SLOTS), jnp.asarray(valid))
        fn = jax.jit(rt.retention_step_pallas, donate_argnums=(0, 1))
        ref = jax.jit(rt.retention_step_ref)
        ops, nbytes = retention_bytes.step(cfg)
        least = live * retention_bytes.least_seconds(ops, nbytes, peak)
        dead = int(np.flatnonzero(~valid)[0]) if live < SLOTS else None
        out.update(live_slots=live)
    else:
        n, real, fresh = (shape + [False])[:3]
        slot = int(rng.integers(SLOTS))
        args = (jnp.int32(slot), jnp.asarray(fresh), rows(n, h), rows(n, hk),
                rows(n, hk), gates(n), jnp.arange(n) < real)
        each = len(rt.chunk_rows(n))
        fn = jax.jit(_piece, donate_argnums=(0, 1))
        ref = jax.jit(rt.retention_chunk_ref)
        least = retention_bytes.least_seconds(
            *retention_bytes.piece(cfg, n), peak)
        dead = (slot + 1) % SLOTS
        out.update(rows=n, real_rows=real, fresh=fresh, calls_a_piece=each)
    # one call against the xla_ref form on the same state
    state = make(key)
    before = None if dead is None else np.asarray(state[0][dead, 0, :256])
    want_y, want_S, want_z = ref(*state, *args)
    y, S, z = fn(*state, *args)
    real_rows = slice(None) if kind == "step" else slice(0, shape[1])
    err = float(jnp.max(jnp.abs(y[real_rows] - want_y[real_rows])))
    err_S = float(jnp.max(jnp.abs(S - want_S)) / jnp.max(jnp.abs(want_S)))
    err_z = float(jnp.max(jnp.abs(z - want_z)) / jnp.max(jnp.abs(want_z)))
    untouched = None if dead is None else bool(
        np.array_equal(np.asarray(S[dead, 0, :256]), before))
    del want_y, want_S, want_z
    us, _, _ = _device_us(fn, (S, z), args, calls,
                          1 if kind == "step" else each)
    out.update(**{"us_a_call" if kind == "step" else "us_a_piece": us},
               roofline_pct=100e6 * least / us,
               worst_error=err, worst_state_error=max(err_S, err_z),
               dead_slot_untouched=untouched)
    if kind == "step":
        out.update(us_a_live_slot=us / live)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default="")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"retention_walk: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    from chipbench import flops
    from chipbench import run as bench_run

    peak = flops.peaks(jax.devices()[0].device_kind)
    cfg = bench_run._read_json(bench_run.HERE, "configs", CONFIG + ".json")
    names = [n for n in args.only.split(",") if n] or list(GEOMETRIES)
    lines = []
    for name in names:
        lines.append(json.dumps(measure(name, args.calls, args.seed, peak,
                                        cfg)))
        print(lines[-1], flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
