"""The gated delta rule's step kernel and WY form alone on the chip, one
JSON line a geometry: device microseconds a call (the step: the Mosaic
call ``delta_step`` of a profiler trace, as ``ssm_walk`` takes it,
beside the whole call's busy time with its XLA rows; the WY form, XLA
einsums: the busy union of the call's operations), the call's share of
its roofline (``chipbench/delta_bytes.py``: a live slot's state of one
layer read once and written once, 2 x 4,194,304 B, against the
operations), the worst error against the row-by-row oracle on the chip
and, for the step, whether a dead slot's state came back bit-equal.

    chiprun -- python3 benchmarks/delta_walk.py \\
        [--only step_3_live,piece_512] [--calls 20] [--out chiprun_out/delta_walk.jsonl]

The geometry is ``solaro2.doc_qa_64k``'s: ONE layer's state of 16 slots
(``[64, 128, 128]`` float32 each: 67 MB) and its tails; a decode step
with 1, 3, 5, 8 and 16 slots live (which ones is drawn from ``--seed``),
a prefill piece of 8, 32, 128, 256 and 512 rows that continues a prompt,
one of 512 rows that STARTS one, a piece of 512 of which 300 are real,
and 512 rows at the init's strongest decay (e^-1.6 a row a lane: the
geometry at which a ratio taken as ``exp(-G)`` overflows).  ``piece_*``
is the WY form the program runs (tiles of 64, sub-blocks of 16);
``pairs_*`` the same rows with every ratio of a tile taken pair by pair
(``SUB = TILE``: no product through a reference row), the other form
this walk compares.  The state is donated and threaded from call to
call, as the engine does it.  Refuses unless JAX finds a TPU: a number
from a CPU run is no device metric.
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONFIG = "solar-open2-250b"
SLOTS = 16
# name -> ("step", live slots) or (form, rows, real rows[, fresh[, strong]])
GEOMETRIES = {
    "step_1_live": ("step", 1), "step_3_live": ("step", 3),
    "step_5_live": ("step", 5), "step_8_live": ("step", 8),
    "step_16_live": ("step", 16),
    "piece_8": ("piece", 8, 8), "piece_32": ("piece", 32, 32),
    "piece_128": ("piece", 128, 128), "piece_256": ("piece", 256, 256),
    "piece_512": ("piece", 512, 512),
    "piece_512_fresh": ("piece", 512, 512, True),
    "piece_512_of_which_300": ("piece", 512, 300),
    "piece_512_strong": ("piece", 512, 512, False, True),
    "pairs_128": ("pairs", 128, 128), "pairs_512": ("pairs", 512, 512),
}


def _seconds(trace_dir):
    """(Mosaic calls, their seconds, the busy union of every operation)
    on the chip's operations line of the newest trace under
    ``trace_dir``; a ``while`` counts as what it holds."""
    from chipbench import trace_reduce

    calls, mosaic, spans = 0, 0, []
    for events in trace_reduce.chip_ops(trace_reduce.load(trace_dir)).values():
        for start, end, _, hlo in events:
            if "tpu_custom_call" in hlo:
                calls += 1
                mosaic += end - start
            spans.append((start, end))
    busy, at = 0, 0
    for start, end in sorted(spans):
        busy += max(0, end - max(start, at))
        at = max(at, end)
    return calls, mosaic * 1e-9, busy * 1e-9


def _device_us(fn, state, args, calls):
    import jax

    y, *state = fn(*state, *args)  # compile, warm
    jax.block_until_ready(y)
    with tempfile.TemporaryDirectory(prefix="delta_walk") as td:
        with jax.profiler.trace(td):
            for _ in range(calls):
                y, *state = fn(*state, *args)
            jax.block_until_ready(y)
        n, mosaic, busy = _seconds(td)
    return n, 1e6 * mosaic / calls, 1e6 * busy / calls


def measure(name, calls, seed, peak, cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import delta_bytes
    from paddle_tpu.kernels import delta

    size = delta_bytes.sizes(cfg)
    H, D, taps = size["delta_heads"], size["delta_head_dim"], size["taps"]
    rng = np.random.default_rng(seed)
    bf16, f32 = jnp.bfloat16, jnp.float32
    layer = dict(conv_w=jnp.asarray(
        rng.uniform(-0.5, 0.5, (3 * H * D, taps)), bf16), heads=H)
    s_shape, t_shape = delta.state_shapes(H, D, taps)
    make = jax.jit(lambda k: (
        jax.random.normal(k, (SLOTS,) + s_shape, f32),
        jax.random.normal(k, (SLOTS,) + t_shape, bf16)))

    def rows(n, strong=False):
        """q, k, v as the projections leave them; the log decay from the
        init (A in [1, 16], a step log-uniform in [0.001, 0.1]) or at its
        strong end; beta on both sides of 1."""
        qkv = tuple(jnp.asarray(1.3 * rng.normal(size=(n, H * D)), bf16)
                    for _ in range(3))
        g = -(np.repeat(rng.uniform(1, 16, H), D) * np.exp(rng.uniform(
            np.log(1e-3), np.log(0.1), (n, H * D))))
        if strong:
            g = -rng.uniform(1.2, 1.6, (n, H * D))
        return qkv + (jnp.asarray(g, f32),
                      jnp.asarray(rng.uniform(0.05, 1.95, (n, H)), f32))

    kind, *shape = GEOMETRIES[name]
    out = {"geometry": name}
    sub = delta.SUB
    if kind == "step":
        live = shape[0]
        valid = np.zeros(SLOTS, bool)
        valid[rng.choice(SLOTS, live, replace=False)] = True
        args = (*rows(SLOTS), jnp.asarray(valid))
        fn = jax.jit(lambda S, t, *a: delta.delta_step_pallas(
            S, t, *a, **layer), donate_argnums=(0, 1))
        ref = jax.jit(lambda S, t, *a: delta.delta_step_ref(
            S, t, *a, **layer))
        least = live * delta_bytes.least_seconds(
            *delta_bytes.step(cfg), peak)
        dead = int(np.flatnonzero(~valid)[0]) if live < SLOTS else None
        out.update(live_slots=live)
    else:
        n, real, fresh, strong = (shape + [False, False])[:4]
        slot = int(rng.integers(SLOTS))
        args = (jnp.int32(slot), jnp.asarray(fresh), *rows(n, strong),
                jnp.arange(n) < real)
        if kind == "pairs":
            delta.SUB = delta.TILE       # this process times one form
        fn = jax.jit(lambda S, t, *a: delta.delta_chunk(S, t, *a, **layer),
                     donate_argnums=(0, 1))

        # the oracle: the same rows one step at a time
        def by_rows(S, t, slot, fresh, q, k, v, g, beta, valid):
            keep = jnp.where(fresh, 0.0, 1.0)
            S = S.at[slot].multiply(keep)
            t = t.at[slot].multiply(keep.astype(t.dtype))
            at = jnp.arange(SLOTS) == slot

            def one(carry, row):
                S, t = carry
                *r, ok = row
                y, S, t = delta.delta_step_ref(
                    S, t, *(jnp.broadcast_to(a, (SLOTS,) + a.shape)
                            for a in r), at & ok, **layer)
                return (S, t), y[slot]

            (S, t), y = jax.lax.scan(one, (S, t), (q, k, v, g, beta, valid))
            return y, S, t

        ref = jax.jit(by_rows)
        least = delta_bytes.least_seconds(*delta_bytes.piece(cfg, n), peak)
        dead = (slot + 1) % SLOTS
        out.update(rows=n, real_rows=real, fresh=fresh, strong=strong,
                   sub_block=delta.SUB)
    state = make(jax.random.PRNGKey(seed))
    before = None if dead is None else np.asarray(state[0][dead, 0, :8])
    with jax.default_matmul_precision("highest"):
        want_y, want_S, want_t = ref(*state, *args)
    y, S, t = fn(*state, *args)
    real_rows = slice(None) if kind == "step" else slice(0, shape[1])
    err = float(jnp.max(jnp.abs(y[real_rows] - want_y[real_rows]))
                / jnp.max(jnp.abs(want_y[real_rows])))
    err_S = float(jnp.max(jnp.abs(S - want_S)) / jnp.max(jnp.abs(want_S)))
    tails = bool(jnp.array_equal(t, want_t))
    finite = bool(jnp.isfinite(y).all() & jnp.isfinite(S).all())
    untouched = None if dead is None else bool(
        np.array_equal(np.asarray(S[dead, 0, :8]), before))
    del want_y, want_S, want_t
    n_mosaic, mosaic_us, busy_us = _device_us(fn, (S, t), args, calls)
    delta.SUB = sub
    kernel_us = mosaic_us if kind == "step" else busy_us
    out.update(us_a_call=busy_us, mosaic_calls=n_mosaic // calls,
               roofline_pct=100e6 * least / kernel_us,
               worst_error=err, worst_state_error=err_S, tails_equal=tails,
               finite=finite, dead_slot_untouched=untouched)
    if kind == "step":
        out.update(us_the_kernel=mosaic_us, us_a_live_slot=mosaic_us / live)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default="")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=57)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"delta_walk: needs a TPU, JAX found "
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
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
