"""Pallas fused ResNet bottleneck block vs XLA — the attempt-or-retire
experiment for the 2,450 img/s HBM ceiling (RESULTS.md round 2).

One conv2-stage bottleneck (NHWC, bs128 @ 56x56, 256 -> 64 -> 3x3x64 ->
256 + residual + relu, BN folded to per-channel scale/shift as in
inference), forward only: the Pallas kernel keeps the two mid
activations entirely in VMEM (grid over (batch, row-bands), 3x3 via 9
shifted matmuls on the band with a 1-row halo), so HBM traffic is read
x-band + write out-band instead of XLA's extra mid-tensor round trips.

If the fused forward cannot substantially beat XLA here — the MOST
bandwidth-bound block shape, without the training-mode complications
(two-pass batch-norm stats, triple-recompute backward) — the full
fused-block program is not worth its cost and the item retires.

Usage: python benchmarks/bottleneck_pallas.py [--interpret]
"""

import argparse
import glob
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_fused(H, W, Cin, Cm, Cout, tile_h, interpret):
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl

    def kernel(x_ref, top_ref, bot_ref, w1_ref, w2_ref, w3_ref, s1_ref,
               b1_ref, s2_ref, b2_ref, s3_ref, b3_ref, out_ref):
        # halo rows arrive as separate single-row blocks (BlockSpec
        # indices are block-granular, so overlapping bands can't be
        # expressed on one input; the same x is passed three times with
        # row-computed index maps instead — clamped duplicates at the
        # tensor edge are masked off below)
        band = jnp.concatenate(
            [top_ref[0], x_ref[0], bot_ref[0]], axis=0
        )                                             # [th+2, W, Cin]
        th2 = band.shape[0]
        # conv1 1x1 + bn + relu: channel matmul on the whole band
        y1 = jax.lax.dot_general(
            band.reshape(th2 * W, Cin), w1_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        y1 = jnp.maximum(y1 * s1_ref[...] + b1_ref[...], 0.0)
        y1 = y1.reshape(th2, W, Cm).astype(band.dtype)

        # 3x3 conv as 9 shifted matmuls; SAME padding via zero row/col
        # masks (the halo provides the vertical neighbours)
        i = pl.program_id(1)
        nbands = pl.num_programs(1)
        acc = jnp.zeros((tile_h * W, Cm), jnp.float32)
        for dy in (-1, 0, 1):
            # rows of the band feeding the output rows for this dy:
            # output row r (global r0+r) reads band row (1+r+dy)
            rows = y1[1 + dy: 1 + dy + tile_h]  # static slice (Mosaic
            # has no dynamic_slice lowering)
            # zero the out-of-image vertical neighbours at the tensor edge
            if dy == -1:
                top_gone = (i == 0)
                rows = jnp.where(
                    top_gone
                    & (jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
                       == 0),
                    0.0, rows)
            if dy == 1:
                bot_gone = (i == nbands - 1)
                rows = jnp.where(
                    bot_gone
                    & (jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
                       == tile_h - 1),
                    0.0, rows)
            for dx in (-1, 0, 1):
                # out[w] sums in[w + dx] * w2[dy+1, dx+1]
                if dx == -1:
                    shifted = jnp.pad(rows[:, :-1, :],
                                      ((0, 0), (1, 0), (0, 0)))
                elif dx == 1:
                    shifted = jnp.pad(rows[:, 1:, :],
                                      ((0, 0), (0, 1), (0, 0)))
                else:
                    shifted = rows
                w = w2_ref[dy + 1, dx + 1]            # [Cm, Cm]
                acc = acc + jax.lax.dot_general(
                    shifted.reshape(tile_h * W, Cm), w,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        y2 = jnp.maximum(acc * s2_ref[...] + b2_ref[...], 0.0)
        y2 = y2.astype(band.dtype)

        # conv3 1x1 + bn + residual + relu
        y3 = jax.lax.dot_general(
            y2, w3_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        y3 = y3 * s3_ref[...] + b3_ref[...]
        resid = band[1:1 + tile_h].reshape(tile_h * W, Cin)
        y3 = jnp.maximum(y3 + resid.astype(jnp.float32), 0.0)
        out_ref[0] = y3.reshape(tile_h, W, Cout).astype(out_ref.dtype)

    nbands = H // tile_h

    def fused(x, w1, w2, w3, s1, b1, s2, b2, s3, b3):
        N = x.shape[0]
        rep = lambda a: a.astype(jnp.float32)
        return pl.pallas_call(
            kernel,
            grid=(N, nbands),
            in_specs=[
                pl.BlockSpec((1, tile_h, W, Cin),
                             lambda n, i: (n, i, 0, 0)),
                # single-row halos: block row size 1 makes the row block
                # index == the row number, so it can be computed (and
                # clamped) from the band index
                pl.BlockSpec((1, 1, W, Cin),
                             lambda n, i: (n, jnp.maximum(
                                 i * tile_h - 1, 0), 0, 0)),
                pl.BlockSpec((1, 1, W, Cin),
                             lambda n, i: (n, jnp.minimum(
                                 (i + 1) * tile_h, H - 1), 0, 0)),
                pl.BlockSpec((Cin, Cm), lambda n, i: (0, 0)),
                pl.BlockSpec((3, 3, Cm, Cm), lambda n, i: (0, 0, 0, 0)),
                pl.BlockSpec((Cm, Cout), lambda n, i: (0, 0)),
            ] + [pl.BlockSpec((c,), lambda n, i: (0,))
                 for c in (Cm, Cm, Cm, Cm, Cout, Cout)],
            out_specs=pl.BlockSpec((1, tile_h, W, Cout),
                                   lambda n, i: (n, i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((N, H, W, Cout), x.dtype),
            interpret=interpret,
        )(x, x, x, w1, w2, w3, rep(s1), rep(b1), rep(s2), rep(b2),
          rep(s3), rep(b3))

    return fused


def xla_reference(x, w1, w2, w3, s1, b1, s2, b2, s3, b3):
    import jax
    import jax.numpy as jnp

    dn = jax.lax.conv_dimension_numbers(x.shape, (1, 1, 1, 1),
                                        ("NHWC", "HWIO", "NHWC"))
    y1 = jax.lax.conv_general_dilated(
        x, w1.reshape(1, 1, *w1.shape), (1, 1), "SAME",
        dimension_numbers=dn,
        preferred_element_type=jnp.float32)
    y1 = jnp.maximum(y1 * s1 + b1, 0.0).astype(x.dtype)
    dn2 = jax.lax.conv_dimension_numbers(y1.shape, w2.shape,
                                         ("NHWC", "HWIO", "NHWC"))
    y2 = jax.lax.conv_general_dilated(
        y1, w2, (1, 1), "SAME", dimension_numbers=dn2,
        preferred_element_type=jnp.float32)
    y2 = jnp.maximum(y2 * s2 + b2, 0.0).astype(x.dtype)
    dn3 = jax.lax.conv_dimension_numbers(y2.shape, (1, 1, 1, 1),
                                         ("NHWC", "HWIO", "NHWC"))
    y3 = jax.lax.conv_general_dilated(
        y2, w3.reshape(1, 1, *w3.shape), (1, 1), "SAME",
        dimension_numbers=dn3,
        preferred_element_type=jnp.float32)
    y3 = y3 * s3 + b3
    return jnp.maximum(y3 + x.astype(jnp.float32), 0.0).astype(x.dtype)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--tile-h", type=int, default=8)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if args.interpret:
        jax.config.update("jax_platforms", "cpu")

    H = W = 56
    Cin = Cout = 256
    Cm = 64
    N = args.batch if not args.interpret else 2
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(N, H, W, Cin)) * 0.5, jnp.bfloat16)
    w1 = jnp.asarray(rng.normal(size=(Cin, Cm)) * 0.05, jnp.bfloat16)
    w2 = jnp.asarray(rng.normal(size=(3, 3, Cm, Cm)) * 0.05, jnp.bfloat16)
    w3 = jnp.asarray(rng.normal(size=(Cm, Cout)) * 0.05, jnp.bfloat16)
    sb = [jnp.asarray(rng.normal(size=(c,)) * 0.1 + 1.0, jnp.float32)
          for c in (Cm, Cm, Cm, Cm, Cout, Cout)]

    fused = jax.jit(make_fused(H, W, Cin, Cm, Cout, args.tile_h,
                               args.interpret))
    ref = jax.jit(xla_reference)

    out_f = fused(x, w1, w2, w3, *sb)
    out_r = ref(x, w1, w2, w3, *sb)
    scale = float(jnp.max(jnp.abs(out_r.astype(jnp.float32)))) or 1.0
    err = float(jnp.max(jnp.abs(out_f.astype(jnp.float32)
                                - out_r.astype(jnp.float32)))) / scale
    print(f"max rel diff fused vs XLA: {err:.2e}")
    assert err < 2e-2, "fused bottleneck disagrees with XLA"
    if args.interpret:
        print("interpret-mode check OK")
        return

    # device-time comparison via SEPARATE traces (a shared trace would
    # attribute the fused program's non-custom-call ops — casts, any
    # layout copies — to the XLA side)
    from benchmarks.gpt_profile import hlo_self_times

    steps = 10

    def device_time(fn):
        td = tempfile.mkdtemp(prefix="bneck")
        out = None
        with jax.profiler.trace(td):
            for _ in range(steps):
                out = fn(x, w1, w2, w3, *sb)
            float(jnp.sum(out.astype(jnp.float32).ravel()[0]))
        rows = hlo_self_times(glob.glob(td + "/**/*.xplane.pb",
                                        recursive=True)[0])
        return sum(us for cat, name, us, occ in rows if occ >= steps)

    fused_us = device_time(fused)
    xla_us = device_time(ref)
    flops = 2 * N * H * W * (Cin * Cm + 9 * Cm * Cm + Cm * Cout)
    print(f"pallas fused: {fused_us/steps/1e3:7.3f} ms "
          f"({flops/(fused_us/steps*1e-6)/1e12:5.1f} TF/s)")
    print(f"xla composed: {xla_us/steps/1e3:7.3f} ms "
          f"({flops/(xla_us/steps*1e-6)/1e12:5.1f} TF/s)")
    print(f"speedup: {xla_us/fused_us:.2f}x")


if __name__ == "__main__":
    main()
