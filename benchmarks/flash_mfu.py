"""Flash-attention kernel MFU sweep — DEVICE-TIME based.

Host wall timing carries per-call dispatch overhead that swamps
ms-scale kernels.  This sweep instead traces one fwd+bwd loop per
config and reads the Pallas kernels' per-HLO self time from the xplane:

* fwd kernel  = the ``jvp``   custom-call inside the grad program
* dq kernel   = the first  ``transpose_jvp`` custom-call
* dkv kernel  = the second ``transpose_jvp`` custom-call

MFU is model-flops based (causal work = half the full t^2; backward
counted at 2x forward, per-kernel recompute NOT credited), against the
chip's bf16 peak.

Usage: python benchmarks/flash_mfu.py [--quick]
"""

import argparse
import glob
import json
import sys
import tempfile

import numpy as np


def custom_call_times(pb_path):
    """{hlo_op_name: avg_self_time_us} for custom-call rows."""
    from xprof.convert import raw_to_tool_data as r2t

    data, _ = r2t.xspace_to_tool_data([pb_path], "hlo_stats", {})
    obj = json.loads(data) if isinstance(data, (str, bytes)) else data
    cols = [c["id"] for c in obj["cols"]]
    i_cat = cols.index("category")
    i_name = cols.index("hlo_op_name")
    i_avg = cols.index("avg_self_time")
    out = {}
    for r in obj["rows"]:
        vals = [c["v"] if isinstance(c, dict) else c for c in r["c"]]
        if vals[i_cat] == "custom-call":
            out[str(vals[i_name])] = float(vals[i_avg])
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, ".")
    from bench import chip_peak_flops
    from paddle_tpu.ops.pallas_attention import flash_attention

    dev = jax.devices()[0]
    peak = chip_peak_flops(dev)
    print(f"# device={dev.device_kind} peak_bf16={peak/1e12:.0f} TF/s "
          f"(device-time MFU via xplane)")

    configs = [
        # (bh, t, d, block)
        (32, 8192, 64, 1024),
        (16, 8192, 128, 1024),
        (8, 16384, 128, 1024),
        (4, 32768, 128, 1024),
        (2, 65536, 128, 1024),
    ]
    if args.quick:
        configs = configs[1:2]

    steps = 6
    for bh, t, d, blk in configs:
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.normal(size=(1, t, bh, d)) * 0.3,
                               jnp.bfloat16) for _ in range(3))

        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, block_q=blk,
                                block_k=blk)
            return jnp.sum(o.astype(jnp.float32) * 1e-3)

        bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        g = bwd(q, k, v)  # compile
        float(jnp.sum(g[0][0, 0, 0].astype(jnp.float32)))

        td = tempfile.mkdtemp(prefix="flmfu")
        with jax.profiler.trace(td):
            for _ in range(steps):
                g = bwd(q, k, v)
            float(jnp.sum(g[0][0, 0, 0].astype(jnp.float32)))
        pbs = glob.glob(td + "/**/*.xplane.pb", recursive=True)
        cc = custom_call_times(pbs[0])
        fwd_us = sum(us for n, us in cc.items()
                     if "jvp" in n and "transpose" not in n)
        bwd_us = sum(us for n, us in cc.items() if "transpose" in n)
        if fwd_us == 0 or bwd_us == 0:
            print(f"t={t} d={d}: unexpected custom-call names {cc}")
            continue

        fwd_flops = 2 * 2 * bh * t * t * d / 2  # causal model flops
        tot_flops = 3 * fwd_flops               # fwd + bwd(2x), no recompute
        fwd_s, fb_s = fwd_us / 1e6, (fwd_us + bwd_us) / 1e6
        print(f"t={t:6d} d={d:3d} bh={bh:2d} | "
              f"fwd {fwd_s*1e3:7.2f} ms {fwd_flops/fwd_s/1e12:6.1f} TF/s "
              f"MFU {fwd_flops/fwd_s/peak*100:5.1f}% | "
              f"fwd+bwd {fb_s*1e3:7.2f} ms {tot_flops/fb_s/1e12:6.1f} TF/s "
              f"MFU {tot_flops/fb_s/peak*100:5.1f}%")


if __name__ == "__main__":
    main()
