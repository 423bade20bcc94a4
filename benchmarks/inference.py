"""Inference benchmark suite — the serving-side numbers the reference
publishes as first-class results (ResNet-50 infer bs16 = 217.69 img/s,
VGG-19 infer, `/root/reference/benchmark/IntelOptimizedPaddle.md:71-87`)
and that rounds 1-4 never measured.

Three rows, printed as JSON lines:
1. resnet50_infer_bs16   — is_test forward through Executor.run, async
   dispatch (device-resident batches, one host sync at the end).
2. gpt_decode_tok_s      — KV-cache autoregressive decode via
   transformer.generate (jitted lax.scan serving path), measured as
   generated tokens/sec.
3. capi_roundtrip_ms     — full C ABI round trip (paddle_create ->
   feed -> run -> fetch) on a small MLP via ctypes against
   libpaddle_tpu_capi.so, per-call host latency.

Usage: python benchmarks/inference.py [--rows resnet,gpt,capi]

These rows also ride along in the driver-captured BENCH json:
``BENCH_INFER=1 python bench.py`` folds them into the flagship line's
``extra`` (``bench.infer_rows``), each row isolated so a failure lands
as an ``"infer_<row>": "FAILED: ..."`` string instead of killing the
round's numbers.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _lat_dict(hist):
    """Histogram of per-call latencies (ms) -> the JSON lat_* fields."""
    pct = hist.percentiles((50, 95, 99))
    return {f"lat_p{p}_ms": round(v, 3) for p, v in pct.items()}


def measure_latency(run_once, calls=30):
    """Per-call latency distribution (p50/p95/p99 ms) with a host sync
    per call — the serving-side tail number async-dispatch throughput
    hides.  ``run_once`` must materialize its result on the host."""
    from paddle_tpu.observability.metrics import Histogram

    hist = Histogram("latency_ms")
    for _ in range(calls):
        t0 = time.perf_counter()
        run_once()
        hist.observe((time.perf_counter() - t0) * 1e3)
    return _lat_dict(hist)


def bench_resnet_infer(batch=16, steps=20, warmup=3, repeats=5):
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import resnet
    from bench import timed_steps  # one timing discipline for all benches

    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        outs = resnet.build(depth=50, class_dim=1000, dtype="bfloat16",
                            is_test=True)
    exe = pt.Executor()
    exe.run(startup)
    img = jnp.asarray(np.random.rand(batch, 3, 224, 224), jnp.bfloat16)
    label = jnp.asarray(np.zeros((batch, 1), np.int32))
    feed = {"img": img, "label": label}
    _, times, _ = timed_steps(exe, main_prog, feed, [outs["prediction"]],
                              steps, warmup, repeats=repeats)
    rates = [batch * steps / t for t in times]
    lat = measure_latency(lambda: np.asarray(exe.run(
        main_prog, feed=feed, fetch_list=[outs["prediction"]],
        return_numpy=False)[0]))
    return float(np.median(rates)), min(rates), max(rates), lat


def bench_gpt_decode(batch=16, prompt_len=16, max_len=512, repeats=5):
    """Greedy KV-cache decode on the serving path (models/transformer.py
    generate): tokens generated per second, whole jitted scan."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import transformer

    n_layer, n_head, d_model, vocab = 12, 6, 768, 32768
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        transformer.build(vocab_size=vocab, n_layer=n_layer, n_head=n_head,
                          d_model=d_model, max_len=max_len,
                          dropout_rate=0.0, fused_head=True,
                          dtype="bfloat16")
    exe = pt.Executor()
    exe.run(startup)
    # device-resident weights, like any real serving process: without
    # this, every call re-uploads ~250 MB of host numpy through the
    # link (extract_params returns host arrays)
    params = jax.device_put({
        k: jnp.asarray(v) for k, v in
        transformer.extract_params(program=main_prog).items()})

    prompt = np.random.randint(1, vocab, (batch, prompt_len)).astype(np.int32)

    # serving config: tokens only (skip stacking ~1 GB of per-step
    # logits), weights/cache in their native bf16 (decode is HBM-bound
    # on weight reads; bf16 halves them).  params MUST be a jit argument
    # — closing over them bakes 250 MB of weights into the HLO as
    # constants (543 MB of HLO text, which kills remote compile).
    gen = jax.jit(lambda ps, pr: transformer.generate(
        ps, pr, max_len, n_layer, n_head, d_model,
        return_logits=False)[0])
    toks = gen(params, prompt)  # compile
    np.asarray(toks)
    new_tokens = batch * (max_len - prompt_len)
    rates, lat_ms = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        toks = gen(params, prompt)
        np.asarray(toks)
        dt = time.perf_counter() - t0
        rates.append(new_tokens / dt)
        lat_ms.append(dt * 1e3)
    from paddle_tpu.observability.metrics import Histogram

    hist = Histogram("decode_ms")
    for v in lat_ms:
        hist.observe(v)
    return float(np.median(rates)), min(rates), max(rates), _lat_dict(hist)


def bench_capi(repeats=200):
    """Per-call latency of the full C ABI round trip on a small MLP."""
    import ctypes
    import tempfile

    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.native import build as nbuild

    lib_path = nbuild.build_capi()
    d = tempfile.mkdtemp(prefix="capibench")
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        x = layers.data("x", shape=[64])
        h = layers.fc(x, 256, act="relu")
        pred = layers.fc(h, 10, act="softmax")
        exe = pt.Executor()
        exe.run(startup)
        pt.io.save_inference_model(d, ["x"], [pred], exe,
                                   main_program=main_prog)

    lib = ctypes.CDLL(lib_path)
    lib.pt_init.argtypes = [ctypes.c_char_p]
    lib.pt_last_error.restype = ctypes.c_char_p
    lib.pt_engine_create.restype = ctypes.c_void_p
    lib.pt_engine_create.argtypes = [ctypes.c_char_p]
    lib.pt_engine_run.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int32, ctypes.c_int32,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
        ctypes.POINTER(ctypes.c_int32)]

    # the bench runs IN-PROCESS (python already hosts the runtime);
    # pt_init binds the embedded interpreter to this repo
    assert lib.pt_init(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))).encode()) == 0, \
        lib.pt_last_error()
    eng = lib.pt_engine_create(d.encode())
    assert eng, lib.pt_last_error()

    x = np.random.rand(1, 64).astype(np.float32)
    names = (ctypes.c_char_p * 1)(b"x")
    datas = (ctypes.POINTER(ctypes.c_float) * 1)(
        x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    shape = np.asarray([1, 64], np.int64)
    shapes = (ctypes.POINTER(ctypes.c_int64) * 1)(
        shape.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    ranks = (ctypes.c_int32 * 1)(2)
    out_data = ctypes.POINTER(ctypes.c_float)()
    out_shape = ctypes.POINTER(ctypes.c_int64)()
    out_rank = ctypes.c_int32()

    def roundtrip():
        rc = lib.pt_engine_run(eng, names, datas, shapes, ranks, 1, 0,
                               ctypes.byref(out_data),
                               ctypes.byref(out_shape),
                               ctypes.byref(out_rank))
        assert rc == 0, lib.pt_last_error()
        assert out_rank.value == 2

    roundtrip()  # compile
    from paddle_tpu.observability.metrics import Histogram

    hist = Histogram("capi_ms")
    for _ in range(repeats):
        t0 = time.perf_counter()
        roundtrip()
        hist.observe((time.perf_counter() - t0) * 1e3)
    pct = hist.percentiles((50, 99))
    return pct[50], pct[99], hist.min, _lat_dict(hist)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="resnet,gpt,capi")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (framework overhead "
                    "without the device)")
    args = ap.parse_args()
    rows = args.rows.split(",")
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    if "resnet" in rows:
        med, lo, hi, lat = bench_resnet_infer()
        print(json.dumps({
            "metric": "resnet50_infer_bs16_img_s", "value": round(med, 1),
            "min": round(lo, 1), "max": round(hi, 1),
            "vs_reference_217.69": round(med / 217.69, 2), **lat}))
    if "gpt" in rows:
        med, lo, hi, lat = bench_gpt_decode()
        print(json.dumps({
            "metric": "gpt_decode_tok_s_bs16", "value": round(med, 1),
            "min": round(lo, 1), "max": round(hi, 1), **lat}))
    if "capi" in rows:
        med, p99, lo, lat = bench_capi()
        print(json.dumps({
            "metric": "capi_roundtrip_ms", "value": round(med, 3),
            "p99": round(p99, 3), "min": round(lo, 3), **lat}))


if __name__ == "__main__":
    main()
