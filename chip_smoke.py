"""The quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py          # one process, one chip (or four)

Drives the main path once at the full width of the GPT flagship
(``tune.flagship_dims()``: 12 layers x 768, 6 heads of 128, vocab 32768,
bf16, fused CE head) through the entry points a user calls:

* kernels — every ``pallas_tpu`` kernel on the path compiled natively
  (``interpret=False``) at the geometry the two phases below use and
  compared with its ``xla_ref`` oracle inside ``ORACLE_TOL``;
* train   — ``pt.Executor().run`` at t=4096, batch 8: two warm-up and
  eight timed steps, loss finite and falling;
* serve   — ``pt.serving.ServingEngine`` over the weights just trained
  (32 slots x 512, 32-token blocks, prefix reuse): eight requests that
  share a 64-token head, every result bounded by a timeout;
* mesh    — with four or more devices, the same GPT on ``dp2 x fsdp2``
  with the FSDP recipe; otherwise reported as not run.

It refuses to run unless ``jax.default_backend() == "tpu"``, lets no
phase's failure be caught, and prints the verdict per phase and then, as
its LAST line, one JSON object with exactly the keys ``ok`` and
``device`` (the device as JAX reports it).  The figures it prints are
smoke figures, not benchmark numbers: one run, no repeats.
"""

import gc
import json
import os
import sys
import time
import traceback

import numpy as np

SEQ, N_WARM, N_TIMED = 4096, 2, 8
# serving geometry: 32 slots of 512 positions, chunks of 16 steps
MAX_LEN, SLOTS, CHUNK, MIN_BUCKET, BLOCK_TOKENS = 512, 32, 16, 16, 32
HEAD_LEN, MAX_NEW, N_REQUESTS = 64, 32, 8
# token ids are drawn from the first DATA_VOCAB entries so that ten
# optimizer steps are enough for the loss to fall (the label marginal
# alone is worth ln(32768/256)); every table keeps its full width
DATA_VOCAB = 256
SERVE_TIMEOUT_S = 600.0
# set once the chip and the package are both found: from then on a
# failed phase still ends the output with a result line ("ok": false)
_DEVICE = None


def result_line(ok, device):
    """The last line of standard output, as the driver reads it: exactly
    ``ok`` and ``device``, the device exactly ``platform``, ``kind`` and
    ``count``.  Anything else the run has to say goes on earlier lines."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def _rel_err(got, ref):
    import jax.numpy as jnp

    got = got.astype(jnp.float32).reshape(ref.shape)
    ref = ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def _on_device(arr, what):
    plats = {d.platform for d in arr.devices()}
    assert plats == {"tpu"}, f"{what} lives on {arr.devices()}"


def kernels_phase(dims):
    """Each Mosaic kernel on the path, natively compiled, against the
    xla_ref oracle (f32, ``highest`` matmuls) at the bf16 tolerance."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import get_kernel, oracle_tol
    from paddle_tpu.kernels.paged_attention import paged_attention_pallas
    from paddle_tpu.ops.pallas_attention import (
        _pallas_flash_attention_packed)
    from paddle_tpu.ops.pallas_ce import _pallas_ce

    h, d_model, vocab = dims["n_head"], dims["d_model"], dims["vocab"]
    dh = d_model // h
    rng = np.random.default_rng(23)
    f32 = lambda *xs: tuple(x.astype(jnp.float32) for x in xs)
    out = {}

    # flash forward + backward, packed d128, the trainer's t and blocks
    # (batch 1: the dense oracle is O(t^2) per (batch, head))
    attn_oracle = get_kernel("flash_attention", "xla_ref").impl
    q, k, v = (jnp.asarray(rng.normal(size=(1, SEQ, d_model)) * 0.5,
                           jnp.bfloat16) for _ in range(3))
    wgt = jnp.cos(jnp.arange(SEQ * d_model, dtype=jnp.float32)
                  .reshape(1, SEQ, d_model) * 1e-3)

    def flash(q, k, v):
        return _pallas_flash_attention_packed(q, k, v, h, causal=True,
                                              interpret=False)

    def dense(q, k, v):
        r4 = lambda x: x.reshape(1, SEQ, h, dh)
        with jax.default_matmul_precision("highest"):
            return attn_oracle.call(r4(q), r4(k), r4(v), causal=True)

    def wsum(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32).reshape(
            wgt.shape) * wgt)

    o = jax.jit(flash)(q, k, v)
    _on_device(o, "flash output")
    out["flash_fwd"] = _rel_err(o, jax.jit(dense)(*f32(q, k, v)))
    g = jax.jit(jax.grad(wsum(flash), (0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(wsum(dense), (0, 1, 2)))(*f32(q, k, v))
    out["flash_bwd"] = max(_rel_err(a, r) for a, r in zip(g, g_ref))
    tol = oracle_tol("flash_attention", "bfloat16")
    assert out["flash_fwd"] <= tol and out["flash_bwd"] <= oracle_tol(
        "flash_attention", "bfloat16", "grad"), out

    # fused CE head forward + backward at the trainer's d_model/vocab
    # and block geometry (4096 of the 32768 rows: the oracle's logits
    # are [rows, vocab] f32)
    ce_oracle = get_kernel("fused_ce", "xla_ref").impl
    n = 4096
    x = jnp.asarray(rng.normal(size=(n, d_model)) * 0.3, jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(d_model, vocab)) * 0.05, jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, vocab, (n,)), jnp.int32)
    gvec = jnp.asarray(rng.normal(size=(n,)) * 0.1, jnp.float32)

    def ce(x, w):
        return _pallas_ce(x, w, y, interpret=False)

    def ce_dense(x, w):
        with jax.default_matmul_precision("highest"):
            return ce_oracle.call(x, w, y)

    loss = jax.jit(ce)(x, w)
    _on_device(loss, "CE loss")
    out["ce_fwd"] = _rel_err(loss, jax.jit(ce_dense)(*f32(x, w)))
    gsum = lambda fn: lambda x, w: jnp.sum(fn(x, w) * gvec)
    g = jax.jit(jax.grad(gsum(ce), (0, 1)))(x, w)
    g_ref = jax.jit(jax.grad(gsum(ce_dense), (0, 1)))(*f32(x, w))
    out["ce_bwd"] = max(_rel_err(a, r) for a, r in zip(g, g_ref))
    assert out["ce_fwd"] <= oracle_tol("fused_ce", "bfloat16") and out[
        "ce_bwd"] <= oracle_tol("fused_ce", "bfloat16", "grad"), out

    # paged attention at the engine's pool geometry: decode (W=1) and a
    # speculative verify window (W=4), ragged chains over a shuffled pool
    paged_oracle = get_kernel("paged_attention", "xla_ref").impl
    nb = MAX_LEN // BLOCK_TOKENS
    n_blocks = 1 + SLOTS * nb + 2 * nb
    pool_k, pool_v = (jnp.asarray(
        rng.normal(size=(n_blocks, BLOCK_TOKENS, h, dh)) * 0.5,
        jnp.bfloat16) for _ in range(2))
    for width in (1, 4):
        table = np.zeros((SLOTS, nb), np.int32)
        pos = np.zeros((SLOTS, width), np.int32)
        free = iter(rng.permutation(np.arange(1, n_blocks)))
        for s in range(SLOTS):
            p0 = int(rng.integers(0, MAX_LEN - width))
            used = (p0 + width - 1) // BLOCK_TOKENS + 1
            table[s, :used] = [next(free) for _ in range(used)]
            pos[s] = p0 + np.arange(width)
        qw = jnp.asarray(rng.normal(size=(SLOTS, width, h, dh)) * 0.5,
                         jnp.bfloat16)
        got = jax.jit(lambda *a: paged_attention_pallas(
            *a, interpret=False))(qw, pool_k, pool_v, table, pos)
        _on_device(got, "paged attention output")
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(paged_oracle.call)(
                *f32(qw, pool_k, pool_v), table, pos)
        out[f"paged_w{width}"] = _rel_err(got, ref)
        assert out[f"paged_w{width}"] <= oracle_tol(
            "paged_attention", "bfloat16"), out
    return out


def _build_gpt(pt, dims, mesh_recipe=False):
    """The flagship GPT (``tune.search.flagship_dims``); with
    ``mesh_recipe`` the PR-10 FSDP recipe in its order."""
    from paddle_tpu.models import transformer

    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        outs = transformer.build(
            vocab_size=dims["vocab"], n_layer=dims["n_layer"],
            n_head=dims["n_head"], d_model=dims["d_model"], max_len=SEQ,
            dropout_rate=0.0, dtype="bfloat16", fused_head=True)
        if mesh_recipe:
            pt.memory_optimize(main, policy="selective")
            pt.gradient_accumulation(main, 2)
            pt.parallel.data_parallel(main, "dp", programs=(startup,))
            pt.parallel.shard_fsdp(main, programs=(startup,))
    return main, startup, outs["avg_cost"]


def _train_steps(exe, main, startup, avg_cost, scope, batch, sharding=None):
    """Startup, warm-up, timed steps on one seeded batch placed with
    ``sharding``.  Returns (losses of every step as floats, ms per timed
    step, compile seconds by executable)."""
    import jax

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, DATA_VOCAB - 1, (batch, SEQ)).astype(np.int32)
    feed = {"tokens": jax.device_put(tokens, sharding),
            "labels": jax.device_put(tokens + 1, sharding)}
    exe.run(startup, scope=scope)
    compile_s = {"startup": exe.last_step_cost["compile_seconds"]}
    losses = []
    for step in range(N_WARM + N_TIMED):
        if step == N_WARM:
            jax.block_until_ready(losses)
            t0 = time.perf_counter()
        (loss,) = exe.run(main, feed=feed, fetch_list=[avg_cost],
                          scope=scope, return_numpy=False)
        losses.append(loss)
    jax.block_until_ready(losses)
    ms = (time.perf_counter() - t0) / N_TIMED * 1e3
    compile_s["train_step"] = exe.last_step_cost["compile_seconds"]
    _on_device(losses[-1], "loss")
    losses = [float(np.asarray(x).reshape(())) for x in losses]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    return losses, ms, compile_s


def train_phase(pt, dims):
    from paddle_tpu import kernels

    main, startup, avg_cost = _build_gpt(pt, dims)
    scope = pt.Scope()
    exe = pt.Executor()
    with kernels.timed_run():
        losses, ms, compile_s = _train_steps(
            exe, main, startup, avg_cost, scope, dims["batch"])
    cost = exe.last_step_cost
    assert cost["kernel_backends"] == {
        "flash_attention": "pallas_tpu", "fused_ce": "pallas_tpu"}, cost
    assert not cost.get("interpret_in_timed_run"), cost
    from paddle_tpu.models import transformer

    params = transformer.extract_params(scope=scope, program=main)
    report = {"loss_first": losses[0], "loss_last": losses[-1],
              "ms_per_step": ms, "compile_seconds": compile_s,
              "compiled_hbm_high_water_bytes": cost["hbm_high_water_bytes"],
              "kernel_backends": cost["kernel_backends"]}
    return report, params


def serve_phase(pt, dims, params):
    eng = pt.serving.ServingEngine(
        params, dims["n_layer"], dims["n_head"], dims["d_model"],
        max_len=MAX_LEN, max_slots=SLOTS, decode_chunk=CHUNK,
        min_bucket=MIN_BUCKET, block_tokens=BLOCK_TOKENS,
        prefix_reuse=True)
    rng = np.random.default_rng(1)
    head = rng.integers(0, DATA_VOCAB, HEAD_LEN)
    prompts = [np.concatenate([head, rng.integers(0, DATA_VOCAB, tail)])
               for tail in (24, 16, 32, 24, 16, 32, 24, 16)[:N_REQUESTS]]
    eng.start()
    t0 = time.perf_counter()
    deadline = t0 + SERVE_TIMEOUT_S
    handles = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    # a hang is a failure, not a wait: every result is bounded, and a
    # timeout raises out of the phase with the engine still running
    # (main() ends the process without joining its thread)
    results = [h.result(timeout=max(1.0, deadline - time.perf_counter()))
               for h in handles]
    wall = time.perf_counter() - t0
    eng.stop()
    for p, r in zip(prompts, results):
        r = np.asarray(r)
        assert r.shape == (len(p) + MAX_NEW,), (r.shape, len(p))
        assert np.array_equal(r[:len(p)], p)
        assert ((r >= 0) & (r < dims["vocab"])).all()
    stats = eng.stats()
    paged = eng.kernel_backends
    # decode and the narrow windows stream blocks through the Mosaic
    # kernel; a prefill window of DENSE_WINDOW rows or more gathers the
    # chain once and attends it densely (the xla_ref spelling)
    from paddle_tpu.kernels.paged_attention import DENSE_WINDOW

    def expected(label):
        wide = (label.startswith("prefill_")
                and int(label.rsplit("_", 1)[1]) >= DENSE_WINDOW)
        return {"paged_attention": "xla_ref" if wide else "pallas_tpu"}

    assert paged and all(sel == expected(label)
                         for label, sel in paged.items()), paged
    assert stats["serving.prefix_hit_rate"] > 0, stats
    assert eng.kv_pool.blocks_in_use == len(eng.prefix_trie), (
        eng.kv_pool.blocks_in_use, len(eng.prefix_trie))
    return {"tokens_served": MAX_NEW * len(results),
            "wall_seconds_incl_compile": wall,
            "compile_seconds": dict(eng.compile_seconds),
            "kernel_backends": paged,
            "prefix_hit_rate": stats["serving.prefix_hit_rate"]}


def mesh_phase(pt, dims, devices):
    """dp2 x fsdp2 over four chips: loss falls and the work is spread."""
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = pt.parallel.make_mesh({"dp": 2, "fsdp": 2}, devices=devices[:4])
    main, startup, avg_cost = _build_gpt(pt, dims, mesh_recipe=True)
    scope = pt.Scope()
    exe = pt.Executor(mesh=mesh)
    losses, ms, compile_s = _train_steps(
        exe, main, startup, avg_cost, scope, dims["batch"],
        sharding=NamedSharding(mesh, PartitionSpec("dp")))
    w = scope.get("block0_ffn1.w")
    shard_devs = {s.device for s in w.addressable_shards}
    assert len(shard_devs) == 4, shard_devs
    assert all(s.data.size < w.size for s in w.addressable_shards), (
        "block0_ffn1.w is replicated, not sharded")
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices[:4]]
    assert min(in_use) > 0 and max(in_use) < 4 * min(in_use), in_use
    return {"mesh": dict(mesh.shape), "loss_first": losses[0],
            "loss_last": losses[-1], "ms_per_step": ms,
            "compile_seconds": compile_s,
            "accum_plan": exe.last_accum_plan,
            "collectives": exe.last_step_cost.get("collective_op_kinds"),
            "weight_shard_shape": list(
                w.addressable_shards[0].data.shape),
            "weight_shape": list(w.shape),
            "bytes_in_use": in_use}


def main():
    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{jax.default_backend()!r} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r})", file=sys.stderr)
        return 2
    import jaxlib
    import libtpu

    # before the first line of output: alone in a directory with nothing
    # else of the repo this raises and standard output stays empty
    import paddle_tpu as pt
    from paddle_tpu import tune
    from paddle_tpu.core import compile_cache

    global _DEVICE
    devices = jax.devices()
    _DEVICE = device = {"platform": devices[0].platform,
                        "kind": devices[0].device_kind,
                        "count": len(devices)}
    print(f"platform: {device['platform']}  device_kind: "
          f"{device['kind']}  devices: {device['count']}  jax "
          f"{jax.__version__}  jaxlib {jaxlib.__version__}  libtpu "
          f"{libtpu.__version__}", flush=True)
    cache_before = compile_cache.entry_count()
    tuned = len(tune.get_cache().entries)
    print(f"compile cache: {compile_cache.cache_dir()}  entries before: "
          f"{cache_before}", flush=True)
    print(f"tune cache: {tune.cache_path()}  entries: {tuned}", flush=True)
    # a tuned schedule from ~/.cache is not a file git would commit
    assert tuned == 0, "chip_smoke must run on the untuned defaults"

    dims = tune.flagship_dims()
    phases = {}
    t0 = time.perf_counter()

    def say(name, report):
        print(f"[smoke figures, not benchmark numbers] {name}: "
              f"{json.dumps(report)}  (+{time.perf_counter() - t0:.0f}s)",
              flush=True)

    report = kernels_phase(dims)
    say("kernels max rel err vs xla_ref", report)
    phases["kernels"] = "ok"

    report, params = train_phase(pt, dims)
    say("train", report)
    phases["train"] = "ok"

    report = serve_phase(pt, dims, params)
    say("serve", report)
    phases["serve"] = "ok"
    del params

    if len(devices) >= 4:
        gc.collect()  # device 0 starts the mesh phase as empty as 1-3
        report = mesh_phase(pt, dims, devices)
        say("mesh", report)
        phases["mesh"] = "ok"
    else:
        phases["mesh"] = f"not run: {len(devices)} device(s)"
        print(f"mesh: {phases['mesh']}", flush=True)

    peak = devices[0].memory_stats()["peak_bytes_in_use"]
    print(f"[smoke figures] peak_bytes_in_use (device 0): {peak}  "
          f"compile cache entries after: {compile_cache.entry_count()} "
          f"(before: {cache_before})  total {time.perf_counter() - t0:.0f}s",
          flush=True)
    print(f"phases: {json.dumps(phases)}", flush=True)
    print(result_line(True, device), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:  # noqa: BLE001 — report, then end the process
        traceback.print_exc()
        if _DEVICE is not None:
            print(result_line(False, _DEVICE))
        sys.stdout.flush()
        sys.stderr.flush()
        # os._exit, not sys.exit: after a failure the serving driver
        # thread may still sit in a device call, and a failed smoke must
        # end, not wait for it
        os._exit(1)
    sys.exit(code)
