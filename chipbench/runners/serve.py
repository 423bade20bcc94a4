"""The serving runner: open-loop load on ``ServingEngine``.

The configuration's family module (``chipbench/families/``) makes the
weights on the device from ``--seed`` in one jitted call and builds the
engine with the geometry the traffic file gives (everything else at the
engine's defaults); the runner warms every prefill bucket the mix can
reach and serves the shared heads once, then submits the schedule of
``chipbench/traffic.py`` at its due times from this one thread.  Each
request is timed from when it was due, not from when it was sent.
"""

import gc
import time

import numpy as np

from .. import device, families, traffic
from ..stats import quantile_hd


def _warm_lengths(eng, lengths):
    """{bucket: a length of ``lengths`` that prefills in it}, for every
    prefill bucket the engine would choose for any of ``lengths``."""
    out = {}
    for n in sorted(set(int(x) for x in lengths)):
        out[eng.bucket_for(n)] = n
    return out


def _warm_up(eng, mix, sched, vocab):
    """Compile every executable the window will use and leave the shared
    heads hot in the prefix cache, as a deployment's system prompts are.
    Returns the number of warm-up requests."""
    rng = np.random.default_rng(0)
    spare = iter(sched["spare_first"])
    max_new = 2 * eng.decode_chunk

    def unshared(n):
        p = rng.integers(0, vocab, n, dtype=np.int32)
        p[0] = next(spare)
        return p

    heads = sched["heads"]
    suffixes = [len(p) - (len(heads[h]) if h >= 0 else 0)
                for p, h in zip(sched["prompts"], sched["head"])]
    prompts = [unshared(n) for n in _warm_lengths(eng, suffixes).values()]
    # the heads last, so that they are the most recently used chains
    prompts += [np.concatenate([h, unshared(eng.min_bucket)]) for h in heads]
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new).result(
            timeout=mix["warmup_timeout_s"])
    return len(prompts)


def _check(family, cfg, params, positions, sample, margin):
    """Prompts come back unchanged, and the reference's full forward over
    prompt + output rates every generated token within ``margin`` of its
    own maximum.  Returns (ok, worst margin seen)."""
    worst, ok = 0.0, True
    for h in sample:
        full = h.result(timeout=0)
        n_p = len(h.prompt)
        if not np.array_equal(full[:n_p], h.prompt):
            return False, float("inf")
        padded = np.zeros((1, positions), np.int32)
        padded[0, :len(full)] = full
        lg = np.asarray(family.logits(params, padded, cfg))[0]
        at = lg[n_p - 1:len(full) - 1]
        gap = at.max(axis=-1) - at[np.arange(len(at)), full[n_p:]]
        worst = max(worst, float(gap.max()))
        ok = ok and bool((full[n_p:] < cfg["vocab_size"]).all())
    return ok and worst <= margin, worst


def run(cell, seed, seconds, tracer):
    """One run of a serving cell; see ``chipbench/run.py`` for the shape
    of what comes back."""
    import jax
    from paddle_tpu.observability.metrics import MetricsRegistry

    cfg, mix = cell["config"], cell["traffic"]
    family = families.of(cfg, "serve")
    geometry = dict(mix["engine"])
    positions = geometry["max_len"]
    params = family.make_params(cfg, positions, seed)
    reg = MetricsRegistry()
    eng = family.serving_engine(params, cfg, reg, geometry)
    sched = traffic.serve_schedule(mix, cfg["vocab_size"], seed, seconds)
    n = len(sched["prompts"])

    eng.start()
    n_warm = _warm_up(eng, mix, sched, cfg["vocab_size"])
    # every executable is compiled now: its HBM high-water, as the engine
    # reads it from memory_analysis, before the registry is zeroed
    warm_stats = eng.stats()
    hbm = max([v for k, v in warm_stats.items()
               if k.startswith("serving.hbm_high_water_bytes")] or [0])
    pool_blocks = int(warm_stats["serving.kv_blocks_total"])
    # the program's own call for "the warm pass is over": re-opens the
    # prefix-hit window; the registry's histograms are zeroed with it
    eng.reset_slo_accounting()
    reg.reset(prefix="serving.")
    compiled_before = len(eng.compile_seconds)

    # a traced run profiles the window's last seconds: stopping the
    # profiler holds this thread for many seconds, and by then every
    # request has been submitted
    trace_at = seconds - mix["trace_seconds"]
    handles, due_abs = [], []
    # what the pool holds, as the engine counts it, read 20 times a
    # second from this thread between submissions
    slots_seen, blocks_seen = [], []
    t_start = time.perf_counter()

    def sleep_until(t):
        while True:
            slots_seen.append(reg.value("serving.slots_active"))
            blocks_seen.append(reg.value("serving.blocks_in_use"))
            left = t - time.perf_counter()
            if left <= 0:
                return
            if tracer is not None and not tracer.running:
                if time.perf_counter() - t_start >= trace_at:
                    tracer.start()
            time.sleep(min(left, 0.05))

    for i in range(n):
        due = t_start + float(sched["due"][i])
        sleep_until(due)
        handles.append(eng.submit(sched["prompts"][i],
                                  max_new_tokens=int(sched["max_new"][i])))
        due_abs.append(due)
    sleep_until(t_start + seconds)
    tokens_in_window = sum(len(h.tokens) for h in handles)
    if tracer is not None and tracer.running:
        tracer.stop()
    drain_by = time.perf_counter() + mix["drain_seconds"]
    for h in handles:
        h.wait(timeout=max(0.0, drain_by - time.perf_counter()))
    t_drained = time.perf_counter()
    stats = eng.stats()
    compiled_in_window = len(eng.compile_seconds) - compiled_before
    eng.stop(drain=False)

    done = [h for h in handles if h.done and h.error is None]
    failed = n - len(done)
    ttft = [((h.first_token_t if h.first_token_t is not None else t_drained)
             - d) for h, d in zip(handles, due_abs)]
    tpot = [(h.finish_t - h.first_token_t) / (len(h.tokens) - 1)
            for h in done if len(h.tokens) > 1]
    requests = [{
        "due": d - t_start, "submit": h.submit_t - t_start,
        "admit": None if h.admit_t is None else h.admit_t - t_start,
        "prefill_t0": (None if h.prefill_t0 is None
                       else h.prefill_t0 - t_start),
        "prefill_t1": (None if h.prefill_t1 is None
                       else h.prefill_t1 - t_start),
        "first": (None if h.first_token_t is None
                  else h.first_token_t - t_start),
        "finish": None if h.finish_t is None else h.finish_t - t_start,
        "prompt_len": len(h.prompt), "prefix_hit": int(h.prefix_hit),
        "bucket": h.bucket, "out": len(h.tokens),
    } for h, d in zip(handles, due_abs)]

    peak = device.memory_peak(jax.devices()[:1], hbm)
    allocator = device.memory_peak(jax.devices()[:1])
    compile_s = dict(eng.compile_seconds)
    decode_chunk, eng_slots = eng.decode_chunk, eng.max_slots
    # free the pool before the reference runs beside the weights
    del eng
    gc.collect()  # the engine and its scheduler refer to each other
    check = mix["check"]
    rng = np.random.default_rng(traffic.seed_words(seed, 4))
    sample = ([done[i] for i in rng.choice(
        len(done), min(check["sample"], len(done)), replace=False)])
    ok, worst = _check(family, cfg, params, positions, sample,
                       check["logit_margin"])
    correct = (ok and bool(done) and compiled_in_window == 0)
    return {
        "correct": bool(correct), "attempted": n, "failed": failed,
        "window_start": t_start,
        "end_to_end": {
            "ttft_p90_ms": quantile_hd(ttft, 0.9) * 1e3,
            "tpot_p90_ms": (quantile_hd(tpot, 0.9) * 1e3
                            if tpot else None),
            "serve_tokens_per_s": tokens_in_window / seconds,
        },
        "memory_peak_bytes": peak,
        "compared": [("worst_logit_margin", worst, check["logit_margin"]),
                     ("compiled_in_window", compiled_in_window, 0)],
        "facts": {
            "runner": "serve", "requests": requests, "seconds": seconds,
            "stats": stats, "compile_seconds": compile_s,
            "compiled_in_window": compiled_in_window,
            "warmup_requests": n_warm, "decode_chunk": decode_chunk,
            "worst_logit_margin": worst,
            "tokens_in_window": tokens_in_window,
            "compiled_high_water_bytes": int(hbm),
            "allocator_peak_bytes": allocator,
            "drain_s": t_drained - (t_start + seconds),
            "max_slots": eng_slots, "pool_blocks": pool_blocks,
            "slots_active_mean": float(np.mean(slots_seen)),
            "slots_active_max": float(max(slots_seen)),
            "blocks_in_use_mean": float(np.mean(blocks_seen)),
            "blocks_in_use_max": float(max(blocks_seen)),
            "slots_active_seen": slots_seen, "blocks_in_use_seen": blocks_seen,
        },
    }
