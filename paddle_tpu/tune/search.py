"""The measured-feedback search loop (TVM-style schedule search with a
benchmark in the loop — PAPERS.md) and the flagship t=16k entry points.

``tune_gpt_step`` is the searchable workload: given a GPT training-step
shape it generates the schedule candidate space
(``space.schedule_candidates``), prunes statically (roofline + analytic
HBM bound), then for each survivor builds the Program, AOT-compiles it
through the production path (``Executor.compile_only`` ->
``lower().compile()``), runs the REAL HBM preflight on the compiled
figures (``analysis.preflight_hbm`` — an OOM-doomed candidate is
rejected from cost analysis alone, before any step executes), and times
the survivors median-of-k.  The winner persists in the on-disk cache
(``tune.cache``) under its workload key plus a companion ``op=flash``
entry so the hot-path attention lookup picks the same geometry.

Every measured candidate emits a ``tune.search`` span (category
``tune``) so a search session reads as a timeline in the Chrome trace;
``tune.searches`` / ``tune.candidates_measured`` /
``tune.pruned_static`` / ``tune.pruned_preflight`` count in the metrics
registry.
"""

import contextlib
import os
import time

import numpy as np

from ..observability import metrics as _obs
from ..observability import trace as _trace
from .cache import get_cache
from .space import (
    POLICY_ORDER, WorkloadKey, estimate_gpt_step_hbm, prune_static,
    schedule_candidates)

__all__ = ["tune_gpt_step", "flagship_static_demo", "flagship_dims",
           "PreflightRejected"]


class PreflightRejected(Exception):
    """A candidate whose COMPILED memory figures exceed the device
    budget — rejected after compile, before any step ran."""


@contextlib.contextmanager
def _diag_w(width):
    """Temporarily pin the causal diagonal strip height while a
    candidate compiles (the kernels read ``pallas_attention.DIAG_W`` at
    trace time; the search is single-threaded).  A PADDLE_TPU_DIAG_W
    env pin wins — candidates then all run at the pinned width."""
    from ..ops import pallas_attention as pa

    if not width or int(width) == pa.DIAG_W or pa._DIAG_W_ENV:
        yield
        return
    old = pa.DIAG_W
    pa.DIAG_W = int(width)
    try:
        yield
    finally:
        pa.DIAG_W = old


@contextlib.contextmanager
def _zero3_rs_env(value):
    """Temporarily pin PADDLE_TPU_ZERO3_RS while a candidate compiles
    (``parallel.api.grad_rs_spec_for`` reads it at trace time; the
    search is single-threaded).  Restores the caller's setting —
    including absence — on exit."""
    old = os.environ.get("PADDLE_TPU_ZERO3_RS")
    os.environ["PADDLE_TPU_ZERO3_RS"] = str(value)
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("PADDLE_TPU_ZERO3_RS", None)
        else:
            os.environ["PADDLE_TPU_ZERO3_RS"] = old


def flagship_dims():
    """The GPT flagship model dims (the BENCH_GPT_* envs win) —
    the ONE env-default table ``chip_smoke.py`` and the tune entry points
    share, so the searched workload key and the flagship run's lookup
    always agree."""
    return {
        "n_layer": int(os.environ.get("BENCH_GPT_LAYERS", "12")),
        "d_model": int(os.environ.get("BENCH_GPT_DMODEL", "768")),
        "n_head": int(os.environ.get("BENCH_GPT_HEADS", "6")),
        "vocab": int(os.environ.get("BENCH_GPT_VOCAB", "32768")),
        "batch": int(os.environ.get("BENCH_GPT_BATCH", "8")),
    }


def _measure_candidate(cand, *, seq_len, n_layer, d_model, n_head, vocab,
                       batch, dtype, fused_head, steps, warmup, repeats,
                       budget_bytes, learning_rate):
    """Build + AOT-compile + HBM-preflight + time ONE candidate.
    Returns ``(median_seconds, cost_dict)``; raises
    :class:`PreflightRejected` when the compiled high-water exceeds the
    budget (nothing was executed)."""
    import paddle_tpu as pt
    from paddle_tpu.analysis import preflight_hbm
    from paddle_tpu.models import transformer

    import contextlib

    from ..kernels import forced_backend

    pt.core.unique_name.reset()
    main_prog, startup = pt.Program(), pt.Program()
    main_prog.random_seed = 11
    with pt.program_guard(main_prog, startup):
        outs = transformer.build(
            vocab_size=vocab, n_layer=n_layer, n_head=n_head,
            d_model=d_model, max_len=seq_len, dropout_rate=0.0,
            dtype=dtype, fused_head=fused_head,
            learning_rate=learning_rate,
            attn_block_q=cand["block_q"], attn_block_k=cand["block_k"],
            attn_packed=cand.get("packed"))
        accum = int(cand.get("accum", 1) or 1)
        if accum > 1:
            pt.gradient_accumulation(main_prog, accum)
        policy = cand.get("policy")
        if policy and policy != "none":
            pt.memory_optimize(main_prog, policy=policy)
    if "fsdp" in cand:
        # the gather-vs-replicate schedule dimension: the executor's
        # scan body honors program._fsdp, so a replicate candidate is
        # measured truly replicated (meaningful only when the measuring
        # executor is mesh-bound with an fsdp axis — the single-chip
        # search times both spellings identically but still persists
        # the winner's choice for memory_optimize(policy="auto"))
        main_prog._fsdp = bool(cand["fsdp"])
    # the true-ZeRO-3 gradient-spelling dimension (docs/parallel.md
    # rule 4): grad_rs_spec_for reads PADDLE_TPU_ZERO3_RS at trace
    # time, so the override wraps the whole compile/measure phase —
    # like fsdp, a single-chip search times both spellings identically
    # but the winner's choice still persists for a mesh-bound consumer
    rs_ctx = (_zero3_rs_env("1" if cand["grad_rs"] else "0")
              if "grad_rs" in cand else contextlib.nullcontext())
    rng = np.random.default_rng(17)
    toks = rng.integers(0, vocab, (batch, seq_len)).astype(np.int64)
    feed = {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}
    scope = pt.core.scope.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe = pt.Executor()
        # the candidate's kernel-registry backend (docs/kernels.md):
        # forced for the whole compile/measure phase — kernel
        # resolution happens at TRACE time inside these runs (program
        # BUILD resolves nothing), so one context around them routes
        # every op of the step (flash AND the CE head) to the backend
        # being measured; an op the backend cannot serve falls back to
        # auto, exactly what the shipped configuration would do
        backend_ctx = (forced_backend(cand["backend"])
                       if cand.get("backend")
                       else contextlib.nullcontext())
        with backend_ctx, rs_ctx:
            exe.run(startup, scope=scope)
            with _diag_w(cand.get("diag_w")):
                cost = exe.compile_only(main_prog, feed=feed,
                                        fetch_list=[outs["avg_cost"]],
                                        scope=scope)
                findings = preflight_hbm(cost.get("hbm_high_water_bytes"),
                                         budget_bytes,
                                         context=f"candidate {cand}")
                if findings:
                    raise PreflightRejected(findings[0].message)
                run = lambda: exe.run(main_prog, feed=feed,
                                      fetch_list=[outs["avg_cost"]],
                                      scope=scope, return_numpy=False)
                for _ in range(max(0, warmup)):
                    run()
                times = []
                for _ in range(max(1, repeats)):
                    t0 = time.perf_counter()
                    out = None
                    for _ in range(max(1, steps)):
                        out = run()
                    np.asarray(out[0])  # host materialization = honest stop
                    times.append(time.perf_counter() - t0)
    finally:
        pt.core.scope._scope_stack.pop()
    return float(np.median(times)), cost


def _truncate_survivors(survivors, max_measure, report):
    """Cap the measured-candidate list at ``max_measure`` WITHOUT
    silently dropping a whole kernel backend: geometry-free backend
    candidates carry no roofline score, so a plain head-slice of the
    sorted list would cut e.g. the only xla_ref candidate and the
    "tuner picks kernels" dimension would degenerate to the pre-registry
    search with no trace.  The head keeps the statically best schedules;
    one best-ranked candidate per otherwise-dropped backend rides along
    (the budget stretches by at most the number of requested
    backends)."""
    if not max_measure or len(survivors) <= max_measure:
        return survivors
    keep = survivors[:max_measure]
    kept_backends = {c.get("backend") for c in keep}
    for c in survivors[max_measure:]:
        b = c.get("backend")
        if b is not None and b not in kept_backends:
            keep.append(c)
            kept_backends.add(b)
    report["truncated_to"] = len(keep)
    return keep


def tune_gpt_step(seq_len, n_layer, d_model, n_head, vocab, batch,
                  dtype="bfloat16", fused_head=True, steps=2, warmup=1,
                  repeats=3, budget_bytes=None, block_caps=None,
                  policies=POLICY_ORDER, accums=(1,), diag_ws=(256,),
                  fsdp_opts=(None,), grad_rs_opts=(None,),
                  backends=None, max_measure=8,
                  learning_rate=1e-3, force=False, mode=None):
    """Search (or serve from cache) the step schedule for one GPT shape.

    Returns a report dict: ``entry`` (the winning cache entry or None),
    ``source`` ("cache" | "search" | "miss"), candidate/prune counters,
    and the per-candidate ``measured`` list.  In mode "cached" (the hot
    path default) this NEVER compiles — a miss returns ``entry=None``
    and callers keep today's defaults.  Mode "search" measures on miss
    (or always, with ``force=True``) and persists the winner."""
    from . import tune_mode  # late: __init__ imports this module

    reg = _obs.get_registry()
    import jax

    key = WorkloadKey("gpt_step", seq_len, d_model // n_head, n_head,
                      dtype, jax.default_backend(), remat="auto")
    mode = mode or tune_mode()  # explicit callers (bench) may override
    report = {"key": key.s, "mode": mode, "entry": None, "source": "miss",
              "candidates": 0, "pruned_static": 0, "pruned_preflight": 0,
              "measured": []}
    if mode == "off":
        report["source"] = "off"
        return report
    cache = get_cache()
    hit = cache.get(key.s)
    if hit is not None and not force:
        reg.counter("tune.cache_hits",
                    help="tuned-config cache lookups served").inc()
        report.update(entry=hit, source="cache")
        return report
    reg.counter("tune.cache_misses",
                help="tuned-config cache lookups missed").inc()
    if mode != "search":
        return report

    reg.counter("tune.searches",
                help="measured schedule searches executed").inc()
    from ..ops import pallas_attention as pa

    if pa._DIAG_W_ENV:
        # env-pinned strip height: every candidate runs (and is
        # labeled) at the pin — anything else would cache a config
        # measured at a width it does not record
        diag_ws = (pa._DIAG_W_ENV,)
    accums = tuple(a for a in accums if batch % a == 0)
    cands = schedule_candidates(seq_len, d_model // n_head, n_head,
                                block_caps=block_caps, policies=policies,
                                accums=accums or (1,), diag_ws=diag_ws,
                                fsdp_opts=fsdp_opts,
                                grad_rs_opts=grad_rs_opts,
                                backends=backends)
    report["candidates"] = len(cands)
    hbm_model = lambda c: estimate_gpt_step_hbm(
        n_layer, d_model, n_head, vocab, seq_len, batch,
        policy=c.get("policy"), accum=c.get("accum", 1))
    survivors, pruned = prune_static(
        seq_len, d_model // n_head, n_head, cands,
        hbm_budget=budget_bytes, hbm_model=hbm_model)
    report["pruned_static"] = len(pruned)
    if pruned:
        reg.counter(
            "tune.pruned_static",
            help="candidates rejected by static pruning (roofline/vmem/"
                 "analytic hbm) without compiling").inc(len(pruned))
        report["pruned_static_reasons"] = [
            (dict(c), r) for c, r in pruned[:8]]
    # cheapest-recompute-policy-first, then roofline: when the measure
    # budget truncates the list, the statically best schedules survive
    survivors.sort(key=lambda c: (
        POLICY_ORDER.index(c.get("policy") or "none"),
        c.get("accum", 1), c.get("roofline", 9.9), -c["block_q"]))
    survivors = _truncate_survivors(survivors, max_measure, report)

    tracer = _trace.get_tracer()
    measured = []
    for i, cand in enumerate(survivors):
        with tracer.span("tune.search", cat="tune", key=key.s,
                         candidate=i, **{k: v for k, v in cand.items()
                                         if k != "hbm_est_bytes"}) as sp:
            try:
                median_s, cost = _measure_candidate(
                    cand, seq_len=seq_len, n_layer=n_layer,
                    d_model=d_model, n_head=n_head, vocab=vocab,
                    batch=batch, dtype=dtype, fused_head=fused_head,
                    steps=steps, warmup=warmup, repeats=repeats,
                    budget_bytes=budget_bytes,
                    learning_rate=learning_rate)
            except PreflightRejected as e:
                reg.counter(
                    "tune.pruned_preflight",
                    help="compiled candidates rejected by the HBM "
                         "preflight before any step executed").inc()
                report["pruned_preflight"] += 1
                measured.append(dict(cand, verdict="preflight_rejected",
                                     reason=str(e)[:200]))
                sp.set(verdict="preflight_rejected")
                continue
            reg.counter("tune.candidates_measured",
                        help="schedule candidates compiled and timed").inc()
            tok_s = batch * seq_len * max(1, steps) / median_s
            rec = dict(cand, verdict="measured",
                       median_s=round(median_s, 6),
                       tok_s=round(tok_s, 1),
                       flops=cost.get("flops"),
                       bytes_accessed=cost.get("bytes_accessed"),
                       hbm_high_water_bytes=cost.get(
                           "hbm_high_water_bytes"),
                       temp_bytes=cost.get("temp_bytes"),
                       compile_seconds=round(
                           cost.get("compile_seconds") or 0.0, 3))
            # persist the backend that ACTUALLY ran, not the request:
            # forced_backend is non-strict, so an unavailable backend
            # candidate measures the auto fallback — recording the
            # requested name would cache a kernel choice that never
            # executed (the "keyed by which kernel ran" contract,
            # docs/kernels.md)
            kb = (cost.get("kernel_backends") or {}).get(
                "flash_attention")
            if cand.get("backend") and kb and kb != cand["backend"]:
                rec["backend"] = kb
                rec["backend_requested"] = cand["backend"]
            measured.append(rec)
            sp.set(verdict="measured", median_s=rec["median_s"])
    report["measured"] = measured
    timed = [m for m in measured if m["verdict"] == "measured"]
    if not timed:
        report["source"] = "exhausted"
        return report
    win = min(timed, key=lambda m: m["median_s"])
    config = {k: win[k] for k in ("block_q", "block_k", "diag_w",
                                  "packed", "policy", "accum", "fsdp",
                                  "grad_rs", "backend")
              if k in win and win[k] is not None}
    meas = {k: win[k] for k in ("median_s", "tok_s", "flops",
                                "bytes_accessed", "hbm_high_water_bytes",
                                # the analytic HBM bound the candidate
                                # was admitted under (prune_static):
                                # paired with the compiled high water
                                # above it is one hbm_scale calibration
                                # point for the learned cost model
                                "hbm_est_bytes",
                                "temp_bytes") if win.get(k) is not None}
    meas["worst_median_s"] = max(m["median_s"] for m in timed)
    meas["measured_candidates"] = len(timed)
    entry = cache.put(key.s, config, measured=meas)
    # companion kernel-geometry entry: the hot-path attention lookup
    # (layers.multi_head_attention) keys on the shape alone — it runs at
    # program BUILD time, before any remat policy is chosen
    flash_key = WorkloadKey("flash", seq_len, d_model // n_head, n_head,
                            dtype, key.platform, remat="-")
    cache.put(flash_key.s,
              {k: config[k] for k in ("block_q", "block_k", "diag_w",
                                      "packed", "backend")
               if k in config},
              measured={"from": key.s})
    cache.save()
    tracer.instant("tune.winner", cat="tune", key=key.s, **config)
    report.update(entry=entry, source="search")
    return report


def flagship_static_demo(seq_len=16384, budget_bytes=None, batch=None):
    """The OFF-ACCELERATOR t=16k demonstration: statically prune the
    flagship schedule space against the chip budget and report which
    configs die and which survives — ``gpt_t16k_*`` keys for the bench
    row.  No compile, no measurement (a t=16k XLA compile is not a CPU
    smoke-path citizen): every figure is the analytic bound, labeled as
    an estimate.  The point on record: the BENCH_r05 config (offload at
    accum=1, default 1024 blocks) is REJECTED by the HBM prune, and a
    compilable capacity schedule (gradient accumulation + a
    lighter-recompute policy, with >=15% HBM headroom against allocator
    fragmentation) is selected instead — the same pruning the on-TPU
    search applies to real compiled figures before measuring."""
    dims = flagship_dims()
    if batch is not None:
        dims["batch"] = int(batch)
    # the t=16k capacity rounds run global batch 6 (bench memory_gate)
    elif seq_len >= 16384:
        dims["batch"] = 6
    if budget_bytes is None:
        budget_bytes = int(float(os.environ.get(
            "BENCH_HBM_BUDGET_GIB", "15.75")) * (1 << 30))
    d_head = dims["d_model"] // dims["n_head"]
    cands = schedule_candidates(
        seq_len, d_head, dims["n_head"], block_caps=(256, 512, 1024),
        policies=POLICY_ORDER, accums=(1, 2), diag_ws=(256,))
    hbm_model = lambda c: estimate_gpt_step_hbm(
        dims["n_layer"], dims["d_model"], dims["n_head"], dims["vocab"],
        seq_len, dims["batch"], policy=c.get("policy"),
        accum=c.get("accum", 1))
    survivors, pruned = prune_static(
        seq_len, d_head, dims["n_head"], cands,
        hbm_budget=budget_bytes, hbm_model=hbm_model)
    out = {
        "gpt_t16k_candidates": len(cands),
        "gpt_t16k_pruned_static": len(pruned),
        "gpt_t16k_survivors": len(survivors),
        "gpt_t16k_static_only": True,
        "gpt_t16k_budget_gib": round(budget_bytes / (1 << 30), 2),
    }
    # the BENCH_r05 configuration must be among the rejected
    r05 = [(c, r) for c, r in pruned
           if c.get("policy") == "offload" and c.get("accum", 1) == 1
           and c["block_q"] == 1024]
    if r05:
        out["gpt_t16k_rejected_r05_config"] = (
            f"offload accum=1 blocks=1024: {r05[0][1]}")
    if survivors:
        survivors.sort(key=lambda c: (
            POLICY_ORDER.index(c.get("policy") or "none"),
            c.get("accum", 1), c.get("roofline", 9.9), -c["block_q"]))
        # a capacity shape needs allocator headroom: a static estimate
        # at 90% of the budget is an OOM coin-flip once XLA fragments —
        # prefer the cheapest-recompute schedule with >= 15% margin
        room = [c for c in survivors
                if c.get("hbm_est_bytes", 0) <= 0.85 * budget_bytes]
        sel = (room or survivors)[0]
        out.update({
            "gpt_t16k_selected_policy": sel.get("policy"),
            "gpt_t16k_selected_accum": sel.get("accum", 1),
            "gpt_t16k_selected_block_q": sel["block_q"],
            "gpt_t16k_selected_block_k": sel["block_k"],
            "gpt_t16k_selected_est_hbm_gib": round(
                sel.get("hbm_est_bytes", 0) / (1 << 30), 2),
        })
    return out
