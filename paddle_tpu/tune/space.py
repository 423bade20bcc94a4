"""Candidate space and STATIC pruning for the autotune engine.

Two tunable workload kinds:

- ``op="flash"`` — kernel geometry for one attention shape:
  ``block_q`` / ``block_k`` flash tiles, the ``DIAG_W`` strip height of the
  causal diagonal cells, and the packed-vs-4-D head routing.
- ``op="gpt_step"`` — the whole training-step schedule at one sequence
  length: the flash geometry PLUS the remat/offload policy and the
  gradient-accumulation factor (the two capacity levers that decide
  whether t=16k compiles at all — BENCH_r05).

Pruning order (cheapest test first; docs/autotune.md):

1. geometry validity — divisibility, packed availability, VMEM fit of
   the kernel's per-cell working set;
2. roofline sanity via ``causal_flash_flops()`` — candidates scheduling
   far more MXU work than the best candidate's schedule are rejected
   without ever compiling;
3. HBM — the analytic ``estimate_gpt_step_hbm`` bound when a device
   budget is known (rejects OOM-doomed schedules from arithmetic
   alone), then the REAL compiled figure
   (``Executor.compile_only`` -> ``compiled_memory_stats`` ->
   ``analysis.preflight_hbm``) in the search loop before any candidate
   executes a step.

Only survivors are measured.
"""

from ..ops.pallas_attention import (
    causal_flash_flops, packed_sub_heads, _pick_block)

__all__ = [
    "WorkloadKey", "attention_candidates", "schedule_candidates",
    "prune_static",
    "estimate_gpt_step_hbm", "POLICY_ORDER",
]

# remat policies from cheapest recompute to most; "none" = no
# memory_optimize marking at all (XLA keeps every activation)
POLICY_ORDER = ("none", "selective", "offload", "compact", "full")

# per-token-per-layer SAVED activation floats, in units of d_model —
# calibrated against the measured t=16k figures (selective bs8 ~23.5 GB
# sat the 16 GiB chip, RESULTS round 3; accum2-no-remat and bs6
# full-remat both fit under 15.75 GiB while offload at accum=1 did NOT
# — measured on the chip in round 5): none keeps everything XLA can't
# free, selective keeps kernel residuals + MXU outputs (~q/k/v/o/
# att_out/ffn1[4d]/ffn2), offload moves the per-layer block-input
# residuals to pinned host, compact keeps only kernel residuals +
# segment boundaries, full keeps block inputs alone.
_ACT_FLOATS_PER_TOKEN_LAYER = {
    "none": 13.0, "selective": 10.0, "offload": 8.0,
    "compact": 3.0, "full": 1.5,
}

# one layer's LIVE forward/recompute working set (floats per token in
# units of d_model): whatever the saved set, one layer's activations —
# dominated by the two [.., 4d] FFN tensors — exist while it computes
_LIVE_LAYER_FLOATS_PER_TOKEN = 16.0


def _canon_dtype(dtype):
    """Canonical dtype string for the workload key ('bfloat16',
    'float32', ...) from a string, numpy dtype, or Program var dtype."""
    s = getattr(dtype, "name", None) or str(dtype)
    return s.split(".")[-1]


class WorkloadKey:
    """The identity a tuned config is valid for:
    ``(op, seq_len, d_head, n_heads, dtype, platform, remat[, backend])``.
    ``remat`` is the POLICY DIMENSION marker: concrete kernel keys pin
    the policy they were measured under; schedule keys (where the policy
    itself is tuned) use ``"auto"``.  ``backend`` is the kernel-registry
    backend the workload RAN on (docs/kernels.md) — appended as a
    ``|kb=`` token only when known, so pre-registry keys stay stable
    (the tuner treats the backend like the policy: a searchable config
    dimension, with the RESOLVED choice recorded on attribution/corpus
    keys).  ``.s`` is the canonical string the cache files key on."""

    __slots__ = ("op", "seq_len", "d_head", "n_heads", "dtype",
                 "platform", "remat", "backend")

    def __init__(self, op, seq_len, d_head, n_heads, dtype,
                 platform, remat="auto", backend=None):
        self.op = str(op)
        self.seq_len = int(seq_len)
        self.d_head = int(d_head)
        self.n_heads = int(n_heads)
        self.dtype = _canon_dtype(dtype)
        self.platform = str(platform)
        self.remat = str(remat)
        self.backend = None if backend is None else str(backend)

    @property
    def s(self):
        base = (f"op={self.op}|t={self.seq_len}|dh={self.d_head}"
                f"|h={self.n_heads}|dt={self.dtype}|plat={self.platform}"
                f"|remat={self.remat}")
        if self.backend:
            base += f"|kb={self.backend}"
        return base

    def __repr__(self):
        return f"WorkloadKey({self.s})"

    def __eq__(self, other):
        return isinstance(other, WorkloadKey) and self.s == other.s

    def __hash__(self):
        return hash(self.s)


def _block_choices(seq_len, caps=None):
    """Distinct exact block sizes for a sequence length: each cap maps
    through ``_pick_block`` (largest divisor <= cap) so every candidate
    tiles ``t`` exactly, toy shapes included."""
    caps = caps or (256, 512, 1024, 2048)
    return sorted({_pick_block(seq_len, int(c)) for c in caps})


def attention_candidates(seq_len, d_head, n_head, block_caps=None,
                         diag_ws=(128, 256), include_packed=True,
                         backends=None):
    """The flash kernel-geometry candidate list for one shape:
    ``{"block_q", "block_k", "diag_w", "packed"}`` dicts.

    ``backends`` adds the kernel-registry choice as a SEARCHABLE
    dimension (docs/kernels.md): each name in the tuple yields
    candidates carrying ``"backend"``.  Block/diag geometry only means
    anything to the Pallas-schedule backends — ``xla_ref`` (and any
    backend that owns its own tiling) contributes ONE candidate with
    the backend alone, so the cross product never multiplies compiles
    for knobs the backend ignores.  ``None`` (default) keeps the
    pre-registry candidate list: no ``"backend"`` key, resolution left
    to env/auto."""
    packs = [None]
    if include_packed and packed_sub_heads(n_head, d_head) is not None:
        # the packed layout is the measured win (no head transposes) but
        # the 4-D spelling is a legal schedule — let measurement decide
        packs = [True, False]
    geo = []
    for bq in _block_choices(seq_len, block_caps):
        for bk in _block_choices(seq_len, block_caps):
            for w in sorted({_pick_block(min(bq, bk), int(dw))
                             for dw in diag_ws}):
                for p in packs:
                    geo.append({"block_q": bq, "block_k": bk,
                                "diag_w": w, "packed": p})
    if not backends:
        return geo
    out = []
    for b in backends:
        if b == "pallas_tpu":
            out.extend(dict(g, backend=str(b)) for g in geo)
        else:
            # geometry-free backend: one candidate, default blocks so
            # downstream consumers (program build) still have values
            out.append({"block_q": _pick_block(seq_len, 1024),
                        "block_k": _pick_block(seq_len, 1024),
                        "diag_w": None, "packed": None,
                        "backend": str(b)})
    return out


def schedule_candidates(seq_len, d_head, n_head, block_caps=None,
                        policies=POLICY_ORDER, accums=(1, 2),
                        diag_ws=(256,), fsdp_opts=(None,),
                        grad_rs_opts=(None,), backends=None):
    """The step-schedule candidate list: kernel geometry x remat policy
    x gradient-accumulation factor (x FSDP gather-vs-replicate when the
    caller is tuning a mesh with an ``fsdp`` axis: ``fsdp_opts=(False,
    True)`` adds the dimension — TVM-style, the schedule decision stays
    inside the measured search instead of hardcoded; ``None`` entries
    leave the key off the candidate, the single-chip default; x the
    kernel-registry ``backends`` when given — the autotuner picks
    KERNELS, not just block shapes, docs/kernels.md).

    ``grad_rs_opts=(False, True)`` adds the true-ZeRO-3 gradient
    spelling (docs/parallel.md rule 4) as a measured dimension on fsdp
    candidates: reduce-scatter at the boundary cuts boundary comm bytes
    by the fsdp degree but GSPMD pays extra in-loop weight gathers for
    the shard-sized carry, so which spelling wins is geometry- and
    interconnect-dependent — measured, not derived.  Crossed only with
    ``fsdp=True`` candidates (without fsdp sharding there is no shard
    to scatter to; the dimension would measure duplicates)."""
    out = []
    for geo in attention_candidates(seq_len, d_head, n_head,
                                    block_caps=block_caps,
                                    diag_ws=diag_ws,
                                    include_packed=False,
                                    backends=backends):
        for pol in policies:
            for acc in accums:
                for fs in fsdp_opts:
                    for rs in (grad_rs_opts if fs else (None,)):
                        c = dict(geo)
                        c["policy"] = pol
                        c["accum"] = int(acc)
                        if fs is not None:
                            c["fsdp"] = bool(fs)
                        if rs is not None:
                            c["grad_rs"] = bool(rs)
                        out.append(c)
    return out


def _vmem_bytes(cand, d_head, n_head, dtype_size=2):
    """Per-grid-cell VMEM working set of the flash forward: one q block,
    one k block, one v block (packed width = every head in the feature
    dim; the 4-D path's width is one head), plus the f32 acc/m/l
    scratch."""
    width = (n_head * d_head if cand.get("packed") is not False
             and packed_sub_heads(n_head, d_head) is not None
             else d_head)
    bq, bk = cand["block_q"], cand["block_k"]
    blocks = (bq + 2 * bk) * width * dtype_size
    scratch = bq * width * 4 + 2 * bq * 128 * 4  # acc + m/l lanes
    return blocks + scratch


def estimate_gpt_step_hbm(n_layer, d_model, n_head, vocab, seq_len,
                          batch, policy="selective", accum=1,
                          dtype_size=2):
    """Analytic HBM high-water bound (bytes) for one GPT training step —
    the pre-compile prune.  Components: bf16 weights, f32 embedding
    masters, f32 Adam moments, the f32 gradient buffer, and the policy's
    SAVED activation set for one microbatch (plus one layer's recompute
    working set).  Deliberately coarse — calibrated on the measured
    t=16k round-4/5 figures (see ``_ACT_FLOATS_PER_TOKEN_LAYER``) to get
    the ORDERING right; marginal candidates are settled by the real
    compiled figure in the search loop."""
    policy = policy or "none"
    if policy not in _ACT_FLOATS_PER_TOKEN_LAYER:
        raise ValueError(f"unknown policy {policy!r}")
    p_block = 12 * d_model * d_model * n_layer  # qkv+out + 2x(d<->4d)
    p_head = vocab * d_model
    p_embed = vocab * d_model + seq_len * d_model
    params = (p_block + p_head) * dtype_size + p_embed * 4
    n_elems = p_block + p_head + p_embed
    opt_state = n_elems * 8          # two f32 Adam moments
    grads = n_elems * 4              # f32 accumulated gradient
    mb = max(1, batch // max(1, int(accum)))
    saved = (_ACT_FLOATS_PER_TOKEN_LAYER[policy]
             * d_model * n_layer * mb * seq_len * dtype_size)
    # one layer's live recompute/forward working set (whatever the
    # policy, one layer's full activations exist while it runs)
    live_layer = (_LIVE_LAYER_FLOATS_PER_TOKEN
                  * d_model * mb * seq_len * dtype_size)
    est = int(params + opt_state + grads + saved + live_layer)
    # calibrated HBM scale from the learned cost model (measured vs
    # estimated high water over the corpus, tune/costmodel.py).  The
    # scale is clamped >= 1.0 — the bound is a PRUNE, so calibration
    # may only make it more conservative — and is exactly 1.0 when no
    # fitted model is loadable or PADDLE_TPU_COSTMODEL=0 (bit-exact).
    try:
        from .costmodel import hbm_scale_for

        scale = hbm_scale_for()
    except Exception:  # noqa: BLE001 — mid-bootstrap partial import
        scale = 1.0
    if scale != 1.0:
        est = int(est * scale)
    return est


def prune_static(seq_len, d_head, n_head, candidates, dtype_size=2,
                 vmem_budget=12 << 20, roofline_slack=1.20,
                 hbm_budget=None, hbm_model=None):
    """Static pruning pass: returns ``(survivors, pruned)`` where each
    survivor dict gains ``roofline`` (scheduled/useful flop ratio) and
    each pruned entry is ``(candidate, reason)``.

    - VMEM: the kernel's per-cell working set must fit the scoped VMEM
      budget (a too-big block pair fails Mosaic at compile time — or
      worse, compiles and thrashes).
    - Roofline: ``causal_flash_flops`` simulates the kernel's exact
      cell and strip walk; a candidate scheduling more than
      ``roofline_slack`` x the best candidate's scheduled flops cannot
      win on the MXU and is rejected unmeasured.
    - HBM (optional): when ``hbm_budget`` and an ``hbm_model(cand)``
      callable are given, candidates whose analytic bound exceeds the
      budget are rejected — the BENCH_r05 class dies here, from
      arithmetic alone, before any compile."""
    scored, pruned, passthrough = [], [], []
    for c in candidates:
        if c.get("backend") not in (None, "pallas_tpu"):
            # geometry-free backend candidate (xla_ref): the VMEM and
            # block-schedule roofline models describe the Pallas
            # schedules, not XLA's own tiling — only the HBM bound
            # applies; measurement settles the rest
            if hbm_budget and hbm_model is not None:
                est = hbm_model(c)
                if est > hbm_budget:
                    pruned.append(
                        (c, f"hbm estimate {est / (1 << 30):.1f} GiB > "
                            f"budget {hbm_budget / (1 << 30):.1f} GiB"))
                    continue
                c = dict(c, hbm_est_bytes=int(est))
            passthrough.append(c)
            continue
        if seq_len % c["block_q"] or seq_len % c["block_k"]:
            pruned.append((c, "blocks do not tile t"))
            continue
        vm = _vmem_bytes(c, d_head, n_head, dtype_size)
        if vm > vmem_budget:
            pruned.append(
                (c, f"vmem {vm >> 20} MiB > {vmem_budget >> 20} MiB"))
            continue
        sched, useful = causal_flash_flops(
            seq_len, seq_len, d_head, c["block_q"], c["block_k"],
            diag_w=c.get("diag_w"))
        c = dict(c, roofline=round(sched / max(useful, 1), 4))
        scored.append((sched, c))
    if not scored:
        return passthrough, pruned
    best = min(s for s, _ in scored)
    # calibrated roofline: when a fitted cost model is loadable, the
    # slack test compares FITTED schedule costs (ms) instead of raw
    # scheduled flops — prediction is monotonic in flops so candidate
    # ordering is unchanged (tests/test_costmodel.py holds it so); only
    # the ratio moves, because the fitted per-step overhead dilutes
    # small flop deltas.  No model / kill switch -> the flop ratio,
    # exactly as before.
    cm_entry = None
    try:
        from . import costmodel as _cm

        cm_entry = _cm.active_entry()
    except Exception:  # noqa: BLE001 — mid-bootstrap partial import
        cm_entry = None
    if cm_entry is not None:
        cost_of = lambda s: _cm.predict_sched_ms(cm_entry, s)  # noqa: E731
    else:
        cost_of = float
    best_cost = cost_of(best)
    survivors = list(passthrough)
    for sched, c in scored:
        if cost_of(sched) > best_cost * roofline_slack:
            what = ("calibrated roofline" if cm_entry is not None
                    else "roofline")
            pruned.append(
                (c, f"{what}: schedules {sched / best:.2f}x the best "
                    f"candidate's flops"))
            continue
        if hbm_budget and hbm_model is not None:
            est = hbm_model(c)
            if est > hbm_budget:
                pruned.append(
                    (c, f"hbm estimate {est / (1 << 30):.1f} GiB > "
                        f"budget {hbm_budget / (1 << 30):.1f} GiB"))
                continue
            c = dict(c, hbm_est_bytes=int(est))
        survivors.append(c)
    return survivors, pruned
