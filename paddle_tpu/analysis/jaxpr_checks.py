"""Traced-jaxpr-level checks: the post-autodiff step as jax will compile
it — scan structure, kernel calls, checkpoint names, dtypes.  All run
off one shared walk (``ctx.walk``); none executes anything."""

from .framework import register_check
from .jaxpr_tools import BLOCK_INPUT_TAG, KERNEL_RESIDUAL_TAG

# up to one layer's worth of kernel calls (fwd + dq + dkv = 3) may
# legitimately sit outside the layer scan when a policy's segmentation
# leaves the first layer out of the uniform group; the failure mode is
# O(L) unrolled calls (what overflowed round 5's flagship), not O(1)
PALLAS_OUTSIDE_SCAN_TOLERANCE = 3


def _has_remat(program):
    return bool(getattr(program, "_remat_segments", None))


@register_check("jaxpr.scan-locality", level="jaxpr")
def scan_locality(ctx):
    """The scan-locality invariant (migrated from
    ``memaudit.jaxpr_report``): under a ``memory_optimize`` policy every
    flash ``pallas_call`` must sit INSIDE a ``lax.scan`` body, and no
    pallas operand/result may carry a leading layer-count axis — the
    stacked/hoisted form means the per-layer kernel calls escaped the
    loop and their residuals coexist across the whole layer stack."""
    if not _has_remat(ctx.program):
        return []  # no remat policy marked: unrolled kernels are the
        # program's declared (memory-unoptimized) shape, not a defect
    rep = ctx.walk
    findings = []
    if rep["layer_stacked_pallas"]:
        findings.append(ctx.finding(
            "jaxpr.scan-locality", "error", "jaxpr", "pallas_call",
            f"pallas operand/result carries a leading layer-count axis "
            f"{rep['layer_stacked_pallas'][:2]} — per-layer kernel "
            f"calls were stacked/hoisted out of the layer scan (the "
            f"shape that runs a capacity config out of memory)",
            hint="the scan-remat engine must own the layer loop: check "
                 "exe.last_remat_plan for fallbacks and run with "
                 "PADDLE_TPU_SCAN_REMAT=strict to fail loudly",
            data={"layer_stacked": rep["layer_stacked_pallas"][:8]}))
    if (rep["pallas_total"] > 0
            and rep["pallas_outside_scan"]
            > PALLAS_OUTSIDE_SCAN_TOLERANCE):
        findings.append(ctx.finding(
            "jaxpr.scan-locality", "error", "jaxpr", "pallas_call",
            f"{rep['pallas_outside_scan']} of {rep['pallas_total']} "
            f"kernel calls sit outside any scan body — the backward is "
            f"unrolled per layer and its remat temps coexist",
            hint="the uniform layer group fell out of the scan engine "
                 "(PADDLE_TPU_SCAN_REMAT disabled, or classification "
                 "failed — see exe.last_remat_plan for the reason)",
            data={"outside": rep["pallas_outside_scan"],
                  "total": rep["pallas_total"]}))
    return findings


@register_check("jaxpr.kernel-residual", level="jaxpr")
def kernel_residual(ctx):
    """The kernel-residual / offload contract: under
    ``memory_optimize(policy='offload')`` the traced step must carry the
    checkpoint-name tags the name-policy reads (``pt_blk_in`` on the
    per-layer block inputs; ``pt_kernel_res`` inside custom-VJP kernels)
    — a missing tag means the policy silently degraded to plain
    selective and the HBM saving never happens.  Scan-remat fallbacks
    (groups that fell back to the barrier spelling) are surfaced here
    too: a silent fallback at a capacity config is a runtime OOM waiting
    to happen."""
    findings = []
    for g in ctx.remat_plan:
        if "fallback" in g:
            findings.append(ctx.finding(
                "jaxpr.kernel-residual", "warning", "jaxpr",
                f"segment group @ {g.get('start')}",
                f"scan-remat group (period {g.get('period')} x "
                f"{g.get('count')}) fell back to the barrier spelling: "
                f"{g['fallback']}",
                hint="run with PADDLE_TPU_SCAN_REMAT=strict at capacity "
                     "configs so the fallback raises instead of OOMing "
                     "at runtime",
                data=dict(g)))
    program = ctx.program
    if not getattr(program, "_offload", False):
        return findings
    from ..core.executor import _offload_mode

    mode = _offload_mode(program)
    if mode == "off":
        return findings
    rep = ctx.walk
    tags = rep["name_tags"]
    if BLOCK_INPUT_TAG not in tags:
        findings.append(ctx.finding(
            "jaxpr.kernel-residual", "warning", "jaxpr",
            "checkpoint names",
            f"offload policy requested (mode {mode!r}) but no "
            f"{BLOCK_INPUT_TAG!r} tag appears in the traced step — no "
            f"block-input residual will leave device memory (policy "
            f"degraded to selective)",
            hint="offload only engages inside scanned uniform groups; "
                 "check exe.last_remat_plan — a non-uniform program "
                 "cannot offload",
            data={"offload_mode": mode, "tags": sorted(tags)}))
    if rep["pallas_total"] > 0 and KERNEL_RESIDUAL_TAG not in tags:
        findings.append(ctx.finding(
            "jaxpr.kernel-residual", "warning", "jaxpr",
            "checkpoint names",
            f"kernel calls present but no {KERNEL_RESIDUAL_TAG!r} tag — "
            f"a name-policy checkpoint would re-run the kernels in the "
            f"backward instead of keeping their residuals",
            hint="kernels' fwd rules must checkpoint_name their "
                 "residuals (ops/pallas_attention.py contract)",
            data={"tags": sorted(tags)}))
    return findings


@register_check("jaxpr.kernel-backend", level="jaxpr")
def kernel_backend(ctx):
    """Interpret-mode kernels in a TIMED run (docs/kernels.md): inside
    a declared timed-run region (``kernels.timed_run()`` around work
    whose time is reported; PADDLE_TPU_TIMED_RUN=1) any
    ``pallas_call`` with ``interpret=True`` is an error — the Pallas
    interpreter is orders of magnitude slower than both hardware and
    the pure-XLA reference, so the "measurement" is a simulation
    artifact, not a number.  Outside timed regions interpret kernels
    are the DESIRED CPU test path and this check stays silent."""
    from ..kernels import timed_run_active

    if not timed_run_active():
        return []
    rep = ctx.walk
    if not rep["pallas_interpret"]:
        return []
    return [ctx.finding(
        "jaxpr.kernel-backend", "error", "jaxpr", "pallas_call",
        f"{rep['pallas_interpret']} of {rep['pallas_total']} kernel "
        f"calls run in Pallas INTERPRET mode inside a timed-run region "
        f"— interpreted kernels are not a measurement",
        hint="route timed off-TPU runs through the registry's XLA "
             "reference (PADDLE_TPU_KERNEL_BACKEND=xla_ref, or a "
             "per-op PADDLE_TPU_KERNEL_BACKEND_<OP> override) or run "
             "on the hardware the kernel targets",
        data={"interpret": rep["pallas_interpret"],
              "total": rep["pallas_total"]})]


# the blessed accum-carry pin axes: the carry shards its GROUP axis over
# dp and NOTHING else (docs/parallel.md constraint-placement rule 3)
_ACCUM_CARRY_OK_AXES = {"dp"}


@register_check("jaxpr.constraint-placement", level="jaxpr")
def constraint_placement(ctx):
    """The three blessed constraint-placement sites are the ONLY
    ``with_sharding_constraint``s allowed inside scan bodies
    (docs/parallel.md): the two ``_fsdp_fwd_pin`` custom-vjp pins
    (forward-only — a symmetric pin transposes into the backward and
    forces per-layer dW replication: measured 19-49 in-loop all-reduces)
    and the accumulation carry's plain-``dp`` group pin (an
    fsdp-composed carry makes GSPMD feature-shard the saved residuals).
    The Executor marks each blessed site with a ``pt_pin[site]`` named
    scope; this check errors on any in-scan constraint that lacks the
    marker, and on a marked ``accum_carry`` pin whose spec strays off
    the plain-dp contract."""
    from .comm.plan import PIN_SCOPE_RE

    unblessed = {}   # (axes, depth) -> [records]
    bad_carry = {}   # axes -> [records]
    for sc in ctx.walk.get("sharding_constraints", ()):
        if sc["scan_depth"] <= 0:
            continue  # boundary-level constraints are the blessed zone
        m = PIN_SCOPE_RE.search(sc["scope"] or "")
        if m and m.group(1) == "shard":
            # a DECLARED activation annotation (parallel.shard_activation
            # -> pt_shard[var]): not a rogue constraint — its comm cost
            # is policed by hlo.accidental-reshard and the contract
            # checks, which attribute it to the var and can bless it
            # via CommContract.expect(...)
            continue
        site = m.group(2) if m else None
        axes = tuple(sorted(sc.get("axes") or ()))
        if site is None:
            unblessed.setdefault(
                (axes, sc["scan_depth"]), []).append(sc)
        elif site.startswith("accum_carry") and \
                not set(axes) <= _ACCUM_CARRY_OK_AXES:
            bad_carry.setdefault(axes, []).append(sc)
    findings = []
    for (axes, depth), recs in sorted(unblessed.items()):
        findings.append(ctx.finding(
            "jaxpr.constraint-placement", "error", "jaxpr",
            f"scan depth {depth}",
            f"{len(recs)} with_sharding_constraint(s) over axes "
            f"{list(axes) or ['<replicated>']} inside scan bodies are "
            f"not one of the blessed pin sites — a symmetric "
            f"constraint transposes into the backward scan and turns "
            f"per-layer gradients/residuals into in-loop collectives "
            f"(e.g. scope: {recs[0]['scope'] or '<none>'})",
            hint="use the Executor's forward-only pin discipline "
                 "(_fsdp_fwd_pin / the pt_pin[...] sites, "
                 "docs/parallel.md); if this movement is intentional, "
                 "declare it in a CommContract and lift the "
                 "constraint out of the loop body",
            data={"axes": list(axes), "scan_depth": depth,
                  "count": len(recs), "constraints": recs[:4]}))
    for axes, recs in sorted(bad_carry.items()):
        extra = sorted(set(axes) - _ACCUM_CARRY_OK_AXES)
        findings.append(ctx.finding(
            "jaxpr.constraint-placement", "error", "jaxpr",
            "pt_pin[accum_carry]",
            f"{len(recs)} accumulation-carry pin(s) constrained over "
            f"axes {list(axes)} — the blessed spelling keeps the "
            f"carry plain P('dp'); composing {extra} onto it makes "
            f"GSPMD feature-shard the saved residuals (in-loop "
            f"LN/softmax partial sums + all-reduces)",
            hint="keep the carry's pin at P('dp') and let the "
                 "optimizer-boundary pin reshard gradients once, "
                 "outside every loop (docs/parallel.md)",
            data={"axes": list(axes), "count": len(recs),
                  "constraints": recs[:4]}))
    return findings


@register_check("jaxpr.bf16-accum", level="jaxpr")
def bf16_accum(ctx):
    """Reduced-precision accumulation lint: an ``acc = acc + delta``
    scan carry held in bf16/f16, or a ``reduce_sum`` folding thousands
    of bf16 terms into a bf16 result, drops low bits as the running sum
    outgrows the terms — gradients and metrics accumulated this way
    drift silently.  The framework's own accumulators (gradient
    accumulation, Adam moments) carry f32 and never fire this."""
    rep = ctx.walk
    findings = []
    for c in rep["low_precision_carries"]:
        findings.append(ctx.finding(
            "jaxpr.bf16-accum", "warning", "jaxpr",
            f"scan carry {c['carry_index']}",
            f"scan (length {c['scan_length']}) accumulates into a "
            f"{c['dtype']} carry of shape {list(c['shape'])} — "
            f"precision loss grows with the scan length",
            hint="carry the accumulator in float32 and cast once at the "
                 "boundary (the gradient-accumulation engine's own "
                 "spelling)",
            data=c))
    for r in rep["low_precision_reduces"]:
        findings.append(ctx.finding(
            "jaxpr.bf16-accum", "warning", "jaxpr", "reduce_sum",
            f"reduce_sum folds {r['folded_elems']} {r['dtype']} "
            f"elements per output element in {r['dtype']} (operand "
            f"shape {list(r['shape'])})",
            hint="cast to float32 before the reduction (or use an f32 "
                 "preferred_element_type accumulator)",
            data=r))
    return findings


@register_check("jaxpr.tanh-gelu", level="jaxpr")
def tanh_gelu(ctx):
    """The tanh-approximation reassociation hazard: tanh-based
    activations (tanh-gelu above all) inside a scanned remat body are
    not reassociation-stable between unrolled and ``lax.scan`` execution
    on XLA — recompute drifts from the forward at the 1e-3 level, which
    breaks the scan-remat engine's bit-exactness contract (the reason
    PR 3 moved the ``gelu`` op to the exact form)."""
    if not _has_remat(ctx.program):
        return []
    rep = ctx.walk
    if not rep["tanh_in_scan"]:
        return []
    return [ctx.finding(
        "jaxpr.tanh-gelu", "warning", "jaxpr", "scan body",
        f"{rep['tanh_in_scan']} tanh op(s) inside scan bodies of a "
        f"remat-marked program — tanh's backward is not "
        f"reassociation-stable under scan, so recompute can drift from "
        f"the saved forward",
        hint="use this framework's 'gelu' op (the exact x * Phi(x): one "
             "float32 erf for a 16-bit input, erfc for a wider one; no "
             "tanh either way) or keep tanh segments unwrapped (saved, "
             "not rematerialized)",
        data={"tanh_in_scan": rep["tanh_in_scan"]})]
