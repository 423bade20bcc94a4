"""Compiled-HLO-level checks: what XLA actually scheduled — cross-chip
collectives and their loop membership, buffer donation, and the static
HBM high-water.  These consume artifacts the AOT compile path already
produces (``memory_analysis()``, optimized HLO text), so the Executor
folds them into every compile for free."""

from .framework import preflight_hbm, register_check

# donation smaller than this is noise (tiny test programs, scalar
# state); the audit targets parameter-scale buffers
DONATION_MIN_BYTES = 1 << 20


@register_check("hlo.inloop-collective", level="hlo")
def inloop_collective(ctx):
    """The comm-aware accumulation invariant (migrated from
    ``memaudit.hlo_comm_report``): a REDUCE collective (all-reduce /
    reduce-scatter) inside a while body executes once per loop iteration
    — the per-microbatch gradient reduction of a naive accumulation loop
    — instead of once per optimizer step at the boundary.  Gather-class
    collectives in the loop are EXPECTED structure, reported as info
    only: FSDP all-gathers each layer's weight shard inside the
    scan-remat body by design (docs/parallel.md), and attention-internal
    gathers are routine; both overlap with compute under the
    latency-hiding flags (``PADDLE_TPU_COMM_OVERLAP``).  The gradient
    reduce-scatter/all-reduce must stay once per optimizer step — the
    error branch."""
    comm = ctx.comm
    if not comm or not comm.get("collective_count"):
        return []
    findings = []
    rin = comm.get("reduce_ops_in_loop", 0)
    if rin and not ctx.in_loop_expected:
        findings.append(ctx.finding(
            "hlo.inloop-collective", "error", "hlo", "while body",
            f"{rin} reduce collective(s) "
            f"({comm.get('reduce_bytes_in_loop', 0)} bytes) execute "
            f"INSIDE a loop body — gradients are cross-chip-reduced "
            f"once per iteration instead of once per optimizer step",
            hint="use the comm-aware accumulation spelling (dp-sharded "
                 "feeds + PADDLE_TPU_LOCAL_ACCUM=1); check "
                 "exe.last_accum_plan for the fallback reason",
            data={"reduce_ops_in_loop": rin,
                  "reduce_bytes_in_loop":
                      comm.get("reduce_bytes_in_loop", 0)}))
    # reduce-class ops are the error branch's business (or expected in a
    # fused run_steps loop); only the gather-class remainder is info
    gathers_in = comm.get("collectives_in_loop", 0) - rin
    if gathers_in > 0:
        findings.append(ctx.finding(
            "hlo.inloop-collective", "info", "hlo", "while body",
            f"{gathers_in} gather-class collective(s) inside loop "
            f"bodies ({comm.get('collective_bytes_in_loop', 0)} total "
            f"in-loop bytes) — expected structure (FSDP per-layer "
            f"weight gathers, attention-internal movement), not gated; "
            f"overlappable via PADDLE_TPU_COMM_OVERLAP",
            data=dict(
                {k: comm.get(k) for k in (
                    "collectives_in_loop", "collective_bytes_in_loop",
                    "collective_ops")},
                gather_ops_in_loop=gathers_in)))
    return findings


def donation_findings(memstats, donate, min_bytes=DONATION_MIN_BYTES):
    """Pure donation audit over flattened memory stats: donation was
    requested for parameter-scale state but XLA aliased NOTHING — every
    parameter exists twice (input + output buffer), which silently
    doubles state HBM.  Returns a list of Findings."""
    from .framework import Finding

    if not donate or not memstats:
        return []
    arg = memstats.get("argument_bytes") or 0
    alias = memstats.get("alias_bytes")
    if alias is None or arg < min_bytes:
        return []
    if alias > 0:
        return []
    return [Finding(
        "hlo.donation-alias", "warning", "hlo", "input_output_alias",
        f"state donation requested but the executable aliases 0 of "
        f"{arg} argument bytes — donated buffers were all copied, "
        f"doubling parameter/optimizer-state HBM",
        hint="donated inputs alias only when dtype/shape/layout match "
             "the corresponding output exactly; check for dtype-changing "
             "parameter updates (and jax donation warnings)",
        data={"argument_bytes": int(arg), "alias_bytes": 0})]


@register_check("hlo.donation-alias", level="hlo")
def donation_alias(ctx):
    """Donated-buffer aliasing audit: the Executor donates the state
    pytree (in-place parameter updates at the XLA level); if the
    compiled module's alias table is empty the donation silently failed
    and peak memory carries two copies of the state."""
    return donation_findings(ctx.memstats, ctx.donate)


@register_check("hlo.hbm-preflight", level="hlo")
def hbm_preflight(ctx):
    """The static HBM preflight: the compiled step's own
    ``hbm_high_water_bytes`` against the device's allocator limit (or an
    explicit ``hbm_budget``) — an allocator failure flagged before
    any step executes.  Skipped when neither figure is known (CPU
    reports no bytes_limit)."""
    budget = ctx.hbm_budget
    if budget is None:
        from ..observability.hardware import device_hbm_bytes

        try:
            budget = device_hbm_bytes()
        except Exception:
            budget = None
    if not budget:
        return []
    high = (ctx.memstats or {}).get("hbm_high_water_bytes")
    return preflight_hbm(high, budget, context="compiled step")
