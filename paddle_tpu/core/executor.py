"""Executor — lowers a Program to one jitted pure function and runs it.

The reference Executor (``paddle/framework/executor.cc:79``) is a sequential
per-op interpreter: every step it re-creates operators, re-runs InferShape,
picks kernels and enqueues them one by one.  That design is wrong for TPU:
XLA wants the *whole* step as a single traced computation so it can fuse
elementwise chains into matmuls, overlap transfers, and tile onto the MXU.

So this Executor walks the block ONCE (at compile time), calling each op's
pure-JAX implementation to build a function

    step(state, *feed) -> (state', fetches)

where ``state`` is the dict of persistable arrays (parameters, optimizer
moments, BN stats, metric accumulators, RNG key) and jits it with donated
state buffers (in-place parameter updates at the XLA level).  Autodiff: if
``append_backward`` marked the block, the forward prefix is differentiated
with ``jax.grad`` and ``<param>@GRAD`` values are injected into the
environment before the remaining (optimizer) ops run — the functional analog
of the reference's MakeBlockBackward-generated gradient ops
(``backward.cc:415``).

Compiled steps are cached keyed on (program identity+version, feed signature,
fetch list, available state) — the analog of the reference caching nothing
and paying interpreter overhead per op per step.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..observability import metrics as _obs
from ..observability import trace as _trace
from ..analysis.jaxpr_tools import BLOCK_INPUT_TAG, KERNEL_RESIDUAL_TAG
from .program import Program, Parameter, default_main_program, GRAD_SUFFIX
from .registry import get_op_impl
from .scope import Scope, global_scope, GRAD_NORM_VAR, RNG_VAR
from .place import CPUPlace, TPUPlace

_pinned_host_cache = []


def _pinned_host_available():
    """True when device 0 exposes a ``pinned_host`` memory space (TPU/GPU
    with memories enabled) — the offload policy's transfer target.  A
    positive/negative ANSWER is cached per process; a transient probe
    failure (backend not yet initialized) is NOT cached, so a later call
    can still discover the memory space instead of silently pinning the
    process to the degraded "save" mode."""
    if not _pinned_host_cache:
        try:
            mems = jax.devices()[0].addressable_memories()
        except Exception:
            return False  # transient: do not cache
        _pinned_host_cache.append(
            any(m.kind == "pinned_host" for m in mems))
    return _pinned_host_cache[0]


def _offload_mode(program):
    """How the scan body should run an offload-marked program:
    ``"host"`` — stream block inputs to pinned host memory; ``"save"`` —
    same name-policy checkpoint structure with block inputs left in
    device memory (backends without a pinned_host space, e.g. CPU —
    keeps the structure testable off-accelerator); ``"off"`` — not an
    offload program, or killed via ``PADDLE_TPU_OFFLOAD=0`` (falls back
    to plain selective execution)."""
    if not getattr(program, "_offload", False):
        return "off"
    if os.environ.get("PADDLE_TPU_OFFLOAD", "1").lower() in (
            "0", "", "false"):
        return "off"
    return "host" if _pinned_host_available() else "save"


def _offload_ckpt_policy(mode):
    """The name-based checkpoint policy for a wrapped sub-segment under
    the offload policy: kernel residuals (should a kernel ever land
    inside a wrapped segment) stay in device memory; block inputs are
    offloaded (mode "host") or saved in place (mode "save"); everything
    untagged rematerializes, exactly like a default ``jax.checkpoint``."""
    cp = jax.checkpoint_policies
    if mode == "host":
        return cp.save_and_offload_only_these_names(
            names_which_can_be_saved=[KERNEL_RESIDUAL_TAG],
            names_which_can_be_offloaded=[BLOCK_INPUT_TAG],
            offload_src="device", offload_dst="pinned_host")
    return cp.save_only_these_names(
        KERNEL_RESIDUAL_TAG, BLOCK_INPUT_TAG)


def _grad_norm_enabled():
    """Training-dynamics telemetry kill switch: ``PADDLE_TPU_GRADNORM=0``
    drops the global grad-norm output from the step entirely (the scope
    never grows the ``@GRAD_NORM@`` entry and the compiled step is
    byte-identical to the pre-telemetry spelling)."""
    return os.environ.get("PADDLE_TPU_GRADNORM", "1").lower() not in (
        "0", "", "false", "off", "no")


def _emits_grad_norm(program):
    """True when the step function for ``program`` will emit the
    ``@GRAD_NORM@`` state entry: a marked backward exists and the kill
    switch is on.  ``_prepare`` (carry structure), ``compile_shardings``
    (pytree match) and ``lower`` (the emission itself) must all agree —
    this predicate is the single source of that decision."""
    if not _grad_norm_enabled():
        return False
    block = program.global_block()
    return (block.backward_index is not None
            and program._backward_info.get(0) is not None)


def _scan_strict():
    """PADDLE_TPU_SCAN_REMAT=strict: a uniform group that fails to scan
    RAISES (with the classification error) instead of silently falling
    back to the barrier spelling — the guard for capacity configs where
    an unrolled backward means a runtime HBM OOM (round 5's flagship)."""
    return os.environ.get("PADDLE_TPU_SCAN_REMAT", "").lower() == "strict"


def _tag_named(v, tag):
    """checkpoint_name for inexact arrays; anything else passes through
    (names on integer/key values are pointless and some backends reject
    them)."""
    try:
        if jnp.issubdtype(jnp.result_type(v), jnp.inexact):
            return checkpoint_name(v, tag)
    except TypeError:
        pass
    return v


def _fsdp_fwd_pin(sharding, site="fsdp"):
    """Forward-only sharding constraint: the primal is pinned to
    ``sharding``, the cotangent passes through UNPINNED.  Both FSDP
    pins use it — the at-rest stack pin (``P(None, *spec)``: at-rest
    bytes divide by the fsdp degree) and the in-body per-layer gather
    (the fsdp-free spec: GSPMD emits the all-gather inside the loop
    body and XLA frees the gathered copy when the iteration's uses
    finish).

    ``site`` names the blessed constraint-placement site: the pin is
    applied under a ``pt_pin[site]`` named scope, which (a) marks it
    blessed for the ``jaxpr.constraint-placement`` check — any in-scan
    constraint WITHOUT the marker is an error — and (b) rides the HLO
    ``op_name`` metadata so the CommPlan extractor attributes the
    collectives GSPMD derives from this pin back to the site
    (docs/analysis.md "Communication contracts").

    Why not a plain ``with_sharding_constraint``?  It transposes to
    itself, constraining the BACKWARD too — the gather's transpose
    forces every per-layer dW to full replication inside the backward
    scan, and the stack pin's transpose (or any sharded dW constraint)
    makes GSPMD feature-shard the saved residuals, turning the in-body
    LN/softmax reductions into partial sums plus in-loop all-reduces
    (measured: 19-49 in-loop reduce ops on the dp2 x fsdp4 mesh,
    depending on spelling).  Left free, the dW values stay replicated
    over fsdp all the way to the optimizer boundary, where the
    elementwise update against the fsdp-sharded moments reads them
    shard-locally (a free slice, outside every loop)."""

    scope = f"pt_pin[{site}]"

    @jax.custom_vjp
    def pin(x):
        with jax.named_scope(scope):
            return jax.lax.with_sharding_constraint(x, sharding)

    def pin_fwd(x):
        with jax.named_scope(scope):
            return jax.lax.with_sharding_constraint(x, sharding), None

    def pin_bwd(_, ct):
        return (ct,)

    pin.defvjp(pin_fwd, pin_bwd)
    return pin


def _accum_carry_spec(lead):
    """The accumulation carry's pin spec: the GROUP axis shards over
    plain ``dp`` and nothing else (docs/parallel.md constraint-placement
    rule 3 — an fsdp-composed carry makes GSPMD feature-shard the saved
    residuals into in-loop partial sums).  Module-level so that
    tests/test_comm_plan.py can plant the composed spelling and prove the
    ``jaxpr.constraint-placement`` check catches it."""
    from jax.sharding import PartitionSpec

    return PartitionSpec(*([None] * lead + ["dp"]))


def _remat_segment(seg_fn, env, param_names=()):
    """``jax.checkpoint``-equivalent for one forward segment whose backward
    recompute is made DATA-DEPENDENT on the incoming cotangents via
    ``optimization_barrier``.

    This is the FALLBACK path for non-uniform segments.  Plain
    ``jax.checkpoint`` on a flat (unrolled) layer stack lets XLA's
    scheduler hoist every segment's rematted forward to the start of the
    backward — all layers' recomputed activations end up live at once and
    remat saves nothing (measured: GPT t=16k bs8 sat at 22.6 GB with the
    OOM dump showing 10+ rematted 768 MB FFN tiles alive together).
    ``lax.scan`` over layers is the canonical fix — the scan-remat engine
    (``_run_fwd``'s ``_try_scan_group``) runs structurally repeated
    segments exactly that way, with weights stacked along the scan axis —
    but a Program's non-repeating segments (prologue/epilogue, irregular
    nets) still need serialization; the barrier gives it — segment k's
    recompute cannot start until segment k+1's backward has produced k's
    output cotangents."""

    def _inexact(x):
        try:
            return jnp.issubdtype(jnp.result_type(x), jnp.inexact)
        except TypeError:
            return False

    @jax.custom_vjp
    def run(env):
        return seg_fn(env)

    def run_fwd(env):
        return seg_fn(env), env

    def run_bwd(env, ct):
        fkeys = sorted(k for k, v in env.items() if _inexact(v))
        ckeys = sorted(k for k, v in ct.items() if _inexact(v))
        env_f, ct_f = jax.lax.optimization_barrier(
            ([env[k] for k in fkeys], [ct[k] for k in ckeys]))
        env2 = dict(env)
        env2.update(zip(fkeys, env_f))
        ct2 = dict(ct)
        ct2.update(zip(ckeys, ct_f))
        # this re-trace IS the recompute, and JAX writes no remat marker
        # for it (the wrapper is a custom_vjp, not jax.checkpoint): the
        # scope says so to whoever reads the device's seconds by phase
        with jax.named_scope(_trace.RECOMPUTE_SCOPE):
            _, vjp_fn = jax.vjp(seg_fn, env2)
        (denv,) = vjp_fn(ct2)
        # Tie the outgoing activation cotangents to this segment's weight
        # gradients with a REAL data dependency.  Without it XLA defers
        # every segment's dW matmuls (nothing consumes dW until the
        # optimizer at the very end), keeping their big recomputed
        # operands alive across the whole backward — measured as 12+
        # concurrent 768 MB tiles on GPT t=16k bs8, which nullified remat
        # entirely.  (A multi-operand optimization_barrier did NOT stop
        # the deferral.)  `tie = s - s` is exactly 0.0 for finite grads
        # but not constant-foldable for floats, so the residual-stream
        # cotangent that unblocks the previous segment's backward now
        # requires every dW of this segment to be finished.
        pkeys = [k for k in param_names if k in denv
                 and _inexact(denv[k])]
        if pkeys:
            s = sum(jnp.sum(denv[k].astype(jnp.float32)) for k in pkeys)
            tie = s - s
            denv = dict(denv)
            for k, v in denv.items():
                if k not in param_names and _inexact(v):
                    denv[k] = v + tie.astype(v.dtype)
        return (denv,)

    run.defvjp(run_fwd, run_bwd)
    return run(env)


def _scan_groups_for(program, segments):
    """Uniform (scan-able) groups among the program's remat segments,
    cached on the program keyed by (version, segment list).  Only groups
    whose period contains at least one WRAPPED segment qualify — the scan
    engine exists to give remat O(1)-per-layer temps; pure saved runs gain
    nothing from restructuring.  ``PADDLE_TPU_SCAN_REMAT=0`` disables the
    engine entirely (every wrapped segment falls back to the barrier)."""
    if os.environ.get("PADDLE_TPU_SCAN_REMAT", "1").lower() in (
            "0", "", "false"):
        return []
    key = (program._version, tuple(tuple(s) for s in segments))
    cached = getattr(program, "_scan_group_cache", None)
    if cached is not None and cached[0] == key:
        return cached[1]
    from .ir import find_uniform_groups

    groups = []
    for g in find_uniform_groups(program, segments):
        period = segments[g["start"]:g["start"] + g["period"]]
        if any((s[2] if len(s) > 2 else True) for s in period):
            groups.append(g)
    program._scan_group_cache = (key, groups)
    return groups


def _rng_op_count(ops):
    """Stateful random-op instances in an op run — each draws one key from
    the LoweringCtx counter, so the scan body must advance the counter by
    this much per iteration to reproduce the unrolled key stream."""
    n = 0
    for op in ops:
        impl = get_op_impl(op.type)
        if impl.stateful_rng and "_key" not in op.attrs:
            n += 1
    return n


def _rng_op_count_deep(program, ops, seen=None):
    """_rng_op_count including sub-blocks (control-flow bodies)."""
    seen = set() if seen is None else seen
    n = _rng_op_count(ops)
    for op in ops:
        sub = op.attrs.get("sub_block")
        if sub is not None and sub not in seen:
            seen.add(sub)
            n += _rng_op_count_deep(program, program.block(sub).ops, seen)
    return n


class LoweringCtx:
    """Passed to raw (control-flow) op implementations so they can lower
    sub-blocks with the same machinery."""

    def __init__(self, executor, program, step_key, batch_axis="dp"):
        self.executor = executor
        self.program = program
        self.step_key = step_key
        self._op_counter = 0
        # the mesh axis that splits the activations' leading (batch)
        # dim at this point of the trace — what a kernel op's shard_map
        # names in its specs.  None inside the local-accumulation lanes,
        # where the enclosing vmap holds ``dp``
        self.batch_axis = batch_axis

    def next_op_key(self):
        """A fresh deterministic PRNG key for one random-op instance."""
        self._op_counter += 1
        return jax.random.fold_in(self.step_key, self._op_counter)

    def run_ops(self, block, ops, env):
        run_block_ops(self, block, ops, env)

    def run_block(self, block_idx, env):
        blk = self.program.block(block_idx)
        run_block_ops(self, blk, blk.ops, env)


def _check_fetches(program, fetch_names):
    """Fail fast with a useful message when a fetch var is not in the
    program — usually the default program is not the one the model was
    built in (missing program= argument / program_guard)."""
    known = {n for blk in program.blocks for n in blk.vars}
    missing = [n for n in fetch_names if n not in known]
    if missing:
        raise ValueError(
            f"fetch var(s) {missing} not found in the program "
            f"({len(program.global_block().ops)} ops); pass the program "
            f"the model was built in (program= argument or program_guard)"
        )


def _gather_input(env, block, name, inside_grad_prefix):
    val = env[name]
    if inside_grad_prefix:
        var = block._find_var(name)
        if var is not None and var.stop_gradient and not isinstance(var, Parameter):
            val = jax.lax.stop_gradient(val)
    return val


def _activation_shard_specs(program):
    """Sharding annotations on non-persistable INTERMEDIATES
    (``parallel.shard_activation``): ``{var_name: PartitionSpec}``,
    cached on the program per version.  Parameters, data feeds and
    persistables are excluded — they have their own sharding paths
    (``compile_shardings``, the boundary pin)."""
    cached = getattr(program, "_act_shard_cache", None)
    if cached is not None and cached[0] == program._version:
        return cached[1]
    specs = {}
    for blk in program.blocks:
        for n, var in blk.vars.items():
            if var.persistable or getattr(var, "is_data", False) \
                    or isinstance(var, Parameter):
                continue
            spec = getattr(var, "partition_spec", None)
            if spec is not None:
                specs[n] = spec
    program._act_shard_cache = (program._version, specs)
    return specs


def _apply_activation_spec(ctx, name, spec, val):
    """Pin one annotated intermediate to its ``partition_spec``.  Always
    called inside the ``pt_shard[var]`` named scope (see
    ``run_block_ops``): the scope wraps BOTH the producing op's lowering
    and this pin, because the SPMD partitioner absorbs the constraint
    custom-call itself — the reshard collectives it inserts inherit the
    surrounding ops' metadata, and that metadata is what lets the
    CommPlan extractor attribute them back to the variable
    (``hlo.accidental-reshard``, ``CommContract.forbid_reshard``)."""
    try:
        if len(spec) > np.ndim(val):
            return val
        from jax.sharding import NamedSharding

        return jax.lax.with_sharding_constraint(
            val, NamedSharding(ctx.executor.mesh, spec))
    except Exception:  # noqa: BLE001 — an unplaceable annotation must
        return val     # not kill the trace; spec-conflict lint names it


def run_block_ops(ctx, block, ops, env, inside_grad_prefix=False,
                  reading=(), over_rows=None):
    """Trace-time evaluation of a list of OpDescs over a name->array env.
    ``reading`` and ``over_rows`` are the scan-remat body's: the outputs
    of the ``mul`` ops that take the reading form
    (``ops/math_ops.py::_mul_reading``), and a set that receives the
    outputs of those lowered with no flattening (``folds_rows_only``)."""
    act_specs = (
        _activation_shard_specs(ctx.program)
        if ctx.program is not None
        and getattr(ctx.executor, "mesh", None) is not None else {})
    for op in ops:
        impl = get_op_impl(op.type)
        if impl.raw:
            impl.fn(ctx, block, op, env)
            if act_specs:
                # raw (control-flow) ops write env themselves — apply
                # any annotated output's pin here so shard_activation
                # on a while/scan-block output is never a silent no-op
                for names in op.outputs.values():
                    for n in names:
                        if n in act_specs and n in env:
                            with jax.named_scope(f"pt_shard[{n}]"):
                                env[n] = _apply_activation_spec(
                                    ctx, n, act_specs[n], env[n])
            continue
        force_stop = inside_grad_prefix and impl.nondiff
        ins = {}
        for slot, names in op.inputs.items():
            if not names:
                continue
            vals = [
                _gather_input(env, block, n, inside_grad_prefix) for n in names
            ]
            if force_stop:
                vals = [jax.lax.stop_gradient(v) for v in vals]
            ins[slot] = vals if len(names) > 1 else vals[0]
        attrs = dict(op.attrs)
        if impl.stateful_rng and "_key" not in attrs:
            attrs["_key"] = ctx.next_op_key()
        if op.type == "mul":
            if op.outputs["Out"][0] in reading:
                attrs["_reads_saved"] = True
            if over_rows is not None:
                from ..ops.math_ops import folds_rows_only

                if folds_rows_only(ins["X"], ins["Y"],
                                   attrs.get("x_num_col_dims", 1),
                                   attrs.get("y_num_col_dims", 1)):
                    over_rows.add(op.outputs["Out"][0])
        pin_names = ()
        if act_specs:
            pin_names = tuple(
                n for names in op.outputs.values() for n in names
                if n in act_specs)
        try:
            # every op lowers under the scope of its kind, type and the
            # name the model gave its layer (the reference's per-op
            # RecordEvent): op_name metadata, which
            # observability.trace.device_scopes reads back
            with _trace.op_scope(op.type,
                                 next(iter(op.output_names()), "")):
                if pin_names:
                    # the pt_shard[vars] scope wraps the WHOLE lowering
                    # of the producing op (not just the constraint):
                    # GSPMD attaches its reshard collectives to these
                    # ops' metadata, which is the provenance the comm
                    # analyzer attributes reshards by.  ALL annotated
                    # outputs join the scope name — provenance matching
                    # is a regex search, so a forbid_reshard pattern on
                    # any of them still fires.
                    with jax.named_scope(
                            f"pt_shard[{','.join(pin_names)}]"):
                        outs = impl.call(ins, attrs, ctx)
                else:
                    outs = impl.call(ins, attrs, ctx)
        except Exception as e:
            raise RuntimeError(f"error lowering {op}: {e}") from e
        outs = outs or {}
        for slot, names in op.outputs.items():
            if slot not in outs:
                continue
            vals = outs[slot]
            if not isinstance(vals, (list, tuple)):
                vals = [vals]
            if len(vals) != len(names):
                raise RuntimeError(
                    f"op {op.type}: output slot {slot} produced {len(vals)} "
                    f"values for {len(names)} variables"
                )
            for n, v in zip(names, vals):
                if n in act_specs:
                    with jax.named_scope(f"pt_shard[{n}]"):
                        v = _apply_activation_spec(
                            ctx, n, act_specs[n], v)
                env[n] = v


class Executor:
    """Executor(place) — place may be CPUPlace(), TPUPlace(), or None (JAX
    default backend).  Optionally bound to a ``jax.sharding.Mesh`` for
    multi-device SPMD execution (see paddle_tpu.parallel)."""

    def __init__(self, place=None, mesh=None, donate_state=True):
        self.place = place
        self.mesh = mesh
        self.donate_state = donate_state
        self._multiproc = mesh is not None and any(
            d.process_index != jax.process_index()
            for d in mesh.devices.flat
        )
        self._cache = {}
        # Telemetry of the most recent run()/run_steps(): compile_seconds,
        # static flops / bytes_accessed from XLA cost analysis, cache_hit,
        # and (mesh runs) the cross-chip collective accounting from the
        # compiled HLO.  The Trainer reads this to report achieved MFU
        # per step.
        self.last_step_cost = None
        # Most recent compile's gradient-accumulation comm plan
        # ({"mode": "local"|"reduce_each", ...}) — the accumulation
        # analogue of last_remat_plan.  None when the step has no accum.
        self.last_accum_plan = None
        # Most recent compile's per-op-class attribution table
        # (observability.attribution: flops/bytes/roofline-ms per class,
        # coverage vs cost_analysis, tune-style workload key).  None
        # until a compile runs with PADDLE_TPU_ATTR on.
        self.last_attribution = None
        # Most recent mesh compile's structured CommPlan
        # (analysis.comm.CommPlan: per-collective kind / mesh axes /
        # bytes / loop membership / phase / provenance) — what
        # CommContracts and comm_diff consume.  None off-mesh.
        self.last_comm_plan = None

    def _fsdp_active(self, program):
        """True when the scan-remat body should gather FSDP-sharded
        per-layer weights in-loop: an ``fsdp`` mesh axis of size > 1,
        the ``PADDLE_TPU_FSDP`` kill switch on, and the program not
        opted out (``program._fsdp = False`` — the autotuner's
        gather-vs-replicate schedule dimension,
        ``memory_optimize(policy="auto")``)."""
        from ..parallel.api import _fsdp_enabled
        from ..parallel.mesh import axis_size

        if self.mesh is None or not _fsdp_enabled():
            return False
        if getattr(program, "_fsdp", True) is False:
            return False
        return axis_size(self.mesh, "fsdp") > 1

    def _aot_compile(self, jitted, args, label, program=None,
                     fetch_names=()):
        """Explicit ``lower().compile()`` instead of first-call jit, so
        compile time and the executable's static cost model are
        observable: increments ``executor.compile_count``, observes
        ``executor.compile_seconds``, and extracts flops/bytes from
        ``compiled.cost_analysis()`` (the reference has no analog — its
        interpreter never compiles; here the cost model is what turns
        step wall-time into achieved MFU).  When ``program`` is given,
        the static-analysis engine's program- and hlo-level checks run
        over the compile artifacts (no extra trace/compile) and their
        findings summarize into the cost dict (``lint_findings`` /
        ``lint_errors`` / ``lint_checks`` — PADDLE_TPU_LINT=0 disables).
        Returns ``(fn, cost)``."""
        reg = _obs.get_registry()
        # kernel-registry recording: resolutions happen at trace time
        # (inside .lower()), so resetting here scopes the snapshot to
        # THIS compile — last_step_cost["kernel_backends"] then says
        # which kernel backend each op class of this executable runs
        # (docs/kernels.md; the attribution workload key carries the
        # flash choice as its |kb= token)
        from ..kernels import registry as _kreg

        _kreg.reset_selected()
        t0 = time.perf_counter()
        compiled = jitted.lower(*args).compile()
        dt = time.perf_counter() - t0
        # the device half of the span primitive: the executable, for
        # whoever asks which sub-layer an instruction belongs to
        _trace.register_executable(label, compiled)
        kernel_backends = _kreg.selected_backends()
        reg.counter(
            "executor.compile_count",
            help="programs compiled (jit cache misses)").inc()
        reg.histogram("executor.compile_seconds").observe(dt)
        cost = {"label": label, "compile_seconds": dt,
                "flops": None, "bytes_accessed": None}
        if kernel_backends:
            cost["kernel_backends"] = kernel_backends
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if ca:
                f = ca.get("flops")
                b = ca.get("bytes accessed")
                cost["flops"] = float(f) if f else None
                cost["bytes_accessed"] = float(b) if b else None
        except Exception:
            pass  # some backends/plugins don't implement cost analysis
        from ..analysis.hlo_tools import compiled_memory_stats

        memstats = compiled_memory_stats(compiled)
        if memstats:
            # hbm_high_water_bytes: XLA's liveness-aware peak when the
            # backend reports one, else argument+output+temp minus
            # donation aliasing; temp_bytes: HLO temps alone (the figure
            # the remat policies move).  Both land in last_step_cost (the
            # bench/trainer JSON channel) and the registry.
            temp = memstats["temp_bytes"]
            high = memstats["hbm_high_water_bytes"]
            peak = high or (memstats["output_bytes"] + temp)
            if peak:
                cost["compiled_peak_bytes"] = int(peak)
            cost["temp_bytes"] = temp
            cost["hbm_high_water_bytes"] = high
            reg.gauge(
                "executor.temp_bytes",
                help="HLO temp bytes of the largest compiled step",
            ).set_max(temp)
            reg.gauge(
                "executor.hbm_high_water_bytes",
                help="compiled-step HBM high-water (memory_analysis)",
            ).set_max(high)
        comm = None
        comm_plan = None
        if self.mesh is not None:
            # cross-chip communication accounting
            # (analysis.hlo_tools.hlo_comm_report): static collective op
            # counts/bytes of the compiled step, with the load-bearing
            # loop split — a reduce op inside a while body pays once per
            # microbatch, one outside pays once per step.  Lands in
            # last_step_cost (bench/trainer JSON channel) and the
            # registry, mirroring the hbm_high_water plumbing.  The
            # same HLO text also yields the structured CommPlan
            # (analysis.comm): per-collective mesh axes, phase and
            # provenance — exe.last_comm_plan carries the full plan,
            # the cost dict its compact per-bucket summary.
            from ..analysis.comm import extract_comm_plan

            try:
                hlo_text = compiled.as_text() or ""
            except Exception:  # noqa: BLE001 — backend can't render
                hlo_text = ""
            comm_plan = extract_comm_plan(
                hlo_text, mesh=self.mesh, label=label)
            # the scalar report derives from the plan: ONE parse of the
            # (potentially huge) HLO text serves both shapes
            comm = comm_plan.comm_report() if hlo_text else {}
            self.last_comm_plan = comm_plan
            if len(comm_plan):
                cost["comm_plan"] = comm_plan.summary()
            if comm:
                cost["collective_count"] = comm["collective_count"]
                cost["collective_bytes"] = comm["collective_bytes"]
                # per-kind counts under a DISTINCT key: "collective_ops"
                # stays the scalar count everywhere scalar-valued (the
                # executor.collective_ops gauge, trainer JSONL)
                cost["collective_op_kinds"] = dict(comm["collective_ops"])
                cost["reduce_ops"] = comm["reduce_ops"]
                cost["reduce_bytes"] = comm["reduce_bytes"]
                if label.startswith("scan"):
                    # run_steps fuses N optimizer steps into ONE while
                    # loop: the per-step boundary reduction is
                    # structurally "in loop" there, so the
                    # one-reduce-per-step invariant does not apply —
                    # emit None rather than a false regression signal
                    cost["reduce_ops_in_loop"] = None
                    cost["collectives_in_loop"] = None
                else:
                    cost["reduce_ops_in_loop"] = comm["reduce_ops_in_loop"]
                    cost["collectives_in_loop"] = comm[
                        "collectives_in_loop"]
                reg.gauge(
                    "executor.collective_ops",
                    help="collective ops in the largest compiled step",
                ).set_max(comm["collective_count"])
                reg.gauge(
                    "executor.collective_bytes",
                    help="static collective bytes of the largest "
                         "compiled step",
                ).set_max(comm["collective_bytes"])
        if self.last_accum_plan is not None:
            cost["accum_comm"] = dict(self.last_accum_plan)
        try:
            # autotune traffic snapshot (tune.cache_hits/misses/searches)
            # — how a trainer JSONL/bench row shows whether this compile
            # ran on tuned or default schedules (docs/autotune.md)
            from ..tune import tune_stats

            ts = tune_stats()
            if ts:
                cost["tune"] = ts
        except Exception:  # noqa: BLE001 — telemetry must never block
            pass
        try:
            # per-op-class performance attribution of this executable
            # (observability/attribution.py): which classes own the
            # milliseconds, coverage vs the cost_analysis figure above,
            # and the tune-style workload key the corpus joins on.  The
            # full table lands on exe.last_attribution; the compact
            # top-op summary rides the cost dict into trainer JSONL and
            # bench rows.  PADDLE_TPU_ATTR=0 skips the walk.
            from ..observability import attribution as _attr

            if _attr.attribution_enabled():
                att = _attr.attribute_compiled(
                    compiled, cost=cost, program=program)
                if att:
                    self.last_attribution = att
                    cost["attribution"] = _attr.summarize(att)
        except Exception:  # noqa: BLE001 — telemetry must never block
            pass
        try:
            # learned cost model status (tune/costmodel.py): whether the
            # attribution estimates above came from the FITTED
            # coefficients or the analytic defaults — rides into trainer
            # JSONL and flight bundles so a corpus row says which model
            # produced its est_ms
            from ..tune.costmodel import model_status

            cost["costmodel"] = model_status()
        except Exception:  # noqa: BLE001 — telemetry must never block
            pass
        from ..analysis import compile_findings, lint_enabled

        if program is not None and lint_enabled():
            # fold the static-analysis findings of this compile into the
            # cost dict (and thence the trainer JSONL): program-level
            # checks over the IR, hlo-level checks over the artifacts
            # computed above.  run_steps fuses N optimizer steps into ONE
            # while loop, so in-loop collectives are expected there.
            try:
                findings = compile_findings(
                    program=program, fetch_names=fetch_names,
                    compiled=compiled, memstats=memstats or None,
                    comm=comm if self.mesh is not None else {},
                    in_loop_expected=label.startswith("scan"),
                    donate=self.donate_state,
                    kernel_backends=kernel_backends,
                    mesh=self.mesh, comm_plan=comm_plan, label=label)
            except Exception:  # noqa: BLE001 — lint must never block a run
                findings = []
            cost["lint_findings"] = len(findings)
            cost["lint_errors"] = sum(
                1 for f in findings if f.severity == "error")
            if findings:
                cost["lint_checks"] = sorted(
                    {f.check for f in findings})[:8]
            if any(f.check == "jaxpr.kernel-backend" for f in findings):
                # dedicated flag for timed-run regions (tests/
                # test_kernels.py): lint_checks caps at 8 names, so
                # membership there is not a reliable signal
                cost["interpret_in_timed_run"] = True
        return compiled, cost

    # ------------------------------------------------------------------
    def _prepare(self, program, feed, fetch_list, scope):
        """Shared run()/run_steps() prologue: resolve defaults, coerce
        feeds (device arrays stay on device), snapshot state, build the
        compile-cache signature."""
        program = program or default_main_program()
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope or global_scope()
        scope.ensure_rng(program.random_seed)

        feed_names = sorted(feed.keys())
        fetch_names = [
            v.name if hasattr(v, "name") else str(v) for v in fetch_list
        ]
        block = program.global_block()
        multiproc = self._multiproc
        feed_vals = []
        for n in feed_names:
            val = feed[n]
            var = block._find_var(n)
            dtype = var.dtype if var is not None else None
            if isinstance(val, jax.Array):
                if multiproc and val.sharding.is_fully_addressable:
                    raise ValueError(
                        f"feed {n!r} is a process-local jax.Array but the "
                        f"mesh spans multiple processes; device_put it "
                        f"with the global NamedSharding (or feed numpy — "
                        f"each process's local batch shard)")
                # already device-resident (e.g. a prefetched pipeline) —
                # no host round-trip; coerce dtype on device if needed.
                if dtype is not None and val.dtype != dtype:
                    val = val.astype(dtype)
                feed_vals.append(val)
                continue
            val = np.asarray(val, dtype=dtype)
            if multiproc:
                # Multi-host mesh: each process feeds its LOCAL portion of
                # the batch (the reference's per-trainer data convention);
                # assemble the global jax.Array — jit rejects raw numpy
                # with cross-process shardings.
                from jax.sharding import NamedSharding, PartitionSpec
                from ..parallel.api import _spec_for

                spec = _spec_for(var, self.mesh) if var else PartitionSpec()
                val = jax.make_array_from_process_local_data(
                    NamedSharding(self.mesh, spec), val)
            feed_vals.append(val)

        state_names = tuple(
            sorted(
                v.name
                for v in program.persistable_vars()
                if scope.find_var(v.name) is not None
            )
        )
        state = {n: scope.get(n) for n in state_names}
        state[RNG_VAR] = scope.get(RNG_VAR)
        if _emits_grad_norm(program):
            # grad-norm is carried like @RNG@: output-only for run(),
            # but lax.scan (run_steps) needs carry-in == carry-out, so
            # the input state holds a (ignored) scalar slot too
            if scope.find_var(GRAD_NORM_VAR) is None:
                scope.set(GRAD_NORM_VAR, jnp.zeros((), jnp.float32))
            state[GRAD_NORM_VAR] = scope.get(GRAD_NORM_VAR)

        feed_sig = tuple(
            (n, v.shape, str(v.dtype)) for n, v in zip(feed_names, feed_vals)
        )
        return (program, scope, feed_names, fetch_names, feed_vals,
                state_names, state, feed_sig)

    def _finish(self, scope, new_state, fetch_names, fetches, return_numpy):
        """Shared run()/run_steps() postlude: debug flags, scope update."""
        from ..flags import FLAGS

        if FLAGS.check_nan_inf:
            # FLAGS_check_nan_inf analog (reference executor.cc:131): scan
            # everything the step produced.  Host-side sync — debug only.
            for name, arr in list(new_state.items()) + list(
                zip(fetch_names, fetches)
            ):
                a = np.asarray(arr)
                if np.issubdtype(a.dtype, np.floating) and not np.isfinite(a).all():
                    # make the abort observable (metrics + timeline),
                    # not just a propagating exception
                    _obs.get_registry().counter(
                        "executor.nan_trips",
                        help="NaN/Inf aborts caught by nan_guard / "
                             "check_nan_inf").inc()
                    _trace.get_tracer().instant(
                        "nan_guard_trip", cat="executor", var=name)
                    # post-mortem: the flight bundle carries the recent
                    # step records (grad-norm window included) alongside
                    # the abort
                    from ..observability import flight as _flight

                    _flight.dump("nan_trip", var=name)
                    err = FloatingPointError(
                        f"NaN/Inf detected in {name!r} after step"
                    )
                    # already recorded here: nan_guard() must not count
                    # the same abort a second time on the way out
                    err._pt_nan_counted = True
                    raise err
        if FLAGS.do_memory_benchmark:
            total = sum(
                np.asarray(v).nbytes for v in new_state.values()
            )
            print(f"[memory] live state: {total / 1e6:.2f} MB "
                  f"({len(new_state)} vars)")
        scope.update(new_state)
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return list(fetches)

    def _run_entry(self, program, feed_names, fetch_names, state_names,
                   state, feed_vals, feed_sig):
        """The single-step executable for this signature — ``(entry,
        cache_hit)`` — compiling (and caching) on miss.  Shared by
        ``run`` and ``compile_only`` so preflighting primes exactly the
        cache entry the real step will hit."""
        key = (
            program._serial,
            program._version,
            feed_sig,
            tuple(fetch_names),
            state_names,
        )
        reg = _obs.get_registry()
        entry = self._cache.get(key)
        if entry is not None:
            reg.counter("executor.cache_hits").inc()
            return entry, True
        reg.counter("executor.cache_misses").inc()
        _check_fetches(program, fetch_names)
        jitted = self._compile(
            program, feed_names, fetch_names, state_names)
        entry = self._aot_compile(
            jitted, (state,) + tuple(feed_vals),
            f"run:{program._serial}v{program._version}",
            program=program, fetch_names=tuple(fetch_names))
        self._cache[key] = entry
        return entry, False

    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        scope=None,
        return_numpy=True,
    ):
        # one host interval per call: on the timeline, in a profiler
        # session's trace, and as the histogram executor.run_seconds.  The
        # step is dispatched, not awaited.  The parts run in a frame of
        # their own so that the interval also holds its teardown: the
        # step's donated input state is released there.
        with _trace.get_tracer().span(
                "executor.run", cat="executor", registry=_obs.get_registry(),
                histogram="executor.run_seconds"):
            return self._run(program, feed, fetch_list, scope, return_numpy)

    def _run(self, program, feed, fetch_list, scope, return_numpy):
        # the parts are timeline and annotation only (timer=False): the
        # aggregate is executor.run_seconds, and a host_timer.executor.*
        # beside host_timer.trainer.dispatch would count the same seconds
        tracer = _trace.get_tracer()
        with tracer.span("executor.prepare", cat="executor", timer=False):
            (program, scope, feed_names, fetch_names, feed_vals, state_names,
             state, feed_sig) = self._prepare(program, feed, fetch_list, scope)
        with tracer.span("executor.dispatch", cat="executor",
                         timer=False) as sp:
            entry, cache_hit = self._run_entry(
                program, feed_names, fetch_names, state_names, state,
                feed_vals, feed_sig)
            sp.set(cache_hit=cache_hit)
            step, cost = entry
            self.last_step_cost = dict(cost, cache_hit=cache_hit)
            new_state, fetches = step(state, *feed_vals)
        with tracer.span("executor.finish", cat="executor", timer=False):
            return self._finish(scope, new_state, fetch_names, fetches,
                                return_numpy)

    # ------------------------------------------------------------------
    def compile_only(self, program=None, feed=None, fetch_list=None,
                     scope=None):
        """AOT-compile the step for this (program, feed, fetch) signature
        WITHOUT running it, priming the same cache ``run`` uses (the
        following ``run`` is a cache hit, not a second compile).  Returns
        a copy of the cost dict — compile_seconds, flops,
        ``hbm_high_water_bytes``, ``temp_bytes`` — so callers can
        preflight a capacity config against the chip's HBM before the
        first real step allocates (the tuner's search rejects a
        candidate this way before it runs a step: ``tune/search.py``
        ``PreflightRejected``)."""
        (program, scope, feed_names, fetch_names, feed_vals, state_names,
         state, feed_sig) = self._prepare(program, feed, fetch_list, scope)
        entry, cache_hit = self._run_entry(
            program, feed_names, fetch_names, state_names, state,
            feed_vals, feed_sig)
        _, cost = entry
        self.last_step_cost = dict(cost, cache_hit=cache_hit)
        return dict(cost)

    # ------------------------------------------------------------------
    def run_steps(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        steps=None,
        scope=None,
        return_numpy=True,
    ):
        """Run ``steps`` training steps as ONE jitted ``lax.scan`` — the
        whole inner loop compiles to a single XLA computation, so per-step
        host dispatch (the cost the reference pays per *op* in its
        interpreter loop, executor.cc:118) disappears entirely.

        ``feed`` values are STACKED along a leading steps axis
        ([steps, batch, ...]); ``steps`` defaults to that axis.  Fetches
        come back stacked ([steps, ...]).  State (parameters, RNG) carries
        through the scan exactly as across separate ``run`` calls.
        """
        (program, scope, feed_names, fetch_names, feed_vals, state_names,
         state, feed_sig) = self._prepare(program, feed, fetch_list, scope)
        if steps is None:
            if not feed_vals:
                raise ValueError("steps is required when there is no feed")
            steps = int(feed_vals[0].shape[0])
        for n, v in zip(feed_names, feed_vals):
            if v.shape[0] != steps:
                raise ValueError(
                    f"feed {n!r} leading (steps) axis {v.shape[0]} != "
                    f"{steps}; run_steps feeds are stacked [steps, ...]"
                )

        key = (
            "scan",
            steps,
            program._serial,
            program._version,
            feed_sig,
            tuple(fetch_names),
            state_names,
        )
        reg = _obs.get_registry()
        entry = self._cache.get(key)
        cache_hit = entry is not None
        if not cache_hit:
            reg.counter("executor.cache_misses").inc()
            _check_fetches(program, fetch_names)
            jitted = self._compile_scan(
                program, feed_names, fetch_names, state_names, steps
            )
            entry = self._aot_compile(
                jitted, (state,) + tuple(feed_vals),
                f"scan{steps}:{program._serial}v{program._version}",
                program=program, fetch_names=tuple(fetch_names))
            self._cache[key] = entry
        else:
            reg.counter("executor.cache_hits").inc()
        fn, cost = entry
        self.last_step_cost = dict(cost, cache_hit=cache_hit, steps=steps)

        new_state, fetches = fn(state, *feed_vals)
        return self._finish(scope, new_state, fetch_names, fetches,
                            return_numpy)

    def _compile_scan(self, program, feed_names, fetch_names, state_names,
                      steps):
        step, persist_out = self.lower(
            program, feed_names, fetch_names, state_names)
        # lax.scan requires carry-in == carry-out structure: every
        # persistable the step will emit must already be in the scope
        # (run() tolerates the step creating them; a scan cannot).
        extra = sorted(set(persist_out) - set(state_names))
        if extra:
            raise ValueError(
                f"run_steps needs persistable var(s) {extra} initialized "
                f"before the scan (run the startup program, or one "
                f"regular run() step, first)"
            )

        def multi(state, *stacked_feeds):
            def body(s, fs):
                return step(s, *fs)

            xs = tuple(stacked_feeds) if stacked_feeds else None
            new_state, fetches = jax.lax.scan(
                body, state, xs, length=steps)
            return new_state, fetches

        jit_kwargs = {}
        if self.donate_state:
            jit_kwargs["donate_argnums"] = 0
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from ..parallel.api import compile_shardings

            in_sh, out_sh = compile_shardings(
                self.mesh, program, feed_names, fetch_names, state_names,
                out_state_names=persist_out,
                extra_state=((GRAD_NORM_VAR,)
                             if _emits_grad_norm(program) else ()),
            )
            state_sh, *feed_sh = in_sh
            # stacked feeds get an unsharded leading steps axis
            feed_sh = [
                NamedSharding(self.mesh, PartitionSpec(None, *s.spec))
                for s in feed_sh
            ]
            jit_kwargs["in_shardings"] = (state_sh, *feed_sh)
            jit_kwargs["out_shardings"] = out_sh
        return jax.jit(multi, **jit_kwargs)

    # ------------------------------------------------------------------
    def lower(self, program, feed_names, fetch_names, state_names):
        """Build the pure (unjitted) step function
        ``step(state, *feed) -> (new_state, fetches)`` for a program.
        Returns ``(step, persist_out)`` where persist_out names the state
        entries the step emits.  Exposed for embedding the framework in
        external jit pipelines (e.g. the driver's compile checks)."""
        self.last_accum_plan = None
        block = program.global_block()
        bw = block.backward_index
        info = program._backward_info.get(0)
        emit_grad_norm = _emits_grad_norm(program)
        # The state the step returns: persistables that are either already
        # live (passed in) or written by some op — static, so sharding
        # pytrees can be built to match.
        written = {
            n
            for blk in program.blocks
            for op in blk.ops
            for n in op.output_names()
        }
        persist_out = [
            v.name
            for v in program.persistable_vars()
            if v.name in written or v.name in state_names
        ]

        def step(state, *feed_vals):
            rng = state[RNG_VAR]
            step_key, next_key = jax.random.split(rng)
            ctx = LoweringCtx(self, program, step_key)
            env = dict(state)
            env.pop(GRAD_NORM_VAR, None)  # carried slot, never an input
            env.update(zip(feed_names, feed_vals))
            grad_norm_out = [None]

            if bw is None or info is None:
                run_block_ops(ctx, block, block.ops, env)
            else:
                param_names = [
                    n for n in info["params"] if n in env
                ]

                segments = getattr(program, "_remat_segments", None)

                # Only the values actually consumed after the backward
                # split may escape the differentiated forward as aux:
                # optimizer-op inputs, fetches, and persistables (BN
                # stats, metric accumulators).  Returning the whole env
                # would make every intermediate activation a computation
                # OUTPUT, forcing XLA to materialize all of them to HBM
                # (measured: 53 GB accessed/step on ResNet-50 bs128 vs
                # ~16 GB with the trimmed aux) and blocking fusion.
                aux_names = set(fetch_names) | set(persist_out)
                aux_names.add(info["loss"])
                for op_ in block.ops[bw:]:
                    for slot_names in op_.inputs.values():
                        aux_names.update(slot_names)

                def make_fwd(fctx):
                    """Differentiable forward bound to one LoweringCtx —
                    gradient accumulation builds one per microbatch so
                    random ops draw distinct keys."""

                    def fwd(tparams, env0):
                        return _run_fwd(fctx, tparams, env0)

                    return fwd

                def _run_fwd(fctx, tparams, env0):
                    e = dict(env0)
                    e.update(tparams)
                    if not segments:
                        run_block_ops(
                            fctx, block, block.ops[:bw], e,
                            inside_grad_prefix=True,
                        )
                    else:
                        # memory_optimize marked remat boundaries: run each
                        # wrapped forward segment under jax.checkpoint so
                        # backward recomputes activations instead of
                        # storing them; unwrapped segments (the selective
                        # policy's expensive ops — flash attention etc.)
                        # run plainly so their residuals stay saved.
                        #
                        # A wrapped segment may only return names consumed
                        # AFTER it (later forward ops, the loss, aux):
                        # returning everything it writes would thread every
                        # internal activation into the next segment's
                        # inputs, where jax.checkpoint saves it as a
                        # residual — remat would then recompute for zero
                        # memory saved (measured: t=16k bs8 GPT sat at
                        # 23.5 GB, OOM, regardless of policy).
                        def op_uses(op_, acc, seen):
                            for slot_names in op_.inputs.values():
                                acc.update(slot_names)
                            sub = op_.attrs.get("sub_block")
                            if sub is not None and sub not in seen:
                                seen.add(sub)
                                for sop in program.block(sub).ops:
                                    op_uses(sop, acc, seen)

                        needed_after = [set(aux_names)
                                        | {info["loss"]}]
                        for op_ in reversed(block.ops[:bw]):
                            nxt = set(needed_after[-1])
                            op_uses(op_, nxt, set())
                            needed_after.append(nxt)
                        needed_after.reverse()  # needed_after[i] = used
                        # by ops[i:] (+loss/aux); index bw == just aux

                        def _try_scan_group(group, use_fsdp=True):
                            """Run ``segments[i0 : i0 + P*G]`` — G
                            structurally identical periods of P segments
                            (one transformer layer each) — as ONE
                            ``lax.scan``: per-layer weights stack along the
                            scan axis (xs), the residual stream threads as
                            the carry, and wrapped sub-segments run under
                            plain ``jax.checkpoint`` INSIDE the scan body.
                            The scan structurally serializes backward
                            recompute (segment k's remat cannot start until
                            its iteration's cotangent arrives), so remat
                            temps are O(1) per layer — the compilable HLO
                            the barrier spelling could not guarantee at
                            t=16k.

                            FSDP rides the same structure: xs entries
                            whose parameter resolves an ``fsdp`` spec
                            stay SHARDED in the stacked at-rest form
                            (``P(None, *spec)`` — at-rest bytes divide
                            by the fsdp degree) and each layer's slice
                            is constrained to the fsdp-free spec INSIDE
                            the body, so GSPMD emits the all-gather in
                            the loop and frees the gathered slice after
                            its layer — live parameter bytes are O(one
                            layer), the PR-3 remat trick applied to
                            weights.  Returns False (caller falls back
                            to the per-segment barrier path) when the
                            group cannot be classified into
                            carry/xs/shared inputs or the scan fails to
                            trace; an fsdp-constrained trace failure
                            first retries WITHOUT the constraints
                            (``executor.fsdp_fallbacks``)."""
                            i0, P, G = (group["start"], group["period"],
                                        group["count"])
                            ext_maps = group["ext_maps"]
                            out_maps = group["out_maps"]
                            c0 = fctx._op_counter
                            reg = _obs.get_registry()
                            fsdp_gather = {}
                            try:
                                out0 = list(out_maps[0].keys())
                                out_sets = [set(m.values()) for m in out_maps]
                                written_any = set().union(*out_sets)
                                pre_w = [set()]
                                for k in range(G):
                                    pre_w.append(pre_w[-1] | out_sets[k])

                                # classify each canonical external input:
                                # carry (produced by the previous period),
                                # shared (same name+value every period), or
                                # xs (per-period values stacked on the scan
                                # axis — the per-layer weights)
                                carry_map, shared_names, xs_names = {}, [], []
                                for n in ext_maps[0]:
                                    vals = [ext_maps[k][n] for k in range(G)]
                                    m = vals[1] if G > 1 else None
                                    if (m in out_maps[0] and n in e and all(
                                            ext_maps[k][n]
                                            == out_maps[k - 1][m]
                                            for k in range(1, G))):
                                        carry_map[n] = m
                                    elif (all(v == n for v in vals)
                                          and n not in written_any
                                          and n in e):
                                        shared_names.append(n)
                                    elif all(vals[k] in e
                                             and vals[k] not in pre_w[k]
                                             for k in range(G)):
                                        xs_names.append(n)
                                    else:
                                        raise ValueError(
                                            f"unclassifiable input {n!r}")

                                # outputs escaping the group: final-period
                                # values come from the carry; anything else
                                # consumed after the group stacks as ys
                                t_end = segments[i0 + P * G - 1][1]
                                names_after = needed_after[t_end]
                                carry_vals = set(carry_map.values())
                                inv_carry = {m: n
                                             for n, m in carry_map.items()}
                                ys_names = set()
                                ys_writes = []   # (env_name, canonical, k)
                                carry_writes = {}  # env_name -> carry input
                                for m in out0:
                                    for k in range(G):
                                        on = out_maps[k][m]
                                        if on not in names_after:
                                            continue
                                        if k == G - 1 and m in inv_carry:
                                            carry_writes[on] = inv_carry[m]
                                        else:
                                            ys_names.add(m)
                                            ys_writes.append((on, m, k))

                                # per-sub-segment plan (canonical frame):
                                # ops, wrap flag, outputs needed later in
                                # the period, external uses, rng-op count
                                sub = []
                                for seg_ in segments[i0:i0 + P]:
                                    s_, t_ = seg_[0], seg_[1]
                                    wrap_ = (seg_[2] if len(seg_) > 2
                                             else True)
                                    sub.append([block.ops[s_:t_], wrap_])
                                needed_sub = [set(ys_names) | carry_vals]
                                for ops_j, _w in reversed(sub):
                                    nxt = set(needed_sub[0])
                                    for op_ in ops_j:
                                        op_uses(op_, nxt, set())
                                    needed_sub.insert(0, nxt)
                                plan_subs = []
                                nr = 0
                                for j, (ops_j, wrap_) in enumerate(sub):
                                    written_j = {
                                        n for op_ in ops_j
                                        for n in op_.output_names()}
                                    out_j = tuple(sorted(
                                        written_j & needed_sub[j + 1]))
                                    uses_j = set()
                                    for op_ in ops_j:
                                        op_uses(op_, uses_j, set())
                                    nr_j = _rng_op_count(ops_j)
                                    plan_subs.append(
                                        (ops_j, wrap_, out_j,
                                         tuple(sorted(uses_j)), nr, nr_j))
                                    nr += nr_j
                                # a product whose left operand a wrapped
                                # sub-segment of the same iteration made
                                # READS it: the compiler is otherwise
                                # free to evaluate a cheap producer again
                                # on the product's operand side
                                # (docs/memory.md)
                                made_wrapped = set()
                                products_reading = set()
                                for ops_j, wrap_, out_j, *_ in plan_subs:
                                    if wrap_:
                                        made_wrapped.update(out_j)
                                        continue
                                    products_reading.update(
                                        op_.outputs["Out"][0]
                                        for op_ in ops_j
                                        if op_.type == "mul"
                                        and op_.inputs["X"][0]
                                        in made_wrapped)
                                # filled as the body is traced: the
                                # rule is the operands' shapes'
                                products_over_rows = set()

                                shared_env = {n: e[n] for n in shared_names}
                                xs_stacked = {
                                    n: jnp.stack(
                                        [e[ext_maps[k][n]]
                                         for k in range(G)])
                                    for n in xs_names
                                }
                                if use_fsdp and self._fsdp_active(
                                        program):
                                    from jax.sharding import (
                                        NamedSharding as _NS,
                                        PartitionSpec as _PS)

                                    from ..parallel.api import \
                                        fsdp_spec_for

                                    for n in xs_names:
                                        v_ = block._find_var(n)
                                        spec = fsdp_spec_for(
                                            v_, self.mesh, block
                                        ) if v_ is not None else None
                                        if spec is None:
                                            continue
                                        gathered = _PS(*(
                                            (tuple(a for a in ent
                                                   if a != "fsdp")
                                             or None)
                                            if isinstance(ent, tuple)
                                            else (None if ent == "fsdp"
                                                  else ent)
                                            for ent in spec))
                                        # at rest: the stack stays
                                        # fsdp-sharded on the weight's
                                        # leading (non-scan) axis
                                        xs_stacked[n] = \
                                            _fsdp_fwd_pin(
                                                _NS(self.mesh,
                                                    _PS(None, *spec)),
                                                site=f"fsdp_stack:{n}")(
                                                xs_stacked[n])
                                        fsdp_gather[n] = \
                                            _fsdp_fwd_pin(
                                                _NS(self.mesh,
                                                    gathered),
                                                site=f"fsdp_gather:{n}")
                                carry0 = {n: e[n] for n in carry_map}
                                # offload ("host"/"save"): the ONE change
                                # vs plain selective execution is that
                                # each wrapped sub-segment's checkpoint
                                # gets a NAME policy and tags the
                                # block-input (carry) args it consumes
                                # BLOCK_INPUT_TAG inside the region — the
                                # segment's backward recompute then reads
                                # the carry from the saved named copy
                                # (pinned host memory in mode "host")
                                # instead of forcing the scan to stack it
                                # in HBM.  The recompute op graph is
                                # IDENTICAL to selective's (a default
                                # jax.checkpoint saves nothing internal
                                # either); only the residual's placement
                                # moves — which is why offload is
                                # bit-exact vs selective.
                                off_mode = _offload_mode(program)
                                ckpt_policy = (
                                    _offload_ckpt_policy(off_mode)
                                    if off_mode != "off" else None)

                                def body(carry, xs):
                                    k_idx, xvals = xs
                                    if fsdp_gather:
                                        # gather THIS layer's weight
                                        # slices to their fsdp-free
                                        # spec inside the loop body:
                                        # XLA frees them when the
                                        # iteration's uses finish, so
                                        # only one layer is ever live
                                        # gathered
                                        xvals = dict(xvals)
                                        for n_, g_ in \
                                                fsdp_gather.items():
                                            xvals[n_] = g_(xvals[n_])
                                    e2 = dict(shared_env)
                                    e2.update(carry)
                                    e2.update(xvals)
                                    base = (c0 + k_idx * nr) if nr else c0
                                    for (ops_j, wrap_, out_j, uses_j,
                                         off_j, _nr_j) in plan_subs:
                                        cj = base + off_j if nr else c0
                                        if not wrap_:
                                            fctx._op_counter = cj
                                            run_block_ops(
                                                fctx, block, ops_j, e2,
                                                inside_grad_prefix=True,
                                                reading=products_reading,
                                                over_rows=products_over_rows)
                                            continue
                                        tags = (
                                            frozenset(carry_map)
                                            & set(uses_j)
                                            if ckpt_policy is not None
                                            else frozenset())

                                        def seg_fn(env_in, _ops=ops_j,
                                                   _out=out_j, _c=cj,
                                                   _tags=tags):
                                            fctx._op_counter = _c
                                            e3 = dict(env_in)
                                            for tn in _tags:
                                                if tn in e3:
                                                    e3[tn] = _tag_named(
                                                        e3[tn],
                                                        BLOCK_INPUT_TAG)
                                            run_block_ops(
                                                fctx, block, _ops, e3,
                                                inside_grad_prefix=True,
                                                over_rows=products_over_rows)
                                            return {n: e3[n] for n in _out
                                                    if n in e3}

                                        env_sub = {u: e2[u] for u in uses_j
                                                   if u in e2}
                                        e2.update(jax.checkpoint(
                                            seg_fn,
                                            policy=ckpt_policy)(env_sub))
                                    new_carry = {
                                        n: e2[carry_map[n]]
                                        for n in carry_map}
                                    ys = {m: e2[m] for m in ys_names}
                                    return new_carry, ys

                                # named scope -> XLA op metadata: XPlane
                                # captures (profiler('dir') / Trainer
                                # trace_dir=) show this group as
                                # "scan_remat[i0+PxG]" so device timelines
                                # line up with the Program's layer
                                # structure
                                with jax.named_scope(
                                        f"scan_remat[{i0}+{P}x{G}]"):
                                    carry_f, ys = jax.lax.scan(
                                        body,
                                        carry0,
                                        (jnp.arange(G, dtype=jnp.int32),
                                         xs_stacked),
                                        length=G)
                                for on, m, k in sorted(ys_writes,
                                                       key=lambda w: w[2]):
                                    e[on] = ys[m][k]
                                for on, n in carry_writes.items():
                                    e[on] = carry_f[n]
                                fctx._op_counter = c0 + G * nr
                                reg.counter(
                                    "executor.scan_remat_groups",
                                    help="remat segment groups executed as "
                                         "lax.scan over layers").inc()
                                reg.counter(
                                    "executor.products_reading_saved",
                                    help="products of a scan-remat body "
                                         "lowered to READ the output of a "
                                         "checkpointed sub-segment").inc(
                                    len(products_reading))
                                reg.counter(
                                    "executor.products_over_rows",
                                    help="products of a scan-remat body "
                                         "lowered over X as it stands, "
                                         "with no flattening").inc(
                                    len(products_over_rows))
                                if fsdp_gather:
                                    reg.counter(
                                        "executor.fsdp_groups",
                                        help="scan groups whose stacked "
                                             "weights are fsdp-sharded "
                                             "with in-loop gathers").inc()
                                plan_log.append(
                                    {"start": i0, "period": P, "count": G,
                                     "carry": sorted(carry_map),
                                     "xs": len(xs_names),
                                     "shared": len(shared_names),
                                     "fsdp": len(fsdp_gather),
                                     "offload": off_mode,
                                     "reading": sorted(products_reading),
                                     "over_rows":
                                         sorted(products_over_rows)})
                                return True
                            except Exception as exc:
                                # classification/trace failure: restore the
                                # rng counter and run the group segment by
                                # segment through the barrier fallback —
                                # with the REASON recorded (a silent
                                # fallback at a capacity config is a
                                # runtime OOM waiting to happen)
                                fctx._op_counter = c0
                                reason = " ".join(
                                    f"{type(exc).__name__}: {exc}"
                                    .split())[:200]
                                if fsdp_gather:
                                    # the fsdp constraints are the only
                                    # delta vs the proven scan spelling:
                                    # drop them and keep the scan before
                                    # surrendering to the barrier path
                                    reg.counter(
                                        "executor.fsdp_fallbacks",
                                        help="scan groups whose fsdp "
                                             "constraints failed to trace "
                                             "(retried replicated)").inc()
                                    plan_log.append(
                                        {"start": i0, "period": P,
                                         "count": G,
                                         "fsdp_fallback": reason})
                                    return _try_scan_group(
                                        group, use_fsdp=False)
                                reg.counter(
                                    "executor.scan_remat_fallbacks",
                                    help="segment groups that fell back to "
                                         "the barrier spelling").inc()
                                plan_log.append(
                                    {"start": i0, "period": P, "count": G,
                                     "fallback": reason})
                                if _scan_strict():
                                    raise RuntimeError(
                                        f"PADDLE_TPU_SCAN_REMAT=strict: "
                                        f"uniform group at segment {i0} "
                                        f"(period {P} x {G}) failed to "
                                        f"scan: {reason}") from exc
                                return False

                        groups = _scan_groups_for(program, segments)
                        by_start = {g["start"]: g for g in groups}
                        plan_log = []
                        self.last_remat_plan = plan_log
                        si = 0
                        while si < len(segments):
                            g = by_start.get(si)
                            if g is not None and _try_scan_group(g):
                                si += g["period"] * g["count"]
                                continue
                            seg = segments[si]
                            si += 1
                            s, t = seg[0], seg[1]
                            wrap = seg[2] if len(seg) > 2 else True
                            seg_ops = block.ops[s:t]
                            if not wrap:
                                run_block_ops(
                                    fctx, block, seg_ops, e,
                                    inside_grad_prefix=True,
                                )
                                continue
                            written = {
                                n for op in seg_ops for n in op.output_names()
                            }
                            out_names = tuple(sorted(
                                written & needed_after[t]))

                            # checkpoint may trace seg_fn more than once;
                            # pin the random-op key counter to the segment
                            # start so fwd and remat derive identical keys
                            c0 = fctx._op_counter

                            def seg_fn(env_in, _ops=seg_ops, _out=out_names,
                                       _c0=c0):
                                fctx._op_counter = _c0
                                e2 = dict(env_in)
                                run_block_ops(
                                    fctx, block, _ops, e2,
                                    inside_grad_prefix=True,
                                )
                                return {n: e2[n] for n in _out if n in e2}

                            seg_uses = set()
                            for op_ in seg_ops:
                                op_uses(op_, seg_uses, set())
                            env_sub = {
                                k: e[k] for k in sorted(seg_uses) if k in e
                            }
                            outs = _remat_segment(
                                seg_fn, env_sub,
                                param_names=frozenset(param_names))
                            e.update(outs)
                    loss = e[info["loss"]]
                    aux = {n: e[n] for n in aux_names if n in e}
                    return jnp.sum(loss), aux

                tparams = {n: env[n] for n in param_names}
                if self.mesh is not None and self._fsdp_active(program):
                    # prologue/epilogue FSDP (shard_fsdp's fsdp_axes
                    # tagging: embedding tables, the LM head): the
                    # at-rest value is fsdp x tp sharded on its leading
                    # dim, but compute must see the EXPLICIT-spec
                    # (gathered) weight — the leading dim is the
                    # lookup/contraction axis, and letting GSPMD keep
                    # the shard turns the embedding lookup and the
                    # head matmul into partial sums plus per-microbatch
                    # in-loop all-reduces (measured: 26 in-loop reduce
                    # ops on dp2 x fsdp4 at accum=4).  The forward-only
                    # pin here sits OUTSIDE the accumulation loop, so
                    # the all-gather runs once per step (overlappable
                    # via PADDLE_TPU_COMM_OVERLAP) and the cotangent
                    # passes through unpinned — dW stays fsdp-replicated
                    # to the boundary exactly like the scan weights'.
                    from jax.sharding import (
                        NamedSharding as _NS, PartitionSpec as _PP)
                    from ..parallel.api import fsdp_spec_for

                    for n in param_names:
                        var = block._find_var(n)
                        if (var is None
                                or not getattr(var, "fsdp_axes", None)
                                or fsdp_spec_for(
                                    var, self.mesh, block) is None):
                            continue
                        gathered = (getattr(var, "partition_spec", None)
                                    or _PP())
                        tparams[n] = _fsdp_fwd_pin(
                            _NS(self.mesh, gathered),
                            site=f"fsdp_prologue_gather:{n}")(
                            tparams[n])
                accum = int(getattr(program, "_grad_accum", 1) or 1)
                if accum <= 1:
                    grads, aux = jax.grad(make_fwd(ctx), has_aux=True)(
                        tparams, env)
                    env.update(aux)
                else:
                    grads, aux = self._accum_grads(
                        program, block, ctx, env, tparams, make_fwd,
                        feed_names, persist_out, accum, step_key, bw)
                    env.update(aux)
                if emit_grad_norm:
                    # global grad norm BEFORE the boundary pin reads the
                    # same values either way; computing it from the dict
                    # here (one f32 sum-of-squares per param + one sqrt)
                    # keeps it a pure extra output — nothing feeds back
                    # into the update math, so every bit-exactness
                    # contract is untouched
                    parts = [jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in grads.values()]
                    grad_norm_out[0] = (jnp.sqrt(sum(parts)) if parts
                                        else jnp.zeros((), jnp.float32))
                if self.mesh is not None:
                    # Pin each gradient at the backward/optimizer boundary
                    # to its PARAMETER's sharding (replicated under plain
                    # dp, the tp spec for tp-sharded params).  ZeRO-1
                    # shards optimizer STATE, not gradients — without this
                    # pin the sharded-moment annotations propagate back
                    # through the grads into the whole backward pass,
                    # repartitioning it (measured: extra in-loop
                    # collectives in the attention scans and loss/params
                    # drifting from the replicated spelling).  With it the
                    # backward is bit-identical to ZeRO-off and only the
                    # update math reads the grad shard-locally.
                    from jax.sharding import (
                        NamedSharding, PartitionSpec as _P)
                    from ..parallel.api import grad_rs_spec_for

                    for n, g in grads.items():
                        var = block._find_var(n)
                        # the true-ZeRO-3 reduce-scatter spelling
                        # (docs/parallel.md rule 4): an fsdp-tagged
                        # parameter's gradient pins to the COMPOSED
                        # spec at the boundary, so GSPMD spells the
                        # cross-chip aggregation as reduce-scatter@fsdp
                        # and each chip receives only its shard.  The
                        # scatter happens ONCE, here — the carry stays
                        # plain P('dp') and the backward cotangents
                        # stay unpinned, so the three PR-10 placement
                        # rules survive (zero3_grad_contract enforces
                        # the shape).  PADDLE_TPU_ZERO3_RS=0 (or any
                        # fsdp_spec_for fallback) restores the
                        # replicated-grad spelling below, bit-exact.
                        rs = grad_rs_spec_for(var, self.mesh, block)
                        if rs is not None:
                            with jax.named_scope(
                                    f"pt_pin[grad_rs_boundary:{n}]"):
                                env[n + GRAD_SUFFIX] = (
                                    jax.lax.with_sharding_constraint(
                                        g, NamedSharding(self.mesh, rs)))
                            continue
                        # the replicated-grad reference spelling: the
                        # EXPLICIT spec, never fsdp-composed — the
                        # gradient stays replicated over fsdp to the
                        # boundary, where the elementwise update
                        # against the fsdp-sharded moments reads it
                        # shard-locally (a free slice, outside every
                        # loop); sharding_report accounts grads at
                        # whichever spec this pin resolves to
                        spec = (getattr(var, "partition_spec", None)
                                if var is not None else None) or _P()
                        with jax.named_scope(
                                f"pt_pin[grad_boundary:{n}]"):
                            env[n + GRAD_SUFFIX] = (
                                jax.lax.with_sharding_constraint(
                                    g, NamedSharding(self.mesh, spec)))
                else:
                    for n, g in grads.items():
                        env[n + GRAD_SUFFIX] = g
                run_block_ops(ctx, block, block.ops[bw:], env)

            new_state = {n: env[n] for n in persist_out}
            new_state[RNG_VAR] = next_key
            if emit_grad_norm:
                new_state[GRAD_NORM_VAR] = (
                    grad_norm_out[0]
                    if grad_norm_out[0] is not None
                    else jnp.zeros((), jnp.float32))
            fetches = tuple(env[n] for n in fetch_names)
            return new_state, fetches

        return step, persist_out

    def _accum_comm_mode(self, program, block, bw, mbs, carry_persist,
                         ndp):
        """Pick the accumulation-loop communication spelling:
        ``("local", None)`` — accumulate per-device partial gradients in a
        dp-sharded carry and cross-chip-reduce ONCE at the optimizer
        boundary; ``("reduce_each", reason)`` — the reference spelling
        whose per-microbatch gradients are full cross-chip values (GSPMD
        reduces — or worse, gathers the batch and replicates compute —
        inside the loop body).  Local mode needs every condition below;
        the reason string lands in ``last_accum_plan`` so a silent
        de-optimization is observable (the scan-remat fallback
        discipline)."""
        if ndp <= 1:
            return "reduce_each", "no dp mesh axis"
        if os.environ.get("PADDLE_TPU_LOCAL_ACCUM", "1").lower() in (
                "0", "", "false"):
            return "reduce_each", "PADDLE_TPU_LOCAL_ACCUM=0"
        if not mbs:
            return "reduce_each", "no batch feeds to split"
        bad = sorted(n for n, mb in mbs.items() if mb % ndp)
        if bad:
            return "reduce_each", (
                f"microbatch not divisible by dp={ndp}: {bad}")
        unsharded = []
        for n in mbs:
            var = block._find_var(n)
            spec = getattr(var, "partition_spec", None) if var else None
            if spec is None or not len(spec) or spec[0] != "dp":
                unsharded.append(n)
        if unsharded:
            return "reduce_each", (
                f"feeds not dp-batch-sharded: {sorted(unsharded)}")
        if carry_persist:
            # BN stats / metric accumulators couple device groups across
            # the batch axis — vmapped lanes would each write their own
            return "reduce_each", (
                f"forward-written persistables: {carry_persist[:3]}")
        if _rng_op_count_deep(program, block.ops[:bw]):
            # the per-lane computation shares one op key under vmap, so
            # every device group would draw the SAME dropout mask —
            # valid dropout, but not the unsharded key stream
            return "reduce_each", "stateful rng ops in the forward"
        return "local", None

    def _accum_grads(self, program, block, ctx, env, tparams, make_fwd,
                     feed_names, persist_out, accum, step_key, bw):
        """Gradient accumulation (``pt.gradient_accumulation``): slice the
        feed batch into ``accum`` microbatches, run forward+backward per
        microbatch under ``lax.scan`` (activation memory scales with the
        microbatch), accumulate gradients in float32, and return the MEAN
        gradient — the big-batch average-loss gradient when microbatches
        weigh equally.  Forward-written persistables (BN stats, metric
        accumulators) thread through the scan carry so microbatch k+1 sees
        k's updates, exactly as consecutive small steps would.

        On a mesh with a dp axis the COMM-AWARE spelling
        (``_accum_grads_local``) is preferred: the reference spelling
        below makes every microbatch's gradient a full cross-chip value,
        so GSPMD either reduces inside the loop body (accum x the
        collective bytes) or — observed on the CPU SPMD partitioner —
        all-gathers the whole batch and REPLICATES the accumulation loop
        on every chip.  Eligibility and fallback reasons:
        ``_accum_comm_mode`` / ``last_accum_plan``."""
        mbs = {}
        for n in feed_names:
            if jnp.ndim(env[n]) == 0:
                continue  # 0-d feeds (scalars) pass through unsplit
            b0 = env[n].shape[0]
            if b0 % accum:
                raise ValueError(
                    f"gradient_accumulation(micro_steps={accum}): feed "
                    f"{n!r} leading dim {b0} is not divisible")
            mbs[n] = b0 // accum
        full_b = env[sorted(mbs)[0]].shape[0] if mbs else 0

        fwd_written = {
            n for op in block.ops[:bw] for n in op.output_names()
        }
        carry_persist = sorted(
            n for n in persist_out if n in fwd_written and n in env
        )
        # aux names the forward merely passes through (optimizer-op state
        # inputs: moments, beta pows, lr — and the params themselves):
        # their env values are already authoritative, and stacking them
        # per microbatch both wastes scan-ys memory and MISCLASSIFIES in
        # the reassembly when a state var's leading dim happens to equal
        # the feed batch (e.g. a [max_len, d] positional-embedding moment
        # at batch == max_len would be "batch-leading"-reshaped).
        passthrough = {
            v.name for v in program.persistable_vars()
            if v.name not in fwd_written
        } | set(tparams)

        from ..parallel.mesh import axis_size

        ndp = axis_size(self.mesh, "dp")
        reg = _obs.get_registry()
        mode, reason = self._accum_comm_mode(
            program, block, bw, mbs, carry_persist, ndp)
        self.last_accum_plan = {"mode": mode, "accum": accum, "dp": ndp}
        if reason:
            self.last_accum_plan["reason"] = reason
        if mode == "local":
            try:
                out = self._accum_grads_local(
                    program, block, env, tparams, make_fwd, accum,
                    step_key, bw, mbs, full_b, ndp, passthrough)
                return out
            except Exception as exc:  # trace failure: reference spelling
                reg.counter(
                    "executor.accum_local_fallbacks",
                    help="accum steps that fell back to per-microbatch "
                         "reduction").inc()
                why = " ".join(
                    f"{type(exc).__name__}: {exc}".split())[:200]
                self.last_accum_plan = {
                    "mode": "reduce_each", "accum": accum, "dp": ndp,
                    "reason": f"local spelling failed: {why}"}

        def one_micro(carry, i):
            gacc, persist = carry
            e0 = dict(env)
            e0.update(persist)
            for n, mb in mbs.items():
                e0[n] = jax.lax.dynamic_slice_in_dim(
                    env[n], i * mb, mb, 0)
            fctx = LoweringCtx(
                self, program, jax.random.fold_in(step_key, i + 1))
            g, aux = jax.grad(make_fwd(fctx), has_aux=True)(tparams, e0)
            gacc = jax.tree_util.tree_map(
                lambda a, gi: a + gi.astype(jnp.float32), gacc, g)
            new_persist = {n: aux[n] for n in carry_persist}
            # params and unwritten optimizer state sit in aux too
            # (optimizer-op inputs) but env already holds the exact
            # values; stacking them across the scan would cost
            # accum x state-bytes of HBM for nothing (see ``passthrough``)
            ys = {n: v for n, v in aux.items()
                  if n not in new_persist and n not in passthrough}
            return (gacc, new_persist), ys

        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(jnp.shape(p), jnp.float32), tparams)
        p0 = {n: env[n] for n in carry_persist}
        (gsum, persist_f), ys = jax.lax.scan(
            one_micro, (g0, p0), jnp.arange(accum))
        grads = {
            n: (gsum[n] / accum).astype(env[n].dtype) for n in gsum
        }
        aux = dict(persist_f)
        aux.update(self._reassemble_accum_aux(block, env, ys, full_b, bw))
        return grads, aux

    def _accum_grads_local(self, program, block, env, tparams, make_fwd,
                           accum, step_key, bw, mbs, full_b, ndp,
                           passthrough):
        """Comm-aware gradient accumulation: one cross-chip gradient
        reduction per OPTIMIZER step instead of one per microbatch.

        The batch is regrouped so each microbatch is the union of every
        device's k-th local slice: feed ``[B, ...]`` (dp-sharded) reshapes
        to ``[ndp, accum, B/(ndp*accum), ...]`` — a shard-local reshape —
        and transposes to scan xs ``[accum, ndp, mb_g, ...]`` with the
        GROUP axis sharded over dp.  The microbatch forward+backward runs
        ``jax.vmap``-ed over that group axis, so every lane's compute is
        resident on one chip and the loop body carries NO collectives
        (``memaudit.comm_report: reduce_ops_in_loop == 0`` — also killing
        the batch-axis gathers GSPMD otherwise inserts for in-loop
        dynamic slicing).  Per-lane gradients accumulate in a dp-sharded
        ``[ndp, ...]`` float32 carry (per-device bytes == one replicated
        gradient buffer); the single sum over the group axis at the
        boundary is where XLA emits the one cross-chip reduction, feeding
        the ZeRO-sharded optimizer update directly.

        Numerics: grads are the mean over (microbatch, group) lanes —
        exactly the reference spelling's mean-of-equal-weight-microbatch
        gradients, refined to device groups (the documented
        equal-weight-mean-loss contract of ``gradient_accumulation``);
        float summation ORDER differs, so vs dp=1 this is
        close-not-bit-identical, like any resharding."""
        from jax.sharding import NamedSharding

        mesh = self.mesh

        def dp_sharded(x, lead=0):
            # the blessed accum-carry pin (docs/parallel.md rule 3):
            # plain dp on the group axis, marked pt_pin[accum_carry] so
            # the constraint-placement check can verify BOTH the site
            # and the spec (an fsdp-composed carry is an error even
            # when marked)
            with jax.named_scope("pt_pin[accum_carry]"):
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, _accum_carry_spec(lead)))

        xs_feeds = {}
        for n, mb in mbs.items():
            v = env[n]
            mb_g = mb // ndp
            g = dp_sharded(jnp.reshape(
                v, (ndp, accum, mb_g) + tuple(v.shape[1:])))
            xs_feeds[n] = dp_sharded(jnp.moveaxis(g, 0, 1), lead=1)

        def one_micro(gacc, xs):
            i, feeds_k = xs
            fctx = LoweringCtx(
                self, program, jax.random.fold_in(step_key, i + 1),
                batch_axis=None)  # the lane vmap below holds dp
            fwd = make_fwd(fctx)

            def lane(feeds_lane):
                e0 = dict(env)
                e0.update(feeds_lane)
                return jax.grad(fwd, has_aux=True)(tparams, e0)

            # spmd_axis_name: the group axis IS the dp split, so a
            # kernel op's shard_map inside a lane gets dp on the lane
            # dim instead of an all-gathered copy of every lane
            g, aux = jax.vmap(lane, spmd_axis_name="dp")(feeds_k)
            # the [ndp, ...] f32 carry shards ONLY its group axis over
            # dp; an FSDP weight's dW deliberately stays replicated
            # over fsdp through the loops (an fsdp-sharded constraint
            # here makes GSPMD feature-shard the saved residuals,
            # turning in-body LN/softmax reductions into in-loop
            # all-reduces) — the optimizer-boundary pin reshards it
            # once, outside every loop
            gacc = jax.tree_util.tree_map(
                lambda a, gi: dp_sharded(a + gi.astype(jnp.float32)),
                gacc, g)
            # params/unwritten optimizer state sit in aux (optimizer-op
            # inputs) but env already holds them — stacking
            # [accum, ndp, ...] copies would burn accum x ndp x
            # state-bytes of scan-ys for nothing
            ys = {n: v for n, v in aux.items() if n not in passthrough}
            return gacc, ys

        g0 = jax.tree_util.tree_map(
            lambda p: dp_sharded(
                jnp.zeros((ndp,) + tuple(jnp.shape(p)), jnp.float32)),
            tparams)
        gacc, ys = jax.lax.scan(
            one_micro, g0, (jnp.arange(accum), xs_feeds))
        from ..parallel.api import grad_rs_spec_for

        def _finalize(n):
            return (jnp.sum(gacc[n], axis=0) / (ndp * accum)).astype(
                env[n].dtype)

        grads = {}
        for n in gacc:
            var = block._find_var(n)
            # the grad-RS provenance scope: this per-param sum over the
            # dp-sharded group axis is WHERE the one cross-chip
            # gradient reduction materializes, and under the
            # reduce-scatter spelling its operand is already the
            # fsdp-shard (GSPMD pushes the boundary pin's slice into
            # the carry — slice-before-reduce, valid because dW is
            # fsdp-replicated).  Scoping the sum per param threads
            # ``pt_pin[grad_rs_boundary:<param>]`` into the derived
            # all-reduce's op_name, which is what lets the CommPlan
            # extractor canonicalize it to a logical reduce-scatter
            # with per-grad attribution (analysis/comm/plan.py).
            if (var is not None
                    and grad_rs_spec_for(var, self.mesh, block)
                    is not None):
                with jax.named_scope(f"pt_pin[grad_rs_boundary:{n}]"):
                    grads[n] = _finalize(n)
            else:
                grads[n] = _finalize(n)
        return grads, self._reassemble_accum_aux(
            block, env, ys, full_b, bw, local_ndp=ndp)

    def _reassemble_accum_aux(self, block, env, ys, full_b, bw,
                              local_ndp=0):
        """Reassemble scan-stacked aux fetches back to their big-batch
        values.  ``ys`` entries carry a leading ``[accum, ...]`` axis —
        or ``[accum, ndp, ...]`` when ``local_ndp`` is set (the
        comm-aware path's vmapped device groups)."""
        producer = {}
        for op in block.ops[:bw]:
            for out_n in op.output_names():
                producer[out_n] = op

        def _static_batch_leading(name):
            var = block._find_var(name)
            vshape = tuple(var.shape) if var is not None else ()
            return len(vshape) >= 1 and (
                vshape[0] == -1 or (full_b and vshape[0] == full_b))

        aux = {}

        # additive combiners through which batch-sum-ness propagates
        # linearly: sum(microbatch values) reassembles the big-batch value
        # (layers.sums appends op type "sum", so no "sums" entry exists)
        _ADDITIVE = {"elementwise_add", "elementwise_sub", "sum", "scale"}
        _bs_memo = {}
        _bs_cap_hits = [0]

        def _is_batch_sum(name, _depth=0):
            """Transitive classification: True when the fetch is a pure
            batch-reduction sum (directly a reduce_sum over batch data, or
            an additive composite of such), so the big-batch value is the
            SUM of the microbatch values.  A composite mixing sum-like and
            non-sum-like terms has no exact reassembly — raise rather than
            silently return 1/accum of the truth.  Memoized per var name:
            a shared-subexpression additive DAG (x = x + x doubling) is
            linear work, not exponential.  A result whose subtree hit the
            depth cap is conservative-for-this-path, not a property of
            the var — it must NOT be memoized, or a later shallower query
            would read the poisoned value (the cap-hit counter detects
            taint anywhere in the subtree, short-circuiting included)."""
            if _depth > 64:
                _bs_cap_hits[0] += 1
                return False  # depth-capped: conservative
            if name in _bs_memo:
                return _bs_memo[name]
            before = _bs_cap_hits[0]
            r = _is_batch_sum_uncached(name, _depth)
            if _bs_cap_hits[0] == before:
                _bs_memo[name] = r
            return r

        def _is_batch_sum_uncached(name, _depth):
            op = producer.get(name)
            if op is None:
                return False
            ins = [i_n for ns_ in op.inputs.values() for i_n in ns_]
            if op.type == "reduce_sum":
                return any(_static_batch_leading(i) for i in ins) or all(
                    _is_batch_sum(i, _depth + 1) for i in ins)
            if op.type in _ADDITIVE:
                flags = [_is_batch_sum(i, _depth + 1) for i in ins]
                if op.type == "scale" and any(flags) and (
                        float(op.attrs.get("bias", 0.0)) != 0.0):
                    # X*s + b over a batch sum: summing microbatch
                    # values would inflate the bias term accum-fold
                    raise ValueError(
                        f"gradient_accumulation cannot reassemble fetch "
                        f"{name!r}: scale with a nonzero bias over a "
                        f"batch-sum term; apply the bias on the host")
                if any(flags) and not all(flags):
                    raise ValueError(
                        f"gradient_accumulation cannot reassemble fetch "
                        f"{name!r}: it mixes batch-sum terms with "
                        f"non-sum terms (op {op.type!r}); fetch the "
                        f"parts separately and combine on the host")
                return all(flags) and bool(flags)
            return False

        lead = 2 if local_ndp else 1
        for n, y in ys.items():
            # classify by the var's STATIC leading dim, not the runtime
            # shape (a [1]-shaped mean fetch with microbatch 1 must not be
            # mistaken for batch data): -1 or the full feed batch means
            # batch-leading -> microbatch results concatenate back.
            if y.ndim >= lead + 1 and _static_batch_leading(n):
                if local_ndp:
                    # [accum, ndp, mb_g, ...] -> device-major, then
                    # microbatch, then row: the exact original global
                    # batch order (each device's shard was split into
                    # accum contiguous slices)
                    aux[n] = jnp.moveaxis(y, 0, 1).reshape(
                        (-1,) + y.shape[3:])
                else:
                    aux[n] = y.reshape((-1,) + y.shape[2:])
                continue
            axes = tuple(range(lead))
            if _is_batch_sum(n):
                # a reduction OVER the batch: the big-batch sum is the
                # sum of the microbatch (x group) sums.  (reduce_sum of
                # batch-independent tensors — weight norms — is
                # microbatch-invariant and falls through to the mean,
                # which is then exact.)
                aux[n] = jnp.sum(y, axis=axes)
            elif jnp.issubdtype(y.dtype, jnp.inexact):
                # scalar metrics (avg loss): mean of equal-weight
                # microbatch (x group) averages == the big-batch average
                aux[n] = jnp.mean(y, axis=axes)
            else:
                aux[n] = y[(-1,) * lead] if local_ndp else y[-1]
        return aux

    def _compile(self, program, feed_names, fetch_names, state_names):
        step, persist_out = self.lower(
            program, feed_names, fetch_names, state_names)
        jit_kwargs = {}
        if self.donate_state:
            jit_kwargs["donate_argnums"] = 0
        if self.mesh is not None:
            from ..parallel.api import compile_shardings

            in_shardings, out_shardings = compile_shardings(
                self.mesh, program, feed_names, fetch_names, state_names,
                out_state_names=persist_out,
                extra_state=((GRAD_NORM_VAR,)
                             if _emits_grad_norm(program) else ()),
            )
            # NamedShardings carry the mesh, so no ambient mesh context is
            # needed around the jitted call.
            jit_kwargs["in_shardings"] = in_shardings
            jit_kwargs["out_shardings"] = out_shardings
        return jax.jit(step, **jit_kwargs)
