"""Static-analysis pass framework: check registry, artifact context,
structured findings.

paddle_tpu proves its hardest invariants statically — scan-remat
locality and the one-reduction-per-step comm audit run on compiled HLO,
not timing — but until this engine each check was a bespoke function.
Here every invariant is a registered *check* over one of three artifact
levels:

* ``program`` — the Program IR itself (``core/program.py``): pure
  Python, no tracing, runs in microseconds;
* ``jaxpr``   — the traced training step (``Executor.lower`` +
  ``jax.jit(...).trace``): sees the real post-autodiff computation,
  scan structure, checkpoint names;
* ``hlo``     — the partitioned/optimized executable (the existing AOT
  compile path): sees what XLA actually scheduled — collectives, buffer
  donation, the memory high-water.

A check is a function ``check(ctx) -> iterable[Finding]`` registered
with ``@register_check(id, level)``.  ``lint(program, feed, fetch_list)``
builds the artifacts lazily (a program-level-only lint never imports
jax), runs every enabled check, and returns an ``AnalysisReport``;
``strict=True`` raises ``AnalysisError`` when any error-severity finding
survives.  Nothing here ever *executes* a training step — compile yes,
run no (the point is catching an allocator failure before any
step allocates).

Registering a new check::

    from paddle_tpu.analysis import register_check, Finding

    @register_check("program.my-invariant", level="program")
    def my_invariant(ctx):
        for op in ctx.program.global_block().ops:
            if bad(op):
                yield ctx.finding(
                    "program.my-invariant", "error", "program",
                    location=f"op {op.type}", message="...",
                    hint="how to fix it")
"""

import os

__all__ = [
    "SEVERITIES", "LEVELS", "Finding", "AnalysisError", "AnalysisReport",
    "CheckContext", "ArtifactError", "register_check", "registered_checks",
    "lint", "compile_findings", "preflight_hbm", "report_json",
    "report_from_json", "LINT_JSON_SCHEMA_VERSION",
]

SEVERITIES = ("info", "warning", "error")
LEVELS = ("program", "jaxpr", "hlo")


class Finding:
    """One structured lint finding: check id, severity, artifact level,
    location, human message, and a remediation hint."""

    __slots__ = ("check", "severity", "level", "location", "message",
                 "hint", "data")

    def __init__(self, check, severity, level, location, message,
                 hint="", data=None):
        if severity not in SEVERITIES:
            raise ValueError(f"severity must be one of {SEVERITIES}, "
                             f"got {severity!r}")
        if level not in LEVELS:
            raise ValueError(f"level must be one of {LEVELS}, "
                             f"got {level!r}")
        self.check = check
        self.severity = severity
        self.level = level
        self.location = location
        self.message = message
        self.hint = hint
        self.data = dict(data or {})

    def to_dict(self):
        d = {"check": self.check, "severity": self.severity,
             "level": self.level, "location": self.location,
             "message": self.message, "hint": self.hint}
        if self.data:
            d["data"] = self.data
        return d

    def __repr__(self):
        return (f"[{self.severity}] {self.check} @ {self.location}: "
                f"{self.message}")


class AnalysisError(RuntimeError):
    """Raised by strict-mode lint when error-severity findings survive."""

    def __init__(self, findings):
        self.findings = list(findings)
        lines = [f"lint found {len(self.findings)} error(s):"]
        lines += [f"  {f!r}" for f in self.findings[:10]]
        if len(self.findings) > 10:
            lines.append(f"  ... and {len(self.findings) - 10} more")
        super().__init__("\n".join(lines))


class ArtifactError(RuntimeError):
    """An artifact level could not be built (trace/compile failed, feed
    missing...).  Checks raising this are reported once per level as an
    ``analysis.artifact`` info finding, not as a crash."""


class AnalysisReport:
    """Ordered findings of one lint run."""

    def __init__(self, findings=()):
        self.findings = list(findings)

    def add(self, finding):
        self.findings.append(finding)

    def extend(self, findings):
        self.findings.extend(findings)

    def __iter__(self):
        return iter(self.findings)

    def __len__(self):
        return len(self.findings)

    @property
    def errors(self):
        return [f for f in self.findings if f.severity == "error"]

    @property
    def warnings(self):
        return [f for f in self.findings if f.severity == "warning"]

    @property
    def ok(self):
        return not self.errors

    def ids(self):
        return sorted({f.check for f in self.findings})

    def by_check(self, check_id):
        return [f for f in self.findings if f.check == check_id]

    def counts(self):
        out = {s: 0 for s in SEVERITIES}
        for f in self.findings:
            out[f.severity] += 1
        return out

    def to_dict(self):
        return {"findings": [f.to_dict() for f in self.findings],
                "counts": self.counts(), "ok": self.ok}

    def summary(self):
        c = self.counts()
        return (f"{len(self.findings)} finding(s): {c['error']} error, "
                f"{c['warning']} warning, {c['info']} info")

    def raise_for_errors(self):
        if self.errors:
            raise AnalysisError(self.errors)
        return self


class CheckSpec:
    __slots__ = ("id", "level", "fn")

    def __init__(self, check_id, level, fn):
        self.id = check_id
        self.level = level
        self.fn = fn


_CHECKS = {}


def register_check(check_id, level):
    """Register a check function ``fn(ctx) -> iterable[Finding]`` under
    ``check_id`` at artifact ``level`` ('program' | 'jaxpr' | 'hlo')."""
    if level not in LEVELS:
        raise ValueError(f"level must be one of {LEVELS}, got {level!r}")

    def deco(fn):
        if check_id in _CHECKS:
            raise ValueError(f"check {check_id!r} registered twice")
        _CHECKS[check_id] = CheckSpec(check_id, level, fn)
        return fn

    return deco


def registered_checks(level=None):
    """Registered CheckSpecs, optionally filtered by level."""
    return [s for s in _CHECKS.values()
            if level is None or s.level == level]


class CheckContext:
    """Lazy artifact store one lint run's checks share.

    Artifacts build on first access and cache: ``prepared`` (Executor +
    feed/state signature), ``traced`` / ``jaxpr`` / ``remat_plan`` /
    ``walk``, ``compiled`` / ``hlo_text`` / ``memstats`` / ``comm``.
    ``seed(name, value)`` pre-loads an artifact the caller already has
    (the Executor's compile-time fold-in seeds ``compiled``/``memstats``/
    ``comm`` so linting costs no extra compile)."""

    def __init__(self, program, feed=None, fetch_list=None, scope=None,
                 mesh=None, layer_count=None, hbm_budget=None, donate=True,
                 in_loop_expected=False, label=None):
        self.program = program
        self.feed = feed
        self.fetch_list = list(fetch_list or [])
        self.scope = scope
        self.mesh = mesh
        self.layer_count = layer_count
        self.hbm_budget = hbm_budget
        self.donate = donate
        self.in_loop_expected = in_loop_expected
        self.label = label
        self._cache = {}

    def seed(self, name, value):
        self._cache[name] = value
        return self

    def finding(self, check, severity, level, location, message, hint="",
                data=None):
        return Finding(check, severity, level, location, message,
                       hint=hint, data=data)

    @property
    def fetch_names(self):
        return [v.name if hasattr(v, "name") else str(v)
                for v in self.fetch_list]

    # -- artifact builders -------------------------------------------------
    def _get(self, name, builder):
        if name not in self._cache:
            try:
                self._cache[name] = builder()
            except ArtifactError:
                raise
            except Exception as e:
                raise ArtifactError(
                    f"{name} unavailable: {type(e).__name__}: {e}") from e
        return self._cache[name]

    @property
    def prepared(self):
        """(exe, feed_names, fetch_names, feed_vals, state_names, state)
        — the Executor's run prologue over a synthetic zero feed/state
        when the caller supplied none (shape/dtype-true, no initializer
        op ever executes)."""
        return self._get("prepared", self._build_prepared)

    def _build_prepared(self):
        import numpy as np

        from ..core.executor import Executor
        from ..core.scope import Scope

        if self.program is None:
            raise ArtifactError("no program")
        block = self.program.global_block()
        feed = dict(self.feed or {})
        for v in block.vars.values():
            if getattr(v, "is_data", False) and v.name not in feed:
                shape = tuple(2 if s is None or int(s) < 0 else int(s)
                              for s in (v.shape or (1,)))
                feed[v.name] = np.zeros(shape, np.dtype(v.dtype))
        scope = self.scope
        if scope is None:
            scope = Scope()
            for v in self.program.persistable_vars():
                shape = tuple(int(s) if s and int(s) > 0 else 1
                              for s in v.shape)
                scope.set(v.name, np.zeros(shape, np.dtype(v.dtype)))
        exe = Executor(mesh=self.mesh, donate_state=self.donate)
        (program, scope, feed_names, fetch_names, feed_vals, state_names,
         state, _sig) = exe._prepare(self.program, feed, self.fetch_list,
                                     scope)
        return (exe, feed_names, fetch_names, feed_vals, state_names,
                state)

    @property
    def traced(self):
        return self._get("traced", self._build_traced)

    def _build_traced(self):
        (exe, feed_names, fetch_names, feed_vals, state_names,
         state) = self.prepared
        # the Executor's own jit wrapper: donation and (on a mesh) the
        # compile_shardings annotations — the trace must see the step
        # exactly as production compiles it, or GSPMD never partitions
        # and the comm checks see an empty module
        jitted = exe._compile(
            self.program, feed_names, fetch_names, state_names)
        traced = jitted.trace(state, *feed_vals)
        # the trace populated the executor's remat plan — snapshot it
        # before anything retraces
        self._cache["remat_plan"] = list(
            getattr(exe, "last_remat_plan", []) or [])
        return traced

    @property
    def jaxpr(self):
        return self._get("jaxpr", lambda: self.traced.jaxpr)

    @property
    def remat_plan(self):
        if "remat_plan" not in self._cache:
            self.traced  # noqa: B018 — building it fills the plan
        return self._cache.get("remat_plan", [])

    @property
    def walk(self):
        """The shared one-pass jaxpr walk (``jaxpr_tools.walk_report``)
        with layer-count hypotheses from the caller plus every scan-remat
        group's repeat count."""
        return self._get("walk", self._build_walk)

    def _build_walk(self):
        from .jaxpr_tools import walk_report

        counts = {self.layer_count} if self.layer_count else set()
        for g in self.remat_plan:
            counts.add(g.get("count"))
        return walk_report(self.jaxpr, layer_counts=counts)

    @property
    def compiled(self):
        return self._get("compiled",
                         lambda: self.traced.lower().compile())

    @property
    def hlo_text(self):
        def build():
            try:
                return self.compiled.as_text() or ""
            except ArtifactError:
                raise
            except Exception:
                return ""
        return self._get("hlo_text", build)

    @property
    def memstats(self):
        from .hlo_tools import compiled_memory_stats

        return self._get(
            "memstats", lambda: compiled_memory_stats(self.compiled))

    @property
    def comm(self):
        from .hlo_tools import hlo_comm_report

        return self._get(
            "comm",
            lambda: hlo_comm_report(self.hlo_text)
            if self.hlo_text else {})

    @property
    def comm_plan(self):
        """The structured CommPlan of the compiled step
        (``analysis.comm.extract_comm_plan``): every collective's kind,
        recovered mesh axes, bytes, loop membership, phase and
        provenance.  The Executor's fold-in seeds it from the compile
        it already did (``exe.last_comm_plan``)."""
        from .comm.plan import extract_comm_plan

        return self._get(
            "comm_plan",
            lambda: extract_comm_plan(
                self.hlo_text, mesh=self.mesh, label=self.label))



def _run_checks(ctx, specs, report):
    """Run checks against a context, containing failures: an artifact
    failure is reported once per (level, reason); a check crash becomes
    a warning finding instead of killing the run."""
    artifact_failures = set()
    for spec in specs:
        try:
            report.extend(spec.fn(ctx) or ())
        except ArtifactError as e:
            key = (spec.level, str(e))
            if key not in artifact_failures:
                artifact_failures.add(key)
                report.add(Finding(
                    "analysis.artifact", "info", spec.level, spec.id,
                    f"{spec.level}-level checks skipped: {e}",
                    hint="pass feed/fetch_list (and a scope holding "
                         "initialized parameters) so the step can be "
                         "traced and compiled"))
        except Exception as e:  # noqa: BLE001 — checks must not kill lint
            report.add(Finding(
                "analysis.check-crash", "warning", spec.level, spec.id,
                f"check crashed: {type(e).__name__}: {e}",
                hint="report/fix the check; its invariant was NOT "
                     "verified"))
    return report


def lint(program=None, feed=None, fetch_list=None, scope=None,
         levels=LEVELS, checks=None, strict=False, mesh=None,
         layer_count=None, hbm_budget=None, donate=True,
         in_loop_expected=False):
    """Run the registered static checks over ``program`` and return an
    ``AnalysisReport``.

    ``feed``/``fetch_list``/``scope`` feed the jaxpr/hlo artifact levels
    (missing feeds and parameters are synthesized as zeros from the
    declared shapes — nothing random runs, nothing executes a step).
    ``levels``/``checks`` restrict what runs; ``layer_count`` sharpens
    the layer-stacked probes; ``hbm_budget`` (bytes) overrides the
    device's reported capacity for the HBM preflight; ``strict=True``
    raises ``AnalysisError`` when error-severity findings survive.
    """
    from ..core.program import default_main_program

    unknown = [lvl for lvl in levels if lvl not in LEVELS]
    if unknown:
        raise ValueError(
            f"unknown artifact level(s) {unknown}; valid: {list(LEVELS)}")
    program = program or default_main_program()
    ctx = CheckContext(
        program, feed=feed, fetch_list=fetch_list, scope=scope, mesh=mesh,
        layer_count=layer_count, hbm_budget=hbm_budget, donate=donate,
        in_loop_expected=in_loop_expected)
    specs = [s for s in _CHECKS.values() if s.level in levels
             and (checks is None or s.id in checks)]
    report = _run_checks(ctx, specs, AnalysisReport())
    if strict:
        report.raise_for_errors()
    return report


def compile_findings(program=None, fetch_names=(), compiled=None,
                     memstats=None, comm=None, in_loop_expected=False,
                     donate=True, hbm_budget=None, kernel_backends=None,
                     mesh=None, comm_plan=None, label=None):
    """The Executor's compile-time fold-in: run the program-level checks
    plus the hlo-level checks over artifacts the compile already
    produced (no extra trace or compile).  Returns a list of Findings —
    the Executor summarizes them into ``last_step_cost``.

    ``kernel_backends`` is the kernel registry's per-op-class resolution
    snapshot of this compile (``last_step_cost["kernel_backends"]``):
    the jaxpr-level ``jaxpr.kernel-backend`` check needs a traced jaxpr
    the fold-in deliberately does not produce, so its timed-run form is
    evaluated here from the snapshot alone — Mosaic backends resolved
    on a non-TPU platform inside a timed-run region mean interpret-mode
    kernels in a timed measurement (docs/kernels.md)."""
    ctx = CheckContext(
        program, fetch_list=list(fetch_names), donate=donate,
        hbm_budget=hbm_budget, in_loop_expected=in_loop_expected,
        mesh=mesh, label=label)
    if compiled is not None:
        ctx.seed("compiled", compiled)
    if memstats is not None:
        ctx.seed("memstats", memstats)
    if comm is not None:
        ctx.seed("comm", comm)
    elif compiled is None:
        ctx.seed("comm", {})
    if comm_plan is not None:
        ctx.seed("comm_plan", comm_plan)
    elif compiled is None or mesh is None:
        # off-mesh there are no collectives and no axes to attribute:
        # seed the empty plan so no comm check forces an expensive
        # compiled.as_text() render (the comm={} discipline)
        from .comm.plan import CommPlan

        ctx.seed("comm_plan", CommPlan([], {}, label))
    specs = []
    if program is not None:
        specs += [s for s in _CHECKS.values() if s.level == "program"]
    if compiled is not None or memstats is not None:
        specs += [s for s in _CHECKS.values() if s.level == "hlo"]
    report = _run_checks(ctx, specs, AnalysisReport())
    # artifact-skip notes are lint() UX; the fold-in only wants real
    # findings
    findings = [f for f in report if f.check != "analysis.artifact"]
    findings += _timed_run_backend_findings(kernel_backends)
    return findings


def _timed_run_backend_findings(kernel_backends):
    """The registry-snapshot form of ``jaxpr.kernel-backend``: inside a
    timed-run region, any op class resolved to an interpret-mode Mosaic
    backend (``pallas_tpu`` off-TPU) is an error — the timed row would
    ship a simulation, not a measurement."""
    if not kernel_backends:
        return []
    try:
        import jax

        from ..kernels import timed_run_active

        if not timed_run_active() or jax.default_backend() == "tpu":
            return []
    except Exception:  # noqa: BLE001 — lint must never block a compile
        return []
    ops = sorted(op for op, b in kernel_backends.items()
                 if b == "pallas_tpu")
    if not ops:
        return []
    return [Finding(
        "jaxpr.kernel-backend", "error", "jaxpr", "kernel registry",
        f"op class(es) {', '.join(ops)} resolved to pallas_tpu on a "
        f"non-TPU platform inside a timed-run region — the kernels run "
        f"in Pallas interpret mode, so the timing is a simulation "
        f"artifact, not a measurement",
        hint="route timed off-TPU runs to the XLA reference "
             "(PADDLE_TPU_KERNEL_BACKEND=xla_ref or a per-op "
             "PADDLE_TPU_KERNEL_BACKEND_<OP> override) or run on the "
             "hardware the kernels target",
        data={"kernel_backends": dict(kernel_backends)})]


def preflight_hbm(high_water_bytes, budget_bytes, context=""):
    """The static HBM preflight as a pure helper: compare a compiled
    step's ``hbm_high_water_bytes`` against a device budget and return
    the error Finding list ([] when it fits or either figure is
    unknown).  The tuner's search consumes this (``tune/search.py``):
    an allocator failure is flagged before any step executes."""
    if not high_water_bytes or not budget_bytes:
        return []
    if high_water_bytes <= budget_bytes:
        return []
    where = f" at {context}" if context else ""
    return [Finding(
        "hlo.hbm-preflight", "error", "hlo", context or "step",
        f"RESOURCE_EXHAUSTED (preflight): compiled hbm high-water "
        f"{high_water_bytes / (1 << 30):.2f} GiB > device limit "
        f"{budget_bytes / (1 << 30):.2f} GiB{where}",
        hint="reduce the sequence length / batch, enable "
             "memory_optimize(policy='offload'|'full') or "
             "gradient_accumulation, or shard over more chips",
        data={"hbm_high_water_bytes": int(high_water_bytes),
              "budget_bytes": int(budget_bytes)})]


# the versioned ``--lint --json`` output contract.  Bump ONLY when a
# key is renamed/removed or a meaning changes; adding keys is
# backward-compatible and needs no bump.  CI consumers pin on this.
LINT_JSON_SCHEMA_VERSION = 1

_SEV_RANK = {s: i for i, s in enumerate(SEVERITIES)}


def report_json(report, levels=None):
    """The schema-versioned JSON form of an ``AnalysisReport`` — the
    ``python -m paddle_tpu --lint --json`` output contract.

    Stable keys: ``schema_version``, ``levels`` (the artifact levels
    that ran), ``findings`` (each with ALL of check / severity / level /
    location / message / hint / data — ``data`` is ``{}`` when a check
    attached none), ``counts`` and ``ok``.  Findings sort by severity
    (errors first), then check id, location and message, so the output
    is deterministic for diffing.  ``report_from_json`` round-trips."""
    findings = sorted(
        report.findings,
        key=lambda f: (-_SEV_RANK[f.severity], f.check, f.location,
                       f.message))
    return {
        "schema_version": LINT_JSON_SCHEMA_VERSION,
        "levels": list(levels if levels is not None else LEVELS),
        "findings": [
            {"check": f.check, "severity": f.severity, "level": f.level,
             "location": f.location, "message": f.message,
             "hint": f.hint, "data": dict(f.data)}
            for f in findings
        ],
        "counts": report.counts(),
        "ok": report.ok,
    }


def report_from_json(obj):
    """Rebuild an ``AnalysisReport`` from ``report_json`` output (the
    round-trip half of the contract).  Refuses newer schema versions —
    a consumer built against v1 must not silently misread v2."""
    version = obj.get("schema_version")
    if version is None or int(version) > LINT_JSON_SCHEMA_VERSION:
        raise ValueError(
            f"unsupported lint JSON schema_version {version!r} "
            f"(this build reads <= {LINT_JSON_SCHEMA_VERSION})")
    return AnalysisReport([
        Finding(f["check"], f["severity"], f["level"], f["location"],
                f["message"], hint=f.get("hint", ""),
                data=f.get("data") or None)
        for f in obj.get("findings", ())
    ])


def lint_enabled():
    """The PADDLE_TPU_LINT kill switch (default on) — gates the
    Executor's compile-time fold-in."""
    return os.environ.get("PADDLE_TPU_LINT", "1").lower() not in (
        "0", "", "false")
