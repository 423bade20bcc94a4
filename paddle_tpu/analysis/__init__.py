"""paddle_tpu.analysis — the Program/HLO static-analysis engine.

A lint pass framework over the three artifact levels a training step
passes through (Program IR -> traced jaxpr -> partitioned/optimized
HLO), with structured findings and a strict mode that raises.  See
``docs/analysis.md`` for the check catalog, the severity policy and how
to register a new check.

    import paddle_tpu as pt

    report = pt.analysis.lint(main_prog, feed, [loss])
    for f in report:
        print(f)                  # [error] hlo.hbm-preflight @ ...
    report.raise_for_errors()     # or lint(..., strict=True)

CLI: ``python -m paddle_tpu --lint <config.py>``; every check has a
planted defect and a clean program in ``tests/test_analysis.py`` and
``tests/test_comm_plan.py``.
The Executor also folds the program- and hlo-level
findings of every compile into ``exe.last_step_cost``
(``lint_findings`` / ``lint_errors`` / ``lint_checks``; kill switch
``PADDLE_TPU_LINT=0``) and the trainer JSONL.

The artifact-level TOOLS live in submodules and are imported from
there, not re-exported here: ``analysis.jaxpr_tools`` (the shared jaxpr
walk, the checkpoint-name tags), ``analysis.hlo_tools``
(``hlo_comm_report``, ``compiled_memory_stats``, ``shape_pattern``) and
``analysis.comm`` (CommPlan extraction, CommContracts, ``comm_diff`` —
docs/analysis.md "Communication contracts").  This package's namespace
is the pass FRAMEWORK surface only; the old ``core/memaudit.py``-parity
re-exports are gone along with the shim module itself.
"""

from .framework import (
    SEVERITIES,
    LEVELS,
    Finding,
    AnalysisError,
    AnalysisReport,
    ArtifactError,
    CheckContext,
    register_check,
    registered_checks,
    lint,
    compile_findings,
    preflight_hbm,
    lint_enabled,
    report_json,
    report_from_json,
    LINT_JSON_SCHEMA_VERSION,
)

# importing the check modules registers the seeded checks
from . import program_checks  # noqa: F401
from . import jaxpr_checks  # noqa: F401
from . import hlo_checks  # noqa: F401
from . import comm  # noqa: F401 — registers the comm-plan checks
from .hlo_checks import donation_findings

__all__ = [
    "SEVERITIES", "LEVELS", "Finding", "AnalysisError", "AnalysisReport",
    "ArtifactError", "CheckContext", "register_check", "registered_checks",
    "lint", "compile_findings", "preflight_hbm", "lint_enabled",
    "report_json", "report_from_json", "LINT_JSON_SCHEMA_VERSION",
    "donation_findings",
    "audit_program",
    "comm",
]


def audit_program(program, feed, fetch_list, scope=None, layer_count=None,
                  compile_stats=True, absent_shapes=()):
    """Lower ``program`` through a fresh Executor, trace the full step
    (forward+backward+optimizer) and return ``jaxpr_report`` extended
    with compile-time memory figures — the PR 4 audit entry point, now
    running on the pass framework's artifact context.

    ``absent_shapes``: iterable of shape tuples that must NOT appear in
    the optimized HLO text (e.g. ``(num_layers, t, d_model)`` — the
    temp that overflowed round 5's flagship); hit counts land in
    ``report["absent_shape_hits"]``.

    The scope must already hold the program's parameters (run the
    startup program into it first).  CPU-safe: used by
    ``tests/test_memory_engine.py``.
    """
    from .hlo_tools import shape_pattern
    from .jaxpr_tools import jaxpr_report

    ctx = CheckContext(program, feed=feed, fetch_list=fetch_list,
                       scope=scope, layer_count=layer_count,
                       donate=False)
    report = jaxpr_report(ctx.jaxpr, layer_count=layer_count)
    report["scan_remat_plan"] = list(ctx.remat_plan)
    if compile_stats:
        report.update(ctx.memstats)
        if absent_shapes:
            text = ctx.hlo_text
            report["absent_shape_hits"] = {
                tuple(s): len(shape_pattern(s).findall(text))
                for s in absent_shapes
            }
    return report
