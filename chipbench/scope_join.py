"""The device's seconds under the names the program gave its sub-layers:
one join a run, shared by the ``step.*_busy_share`` and
``train.*_busy_share`` readers.

The program names its work where the model is written
(``jax.named_scope`` of a kind from ``paddle_tpu.observability.trace.
KINDS``), keeps every executable it compiles, and on request builds the
map from HLO instruction to named scope (``trace.device_scopes()``, in
this process, after the window: the registry is the process's, as the
metrics registry ``executor.run_host_ms`` reads).  The trace names an
operation by its whole HLO instruction and the module it ran in; the
join (``trace.device_seconds_by_scope``) is by (module, instruction) and
counts SELF seconds, so a loop is not counted with its body.  A program
without the map (a parent of the PR that brought it) gives nothing to
read: every reader built on this returns ``None``.

A share is percent of the seconds the join saw, which is the busy union
of the traced window (``facts["trace"]["busy_s"]``) to within rounding,
decode chunk and prefill pieces together; the shares of a cell's readers
sum to 100.  ``facts["device_scopes"]`` stands in for the process's map
where a test brings a recorded one.  What the join costs is printed on
standard error, once a run."""

import sys
import time

GLUE = ("embed", "norm", "cache", None)
_joined = {}


def joined(facts):
    """``{"total", "kinds": {kind: s}, "by": {(kind, phase): s},
    "unnamed": s}`` of the run's trace, or None where there is no trace
    or no map."""
    path = facts.get("trace_path")
    if not facts.get("trace") or not path:
        return None
    try:
        from paddle_tpu.observability import trace
    except ImportError:
        return None
    if not hasattr(trace, "device_seconds_by_scope"):
        return None
    if path not in _joined:
        t0 = time.perf_counter()
        scopes = facts.get("device_scopes")
        if scopes is None:
            scopes = trace.device_scopes()
        t1 = time.perf_counter()
        got = trace.device_seconds_by_scope(path, scopes) if scopes else None
        t2 = time.perf_counter()
        print(f"chipbench: scope join: {len(scopes)} executables, map "
              f"{t1 - t0:.2f} s, join {t2 - t1:.2f} s", file=sys.stderr)
        _joined[path] = _fold(got)
        _report(got)
    return _joined[path]


def _report(got, top=24):
    """The by-(module, kind, phase) table and the largest operations with
    the names the program gave them, on standard error: what PERF.md's
    breakdowns are written from."""
    if not got or not got["total"]:
        return
    total = got["total"]
    for key, s in sorted(got["seconds"].items(), key=lambda kv: -kv[1]):
        print(f"chipbench: scope {100 * s / total:6.2f}% {s:.5f} s {key}",
              file=sys.stderr)
    rows = [(s, key, kind, phase, path)
            for key, (s, kind, phase, path) in got.get("ops", {}).items()]
    rows += [(s, key, "UNNAMED", None, "") for key, s in
             got["unnamed"].items()]
    for s, key, kind, phase, path in sorted(rows, key=lambda r: -r[0])[:top]:
        print(f"chipbench: op {100 * s / total:6.2f}% {s:.5f} s {key[0]} "
              f"%{key[1]} -> {kind}/{phase} {path[-150:]}", file=sys.stderr)


def _fold(got):
    if not got or not got["total"]:
        return None
    out = {"total": got["total"], "kinds": {}, "by": {},
           "unnamed": sum(got["unnamed"].values())}
    for (_module, kind, phase), s in got["seconds"].items():
        out["kinds"][kind] = out["kinds"].get(kind, 0.0) + s
        out["by"][kind, phase] = out["by"].get((kind, phase), 0.0) + s
    return out


def share(facts, kinds=None, phase=None):
    """Percent of the joined seconds under ``kinds`` (all of them where
    None) in ``phase`` (every phase where None); None with no join."""
    got = joined(facts)
    if got is None:
        return None
    seconds = sum(s for (k, p), s in got["by"].items()
                  if (kinds is None or k in kinds)
                  and (phase is None or p == phase))
    return 100.0 * seconds / got["total"]


def unnamed_share(facts):
    """Percent of the joined seconds whose instruction has no scope or
    no match in the map: the instrument's blind share."""
    got = joined(facts)
    if got is None:
        return None
    return 100.0 * got["unnamed"] / got["total"]
