"""Multi-backend kernel registry (docs/kernels.md).

The paper's framework survived a hardware transition because op
SEMANTICS were separated from op IMPLEMENTATION — layer-graph ops were
re-lowered per device.  This module is that separation for the fused
kernels: each op CLASS (flash attention fwd/bwd, the fused CE/LSE head,
paged attention on the serving block table) registers up to two
backends and every call site resolves through ONE selection path:

* ``pallas_tpu`` — the Mosaic kernels (``ops/pallas_attention.py``,
  ``ops/pallas_ce.py``).  Native on TPU; off-TPU they run in Pallas
  interpret mode (slow, but the exact kernel logic — the CPU test
  path).
* ``xla_ref`` — the shape-complete pure-XLA reference
  (``kernels/xla_ref.py``): causal/non-causal, d_head 64/128, packed
  layouts, lse outputs, grads through the same custom-vjp algebra.
  Always available, and the universal numerics ORACLE every other
  backend is tested against (``tests/test_kernels.py``: every
  backend, float32 and bfloat16, forward and gradient).

Selection precedence (the registry unit suite pins this):

1. explicit ``backend=`` argument at the call site (or a tuner-forced
   backend inside :func:`forced_backend`) — unknown raises
   ``ValueError``, registered-but-unavailable raises
   :class:`KernelUnavailable` with the reason;
2. per-op env ``PADDLE_TPU_KERNEL_BACKEND_<OP>`` (op class upper-cased,
   e.g. ``PADDLE_TPU_KERNEL_BACKEND_FLASH_ATTENTION=xla_ref``) — same
   strictness as an explicit argument;
3. global env ``PADDLE_TPU_KERNEL_BACKEND=auto|pallas_tpu|xla_ref``
   — unavailable/unregistered degrades to auto with the fallback
   counted (``kernels.env_fallbacks``) so a fleet-wide env pin
   never crashes the one op that lacks the backend;
4. ``auto`` — the per-platform preference order (:data:`AUTO_ORDER`):
   first registered AND available backend wins.

Every resolution is recorded (``selected_backends()``); the Executor
snapshots the record per compile into ``last_step_cost
["kernel_backends"]``, the attribution workload key gains a ``|kb=``
token, and bench rows / trainer JSONL carry it — tuner cache entries
and the learned-cost-model corpus are keyed by WHICH kernel ran, not
just the platform.
"""

import contextlib
import os
import threading

from ..observability import metrics as _obs

__all__ = [
    "BACKENDS", "AUTO_ORDER", "KernelUnavailable", "register_kernel",
    "get_kernel", "resolve", "resolve_name", "available_backends",
    "registered_op_classes", "selected_backends", "reset_selected",
    "forced_backend", "timed_run", "timed_run_active",
    "TIMED_RUN_ENV", "GLOBAL_ENV",
]

BACKENDS = ("pallas_tpu", "xla_ref")

GLOBAL_ENV = "PADDLE_TPU_KERNEL_BACKEND"
TIMED_RUN_ENV = "PADDLE_TPU_TIMED_RUN"

# per-platform auto preference.  CPU deliberately prefers the Mosaic
# kernels in interpret mode: a CPU process is a CI/test process and
# exercising the REAL kernel logic is the point (every pre-registry
# test ran this way).  Timed CPU runs are the exception — bench
# declares its flagship sections timed-run regions so interpret-mode
# kernels are flagged as a lint error on the row
# (jaxpr.kernel-backend); the operator routes such runs with
# PADDLE_TPU_KERNEL_BACKEND=xla_ref (docs/kernels.md).
AUTO_ORDER = {
    "tpu": ("pallas_tpu", "xla_ref"),
    "cpu": ("pallas_tpu", "xla_ref"),
}
_DEFAULT_ORDER = ("xla_ref",)


class KernelUnavailable(RuntimeError):
    """An explicitly requested backend is registered for the op class
    but not available on this host (e.g. ``pallas_tpu`` paged attention
    off the TPU).
    ``.reason`` carries the availability probe's explanation — test
    suites turn it into a skip, resolution fallbacks record it."""

    def __init__(self, op_class, backend, reason):
        super().__init__(
            f"kernel backend {backend!r} for op {op_class!r} is "
            f"unavailable on this host: {reason}")
        self.op_class = op_class
        self.backend = backend
        self.reason = reason


class _Kernel:
    __slots__ = ("op_class", "backend", "impl", "_available")

    def __init__(self, op_class, backend, impl, available):
        self.op_class = op_class
        self.backend = backend
        self.impl = impl
        self._available = available

    def availability(self):
        """(ok, reason) — ``reason`` explains an unavailable backend or
        annotates an available one (e.g. "interpret mode off-TPU")."""
        if self._available is None:
            return True, ""
        try:
            out = self._available()
        except Exception as e:  # noqa: BLE001 — a probe crash = absent
            return False, f"availability probe failed: {e}"
        if isinstance(out, tuple):
            return bool(out[0]), str(out[1] or "")
        return bool(out), ""


_KERNELS = {}  # {op_class: {backend: _Kernel}}
_SELECTED = {}  # {op_class: backend} — most recent resolutions
_SEL_LOCK = threading.Lock()
_FORCED = []  # [(op_class_or_None, backend)] — tuner/test hook stack


def register_kernel(op_class, backend, impl, available=None):
    """Register ``impl`` (an opaque namespace of callables — each op
    class defines its own calling convention, see the op modules) as
    ``op_class``'s ``backend`` implementation.  ``available`` is an
    optional zero-arg probe returning ``bool`` or ``(bool, reason)``."""
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r} (valid: {BACKENDS})")
    per_op = _KERNELS.setdefault(op_class, {})
    if backend in per_op:
        raise ValueError(
            f"kernel {op_class!r}/{backend!r} registered twice")
    per_op[backend] = _Kernel(op_class, backend, impl, available)
    return impl


def registered_op_classes():
    return sorted(_KERNELS)


def get_kernel(op_class, backend):
    """The registered ``_Kernel`` or None (no resolution, no checks —
    introspection only)."""
    return _KERNELS.get(op_class, {}).get(backend)


def available_backends(op_class):
    """``[(backend, ok, reason)]`` for every registered backend of the
    op class, in ``BACKENDS`` order — the oracle tests' enumeration."""
    per_op = _KERNELS.get(op_class, {})
    out = []
    for b in BACKENDS:
        k = per_op.get(b)
        if k is None:
            continue
        ok, reason = k.availability()
        out.append((b, ok, reason))
    return out


def _platform():
    try:
        import jax

        return jax.default_backend()
    except Exception:  # backendless callers (pure-unit tests)
        return "cpu"


def _env_value(op_class):
    """(value, source) from the env layers: per-op wins over global.
    Empty/unset values fall through; names are validated by resolve."""
    per_op = os.environ.get(
        f"{GLOBAL_ENV}_{op_class.upper()}", "").strip().lower()
    if per_op:
        return per_op, "env_op"
    glob = os.environ.get(GLOBAL_ENV, "").strip().lower()
    if glob:
        return glob, "env"
    return None, "auto"


def _auto_resolve(op_class, platform):
    order = AUTO_ORDER.get(platform, _DEFAULT_ORDER)
    per_op = _KERNELS.get(op_class, {})
    reasons = []
    for b in order:
        k = per_op.get(b)
        if k is None:
            reasons.append(f"{b}: not registered")
            continue
        ok, reason = k.availability()
        if ok:
            return k
        reasons.append(f"{b}: {reason or 'unavailable'}")
    raise KernelUnavailable(
        op_class, "auto",
        f"no backend available on platform {platform!r} "
        f"({'; '.join(reasons) or 'none registered'})")


def _validate(name):
    if name not in BACKENDS and name != "auto":
        raise ValueError(
            f"unknown kernel backend {name!r} (valid: auto, "
            f"{', '.join(BACKENDS)})")


def resolve(op_class, backend=None, platform=None):
    """Resolve the backend for one op-class call site at trace time.

    Returns the chosen ``_Kernel``.  Precedence: explicit ``backend``
    arg > tuner-forced > per-op env > global env > auto (see module
    docstring).  Explicit/per-op requests are strict (unknown
    raises ``ValueError``, unavailable raises
    :class:`KernelUnavailable`); a global-env or tuner-forced request
    that this op cannot serve degrades to auto with
    ``kernels.env_fallbacks`` counted.  The resolution is recorded in
    :func:`selected_backends`."""
    if op_class not in _KERNELS:
        raise KeyError(f"no kernels registered for op {op_class!r}")
    platform = platform or _platform()
    source = "arg"
    strict = True
    name = backend
    if name is None and _FORCED:
        for scope, forced in reversed(_FORCED):
            if scope is None or scope == op_class:
                name, source, strict = forced, "forced", False
                break
    if name is None:
        name, source = _env_value(op_class)
        strict = source == "env_op"
    if name is not None:
        name = str(name).strip().lower()
        _validate(name)
    if name is None or name == "auto":
        kernel = _auto_resolve(op_class, platform)
    else:
        kernel = _KERNELS[op_class].get(name)
        ok, reason = (kernel.availability() if kernel is not None
                      else (False, "not registered for this op"))
        if not ok:
            if strict:
                raise KernelUnavailable(op_class, name,
                                        reason or "unavailable")
            # non-strict sources (global env, tuned/forced configs)
            # degrade to auto: a fleet-wide pin must never crash the
            # one op that lacks the backend
            _obs.get_registry().counter(
                "kernels.env_fallbacks",
                help="kernel backend requests that fell back to auto "
                     "(requested backend unavailable for the op)").inc()
            kernel = _auto_resolve(op_class, platform)
    with _SEL_LOCK:
        _SELECTED[op_class] = kernel.backend
    _obs.get_registry().counter(
        "kernels.resolved",
        help="kernel registry resolutions (per traced call site)").inc()
    return kernel


def resolve_name(op_class, backend=None, platform=None):
    """:func:`resolve`, returning just the backend name."""
    return resolve(op_class, backend=backend, platform=platform).backend


def selected_backends():
    """Snapshot of the most recent resolution per op class — the
    Executor folds this into ``last_step_cost["kernel_backends"]`` per
    compile (it resets the record before tracing)."""
    with _SEL_LOCK:
        return dict(_SELECTED)


def reset_selected():
    with _SEL_LOCK:
        _SELECTED.clear()


@contextlib.contextmanager
def forced_backend(backend, op_class=None):
    """Force resolution to ``backend`` inside the context (all op
    classes, or one) — how the autotuner measures a backend candidate
    and how tests pin routing without env mutation.  Non-strict: an op
    the backend cannot serve falls back to auto (counted), so forcing
    ``pallas_tpu`` paged attention off the TPU measures what auto would
    actually run.
    Explicit ``backend=`` call-site arguments still win."""
    if backend is not None:
        _validate(str(backend).strip().lower())
    _FORCED.append((op_class, None if backend is None
                    else str(backend).strip().lower()))
    try:
        yield
    finally:
        _FORCED.pop()


def pallas_tpu_availability():
    """The shared availability probe of the Mosaic (``pallas_tpu``)
    kernel backends: native on TPU; AVAILABLE everywhere else too, in
    Pallas interpret mode (the CPU test path) — the reason string
    annotates the cost so timed runs know to route elsewhere."""
    try:
        import jax

        backend = jax.default_backend()
    except Exception as e:  # noqa: BLE001
        return False, f"jax backend probe failed: {e}"
    if backend == "tpu":
        return True, ""
    return True, (f"interpret mode on platform {backend!r} — exact "
                  f"kernel logic, orders of magnitude slower than "
                  f"hardware (timed runs should route xla_ref)")


def timed_run_active():
    """True inside a declared timed-run region — the
    ``jaxpr.kernel-backend`` analysis check only flags interpret-mode
    kernels there (a CPU test compile is SUPPOSED to interpret)."""
    return os.environ.get(TIMED_RUN_ENV, "").lower() in (
        "1", "true", "yes")


@contextlib.contextmanager
def timed_run():
    """Declare a timed-run region (around work whose time will be
    reported): compiles inside it lint interpret-mode Pallas kernels as
    errors — an interpreted kernel in a timed row is a benchmarking
    bug, not a measurement (docs/kernels.md)."""
    old = os.environ.get(TIMED_RUN_ENV)
    os.environ[TIMED_RUN_ENV] = "1"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(TIMED_RUN_ENV, None)
        else:
            os.environ[TIMED_RUN_ENV] = old
