"""paddle_tpu.tune — the autotuning engine (docs/autotune.md).

Every performance knob that decides whether a configuration compiles
and how fast it runs — flash ``block_q``/``block_k``, the ``DIAG_W``
diagonal strip height, packed ``sub_heads`` routing, the remat/offload
policy, gradient accumulation — used to be hand-picked and global.
This package makes them MEASURED, per workload key
``(op, seq_len, d_head, n_heads, dtype, platform, remat)``:

- ``tune_gpt_step`` sweeps the candidate space, prunes statically
  (roofline via ``causal_flash_flops`` + analytic HBM bound), rejects
  OOM-doomed survivors from the COMPILED cost analysis
  (``Executor.compile_only`` + ``analysis.preflight_hbm``) before any
  step executes, times the rest median-of-k, and persists the winner
  in the on-disk cache (``PADDLE_TPU_TUNE_CACHE`` or
  ``~/.cache/paddle_tpu/tuned.json``);
- the hot path consults the cache: ``layers.multi_head_attention`` /
  ``models.transformer.build`` pick tuned flash geometry when the
  caller passes no explicit blocks, and
  ``memory_optimize(policy="auto")`` resolves the tuned remat policy;
- explicit arguments and the ``PADDLE_TPU_DIAG_W`` pin
  always win over the cache.

Modes (``PADDLE_TPU_TUNE``): ``off``/``0`` — kill switch, the framework
behaves bit-exactly as if this package did not exist; ``cached``
(default) — lookups only, a miss keeps today's defaults and NEVER
compiles; ``search`` — a miss triggers the measured search.  Lookup
traffic counts in the metrics registry (``tune.cache_hits`` /
``tune.cache_misses`` / ``tune.searches``) and is folded into
``Executor.last_step_cost``.

The serving path reads none of this: ``ServingEngine`` takes its
geometry from its arguments and ``kernels.paged_attention.attend``
chooses its spelling from what it observes.

Its tests: ``tests/test_tune.py`` (a measured search end to end).
"""

import contextlib
import os

from ..observability import metrics as _obs
from .cache import (
    CACHE_SCHEMA_VERSION, TuneCache, cache_path, geometry_fingerprint,
    get_cache, reset_cache)
from .costmodel import (
    COSTMODEL_SCHEMA_VERSION, CostModel, costmodel_enabled,
    costmodel_path, fit_and_save, fit_cost_model, get_model,
    model_status, reset_model)
from .space import (
    POLICY_ORDER, WorkloadKey, attention_candidates,
    estimate_gpt_step_hbm, prune_static, schedule_candidates)
from .search import (
    PreflightRejected, flagship_dims, flagship_static_demo,
    tune_gpt_step)

__all__ = [
    "CACHE_SCHEMA_VERSION", "TuneCache", "cache_path",
    "geometry_fingerprint", "get_cache", "reset_cache",
    "POLICY_ORDER", "WorkloadKey", "attention_candidates",
    "estimate_gpt_step_hbm", "prune_static", "schedule_candidates",
    "PreflightRejected", "flagship_dims", "flagship_static_demo",
    "tune_gpt_step", "tune_mode", "attention_config",
    "schedule_config_for", "forced_attention_config", "tune_stats",
    "COSTMODEL_SCHEMA_VERSION", "CostModel", "costmodel_enabled",
    "costmodel_path", "fit_and_save", "fit_cost_model", "get_model",
    "model_status", "reset_model",
]


def tune_mode():
    """The PADDLE_TPU_TUNE mode: "off" | "cached" | "search".  Default
    "cached" — consult the cache, never search in the hot path.  "0" /
    "off" / "false" is the kill switch: no lookup happens at all and
    every knob keeps its hand-picked default (bit-exact parity with the
    pre-tune framework, pinned by ``tests/test_tune.py``)."""
    v = os.environ.get("PADDLE_TPU_TUNE", "cached").strip().lower()
    if v in ("0", "off", "false", "no", ""):
        return "off"
    if v == "search":
        return "search"
    return "cached"


# test/search hook: a forced config consulted before the cache
_FORCED = []


@contextlib.contextmanager
def forced_attention_config(cfg):
    """Force :func:`attention_config` to return ``cfg`` inside the
    context — how the search measures a specific candidate's routing
    and how tests pin the hot path without a cache file."""
    _FORCED.append(dict(cfg) if cfg else None)
    try:
        yield
    finally:
        _FORCED.pop()


def _platform():
    import jax

    return jax.default_backend()


def _cache_lookup(op, seq_len, d_head, n_head, dtype, remat):
    """Counted cache lookup shared by every hot-path entry point.
    Returns the tuned config dict or None.  Zero side effects on the
    kill switch or an empty cache (the common CI case — the
    backend-initializing platform probe is skipped entirely); a real
    hit/miss counts ``tune.cache_hits``/``tune.cache_misses``."""
    if tune_mode() == "off":
        return None
    cache = get_cache()
    if not cache.entries:
        return None
    reg = _obs.get_registry()
    key = WorkloadKey(op, seq_len, d_head, n_head, dtype,
                      _platform(), remat=remat)
    entry = cache.get(key.s)
    if entry is None:
        reg.counter("tune.cache_misses",
                    help="tuned-config cache lookups missed").inc()
        return None
    reg.counter("tune.cache_hits",
                help="tuned-config cache lookups served").inc()
    return dict(entry.get("config") or {}) or None


def attention_config(seq_len, d_head, n_head, dtype, causal=True):
    """Hot-path lookup for ``layers.multi_head_attention``: the tuned
    kernel geometry ``{"block_q", "block_k", "diag_w", "packed"}`` for
    one attention shape, or None (caller keeps defaults)."""
    if _FORCED:
        return _FORCED[-1]
    if not causal or seq_len is None or int(seq_len) <= 0:
        return None
    return _cache_lookup("flash", seq_len, d_head, n_head, dtype,
                         remat="-")


def schedule_config_for(seq_len, d_head, n_head, dtype):
    """The tuned STEP schedule ``{"policy", "accum", "block_q", ...}``
    for one GPT shape, or None — consulted by
    ``memory_optimize(policy="auto")``."""
    return _cache_lookup("gpt_step", seq_len, d_head, n_head, dtype,
                         remat="auto")


def program_schedule_config(program):
    """The tuned schedule for a built Program, located by its flash
    attention op (shape + dtype read off the op's input var) — the
    ``memory_optimize(policy="auto")`` entry point.  None when the
    program has no flash op or the cache misses."""
    if tune_mode() == "off":
        return None
    block = program.global_block()
    for op in block.ops:
        if op.type not in ("flash_attention_packed", "flash_attention"):
            continue
        q_names = op.inputs.get("Q") or []
        var = block._find_var(q_names[0]) if q_names else None
        if var is None or len(var.shape) < 3:
            continue
        t = int(var.shape[1])
        if t <= 0:
            continue
        if op.type == "flash_attention_packed":
            n_head = int(op.attrs.get("n_head") or 0)
            if not n_head:
                continue
            d_head = int(var.shape[2]) // n_head
        else:
            n_head, d_head = int(var.shape[2]), int(var.shape[3])
        return schedule_config_for(t, d_head, n_head, var.dtype)
    return None


def tune_stats():
    """Registry snapshot for ``Executor.last_step_cost``: None when no
    tune traffic happened this process (keeps cost dicts stable for
    untuned runs)."""
    reg = _obs.get_registry()
    hits = int(reg.value("tune.cache_hits"))
    misses = int(reg.value("tune.cache_misses"))
    searches = int(reg.value("tune.searches"))
    if not (hits or misses or searches):
        return None
    return {"mode": tune_mode(), "cache_hits": hits,
            "cache_misses": misses, "searches": searches}
