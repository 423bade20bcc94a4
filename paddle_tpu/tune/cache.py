"""Persistent autotune config cache.

One JSON file maps canonical workload-key strings to winning configs and
their measured numbers::

    {"schema_version": 1,
     "fingerprint": "0f3a9c21bd04",
     "git_sha": "269de37a1b2c",
     "entries": {
        "op=gpt_step|t=16384|dh=128|h=6|dt=bfloat16|plat=tpu": {
            "config":   {"policy": "offload", "accum": 2,
                         "block_q": 512, "block_k": 1024, ...},
            "measured": {"median_s": 4.91, "tok_s": 120133.0, ...},
            "searched_at": 1754200000.0},
        "op=flash|t=16384|dh=128|h=6|dt=bfloat16|plat=tpu|remat=-": {
            "config":   {"block_q": 512, "block_k": 1024, ...},
            "measured": {"median_s": 4.91, ...},
            "searched_at": 1754200000.0}}}

Ops currently cached: ``gpt_step`` (training schedule) and ``flash``
(attention kernel geometry).  Entries of other ops (a file written
before the serving path stopped reading the tuner) are never looked up.

Location: ``PADDLE_TPU_TUNE_CACHE`` or ``~/.cache/paddle_tpu/tuned.json``.

The ``fingerprint`` is a content hash over the kernel-geometry decisions
(``DIAG_W``, ``LSE_LANES``, the ``FLASH_BWD_RESIDUALS`` contract, the
``packed_sub_heads``/``_pick_block`` decision tables): a tuned block size
is only meaningful for the kernel geometry it was measured against, so a
cache written by a different kernel generation is STALE — its entries
are ignored and the workload re-tunes (``git_sha`` rides along so a
stale file is attributable to a commit).  Robustness contract (pinned by
``tests/test_tune.py``): a corrupt/truncated file, a schema-version
mismatch, and a stale fingerprint each degrade to an EMPTY cache —
lookups miss, defaults apply, the next persisted search rewrites the
file — never a crash and never a silently-served wrong config.
"""

import hashlib
import json
import os
import tempfile
import time

from ..observability import metrics as _obs

__all__ = ["CACHE_SCHEMA_VERSION", "cache_path", "geometry_fingerprint",
           "TuneCache", "get_cache", "reset_cache"]

CACHE_SCHEMA_VERSION = 1
_ENV_PATH = "PADDLE_TPU_TUNE_CACHE"


def cache_path():
    """The on-disk cache location: ``PADDLE_TPU_TUNE_CACHE`` wins, else
    ``~/.cache/paddle_tpu/tuned.json``."""
    p = os.environ.get(_ENV_PATH)
    if p:
        return os.path.expanduser(p)
    return os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu",
                        "tuned.json")


def geometry_fingerprint():
    """Content hash of the kernel-geometry decision surface.  Any change
    to the causal cell walk, the packed-head routing table, the
    block-picking rule, or the flash backward residual contract changes
    the hash — and invalidates every cached schedule measured against
    the old geometry."""
    from ..ops import pallas_attention as pa

    basis = (
        CACHE_SCHEMA_VERSION,
        # NOT pa.DIAG_W: the strip height is itself a tunable the
        # cache stores (and applies via apply_tuned_diag_w) — hashing
        # its current value would make a tuned cache invalidate itself.
        # The cell WALK is covered by sampling what it schedules:
        tuple((tuple(w), w.updates_per_row, w.branches_per_cell)
              for w in (pa.causal_flash_flops(t, t, 128, bq, bk, diag_w=256)
                        for t, bq, bk in ((2048, 1024, 1024),
                                          (4096, 1024, 1024),
                                          (4096, 512, 1024)))),
        pa.LSE_LANES,
        tuple(pa.FLASH_BWD_RESIDUALS),
        # the packed-head routing table over the geometries that matter
        tuple((h, d, pa.packed_sub_heads(h, d))
              for h in (1, 2, 3, 4, 6, 8)
              for d in (32, 64, 128, 256)),
        # the block-picking rule sampled over representative (t, cap)
        tuple(pa._pick_block(t, c)
              for t in (96, 2048, 4096, 16384)
              for c in (128, 256, 512, 1024, 2048)),
        _registry_surface(),
        # the schedule-dimension surface: which non-geometry knobs a
        # persisted gpt_step winner can carry.  Adding a dimension
        # (grad_rs joined with the true-ZeRO-3 gradient spelling,
        # docs/parallel.md rule 4) changes what an OLD winner means —
        # it was measured with the dimension pinned at its default —
        # so the fingerprint must move and retire it.
        ("policy", "accum", "fsdp", "grad_rs"),
    )
    return hashlib.sha256(repr(basis).encode()).hexdigest()[:12]


def _registry_surface():
    """The kernel-registry decision surface (docs/kernels.md): which
    backends each op class registers and the per-platform auto order.
    A tuned winner persists its kernel choice, so adding/removing a
    backend or reordering auto resolution changes what a cached config
    MEANS — the fingerprint must move with it.  Availability is
    deliberately NOT hashed: it is a host property, not a geometry
    decision (the workload key's ``plat=`` field already scopes it)."""
    try:
        from .. import kernels
    except Exception:  # mid-bootstrap partial import
        return ()
    return (
        tuple((op, tuple(sorted(b for b in kernels.BACKENDS
                                if kernels.get_kernel(op, b))))
              for op in kernels.registered_op_classes()),
        tuple(sorted((plat, order)
                     for plat, order in kernels.AUTO_ORDER.items())),
    )


def _git_sha():
    try:
        from ..observability.runlog import run_stamp

        return run_stamp().get("git_sha")
    except Exception:  # noqa: BLE001 — identity must never block caching
        return None


class TuneCache:
    """Load/lookup/persist tuned configs with the robustness contract
    above.  ``stale_reason`` records why a file on disk was ignored
    (None when it loaded cleanly or did not exist)."""

    def __init__(self, path=None):
        self.path = path or cache_path()
        self.fingerprint = geometry_fingerprint()
        self.entries = {}
        self.stale_reason = None
        self._load()

    def _reject(self, reason):
        self.stale_reason = reason
        self.entries = {}
        _obs.get_registry().counter(
            "tune.cache_errors",
            help="tune cache files ignored (corrupt/schema/fingerprint); "
                 "defaults applied, next search rewrites").inc()

    def _load(self):
        try:
            with open(self.path, "r", encoding="utf-8") as fh:
                raw = json.load(fh)
        except FileNotFoundError:
            return
        except (OSError, ValueError, UnicodeDecodeError) as e:
            # corrupt / truncated / unreadable: empty cache, re-tune
            self._reject(f"unreadable cache: {type(e).__name__}: {e}")
            return
        if not isinstance(raw, dict) or not isinstance(
                raw.get("entries"), dict):
            self._reject("cache is not a {schema_version, entries} object")
            return
        if raw.get("schema_version") != CACHE_SCHEMA_VERSION:
            self._reject(
                f"schema_version {raw.get('schema_version')!r} != "
                f"{CACHE_SCHEMA_VERSION}")
            return
        if raw.get("fingerprint") != self.fingerprint:
            self._reject(
                f"kernel-geometry fingerprint {raw.get('fingerprint')!r} "
                f"is stale (current {self.fingerprint}, written at git "
                f"{raw.get('git_sha')!r})")
            return
        self.entries = {k: v for k, v in raw["entries"].items()
                        if isinstance(v, dict) and "config" in v}

    def get(self, key_s):
        """The entry for a canonical key string, or None."""
        e = self.entries.get(key_s)
        return e if isinstance(e, dict) else None

    def put(self, key_s, config, measured=None):
        entry = {"config": dict(config), "searched_at": time.time()}
        if measured:
            entry["measured"] = dict(measured)
        self.entries[key_s] = entry
        return entry

    def save(self):
        """Atomic persist (tmp + rename): a reader never sees a torn
        file, and a crash mid-write leaves the previous cache intact."""
        payload = {
            "schema_version": CACHE_SCHEMA_VERSION,
            "fingerprint": self.fingerprint,
            "git_sha": _git_sha(),
            "entries": self.entries,
        }
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(prefix=".tuned.", suffix=".tmp", dir=d)
        try:
            with os.fdopen(fd, "w", encoding="utf-8") as fh:
                json.dump(payload, fh, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return self.path


_cache_singleton = []  # [(resolved_path, TuneCache)]


def get_cache():
    """Process-wide cache bound to the CURRENT resolved path — changing
    ``PADDLE_TPU_TUNE_CACHE`` (as the tests do) re-loads."""
    path = cache_path()
    if _cache_singleton and _cache_singleton[0][0] == path:
        return _cache_singleton[0][1]
    c = TuneCache(path)
    _cache_singleton[:] = [(path, c)]
    return c


def reset_cache():
    """Drop the in-process singleton (the next get_cache() re-reads the
    file) — for tests and for re-reading a cache another process wrote."""
    _cache_singleton[:] = []
