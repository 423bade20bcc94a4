"""Elastic resilience engine — surviving process churn, deterministically.

The reference survived trainer/pserver death by design (etcd-leased task
dispatch in the Go master, periodic pserver checkpoints —
go/master/service.go, go/pserver/service.go:342).  This package
reproduces that capability for the jitted-step world and makes failure a
*tested* code path:

* ``checkpoint`` — the schema-versioned train-state sidecar (RNG key,
  reader cursor, pass/step counters) that upgrades the persistables
  snapshot to a FULL-state checkpoint, plus latest-valid discovery and
  retention;
* ``faults``     — ``PADDLE_TPU_FAULT=kind:n`` injection points
  (SIGKILL mid-pass, crash mid-publish, transient IO error, reader
  exception, NaN gradient);
* ``retry``      — jittered-exponential-backoff for transient IO and
  RPC;
* ``watchdog``   — step-deadline supervision (trips are metrics + trace
  instants, not silent hangs).

``Trainer.train(..., checkpoint_every_n_steps=N, resume=True)`` is the
consumer: kill-and-resume reproduces the uninterrupted loss trajectory
bit-exactly (``tests/test_resilience.py`` SIGKILLs a trainer to
show it).  See docs/resilience.md.
"""

from . import checkpoint
from . import faults
from . import retry
from . import watchdog
from .checkpoint import (
    latest_checkpoint, load_train_state, prune_checkpoints,
    save_train_state, step_dir,
)
from .retry import Backoff, RetryError, retry_call
from .watchdog import Watchdog

__all__ = [
    "checkpoint", "faults", "retry", "watchdog",
    "latest_checkpoint", "load_train_state", "prune_checkpoints",
    "save_train_state", "step_dir",
    "Backoff", "RetryError", "retry_call", "Watchdog",
]
