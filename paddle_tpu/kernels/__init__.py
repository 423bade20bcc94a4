"""paddle_tpu.kernels — the multi-backend kernel registry
(docs/kernels.md, ROADMAP item 2).

One op, two targets, one numerics oracle: every fused op class
(``flash_attention``, ``fused_ce``, ``paged_attention``,
``chain_attention``, ``grouped_matmul``, ``retention``, ``ssm``,
``delta_rule``, ``index_scores``, ``sparse_latent_attention``,
``block_scores``, ``block_sparse_attention``) resolves
through :mod:`.registry` to ``pallas_tpu`` (the Mosaic kernels — native
on TPU, interpret mode in CPU tests; the last four op classes, a learned
indexer's scores and the attention of the rows it selects, a K/V plane's
block scores and the attention of the blocks they select, have no such
backend yet: the last walks its selected table through ``paged_attention``) or ``xla_ref`` (:mod:`.xla_ref` —
the shape-complete pure-XLA reference every backend is tested against,
with the documented cross-backend tolerances in ``ORACLE_TOL``).  How a
serving row attends through the block table (streaming kernel, dense
gather or a walk of the chain in tiles, by the call's shapes) is
:func:`.paged_attention.attend`'s to decide.

Selection: ``PADDLE_TPU_KERNEL_BACKEND=auto|pallas_tpu|xla_ref``
(global), ``PADDLE_TPU_KERNEL_BACKEND_<OP>`` (per op class), explicit
``backend=`` call-site arguments, or the training tuner's persisted
kernel choice — precedence and fallback semantics in
:mod:`.registry`.  Its tests: ``tests/test_kernels.py`` (oracle
parity, precedence, the xla_ref trainer path).
"""

from . import registry  # must load first: backend modules register into it
from .registry import (
    AUTO_ORDER, BACKENDS, GLOBAL_ENV, TIMED_RUN_ENV, KernelUnavailable,
    available_backends, forced_backend, get_kernel,
    registered_op_classes, reset_selected, resolve, resolve_name,
    selected_backends, timed_run, timed_run_active)
from .xla_ref import ORACLE_TOL, oracle_tol
from . import xla_ref  # registers the oracle backend
from . import paged_attention  # registers the paged-attention op class
from . import chain_attention  # registers a wide window's chain walk
from . import grouped_matmul  # registers the grouped matrix product
from . import retention  # registers power retention's step and chunk
from . import ssm  # registers Mamba-2's step and chunked form
from . import delta  # registers the gated delta rule's step and WY form
from . import sparse_attention  # registers an indexer's scores and rows
from . import block_sparse_attention  # registers block scores and their read

__all__ = [
    "AUTO_ORDER", "BACKENDS", "GLOBAL_ENV", "TIMED_RUN_ENV",
    "KernelUnavailable", "ORACLE_TOL", "available_backends",
    "forced_backend", "get_kernel", "oracle_tol",
    "registered_op_classes", "reset_selected", "resolve",
    "resolve_name", "selected_backends", "timed_run",
    "timed_run_active",
]

# The pallas_tpu flash/CE backends register from the op modules
# themselves (they own the kernels).  Importing them here makes a bare
# ``import paddle_tpu.kernels`` self-sufficient; inside the package's
# own import cycle they may arrive partially initialized, in which case
# their bottom-of-module registration still runs when the outer import
# completes.
try:  # noqa: SIM105
    from ..ops import pallas_attention as _pa  # noqa: F401
    from ..ops import pallas_ce as _pce  # noqa: F401
except ImportError:  # pragma: no cover — mid-bootstrap partial import
    pass
