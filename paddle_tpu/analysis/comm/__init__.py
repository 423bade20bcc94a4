"""paddle_tpu.analysis.comm — the sharding & communication contract
analyzer.

GSPMD decides where every cross-chip collective lands; the jaxpr never
shows them and ``hlo_comm_report``'s scalar counts cannot say *which*
collective moved or *why*.  This package turns the partitioned SPMD HLO
into a structured **CommPlan** — every collective's kind, recovered mesh
axes (from its replica groups), bytes, loop membership, phase
(fwd-scan / bwd-scan / optimizer boundary) and sharding-annotation
provenance — and checks it against declarative **CommContracts**
(``expect`` / ``forbid`` / ``forbid_reshard``) so the load-bearing
constraint-placement invariants of docs/parallel.md are machine-checked
instead of documented prose.

See docs/analysis.md ("Communication contracts") for the check catalog
and how to write a contract; ``tests/test_comm_plan.py`` plants
each mis-spelling and sweeps the clean ones.
"""

from .plan import (
    CommOp,
    CommPlan,
    extract_comm_plan,
    comm_diff,
    mesh_axis_groups,
    PIN_SCOPE_RE,
)
from .contract import (
    CommContract,
    attach_comm_contract,
    comm_contracts,
)

# importing the check module registers the comm checks with the
# analysis framework's registry
from . import checks  # noqa: F401

__all__ = [
    "CommOp", "CommPlan", "extract_comm_plan", "comm_diff",
    "mesh_axis_groups", "PIN_SCOPE_RE",
    "CommContract", "attach_comm_contract", "comm_contracts",
]
