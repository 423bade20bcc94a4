"""Distributed layer — the Go master / pserver generation and the fluid
send/recv transpiler, rebuilt for the TPU world (SURVEY §L8, §2.6).

Division of labor (the north star of SURVEY.md):
* DENSE data parallelism never leaves the pod: it is mesh sharding + ICI
  collectives (paddle_tpu.parallel) — no server in the loop.
* The DCN-side services here cover what ICI cannot: elastic *data* dispatch
  (master: task queue over record chunks, timeout requeue, failure drop,
  snapshot/recover — go/master/service.go), cross-host SPARSE embedding
  updates (pserver: sharded tables, sync/async, checkpoint — go/pserver +
  paddle/pserver/ParameterServer2), and discovery (a coordination store
  replacing etcd).
* ``transpiler`` rewrites one program into trainer/pserver halves exactly
  like fluid's distribute_transpiler.py:81.

Transport is a small length-prefixed-pickle TCP RPC (rpc.py) — the
structural stand-in for the reference's gRPC / Go net/rpc / LightNetwork.
"""

from . import rpc
from . import store
from . import launch
from .master import MasterService, MasterClient
from .pserver import ParameterServer, PServerClient
from .transpiler import DistributeTranspiler

__all__ = [
    "rpc", "store", "launch", "MasterService", "MasterClient",
    "ParameterServer", "PServerClient", "DistributeTranspiler",
]
