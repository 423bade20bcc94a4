"""Share of the chip's busy time in the selection: the exact
``lax.top_k`` of ``index_topk`` positions from a chain's index scores,
decode chunks and prefill pieces together, the operations under the
named scope ``index_select`` (``kernels/sparse_attention.py``) by the
program's own map from HLO instruction to scope, SELF seconds, over the
seconds the join saw (the traced window's busy time).  Latency-bound
work on the decode step's critical path: no roofline is stated for it.
A program without the map or the scope gives nothing to read."""

from chipbench import run as bench_run

NAME = "dsa.select_busy_share"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLE = "index_select"


def read(facts):
    helper = bench_run.load_reader("dsa.indexer_roofline")
    got = helper.scope_ops(facts)
    spent = got and helper.scope_seconds(facts, NEEDLE)
    if not spent or not got[1]:
        return None
    return 100.0 * spent / got[1]
