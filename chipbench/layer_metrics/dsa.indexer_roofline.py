"""The indexer's scores against their roofline over the traced window:
the least time the chip could take to score the decode positions
processed in the window (``dsa_bytes.index_call``: every cached
position's index key read once a full plane, 128 lanes, against the 64
index heads' products with it) over the device time of the decode
chunk's operations under the named scope ``paged_index_scores``
(``kernels/sparse_attention.py``: XLA's gather through the table, the
product, ``relu`` and the sum over the heads).

The operations are found by the program's own map from HLO instruction
to scope (``trace.device_scopes``; ``scope_ops`` below makes the join
once a run and the other ``dsa.*`` readers share it), SELF seconds, in
the modules whose name says decode.  The contexts' LENGTHS are taken
from the requests' times and their NUMBER from the program's
``serving.decode_chunk`` spans (``active`` x ``steps``), as
``swa.paged_attention_roofline`` does.  A reading over 100 is a fault of
the count.  A program without the map or the scope, or a family with no
indexer, gives nothing to read."""

from chipbench import dsa_bytes
from chipbench import run as bench_run

NAME = "dsa.indexer_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLE = "paged_index_scores"
_ops = {}


def scope_ops(facts):
    """``{(module, instruction): (seconds, kind, phase, path)}`` and the
    join's total of the run's trace, or None where there is no trace or
    no map."""
    path = facts.get("trace_path")
    if not facts.get("trace") or not path:
        return None
    try:
        from paddle_tpu.observability import trace
    except ImportError:
        return None
    if not hasattr(trace, "device_seconds_by_scope"):
        return None
    if path not in _ops:
        scopes = facts.get("device_scopes")
        if scopes is None:
            scopes = trace.device_scopes()
        got = trace.device_seconds_by_scope(path, scopes) if scopes else None
        _ops[path] = (got.get("ops", {}), got["total"]) if got else None
    return _ops[path]


def scope_seconds(facts, needle, module=None):
    """Device seconds of the operations whose scope path holds
    ``needle`` (in the modules whose name holds ``module``), or None."""
    got = scope_ops(facts)
    if not got:
        return None
    return sum(s for (mod, _), (s, _kind, _phase, path) in got[0].items()
               if needle in path and (module is None or module in mod)
               ) or None


def decode_least(facts, call):
    """The least seconds of ``call(config, contexts)`` a full or sliding
    plane, over the decode positions of the traced window and that
    kind's planes: lengths from the requests, their number from the
    program's spans."""
    contexts = bench_run.load_reader(
        "paged_attention_named_roofline").decode_contexts(
            facts["requests"], *facts["trace_span"])
    if not contexts:
        return None
    least = dsa_bytes.least_seconds(call(facts["config"], contexts),
                                    facts["peak"])
    sent = bench_run.load_reader("swa.paged_attention_roofline").positions(
        facts)
    return least * (sent / len(contexts) if sent else 1.0)


def read(facts):
    if not facts.get("trace") or "trace_span" not in facts:
        return None
    size = dsa_bytes.sizes(facts["config"])
    if size is None:
        return None
    spent = scope_seconds(facts, NEEDLE, "decode")
    least = spent and decode_least(facts, dsa_bytes.index_call)
    if not least:
        return None
    return 100.0 * size["full"]["planes"] * least / spent
