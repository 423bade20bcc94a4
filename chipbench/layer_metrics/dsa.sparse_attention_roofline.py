"""The attention of the selected rows against its roofline over the
traced window: the least time the chip could take over the decode
positions processed in the window (``dsa_bytes.sparse_call``:
``min(context, index_topk)`` rows of 640 stored lanes read a full plane,
against the 128 heads' products with them) over the device time of the
decode chunk's operations under the named scope
``paged_sparse_latent_attention`` (``kernels/sparse_attention.py``:
XLA's gather of the rows by (block, offset), the scores, one softmax and
the value product).

Found and counted as ``dsa.indexer_roofline`` does (its ``scope_ops``,
``scope_seconds`` and ``decode_least``).  A reading over 100 is a fault
of the count.  A program without the map or the scope, or a family with
no indexer, gives nothing to read."""

from chipbench import dsa_bytes
from chipbench import run as bench_run

NAME = "dsa.sparse_attention_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLE = "paged_sparse_latent_attention"


def read(facts):
    if not facts.get("trace") or "trace_span" not in facts:
        return None
    size = dsa_bytes.sizes(facts["config"])
    if size is None:
        return None
    helper = bench_run.load_reader("dsa.indexer_roofline")
    spent = helper.scope_seconds(facts, NEEDLE, "decode")
    least = spent and helper.decode_least(facts, dsa_bytes.sparse_call)
    if not least:
        return None
    return 100.0 * size["full"]["planes"] * least / spent
