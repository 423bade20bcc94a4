"""The block selection against its roofline over the traced window: the
least time the chip could take to score the compressed keys of the decode
positions processed in the window (``sala_bytes.select_call``: every
compressed row of a context read once an ``S`` layer, 256 values, against
the 32 query heads' products with it) over the device time of the decode
chunk's operations under the named scopes ``paged_block_scores`` (XLA's
gather of the compressed plane through the table, the product, the
softmax a head, the sum over a K/V group, the maximum a block) and
``block_select`` (the counting passes and the compaction that turn the
scores into 64 block ids; they read no cached byte, so the roofline
counts nothing for them) of ``kernels/block_sparse_attention.py``.

Found by the program's own map from HLO instruction to scope, as
``dsa.indexer_roofline`` finds its scope (its ``scope_seconds``), in the
modules whose name says decode; counted by ``sala_bytes.decode_least``.
A reading over 105 is refused.  A program without the map or the scopes,
or a family with no such layer, gives nothing to read."""

from chipbench import run as bench_run
from chipbench import sala_bytes

NAME = "sala.block_select_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLES = ("paged_block_scores", "block_select")


def read(facts):
    if not facts.get("trace") or "trace_span" not in facts:
        return None
    size = sala_bytes.sizes(facts["config"])
    if size is None:
        return None
    helper = bench_run.load_reader("dsa.indexer_roofline")
    spent = sum(helper.scope_seconds(facts, needle, "decode") or 0.0
                for needle in NEEDLES)
    least = spent and sala_bytes.decode_least(facts, sala_bytes.select_call)
    if not least:
        return None
    return sala_bytes.share(
        NAME, 100.0 * size["sparse_layers"] * least / spent)
