"""The attention of the selected blocks against its roofline over the
traced window: the least time the chip could take over the decode
positions processed in the window (``sala_bytes.sparse_call``: 97 blocks
of 64 positions a K/V head, each head's own 128 lanes of K and of V,
against the 32 query heads' products with them) over the device time of
the decode chunk's operations under the named scope
``paged_block_sparse_attention`` (``kernels/block_sparse_attention.py``:
the selected table's entries and the paged kernel's walk of them).  The
roofline counts what the MODEL caches of a selected block; the pool
stores its two K/V heads in eight rows a position and a block is fetched
whole, so the walk streams eight times that and a reading near 12 is the
layout's, not the kernel's (PERF.md section 7).

Found and counted as ``sala.block_select_roofline`` does.  A reading
over 105 is refused.  A program without the map or the scope, or a family
with no such layer, gives nothing to read."""

from chipbench import run as bench_run
from chipbench import sala_bytes

NAME = "sala.sparse_attention_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLE = "paged_block_sparse_attention"


def read(facts):
    if not facts.get("trace") or "trace_span" not in facts:
        return None
    size = sala_bytes.sizes(facts["config"])
    if size is None:
        return None
    helper = bench_run.load_reader("dsa.indexer_roofline")
    spent = helper.scope_seconds(facts, NEEDLE, "decode")
    least = spent and sala_bytes.decode_least(facts, sala_bytes.sparse_call)
    if not least:
        return None
    return sala_bytes.share(
        NAME, 100.0 * size["sparse_layers"] * least / spent)
