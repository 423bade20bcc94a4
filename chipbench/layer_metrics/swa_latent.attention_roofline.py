"""The latent kernel under a lower bound against its roofline over the
traced window: the least time the chip could take over the decode
positions processed in the window (``dsa_bytes.window_call``:
``min(context, window)`` rows of 1,152 stored lanes read a sliding
plane, against the 64 heads' products with them) over the device time of
the Mosaic calls whose HLO instruction is named
``paged_latent_attention``: in this family the sliding planes' decode
calls and no others (a full plane's table holds more than ``index_topk``
positions, so its rows are selected and it never makes the dense call).

The calls are found by name as ``mla.latent_attention_roofline`` finds
them; the positions counted as ``dsa.indexer_roofline`` counts them.
Decode positions only: a prefill piece of 8 rows or more attends densely.
The kernel fetches whole groups of ``LATENT_BLOCKS`` table entries (256
positions): the rows of a group under the bound are read and not counted.
A reading over 100 is a fault of the count.  A trace in which no call
carries the name, or a family with no indexer, gives nothing to read."""

from chipbench import dsa_bytes
from chipbench import run as bench_run

NAME = "swa_latent.attention_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def _named():
    return bench_run.load_reader("mla.latent_attention_roofline")


def kernels(cfg, mix):
    return _named().kernels(cfg, mix)


def read(facts):
    trace = facts.get("trace")
    if not trace or "trace_span" not in facts:
        return None
    size = dsa_bytes.sizes(facts["config"])
    if size is None:
        return None
    spent = _named().call_seconds(trace)
    least = spent and bench_run.load_reader(
        "dsa.indexer_roofline").decode_least(facts, dsa_bytes.window_call)
    if not least:
        return None
    return 100.0 * size["sliding"]["planes"] * least / spent
