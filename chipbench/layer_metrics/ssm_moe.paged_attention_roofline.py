"""The paged kernel's share of its roofline over the traced window for a
stack whose attention planes hold FEWER K/V heads than the pool stores
rows for: the least time the chip could take over the paged calls of the
decode positions processed in the window (``ssm_moe_bytes.attention``:
for every attended position the K and V the model CACHES, 6,144 B over
the six planes of 2 K/V heads of 128 at the published sizes, against the
32 query heads' products with them) over the device time of the Mosaic
calls whose HLO instruction is named ``paged_attention``.

The calls are found by the kernel's name and the contexts' LENGTHS taken
from the requests' times, both as ``paged_attention_named_roofline``
does; their NUMBER is the program's own, as
``swa.paged_attention_roofline`` takes it (``active`` x ``steps`` over
the ``serving.decode_chunk`` spans of the profile; a trace without such
spans keeps the requests' count).  Decode positions only: a prefill
window of 8 rows or more attends densely and makes no call to the
kernel.  What the pool stores beyond what is cached (``pool_rows`` pads
a 2-head plane to 8 rows: 24,576 B a position) is read by the kernel
and NOT counted, so the share says what a pool packed to what it caches
could gain: a quarter at the most while the padding stands.  A reading
over 100 is a fault of the count.  A trace in which no call carries the
name, or a family with no such layers, gives nothing to read."""

from chipbench import run as bench_run
from chipbench import ssm_moe_bytes

NAME = "ssm_moe.paged_attention_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def _named():
    return bench_run.load_reader("paged_attention_named_roofline")


def kernels(cfg, mix):
    return _named().kernels(cfg, mix)


def read(facts):
    trace = facts.get("trace")
    if not trace or "trace_span" not in facts:
        return None
    if ssm_moe_bytes.sizes(facts["config"]) is None:
        return None
    spent = _named().call_seconds(trace)
    if not spent:
        return None
    contexts = _named().decode_contexts(facts["requests"],
                                        *facts["trace_span"])
    least = ssm_moe_bytes.least_seconds(
        *ssm_moe_bytes.attention(facts["config"], sum(contexts)),
        facts["peak"])
    sent = bench_run.load_reader(
        "swa.paged_attention_roofline").positions(facts)
    if sent and contexts:
        least *= sent / len(contexts)
    return 100.0 * least / spent
