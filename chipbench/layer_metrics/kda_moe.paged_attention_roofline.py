"""The paged kernel's share of its roofline over the traced window for
the ONE position-free plane of a delta-rule stack: the least time the
chip could take over the paged calls of the decode positions processed
in the window (``delta_bytes.attention``: for every attended position
the K and V the model caches, 4,096 B over the one plane of 8 K/V heads
of 128 at the published sizes, against the 64 query heads' products with
them) over the device time of the Mosaic calls whose HLO instruction is
named ``paged_attention``.

The calls are found by the kernel's name and the contexts' LENGTHS taken
from the requests' times, both as ``paged_attention_named_roofline``
does; their NUMBER is the program's own: ``active`` x ``steps`` over the
``serving.decode_chunk`` spans that START inside the traced window's
interval (a trace without such spans keeps the requests' count).  Decode
positions only: a prefill window of 8 rows or more walks its chain in
``chain_attention`` and makes no call to this kernel.  The pool stores
what is cached (8 K/V heads fill the 8 rows ``pool_rows`` gives), so the
share is the kernel's own.  A reading over 105 is refused.  A trace in
which no call carries the name, or a family with no such layers, gives
nothing to read."""

from chipbench import delta_bytes, trace_reduce
from chipbench import run as bench_run

NAME = "kda_moe.paged_attention_roofline"
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
    if delta_bytes.sizes(facts["config"]) is None:
        return None
    spent = _named().call_seconds(trace)
    if not spent:
        return None
    contexts = _named().decode_contexts(facts["requests"],
                                        *facts["trace_span"])
    least = delta_bytes.least_seconds(
        *delta_bytes.attention(facts["config"], sum(contexts)),
        facts["peak"])
    sent = sum(int(a) * int(s) for a, s in delta_bytes.spans_inside(
        trace_reduce.load(facts["trace_path"]), facts["trace_interval"],
        "serving.decode_chunk", "active", "steps")) \
        if facts.get("trace_path") else 0
    if sent and contexts:
        least *= sent / len(contexts)
    return delta_bytes.share(NAME, 100.0 * least / spent)
