"""The paged kernel's share of its roofline over the traced window for a
stack whose planes are of two kinds: the least time the chip could take
over the paged calls of the decode positions processed in the window
(``mixed_kv_bytes.least_seconds``: for every position and plane the
larger of reading what the mask lets through, every position of a full
plane and the window of a window plane, at the PUBLISHED 4 x (192 + 128)
and 8 x (192 + 128) values a position, and of the 64 query heads'
products with it) over the device time of the Mosaic calls whose HLO
instruction is named ``paged_attention``: the kernel with the sink, the
192-lane key stored at 256 and the values of 128.

The calls are found by the kernel's name and the contexts' LENGTHS taken
from the requests' times, both as ``paged_attention_named_roofline``
does; their NUMBER is the program's own: every ``serving.decode_chunk``
span is a profiler annotation on the host plane of the same
``.xplane.pb`` and carries ``active`` (the slots live when the chunk was
sent: the slots the kernel visits in every step of it) and ``steps``, and
the least seconds of the requests' contexts are scaled to that many
positions (the requests' times spread a request's tokens evenly over its
life and overcounted a three-second window by a quarter: PERF.md, PR 42).
A chunk whose span began before the profiler did is not in the trace
while some of its calls are, so the count errs low; a trace without such
spans keeps the requests' count.  Decode positions only: a prefill
window of 8 rows or more attends densely and makes no call to the
kernel.  What the pool stores beyond the published values (the rows
``pool_rows`` pads a 4-head plane to, the 64 spare lanes of a key) is
read by the kernel and not counted, so the share says what a
token-packed pool could gain.  A reading over 100 is a fault of the
count.  A trace in which no call carries the name, or a family whose
planes are of one kind, gives nothing to read."""

from chipbench import mixed_kv_bytes, trace_reduce
from chipbench import run as bench_run

NAME = "swa.paged_attention_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def _named():
    return bench_run.load_reader("paged_attention_named_roofline")


def kernels(cfg, mix):
    return _named().kernels(cfg, mix)


def chunks(path):
    """``(active, steps)`` of every ``serving.decode_chunk`` span of the
    profile at ``path``."""
    return bench_run.load_reader("retention.step_kernel_roofline").spans(
        trace_reduce.load(path), "serving.decode_chunk", "active", "steps")


def positions(facts):
    """Decode positions the program sent through the kernel in the traced
    window (``active`` x ``steps`` over the chunks' spans), or None where
    there is no profile or no such span."""
    if not facts.get("trace_path"):
        return None
    return sum(int(a) * int(s)
               for a, s in chunks(facts["trace_path"])) or None


def read(facts):
    trace = facts.get("trace")
    if not trace or "trace_span" not in facts:
        return None
    if mixed_kv_bytes.sizes(facts["config"]) is None:
        return None
    spent = _named().call_seconds(trace)
    if not spent:
        return None
    contexts = _named().decode_contexts(facts["requests"],
                                        *facts["trace_span"])
    least = mixed_kv_bytes.least_seconds(facts["config"], contexts,
                                         facts["peak"])
    sent = positions(facts)
    if sent and contexts:
        least *= sent / len(contexts)
    return 100.0 * least / spent
