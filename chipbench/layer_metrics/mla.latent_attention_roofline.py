"""The latent plane's kernel against its roofline over the traced
window: the least time the chip could take to attend the decode
positions processed in the window (``latent_bytes.least_seconds``: the
larger of reading each position's cached rows, 576 values a plane at the
published sizes, and of the 16 query rows' products with them) over the
device time of the Mosaic calls whose HLO instruction is named
``paged_latent_attention`` (the name the program gives its
``pallas_call``).

Decode positions only: a prefill piece of 8 rows or more attends densely
and makes no call to the kernel.  The positions come from the requests'
own times (``paged_attention_named_roofline.decode_contexts``); a slot
that finished inside a chunk rides it out on the device and is not
counted, so the count errs low.  A reading over 100 is a fault of the
count.  A trace in which no call carries the name, or a family with no
latent plane, gives nothing to read."""

from chipbench import latent_bytes
from chipbench import run as bench_run

NAME = "mla.latent_attention_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLE = "paged_latent_attention"
CALL = 'custom_call_target="tpu_custom_call"'


def kernels(cfg, mix):
    return {"paged_latent_attention": ("%" + NEEDLE, CALL)}


def call_seconds(trace):
    """Device seconds of the calls named after the kernel, or None."""
    seconds = [rec["seconds"] for rec in trace["ops"].values()
               if NEEDLE in rec["provenance"].split(" = ")[0]
               and CALL in rec["provenance"]]
    return sum(seconds) if seconds else None


def read(facts):
    trace = facts.get("trace")
    if not trace or "trace_span" not in facts:
        return None
    if latent_bytes.sizes(facts["config"]) is None:
        return None
    spent = call_seconds(trace)
    if not spent:
        return None
    contexts = bench_run.load_reader(
        "paged_attention_named_roofline").decode_contexts(
            facts["requests"], *facts["trace_span"])
    return 100.0 * latent_bytes.least_seconds(
        facts["config"], contexts, facts["peak"]) / spent
