"""A retention stack's decode step against its memory roofline: the
least time one batched decode step could take over the median step the
engine measured (``serving.step_seconds``: chunk wall over steps in the
chunk, the wall ending in the token fetch).

``chipbench/retention_bytes.py`` counts 2 bytes for every matmul
parameter a token is multiplied by (once for the whole batch) and, for
every LIVE slot in every layer, its state read once and written once
(2 x 34,080,768 B at the published sizes, 8,256 rows of float32 whatever
the layout stores).  The live slots come from the program's counter
``serving.retention_slot_steps`` (live slots x layers x steps, added on
the host at every decode chunk) over the steps the histogram counted.
A step cannot stream less, so the share cannot pass 100; a reading over
100 is a miscount.  A program without the counter, or a family with no
retention layer, gives nothing to read."""

from chipbench import families, retention_bytes

NAME = "retention.decode_stream_roofline"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def live_slots(facts, size):
    """The mean number of slots live a decode step, or None."""
    hist = facts["stats"].get("serving.step_seconds") or {}
    slot_steps = facts["stats"].get("serving.retention_slot_steps")
    if not hist.get("count") or not slot_steps:
        return None
    steps = hist["count"] * facts["decode_chunk"]
    return slot_steps / (size["layers"] * steps)


def read(facts):
    hist = facts["stats"].get("serving.step_seconds") or {}
    peak, config = facts.get("peak"), facts.get("config")
    if not peak or not hist.get("p50") or not config:
        return None
    size = retention_bytes.sizes(config)
    if size is None:
        return None
    live = live_slots(facts, size)
    if live is None:
        return None
    nbytes = retention_bytes.decode_step_bytes(
        config, families.sizes(config)["matmul_params"], live)
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / hist["p50"]
