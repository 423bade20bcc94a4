"""A block-sparse / lightning stack's decode step against its memory
roofline: the least time the run's MEAN batched decode step could take
over the MEAN step the engine measured (``serving.step_seconds``: chunk
wall over steps in the chunk, the wall ending in the token fetch).  Both
are means over the same steps, so the share is the bytes every decode
step of the run had to stream over the seconds they took: it cannot pass
100 whatever the spread of the load.

``chipbench/sala_bytes.py`` counts 2 bytes for every matmul parameter
(once for the whole batch: 5.04 GB at the held layers, their
matrices and the head; the table's rows are gathered, not streamed), every LIVE slot's
state of every ``L`` layer read once and written once (2 x 2,097,152 B a
layer), and for every decode position past ``dense_len`` in every ``S``
layer its context's compressed keys (512 B every 16 positions) and the
K and V of the 97 blocks each of its two K/V heads selected (the
requests' own lengths: one entry a decode position).  A step cannot
stream less; a reading over 105 is refused as a miscount.  A program
without the histogram, or a family with no such layers, gives nothing to
read."""

from chipbench import run as bench_run
from chipbench import sala_bytes

NAME = "sala.decode_stream_roofline"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    hist = facts["stats"].get("serving.step_seconds") or {}
    peak, config = facts.get("peak"), facts.get("config")
    if not peak or not hist.get("mean") or not hist.get("count") \
            or not config:
        return None
    if sala_bytes.sizes(config) is None:
        return None
    steps = hist["count"] * facts["decode_chunk"]
    contexts = bench_run.load_reader(
        "hybrid.decode_stream_roofline").decode_contexts(facts["requests"])
    if not contexts:
        return None
    nbytes = sala_bytes.decode_step_bytes(
        config, len(contexts) / steps, contexts, steps)
    return sala_bytes.share(
        NAME, 100.0 * nbytes / peak["hbm_bytes_per_s"] / hist["mean"])
