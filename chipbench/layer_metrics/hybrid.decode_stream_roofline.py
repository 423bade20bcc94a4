"""A hybrid stack's decode step against its memory roofline: the least
time one batched decode step could take over the median step the engine
measured (``serving.step_seconds``: chunk wall over steps in the chunk,
the wall ending in the token fetch).

``step.decode_stream_roofline`` counts K and V as planes x heads x head
size, every plane read once and whole; a stack with fewer K/V heads than
heads, window planes, a plane read by several layers and recurrent state
is counted by ``chipbench/hybrid_bytes.py`` instead: 2 bytes a matmul
parameter once for the batch, the K/V of the live contexts clipped to
each plane's window with the full plane once a reader, and twice the
state bytes of the live slots.  The live contexts are the mean over the
run's decode steps, from the requests' own lengths, never from the
program's counter (``serving.paged_bytes_streamed`` is held to the same
count by a test).  The step includes the host's turn-round; it cannot
pass 100%: a reading over 100 is a miscount."""

from chipbench import families, hybrid_bytes

NAME = "hybrid.decode_stream_roofline"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def decode_contexts(requests):
    """Tokens attended, one entry per decode step of the run: the step
    that emits a request's token ``i + 1`` attends the prompt and the
    ``i`` tokens emitted so far."""
    return [r["prompt_len"] + i for r in requests if r["first"] is not None
            for i in range(1, r["out"])]


def read(facts):
    hist = facts["stats"].get("serving.step_seconds") or {}
    peak, config = facts.get("peak"), facts.get("config")
    if not hist.get("count") or not peak or not hist.get("p50"):
        return None
    if not hasattr(families.of(config), "hybrid_sizes"):
        return None
    steps = hist["count"] * facts["decode_chunk"]
    nbytes = hybrid_bytes.decode_step_bytes(
        config, decode_contexts(facts["requests"]), steps)
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / hist["p50"]
