"""What the engine publishes of its finished requests' time per output
token (``ServingEngine._publish_tail``, ``_account_stall``; PR 53), read
from ``facts["stats"]`` for the ``tail.*`` and ``step.decode_*`` readers.

The TAIL SET is the finished requests at or above the 80th percentile of
their own time per output token: the slowest fifth, which the judged 90th
percentile bisects.  Over it the engine sums T seconds from first token to
finish in three parts (inside the requests' chunks' clock pairs C, waiting
inside another request's prefill clock pair, waiting for the rest), N tokens
after the first, K steps the chunks ran and the rows those chunks stepped x
steps.  T / N = (C / K) x (K / N) x (T / C).  A program without the gauges
(the parent of PR 53) gives nothing to read."""

PARTS = ("chunk", "stall_prefill", "stall_host")


def tail(stats):
    """{"seconds": {part: s}, "T", "N", "K", "slot_steps", "requests"} of
    the tail set, or None where nothing was published."""
    tokens = stats.get("serving.tpot_tail_tokens")
    steps = stats.get("serving.tpot_tail_steps")
    seconds = {p: stats.get("serving.tpot_tail_seconds{part=%s}" % p)
               for p in PARTS}
    if not tokens or not steps or None in seconds.values():
        return None
    total = sum(seconds.values())
    if total <= 0:
        return None
    return {"seconds": seconds, "T": total, "N": tokens, "K": steps,
            "slot_steps": stats.get("serving.tpot_tail_slot_steps", 0.0),
            "requests": stats.get("serving.tpot_tail_requests", 0.0)}


def chunk_fit(stats):
    """(base, slope) in seconds of the least-squares line ``w = base +
    slope x a`` over every decode chunk collected (``w`` the chunk's clock
    pair / its steps, ``a`` the rows it stepped), from the five sums
    ``serving.chunk_fit{sum=n|a|aa|w|aw}``; None without them, and where
    the rows never varied (no line through one abscissa)."""
    s = {k: stats.get("serving.chunk_fit{sum=%s}" % k)
         for k in ("n", "a", "aa", "w", "aw")}
    if None in s.values() or s["n"] < 2:
        return None
    spread = s["n"] * s["aa"] - s["a"] ** 2
    if spread <= 0:
        return None
    slope = (s["n"] * s["aw"] - s["a"] * s["w"]) / spread
    return (s["w"] - slope * s["a"]) / s["n"], slope
