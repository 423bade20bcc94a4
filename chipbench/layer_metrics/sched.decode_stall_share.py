"""Share of a decoding request's time spent waiting for its next chunk
while the driver did something else (admission, another request's
prefill, the emit loop): the engine's ``serving.stalled_seconds`` over
``serving.live_seconds``.  At every decode chunk the engine adds, for
each live slot, the time since that slot last advanced up to the chunk's
start (stalled) and up to its end (live), so both cover the same
requests at any snapshot and stalled <= live."""

NAME = "sched.decode_stall_share"
LAYER = "Serving scheduler"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    stats = facts["stats"]
    live = stats.get("serving.live_seconds")
    if not live:
        return None
    return 100.0 * stats.get("serving.stalled_seconds", 0.0) / live
