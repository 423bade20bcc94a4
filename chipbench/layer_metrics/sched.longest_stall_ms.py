"""The longest single wait before a decode chunk that any live request met
in the window: the engine's ``serving.longest_stall_seconds`` (one ``max`` a
chunk, zeroed after the warm pass).  ``sched.decode_stall_share`` pools every
wait; this tells one stall of a second from a thousand of a millisecond.  A
program without the gauge (the parent of PR 53) gives nothing to read."""

NAME = "sched.longest_stall_ms"
LAYER = "Serving scheduler"
UNIT = "ms"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"
RUNNERS = ("serve",)


def read(facts):
    longest = facts["stats"].get("serving.longest_stall_seconds")
    return None if longest is None else 1e3 * longest
