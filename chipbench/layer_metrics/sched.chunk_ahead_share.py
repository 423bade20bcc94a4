"""How often the driver kept a decode chunk in flight: 100 x
``serving.chunks_dispatched{ahead=1}`` / the chunks dispatched.  The
engine counts every decode chunk it sends to the device, ``ahead=1`` where
another chunk was still unread at that moment (the device goes from that
one to this with no host in between: the fetch, the emit loop, the queue
poll and the next table run under a chunk), ``ahead=0`` where nothing was
in flight (the first chunk after an admission, which reads what is in
flight while its pieces run, or after an idle stretch).  A fact of the
traffic and the driver loop, not of any kernel: near 100 where admissions
are rare beside chunks, some 85 where one arrives every seventh chunk.
What it hides is the host's turn-round a chunk, which the requests' token
gaps contain: the tail it moves is ``tpot_p90_ms``.  A program without the
counter (the parent of PR 48: one chunk at a time) gives nothing to
read."""

NAME = "sched.chunk_ahead_share"
LAYER = "Serving scheduler"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    stats = facts["stats"]
    ahead = stats.get("serving.chunks_dispatched{ahead=1}", 0.0)
    sent = ahead + stats.get("serving.chunks_dispatched{ahead=0}", 0.0)
    if not sent:
        return None
    return 100.0 * ahead / sent
