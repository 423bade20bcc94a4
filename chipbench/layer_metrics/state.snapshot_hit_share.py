"""Share of the window's prompt tokens whose prefill was skipped because
the request started from a STATE SNAPSHOT: for an architecture that
holds recurrent state beside its planes every prefix hit is one (the hit
is cut back to the deepest cached node that has a snapshot, and the
slot's state is written from it), so it is the engine's
``serving.prefix_hit_tokens`` over the prompt tokens it admitted (those
and ``serving.prefill_real_tokens``, the suffixes it computed), counted
only where the engine says it restored snapshots
(``serving.state_snapshot_hits``).  A program without that counter (no
state, or no snapshots yet) gives nothing to read."""

NAME = "state.snapshot_hit_share"
LAYER = "Serving scheduler"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    stats = facts["stats"]
    if stats.get("serving.state_snapshot_hits") is None:
        return None
    skipped = stats.get("serving.prefix_hit_tokens") or 0.0
    admitted = skipped + (stats.get("serving.prefill_real_tokens") or 0.0)
    return 100.0 * skipped / admitted if admitted else None
