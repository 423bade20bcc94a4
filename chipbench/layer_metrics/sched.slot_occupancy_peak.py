"""Most slots the window had in use at once, as a share of the engine's
``max_slots``: the ``serving.slots_active`` gauge, read by the load
generator 20 times a second.  The pool reserves a worst-case chain for
every slot, so slots the traffic never reaches are HBM that holds
nothing and table entries the paged kernel streams for nothing."""

NAME = "sched.slot_occupancy_peak"
LAYER = "Serving scheduler"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    seen = facts.get("slots_active_seen")
    return 100.0 * max(seen) / facts["max_slots"] if seen else None
