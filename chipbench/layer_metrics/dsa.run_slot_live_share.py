"""Of the slots the decode chunks' sparse calls scored, selected and
gathered for, the share that were live: ``serving.sparse_slots_live``
over ``serving.sparse_slots_run`` (``kernels/sparse_attention.py``: a
call runs its three steps for ``slots_run(live, S)`` packed slots, the
smallest of a few static counts that holds the live ones; 28% where it
runs for every row of a ten-slot table with 2.8 live).  A program
without the counters gives nothing to read."""

NAME = "dsa.run_slot_live_share"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    stats = facts["stats"]
    live = stats.get("serving.sparse_slots_live")
    run = stats.get("serving.sparse_slots_run")
    if not run or live is None:
        return None
    return 100.0 * live / run
