"""Query rows that share one online-softmax update of the paged kernel:
``serving.paged_rows_live`` / ``serving.paged_updates_live``.  At every
decode chunk the engine adds, on the host, the query rows its paged
calls send through the live table entries (a K/V group folds into that
many rows of the window) and the softmax updates the kernel makes for
those entries, which the kernel's module states as a function of the
folded width.  1.0 where one row attends a block: no second row to share
the update with.  A fact of the architecture and the kernel's body, not
of the traffic: it says which cell a body that folds a block once for
all its rows can reach.  A program without the two counters gives
nothing to read."""

NAME = "paged.rows_per_update"
LAYER = "Kernels"
UNIT = "rows"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    stats = facts["stats"]
    updates = stats.get("serving.paged_updates_live")
    if not updates:
        return None
    return stats.get("serving.paged_rows_live", 0.0) / updates
