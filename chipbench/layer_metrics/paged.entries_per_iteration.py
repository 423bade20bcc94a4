"""Table entries ONE iteration of the paged kernel's loop takes:
``serving.paged_entries_live`` / ``serving.paged_iterations_live``.  At
every decode chunk the engine adds, on the host, the table entries its
paged calls have to visit and the iterations the kernel's loop makes
over them, a live slot at a time, which the kernel's module states
(``kernels.paged_attention.loop_iterations``: a group of entries an
iteration where rows share a fold, by a rule on the shapes the kernel
sees, and on a latent plane; an entry an iteration for one row a block
and for the grid form).  An iteration costs its chain of latencies
whatever it holds, so this says how far a cell's chains divide it: 1.0
where every iteration takes one entry.  A fact of the architecture's
planes, the kernel's rule and the chains' lengths (a chain's last group
counts whole).  A program without the second counter gives nothing to
read."""

NAME = "paged.entries_per_iteration"
LAYER = "Kernels"
UNIT = "entries"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    stats = facts["stats"]
    iterations = stats.get("serving.paged_iterations_live")
    if not iterations:
        return None
    return stats.get("serving.paged_entries_live", 0.0) / iterations
