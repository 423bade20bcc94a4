"""Share of the block-table entries the decode calls visit whose block
more than one live slot's table names: 100 x
``serving.paged_entries_shared`` / ``serving.paged_entries_live``.  At
every decode chunk the engine adds, on the host, the live entries (those
that hold a key some live slot attends at the chunk's start) and, of
them, those whose block id stands in two live slots' chains: the shared
heads the prefix trie handed out.  A fact of the traffic, not of the
kernel: it is what a paged read that fetches a chain ONCE for the slots
that share it has to gain over one that fetches it once a slot.  A
program without the counter gives nothing to read."""

NAME = "paged.shared_entry_share"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    stats = facts["stats"]
    shared = stats.get("serving.paged_entries_shared")
    live = stats.get("serving.paged_entries_live")
    if shared is None or not live:
        return None
    return 100.0 * shared / live
