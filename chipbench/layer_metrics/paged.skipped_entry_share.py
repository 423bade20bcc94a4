"""Share of the block-table entries a paged-attention call spans that it
does not have to visit: 100 x (1 - ``serving.paged_entries_live`` /
``serving.paged_entries_total``).  At every decode chunk the engine
adds, on the host, the entries that hold a key some live slot attends at
the chunk's start (``ceil((pos + 1) / block_tokens)`` summed over live
slots) and the whole table (``max_slots x blocks_per_slot``).  A fact of
the traffic and the engine's geometry, not of the kernel: it says how
much a kernel that visits live entries only has to gain."""

NAME = "paged.skipped_entry_share"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    stats = facts["stats"]
    total = stats.get("serving.paged_entries_total")
    if not total:
        return None
    return 100.0 * (1.0 - stats.get("serving.paged_entries_live", 0.0) / total)
