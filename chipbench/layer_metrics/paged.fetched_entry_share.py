"""Share of the block-table entries the decode calls have to visit that
they were told to FETCH: 100 x ``serving.paged_entries_fetched{phase=
decode}`` / ``serving.paged_entries_live``.  At every decode chunk the
engine adds, on the host, the live entries (a slot at a time) and, where
its architecture's planes fetch a run of entries several live slots share
once (``kernels.paged_attention.shared_runs``: the latent plane's Mosaic
kernel walks the run with the members' query rows stacked, each member
then walks what is its own), the entries those calls fetch: a shared run
once.  100 where nothing is shared; what is under it is bytes the kernel
did not read again.  ``paged.shared_entry_share`` beside it is what the
traffic offers (entries named by two live tables); this is what the
kernel took of it.  A program without the counter (an engine with no
prefix trie, an architecture whose planes fetch a slot at a time, the
parent of PR 58) gives nothing to read."""

NAME = "paged.fetched_entry_share"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    stats = facts["stats"]
    fetched = stats.get("serving.paged_entries_fetched{phase=decode}")
    live = stats.get("serving.paged_entries_live")
    if fetched is None or not live:
        return None
    return 100.0 * fetched / live
