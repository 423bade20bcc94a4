"""What the prefill pieces' chain walks visit of what a dense window
attends: 100 x ``serving.prefill_entries{kind=attended}`` /
``serving.prefill_entries{kind=chain}``.  Once a piece the engine adds,
on the host, for every call that walks its chain (a wide window over a
K/V plane: ``kernels.paged_attention.walks_chain``), the table entries
from the piece's lower bound to its last position, and the entries the
dense spelling of the same call gathers and scores whatever the context:
the whole chain (``blocks_per_slot``), or, where it goes one K/V head at
a time, a lower bound's own entries.  A fact of the traffic and the
engine's geometry, not of the kernel: it says what a walk bounded by the
context leaves out (a full plane reads some 26% where prompts of a median
4,096 tokens fill chains of 13,312 positions: the dense spelling did four
times the work there; a window-128 plane, 20 entries of 22, near all).  A
prompt token's cost sets how long an admission stalls the live slots,
so the tail it moves is ``tpot_p90_ms``.  A program without the counter
(the parent, or a cell whose pieces stay dense) gives nothing to read."""

NAME = "prefill.attended_entry_share"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    stats = facts["stats"]
    chain = stats.get("serving.prefill_entries{kind=chain}")
    if not chain:
        return None
    return 100.0 * stats.get("serving.prefill_entries{kind=attended}",
                             0.0) / chain
