"""Share of the held experts that no live row selected, over the decode
steps: 100 x (1 - ``serving.moe_experts_touched{phase=decode}`` /
``serving.moe_expert_visits{phase=decode}``).  The compiled decode step
returns, beside its tokens, how many of the experts held here got at
least one live row in each routed layer; the visits are held experts x
routed layers x steps.  A fact of the traffic and the geometry (25 rows
over 32 experts miss one with probability (31/32)^25 = 46%), not of the
kernel: it is what a grouped product that reads touched experts only
has to gain over one that streams them all.  A program without the
counters gives nothing to read."""

from chipbench import moe_bytes

NAME = "moe.untouched_expert_share"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    count = moe_bytes.counts(facts["stats"], "decode")
    if count is None:
        return None
    return 100.0 * moe_bytes.untouched_share(count)
