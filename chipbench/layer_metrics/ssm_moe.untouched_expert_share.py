"""Share of the held experts that no live row selected, over the decode
steps of a stack whose experts are two matrices: 100 x (1 -
``serving.moe_experts_touched{phase=decode}`` / ``serving.
moe_expert_visits{phase=decode}``), ``moe.untouched_expert_share``'s
counters and arithmetic.  A fact of the traffic and the geometry (some
18 rows x top 6 over 128 experts leave a held expert without a row with
probability (1 - 6/128)^18 = 42%), not of the kernel: what a grouped
product that reads touched experts only gains over one that streams all
16.  A program without the counters gives nothing to read."""

from chipbench import moe_bytes

NAME = "ssm_moe.untouched_expert_share"
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
