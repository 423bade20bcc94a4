"""Bytes and operations of a routed FFN on the serving path, computed
from sizes and from counts of what was routed: whatever implements the
layer, this is what it cannot avoid.

A routed layer holds ``experts_held`` of the router's experts here; a
step routes every live row over all of them and the held experts
compute their part for the rows that selected them.  What a step has to
READ of the routed experts is the three matrices of each expert that at
least one live row selected (``experts touched``); what it has to
COMPUTE is two operations a parameter of an expert for every (row,
expert) pair that fell on a held expert.  The sizes come from the
family's ``sizes(config)``, ``hybrid_sizes(config)`` and
``moe_sizes(config)``; the counts from the program's counters
(``serving.moe_experts_touched``, ``serving.moe_assignments_held``,
``serving.moe_rows``, ``serving.moe_expert_visits``, each by phase),
which ``chipbench/tests/test_gated_moe_family.py`` holds to a count of
the reference's own selections.  ``chipbench/MOE.md`` has the
arithmetic at the published sizes.
"""

from . import families, hybrid_bytes

PHASES = ("decode", "prefill")


def sizes(config):
    """``sizes``, ``hybrid_sizes`` and ``moe_sizes`` of the
    configuration's family in one dict; ``None`` for a family with no
    routed layer."""
    family = families.of(config)
    if not hasattr(family, "moe_sizes"):
        return None
    return dict(hybrid_bytes.sizes(config), **family.moe_sizes(config))


def expert_bytes(config, itemsize=2):
    """One routed expert's three matrices."""
    return sizes(config)["expert_params"] * itemsize


def counts(stats, phase):
    """The routing counters of ``phase`` out of ``eng.stats()``: ``{rows,
    assignments, touched, visits}``, or ``None`` where the program has no
    such counters (or the phase never ran)."""
    out = {short: stats.get(f"serving.moe_{name}{{phase={phase}}}")
           for short, name in (("rows", "rows"),
                               ("assignments", "assignments_held"),
                               ("touched", "experts_touched"),
                               ("visits", "expert_visits"))}
    if not out["visits"] or out["rows"] is None:
        return None
    return out


def steps(config, count):
    """Steps (decode) or pieces (prefill) the counters of one phase were
    summed over: every one of them visits every held expert of every
    routed layer once at most."""
    size = sizes(config)
    return count["visits"] / (size["experts_held"] * size["moe_layers"])


def decode_step_bytes(config, touched_per_step, contexts, n_steps,
                      itemsize=2):
    """Bytes ONE batched decode step cannot avoid, as the mean over
    ``n_steps`` steps that together processed ``contexts`` (one entry a
    decode position: the tokens it attended) and touched
    ``touched_per_step`` (expert, layer) pairs a step: every matmul
    parameter OUTSIDE the routed experts once for the whole batch, the
    matrices of the experts touched, and the K/V the masks let
    through."""
    size = sizes(config)
    _, kv = hybrid_bytes.paged_live(config, contexts, itemsize)
    return (itemsize * (size["outside_params"]
                        + size["expert_params"] * touched_per_step)
            + kv / n_steps)


def expert_call_seconds(config, touched, assignments, peak, itemsize=2):
    """The least seconds ONE routed layer's grouped product (its three
    matrices together) can take on ``touched`` experts with
    ``assignments`` (row, expert) pairs: the larger of reading the
    touched experts and of multiplying the pairs."""
    size = sizes(config)
    return max(touched * size["expert_params"] * itemsize
               / peak["hbm_bytes_per_s"],
               size["expert_ops_per_row"] * assignments
               / peak["bf16_flops_per_s"])


def untouched_share(count):
    """Share of the (held expert, layer, step) visits in which no live
    row selected the expert."""
    return 1.0 - count["touched"] / count["visits"]
