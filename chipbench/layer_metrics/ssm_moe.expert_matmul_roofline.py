"""The grouped product's share of its roofline over the traced window
where an expert is TWO matrices (``relu(x W_up) ** 2 W_down``, 1,856
wide: 14.5 lane tiles): the least time the chip could take for the
routed experts' products of BOTH phases over the device time of the
calls whose HLO instruction is named ``grouped_matmul``.

``moe.expert_matmul_roofline``'s arithmetic with this family's sizes
(``ssm_moe_bytes.expert_call_seconds``: the larger of touched experts x
one expert's two matrices / the HBM rate and 4 x d x e x pairs / the
bf16 peak) and ``CALLS_A_LAYER`` 2 (up, down): the counters give each
phase's MEAN touched experts and pairs a layer-step, the layer-steps of
the window are the named calls over two, split between the phases in
the proportion of each phase's positions processed in the window over
the phase's mean rows a step.  The traced window lies at the end of the
measured one, where more slots are live: its steps touch MORE experts
than the run's mean step, so the count errs low.  A reading over 100 is
a fault of the count.  A trace in which no call carries the name, or a
program without the counters, gives nothing to read."""

from chipbench import moe_bytes, ssm_moe_bytes
from chipbench import run as bench_run

NAME = "ssm_moe.expert_matmul_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

# grouped products a routed layer makes of one step: up, down
CALLS_A_LAYER = 2


def kernels(cfg, mix):
    return bench_run.load_reader("moe.expert_matmul_roofline").kernels(
        cfg, mix)


def least_seconds(config, stats, in_window, layer_steps, peak):
    """The least seconds the grouped products of the window's
    ``layer_steps`` could take, split between the phases in the
    proportion of their estimated steps; None where no phase has
    counters."""
    size = ssm_moe_bytes.sizes(config)
    share, each = {}, {}
    for phase in ssm_moe_bytes.PHASES:
        count = moe_bytes.counts(stats, phase)
        if count is None or not count["rows"]:
            continue
        run = ssm_moe_bytes.steps(config, count) * size["moe_layers"]
        share[phase] = in_window[phase] / (count["rows"] / run)
        each[phase] = ssm_moe_bytes.expert_call_seconds(
            config, count["touched"] / run, count["assignments"] / run, peak)
    if not share or not sum(share.values()):
        return None
    return sum(layer_steps * share[phase] / sum(share.values()) * each[phase]
               for phase in share)


def read(facts):
    trace = facts.get("trace")
    if not trace or "trace_span" not in facts:
        return None
    if ssm_moe_bytes.sizes(facts["config"]) is None:
        return None
    gated = bench_run.load_reader("moe.expert_matmul_roofline")
    named = gated.named_calls(trace)
    if not named or not named[1]:
        return None
    calls, spent = named
    least = least_seconds(
        facts["config"], facts["stats"],
        gated.positions(facts["requests"], *facts["trace_span"]),
        calls / CALLS_A_LAYER, facts["peak"])
    if least is None:
        return None
    return 100.0 * least / spent
