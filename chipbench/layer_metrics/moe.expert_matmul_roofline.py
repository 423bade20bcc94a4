"""The grouped product's share of its roofline over the traced window:
the least time the chip could take for the routed experts' matrix
products of BOTH phases over the device time of the calls whose HLO
instruction is named ``grouped_matmul`` (the name the program gives its
``pallas_call``).

A routed layer's products of one step read the matrices of the experts
touched and multiply the (row, expert) pairs that fell on a held expert:
the least a layer-step takes is the larger of (touched experts x one
expert's bytes / the HBM rate) and (6 x d x e x pairs / the bf16 peak)
(``moe_bytes.expert_call_seconds``).  The counters give each phase's
MEAN touched experts and pairs a layer-step over the whole run; the
mean of maxima is at least the maximum of the means, so a layer-step's
least time is never overstated.  HOW MANY layer-steps the traced window
holds is counted, not estimated: the calls named after the kernel in
the trace, ``CALLS_A_LAYER`` (gate, up, down) to a layer-step.  They are
split between the phases in the proportion of each phase's positions
processed in the window (from the requests' times, as
``paged_attention_named_roofline`` takes its contexts) over the phase's
mean rows a step.  The traced window lies at the end of the measured
one, where more slots are live than over ramp-up and drain: its steps
touch MORE experts than the run's mean step, so the count errs low.  A
reading over 100 is a fault of the count.  A trace in which no call
carries the name, or a program without the counters, gives nothing to
read."""

from chipbench import moe_bytes
from chipbench import run as bench_run

NAME = "moe.expert_matmul_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)

NEEDLE = "grouped_matmul"
CALL = 'custom_call_target="tpu_custom_call"'
# grouped products a routed layer makes of one step: gate, up, down
CALLS_A_LAYER = 3


def kernels(cfg, mix):
    return {"grouped_matmul": ("%" + NEEDLE, CALL)}


def named_calls(trace):
    """(calls, device seconds) of the calls named after the kernel, or
    None."""
    found = [(rec["calls"], rec["seconds"]) for rec in trace["ops"].values()
             if NEEDLE in rec["provenance"].split(" = ")[0]
             and CALL in rec["provenance"]]
    if not found:
        return None
    return sum(c for c, _ in found), sum(s for _, s in found)


def positions(requests, w0, w1):
    """{phase: positions processed in [w0, w1]} from the requests'
    times."""
    both = bench_run.load_reader("paged_attention_roofline").live_contexts
    named = bench_run.load_reader("paged_attention_named_roofline")
    decode = len(named.decode_contexts(requests, w0, w1))
    return {"decode": decode, "prefill": len(both(requests, w0, w1)) - decode}


def least_seconds(config, stats, in_window, layer_steps, peak):
    """The least seconds the grouped products of the window's
    ``layer_steps`` could take, split between the phases in the
    proportion of their estimated steps; None where no phase has
    counters."""
    size = moe_bytes.sizes(config)
    share, each = {}, {}
    for phase in moe_bytes.PHASES:
        count = moe_bytes.counts(stats, phase)
        if count is None or not count["rows"]:
            continue
        run = moe_bytes.steps(config, count) * size["moe_layers"]
        share[phase] = in_window[phase] / (count["rows"] / run)
        each[phase] = moe_bytes.expert_call_seconds(
            config, count["touched"] / run, count["assignments"] / run, peak)
    if not share or not sum(share.values()):
        return None
    return sum(layer_steps * share[phase] / sum(share.values()) * each[phase]
               for phase in share)


def read(facts):
    trace = facts.get("trace")
    if not trace or "trace_span" not in facts:
        return None
    if moe_bytes.sizes(facts["config"]) is None:
        return None
    named = named_calls(trace)
    if not named or not named[1]:
        return None
    calls, spent = named
    least = least_seconds(facts["config"], facts["stats"],
                          positions(facts["requests"], *facts["trace_span"]),
                          calls / CALLS_A_LAYER, facts["peak"])
    if least is None:
        return None
    return 100.0 * least / spent
