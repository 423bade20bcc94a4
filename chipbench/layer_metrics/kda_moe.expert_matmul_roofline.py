"""The grouped product's share of its roofline over the traced window at
40 held experts 1,280 wide (ten lane tiles; 31.5 MB an expert's three
matrices): the least time the chip could take for the routed experts'
products of BOTH phases over the device time of the calls whose HLO
instruction is named ``grouped_matmul``.

A layer-step's least time is ``delta_bytes.expert_call_seconds``: the
larger of touched experts x one expert's three matrices / the HBM rate
and 6 x d x e x pairs / the bf16 peak.  The counters give each phase's
MEAN touched experts and pairs a layer-step over the whole run; the
layer-steps of the WINDOW are the program's own: every
``serving.decode_chunk`` span that starts inside the traced window's
interval is ``steps`` x ``moe_layers`` decode layer-steps of ``active``
rows, every ``serving.prefill`` span ``pieces`` x ``moe_layers`` prefill
ones.  (``moe.expert_matmul_roofline`` splits the named calls between the
phases by the requests' times over the run's mean rows a step: where the
window is quieter than the run, as the last seconds of this cell's
schedule are, it takes decode steps for pieces, 38 experts each, and read
161 here.)  A chunk of fewer rows than the run's mean step is held to
that share of the mean step's touched experts and pairs, which errs LOW
(experts touched grow more slowly than rows); one of more rows to the
mean itself.  A reading over 105 is refused.  A trace in which no call
carries the name, a program without the counters or the spans, or a
family with no such layers, gives nothing to read."""

from chipbench import delta_bytes, moe_bytes, trace_reduce
from chipbench import run as bench_run

NAME = "kda_moe.expert_matmul_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def _gated():
    return bench_run.load_reader("moe.expert_matmul_roofline")


def kernels(cfg, mix):
    return _gated().kernels(cfg, mix)


def least_seconds(config, stats, chunks, admitted, peak):
    """The least seconds the grouped products of the window could take:
    ``chunks`` are ``(active, steps, moe_layers)`` of its decode chunks,
    ``admitted`` ``(pieces, moe_layers)`` of its admissions; None where a
    phase that ran in the window has no counters."""
    mean = {}
    for phase in delta_bytes.PHASES:
        count = moe_bytes.counts(stats, phase)
        if count is not None and count["rows"]:
            run = delta_bytes.steps(config, count) * delta_bytes.sizes(
                config)["moe_layers"]
            mean[phase] = {k: count[k] / run
                           for k in ("rows", "touched", "assignments")}
    if (chunks and "decode" not in mean) or (admitted
                                             and "prefill" not in mean):
        return None
    least = 0.0
    for active, steps, layers in chunks:
        m = mean["decode"]
        part = min(1.0, int(active) / m["rows"])
        least += int(steps) * int(layers) * delta_bytes.expert_call_seconds(
            config, m["touched"] * part, m["assignments"] * part, peak)
    for pieces, layers in admitted:
        m = mean["prefill"]
        least += int(pieces) * int(layers) * delta_bytes.expert_call_seconds(
            config, m["touched"], m["assignments"], peak)
    return least


def read(facts):
    trace = facts.get("trace")
    if not trace or not facts.get("trace_path"):
        return None
    if delta_bytes.sizes(facts["config"]) is None:
        return None
    named = _gated().named_calls(trace)
    if not named or not named[1]:
        return None
    profile = trace_reduce.load(facts["trace_path"])
    inside = lambda name, *attrs: delta_bytes.spans_inside(      # noqa: E731
        profile, facts["trace_interval"], name, *attrs)
    chunks = inside("serving.decode_chunk", "active", "steps", "moe_layers")
    admitted = inside("serving.prefill", "pieces", "moe_layers")
    if not chunks and not admitted:
        return None
    least = least_seconds(facts["config"], facts["stats"], chunks, admitted,
                          facts["peak"])
    if least is None:
        return None
    return delta_bytes.share(NAME, 100.0 * least / named[1])
