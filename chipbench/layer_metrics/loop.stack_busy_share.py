"""Share of the chip's busy time spent inside the loop over passes of a
stack that runs several times over the same weights: device seconds of
the pass loops over the busy union, over the traced window.  What is
left is embedding, head, argmax, the copy-on-write copies and whatever
the compiler put between them.

The program runs that stack under ``jax.named_scope("serving.stack_pass")``,
but this runtime's trace carries the HLO instruction and not its
``op_name``, so the loop is found as what the compiler made of it: a
``while`` operation that holds no other ``while`` (in the decode chunk it
sits inside the loop over the chunk's steps; in a prefill it is the only
one) and carries the K/V pool with the passes folded into its block axis:
a plane of ``[passes * blocks, block_tokens, heads, head_dim]``,
``passes`` from the family's ``stack_passes`` (1 where a family has
none) and ``blocks`` from the engine geometry in the traffic file.  A program whose stack runs once has no such loop and
gives nothing to read.  One whose stack runs several times has to show
it: a trace in which no such loop is found (a layer-level scan inside the
stack, another compiler's spelling of the loop) is an error, not a
missing number, and so is a loop that outlasts the device's busy time.
The events themselves are needed (which operation holds which), so the
trace file is read, not the reducer's summary."""

import re

from chipbench import families, trace_reduce

NAME = "loop.stack_busy_share"
LAYER = "Decode/prefill step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def pool_blocks(mix):
    eng = mix["engine"]
    per_slot = -(-eng["max_len"] // eng["block_tokens"])
    return (1 + eng["max_slots"] * per_slot
            + eng.get("cache_blocks", 2 * per_slot))


def stack_passes(cfg):
    return getattr(families.of(cfg), "stack_passes", lambda cfg: 1)(cfg)


def folded_plane(cfg, mix):
    """Matches the shape of a pool array that holds every pass of the
    configuration's stack."""
    size = families.sizes(cfg)
    tail = (f"{mix['engine']['block_tokens']},{size['heads']},"
            f"{size['head_dim']}")
    rows = stack_passes(cfg) * pool_blocks(mix)
    pattern = re.compile(r"bf16\[(\d+)," + re.escape(tail) + r"\]")

    def matches(provenance):
        return any(int(n) == rows for n in pattern.findall(provenance))

    return matches


def _is_while(provenance):
    return " while(" in provenance.split(", condition=")[0]


def pass_loop_seconds(events, carries_folded_plane):
    """Device seconds of the innermost ``while`` operations among
    ``events`` [(start_ns, end_ns, name, provenance)], sorted by start
    with a holder before what it holds, that carry a folded plane."""
    loops = [e for e in events if _is_while(e[3])]
    total = 0
    for (start, end, _name, prov), after in zip(loops, loops[1:] + [None]):
        # sorted by start, so a loop it holds would be the next one
        holds_one = after is not None and after[1] <= end
        if not holds_one and carries_folded_plane(prov):
            total += end - start
    return total * 1e-9


def read(facts):
    trace, path = facts.get("trace"), facts.get("trace_path")
    if not trace or not path or not trace.get("busy_s"):
        return None
    if stack_passes(facts["config"]) < 2:
        return None
    chips = trace_reduce.chip_ops(trace_reduce.load(path))
    if not chips:
        return None
    matches = folded_plane(facts["config"], facts["traffic"])
    seconds = sum(pass_loop_seconds(events, matches)
                  for events in chips.values()) / len(chips)
    if not 0 < seconds <= trace["busy_s"]:
        raise RuntimeError(
            f"{NAME}: the stack runs {stack_passes(facts['config'])} "
            f"times, and the trace's pass loops take {seconds} s of "
            f"{trace['busy_s']} s busy: the loop is not spelled as this "
            f"reader expects (see the module's docstring)")
    return 100.0 * seconds / trace["busy_s"]
