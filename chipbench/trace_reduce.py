"""From a profiler trace (``.xplane.pb``) to device numbers, with nothing
but ``jax.profiler.ProfileData``.

* busy seconds: the union of the intervals in which an operation ran on
  a chip (its "XLA Ops" line), averaged over the chips that ran any;
* per-operation sums: device seconds and calls by operation name;
* idle gaps: the intervals of that union's complement, each named by
  the host event that covers most of it (or by the operations on either
  side where the host recorded nothing).
"""

import bisect
import collections
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
_HLO = re.compile(r"^(%[\w.\-]+) = (.*?) ([\w\-]+)\(")


def find_xplane(path):
    """The newest ``.xplane.pb`` at or under ``path``."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(path))


def _is_chip(plane_name):
    rest = plane_name[len(DEVICE_PREFIX):]
    return plane_name.startswith(DEVICE_PREFIX) and rest.isdigit()


def short_name(hlo_text):
    """``%fusion.12 fusion bf16[8,128]`` from the HLO instruction text the
    trace gives as an operation's name (the whole instruction, operands
    and all); other names pass unchanged."""
    m = _HLO.match(hlo_text)
    if not m:
        return hlo_text[:120]
    name, shape, opcode = m.groups()
    shape = re.sub(r"\{[^{}]*\}", "", shape)  # layouts and tilings
    return f"{name} {opcode} {shape}"[:120]


def chip_ops(profile):
    """{plane name: [(start_ns, end_ns, name, provenance)]} for every
    chip plane that has an operations line, sorted by start (an operation
    that contains others, such as a while loop, before what it holds).
    The provenance is the whole HLO instruction, which is all this
    runtime's trace says of where an operation comes from."""
    chips = {}
    for plane in profile.planes:
        if not _is_chip(plane.name):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            events = [(e.start_ns, e.start_ns + e.duration_ns,
                       short_name(e.name), e.name) for e in line.events]
            if events:
                events.sort(key=lambda e: (e[0], -e[1]))
                chips[plane.name] = events
    return chips


def host_events(profile):
    """(starts, ends, names) of every event on a host plane, as two
    int64 arrays and a list."""
    import numpy as np

    rows = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns > 0:
                    rows.append((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name))
    starts = np.array([r[0] for r in rows], np.int64)
    ends = np.array([r[1] for r in rows], np.int64)
    return starts, ends, [r[2] for r in rows]


def busy_union(events):
    """Merged [(start, end)] of possibly nested or overlapping events."""
    merged = []
    for start, end, *_ in events:
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _name_gap(gap, hosts, before, after):
    """The shortest host event that covers at least half of ``gap`` (the
    most specific thing the host was doing), else the neighbours."""
    import numpy as np

    g0, g1 = gap
    starts, ends, names = hosts
    if len(names):
        overlap = np.minimum(ends, g1) - np.maximum(starts, g0)
        covering = np.flatnonzero(overlap >= 0.5 * (g1 - g0))
        if len(covering):
            best = covering[np.argmin((ends - starts)[covering])]
            return f"host:{names[best]}"
    return f"between {before} and {after}"


def _matches(text, needles):
    text = text.lower()
    return all(n.lower() in text for n in needles)


def reduce(profile, top=10, max_named_gaps=200, labels=None):
    """The trace's device numbers, or None where no chip ran anything.

    ``labels`` maps a kernel's label to the needles that find its
    operations (the trace names a Mosaic call ``%closed_call.N``): in
    ``device_ops`` those operations are summed under the label.

    {"chips", "busy_s", "span_s", "ops": {name: {"calls", "seconds",
    "self", "provenance"}}, "device_ops": [[name, self seconds]...],
    "idle_gaps": [[name, seconds]...]}: seconds are averaged over the
    chips; span_s is first operation start to last operation end.
    """
    chips = chip_ops(profile)
    if not chips:
        return None
    n = len(chips)
    hosts = host_events(profile)
    busy_ns = 0
    span_ns = 0
    ops = {}
    gaps_by_name = collections.Counter()
    for events in chips.values():
        merged = busy_union(events)
        busy_ns += sum(end - start for start, end in merged)
        span_ns += merged[-1][1] - merged[0][0]
        # an operation nested inside another (a fusion inside a while
        # loop's body) has its time under "seconds" of both; "self" is
        # an operation's time less what it holds, and sums to the union
        stack = []
        for start, end, name, prov in events:
            if name not in ops:
                label = next((k for k, needles in (labels or {}).items()
                              if _matches(prov, needles)), None)
                ops[name] = {"calls": 0, "seconds": 0.0, "self": 0.0,
                             "provenance": prov, "label": label}
            rec = ops[name]
            rec["calls"] += 1
            rec["seconds"] += (end - start) * 1e-9 / n
            rec["self"] += (end - start) * 1e-9 / n
            while stack and stack[-1][0] <= start:
                stack.pop()
            if stack:
                stack[-1][1]["self"] -= (end - start) * 1e-9 / n
            stack.append((end, rec))
        gaps = [(merged[i][1], merged[i + 1][0])
                for i in range(len(merged) - 1)]
        gaps.sort(key=lambda g: g[0] - g[1])
        starts = [e[0] for e in events]
        for g0, g1 in gaps[:max_named_gaps]:
            i = bisect.bisect_left(starts, g1)
            after = events[i][2] if i < len(events) else "end"
            before = events[i - 1][2] if i > 0 else "start"
            gaps_by_name[_name_gap((g0, g1), hosts, before, after)] += (
                (g1 - g0) * 1e-9 / n)
        rest = sum(g1 - g0 for g0, g1 in gaps[max_named_gaps:])
        if rest:
            gaps_by_name["shorter gaps, unnamed"] += rest * 1e-9 / n
    for rec in ops.values():
        rec["calls"] = rec["calls"] / n
    grouped = collections.Counter()
    for name, rec in ops.items():
        grouped[f"kernel:{rec['label']}" if rec["label"] else name] += (
            rec["self"])
    device_ops = grouped.most_common(top)
    return {"chips": n, "busy_s": busy_ns * 1e-9 / n,
            "span_s": span_ns * 1e-9 / n, "ops": ops,
            "device_ops": [[k, s] for k, s in device_ops],
            "idle_gaps": [[k, s] for k, s in gaps_by_name.most_common(top)]}


def matching(summary, *needles):
    """(calls, seconds) summed over operations whose name or provenance
    contains every needle (case-insensitive)."""
    calls, seconds = 0.0, 0.0
    for name, rec in summary["ops"].items():
        if _matches(name + " " + rec["provenance"], needles):
            calls += rec["calls"]
            seconds += rec["seconds"]
    return calls, seconds


def idle_share_percent(facts):
    """1 - busy union / traced window, in percent, from a run's facts."""
    trace, window = facts.get("trace"), facts.get("trace_window_s")
    if not trace or not window:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / window)
