"""Compiled-HLO artifact tools for the static-analysis engine.

The optimized-HLO parsing that used to live in ``core/memaudit.py``
(PR 5's cross-chip comm audit) plus the ``memory_analysis()`` flattener
and the donated-buffer alias probe.  GSPMD *inserts* collectives at
compile time, so the jaxpr never shows them — the only place the "one
gradient reduction per optimizer step" invariant is checkable is the
partitioned optimized HLO, and the load-bearing classification is LOOP
MEMBERSHIP: a reduce op inside a while body executes once per loop
iteration, one at top level executes once per step.
"""

import collections
import re

__all__ = [
    "REDUCE_COLLECTIVES", "GATHER_COLLECTIVES", "ALL_COLLECTIVES",
    "hlo_comm_report", "comm_report", "loop_computations",
    "iter_instructions", "called_computations",
    "compiled_memory_stats", "shape_pattern",
]

# collectives that REDUCE across chips (gradient aggregation); gathers /
# permutes move activations and are reported separately
REDUCE_COLLECTIVES = ("all-reduce", "reduce-scatter")
GATHER_COLLECTIVES = ("all-gather", "collective-permute", "all-to-all",
                      "collective-broadcast")
ALL_COLLECTIVES = REDUCE_COLLECTIVES + GATHER_COLLECTIVES
# legacy aliases (pre-ISSUE-14 private names)
_GATHER_COLLECTIVES = GATHER_COLLECTIVES
_ALL_COLLECTIVES = ALL_COLLECTIVES

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(")
_CALL_RE = re.compile(
    r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
# lhs shapes may be a tuple — async ``-start`` forms return
# ``(operand..., result...)`` — so the shape-list class admits parens
_COLL_RE = re.compile(
    r"=\s*(\(?[\w\[\]{},:*/() ]*?)\s*"
    r"\b(" + "|".join(_ALL_COLLECTIVES) + r")((?:-start)?)[.\d]*\(")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
# ``[ROOT] %name = <shape> opcode(``: the opcode is the first word that
# follows a space and opens a parenthesis (a tiling ``T(8,128)`` follows
# a colon, a tuple shape's members follow ``(`` or ``, `` and end in
# ``]`` or ``}``)
_INSTR_RE = re.compile(
    r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\(")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')

Instruction = collections.namedtuple(
    "Instruction", "comp name shape opcode head op_name root")


def iter_instructions(text):
    """The one line walk over optimized HLO ``text``: an ``Instruction``
    for every instruction of every computation, in the text's order.
    ``comp`` is the computation the line belongs to, ``shape`` the
    result's (layouts and all), ``head`` the line up to its
    ``metadata=`` (operands and attributes, ``calls=`` / ``body=``
    among them), ``op_name`` the metadata's (``""`` where the compiler
    wrote none: copies and async starts it made itself), ``root``
    whether the line is its computation's ``ROOT``.  The comm report,
    the CommPlan extractor (``analysis.comm.plan``), the loop membership
    walk and the device half of the span primitive
    (``observability.trace.device_scopes``) all read the text through
    this."""
    cur = None
    for line in text.splitlines():
        m = _COMP_RE.match(line)
        if m:
            cur = m.group(1)
            continue
        head, _, meta = line.partition(" metadata=")
        m = _INSTR_RE.match(head)
        if not m:
            continue
        om = _OP_NAME_RE.search(meta)
        yield Instruction(cur, m.group(2), m.group(3), m.group(4), head,
                          om.group(1) if om else "", bool(m.group(1)))


def called_computations(head):
    """Names of the computations an instruction's ``head`` refers to
    (``calls=`` / ``to_apply=`` / ``body=`` / ``condition=`` /
    ``branch_computations=``)."""
    refs = list(_CALL_RE.findall(head))
    for grp in _BRANCH_RE.findall(head):
        refs += [ref.strip().lstrip("%") for ref in grp.split(",")]
    return refs


def _shape_bytes_list(text):
    sizes = []
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue  # token[] etc.
        numel = 1
        for d in dims.split(","):
            if d:
                numel *= int(d)
        sizes.append(numel * _DTYPE_BYTES[dtype])
    return sizes


def _collective_bytes(shape_text, is_start):
    """Output bytes of one collective.  Async ``-start`` forms return an
    ``(operands..., results...)`` tuple — counting the whole tuple would
    double the figure the moment latency hiding rewrites the op, so take
    the result half (the last shape when the split is uneven, e.g.
    all-gather-start's small operand / big result)."""
    sizes = _shape_bytes_list(shape_text)
    if is_start and len(sizes) > 1:
        if len(sizes) % 2 == 0:
            return sum(sizes[len(sizes) // 2:])
        return sizes[-1]
    return sum(sizes)


def loop_computations(text):
    """Names of every computation reachable from a while body/condition
    in optimized HLO ``text`` — the one-level call graph (``calls=`` /
    ``to_apply=`` / ``branch_computations=``) closed over the loop
    bodies.  An op inside any of these executes once per loop
    iteration.  The single source of the loop-membership discipline:
    ``hlo_comm_report`` and the CommPlan extractor
    (``analysis.comm.plan``) both classify with it."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    bodies |= set(re.findall(r"condition=%?([\w.\-]+)", text))
    edges = {}
    for ins in iter_instructions(text):
        refs = called_computations(ins.head)
        if refs:
            edges.setdefault(ins.comp, set()).update(refs)
    in_loop = set()
    frontier = list(bodies)
    while frontier:
        c = frontier.pop()
        if c in in_loop:
            continue
        in_loop.add(c)
        frontier.extend(edges.get(c, ()))
    return in_loop


def hlo_comm_report(text):
    """Parse optimized (post-SPMD) HLO text and report every cross-chip
    collective: static counts and output bytes per kind, split by whether
    the op sits inside a while-loop body (directly, or in a computation a
    loop body calls).  Keys:

    * ``collective_ops``: ``{kind: count}`` (async ``-start`` forms count
      once — and contribute their RESULT bytes only, not the whole
      operand+result tuple — ``-done`` not at all);
    * ``collective_count`` / ``collective_bytes``: totals;
    * ``reduce_ops`` / ``reduce_bytes``: the REDUCE class (all-reduce +
      reduce-scatter) — gradient aggregation;
    * ``reduce_ops_in_loop`` / ``reduce_bytes_in_loop``: reduce ops that
      execute once per loop iteration.  The comm-aware accumulation
      invariant is exactly ``reduce_ops_in_loop == 0``: every gradient is
      cross-chip-reduced once per optimizer step, at the boundary;
    * ``collectives_in_loop`` / ``collective_bytes_in_loop``: all kinds
      (attention-internal gathers land here — reported, not gated).
    """
    # loop membership via the shared call-graph walk (a collective
    # inside a computation CALLED from a while body counts as in-loop)
    in_loop = loop_computations(text)
    colls = []  # (kind, bytes, computation)
    for ins in iter_instructions(text):
        cm = _COLL_RE.search(ins.head)
        if cm:
            colls.append((cm.group(2),
                          _collective_bytes(cm.group(1),
                                            bool(cm.group(3))),
                          ins.comp))

    report = {
        "collective_ops": {},
        "collective_count": 0, "collective_bytes": 0,
        "reduce_ops": 0, "reduce_bytes": 0,
        "reduce_ops_in_loop": 0, "reduce_bytes_in_loop": 0,
        "collectives_in_loop": 0, "collective_bytes_in_loop": 0,
    }
    for kind, nbytes, comp in colls:
        report["collective_ops"][kind] = (
            report["collective_ops"].get(kind, 0) + 1)
        report["collective_count"] += 1
        report["collective_bytes"] += nbytes
        looped = comp in in_loop
        if looped:
            report["collectives_in_loop"] += 1
            report["collective_bytes_in_loop"] += nbytes
        if kind in REDUCE_COLLECTIVES:
            report["reduce_ops"] += 1
            report["reduce_bytes"] += nbytes
            if looped:
                report["reduce_ops_in_loop"] += 1
                report["reduce_bytes_in_loop"] += nbytes
    return report


def comm_report(compiled):
    """``hlo_comm_report`` over a compiled executable's optimized HLO;
    ``{}`` when the backend cannot render it."""
    try:
        text = compiled.as_text()
    except Exception:
        return {}
    if not text:
        return {}
    return hlo_comm_report(text)


def compiled_memory_stats(compiled):
    """``compiled.memory_analysis()`` flattened into the fields the rest
    of the stack reports: ``temp_bytes``, ``argument_bytes``,
    ``output_bytes``, ``alias_bytes``, and ``hbm_high_water_bytes``
    (XLA's own liveness-aware peak when the backend reports one, else
    argument+output+temp minus donation aliasing).  ``{}`` when the
    backend has no memory analysis."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    if mem is None:
        return {}
    temp = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
    arg = int(getattr(mem, "argument_size_in_bytes", 0) or 0)
    out = int(getattr(mem, "output_size_in_bytes", 0) or 0)
    alias = int(getattr(mem, "alias_size_in_bytes", 0) or 0)
    peak = int(getattr(mem, "peak_memory_in_bytes", 0) or 0)
    high = peak if peak else max(0, arg + out + temp - alias)
    return {
        "temp_bytes": temp,
        "argument_bytes": arg,
        "output_bytes": out,
        "alias_bytes": alias,
        "hbm_high_water_bytes": high,
    }


def shape_pattern(shape):
    """Regex matching a dims list like ``[6,16384,768]`` in HLO text —
    the absent-shape probe (e.g. round 5's flagship's failing temp)."""
    return re.compile(
        r"\[" + ",".join(str(int(s)) for s in shape) + r"\]")
