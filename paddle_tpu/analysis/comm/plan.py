"""CommPlan: the partitioned SPMD HLO's collectives as a structured,
mesh-aware plan.

``hlo_tools.hlo_comm_report`` answers "how many reduce ops sit inside
loops"; this extractor answers the questions the contract checks need:

* **which mesh axes** each collective spans — recovered by matching its
  ``replica_groups`` (both the explicit ``{{0,4},{1,5}}`` and the iota
  ``[4,2]<=[2,4]T(1,0)`` spellings) against the canonical group
  partition of every mesh-axis subset.  A collective whose groups match
  NO axis subset is GSPMD *inventing* a resharding the program never
  asked for (``hlo.axis-attribution``);
* **which phase** it executes in — ``fwd-scan`` / ``bwd-scan`` (loop
  membership + jax's ``transpose(`` autodiff marker in the op metadata)
  or ``boundary`` (top level: the optimizer boundary of a training
  step).  Whole-executable phases (serving ``prefill`` / ``decode``)
  come from the compile label;
* **which annotation put it there** — the Executor wraps every blessed
  sharding-constraint site in a ``pt_pin[site]`` named scope and every
  activation-annotation constraint in ``pt_shard[var]``
  (core/executor.py), and XLA threads those scopes into each derived
  op's ``op_name`` metadata, so a collective can be attributed to the
  responsible variable (``hlo.accidental-reshard``).

``comm_diff(plan_a, plan_b)`` explains which op moved when two configs
disagree — the tool for "why did FSDP=1 add 19 in-loop all-reduces".
"""

import re

import numpy as np

from ..hlo_tools import (
    ALL_COLLECTIVES,
    GATHER_COLLECTIVES,
    REDUCE_COLLECTIVES,
    _collective_bytes,
    iter_instructions,
    loop_computations,
)

__all__ = [
    "CommOp", "CommPlan", "extract_comm_plan", "comm_diff",
    "mesh_axis_groups", "PIN_SCOPE_RE",
]

# kind aliases a contract may use instead of one concrete HLO op kind
KIND_CLASSES = {
    "reduce": REDUCE_COLLECTIVES,
    "gather": GATHER_COLLECTIVES,
    "any": ALL_COLLECTIVES,
}

PHASES = ("fwd-scan", "bwd-scan", "boundary", "prefill", "decode")

# the provenance markers the Executor's named scopes emit:
# pt_pin[site] for the blessed constraint-placement sites,
# pt_shard[var] for activation sharding annotations
PIN_SCOPE_RE = re.compile(r"pt_(pin|shard)\[([^\]]*)\]")

# NOTE: async ``-done`` forms can never match this (after the kind the
# regex requires optional ``-start`` then ``(``, and ``-`` is excluded
# from the shape class), so no separate -done guard is needed — one
# would false-skip real collectives whose OPERAND names contain
# ``-done`` (the async-overlap spelling).
_COLL_LINE_RE = re.compile(
    r"=\s*(\(?[\w\[\]{},:*/() ]*?)\s*"
    r"\b(" + "|".join(ALL_COLLECTIVES) + r")"
    r"((?:-start)?)(?:\.\d+)?\(")
_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=(\{.*?\}\}|\{\}|\[[0-9,]+\]<=\[[0-9,]+\]"
    r"(?:T\([0-9,]+\))?)")
_SOURCE_TARGET_RE = re.compile(r"source_target_pairs=\{([^}]*(?:\},\{[^}]*)*)\}")


def _parse_replica_groups(text):
    """``replica_groups=...`` -> list of device-id lists, or None when
    the attribute is absent/unparseable.  Handles the explicit nested
    list (``{{0,1},{2,3}}``), the empty form (``{}`` — all devices in
    one group), and the iota form (``[G,K]<=[dims]T(perm)``)."""
    if text is None:
        return None
    text = text.strip()
    if text.startswith("{"):
        if text == "{}":
            return []
        groups = []
        for grp in re.findall(r"\{([0-9, ]+)\}", text):
            ids = [int(t) for t in grp.replace(" ", "").split(",") if t]
            if ids:
                groups.append(ids)
        return groups or None
    m = re.match(
        r"\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?$", text)
    if not m:
        return None
    out_dims = [int(t) for t in m.group(1).split(",")]
    reshape_dims = [int(t) for t in m.group(2).split(",")]
    n = int(np.prod(reshape_dims))
    arr = np.arange(n).reshape(reshape_dims)
    if m.group(3):
        perm = [int(t) for t in m.group(3).split(",")]
        arr = arr.transpose(perm)
    if len(out_dims) == 1:
        return [arr.reshape(-1).tolist()]
    return arr.reshape(out_dims[0], -1).tolist()


def _mesh_ids(mesh):
    """The mesh's device-id ndarray plus its axis names/sizes, from a
    ``jax.sharding.Mesh`` (or anything with ``.devices`` /
    ``.axis_names``)."""
    devices = np.asarray(mesh.devices)
    ids = np.vectorize(
        lambda d: int(getattr(d, "id", d)), otypes=[np.int64])(devices)
    names = tuple(mesh.axis_names)
    return ids, names, dict(zip(names, ids.shape))


def mesh_axis_groups(mesh):
    """Canonical replica-group partition per mesh-axis subset.

    Returns ``{axes_tuple: frozenset(frozenset(device_ids))}`` for every
    non-empty subset of the mesh's axes: the groups a collective that
    reduces/gathers over exactly ``axes_tuple`` (with all other axes
    fixed) must use.  The inverse lookup recovers a collective's axes
    from its replica groups."""
    ids, names, _sizes = _mesh_ids(mesh)
    out = {}
    n = len(names)
    for mask in range(1, 1 << n):
        axes = tuple(names[i] for i in range(n) if mask & (1 << i))
        keep = [i for i in range(n) if not (mask & (1 << i))]
        move = [i for i in range(n) if mask & (1 << i)]
        arr = np.transpose(ids, keep + move)
        grp_size = int(np.prod([ids.shape[i] for i in move]))
        arr = arr.reshape(-1, grp_size)
        out[axes] = frozenset(frozenset(row.tolist()) for row in arr)
    return out


def _axes_for_groups(groups, axis_groups, n_devices):
    """Recover the mesh-axis subset a replica-group list spans, or None
    when it matches no subset (GSPMD invented a resharding).  An empty
    group list / a single all-devices group matches the full-mesh
    subset."""
    if groups is None:
        return None
    if not groups:
        groups = [list(range(n_devices))]
    key = frozenset(frozenset(g) for g in groups)
    for axes, part in axis_groups.items():
        if key == part:
            return axes
    # groups of size 1 = no communication (a degenerate partition some
    # spellings emit); attribute to no axis but don't call it invented
    if all(len(g) <= 1 for g in key):
        return ()
    return None


def _device_coords(ids):
    """``{device_id: mesh coordinate tuple}`` for a mesh-id ndarray —
    computed once per extraction, shared by every collective-permute's
    axis attribution."""
    return {int(ids[idx]): idx for idx in np.ndindex(ids.shape)}


def _axes_for_pairs(pairs, coord, names):
    """Mesh-axis attribution for a collective-permute's
    ``source_target_pairs``: the single axis along which every
    (src, tgt) pair's mesh coordinates differ, or None.  ``coord`` is
    the precomputed ``_device_coords`` map."""
    if not pairs:
        return ()
    axes = set()
    for s, t in pairs:
        if s not in coord or t not in coord:
            return None
        cs, ct = coord[s], coord[t]
        diff = [i for i in range(len(cs)) if cs[i] != ct[i]]
        if len(diff) != 1:
            return None
        axes.add(names[diff[0]])
    return tuple(sorted(axes)) if len(axes) == 1 else None


class CommOp:
    """One collective of the plan: kind, bytes, mesh axes, loop
    membership, phase, and provenance."""

    __slots__ = ("kind", "bytes", "axes", "in_loop", "phase",
                 "computation", "op_name", "provenance", "channel")

    def __init__(self, kind, nbytes, axes, in_loop, phase,
                 computation="", op_name="", provenance=None,
                 channel=None):
        self.kind = kind
        self.bytes = int(nbytes)
        self.axes = axes  # tuple of axis names, () for degenerate,
        #                   None = matched no mesh-axis subset
        self.in_loop = bool(in_loop)
        self.phase = phase
        self.computation = computation
        self.op_name = op_name
        self.provenance = provenance  # {"site"|"var": name} or None
        self.channel = channel

    def matches_kind(self, kind):
        if kind is None:
            return True
        return self.kind == kind or self.kind in KIND_CLASSES.get(
            kind, ())

    def matches_axis(self, axis):
        if axis is None:
            return True
        return self.axes is not None and axis in self.axes

    def provenance_names(self):
        """The individual annotation names of this op's provenance (a
        multi-output producer's ``pt_shard`` scope joins its annotated
        outputs with commas)."""
        if not self.provenance:
            return ()
        value = next(iter(self.provenance.values()))
        return tuple(n for n in value.split(",") if n)

    def to_dict(self):
        return {
            "kind": self.kind, "bytes": self.bytes,
            "axes": list(self.axes) if self.axes is not None else None,
            "in_loop": self.in_loop, "phase": self.phase,
            "computation": self.computation, "op_name": self.op_name,
            "provenance": dict(self.provenance)
            if self.provenance else None,
        }

    def describe(self):
        ax = ("?" if self.axes is None
              else "x".join(self.axes) if self.axes else "-")
        prov = ""
        if self.provenance:
            k, v = next(iter(self.provenance.items()))
            prov = f" [{k}={v}]"
        return (f"{self.kind}@{ax} {self.phase}"
                f"{' in-loop' if self.in_loop else ''}"
                f" {self.bytes}B{prov}")

    def __repr__(self):
        return f"CommOp({self.describe()})"


class CommPlan:
    """The structured communication plan of one compiled executable."""

    def __init__(self, ops=(), mesh_axes=None, label=None):
        self.ops = list(ops)
        self.mesh_axes = dict(mesh_axes or {})  # axis name -> size
        self.label = label

    def __iter__(self):
        return iter(self.ops)

    def __len__(self):
        return len(self.ops)

    def select(self, kind=None, axis=None, in_loop=None, phase=None,
               provenance=None):
        """Ops matching every given criterion.  ``kind`` may be a
        concrete HLO kind or a class alias ('reduce' / 'gather' /
        'any'); ``provenance`` is a regex matched against EACH name of
        the op's ``pt_pin``/``pt_shard`` annotation (a multi-output
        producer's scope joins its annotated outputs with commas, and
        anchored patterns must still hit every one)."""
        out = []
        pat = re.compile(provenance) if provenance else None
        for op in self.ops:
            if not op.matches_kind(kind):
                continue
            if not op.matches_axis(axis):
                continue
            if in_loop is not None and op.in_loop != in_loop:
                continue
            if phase is not None and op.phase != phase:
                continue
            if pat is not None:
                if not any(pat.search(n)
                           for n in op.provenance_names()):
                    continue
            out.append(op)
        return out

    def unattributed(self):
        """Ops whose replica groups matched no mesh-axis subset — the
        ``hlo.axis-attribution`` input."""
        return [op for op in self.ops if op.axes is None]

    def buckets(self):
        """``{(kind, axes, phase, in_loop): {"count", "bytes"}}`` — the
        aggregation ``comm_diff`` and the compact summary share."""
        out = {}
        for op in self.ops:
            axes = (tuple(op.axes) if op.axes is not None else ("?",))
            key = (op.kind, axes, op.phase, op.in_loop)
            b = out.setdefault(key, {"count": 0, "bytes": 0})
            b["count"] += 1
            b["bytes"] += op.bytes
        return out

    def summary(self):
        """JSON-able compact form for ``last_step_cost["comm_plan"]`` /
        trainer JSONL: one sorted row per (kind, axes, phase, in_loop)
        bucket."""
        rows = []
        for (kind, axes, phase, in_loop), b in sorted(
                self.buckets().items(),
                key=lambda kv: (kv[0][2], kv[0][0], kv[0][1])):
            rows.append({
                "kind": kind, "axes": "x".join(axes) if axes else "-",
                "phase": phase, "in_loop": in_loop,
                "count": b["count"], "bytes": b["bytes"],
            })
        return rows

    def comm_report(self):
        """The legacy scalar comm report (``hlo_tools.hlo_comm_report``
        key-compatible: per-kind counts, totals, the reduce class and
        every loop split) derived from this plan — one HLO parse serves
        both shapes (the Executor's fold-in uses this instead of
        re-parsing the text)."""
        report = {
            "collective_ops": {},
            "collective_count": 0, "collective_bytes": 0,
            "reduce_ops": 0, "reduce_bytes": 0,
            "reduce_ops_in_loop": 0, "reduce_bytes_in_loop": 0,
            "collectives_in_loop": 0, "collective_bytes_in_loop": 0,
        }
        for op in self.ops:
            report["collective_ops"][op.kind] = (
                report["collective_ops"].get(op.kind, 0) + 1)
            report["collective_count"] += 1
            report["collective_bytes"] += op.bytes
            if op.in_loop:
                report["collectives_in_loop"] += 1
                report["collective_bytes_in_loop"] += op.bytes
            if op.kind in REDUCE_COLLECTIVES:
                report["reduce_ops"] += 1
                report["reduce_bytes"] += op.bytes
                if op.in_loop:
                    report["reduce_ops_in_loop"] += 1
                    report["reduce_bytes_in_loop"] += op.bytes
        return report

    def to_dict(self):
        return {"label": self.label, "mesh_axes": dict(self.mesh_axes),
                "ops": [op.to_dict() for op in self.ops],
                "summary": self.summary()}


def _classify_phase(in_loop, op_name, label=None):
    if label in ("prefill", "decode"):
        return label
    if in_loop:
        return "bwd-scan" if "transpose(" in op_name else "fwd-scan"
    return "boundary"


def _provenance(op_name):
    m = PIN_SCOPE_RE.search(op_name or "")
    if not m:
        return None
    return {"site" if m.group(1) == "pin" else "var": m.group(2)}


def extract_comm_plan(text, mesh=None, label=None):
    """Walk partitioned/optimized HLO ``text`` into a :class:`CommPlan`.

    ``mesh`` (a ``jax.sharding.Mesh``) enables mesh-axis recovery from
    replica groups; without one every op's ``axes`` stays ``None``
    (unresolved, not "invented") and ``hlo.axis-attribution`` stays
    silent — it needs a mesh to judge.  ``label`` tags
    whole-executable phases: a label containing ``prefill`` /
    ``decode`` (the serving executables) overrides the per-op phase
    classification."""
    if not text:
        return CommPlan([], {}, label)
    axis_groups = {}
    mesh_axes = {}
    n_devices = 0
    coord, axis_names = None, ()
    if mesh is not None:
        try:
            ids, axis_names, mesh_axes = _mesh_ids(mesh)
            n_devices = int(ids.size)
            axis_groups = mesh_axis_groups(mesh)
            coord = _device_coords(ids)
        except Exception:  # noqa: BLE001 — plan must survive odd meshes
            axis_groups, mesh_axes, coord = {}, {}, None
    loop_comps = loop_computations(text)
    phase_label = None
    for tag in ("prefill", "decode"):
        if label and tag in str(label):
            phase_label = tag

    ops = []
    for ins in iter_instructions(text):
        cur, head, op_name = ins.comp, ins.head, ins.op_name
        cm = _COLL_LINE_RE.search(head)
        if not cm:
            continue
        kind, is_start = cm.group(2), bool(cm.group(3))
        nbytes = _collective_bytes(cm.group(1), is_start)
        chan_m = re.search(r"channel_id=(\d+)", head)
        axes = None
        if kind == "collective-permute":
            pm = _SOURCE_TARGET_RE.search(head)
            if pm and coord is not None:
                pairs = [
                    tuple(int(t) for t in p.split(","))
                    for p in re.findall(r"\{?(\d+,\d+)\}?", pm.group(1))
                ]
                axes = _axes_for_pairs(pairs, coord, axis_names)
        else:
            rm = _REPLICA_GROUPS_RE.search(head)
            groups = _parse_replica_groups(rm.group(1) if rm else None)
            if axis_groups:
                axes = _axes_for_groups(groups, axis_groups, n_devices)
        in_loop = cur in loop_comps
        provenance = _provenance(op_name)
        # Reduce-scatter canonicalization (docs/parallel.md rule 4).
        # A boundary all-reduce carrying ``pt_pin[grad_rs_boundary:*]``
        # provenance is the Executor's ZeRO-3 gradient aggregation: its
        # operand is the fsdp-SHARD of the gradient (GSPMD pushes the
        # boundary pin's partition-id slice ahead of the reduce —
        # slice-before-reduce is valid because dW is fsdp-replicated),
        # so the op the chips actually run is a shard-volume
        # all-reduce over the remaining reduce axes.  Logically over
        # the full mesh that IS a reduce-scatter — reduce over dp,
        # scatter over fsdp — and XLA pipelines with a
        # ReduceScatterCreator pass (GPU/TPU) spell it as the literal
        # instruction; the CPU pipeline never runs that pass, so the
        # plan canonicalizes the provenance-marked form instead of
        # reporting the spelling accident.  Bytes stay the op's true
        # (shard) volume — the comm-contract and bench gates read the
        # honest figure.
        if (kind == "all-reduce" and not in_loop and provenance
                and str(provenance.get("site", "")).startswith(
                    "grad_rs_boundary:")
                and mesh_axes.get("fsdp", 0) > 1
                and "fsdp" not in (axes or ())):
            kind = "reduce-scatter"
            axes = tuple(axes or ()) + ("fsdp",)
        ops.append(CommOp(
            kind, nbytes, axes, in_loop,
            _classify_phase(in_loop, op_name, phase_label),
            computation=cur or "", op_name=op_name,
            provenance=provenance,
            channel=int(chan_m.group(1)) if chan_m else None))
    return CommPlan(ops, mesh_axes, label)


def comm_diff(plan_a, plan_b, name_a="A", name_b="B"):
    """Explain which collective moved between two plans.

    Buckets both plans by (kind, axes, phase, in_loop) and reports every
    bucket whose count or bytes changed, plus a human-readable ``text``
    list — the tool for "FSDP=1 added 19 in-loop all-reduces: they are
    all-reduce@fsdp bwd-scan, i.e. the dW replication the asymmetric
    pin exists to prevent" (docs/parallel.md)."""
    ba, bb = plan_a.buckets(), plan_b.buckets()
    changed = []
    for key in sorted(set(ba) | set(bb),
                      key=lambda k: (k[2], k[0], k[1])):
        a = ba.get(key, {"count": 0, "bytes": 0})
        b = bb.get(key, {"count": 0, "bytes": 0})
        if a == b:
            continue
        kind, axes, phase, in_loop = key
        changed.append({
            "kind": kind, "axes": "x".join(axes) if axes else "-",
            "phase": phase, "in_loop": in_loop,
            "count_a": a["count"], "count_b": b["count"],
            "bytes_a": a["bytes"], "bytes_b": b["bytes"],
        })
    text = []
    for c in changed:
        where = f"{c['phase']}{' in-loop' if c['in_loop'] else ''}"
        text.append(
            f"{c['kind']}@{c['axes']} {where}: "
            f"{c['count_a']} -> {c['count_b']} ops "
            f"({c['bytes_a']} -> {c['bytes_b']} bytes) "
            f"[{name_a} -> {name_b}]")
    return {"changed": changed, "text": text,
            "same": not changed}
