"""Jaxpr artifact tools for the static-analysis engine.

The traced-jaxpr walk that used to live in ``core/memaudit.py`` (PR 4's
scan-locality audit), generalized for the pass framework: one traversal
collects everything the jaxpr-level checks consume — kernel-call scan
depth, layer-stacked operand probes, checkpoint-name tags, reduced-
precision accumulation patterns, and tanh-in-scan occurrences — so N
checks cost one walk, not N.

Also the canonical home of the checkpoint-name tags shared by the
kernels (``ops/pallas_attention``, ``ops/pallas_ce``) and the Executor's
offload scan body (``core/memaudit`` re-exports them for compatibility).
"""

import numpy as np
from jax.extend import core as _jex_core

__all__ = [
    "KERNEL_RESIDUAL_TAG", "BLOCK_INPUT_TAG",
    "jaxpr_report", "walk_report",
]

# Residuals a custom-VJP kernel saves for its own backward (the flash
# contract is exactly (q, k, v, o, lse); the fused CE head's is its lse).
# Tagged INSIDE the kernels' fwd rules so a name-policy checkpoint keeps
# them instead of re-running the kernel in the backward pass.
KERNEL_RESIDUAL_TAG = "pt_kernel_res"

# The per-layer block input (the residual stream entering each scanned
# layer) — the one stacked [L, b, t, d] residual the offload policy
# moves to pinned host memory on the forward scan and prefetches back
# during the backward scan.
BLOCK_INPUT_TAG = "pt_blk_in"

# reduced-precision dtypes whose naive accumulation loses low bits after
# a few thousand terms (the bf16-accum lint's trigger set)
_LOW_PRECISION = ("bfloat16", "float16")

# a reduce_sum folding at least this many elements per output element in
# reduced precision is worth flagging (under it, the error is noise)
REDUCE_ACCUM_MIN_ELEMS = 4096


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if isinstance(x, _jex_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, _jex_core.Jaxpr):
                yield x


def _aval_bytes(aval):
    try:
        return int(np.prod(aval.shape) * np.dtype(aval.dtype).itemsize)
    except Exception:
        return 0


def _carry_accumulations(eqn):
    """Reduced-precision accumulator carries of one scan eqn: carry slots
    whose dtype is bf16/f16 AND whose carry-out is an ``add`` (possibly
    behind a ``convert_element_type``) with the carry-in as a direct
    operand — the ``acc = acc + delta`` spelling that silently drops low
    bits once the running sum outgrows the term magnitude.  A residual
    stream (``x + attn``, ``h + ffn``) does NOT match: its carry-out add
    combines two derived values, not the carry-in itself."""
    params = eqn.params
    body = params.get("jaxpr")
    if body is None:
        return []
    if isinstance(body, _jex_core.ClosedJaxpr):
        body = body.jaxpr
    nc = int(params.get("num_consts", 0))
    k = int(params.get("num_carry", 0))
    carry_in = body.invars[nc:nc + k]
    carry_out = body.outvars[:k]
    producer = {}
    for beqn in body.eqns:
        for ov in beqn.outvars:
            producer[id(ov)] = beqn
    out = []
    for i in range(min(k, len(carry_in), len(carry_out))):
        aval = getattr(carry_out[i], "aval", None)
        if aval is None or str(getattr(aval, "dtype", "")) not in \
                _LOW_PRECISION:
            continue
        peqn = producer.get(id(carry_out[i]))
        # peel one convert_element_type (add-then-cast accumulators)
        if peqn is not None and peqn.primitive.name == \
                "convert_element_type":
            peqn = producer.get(id(peqn.invars[0]))
        if peqn is None or peqn.primitive.name not in ("add", "add_any"):
            continue
        if any(iv is carry_in[i] for iv in peqn.invars):
            out.append({
                "carry_index": i,
                "dtype": str(aval.dtype),
                "shape": tuple(getattr(aval, "shape", ())),
                "scan_length": params.get("length"),
            })
    return out


def _constraint_record(eqn, depth):
    """One ``sharding_constraint`` eqn flattened for the
    constraint-placement check: scan depth, the named-scope stack it was
    traced under, and the mesh axes its spec mentions."""
    import re as _re

    scope = ""
    try:
        scope = str(eqn.source_info.name_stack)
    except Exception:
        pass
    sh = eqn.params.get("sharding")
    spec = getattr(sh, "spec", None)
    axes = set()
    if spec is not None:
        for entry in spec:
            for a in (entry if isinstance(entry, tuple)
                      else (entry,) if entry else ()):
                # P.UNCONSTRAINED is truthy but names no mesh axis
                if a and str(a) != "UNCONSTRAINED":
                    axes.add(str(a))
    elif sh is not None:
        axes.update(_re.findall(r"'(\w+)'", str(sh)))
    return {"scan_depth": depth, "scope": scope,
            "spec": str(spec) if spec is not None else str(sh),
            "axes": sorted(axes)}


def walk_report(jaxpr, layer_counts=()):
    """One traversal of a (Closed)Jaxpr feeding every jaxpr-level check.

    Returns a dict with the PR 4 scan-locality fields (``pallas_calls``,
    ``pallas_total``, ``pallas_outside_scan``, ``scan_lengths``,
    ``layer_stacked_pallas``, ``residual_stacks``) plus:

    * ``name_tags``: every ``checkpoint_name`` tag present (the offload /
      kernel-residual contract probes);
    * ``low_precision_carries``: scan carries matching the
      ``acc = acc + delta`` pattern in bf16/f16 (see
      ``_carry_accumulations``);
    * ``low_precision_reduces``: ``reduce_sum`` eqns folding >=
      ``REDUCE_ACCUM_MIN_ELEMS`` elements per output element with a
      reduced-precision operand AND result;
    * ``tanh_in_scan``: count of ``tanh`` eqns inside scan/while bodies
      (the reassociation-stability hazard for scanned remat bodies);
    * ``sharding_constraints``: every ``sharding_constraint`` eqn with
      its scan depth, named-scope stack (the ``pt_pin[site]`` blessed
      markers — ``jaxpr.constraint-placement``'s input), spec string
      and the mesh axes the spec mentions.

    ``layer_counts``: leading-dim candidates for the layer-stacked
    probes (the stacked-shape detector accepts several hypotheses —
    e.g. the caller's hint plus every scan-group repeat count).
    """
    if isinstance(jaxpr, _jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    layer_counts = tuple(sorted({int(c) for c in layer_counts if c}))
    report = {
        "pallas_calls": [],
        "pallas_total": 0,
        "pallas_outside_scan": 0,
        "pallas_interpret": 0,
        "scan_lengths": [],
        "layer_stacked_pallas": [],
        "residual_stacks": [],
        "name_tags": set(),
        "low_precision_carries": [],
        "low_precision_reduces": [],
        "tanh_in_scan": 0,
        "sharding_constraints": [],
    }

    def walk(jx, depth):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                shapes = [tuple(v.aval.shape)
                          for v in list(eqn.invars) + list(eqn.outvars)
                          if hasattr(v, "aval")
                          and hasattr(v.aval, "shape")]
                report["pallas_calls"].append(
                    {"scan_depth": depth, "shapes": shapes})
                report["pallas_total"] += 1
                if eqn.params.get("interpret"):
                    # interpret-mode kernel: exact logic, simulated
                    # speed — the jaxpr.kernel-backend check flags
                    # these inside timed-run regions
                    report["pallas_interpret"] += 1
                if depth == 0:
                    report["pallas_outside_scan"] += 1
                if layer_counts:
                    report["layer_stacked_pallas"] += [
                        s for s in shapes
                        if len(s) >= 2 and s[0] in layer_counts]
            elif name == "name":
                tag = eqn.params.get("name")
                if tag:
                    report["name_tags"].add(str(tag))
            elif name == "tanh" and depth > 0:
                report["tanh_in_scan"] += 1
            elif name == "sharding_constraint":
                report["sharding_constraints"].append(
                    _constraint_record(eqn, depth))
            elif name == "reduce_sum":
                iv = eqn.invars[0] if eqn.invars else None
                ov = eqn.outvars[0] if eqn.outvars else None
                ia = getattr(iv, "aval", None)
                oa = getattr(ov, "aval", None)
                if (ia is not None and oa is not None
                        and str(getattr(ia, "dtype", ""))
                        in _LOW_PRECISION
                        and str(getattr(oa, "dtype", ""))
                        in _LOW_PRECISION):
                    n_in = int(np.prod(ia.shape)) if ia.shape else 1
                    n_out = int(np.prod(oa.shape)) if oa.shape else 1
                    folded = n_in // max(n_out, 1)
                    if folded >= REDUCE_ACCUM_MIN_ELEMS:
                        report["low_precision_reduces"].append({
                            "dtype": str(ia.dtype),
                            "shape": tuple(ia.shape),
                            "folded_elems": folded,
                            "scan_depth": depth,
                        })
            if name == "scan":
                length = eqn.params.get("length")
                report["scan_lengths"].append(length)
                report["low_precision_carries"] += \
                    _carry_accumulations(eqn)
                if layer_counts and length in layer_counts:
                    for v in eqn.outvars:
                        aval = getattr(v, "aval", None)
                        shape = getattr(aval, "shape", ())
                        if len(shape) >= 1 and shape[0] == length:
                            report["residual_stacks"].append({
                                "shape": tuple(shape),
                                "dtype": str(aval.dtype),
                                "bytes": _aval_bytes(aval),
                            })
            next_depth = depth + (1 if name in ("scan", "while") else 0)
            for sub in _sub_jaxprs(eqn):
                walk(sub, next_depth)

    walk(jaxpr, 0)
    report["residual_stacks"].sort(key=lambda r: -r["bytes"])
    return report


def jaxpr_report(jaxpr, layer_count=None):
    """Walk a (Closed)Jaxpr and report kernel-call scan locality — the
    PR 4 contract (see ``core/memaudit.jaxpr_report``): ``pallas_calls``
    with scan depth, ``pallas_total`` / ``pallas_outside_scan`` counts,
    ``scan_lengths``, ``layer_stacked_pallas`` leading-axis probes, and
    ``residual_stacks`` (largest first)."""
    rep = walk_report(
        jaxpr, layer_counts=(layer_count,) if layer_count else ())
    return {k: rep[k] for k in (
        "pallas_calls", "pallas_total", "pallas_outside_scan",
        "scan_lengths", "layer_stacked_pallas", "residual_stacks")}
