"""Per-op performance attribution — which op classes own the
milliseconds of a compiled step.

``last_step_cost`` (PR 1) reports ONE flops/bytes figure per compile and
the PR-7 spans time whole phases; neither answers "is the step matmul-
bound or data-movement-bound, and which class regressed".  This module
walks the compiled executable's optimized HLO text (the same artifact
``analysis.hlo_tools`` parses for the comm audit) into a per-op-CLASS
table:

* ``pallas``            — the flash/CE kernels: TPU ``custom-call``s
  (Mosaic), or — in CPU interpret mode, where Pallas lowers to plain
  HLO — any op whose ``metadata.source_file`` points into
  ``ops/pallas_*.py`` (the dots and exponentials of the interpreted
  kernel attribute to the kernel, not to the generic classes);
* ``matmul``            — ``dot`` / ``convolution`` outside kernels;
* ``collective.<kind>`` — cross-chip collectives, one class per kind
  (all-reduce, all-gather, ...), async ``-start`` forms counted once;
* ``elementwise``       — the fused pointwise ocean (fusion ops count
  their boundary bytes; ops inside fusion bodies contribute flops but
  no bytes — XLA reads fusion intermediates from registers, so
  counting their bytes would invent traffic the chip never pays);
* ``reduce``            — reductions (softmax denominators, norms,
  loss sums);
* ``other``             — data movement (copy/slice/scatter/transpose/
  convert) and everything unclassified.

Each class row carries static ``flops`` (dot flops are exact:
``2 * result_elems * contraction_width`` from the printed operand
shapes; elementwise counts one flop per output element, the XLA
cost-analysis convention; transcendentals are tracked in their own
column exactly because ``cost_analysis()["flops"]`` excludes them),
``bytes`` (operand + result traffic at fusion boundaries), a
roofline-estimated ``est_ms`` (the ``tune/space.py`` discipline:
``max(flops / peak_flops, bytes / hbm_bw)`` — compute- vs memory-bound
is which side of the max wins), and ``share`` of the estimated step
time.  ``coverage`` is the table's flop sum over the executable's own
``cost_analysis()`` figure — the ≥95% contract that
``tests/test_attribution.py`` pins.

The Executor runs this on every AOT compile (``exe.last_attribution``,
kill switch ``PADDLE_TPU_ATTR=0``), folds a compact top-op summary into
``last_step_cost["attribution"]`` (and thence the trainer's
JSONL), and ``reconcile()`` reports the roofline model's error against
the measured step wall time — every (workload key, table, measured ms)
triple is one corpus row for the ROADMAP item-5(c) learned cost model,
keyed exactly like the tune cache so the two datasets join.
"""

import math
import os
import re

from . import metrics as _obs

__all__ = [
    "SCHEMA_VERSION", "attribution_enabled", "attribute_hlo",
    "attribute_compiled", "summarize", "reconcile",
    "program_workload_key", "normalize_workload_key",
]

SCHEMA_VERSION = 1

# mirror of analysis.hlo_tools._DTYPE_BYTES (kept local: observability
# must stay importable before the analysis package initializes)
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVE_KINDS = ("all-reduce", "reduce-scatter", "all-gather",
                     "collective-permute", "all-to-all",
                     "collective-broadcast")

# one flop per output element, the HloCostAnalysis convention
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "compare", "select", "and", "or", "xor", "not", "negate", "abs",
    "sign", "floor", "ceil", "round-nearest-afz", "round-nearest-even",
    "clamp", "remainder", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "popcnt", "clz", "is-finite", "atan2",
}
# cost_analysis() reports these under "transcendentals", NOT "flops" —
# tracked in their own column so coverage vs the flops figure is honest
_TRANSCENDENTAL = {
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "sqrt", "rsqrt", "cbrt", "power", "sine", "cosine", "tan",
    "logistic", "erf", "expm1", "log1p",
}
_REDUCE_OPS = {"reduce", "reduce-window", "select-and-scatter"}
# control flow / structure: bodies are counted where they are defined
_STRUCTURAL = {
    "while", "conditional", "call", "fusion", "parameter", "constant",
    "get-tuple-element", "tuple", "bitcast", "after-all", "domain",
    "opt-barrier", "optimization-barrier", "partition-id", "replica-id",
}

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(")
_OP_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%[\w.\-]+\s*=\s*"
    r"(\(?[\w\[\]{},:*/ ]*?\)?)\s*\b([a-z][\w\-]*?)((?:-start|-done)?)"
    r"[.\d]*\(")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_SRC_RE = re.compile(r'source_file="([^"]*)"')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_CONV_LABELS_RE = re.compile(r"dim_labels=\w+_(\w+)->\w+")


def attribution_enabled():
    """``PADDLE_TPU_ATTR=0`` kills the walk entirely (the Executor then
    never touches ``last_attribution``)."""
    return os.environ.get("PADDLE_TPU_ATTR", "1").lower() not in (
        "0", "", "false", "off", "no")


def _shapes(text):
    """Every ``dtype[dims]`` in ``text`` as ``(numel, bytes)`` pairs."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue  # token[] / layout noise
        numel = 1
        for d in dims.split(","):
            if d:
                numel *= int(d)
        out.append((numel, numel * _DTYPE_BYTES[dtype]))
    return out


def _dot_flops(result_text, operand_text, tail):
    """Exact dot flops from the printed shapes:
    ``2 * result_elems * contraction_width`` (the fma convention the
    XLA cost analysis uses), contraction width read off the lhs
    operand's shape at ``lhs_contracting_dims``."""
    res = _shapes(result_text)
    ops = _shapes(operand_text)
    if not res or not ops:
        return 0
    m = _CONTRACT_RE.search(tail)
    if not m:
        return 0
    lhs_dims = None
    sm = _SHAPE_RE.search(operand_text)
    if sm:
        lhs_dims = [int(d) for d in sm.group(2).split(",") if d]
    if lhs_dims is None:
        return 0
    k = 1
    for idx in m.group(1).split(","):
        if idx and int(idx) < len(lhs_dims):
            k *= lhs_dims[int(idx)]
    return 2 * res[0][0] * k


def _conv_flops(result_text, operand_text, tail):
    """Convolution flops, best effort: ``2 * output_elems * macs`` where
    macs = kernel elements per output feature (rhs numel / output
    features, output-feature dim located via ``dim_labels``'s ``o``).
    0 when the line doesn't parse — convs are a ResNet-side minority."""
    res = _shapes(result_text)
    ops = _shapes(operand_text)
    if not res or len(ops) < 2:
        return 0
    m = _CONV_LABELS_RE.search(tail)
    sm = list(_SHAPE_RE.finditer(operand_text))
    if not m or len(sm) < 2:
        return 0
    rhs_dims = [int(d) for d in sm[1].group(2).split(",") if d]
    labels = m.group(1)
    if "o" not in labels or len(labels) != len(rhs_dims):
        return 0
    out_f = rhs_dims[labels.index("o")]
    rhs_numel = 1
    for d in rhs_dims:
        rhs_numel *= d
    if not out_f:
        return 0
    return 2 * res[0][0] * (rhs_numel // out_f)


def _classify(opcode, src_file, is_custom_call, target=""):
    """The op class an HLO line attributes to (kernel membership wins:
    an interpreted Pallas kernel's dots belong to the kernel, not to
    the generic matmul bucket)."""
    if src_file and "paged_attention" in src_file:
        # the blocked online-softmax attention over the KV block table
        # (kernels/paged_attention.py) — its own bucket
        return "paged_attention"
    if src_file and ("pallas_attention" in src_file
                    or "pallas_ce" in src_file):
        return "pallas"
    if is_custom_call:
        t = target.lower()
        if "mosaic" in t or "pallas" in t or "tpu_custom_call" in t:
            return "pallas"
        return "other"
    if opcode in _COLLECTIVE_KINDS:
        return f"collective.{opcode}"
    if opcode in ("dot", "convolution"):
        return "matmul"
    if opcode in _REDUCE_OPS:
        return "reduce"
    if opcode in _ELEMENTWISE or opcode in _TRANSCENDENTAL:
        return "elementwise"
    if opcode == "fusion":
        # a fusion's boundary traffic belongs to the pointwise ocean its
        # body almost always is (its dots, if any, are counted in the
        # body under their own class)
        return "elementwise"
    return "other"


def attribute_hlo(text, peak_flops=None, hbm_bw=None):
    """Walk optimized HLO text into the per-op-class table.

    Returns ``{"classes": {name: row}, "hlo_flops_total",
    "transcendentals_total", "bytes_total", "est_ms_total", "ops_total"}``
    where each row is ``{"ops", "flops", "transcendentals", "bytes",
    "est_ms", "bound", "share"}``.  Every computation is counted once
    (the cost-analysis convention: a while body prices one iteration),
    and ops inside fusion bodies contribute flops but no bytes."""
    if peak_flops is None or hbm_bw is None:
        pk, bw = _machine_roofline()
        peak_flops = peak_flops or pk
        hbm_bw = hbm_bw or bw
    fusion_bodies = set(_CALLS_RE.findall(text))
    classes = {}
    cur = None
    for line in text.splitlines():
        cm = _COMP_RE.match(line)
        if cm:
            cur = cm.group(1)
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        result_text, opcode, async_suffix = m.groups()
        if async_suffix == "-done":
            continue  # the -start form carries the shapes once
        head, _, _meta = line.partition(" metadata=")
        if opcode in _STRUCTURAL and opcode != "fusion":
            continue
        # operand text: everything between the opcode's "(" and the
        # matching attribute tail; shapes are inline, so a flat slice
        # after the first "(" past the result section is enough
        body = head[m.end() - 1:]
        depth = 0
        end = len(body)
        for i, ch in enumerate(body):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operand_text, tail = body[1:end], body[end:]
        src = _SRC_RE.search(line)
        src_file = src.group(1) if src else ""
        is_cc = opcode == "custom-call"
        target = ""
        if is_cc:
            tm = re.search(r'custom_call_target="([^"]*)"', line)
            target = tm.group(1) if tm else ""
        cls = _classify(opcode, src_file, is_cc, target)

        flops = 0
        transcendentals = 0
        if opcode == "dot":
            flops = _dot_flops(result_text, operand_text, tail)
        elif opcode == "convolution":
            flops = _conv_flops(result_text, operand_text, tail)
        elif opcode in _TRANSCENDENTAL:
            transcendentals = sum(n for n, _ in _shapes(result_text))
        elif opcode in _ELEMENTWISE:
            flops = sum(n for n, _ in _shapes(result_text))
        elif opcode in _REDUCE_OPS:
            flops = sum(n for n, _ in _shapes(operand_text))

        # bytes: operand + result traffic — except inside fusion bodies,
        # whose intermediates never touch HBM (the fusion op line carries
        # the boundary bytes)
        if cur in fusion_bodies:
            nbytes = 0
        else:
            nbytes = (sum(b for _, b in _shapes(result_text))
                      + sum(b for _, b in _shapes(operand_text)))
        if opcode == "fusion":
            flops = 0  # body ops carry the arithmetic

        row = classes.setdefault(cls, {
            "ops": 0, "flops": 0, "transcendentals": 0, "bytes": 0})
        row["ops"] += 1
        row["flops"] += flops
        row["transcendentals"] += transcendentals
        row["bytes"] += nbytes

    att = {
        "schema_version": SCHEMA_VERSION,
        "classes": classes,
        "ops_total": sum(r["ops"] for r in classes.values()),
        "transcendentals_total": sum(
            r["transcendentals"] for r in classes.values()),
        "bytes_total": sum(r["bytes"] for r in classes.values()),
        "peak_flops": peak_flops,
        "hbm_bw": hbm_bw,
    }
    _finalize_roofline(att)
    return att


def _fitted_costmodel():
    """``(entry, status)`` of the fitted cost model for the current
    platform (``tune/costmodel.py``), or ``(None, {"mode":
    "analytic"})`` — None when the ``PADDLE_TPU_COSTMODEL=0`` kill
    switch is set, no fit covers this platform, or the tune package is
    unavailable mid-bootstrap.  A None entry means the analytic
    roofline in :func:`_finalize_roofline` runs exactly as before the
    learned model existed, bit-exact."""
    try:
        from ..tune import costmodel as _cm

        entry = _cm.active_entry()
        if entry is None:
            return None, {"mode": "analytic"}
        return entry, _cm.model_status()
    except Exception:  # noqa: BLE001 — the consult must never break a walk
        return None, {"mode": "analytic"}


def _finalize_roofline(att):
    """(Re)compute the per-class roofline estimates, bound verdicts and
    shares plus the flop/est totals from the classes' flops/bytes —
    called by :func:`attribute_hlo` and AGAIN by
    :func:`attribute_compiled` after an opaque kernel's flop estimate
    is patched in (the shares must reflect the kernel's math, or a
    flash slowdown on TPU would never move the pallas share).

    When a FITTED cost model is loadable (``tune/costmodel.py``), each
    class's estimate comes from the calibrated per-class coefficients
    instead of the analytic ``max(flops/peak, bytes/bw)`` — the bound
    verdict then compares the fitted compute vs memory terms.  The
    model status rides on ``att["costmodel"]`` either way."""
    classes = att["classes"]
    peak_flops, hbm_bw = att["peak_flops"], att["hbm_bw"]
    entry, status = _fitted_costmodel()
    att["costmodel"] = status
    if entry is not None:
        from ..tune import costmodel as _cm
    total_est = 0.0
    for cls, row in classes.items():
        if entry is not None:
            est_ms, compute_ms, mem_ms = _cm.predict_class_ms(
                entry, cls, row["flops"], row["bytes"], row["ops"])
            row["est_ms"] = est_ms
            row["bound"] = ("compute" if compute_ms >= mem_ms
                            else "memory")
            total_est += row["est_ms"]
            continue
        compute_s = row["flops"] / peak_flops if peak_flops else 0.0
        mem_s = row["bytes"] / hbm_bw if hbm_bw else 0.0
        row["est_ms"] = max(compute_s, mem_s) * 1e3
        row["bound"] = "compute" if compute_s >= mem_s else "memory"
        total_est += row["est_ms"]
    for row in classes.values():
        row["share"] = (round(row["est_ms"] / total_est, 4)
                        if total_est else 0.0)
        row["est_ms"] = round(row["est_ms"], 6)
    att["hlo_flops_total"] = sum(r["flops"] for r in classes.values())
    att["est_ms_total"] = round(total_est, 6)
    return att


def _machine_roofline():
    """(peak_flops, hbm_bandwidth) of device 0 — the roofline the
    per-class ms estimates are computed against."""
    from . import hardware as _hardware

    try:
        import jax

        dev = jax.devices()[0]
    except Exception:  # backendless callers (pure-text tests)
        dev = None
    return (_hardware.device_peak_flops(dev),
            _hardware.device_hbm_bandwidth(dev))


def program_workload_key(program, remat=None):
    """The tune-cache-style workload key string for a Program's step —
    located by its flash attention op exactly the way
    ``tune.program_schedule_config`` locates the schedule key, so an
    attribution corpus row and a tuner measurement of the same workload
    share one join key.  None when the program has no flash op."""
    if program is None:
        return None
    try:
        from ..tune.space import WorkloadKey
    except Exception:  # tune package unavailable mid-bootstrap
        return None
    block = program.global_block()
    for op in block.ops:
        if op.type not in ("flash_attention_packed", "flash_attention"):
            continue
        q_names = op.inputs.get("Q") or []
        var = block._find_var(q_names[0]) if q_names else None
        if var is None or len(var.shape) < 3:
            continue
        t = int(var.shape[1])
        if t <= 0:
            continue
        if op.type == "flash_attention_packed":
            n_head = int(op.attrs.get("n_head") or 0)
            if not n_head:
                continue
            d_head = int(var.shape[2]) // n_head
        else:
            n_head, d_head = int(var.shape[2]), int(var.shape[3])
        try:
            import jax

            platform = jax.default_backend()
        except Exception:
            platform = "unknown"
        pol = remat if remat is not None else (
            getattr(program, "_remat_policy", None) or "-")
        try:
            # which kernel backend the flash op class resolved to at
            # THIS compile's trace (kernels/registry.py) — the |kb=
            # token that keys corpus rows and the trainer's JSONL
            # by which kernel ran, not just the platform
            from ..kernels import selected_backends

            kb = selected_backends().get("flash_attention")
        except Exception:  # kernels package unavailable mid-bootstrap
            kb = None
        return WorkloadKey("step", t, d_head, n_head, var.dtype,
                           platform, remat=pol, backend=kb).s
    return None


def normalize_workload_key(key):
    """Canonicalize a workload-key string for corpus joins: keys
    written before the kernel registry existed (pre-PR-13 JSONL) carry
    no ``|kb=`` backend token — backfill ``|kb=unknown`` so
    mixed-vintage corpora join on one key shape instead of the old
    rows being silently skipped.  Non-key strings and None pass
    through unchanged (None stays None)."""
    if not isinstance(key, str) or not key.startswith("op="):
        return key if key else None
    if "|kb=" in key:
        return key
    return key + "|kb=unknown"


def _flash_estimate(program, n_calls):
    """Roofline flop estimate for opaque kernel custom-calls (the TPU
    path, where the Mosaic body is invisible to the HLO walk): the
    ``causal_flash_flops`` schedule simulation — the exact model
    ``tune/space.py``'s static pruning ranks candidates with — per
    (batch, head), scaled by the call count."""
    if program is None or not n_calls:
        return 0
    try:
        from ..ops.pallas_attention import causal_flash_flops
    except Exception:
        return 0
    block = program.global_block()
    for op in block.ops:
        if op.type not in ("flash_attention_packed", "flash_attention"):
            continue
        q_names = op.inputs.get("Q") or []
        var = block._find_var(q_names[0]) if q_names else None
        if var is None or len(var.shape) < 3:
            continue
        t = int(var.shape[1])
        if op.type == "flash_attention_packed":
            n_head = int(op.attrs.get("n_head") or 0) or 1
            d_head = int(var.shape[2]) // n_head
        else:
            n_head, d_head = int(var.shape[2]), int(var.shape[3])
        batch = int(var.shape[0]) if int(var.shape[0]) > 0 else 1
        bq = int(op.attrs.get("block_q") or 1024)
        bk = int(op.attrs.get("block_k") or 1024)
        try:
            sched, _useful = causal_flash_flops(t, t, d_head, bq, bk)
        except Exception:
            return 0
        return int(sched * n_head * batch * n_calls)
    return 0


def attribute_compiled(compiled, cost=None, program=None, remat=None):
    """The full attribution record for one compiled executable:
    :func:`attribute_hlo` over its optimized HLO plus the coverage
    figure against the executable's own cost analysis and the
    tune-style workload key.  ``{}`` when the backend cannot render
    HLO text."""
    try:
        text = compiled.as_text()
    except Exception:
        return {}
    if not text:
        return {}
    att = attribute_hlo(text)
    pallas = att["classes"].get("pallas")
    if pallas is not None and pallas["flops"] == 0 and pallas["ops"]:
        # opaque custom-calls (TPU Mosaic): fill in the tune/space.py
        # schedule estimate so the kernel class still owns its math —
        # then REDO the roofline so est_ms/bound/share (the figures
        # the trainer's JSONL carries) reflect it
        est = _flash_estimate(program, pallas["ops"])
        if est:
            pallas["flops"] = est
            pallas["flops_estimated"] = True
            _finalize_roofline(att)
    cost_flops = (cost or {}).get("flops")
    att["cost_flops"] = cost_flops
    att["coverage"] = (round(att["hlo_flops_total"] / cost_flops, 4)
                       if cost_flops else None)
    att["workload"] = program_workload_key(program, remat=remat)
    reg = _obs.get_registry()
    reg.counter("attribution.tables",
                help="compiled steps walked into attribution tables").inc()
    if att["coverage"] is not None:
        reg.gauge(
            "attribution.coverage",
            help="attributed flops / cost-analysis flops of the last "
                 "compile").set(att["coverage"])
    return att


def summarize(att, top_n=3):
    """The compact summary folded into ``last_step_cost["attribution"]``
    (and thence the trainer's JSONL): the top-``top_n`` classes
    by estimated time plus the totals the reconciliation needs, the
    compact per-class ``[flops, bytes, ops, est_ms]`` table a corpus
    row fits on (``observability/corpus.py``), and the cost-model
    status (fitted vs analytic) the estimates were computed under."""
    if not att:
        return None
    rows = sorted(att.get("classes", {}).items(),
                  key=lambda kv: -(kv[1].get("est_ms") or 0))
    return {
        "top": [[c, r.get("share"), r.get("bound")]
                for c, r in rows[:top_n]],
        "est_ms_total": att.get("est_ms_total"),
        "coverage": att.get("coverage"),
        "workload": att.get("workload"),
        "classes": {c: [r.get("flops"), r.get("bytes"), r.get("ops"),
                        r.get("est_ms")]
                    for c, r in att.get("classes", {}).items()},
        "costmodel": att.get("costmodel"),
    }


def reconcile(att, measured_step_s):
    """Roofline-estimate vs measured step time: ``{"est_ms",
    "measured_ms", "err_pct"}`` — the model-quality figure every
    attribution corpus row ships with (a learned cost model is only as
    good as the measurement it fits; CUDA-L2's lesson in PAPERS.md).
    ``err_pct`` is signed: negative = the roofline under-estimates
    (host overhead, serialization), positive = over-estimates."""
    if not att or not measured_step_s or measured_step_s <= 0:
        return None
    est_ms = att.get("est_ms_total")
    if est_ms is None:
        return None
    measured_ms = measured_step_s * 1e3
    out = {
        "est_ms": round(est_ms, 6),
        "measured_ms": round(measured_ms, 6),
        "err_pct": round((est_ms - measured_ms) / measured_ms * 100.0, 2),
    }
    # the corpus join key, NORMALIZED: pre-PR-13 records whose key lacks
    # the |kb= backend token used to be silently unjoinable — backfill
    # backend=unknown so mixed-vintage corpora reconcile (one row shape)
    wk = normalize_workload_key(att.get("workload"))
    if wk:
        out["workload"] = wk
    return out
