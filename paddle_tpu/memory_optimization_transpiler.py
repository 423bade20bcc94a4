"""Memory optimization transpiler.

Reference: ``python/paddle/v2/fluid/memory_optimization_transpiler.py`` —
``ControlFlowGraph`` (:33) runs a dataflow/liveness analysis
(``_dataflow_analyze`` :89) and reuses dead buffers of matching shape
(``memory_optimize`` :121), because the per-op interpreter otherwise keeps
every activation alive for the whole step.

TPU translation: XLA already performs buffer reuse/liveness inside one
compiled program, so the half of the reference pass that matters here is the
*activation memory of the backward pass*: the jitted step holds every
forward activation alive until its gradient use.  ``memory_optimize``
therefore selects rematerialization segment boundaries at the
liveness-minimal cut points of the forward prefix and marks them on the
program; the Executor wraps each segment in ``jax.checkpoint`` so backward
recomputes activations instead of storing them (sqrt-N checkpointing —
the FLOPs-for-HBM trade the survey's build plan calls for).

``ControlFlowGraph`` is also exposed directly (defs/uses/live-in/live-out
and a peak-live-bytes estimate) for inspection parity with the reference.
"""

import math

import numpy as np

from .core.program import GRAD_SUFFIX

__all__ = ["ControlFlowGraph", "memory_optimize", "release_memory"]


def _dtype_size(dtype):
    try:
        return np.dtype(dtype.name if hasattr(dtype, "name") else dtype).itemsize
    except TypeError:
        return 4


class ControlFlowGraph:
    """Liveness over one block's op list (reference :33-120).

    defs[i]/uses[i]: names written/read by op i.  live_in[i]/live_out[i]:
    the classic backward dataflow fixpoint — here computed in one reverse
    sweep since the op list is a straight line (control flow lives in
    sub-blocks, handled by their ops as units)."""

    def __init__(self, program, block_idx=0, ops=None):
        self.program = program
        self.block = program.block(block_idx)
        self.ops = list(self.block.ops) if ops is None else list(ops)
        self.defs = []
        self.uses = []
        for op in self.ops:
            reads = set(op.input_names())
            writes = set(op.output_names())
            sub = op.attrs.get("sub_block")
            if sub is not None:
                sub_reads, sub_writes = self._sub_block_names(sub, set())
                reads |= sub_reads
                writes |= sub_writes
            self.uses.append(reads)
            self.defs.append(writes)
        self._analyze()

    def _sub_block_names(self, block_idx, seen):
        if block_idx in seen:
            return set(), set()
        seen.add(block_idx)
        reads, writes = set(), set()
        for op in self.program.block(block_idx).ops:
            reads |= set(op.input_names())
            writes |= set(op.output_names())
            sub = op.attrs.get("sub_block")
            if sub is not None:
                r, w = self._sub_block_names(sub, seen)
                reads |= r
                writes |= w
        return reads, writes

    def _analyze(self):
        n = len(self.ops)
        self.live_in = [set() for _ in range(n)]
        self.live_out = [set() for _ in range(n)]
        live = set()
        for i in range(n - 1, -1, -1):
            self.live_out[i] = set(live)
            live = (live - self.defs[i]) | self.uses[i]
            self.live_in[i] = set(live)

    def live_at_cut(self, i):
        """Names that must cross the boundary *before* op i (defined earlier,
        used at/after i)."""
        if i >= len(self.ops):
            return set()
        return self.live_in[i]

    def _var_bytes(self, name):
        var = self.block._find_var(name)
        if var is None or not var.shape:
            return 0
        numel = 1
        for s in var.shape:
            numel *= abs(int(s)) if s else 1
        return numel * _dtype_size(var.dtype)

    def peak_live_bytes(self):
        """Estimated peak of live (non-persistable) activation bytes —
        the quantity the reference pass minimized by buffer reuse."""
        peak = 0
        for i in range(len(self.ops)):
            total = 0
            for name in self.live_in[i] | self.defs[i]:
                var = self.block._find_var(name)
                if var is not None and not var.persistable:
                    total += self._var_bytes(name)
            peak = max(peak, total)
        return peak


def _cut_cost(graph, i, exclude):
    return sum(
        graph._var_bytes(n)
        for n in graph.live_at_cut(i)
        if n not in exclude
    )


# op types whose forward is too expensive to recompute in backward: every
# remat policy keeps them OUTSIDE jax.checkpoint wrappers so their
# custom-VJP residuals (e.g. flash attention's o + lse, the fused CE
# head's lse) stay saved and the kernels never re-run.
EXPENSIVE_OPS = ("flash_attention", "flash_attention_packed",
                 "fused_softmax_ce_head", "scan_block",
                 "nested_rnn", "warpctc")

# MXU ops: the selective policy also keeps these saved — on TPU the right
# recompute set is the VPU-cheap tail (LN, activations, residual adds,
# dropout), which hides under the backward matmuls; re-running MXU work
# costs real step time (measured −17% when projections/FFN matmuls are
# rematerialized on the GPT flagship vs −4% recomputing only VPU ops).
MXU_OPS = ("mul", "matmul", "conv2d", "conv3d", "depthwise_conv2d",
           "conv2d_transpose", "conv3d_transpose")


def memory_optimize(input_program=None, num_segments=None, min_segment=2,
                    level=0, print_log=False, policy="selective",
                    expensive_ops=None):
    """Mark remat segments on the forward prefix of ``input_program``
    (in place, like the reference — the TPU translation of the liveness
    judgment in ``memory_optimization_transpiler.py:33``).

    ``policy="selective"`` (default): maximal runs of VPU-cheap ops
    (layer norm, activations, residual adds, dropout) are wrapped in
    ``jax.checkpoint`` — backward recomputes them under the shadow of the
    backward matmuls; kernel ops (flash attention, the fused CE head) and
    MXU ops (projections, FFN matmuls, convs) stay unwrapped with their
    outputs/residuals saved.  Frees the elementwise activations (the
    gelu/LN/residual tensors — the bulk by count) at a few percent step
    cost.

    ``policy="compact"``: only kernel ops stay saved; matmuls are
    rematerialized too.  Maximum memory saving (only kernel residuals +
    segment boundaries survive) at ~15-17% step cost — the
    bigger-than-memory lever (t=16k+ flagship shapes).

    ``policy="full"``: the round-2 all-or-nothing behavior — sqrt-N
    liveness-minimal cuts, every segment rematerialized (recomputes flash
    too; measured −23% on the GPT flagship, round 4).

    ``policy="offload"``: the selective saved set, with the per-layer
    scan residuals (the block inputs — the residual stream entering each
    scanned transformer layer) streamed to PINNED HOST memory on the
    forward scan and prefetched back one layer ahead during the backward
    scan.  A pure memory-PLACEMENT change relative to ``selective``: the
    computation (and hence loss/grads) is identical; only the HBM
    high-water drops by the stacked block-input residual.  Executed by
    the Executor's scan-remat engine via a name-policy ``jax.checkpoint``
    (``core/memaudit.py`` tags); outside scanned groups (prologue/
    epilogue, non-uniform programs) it degrades to plain ``selective``.
    Kill switch: ``PADDLE_TPU_OFFLOAD=0``; on backends without a
    ``pinned_host`` memory space (CPU) the same checkpoint structure
    runs with the block inputs left in device memory.

    ``policy="auto"``: consult the autotune cache
    (``paddle_tpu.tune``, docs/autotune.md) for this program's flash
    workload key and apply the MEASURED winning policy; a cache miss
    (or ``PADDLE_TPU_TUNE=0``) falls back to ``selective`` — today's
    default.  A tuned winner of ``"none"`` leaves the program unmarked
    (no remat at all was the measured-fastest schedule that fit).

    Returns the segment list ``[(start, end, wrapped), ...]`` tiling the
    forward prefix."""
    from .core.program import default_main_program

    program = input_program or default_main_program()
    if policy == "auto":
        from .tune import program_schedule_config

        cfg = program_schedule_config(program) or {}
        policy = cfg.get("policy") or "selective"
        if "fsdp" in cfg:
            # the tuned gather-vs-replicate decision (schedule_candidates'
            # fsdp dimension): False opts the Executor's scan body out of
            # the in-loop FSDP weight gathers for this program
            program._fsdp = bool(cfg["fsdp"])
        if policy == "none":
            program._offload = False
            program._remat_segments = []
            program._remat_policy = "none"
            return []
    block = program.global_block()
    if policy not in ("selective", "compact", "full", "offload"):
        raise ValueError(
            f"memory_optimize policy must be 'selective', 'compact', "
            f"'full', 'offload' or 'auto', got {policy!r}")
    # the offload flag rides on the program (the Executor's scan body
    # reads it); segmentation below is exactly selective's
    program._offload = policy == "offload"
    # the resolved policy label rides on the program: the attribution
    # engine's workload key carries it (observability/attribution.py),
    # matching the tune cache's remat dimension
    program._remat_policy = policy
    policy_label = policy
    if policy == "offload":
        policy = "selective"
    bw = block.backward_index
    n_fwd = bw if bw is not None else len(block.ops)
    if n_fwd < 2 * min_segment:
        program._remat_segments = []
        return []

    if expensive_ops is None:
        expensive_ops = EXPENSIVE_OPS
        if policy == "selective":
            expensive_ops = EXPENSIVE_OPS + MXU_OPS
    expensive_at = [
        i for i in range(n_fwd) if block.ops[i].type in expensive_ops
    ]
    if policy in ("selective", "compact") and expensive_at:
        segments = []
        pos = 0
        for i in expensive_at:
            if i > pos:
                segments.append((pos, i, True))
            segments.append((i, i + 1, False))
            pos = i + 1
        if pos < n_fwd:
            segments.append((pos, n_fwd, True))
        # wrapping a tiny tail saves nothing and costs a checkpoint trace
        segments = [
            (s, t, wrap and (t - s) >= min_segment)
            for s, t, wrap in segments
        ]
        # merge adjacent unwrapped segments (runs of saved ops) so the
        # executor sees few, large segments instead of op-sized slivers
        merged = []
        for seg in segments:
            if (merged and not seg[2] and not merged[-1][2]
                    and merged[-1][1] == seg[0]):
                merged[-1] = (merged[-1][0], seg[1], False)
            else:
                merged.append(seg)
        segments = [tuple(s) for s in merged]
        program._remat_segments = segments
        program._bump_version()
        if print_log:
            n_wrap = sum(1 for _, _, w in segments if w)
            print(f"memory_optimize[{policy_label}]: {len(segments)} "
                  f"segments, {n_wrap} wrapped, expensive at {expensive_at}")
        return segments

    # "full" policy: prefer cuts at the boundaries of the program's
    # repeated structure (one transformer block per segment) — uniform
    # segments are what the Executor's scan-remat engine can run as one
    # lax.scan with stacked weights (O(1)-per-layer remat temps, the form
    # that compiles at t=16k).  Liveness-minimal sqrt-N cuts remain the
    # fallback for programs with no repetition.
    from .core.ir import detect_repeated_run

    rep = detect_repeated_run(program, 0, n_fwd)
    if rep is not None and num_segments is None:
        s0, p, count = rep
        segments = []
        if s0 > 0:
            segments.append((0, s0, s0 >= min_segment))
        segments += [(s0 + i * p, s0 + (i + 1) * p, True)
                     for i in range(count)]
        tail = s0 + count * p
        if tail < n_fwd:
            segments.append((tail, n_fwd, (n_fwd - tail) >= min_segment))
        program._remat_segments = segments
        program._bump_version()
        if print_log:
            print(f"memory_optimize[full]: {count} uniform segments of "
                  f"{p} ops at {s0} (+prologue/epilogue), scan-remat "
                  f"eligible")
        return segments

    graph = ControlFlowGraph(program, 0, block.ops[:n_fwd])
    k = num_segments or max(2, int(math.isqrt(n_fwd)))
    # parameters/data cross every cut anyway — exclude them from cut cost
    always_live = {
        v.name for v in block.vars.values() if v.persistable or v.is_data
    }
    # candidate cut positions ranked by bytes that would have to be saved
    candidates = sorted(
        range(min_segment, n_fwd - min_segment + 1),
        key=lambda i: _cut_cost(graph, i, always_live),
    )
    cuts = []
    for i in candidates:
        if len(cuts) >= k - 1:
            break
        if all(abs(i - c) >= min_segment for c in cuts):
            cuts.append(i)
    cuts = sorted(cuts)
    bounds = [0] + cuts + [n_fwd]
    segments = [
        (bounds[j], bounds[j + 1], True) for j in range(len(bounds) - 1)
        if bounds[j + 1] > bounds[j]
    ]
    program._remat_segments = segments
    program._bump_version()
    if print_log:
        print(f"memory_optimize: {len(segments)} remat segments {segments}, "
              f"peak live ~{graph.peak_live_bytes() / 1e6:.1f} MB")
    return segments


def gradient_accumulation(input_program=None, micro_steps=1):
    """Split every training step into ``micro_steps`` microbatches: the
    feed batch is sliced along its leading axis, forward+backward runs per
    microbatch under ``lax.scan``, gradients accumulate in float32, and
    the optimizer applies ONCE with the mean gradient — the memory lever
    that lets remat policies lighter than ``full`` fit long-context shapes
    (activation memory scales with the microbatch, gradients are one
    param-sized buffer).  Mean-of-microbatch-averages equals the big-batch
    average-loss gradient when microbatches carry equal loss weight (the
    same-math-different-schedule contract of the reference's
    ``test_CompareTwoNets.cpp``); ``tests/test_grad_accum.py`` pins it.

    Composes with ``memory_optimize``: segments apply inside each
    microbatch.  Feed leading dims must divide by ``micro_steps``."""
    from .core.program import default_main_program

    program = input_program or default_main_program()
    micro_steps = int(micro_steps)
    if micro_steps < 1:
        raise ValueError(f"micro_steps must be >= 1, got {micro_steps}")
    program._grad_accum = micro_steps
    program._bump_version()
    return program


def release_memory(input_program=None):
    """Reference API parity (drop-in no-op: XLA frees/reuses buffers inside
    the compiled step; remat via memory_optimize is the active knob)."""
    return input_program
