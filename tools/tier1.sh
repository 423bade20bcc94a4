#!/usr/bin/env bash
# Tier-1 verify with a DOTS_PASSED regression gate.
#
# Runs the ROADMAP.md tier-1 pytest command, counts passed tests the same
# way the driver does (dots in the progress lines), and fails if the count
# drops below the floor recorded in tests/TIER1_FLOOR.  Raise the floor
# whenever a PR adds passing tests; never lower it.
#
# Usage: tools/tier1.sh
set -o pipefail
cd "$(dirname "$0")/.."
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
passed=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
floor=$(cat tests/TIER1_FLOOR 2>/dev/null || echo 0)
echo "DOTS_PASSED=$passed (floor: $floor)"
if [ "$passed" -lt "$floor" ]; then
    echo "TIER1 REGRESSION: DOTS_PASSED $passed < floor $floor" >&2
    exit 1
fi
# the metrics-selftest smoke entry rides along: the telemetry subsystem
# must stay healthy for every perf PR that reads it
if ! python -m paddle_tpu --metrics-selftest > /tmp/_t1_selftest.log 2>&1; then
    echo "TIER1 REGRESSION: metrics selftest failed" >&2
    cat /tmp/_t1_selftest.log >&2
    exit 1
fi
# backward-pass memory smoke: the no-accelerator scan-locality /
# memory_analysis regression (docs/memory.md invariants) run explicitly —
# all four memory_optimize policies must keep their flash kernel calls
# scan-local and offload must stay bit-exact vs selective
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
        python -m paddle_tpu --memory-selftest \
        > /tmp/_t1_memtest.log 2>&1; then
    echo "TIER1 REGRESSION: memory selftest failed" >&2
    cat /tmp/_t1_memtest.log >&2
    exit 1
fi
# multichip smoke: the scaling-engine invariants on the 8-device virtual
# CPU mesh — ZeRO-1 accumulator sharding (state bytes/device <=
# replicated/4), one cross-chip gradient reduction per optimizer step
# under accum (comm audit on compiled HLO), ZeRO/FSDP bit-exactness vs
# the replicated spelling, and the true-ZeRO-3 gradient gates:
# zero3_grad_contract clean on the compiled plan (one boundary
# reduce-scatter@fsdp per fsdp-tagged grad, zero in-loop reduces),
# prologue (embedding + LM head) bytes/device bound, 5-step
# bit-exactness vs PADDLE_TPU_ZERO3_RS=0, and comm_diff naming the
# moved collectives (docs/parallel.md rule 4)
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
        python -m paddle_tpu --multichip-selftest \
        > /tmp/_t1_multichip.log 2>&1; then
    echo "TIER1 REGRESSION: multichip selftest failed" >&2
    cat /tmp/_t1_multichip.log >&2
    exit 1
fi
# static-analysis smoke: the lint pass framework's planted-defect /
# clean-program contract — every seeded check fires on its deliberately
# broken Program (dead code, shape-dtype, read-before-write, fetch
# overwrite, bf16 accum, tanh-in-scan, scan-locality, degraded offload,
# HBM preflight, donation audit, in-loop collective on the 2-device
# virtual mesh), the GPT benchmark program lints to ZERO findings, and
# every examples/ script's program lints clean (docs/analysis.md)
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
        python -m paddle_tpu --lint-selftest \
        > /tmp/_t1_linttest.log 2>&1; then
    echo "TIER1 REGRESSION: lint selftest failed" >&2
    cat /tmp/_t1_linttest.log >&2
    exit 1
fi
# sharding/comm-contract smoke: the communication contract analyzer —
# four planted constraint-placement violations (symmetric fsdp pin,
# fsdp-composed grad carry, forbidden activation reshard, in-loop
# reduce-scatter caught by zero3_grad_contract) each caught with the
# right kind/axis/loop attribution, CommPlan mesh-axis recovery +
# comm_diff, and the clean-GPT sweep (every memory_optimize policy x
# FSDP on/off x ZeRO on/off on the 8-device CPU mesh) reporting zero
# error-severity comm findings under the attached training contracts,
# zero3_grad_contract included (docs/analysis.md "Communication
# contracts")
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
        python -m paddle_tpu --sharding-selftest \
        > /tmp/_t1_sharding.log 2>&1; then
    echo "TIER1 REGRESSION: sharding selftest failed" >&2
    cat /tmp/_t1_sharding.log >&2
    exit 1
fi
# tracing smoke: the end-to-end tracing engine — span runtime semantics,
# the trainer's five step-phase spans into a valid Chrome-trace file,
# the serving request span tree's TTFT decomposition (queue + prefill
# within 10% of the ttft histogram), and the --bench-history gate
# exiting non-zero on a planted failed/regressed artifact fixture
# (docs/observability.md)
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
        python -m paddle_tpu --trace-selftest \
        > /tmp/_t1_trace.log 2>&1; then
    echo "TIER1 REGRESSION: trace selftest failed" >&2
    cat /tmp/_t1_trace.log >&2
    exit 1
fi
# resilience smoke: the elastic resilience engine — a trainer subprocess
# on the 8-device virtual CPU mesh SIGKILLed mid-pass (PADDLE_TPU_FAULT)
# resumes from its latest loadable full-state checkpoint (params +
# optimizer state + RNG + reader cursor) and reproduces the
# uninterrupted loss trajectory bit-exact, and a crash injected DURING
# checkpoint publish still leaves a loadable checkpoint via the .old
# fallback (docs/resilience.md)
if ! timeout -k 10 900 env JAX_PLATFORMS=cpu \
        python -m paddle_tpu --resilience-selftest \
        > /tmp/_t1_resilience.log 2>&1; then
    echo "TIER1 REGRESSION: resilience selftest failed" >&2
    cat /tmp/_t1_resilience.log >&2
    exit 1
fi
# autotune smoke: the measured schedule search on the CPU backend — a
# toy-transformer search whose HBM preflight rejects over-budget
# candidates from compiled cost analysis alone and whose winner beats
# the worst measured candidate, a pure cache hit (zero recompiles) on
# the second invocation, PADDLE_TPU_TUNE=0 bit-exact vs untuned
# defaults, and the t=16k static prune rejecting the BENCH_r05 config
# (docs/autotune.md)
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
        python -m paddle_tpu --tune-selftest \
        > /tmp/_t1_tune.log 2>&1; then
    echo "TIER1 REGRESSION: tune selftest failed" >&2
    cat /tmp/_t1_tune.log >&2
    exit 1
fi
# kernel-registry smoke: the multi-backend kernel subsystem — registry
# resolution + override precedence on this host, oracle parity of every
# available backend (plus the interpret-forced Mosaic paged kernel)
# against the pure-XLA reference within the documented tolerances,
# paged-attention parity over ragged block chains (trash-block masking,
# CoW forks, the fully-cached one-token prefill),
# PADDLE_TPU_KERNEL_BACKEND=xla_ref running the full
# GPT trainer path under every memory_optimize policy with ZERO Pallas
# calls in the jaxpr, and the interpret-mode-in-timed-run lint finding
# planted and detected (docs/kernels.md)
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
        python -m paddle_tpu --kernels-selftest \
        > /tmp/_t1_kernels.log 2>&1; then
    echo "TIER1 REGRESSION: kernels selftest failed" >&2
    cat /tmp/_t1_kernels.log >&2
    exit 1
fi
# learned-cost-model smoke: the observability->tuning loop closed — two
# real CPU-measured toy-GPT runs seed the measurement corpus through the
# production MetricsReporter JSONL path, the fitted roofline's holdout
# error strictly improves on the analytic model's recorded error, the
# t=16k static prune under the fitted model still rejects the known-OOM
# BENCH_r05 config and selects the same known-good schedule, corrupt/
# truncated/schema-mismatched model files degrade to analytic defaults,
# and PADDLE_TPU_COSTMODEL=0 is bit-exact vs the no-model baseline
# (docs/observability.md "Cost model calibration")
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
        python -m paddle_tpu --costmodel-selftest \
        > /tmp/_t1_costmodel.log 2>&1; then
    echo "TIER1 REGRESSION: costmodel selftest failed" >&2
    cat /tmp/_t1_costmodel.log >&2
    exit 1
fi
# attribution smoke: the per-op performance attribution engine + crash
# flight recorder — the compiled GPT flagship-family step's attribution
# table covers >= 95% of cost-analysis flops with a tune-style workload
# key, the roofline estimate-vs-measured error is reported, injected
# NaN/watchdog faults each dump a loadable flight bundle containing the
# triggering step, and a planted bench-history regression is attributed
# to the op class whose share moved (docs/observability.md)
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
        python -m paddle_tpu --attribution-selftest \
        > /tmp/_t1_attr.log 2>&1; then
    echo "TIER1 REGRESSION: attribution selftest failed" >&2
    cat /tmp/_t1_attr.log >&2
    exit 1
fi
# speculative-decoding smoke: draft-model propose / single-pass target
# verify / token-exact rollback on the paged serving engine — a
# depth-pruned draft emits TOKEN-EXACT output vs single-stream greedy
# (f32 + bf16, prefix reuse on/off), a self-draft run's acceptance ~1
# proves the parallel verify window bit-consistent with the sequential
# step, an adversarial draft stays exact, propose/rollback leaves
# blocks_in_use at the plain engine's baseline, and PADDLE_TPU_SPEC=0
# is bit-exact with zero spec metrics (docs/serving.md)
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
        python -m paddle_tpu --spec-selftest \
        > /tmp/_t1_spec.log 2>&1; then
    echo "TIER1 REGRESSION: spec selftest failed" >&2
    cat /tmp/_t1_spec.log >&2
    exit 1
fi
# bench-history gate: every BENCH_*/MULTICHIP_* artifact in the repo
# must classify (failures acknowledged in tools/bench_known_failures.json
# with a root cause, never silent) and no tracked metric may regress
# >10% vs best-so-far — a rotted bench artifact fails CI here instead of
# sitting on disk (the BENCH_r05 lesson)
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
        python -m paddle_tpu --bench-history \
        > /tmp/_t1_benchhist.json 2> /tmp/_t1_benchhist.log; then
    echo "TIER1 REGRESSION: bench-history gate failed" >&2
    cat /tmp/_t1_benchhist.log >&2
    cat /tmp/_t1_benchhist.json >&2
    exit 1
fi
if ! python -c "
import json
rows = [json.loads(l) for l in open('/tmp/_t1_benchhist.json') if l.strip()]
assert len(rows) == 1, f'expected ONE json line, got {len(rows)}'
row = rows[0]
for k in ('metric', 'artifacts', 'failed', 'regressions', 'ok'):
    assert k in row, f'missing field {k}: {row}'
assert row['ok'] is True, row
print('bench history:', json.dumps(row))
"; then
    echo "TIER1 REGRESSION: bench-history emitted invalid JSON" >&2
    cat /tmp/_t1_benchhist.json >&2
    exit 1
fi
# serving smoke: the continuous-batching engine must beat the sequential
# single-stream baseline, SLO-scheduled goodput must beat the FIFO
# baseline's goodput under the same shared-prefix Poisson load, and the
# paged prefix-reuse cache must hit (prefix_hit_rate > 0, strictly fewer
# prefill tokens than reuse-off), and the speculative pass must beat the
# SLO pass's goodput on the same arrival schedule with zero scratch-block
# leak — all asserted inside --smoke — and the script must print ONE
# parseable JSON row with the throughput/latency/goodput/prefix/compile/
# speculative fields
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
        python benchmarks/serving.py --smoke \
        > /tmp/_t1_serving.json 2> /tmp/_t1_serving.log; then
    echo "TIER1 REGRESSION: serving smoke failed" >&2
    cat /tmp/_t1_serving.log >&2
    cat /tmp/_t1_serving.json >&2
    exit 1
fi
if ! python -c "
import json, sys
rows = [json.loads(l) for l in open('/tmp/_t1_serving.json') if l.strip()]
assert len(rows) == 1, f'expected ONE json line, got {len(rows)}'
row = rows[0]
for k in ('tok_s', 'baseline_tok_s', 'speedup', 'ttft_p50_ms',
          'e2e_p99_ms', 'prefill_compiles', 'decode_compiles',
          'goodput_under_slo', 'slo_violations', 'prefix_hit_rate',
          'shed_total', 'fifo_goodput_under_slo', 'prefill_tokens',
          'fifo_prefill_tokens', 'cow_copies',
          'spec_goodput_under_slo', 'spec_accept_rate', 'spec_speedup'):
    assert k in row, f'missing field {k}: {row}'
print('serving smoke:', json.dumps(row))
"; then
    echo "TIER1 REGRESSION: serving smoke emitted invalid JSON" >&2
    cat /tmp/_t1_serving.json >&2
    exit 1
fi
exit $rc
