"""Registry contract (docs/kernels.md): precedence explicit arg > per-op
env > global env > auto; unknown backends raise ValueError; explicitly
requested unavailable backends raise KernelUnavailable with a reason; a
global env pin an op cannot serve degrades to auto; what a compile
selected is recorded; and the tuner's backend dimension."""

import os

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import kernels
from paddle_tpu.kernels import (
    KernelUnavailable, available_backends, forced_backend, get_kernel,
    resolve_name)


# -- registry unit suite -----------------------------------------------------

def test_precedence_explicit_arg_beats_env(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_KERNEL_BACKEND", "xla_ref")
    assert resolve_name("flash_attention") == "xla_ref"
    assert resolve_name("flash_attention", "pallas_tpu") == "pallas_tpu"


def test_precedence_per_op_env_beats_global(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_KERNEL_BACKEND", "xla_ref")
    monkeypatch.setenv("PADDLE_TPU_KERNEL_BACKEND_FLASH_ATTENTION",
                       "pallas_tpu")
    assert resolve_name("flash_attention") == "pallas_tpu"
    # the per-op pin does not leak to other op classes
    assert resolve_name("fused_ce") == "xla_ref"


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown kernel backend"):
        resolve_name("flash_attention", "cuda_graphs")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        with forced_backend("notabackend"):
            pass


def _off_tpu_only():
    """The registered-but-unavailable backend of the registry unit
    suite is the Mosaic paged kernel off the TPU."""
    if get_kernel("paged_attention", "pallas_tpu").availability()[0]:
        pytest.skip("pallas_tpu paged attention is available here")


def test_unavailable_backend_raises_with_reason():
    _off_tpu_only()
    with pytest.raises(KernelUnavailable) as ei:
        resolve_name("paged_attention", "pallas_tpu")
    assert ei.value.reason


def test_global_env_fallback_to_auto(monkeypatch):
    # off the TPU the Mosaic paged kernel is unavailable: a fleet-wide
    # pallas_tpu pin must degrade that op to auto instead of crashing
    # serving
    _off_tpu_only()
    monkeypatch.setenv("PADDLE_TPU_KERNEL_BACKEND", "pallas_tpu")
    assert resolve_name("paged_attention") == "xla_ref"


def test_global_env_fallback_counted_once_per_resolution(monkeypatch):
    """The degrade-to-auto path's accounting contract (ISSUE 14
    satellite): a global env pin an op cannot serve increments
    ``kernels.env_fallbacks`` EXACTLY once per resolution — no double
    count inside one resolve, no missed count across repeats — while a
    servable pin and a strict (raising) explicit request increment
    nothing."""
    from paddle_tpu.observability import get_registry

    reg = get_registry()

    def count():
        return int(reg.value("kernels.env_fallbacks") or 0)

    _off_tpu_only()
    monkeypatch.setenv("PADDLE_TPU_KERNEL_BACKEND", "pallas_tpu")
    c0 = count()
    assert resolve_name("paged_attention") == "xla_ref"
    assert count() == c0 + 1
    assert resolve_name("paged_attention") == "xla_ref"
    assert count() == c0 + 2
    # a pin the op CAN serve resolves directly: no fallback counted
    monkeypatch.setenv("PADDLE_TPU_KERNEL_BACKEND", "xla_ref")
    assert resolve_name("paged_attention") == "xla_ref"
    assert count() == c0 + 2
    # strict sources raise instead of degrading: still no count
    monkeypatch.delenv("PADDLE_TPU_KERNEL_BACKEND")
    with pytest.raises(KernelUnavailable):
        resolve_name("paged_attention", "pallas_tpu")
    assert count() == c0 + 2


def test_two_backends_twelve_op_classes_and_any_platform_is_served():
    """What the registry holds since the GPU lowerings and the gather op
    class went and the grouped matrix product, retention, a wide window's
    chain walk, Mamba-2's recurrence, the gated delta rule, a learned
    indexer's scores and the attention of the rows it selects, a K/V
    plane's block scores and the attention of the blocks they select came:
    two backends, twelve op classes (the last four in the oracle's backend
    only), an auto order
    for the TPU and the CPU; a platform with no order of its own is
    served by the oracle for every op class."""
    one_backend = {"index_scores", "sparse_latent_attention",
                   "block_scores", "block_sparse_attention"}
    assert kernels.BACKENDS == ("pallas_tpu", "xla_ref")
    assert sorted(kernels.registered_op_classes()) == sorted([
        "chain_attention", "delta_rule", "flash_attention", "fused_ce",
        "grouped_matmul", "paged_attention", "retention", "ssm",
        *one_backend])
    assert set(kernels.AUTO_ORDER) == {"tpu", "cpu"}
    for op in kernels.registered_op_classes():
        assert {b for b, _, _ in available_backends(op)} == (
            {"xla_ref"} if op in one_backend else set(kernels.BACKENDS))
        assert resolve_name(op, platform="gpu") == "xla_ref"
        assert resolve_name(op, platform="tpu") in kernels.BACKENDS
        for dtype in ("float32", "bfloat16"):
            if (op, dtype) in kernels.ORACLE_TOL:
                assert kernels.oracle_tol(op, dtype) > 0
    assert all((op, "float32") in kernels.ORACLE_TOL for op in one_backend)
    with pytest.raises(ValueError, match="unknown kernel backend"):
        resolve_name("flash_attention", "triton")


def test_forced_backend_scopes_and_restores():
    before = resolve_name("fused_ce")
    with forced_backend("xla_ref"):
        assert resolve_name("fused_ce") == "xla_ref"
    with forced_backend("xla_ref", op_class="fused_ce"):
        assert resolve_name("fused_ce") == "xla_ref"
        # op-scoped force does not leak across op classes
        assert resolve_name("flash_attention") == resolve_name(
            "flash_attention", None)
    assert resolve_name("fused_ce") == before


def test_selected_backends_recorded_per_compile():
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        from paddle_tpu.models import transformer

        outs = transformer.build(vocab_size=64, n_layer=1, n_head=2,
                                 d_model=32, max_len=16,
                                 dropout_rate=0.0, dtype="float32",
                                 fused_head=True)
    scope = pt.core.scope.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        toks = np.zeros((2, 16), np.int64)
        exe.run(main, feed={"tokens": toks, "labels": toks},
                fetch_list=[outs["avg_cost"]], scope=scope)
        kb = (exe.last_step_cost or {}).get("kernel_backends")
        assert kb and kb.get("flash_attention") and kb.get("fused_ce")
        att = exe.last_attribution or {}
        assert f"|kb={kb['flash_attention']}" in att.get("workload", "")
    finally:
        pt.core.scope._scope_stack.pop()


@pytest.mark.parametrize("policy", [None, "selective", "offload",
                                    "compact", "full"])
def test_xla_ref_trainer_zero_pallas(monkeypatch, policy):
    """The acceptance bar at toy scale: under every memory_optimize
    policy an env-routed xla_ref GPT training step resolves both kernel
    op classes to xla_ref and traces with zero pallas calls."""
    from paddle_tpu.analysis.jaxpr_tools import walk_report
    from paddle_tpu.models import transformer

    monkeypatch.setenv("PADDLE_TPU_KERNEL_BACKEND", "xla_ref")
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        outs = transformer.build(vocab_size=64, n_layer=3, n_head=2,
                                 d_model=32, max_len=16,
                                 dropout_rate=0.0, dtype="float32",
                                 fused_head=True)
        if policy:
            pt.memory_optimize(main, policy=policy)
    scope = pt.core.scope.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        toks = np.zeros((2, 16), np.int64)
        loss = exe.run(main, feed={"tokens": toks, "labels": toks},
                       fetch_list=[outs["avg_cost"]], scope=scope)[0]
        assert np.isfinite(np.asarray(loss)).all()
        kb = exe.last_step_cost["kernel_backends"]
        assert kb["flash_attention"] == kb["fused_ce"] == "xla_ref"
        state_names = tuple(sorted(
            v.name for v in main.persistable_vars()
            if scope.find_var(v.name) is not None))
        step, _ = exe.lower(main, ["labels", "tokens"],
                            [outs["avg_cost"].name], state_names)
        state = {n: scope.get(n) for n in state_names}
        state[pt.core.scope.RNG_VAR] = scope.get(pt.core.scope.RNG_VAR)
        rep = walk_report(jax.make_jaxpr(step)(state, toks, toks))
        assert rep["pallas_total"] == 0
    finally:
        pt.core.scope._scope_stack.pop()


def test_timed_run_lint_fires_on_interpret_kernels():
    if jax.default_backend() == "tpu":
        pytest.skip("interpret planting needs a non-TPU host")
    from paddle_tpu.models import transformer

    def compile_under(env_backend):
        pt.core.unique_name.reset()
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            outs = transformer.build(
                vocab_size=64, n_layer=1, n_head=2, d_model=32,
                max_len=16, dropout_rate=0.0, dtype="float32",
                fused_head=True)
        scope = pt.core.scope.Scope()
        pt.core.scope._scope_stack.append(scope)
        try:
            if env_backend:
                os.environ["PADDLE_TPU_KERNEL_BACKEND"] = env_backend
            exe = pt.Executor()
            with kernels.timed_run():
                exe.run(startup, scope=scope)
                toks = np.zeros((2, 16), np.int64)
                exe.run(main, feed={"tokens": toks, "labels": toks},
                        fetch_list=[outs["avg_cost"]], scope=scope)
            return exe.last_step_cost or {}
        finally:
            os.environ.pop("PADDLE_TPU_KERNEL_BACKEND", None)
            pt.core.scope._scope_stack.pop()

    planted = compile_under(None)
    assert planted.get("interpret_in_timed_run") is True
    assert "jaxpr.kernel-backend" in (planted.get("lint_checks") or [])
    clean = compile_under("xla_ref")
    assert not clean.get("interpret_in_timed_run")
    assert "jaxpr.kernel-backend" not in (clean.get("lint_checks") or [])


# -- tuner integration -------------------------------------------------------

def test_attention_candidates_backend_dimension():
    from paddle_tpu.tune.space import attention_candidates, prune_static

    plain = attention_candidates(256, 64, 2)
    assert all("backend" not in c for c in plain)
    cands = attention_candidates(256, 64, 2,
                                 backends=("pallas_tpu", "xla_ref"))
    by_backend = {}
    for c in cands:
        by_backend.setdefault(c.get("backend"), []).append(c)
    assert set(by_backend) == {"pallas_tpu", "xla_ref"}
    # geometry-free backend contributes ONE candidate, not a cross
    assert len(by_backend["xla_ref"]) == 1
    # pruning keeps the xla_ref candidate (VMEM/roofline models are
    # Pallas-schedule models) while still vmem/roofline-pruning pallas
    surv, _pruned = prune_static(256, 64, 2, cands)
    assert any(c.get("backend") == "xla_ref" for c in surv)


def test_workload_key_backend_token():
    from paddle_tpu.tune.space import WorkloadKey

    plain = WorkloadKey("flash", 256, 64, 2, "bfloat16", "cpu",
                        remat="-")
    assert "kb=" not in plain.s
    keyed = WorkloadKey("flash", 256, 64, 2, "bfloat16", "cpu",
                        remat="-", backend="xla_ref")
    assert keyed.s.endswith("|kb=xla_ref")
    assert keyed.s.startswith(plain.s)


def test_tuned_winner_backend_reaches_flash_op():
    """A tuned config that persisted a kernel choice re-resolves on the
    hot path: multi_head_attention threads it into the flash op's
    ``backend`` attr."""
    from paddle_tpu import layers
    from paddle_tpu.tune import forced_attention_config

    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        with forced_attention_config({"block_q": 128, "block_k": 128,
                                      "backend": "xla_ref"}):
            x = layers.data("x", shape=[2, 256, 64], dtype="float32")
            layers.multi_head_attention(x, x, x, d_model=64, n_head=1,
                                        causal=True)
    ops = [op for op in main.global_block().ops
           if op.type.startswith("flash_attention")]
    assert ops, "no flash op built"
    assert ops[0].attrs.get("backend") == "xla_ref"
    assert ops[0].attrs.get("block_q") == 128


def test_cache_fingerprint_covers_registry_surface(monkeypatch):
    from paddle_tpu.tune import cache as tcache

    base = tcache.geometry_fingerprint()
    # reordering a platform's auto preference changes what a cached
    # config resolves to -> the fingerprint must move
    monkeypatch.setitem(kernels.AUTO_ORDER, "cpu",
                        ("xla_ref", "pallas_tpu"))
    assert tcache.geometry_fingerprint() != base


def test_tune_search_measures_backend_candidate(tmp_path, monkeypatch):
    """Live regression for the backend-forced measurement window: a
    search over a backend-carrying candidate must build, compile,
    measure and persist the winner's kernel choice (the forced context
    is single-use — entering it per phase used to crash the search)."""
    from paddle_tpu.tune import reset_cache, tune_gpt_step

    monkeypatch.setenv("PADDLE_TPU_TUNE_CACHE",
                       str(tmp_path / "tuned.json"))
    monkeypatch.setenv("PADDLE_TPU_TUNE", "search")
    reset_cache()
    try:
        rep = tune_gpt_step(
            seq_len=32, n_layer=1, d_model=32, n_head=2, vocab=61,
            batch=4, dtype="float32", steps=1, warmup=0, repeats=1,
            block_caps=(32,), policies=("none",), accums=(1,),
            backends=("xla_ref", "pallas_tpu"), max_measure=3,
            mode="search", force=True)
        assert rep["source"] == "search", rep
        measured = [m for m in rep["measured"]
                    if m.get("verdict") == "measured"]
        # every candidate's record carries the backend that ran
        assert {m.get("backend") for m in measured} == {"xla_ref",
                                                        "pallas_tpu"}
        assert rep["entry"]["config"].get("backend") in ("xla_ref",
                                                         "pallas_tpu")
    finally:
        reset_cache()


def test_truncate_survivors_keeps_every_backend():
    from paddle_tpu.tune.search import _truncate_survivors

    survivors = ([{"block_q": 64, "backend": "pallas_tpu", "roofline": 1.0}]
                 * 5 + [{"block_q": 64, "backend": "xla_ref"}])
    report = {}
    keep = _truncate_survivors(list(survivors), 3, report)
    assert any(c.get("backend") == "xla_ref" for c in keep)
    assert report["truncated_to"] == len(keep) == 4
    # no truncation -> untouched, no report key
    report2 = {}
    same = _truncate_survivors(list(survivors), 10, report2)
    assert len(same) == 6 and "truncated_to" not in report2
