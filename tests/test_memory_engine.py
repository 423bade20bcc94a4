"""Backward-pass memory engine tests (ISSUE 4 tentpole).

Pins the three coordinated pieces of the memory engine:

- **residual slimming**: the flash custom-VJP saves EXACTLY
  ``(q, k, v, o, lse)`` (``FLASH_BWD_RESIDUALS``) — nothing stacked
  beyond that contract;
- **backward-scan locality**: for every ``memory_optimize`` policy the
  traced training step keeps its flash ``pallas_call``s inside
  ``lax.scan`` bodies — no per-layer unrolled kernel calls, no pallas
  operand with a leading layer-count axis, and the optimized HLO is
  free of the round-5 flagship's failure shape ``[L, t, d_model]``
  (checked via ``analysis.audit_program`` +
  ``compiled.memory_analysis()``, CPU-safe);
- **policy="offload"**: marks selective segments plus the program
  offload flag, is loss AND grad BIT-EXACT vs ``selective`` (a pure
  memory-placement change), and obeys the ``PADDLE_TPU_OFFLOAD=0`` kill
  switch.

Plus the satellites: ``hbm_high_water_bytes``/``temp_bytes`` in
``exe.last_step_cost`` and the registry, and the
``Executor.compile_only`` preflight.
"""

import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.analysis import audit_program
from paddle_tpu.core.program import GRAD_SUFFIX
from paddle_tpu.models import transformer

# layer count must differ from batch (2), heads (2) AND b*h (4) so the
# leading-axis probes are unambiguous (pallas operands are [b*h, t, d])
N_LAYER = 5
T, D = 12, 32


def _build(policy, drop=0.0, n_layer=N_LAYER, seed=11):
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = seed
    with pt.program_guard(main, startup):
        outs = transformer.build(vocab_size=30, n_layer=n_layer, n_head=2,
                                 d_model=D, max_len=T, dropout_rate=drop,
                                 dtype="float32")
    if policy:
        pt.memory_optimize(main, policy=policy)
    return main, startup, outs["avg_cost"]


def _feed(seed=3):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, 30, (2, T)).astype(np.int64)
    return {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}


def _step_outputs(main, startup, loss, steps=2):
    """[loss, *param grads] per optimizer step, in a private scope."""
    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        known = {n for blk in main.blocks for n in blk.vars}
        gnames = [p.name + GRAD_SUFFIX for p in main.all_parameters()
                  if p.name + GRAD_SUFFIX in known]
        out = []
        for _ in range(steps):
            vals = exe.run(main, feed=_feed(), fetch_list=[loss] + gnames,
                           scope=scope)
            out.append([np.asarray(v) for v in vals])
        return out, exe
    finally:
        pt.core.scope._scope_stack.pop()


# -- offload policy ---------------------------------------------------------

def test_offload_policy_marks_program():
    """offload == selective segmentation + the program offload flag."""
    sel, _, _ = _build("selective")
    off, _, _ = _build("offload")
    assert off._remat_segments == sel._remat_segments
    assert off._offload is True
    assert sel._offload is False
    with pytest.raises(ValueError, match="offload"):
        pt.memory_optimize(_build(None)[0], policy="bogus")


def test_offload_bit_exact_vs_selective():
    """The acceptance bar: offload is a pure memory-PLACEMENT change —
    loss AND every parameter gradient BIT-EXACT vs selective across
    optimizer steps, XLA fusion on, in process."""
    sel, _ = _step_outputs(*_build("selective"))
    off, exe = _step_outputs(*_build("offload"))
    plan = exe.last_remat_plan
    assert plan and plan[0]["offload"] in ("save", "host")
    for s_step, o_step in zip(sel, off):
        for a, b in zip(s_step, o_step):
            np.testing.assert_array_equal(a, b)


def test_offload_bit_exact_with_dropout():
    """Dropout keys must be reproduced identically through the
    name-policy checkpoints (a wrong key shows at 1e-2, not ulp)."""
    sel, _ = _step_outputs(*_build("selective", drop=0.3))
    off, _ = _step_outputs(*_build("offload", drop=0.3))
    np.testing.assert_array_equal(sel[0][0], off[0][0])
    np.testing.assert_array_equal(sel[1][0], off[1][0])


def test_offload_kill_switch():
    """PADDLE_TPU_OFFLOAD=0 routes an offload program through the plain
    selective scan body (plan records offload "off"), bit-exact."""
    sel, _ = _step_outputs(*_build("selective"))
    try:
        os.environ["PADDLE_TPU_OFFLOAD"] = "0"
        off, exe = _step_outputs(*_build("offload"))
    finally:
        os.environ.pop("PADDLE_TPU_OFFLOAD", None)
    assert exe.last_remat_plan[0]["offload"] == "off"
    for a, b in zip(sel[0], off[0]):
        np.testing.assert_array_equal(a, b)


# -- backward-scan locality regression --------------------------------------

@pytest.mark.parametrize("policy",
                         ["selective", "compact", "full", "offload"])
def test_backward_scan_locality(policy):
    """For every policy: the full training step's flash kernel calls are
    scan-local (at most one un-grouped layer's worth outside — NOT O(L)
    unrolled), no pallas operand/result carries a leading layer-count
    axis, the optimized HLO contains no ``[L, t, d_model]`` buffer (the
    temp shape that overflowed the round-5 flagship), the scan engine engaged without
    fallback, and memory_analysis reports real figures."""
    main, startup, loss = _build(policy)
    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        rep = audit_program(main, _feed(), [loss], scope=scope,
                            layer_count=N_LAYER,
                            absent_shapes=[(N_LAYER, T, D)])
    finally:
        pt.core.scope._scope_stack.pop()
    assert rep["pallas_total"] > 0
    assert rep["pallas_outside_scan"] <= 3, rep["pallas_calls"]
    assert rep["pallas_total"] > rep["pallas_outside_scan"]
    assert not rep["layer_stacked_pallas"]
    assert all(n == 0 for n in rep["absent_shape_hits"].values()), rep[
        "absent_shape_hits"]
    plan = rep["scan_remat_plan"]
    assert plan and not any("fallback" in p for p in plan), plan
    assert rep["temp_bytes"] > 0
    assert rep["hbm_high_water_bytes"] > 0


def test_scan_fallback_records_reason_and_strict_raises():
    """A group the engine cannot classify falls back WITH the reason in
    the plan (no silent fallbacks);
    PADDLE_TPU_SCAN_REMAT=strict turns that into a hard error."""
    main, startup, loss = _build("selective")
    # poison the cached group list with a malformed group so the scan
    # classification throws while the barrier fallback still works
    key = (main._version,
           tuple(tuple(s) for s in main._remat_segments))
    bogus = {"start": 0, "period": 1, "count": 2,
             "ext_maps": [{}, {}], "out_maps": [{}, {}]}
    main._scan_group_cache = (key, [bogus])
    out, exe = _step_outputs(main, startup, loss, steps=1)
    assert np.isfinite(out[0][0]).all()
    fallbacks = [p for p in exe.last_remat_plan if "fallback" in p]
    assert fallbacks and fallbacks[0]["fallback"]

    main2, startup2, loss2 = _build("selective")
    key2 = (main2._version,
            tuple(tuple(s) for s in main2._remat_segments))
    main2._scan_group_cache = (key2, [dict(bogus)])
    try:
        os.environ["PADDLE_TPU_SCAN_REMAT"] = "strict"
        with pytest.raises(Exception, match="strict"):
            _step_outputs(main2, startup2, loss2, steps=1)
    finally:
        os.environ.pop("PADDLE_TPU_SCAN_REMAT", None)


# -- residual slimming ------------------------------------------------------

def test_flash_residual_contract():
    """The custom-VJP forward returns residuals of EXACTLY
    FLASH_BWD_RESIDUALS — (q, k, v, o, lse) with the narrow 2-D lse —
    so nothing extra stacks per layer under a scanned group."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_attention import (
        FLASH_BWD_RESIDUALS, _flash_core_fwd)

    assert FLASH_BWD_RESIDUALS == ("q", "k", "v", "o", "lse")
    bh, t, d = 4, 16, 8
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(bh, t, d)), jnp.float32)
               for _ in range(3))
    o, res = _flash_core_fwd(q, k, v, d ** -0.5, True, 8, 8, True, None)
    assert len(res) == len(FLASH_BWD_RESIDUALS)
    rq, rk, rv, ro, rlse = res
    assert rq is q and rk is k and rv is v  # inputs pass through, no copies
    assert ro.shape == o.shape
    assert rlse.shape == (bh, t)  # 2-D narrow layout, not lane-replicated


# -- telemetry satellites ---------------------------------------------------

def test_step_cost_memory_fields_and_gauges():
    """exe.last_step_cost carries hbm_high_water_bytes/temp_bytes from
    memory_analysis, mirrored into the registry gauges."""
    from paddle_tpu.observability.metrics import get_registry

    # the gauges keep the largest step the PROCESS compiled (set_max):
    # zeroed, they read this program's, whatever the worker ran before
    reg = get_registry()
    reg.reset(prefix="executor.")
    main, startup, loss = _build("selective")
    out, exe = _step_outputs(main, startup, loss, steps=1)
    sc = exe.last_step_cost
    assert isinstance(sc["temp_bytes"], int) and sc["temp_bytes"] > 0
    assert isinstance(sc["hbm_high_water_bytes"], int)
    assert sc["hbm_high_water_bytes"] >= sc["temp_bytes"]
    assert reg.value("executor.temp_bytes") > 0
    assert reg.value("executor.hbm_high_water_bytes") >= \
        reg.value("executor.temp_bytes")


def test_compile_only_primes_run_cache():
    """compile_only AOT-compiles into run()'s cache: it returns the cost
    dict (preflight fields included) and the following run() is a cache
    HIT — one compile total."""
    from paddle_tpu.observability.metrics import get_registry

    main, startup, loss = _build(None)
    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        reg = get_registry()
        c0 = reg.value("executor.compile_count")
        cost = exe.compile_only(main, feed=_feed(), fetch_list=[loss],
                                scope=scope)
        assert cost["hbm_high_water_bytes"] > 0
        assert reg.value("executor.compile_count") == c0 + 1
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
        assert reg.value("executor.compile_count") == c0 + 1  # cache hit
        assert exe.last_step_cost["cache_hit"] is True
    finally:
        pt.core.scope._scope_stack.pop()

