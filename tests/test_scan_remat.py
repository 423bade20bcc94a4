"""Scan-based remat engine tests (ISSUE 3 tentpole).

The Executor runs structurally repeated remat segments (transformer
layers) as ONE ``lax.scan`` with weights stacked on the scan axis and
``jax.checkpoint`` inside the body — the spelling whose backward has
O(1)-per-layer remat temps (the t=16k capacity path).  These tests pin:

- all three ``memory_optimize`` policies x accum {1, 2} COMPILE AND RUN
  on a small transformer under JAX_PLATFORMS=cpu;
- the LOSS agrees with the unrematted step's to float32's last digit in
  every configuration (``LOSS_RTOL``; forward math unchanged) and to the
  bit with dropout on (the keys reproduced through the scan);
- GRADIENTS are bit-exact vs the unrematted step for the full/compact
  policies when XLA fusion is disabled (subprocess), and within a few
  f32 ulps otherwise — XLA fuses the checkpoint-island boundaries
  differently from the flat graph, which reassociates a handful of
  elementwise chains (measured <= ~1e-7 absolute; a real remat bug —
  wrong mask, wrong key, wrong carry — shows up at 1e-2+);
- the scan engine is numerically invisible: scanned execution is
  bit-identical to the per-segment barrier execution of the same policy;
- the structural matcher (core/ir.py) groups what it should.
"""

import functools
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.ir import (
    detect_repeated_run,
    find_uniform_groups,
    match_op_run,
)
from paddle_tpu.core.program import GRAD_SUFFIX
from paddle_tpu.models import transformer

# one-or-two-ulp bound for f32 grads across XLA fusion boundaries (see
# module docstring); NOT a model-accuracy tolerance
ULP_ATOL = 5e-7
ULP_RTOL = 5e-6
# the loss of a rematerialised step against the unrematted one: the scan
# body and the unrolled layers are two programs, and whether XLA:CPU
# gives them the same bits is its code generator's business (it does
# with LLVM's optimiser on; with it off, as the tests compile, ``full``
# and ``compact`` differ from the baseline in the loss's last float32
# digit: 4.050076 against 4.050077, 2.4e-7 relative).  A policy
# that changed the arithmetic (a layer left out, a dropout key replayed
# wrong) moves the loss in its second digit.
LOSS_RTOL = 1e-6


def _build(policy, accum=1, drop=0.0, n_layer=2, seed=11):
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = seed
    with pt.program_guard(main, startup):
        outs = transformer.build(vocab_size=30, n_layer=n_layer, n_head=2,
                                 d_model=32, max_len=12, dropout_rate=drop,
                                 dtype="float32")
    if accum > 1:
        pt.gradient_accumulation(main, accum)
    if policy:
        pt.memory_optimize(main, policy=policy)
    return main, startup, outs["avg_cost"]


def _feed(seed=3):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, 30, (4, 12)).astype(np.int64)
    return {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}


def _step_grads(main, startup, loss, steps=1):
    """Losses over ``steps`` optimizer steps plus the LAST step's param
    gradients, in a private scope."""
    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        known = {n for blk in main.blocks for n in blk.vars}
        gnames = [p.name + GRAD_SUFFIX for p in main.all_parameters()
                  if p.name + GRAD_SUFFIX in known]
        losses, grads = [], {}
        for _ in range(steps):
            outs = exe.run(main, feed=_feed(),
                           fetch_list=[loss] + gnames, scope=scope)
            losses.append(np.asarray(outs[0]))
            grads = dict(zip(gnames, [np.asarray(o) for o in outs[1:]]))
        return losses, grads, exe
    finally:
        pt.core.scope._scope_stack.pop()


@functools.lru_cache(maxsize=None)
def _unrematted(accum):
    """Two steps of the unrematted model: what every policy at this
    ``accum`` is compared with, trained once."""
    return _step_grads(*_build(None, accum), steps=2)[:2]


@pytest.mark.parametrize("accum", [1, 2])
@pytest.mark.parametrize("policy", ["full", "selective", "compact"])
def test_remat_policy_compiles_and_loss_bit_exact(policy, accum):
    """Every policy x accum compiles, runs, keeps the loss to float32's
    last digit (``LOSS_RTOL``) vs the unrematted step across optimizer
    steps, and keeps gradients within a few f32 ulps (fusion
    reassociation only)."""
    base_losses, base_grads = _unrematted(accum)
    opt_losses, opt_grads, exe = _step_grads(*_build(policy, accum), steps=2)
    for b, o in zip(base_losses, opt_losses):
        np.testing.assert_allclose(o, b, rtol=LOSS_RTOL, atol=0)
    assert set(base_grads) == set(opt_grads)
    for n in base_grads:
        np.testing.assert_allclose(opt_grads[n], base_grads[n],
                                   atol=ULP_ATOL, rtol=ULP_RTOL,
                                   err_msg=n)
    if policy in ("full", "selective"):
        # the 2-layer model's repeated blocks must actually hit the scan
        # engine (compact needs >= 3 layers for 2 full periods; covered
        # by test_scan_groups_selective_and_compact)
        assert exe.last_remat_plan, "scan-remat engine did not engage"
        assert exe.last_remat_plan[0]["count"] == 2


def test_remat_dropout_keys_reproduced_through_scan():
    """With dropout ON, the scanned layers must derive the SAME per-layer
    dropout keys as the unrolled trace — bit-exact loss is the proof (a
    wrong mask moves the loss at 1e-2, not 1e-7)."""
    base_losses, _, _ = _step_grads(*_build(None, drop=0.3), steps=2)
    for policy in ("full", "selective"):
        opt_losses, _, exe = _step_grads(*_build(policy, drop=0.3), steps=2)
        assert exe.last_remat_plan
        for b, o in zip(base_losses, opt_losses):
            np.testing.assert_array_equal(b, o)


def test_scan_engine_bit_identical_to_barrier_fallback():
    """The scan engine must be numerically INVISIBLE: scanned execution
    bit-identical (loss and grads) to the barrier per-segment execution
    of the same policy."""
    try:
        os.environ["PADDLE_TPU_SCAN_REMAT"] = "1"
        l1, g1, exe = _step_grads(*_build("full"))
        assert exe.last_remat_plan
        os.environ["PADDLE_TPU_SCAN_REMAT"] = "0"
        l0, g0, exe = _step_grads(*_build("full"))
        assert not exe.last_remat_plan
    finally:
        os.environ.pop("PADDLE_TPU_SCAN_REMAT", None)
    np.testing.assert_array_equal(l1[0], l0[0])
    for n in g1:
        np.testing.assert_array_equal(g1[n], g0[n], err_msg=n)


_NO_FUSION_PROBE = textwrap.dedent("""
    import jax
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.core.program import GRAD_SUFFIX
    from paddle_tpu.models import transformer

    # as tests/conftest.py: the claim is about the two graphs, not about
    # what LLVM's optimiser makes of each
    jax.config.update("jax_disable_most_optimizations", True)

    def build(policy, accum):
        pt.core.unique_name.reset()
        main, startup = pt.Program(), pt.Program()
        main.random_seed = 11
        with pt.program_guard(main, startup):
            outs = transformer.build(vocab_size=30, n_layer=2, n_head=2,
                                     d_model=32, max_len=12,
                                     dropout_rate=0.0, dtype="float32")
        if accum > 1:
            pt.gradient_accumulation(main, accum)
        if policy:
            pt.memory_optimize(main, policy=policy)
        return main, startup, outs["avg_cost"]

    rng = np.random.default_rng(3)
    toks = rng.integers(0, 30, (4, 12)).astype(np.int64)
    feed = {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}

    def grads(main, startup, loss):
        scope = pt.Scope()
        pt.core.scope._scope_stack.append(scope)
        try:
            exe = pt.Executor()
            exe.run(startup, scope=scope)
            known = {n for blk in main.blocks for n in blk.vars}
            gnames = [p.name + GRAD_SUFFIX for p in main.all_parameters()
                      if p.name + GRAD_SUFFIX in known]
            outs = exe.run(main, feed=feed, fetch_list=[loss] + gnames,
                           scope=scope)
            return dict(zip(["loss"] + gnames,
                            [np.asarray(o) for o in outs]))
        finally:
            pt.core.scope._scope_stack.pop()

    for accum in (1, 2):
        base = grads(*build(None, accum))
        for policy in ("full", "compact"):
            opt = grads(*build(policy, accum))
            for n in base:
                np.testing.assert_array_equal(
                    base[n], opt[n],
                    err_msg=f"{policy} accum={accum} {n}")
    print("EXACT_OK")
""")


def test_remat_loss_and_grads_bit_exact_without_fusion():
    """The acceptance-criterion exactness run: with XLA's fusion pass
    disabled (so the only difference between the two graphs is the remat
    structure itself), full and compact remat x accum {1, 2} produce
    BIT-EXACT loss AND gradients vs the unrematted step.  Subprocess
    because XLA_FLAGS is read once per process.  (selective's finer
    checkpoint islands reassociate cotangent sums in the HLO itself —
    its ulp-bound is pinned in-process above.)"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_disable_hlo_passes=fusion,cpu-fusion")
    env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))) + os.pathsep + env.get("PYTHONPATH", ""))
    res = subprocess.run([sys.executable, "-c", _NO_FUSION_PROBE],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "EXACT_OK" in res.stdout


def test_full_policy_layer_aligned_segments():
    """memory_optimize(policy='full') cuts at the repeated-structure
    boundaries (one transformer block per segment), tiling the forward
    prefix."""
    main, _, _ = _build("full", n_layer=3)
    segs = main._remat_segments
    bw = main.global_block().backward_index
    assert segs[0][0] == 0 and segs[-1][1] == bw
    for (a, b, _), (c, d, _) in zip(segs, segs[1:]):
        assert b == c
    sizes = [t - s for s, t, w in segs if w]
    # three equal-size block segments among the wrapped ones
    assert sizes.count(max(set(sizes), key=sizes.count)) >= 3


def test_detect_repeated_run_finds_blocks():
    main, _, _ = _build(None, n_layer=3)
    bw = main.global_block().backward_index
    rep = detect_repeated_run(main, 0, bw)
    assert rep is not None
    s0, p, count = rep
    assert count == 3


def test_match_op_run_rejects_shape_mismatch():
    """Structural matching must reject runs whose paired external inputs
    have different static shapes (stacking needs uniform operands)."""
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        from paddle_tpu import layers

        x = layers.data("x", shape=[16])
        h1 = layers.fc(input=x, size=32, act="relu")    # W [16, 32]
        h2 = layers.fc(input=h1, size=32, act="relu")   # W [32, 32]
        h3 = layers.fc(input=h2, size=32, act="relu")   # W [32, 32]
        loss = layers.mean(h3)
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    ops = main.global_block().ops
    # fc lowers to (mul, elementwise_add, relu)
    assert match_op_run(main, ops[0:3], ops[3:6]) is None  # 16x32 vs 32x32
    assert match_op_run(main, ops[3:6], ops[6:9]) is not None


def test_scan_groups_selective_and_compact():
    """find_uniform_groups recovers multi-segment periods: selective's
    per-layer [wrapped cheap-run / unwrapped kernel] pattern and
    compact's [unwrapped kernel / wrapped everything-else] pattern."""
    for policy, n_layer in (("selective", 3), ("compact", 3)):
        main, _, _ = _build(policy, n_layer=n_layer)
        groups = find_uniform_groups(main, main._remat_segments)
        assert groups, policy
        best = max(groups, key=lambda g: g["count"])
        assert best["count"] >= 2, (policy, groups)


def test_scan_remat_env_kill_switch():
    """PADDLE_TPU_SCAN_REMAT=0 must route every segment through the
    barrier fallback and still train (the loss to float32's last digit
    vs baseline: ``LOSS_RTOL``)."""
    base_losses, _, _ = _step_grads(*_build(None))
    try:
        os.environ["PADDLE_TPU_SCAN_REMAT"] = "0"
        losses, _, exe = _step_grads(*_build("full"))
        assert not exe.last_remat_plan
    finally:
        os.environ.pop("PADDLE_TPU_SCAN_REMAT", None)
    np.testing.assert_allclose(losses[0], base_losses[0], rtol=LOSS_RTOL,
                               atol=0)


def test_scan_remat_composes_with_run_steps():
    """The scanned remat group nests inside run_steps' outer lax.scan
    (scan-in-scan) and matches step-by-step run() exactly."""
    main, startup, loss = _build("full")
    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        feed = _feed()
        stacked = {n: np.stack([v, v]) for n, v in feed.items()}
        (fetched,) = exe.run_steps(main, feed=stacked, fetch_list=[loss],
                                   scope=scope)
    finally:
        pt.core.scope._scope_stack.pop()

    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe2 = pt.Executor()
        exe2.run(startup, scope=scope)
        seq = [np.asarray(exe2.run(main, feed=_feed(), fetch_list=[loss],
                                   scope=scope)[0]) for _ in range(2)]
    finally:
        pt.core.scope._scope_stack.pop()
    np.testing.assert_array_equal(np.asarray(fetched).ravel(),
                                  np.asarray(seq).ravel())


# -- the reading product (ISSUE 54) -----------------------------------------

def _product_and_grads(reading, dtype, scanned):
    """Value and both gradients of two products in a row through ``mul``,
    reading or plain, under ``lax.scan`` or unrolled, on rows ``[2, 6, .]``
    (so the reading form is the one taken over the rows as they stand)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.math_ops import mul

    rng = np.random.default_rng(54)
    x = jnp.asarray(rng.normal(size=(2, 6, 16)), dtype)
    ws = jnp.asarray(rng.normal(size=(2, 16, 16)) * 0.3, dtype)

    def product(x, w):
        return mul(x, w, x_num_col_dims=2, _reads_saved=reading)["Out"]

    def net(x, ws):
        if scanned:
            return jax.lax.scan(lambda c, w: (product(c, w), None), x, ws)[0]
        for k in range(ws.shape[0]):
            x = product(x, ws[k])
        return x

    def loss(x, ws):
        return (net(x, ws).astype(jnp.float32) ** 2).sum()

    out = jax.jit(net)(x, ws)
    grads = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, ws)
    return [np.asarray(v.astype(jnp.float32)) for v in (out,) + grads]


@pytest.mark.parametrize("scanned", [True, False],
                         ids=["scanned", "unrolled"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_reading_product_bit_equal_to_mul(dtype, scanned):
    """The barrier is the identity on values and the backward is the
    transpose of the same product on the operands as they came."""
    plain = _product_and_grads(False, dtype, scanned)
    reading = _product_and_grads(True, dtype, scanned)
    for name, p, r in zip(("out", "dx", "dw"), plain, reading):
        np.testing.assert_array_equal(p, r, err_msg=name)


@pytest.mark.parametrize("policy,want", [("selective", 2), (None, 0)],
                         ids=["selective", "none"])
def test_products_reading_saved_counter(policy, want):
    """A scanned GPT body under `selective` holds two products whose
    ``X`` a checkpointed sub-segment made: ``ffn1`` after LayerNorm 2
    and ``ffn2`` after the bias add and the GELU (q, k, v read the
    CARRY, which the previous iteration made).  Both layers share the
    one body; with no remat segments no product reads."""
    from paddle_tpu.observability import get_registry

    counter = get_registry().counter("executor.products_reading_saved")
    before = counter.value
    _losses, _grads, exe = _step_grads(*_build(policy))
    assert counter.value - before == want
    if policy:
        assert exe.last_remat_plan[0]["count"] == 2


# -- the product over the rows as they stand (ISSUE 60) ---------------------

def _mlp_2d(policy):
    """Four ``fc`` + LayerNorm layers on 2-D rows: a scanned body whose
    products have nothing to fold."""
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        h = pt.layers.data(name="x", shape=[16], dtype="float32")
        y = pt.layers.data(name="y", shape=[1], dtype="float32")
        for _ in range(4):
            h = pt.layers.layer_norm(pt.layers.fc(h, size=16, act="relu"))
        cost = pt.layers.mean(
            pt.layers.square_error_cost(pt.layers.fc(h, size=1), y))
        pt.optimizer.SGD(learning_rate=0.1).minimize(cost)
    pt.memory_optimize(main, policy=policy)
    return main, startup, cost


def _run_mlp_2d(policy):
    main, startup, cost = _mlp_2d(policy)
    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        rng = np.random.default_rng(60)
        exe.run(main, feed={"x": rng.normal(size=(8, 16)).astype("float32"),
                            "y": rng.normal(size=(8, 1)).astype("float32")},
                fetch_list=[cost], scope=scope)
        return exe
    finally:
        pt.core.scope._scope_stack.pop()


@pytest.mark.parametrize("run,over_rows,reading", [
    (lambda: _step_grads(*_build("selective"))[2], 6, 2),
    (lambda: _step_grads(*_build("full"))[2], 6, 0),
    (lambda: _run_mlp_2d("selective"), 0, 0),
], ids=["gpt-selective", "gpt-full", "fc-2d"])
def test_products_over_rows_counter(run, over_rows, reading):
    """A scanned GPT body holds six products on rows ``[b, t, .]`` (q,
    k, v, out, ``ffn1``, ``ffn2``), every one lowered with no
    flattening wherever its segment sits, wrapped (``full``) or not;
    two of them read under `selective`.  Both layers share the one
    body.  A body of 2-D ``fc``s is scanned too and keeps the flat path:
    the rule is the shape's."""
    from paddle_tpu.observability import get_registry

    counters = [get_registry().counter("executor.products_" + name)
                for name in ("over_rows", "reading_saved")]
    before = [c.value for c in counters]
    exe = run()
    assert [c.value - b for c, b in zip(counters, before)] == [over_rows,
                                                               reading]
    (group,) = exe.last_remat_plan
    assert len(group["over_rows"]) == over_rows
    assert set(group["reading"]) <= set(group["over_rows"])
