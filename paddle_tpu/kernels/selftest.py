"""``python -m paddle_tpu --kernels-selftest`` — the multi-backend
kernel registry's CI gate (tools/tier1.sh, docs/kernels.md).

What it proves on THIS host, accelerator or not:

1. registry resolution — every op class resolves under auto, the
   override precedence holds (explicit arg > per-op env > global env >
   auto), unknown backends raise, unavailable explicit backends raise
   with a reason, a global-env pin an op cannot serve degrades to auto;
2. oracle parity — every backend AVAILABLE here (plus the Mosaic
   kernels force-run in interpret mode, so the kernel logic itself is
   exercised even on a CPU-only host) matches the xla_ref oracle
   within the documented ``ORACLE_TOL`` bounds, f32 + bf16, causal +
   non-causal, d_head 64/128, grads through the custom-vjp — and is
   BIT-EXACT run-to-run within itself;
3. paged-attention parity — every runnable backend of the
   ``paged_attention`` op class (interpret-forced where unavailable)
   matches a dense gather+softmax reference within ``ORACLE_TOL``
   over ragged chains (fully-cached one-token prefill, a CoW fork,
   trash-block garbage) and is bit-exact run-to-run;
4. the xla_ref acceptance bar — ``PADDLE_TPU_KERNEL_BACKEND=xla_ref``
   runs the full GPT trainer path under EVERY memory_optimize policy
   with ZERO Pallas calls in the traced jaxpr and a finite loss;
5. the timed-run lint — a timed-run region compiled with interpret-mode
   kernels plants a ``jaxpr.kernel-backend`` error and the same region
   routed to xla_ref compiles clean.
"""

import os

import numpy as np


def _rel_err(a, ref):
    import jax.numpy as jnp

    a = jnp.asarray(a, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    scale = float(jnp.max(jnp.abs(ref))) or 1.0
    return float(jnp.max(jnp.abs(a - ref))) / scale


def _check_registry(failures):
    import jax

    from . import (KernelUnavailable, available_backends, forced_backend,
                   registered_op_classes, resolve_name)

    ops = registered_op_classes()
    print(f"registry: op classes {ops} on platform "
          f"{jax.default_backend()!r}")
    if sorted(ops) != ["flash_attention", "fused_ce", "grouped_matmul",
                       "paged_attention", "retention"]:
        failures.append(f"unexpected op classes: {ops}")
    for op in ops:
        auto = resolve_name(op)
        rows = available_backends(op)
        print(f"  {op}: auto -> {auto}; "
              + "; ".join(f"{b}={'ok' if ok else 'SKIP'}"
                          + (f" ({r})" if r and not ok else "")
                          for b, ok, r in rows))
    # precedence: explicit arg wins over env
    os.environ["PADDLE_TPU_KERNEL_BACKEND"] = "xla_ref"
    try:
        if resolve_name("flash_attention") != "xla_ref":
            failures.append("global env did not route flash to xla_ref")
        if resolve_name("flash_attention", "pallas_tpu") != "pallas_tpu":
            failures.append("explicit arg did not beat global env")
        os.environ["PADDLE_TPU_KERNEL_BACKEND_FLASH_ATTENTION"] = \
            "pallas_tpu"
        if resolve_name("flash_attention") != "pallas_tpu":
            failures.append("per-op env did not beat global env")
        if resolve_name("fused_ce") != "xla_ref":
            failures.append("per-op env leaked across op classes")
    finally:
        os.environ.pop("PADDLE_TPU_KERNEL_BACKEND", None)
        os.environ.pop("PADDLE_TPU_KERNEL_BACKEND_FLASH_ATTENTION", None)
    # unknown raises
    try:
        resolve_name("flash_attention", "cuda_graphs")
        failures.append("unknown backend did not raise")
    except ValueError:
        pass
    # explicitly requesting an unavailable backend raises with a reason
    unavailable = [b for b, ok, _ in
                   available_backends("flash_attention") if not ok]
    for b in unavailable:
        try:
            resolve_name("flash_attention", b)
            failures.append(f"unavailable backend {b} did not raise")
        except KernelUnavailable as e:
            if not e.reason:
                failures.append(f"unavailable backend {b} has no reason")
    # a global-env pin an op cannot serve degrades to auto (the Mosaic
    # paged kernel is unavailable off the TPU)
    os.environ["PADDLE_TPU_KERNEL_BACKEND"] = "pallas_tpu"
    try:
        name = resolve_name("paged_attention")
        want = ("pallas_tpu" if jax.default_backend() == "tpu"
                else "xla_ref")
        if name != want:
            failures.append(
                f"global-env fallback resolved paged_attention to {name}")
    finally:
        os.environ.pop("PADDLE_TPU_KERNEL_BACKEND", None)
    # the tuner's forced hook routes without env mutation
    with forced_backend("xla_ref"):
        if resolve_name("fused_ce") != "xla_ref":
            failures.append("forced_backend did not route fused_ce")
    print("registry precedence ok")


def _flash_impls():
    """(name, fn(q4, k4, v4, causal) -> o) for every backend whose
    kernel logic can run on this host — available ones as the registry
    would run them (off-TPU the Mosaic backend is available in
    interpret mode: the kernel logic is what runs either way)."""
    from . import available_backends, get_kernel

    avail = {b: ok for b, ok, _ in available_backends("flash_attention")}
    out = []
    for b, ok in avail.items():
        if b == "xla_ref":
            continue
        impl = get_kernel("flash_attention", b).impl
        # explicit 64-wide blocks: at the t=128 parity shapes the
        # default (1024-capped) blocks compile a degenerate
        # single-block kernel in which the cross-block online-softmax
        # carry — the thing under test — is dead code
        if ok:
            out.append((b, lambda q, k, v, c, i=impl: i.call(
                q, k, v, causal=c, block_q=64, block_k=64)))
    return out


def _check_oracle(failures):
    import jax
    import jax.numpy as jnp

    from . import get_kernel, oracle_tol

    oracle = get_kernel("flash_attention", "xla_ref").impl
    rng = np.random.default_rng(11)
    impls = _flash_impls()
    print(f"oracle parity (flash): backends "
          f"{[n for n, _ in impls]} vs xla_ref")
    for dt in (jnp.float32, jnp.bfloat16):
        dt_name = str(jnp.dtype(dt))
        for causal in (False, True):
            for d in (64, 128):
                b, t, h = 2, 128, 2
                q, k, v = (jnp.asarray(
                    rng.normal(size=(b, t, h, d)) * 0.5, dt)
                    for _ in range(3))
                ref = oracle.call(q, k, v, causal=causal)
                for name, fn in impls:
                    err = _rel_err(fn(q, k, v, causal), ref)
                    tol = oracle_tol("flash_attention", dt_name, "fwd")
                    if err > tol:
                        failures.append(
                            f"flash {name} {dt_name} causal={causal} "
                            f"d={d}: fwd err {err:.2e} > {tol}")
    # grads through the custom-vjp, f32 + bf16
    for dt in (jnp.float32, jnp.bfloat16):
        dt_name = str(jnp.dtype(dt))
        b, t, h, d = 1, 128, 2, 64
        q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)) * 0.5, dt)
                   for _ in range(3))
        wgt = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)

        def make_loss(fn):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v, True).astype(jnp.float32) * wgt)

        g_ref = jax.grad(make_loss(
            lambda q, k, v, c: oracle.call(q, k, v, causal=c)),
            (0, 1, 2))(q, k, v)
        for name, fn in impls:
            gs = jax.grad(make_loss(fn), (0, 1, 2))(q, k, v)
            tol = oracle_tol("flash_attention", dt_name, "grad")
            for which, a, r in zip("qkv", gs, g_ref):
                err = _rel_err(a, r)
                if err > tol:
                    failures.append(
                        f"flash {name} {dt_name} d{which}: grad err "
                        f"{err:.2e} > {tol}")
    print("flash parity ok")

    # fused CE: available backends vs oracle
    from . import available_backends

    ce_oracle = get_kernel("fused_ce", "xla_ref").impl
    ce_impls = []
    for bk, ok, _ in available_backends("fused_ce"):
        if bk == "xla_ref":
            continue
        impl = get_kernel("fused_ce", bk).impl
        # explicit small blocks: the default caps would compile a
        # single-vocab-tile kernel at the parity shape — the online
        # carry across vocab tiles must actually run
        blks = dict(block_n=64, block_v=128, block_v_fwd=128)
        if ok:
            ce_impls.append((bk, lambda x, w, y, i=impl: i.call(
                x, w, y, **blks)))
    for dt in (jnp.float32, jnp.bfloat16):
        dt_name = str(jnp.dtype(dt))
        n, dm, vocab = 128, 64, 512
        x = jnp.asarray(rng.normal(size=(n, dm)) * 0.3, dt)
        w = jnp.asarray(rng.normal(size=(dm, vocab)) * 0.05, dt)
        y = jnp.asarray(rng.integers(0, vocab, (n,)), jnp.int32)
        ref = ce_oracle.call(x, w, y)
        for name, fn in ce_impls:
            err = _rel_err(fn(x, w, y), ref)
            tol = oracle_tol("fused_ce", dt_name, "fwd")
            if err > tol:
                failures.append(f"ce {name} {dt_name}: fwd err "
                                f"{err:.2e} > {tol}")
        gvec = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
        g_ref = jax.grad(lambda x, w: jnp.sum(
            ce_oracle.call(x, w, y) * gvec), (0, 1))(x, w)
        for name, fn in ce_impls:
            gs = jax.grad(lambda x, w, f=fn: jnp.sum(
                f(x, w, y) * gvec), (0, 1))(x, w)
            tol = oracle_tol("fused_ce", dt_name, "grad")
            for which, a, r in zip(("x", "w"), gs, g_ref):
                err = _rel_err(a, r)
                if err > tol:
                    failures.append(f"ce {name} {dt_name} d{which}: "
                                    f"grad err {err:.2e} > {tol}")
    print("ce parity ok")

    # run-to-run bit-exactness WITHIN a backend: one compiled fn, same
    # inputs, twice -> identical bits
    import jax as _jax

    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, 2, 64)) * 0.5,
                           jnp.float32) for _ in range(3))
    for name, fn in impls + [("xla_ref", lambda q, k, v, c:
                              oracle.call(q, k, v, causal=c))]:
        jf = _jax.jit(lambda q, k, v, f=fn: f(q, k, v, True))
        a, b2 = jf(q, k, v), jf(q, k, v)
        if not bool(jnp.array_equal(a, b2)):
            failures.append(f"flash {name}: not bit-exact run-to-run")
    print("run-to-run bit-exactness ok")


def _paged_impls():
    """(name, fn(q, pk, pv, table, pos) -> ctx) for every backend whose
    paged-attention logic can run on this host — available ones as the
    registry would run them, plus the Mosaic kernel force-run in
    interpret mode (the blocked online-softmax logic is the thing under
    test, accelerator or not)."""
    from . import available_backends, get_kernel

    out = []
    for b, ok, _ in available_backends("paged_attention"):
        impl = get_kernel("paged_attention", b).impl
        if b == "xla_ref":
            # the oracle itself re-runs at several block_steps (None =
            # the W-aware default, including the one-step no-scan
            # path): the cross-block carry must not depend on the
            # iteration grouping
            for bs in (None, 1, 3):
                out.append((f"xla_ref(bs={bs or 'auto'})",
                            lambda q, k, v, t, p, i=impl, s=bs: i.call(
                                q, k, v, t, p, block_step=s)))
        elif ok:
            out.append((b, lambda q, k, v, t, p, i=impl: i.call(
                q, k, v, t, p)))
        else:
            out.append((b + "(interpret)",
                        lambda q, k, v, t, p, i=impl: i.call(
                            q, k, v, t, p, interpret=True)))
    return out


def _paged_dense_ref(q, pool_k, pool_v, table, pos):
    """The independent spelling the kernels must match: materialize the
    gathered [S, T, h, dh] view (exactly what the paged kernel exists
    to avoid), dense-mask past ``pos``, one softmax — all f32."""
    import jax.numpy as jnp

    from .xla_ref import NEG_INF

    S, NB = table.shape
    B = pool_k.shape[1]
    h, dh = q.shape[-2:]
    kg = pool_k[table].reshape(S, NB * B, h, dh).astype(jnp.float32)
    vg = pool_v[table].reshape(S, NB * B, h, dh).astype(jnp.float32)
    s = jnp.einsum("swhd,sthd->swht", q.astype(jnp.float32), kg)
    s = s / jnp.sqrt(jnp.float32(dh))
    tok = jnp.arange(NB * B, dtype=jnp.int32)
    keep = tok[None, None, None, :] <= pos[:, :, None, None]
    s = jnp.where(keep, s, NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("swht,sthd->swhd", p, vg).astype(q.dtype)


def _check_paged_oracle(failures):
    import jax
    import jax.numpy as jnp

    from . import oracle_tol

    rng = np.random.default_rng(23)
    impls = _paged_impls()
    print(f"oracle parity (paged attention): backends "
          f"{[n for n, _ in impls]} vs dense gather+softmax")
    S, NB, B, h, dh = 4, 3, 4, 2, 16
    num_blocks = 1 + S * NB
    # ragged chains: a full slot, a mid-chain decode, a fully-cached
    # one-token prefill (pos = plen-1 with plen < capacity), and a CoW
    # fork — slot 3 shares slot 0's first block id, diverges after
    table = np.arange(1, 1 + S * NB, dtype=np.int32).reshape(S, NB)
    table[3, 0] = table[0, 0]
    table[2, 2] = 0          # unused tail -> trash block (masked)
    pos_cases = (
        ("decode", 1, np.array([[NB * B - 1], [5], [7], [9]], np.int32)),
        ("cached-prefill", 1, np.array([[3], [0], [6], [4]], np.int32)),
        ("verify-window", 3,
         np.array([[4, 5, 6], [1, 2, 3], [5, 6, 7], [8, 9, 10]],
                  np.int32)),
    )
    for dt in (jnp.float32, jnp.bfloat16):
        dt_name = str(jnp.dtype(dt))
        pool_k = jnp.asarray(
            rng.normal(size=(num_blocks, B, h, dh)) * 0.5, dt)
        pool_v = jnp.asarray(
            rng.normal(size=(num_blocks, B, h, dh)) * 0.5, dt)
        # trash block 0 holds garbage, as in the live engine: masking,
        # not zeroing, must keep it out of every context
        pool_k = pool_k.at[0].set(1e3)
        pool_v = pool_v.at[0].set(1e3)
        tol = oracle_tol("paged_attention", dt_name, "fwd")
        for case, W, pos in pos_cases:
            q = jnp.asarray(rng.normal(size=(S, W, h, dh)) * 0.5, dt)
            tbl = jnp.asarray(table)
            p = jnp.asarray(pos)
            ref = _paged_dense_ref(q, pool_k, pool_v, tbl, p)
            for name, fn in impls:
                err = _rel_err(fn(q, pool_k, pool_v, tbl, p), ref)
                if err > tol:
                    failures.append(
                        f"paged {name} {dt_name} {case}: fwd err "
                        f"{err:.2e} > {tol}")
    print("paged parity ok (incl. trash-block masking, CoW fork)")

    # run-to-run bit-exactness WITHIN a backend
    q = jnp.asarray(rng.normal(size=(S, 1, h, dh)) * 0.5, jnp.float32)
    pool_k = jnp.asarray(
        rng.normal(size=(num_blocks, B, h, dh)), jnp.float32)
    pool_v = jnp.asarray(
        rng.normal(size=(num_blocks, B, h, dh)), jnp.float32)
    tbl = jnp.asarray(table)
    p = jnp.asarray([[5], [7], [9], [11]], np.int32)
    for name, fn in impls:
        jf = jax.jit(fn)
        a, b2 = jf(q, pool_k, pool_v, tbl, p), jf(q, pool_k, pool_v,
                                                  tbl, p)
        if not bool(jnp.array_equal(a, b2)):
            failures.append(f"paged {name}: not bit-exact run-to-run")
    print("paged run-to-run bit-exactness ok")


def _check_xla_ref_trainer(failures):
    import jax

    import paddle_tpu as pt
    from paddle_tpu.analysis.jaxpr_tools import walk_report
    from paddle_tpu.models import transformer

    os.environ["PADDLE_TPU_KERNEL_BACKEND"] = "xla_ref"
    try:
        for policy in (None, "selective", "offload", "compact", "full"):
            pt.core.unique_name.reset()
            main, startup = pt.Program(), pt.Program()
            main.random_seed = 7
            with pt.program_guard(main, startup):
                outs = transformer.build(
                    vocab_size=128, n_layer=3, n_head=2, d_model=32,
                    max_len=64, dropout_rate=0.0, dtype="float32",
                    fused_head=True)
                if policy:
                    pt.memory_optimize(main, policy=policy)
            scope = pt.core.scope.Scope()
            pt.core.scope._scope_stack.append(scope)
            try:
                exe = pt.Executor()
                exe.run(startup, scope=scope)
                rng = np.random.default_rng(3)
                toks = rng.integers(0, 128, (2, 64)).astype(np.int64)
                feed = {"tokens": toks, "labels": np.roll(toks, -1, 1)}
                loss = exe.run(main, feed=feed,
                               fetch_list=[outs["avg_cost"]],
                               scope=scope)[0]
                if not np.isfinite(np.asarray(loss)).all():
                    failures.append(
                        f"xla_ref trainer: non-finite loss at "
                        f"policy={policy}")
                kb = (exe.last_step_cost or {}).get(
                    "kernel_backends") or {}
                if kb.get("flash_attention") != "xla_ref" or \
                        kb.get("fused_ce") != "xla_ref":
                    failures.append(
                        f"xla_ref trainer: backends {kb} at "
                        f"policy={policy}")
                state_names = tuple(sorted(
                    v.name for v in main.persistable_vars()
                    if scope.find_var(v.name) is not None))
                step, _ = exe.lower(
                    main, ["labels", "tokens"],
                    [outs["avg_cost"].name], state_names)
                state = {n: scope.get(n) for n in state_names}
                state[pt.core.scope.RNG_VAR] = scope.get(
                    pt.core.scope.RNG_VAR)
                rep = walk_report(jax.make_jaxpr(step)(state, toks,
                                                       toks))
                if rep["pallas_total"] != 0:
                    failures.append(
                        f"xla_ref trainer: {rep['pallas_total']} pallas "
                        f"calls in jaxpr at policy={policy}")
                print(f"xla_ref trainer policy={policy}: loss "
                      f"{float(np.asarray(loss).ravel()[0]):.4f}, "
                      f"pallas calls 0")
            finally:
                pt.core.scope._scope_stack.pop()
    finally:
        os.environ.pop("PADDLE_TPU_KERNEL_BACKEND", None)


def _check_timed_run_lint(failures):
    import paddle_tpu as pt
    from paddle_tpu.models import transformer

    from . import timed_run

    def compile_step(backend_env):
        pt.core.unique_name.reset()
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            outs = transformer.build(
                vocab_size=128, n_layer=2, n_head=2, d_model=32,
                max_len=16, dropout_rate=0.0, dtype="float32",
                fused_head=True)
        scope = pt.core.scope.Scope()
        pt.core.scope._scope_stack.append(scope)
        try:
            if backend_env:
                os.environ["PADDLE_TPU_KERNEL_BACKEND"] = backend_env
            exe = pt.Executor()
            with timed_run():
                exe.run(startup, scope=scope)
                toks = np.zeros((2, 16), np.int64)
                exe.run(main, feed={"tokens": toks, "labels": toks},
                        fetch_list=[outs["avg_cost"]], scope=scope)
            return exe.last_step_cost or {}
        finally:
            os.environ.pop("PADDLE_TPU_KERNEL_BACKEND", None)
            pt.core.scope._scope_stack.pop()

    import jax

    if jax.default_backend() == "tpu":
        print("timed-run lint: on TPU, interpret planting n/a — skipped")
        return
    planted = compile_step(None)  # auto on CPU = interpret kernels
    if not planted.get("interpret_in_timed_run"):
        failures.append(
            f"timed-run lint did not fire on interpret kernels "
            f"(lint_checks={planted.get('lint_checks')})")
    else:
        print("timed-run lint: planted interpret-mode kernels detected")
    clean = compile_step("xla_ref")
    if clean.get("interpret_in_timed_run"):
        failures.append("timed-run lint fired on an xla_ref-routed run")
    else:
        print("timed-run lint: xla_ref-routed region compiles clean")


def run_selftest():
    failures = []
    for check in (_check_registry, _check_oracle, _check_paged_oracle,
                  _check_xla_ref_trainer, _check_timed_run_lint):
        try:
            check(failures)
        except Exception as e:  # noqa: BLE001 — report, don't crash CI
            import traceback

            traceback.print_exc()
            failures.append(f"{check.__name__}: {type(e).__name__}: {e}")
    for f in failures:
        print(f"FAILURE: {f}")
    print("kernels selftest " + ("FAILED" if failures else "PASSED"))
    return 1 if failures else 0
