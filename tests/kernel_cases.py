"""What the kernel test files share (``tests/test_paged_*.py``,
``tests/test_kernel_*.py``): the cases' pools and tables, the dense
truths they are held to and the backends by name.  Not a test module."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import available_backends, get_kernel


def rel_err(a, ref):
    a = jnp.asarray(a, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    scale = float(jnp.max(jnp.abs(ref))) or 1.0
    return float(jnp.max(jnp.abs(a - ref))) / scale


def impl_or_skip(op, backend):
    rows = {b: (ok, reason) for b, ok, reason in available_backends(op)}
    if backend not in rows:
        pytest.skip(f"{backend} not registered for {op}")
    ok, reason = rows[backend]
    if not ok:
        pytest.skip(f"{backend} unavailable: {reason}")
    return get_kernel(op, backend).impl


# float32 with 2 heads takes the kernel's loop over the chain, bfloat16
# with 6 heads the grid Mosaic needs where it cannot slice the pool
LIVE_FORMS = [("float32", 2), ("bfloat16", 6)]


def windowed_truth(q, pk, pv, table, pos, group, window, scale,
                   weights=lambda a: a):
    """The dense truth, in numpy, from the values the backends see: row
    ``r`` of slot ``s`` attends keys ``max(0, pos - window + 1) .. pos``
    of its chain, query head ``i`` the K/V head ``i // group``; a row
    with ``pos < 0`` stays zeros.  ``weights`` is what becomes of the
    unnormalized weights before they meet the values (a rounding, for a
    test that has to tell one from none)."""
    S, NB = table.shape
    dh = q.shape[-1]
    k32 = np.asarray(pk, np.float32)[table].reshape(S, -1, pk.shape[2], dh)
    v32 = np.asarray(pv, np.float32)[table].reshape(S, -1, pv.shape[2], dh)
    q32 = np.asarray(q, np.float32)
    want = np.zeros(q.shape, np.float32)
    for s_ in range(S):
        for r in range(q.shape[1]):
            at = int(pos[s_, r])
            if at < 0:
                continue
            lo = 0 if window is None else max(0, at - window + 1)
            for i in range(q.shape[2]):
                sc = k32[s_, lo:at + 1, i // group] @ q32[s_, r, i] * scale
                a = np.exp(sc - sc.max())
                want[s_, r, i] = (weights(a) / a.sum()) @ v32[
                    s_, lo:at + 1, i // group]
    return want


def paged_backends():
    from paddle_tpu.kernels.paged_attention import (
        paged_attention_pallas, paged_attention_ref)

    return {"xla_ref": paged_attention_ref,
            "xla_ref_block_step_1": lambda *a, **k: paged_attention_ref(
                *a, block_step=1, **k),
            "pallas_tpu_interpret": lambda *a, **k: paged_attention_pallas(
                *a, interpret=True, **k)}


# the shared fold (PR 33): a live block is folded ONCE for all the rows
# of the window (a K/V group's rows included): the rows side by side on
# the lanes of one array, one softmax update for all of them.  Three slots over
# NB = 16 blocks of B = 8 tokens (T = 128): slot 0's window ends at
# position 70 with its rows at DIFFERENT positions (a verify window: no
# two rows share a mask), slot 1 has a row with ``pos < 0`` beside live
# ones (dead where W = 1), slot 2's window ends at the chain's last
# position.
def shared_fold_case(w, group, window, dtype, hk, seed=13):
    rng = np.random.default_rng(seed)
    S, NB, B, dh = 3, 16, 8, 16
    dt = jnp.dtype(dtype)
    shape = (1 + S * NB, B, hk, dh)
    pool_k = np.asarray(rng.normal(size=shape) * 0.5, np.float32)
    pool_v = np.asarray(rng.normal(size=shape) * 0.5, np.float32)
    pool_k[0] = pool_v[0] = 1e3                      # the trash block
    table = 1 + np.arange(S * NB, dtype=np.int32).reshape(S, NB)
    last = np.array([70, 37, NB * B - 1])
    pos = last[:, None] - (w - 1) + np.arange(w)[None, :]
    pos[1, 0] = -1
    table[0, 70 // B + 1:] = 0
    table[1, 37 // B + 1:] = 0
    if w == 1:
        table[1] = 0
    q = jnp.asarray(rng.normal(size=(S, w, hk * group, dh)) * 0.5, dt)
    pk, pv = jnp.asarray(pool_k, dt), jnp.asarray(pool_v, dt)
    want = windowed_truth(q, pk, pv, table, pos, group, window,
                          dh ** -0.5)             # the kernels' default
    return (q, pk, pv, jnp.asarray(table), jnp.asarray(pos, jnp.int32),
            dict(group=group, window=window), jnp.asarray(want), pos >= 0)


def primitive_counts(jaxpr, counts=None):
    """Primitive name -> occurrences, sub-jaxprs (scan and while bodies,
    pjit) included."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    primitive_counts(sub, counts)
    return counts
