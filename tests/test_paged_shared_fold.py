"""The paged kernel's shared fold (``kernels/paged_attention.py``, PR 33):
a live block is folded ONCE for all the rows of a window, a K/V group's
rows included, against the block-scan oracle and the dense truth, in
interpret mode; bit-exact run to run; one softmax update a block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kernel_cases import (
    LIVE_FORMS, primitive_counts, rel_err, shared_fold_case)
from paddle_tpu.kernels import oracle_tol


@pytest.mark.parametrize("dtype,hk", LIVE_FORMS)
@pytest.mark.parametrize("window", [None, 48, 512])
@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("w", [1, 2, 4, 5])
def test_paged_shared_fold_matches_the_oracle_and_the_dense_truth(
        w, group, window, dtype, hk):
    """Every row of every window width, K/V group and lower bound, in
    the loop form and the grid form: the Mosaic kernel (interpret)
    against the dense truth and against ``xla_ref``; a row with ``pos <
    0`` is zeros although its neighbours in the window are live."""
    from paddle_tpu.kernels.paged_attention import (
        paged_attention_pallas, paged_attention_ref)

    q, pk, pv, tbl, pos, how, want, live = shared_fold_case(
        w, group, window, dtype, hk)
    got = paged_attention_pallas(q, pk, pv, tbl, pos, interpret=True,
                                 out_dtype=jnp.float32, **how)
    assert got.shape == q.shape and got.dtype == jnp.float32
    tol = oracle_tol("paged_attention", dtype, "fwd")
    ref = paged_attention_ref(q, pk, pv, tbl, pos, out_dtype=jnp.float32,
                              **how)
    assert live.any() and not live.all()
    assert rel_err(got[live], want[live]) <= tol
    assert rel_err(got[live], ref[live]) <= tol
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("dtype,hk", LIVE_FORMS)
@pytest.mark.parametrize("w,group", [(1, 4), (5, 1), (2, 2)])
def test_paged_shared_fold_is_bit_exact_run_to_run(w, group, dtype, hk):
    from paddle_tpu.kernels.paged_attention import paged_attention_pallas

    q, pk, pv, tbl, pos, how, _, _ = shared_fold_case(w, group, 48, dtype,
                                                      hk)
    a = paged_attention_pallas(q, pk, pv, tbl, pos, interpret=True, **how)
    b = paged_attention_pallas(q, pk, pv, tbl, pos, interpret=True, **how)
    assert bool(jnp.array_equal(a, b))
    assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))


@pytest.mark.parametrize("dtype,hk", LIVE_FORMS)
@pytest.mark.parametrize("w,group", [(1, 1), (2, 1), (1, 4), (5, 1), (3, 2)])
def test_paged_mosaic_makes_one_softmax_update_a_block(w, group, dtype, hk):
    """What the fold shares, read off the traced kernel: however many
    rows attend a block (window rows x K/V group) ONE update holds TWO
    ``exp`` (``alpha`` and ``p``), where a per-row body holds two a row;
    from two rows up the scores AND the value product are ONE
    ``dot_general`` each a block, and their count does not grow with the
    rows.  The loop form traces each at two places (the first group's
    scores ahead of the loop and the next group's inside it; the update
    inside the loop and the last group's after it: four products and
    four ``exp`` in the body), the grid form at one; one row keeps the
    per-row program (a product and a lane reduction, no matmul), which
    is what every ``W = 1`` caller lowered to before."""
    from paddle_tpu.kernels.paged_attention import (
        _block_is_sliceable, paged_attention_pallas, softmax_updates)

    q, pk, pv, tbl, pos, how, _, _ = shared_fold_case(w, group, 48, dtype,
                                                      hk)
    counts = primitive_counts(jax.make_jaxpr(
        lambda *a: paged_attention_pallas(*a, interpret=True, **how))(
            q, pk, pv, tbl, pos).jaxpr)
    assert counts.get("pallas_call") == 1
    shared_loop = w * group > 1 and _block_is_sliceable(pk)
    places = 2 if shared_loop else 1
    assert counts.get("exp") == 2 * places * softmax_updates(w * group), counts
    assert counts.get("dot_general", 0) == (0 if w * group == 1
                                            else 2 * places)
