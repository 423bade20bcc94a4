"""G table entries an iteration of the paged kernel's loop
(``kernels/paged_attention.py``, PR 52): the answers at every count of
entries, what a group fetches and what it leaves alone, and the rule
``entries_per_iteration`` at the serving cells' decode geometries."""

import jax.numpy as jnp
import numpy as np
import pytest

from kernel_cases import rel_err, shared_fold_case
from paddle_tpu.kernels import oracle_tol


# G table entries an iteration (PR 52): the loop of two rows or more
# takes a group of consecutive entries as ONE block of G x B tokens.  The
# rule gives these chains of 16 entries one entry (``GROUP_SHARE``), so
# its answer is overridden here: chains of 9, 5 and 16 live entries (no
# multiple of 4 or 8 among the first two, 5 shorter than 8), a window of
# 48 whose first entries (2 and 10) are no multiple of a group, a verify
# window whose rows sit at different positions, a float32 pool, a slot
# whose only row is dead; every entry past a chain's end names the trash
# block, whose values are 1e3.  (window rows, K/V group, lower bound,
# dtype, K/V rows: the loop form)
_ENTRY_CASES = {
    "group_4_ragged_chains": (1, 4, None, "bfloat16", 8),
    "group_4_window_first_entry_off_a_group": (1, 4, 48, "bfloat16", 8),
    "verify_5_rows_window": (5, 1, 48, "bfloat16", 8),
    "float32_pool_2_rows_group_2": (2, 2, None, "float32", 2),
}


@pytest.mark.parametrize("entries", [1, 2, 4, 8])
@pytest.mark.parametrize("case", list(_ENTRY_CASES))
def test_paged_shared_fold_takes_g_entries_an_iteration(case, entries,
                                                        monkeypatch):
    from paddle_tpu.kernels import paged_attention as pa

    w, group, window, dtype, hk = _ENTRY_CASES[case]
    q, pk, pv, tbl, pos, how, want, live = shared_fold_case(
        w, group, window, dtype, hk)
    monkeypatch.setattr(pa, "entries_per_iteration", lambda *a: entries)
    got = pa.paged_attention_pallas(q, pk, pv, tbl, pos, interpret=True,
                                    out_dtype=jnp.float32, **how)
    tol = oracle_tol("paged_attention", dtype, "fwd")
    ref = pa.paged_attention_ref(q, pk, pv, tbl, pos, out_dtype=jnp.float32,
                                 **how)
    assert live.any() and not live.all()
    assert rel_err(got[live], want[live]) <= tol
    assert rel_err(got[live], ref[live]) <= tol
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("window,entries", [(None, 2), (48, 4), (48, 8)])
def test_paged_group_fetches_its_own_entries_and_no_other(window, entries,
                                                          monkeypatch):
    """The kernel's trip count, read off what it fetches: NaN in every
    block but those of entries ``[f_s, f_s + iterations x G)`` of a live
    slot's table (``loop_iterations`` of the slot's live entries; the
    index clamped to the table) changes no bit, a dead slot's row and
    the entries under a lower bound among them; NaN in the LAST entry of
    a chain's last group, past the chain's end, shows (a zero weight
    times NaN): that entry is fetched, and a finite block under a zero
    weight adds nothing."""
    from paddle_tpu.kernels import paged_attention as pa

    q, pk, pv, tbl, pos, how, _, live = shared_fold_case(
        1, 4, window, "bfloat16", 8)
    B, NB = pk.shape[1], tbl.shape[1]
    monkeypatch.setattr(pa, "entries_per_iteration", lambda *a: entries)
    # distinct blocks past the chains' ends, so that each can be poisoned
    table = np.asarray(tbl).copy()
    spare = iter(range(int(table.max()) + 1, 10 ** 6))
    grown = np.concatenate(
        [np.asarray(pk, np.float32),
         np.full((int((table == 0).sum()),) + pk.shape[1:], 1e3,
                 np.float32)])
    for s, n in np.argwhere(table == 0):
        if live[s, 0]:
            table[s, n] = next(spare)
    pk2 = pv2 = jnp.asarray(grown, pk.dtype)
    shapes = ((B,) + pk.shape[2:],) * 2
    fetched, tails = np.zeros(grown.shape[0], bool), []
    for s in np.flatnonzero(live[:, 0]):
        at = int(np.asarray(pos)[s, 0])
        first = 0 if window is None else max(at - window + 1, 0) // B
        n = at // B + 1 - first
        trips = pa.loop_iterations(n, 4, shapes, pk.dtype, NB, window)
        assert trips == -(-n // entries)     # the override reaches it too
        last = min(first + trips * entries, NB) - 1
        fetched[table[s, first:last + 1]] = True
        if last >= first + n:
            tails.append(int(table[s, last]))
    base = pa.paged_attention_pallas(q, pk2, pv2, jnp.asarray(table), pos,
                                     interpret=True, **how)
    assert not fetched.all() and tails
    poison = jnp.asarray(~fetched)[:, None, None, None]
    again = pa.paged_attention_pallas(
        q, jnp.where(poison, jnp.nan, pk2), jnp.where(poison, jnp.nan, pv2),
        jnp.asarray(table), pos, interpret=True, **how)
    assert bool(jnp.all(jnp.isfinite(again.astype(jnp.float32))))
    assert bool(jnp.array_equal(base, again))
    tail = jnp.zeros(grown.shape[0], bool).at[jnp.asarray(tails)].set(True)
    shown = pa.paged_attention_pallas(
        q, pk2, jnp.where(tail[:, None, None, None], jnp.nan, pv2),
        jnp.asarray(table), pos, interpret=True, **how)
    assert bool(jnp.any(jnp.isnan(shown.astype(jnp.float32))))


def test_paged_group_of_a_slot_with_no_live_row_fetches_nothing(monkeypatch):
    """Every row at ``pos < 0``: zeros, whatever the pool holds (NaN in
    every block) and whatever the group."""
    from paddle_tpu.kernels import paged_attention as pa

    q, pk, pv, tbl, pos, how, _, _ = shared_fold_case(2, 2, None,
                                                      "bfloat16", 8)
    monkeypatch.setattr(pa, "entries_per_iteration", lambda *a: 4)
    got = pa.paged_attention_pallas(
        q, jnp.full_like(pk, jnp.nan), jnp.full_like(pv, jnp.nan), tbl,
        jnp.full_like(pos, -1), interpret=True, **how)
    assert not np.asarray(got, np.float32).any()


# the rule at the serving cells' decode geometries (bfloat16; blocks of
# 32 tokens but the slabs', 64): (K/V rows, K lanes, V lanes, folded rows
# a K/V row, table entries, lower bound) -> entries an iteration.  A SLAB
# (one K/V head's rows alone, no head axis: ``sala9b.doc_qa_128k``'s
# head-major planes, 32 KB a slab of K and V) weighs a quarter of
# ``ENTRY_BYTES``: its 97 selected blocks take 8 an iteration, a chain of
# 128 entries under the dense length 16.
_ENTRY_RULE = {
    "long_reason_full": ((8, 256, 128, 16, 416, None), 8),
    "long_reason_window_5_live_entries": ((8, 256, 128, 8, 416, 128), 1),
    "think_decode_full": ((16, 128, 128, 4, 64, None), 2),
    "think_decode_window_17_live_entries": ((16, 128, 128, 4, 64, 512), 1),
    "chat_moe_window_wider_than_the_table": ((8, 128, 128, 6, 64, 4096), 2),
    "chat_ssm": ((8, 128, 128, 16, 80, None), 2),
    "verify_window_under_group_4_fits_vmem": ((16, 128, 128, 20, 416, None),
                                              2),
    "slab_97_selected_blocks": ((None, 128, 128, 16, 97, None), 8),
    "slab_chain_under_the_dense_length": ((None, 128, 128, 16, 128, None),
                                          16),
}


@pytest.mark.parametrize("geometry", list(_ENTRY_RULE))
def test_entries_per_iteration_follows_the_shapes(geometry):
    """A power of two up to ``MAX_ENTRIES``, no more than a
    ``GROUP_SHARE``-th of the entries a chain can have live, within the
    loop's VMEM, a slab counted by the share of ``ENTRY_BYTES`` it holds;
    ``loop_iterations`` is the kernel's trip count at it."""
    from paddle_tpu.kernels import paged_attention as pa

    (h, dk, dv, rows, NB, window), want = _ENTRY_RULE[geometry]
    slab = h is None            # no head axis
    B = 64 if slab else 32
    light = pa.ENTRY_BYTES // (B * (dk + dv) * 2) if slab else 1
    live = pa.window_entries(NB, B, 1, window)
    folded = rows * (h or 1)
    got = pa.entries_per_iteration(B, h, dk, dv, folded, jnp.bfloat16, live)
    assert got == want and light == (4 if slab else 1)
    assert got * pa.GROUP_SHARE <= live * light or got == 1
    assert got <= pa.MAX_ENTRIES * light
    if slab:        # ONE K/V head WITH a head axis is no slab
        assert pa.entries_per_iteration(
            B, 1, dk, dv, folded, jnp.bfloat16, live) == {97: 2, 128: 4}[NB]
    assert got == 1 or pa._loop_vmem_bytes(
        got, B, h or 1, dk, dv, folded, jnp.bfloat16) <= pa.LOOP_VMEM_BYTES
    shapes = ((B, h, dk), (B, h, dv))
    for entries in (0, 1, got, got + 1, 5 * got + 3):
        assert pa.loop_iterations(entries, rows, shapes, jnp.bfloat16, NB,
                                  window) == -(-entries // got)


@pytest.mark.parametrize("form,want", [("one_row", 37), ("grid", 37),
                                       ("latent", 5), ("short_table", 19)])
def test_loop_iterations_of_the_other_forms(form, want):
    """One row a block and the grid form take an entry an iteration (a
    step); a latent plane ``LATENT_BLOCKS``, and no more than its table
    has."""
    from paddle_tpu.kernels import paged_attention as pa

    kv = lambda h: ((32, h, 128), (32, h, 128))  # noqa: E731
    args = {"one_row": (1, kv(16), jnp.bfloat16, 416),
            "grid": (5, kv(12), jnp.bfloat16, 416),
            "latent": (16, ((32, 640),), jnp.bfloat16, 288),
            "short_table": (16, ((32, 640),), jnp.bfloat16, 2)}[form]
    assert pa.loop_iterations(37, *args) == want
