"""A run of table entries several live slots share is fetched ONCE by the
latent plane's Mosaic kernel (``kernels.paged_attention``:
``shared_runs`` on the host, ``latent_attention_pallas(.., shared=)`` on
the device): the told call against the block-scan oracle and against the
same call told nothing (interpret mode), the host's runs from tables
written by hand, and ``serving.paged_entries_fetched`` held to a hand
count."""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import paged_attention as paged
from tiny import latent_moe as fam

B, NB, H, L, DV, G = 4, 12, 4, 128, 64, 2


def _tables(chains):
    """``chains``: a slot each, ``None`` (dead) or ``(document, entries,
    position)``: the slot's first entries are the document's blocks (a
    list of ids, shared by name), the rest its own."""
    table = np.zeros((len(chains), NB), np.int32)
    pos = np.full((len(chains), 1), -1, np.int32)
    own = iter(range(40, 200))
    for s, chain in enumerate(chains):
        if chain is None:
            continue
        doc, entries, at = chain
        table[s, :len(doc)] = doc
        table[s, len(doc):entries] = [next(own)
                                      for _ in range(entries - len(doc))]
        pos[s] = at
    return table, pos


DOC_A, DOC_B, DOC_C = [1, 2, 3, 4, 5, 6], [7, 8, 9, 10], [11, 12]
CASES = {
    # name: (chains, the runs' (length, members) a leader, by leader slot)
    "no_group": ([(DOC_A, 8, 30), (DOC_B, 7, 25), (DOC_C, 5, 18)], {}),
    "one_group_of_two": ([(DOC_A, 8, 30), (DOC_A, 9, 33)],
                         {0: (6, [0, 1])}),
    "groups_of_3_2_1": ([(DOC_A, 9, 34), (DOC_B, 8, 31), (DOC_A, 10, 37),
                         (DOC_B, 7, 26), (DOC_C, 6, 22), (DOC_A, 9, 35)],
                        {0: (6, [0, 2, 5]), 1: (4, [1, 3])}),
    "own_tails_of_different_lengths": (
        [(DOC_B, 5, 17), (DOC_B, 12, 47), (DOC_B, 8, 28)],
        {0: (4, [0, 1, 2])}),
    "a_dead_slot_inside_a_group": (
        [(DOC_A, 8, 29), None, (DOC_A, 9, 32), None], {0: (6, [0, 2])}),
}


def _case(name, dtype, w=1):
    chains, _ = CASES[name]
    table, pos = _tables(chains)
    rng = np.random.default_rng(len(name))
    pool = rng.normal(size=(200, B, L)).astype(np.float32)
    pool[0] = 1e3                 # the trash block weighs nothing
    S = len(chains)
    q = rng.normal(size=(S, w, H, L)).astype(np.float32) * 0.3
    at = np.where(pos >= 0, pos - (w - 1) + np.arange(w)[None, :], -1)
    whole = np.where(pos[:, 0] >= 0, at[:, 0] // B, 0)
    runs = paged.shared_runs(table, whole, H, blocks=G)
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(table), jnp.asarray(at, jnp.int32)), runs


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(CASES))
def test_a_shared_run_fetched_once_is_the_call_told_nothing(name, dtype):
    """Every live row of the told call equals the untold kernel's bit for
    bit (a row folds the same groups of entries in the same order) and
    the block-scan oracle within its tolerance; a dead slot's rows come
    back zero; the host's runs are the hand count."""
    from paddle_tpu.kernels.xla_ref import oracle_tol

    args, runs = _case(name, jnp.dtype(dtype))
    want_runs = CASES[name][1]
    assert {s: (int(r[0]), [int(i) for i in r[2:2 + r[1]]])
            for s, r in enumerate(runs) if r[1]} == want_runs
    how = dict(scale=0.2, interpret=True, blocks=G)
    alone = np.asarray(paged.latent_attention_pallas(*args, DV, **how),
                       np.float32)
    told = np.asarray(paged.latent_attention_pallas(
        *args, DV, shared=jnp.asarray(runs), **how), np.float32)
    ref = np.asarray(paged.paged_attention_ref(
        args[0], args[1], None, *args[2:], value_lanes=DV, scale=0.2),
        np.float32)
    live = np.asarray(args[3])[:, 0] >= 0
    assert np.array_equal(told, alone)
    tol = oracle_tol("paged_latent_attention", dtype, "fwd") * np.abs(
        ref[live]).max()
    assert np.abs(told - ref)[live].max() <= tol
    assert not told[~live].any()


@pytest.mark.parametrize("how", ["a_verify_window", "a_lower_bound"])
def test_a_window_of_rows_or_a_lower_bound_is_left_ungrouped(how):
    """``W > 1`` and a ``window`` drop ``shared``: the kernel is the
    call's without it (two scalar-prefetch operands, the parent's scratch),
    whatever the runs say."""
    import jax

    args, runs = _case("one_group_of_two", jnp.float32,
                       w=3 if how == "a_verify_window" else 1)
    kw = dict(scale=0.2, interpret=True, blocks=G,
              window=None if how == "a_verify_window" else 9)
    assert runs[0, 1] == 2

    def call(jaxpr):
        eqn, = [e for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "pallas_call"]
        return str(eqn.params["jaxpr"]), eqn.params["grid_mapping"]

    told = call(jax.make_jaxpr(lambda *a: paged.latent_attention_pallas(
        *a[:4], DV, shared=a[4], **kw))(*args, jnp.asarray(runs)))
    alone = call(jax.make_jaxpr(lambda *a: paged.latent_attention_pallas(
        *a, DV, **kw))(*args))
    assert told[0] == alone[0]
    assert told[1].num_index_operands == alone[1].num_index_operands == 2
    got = paged.paged_attention_pallas(
        args[0], args[1], None, *args[2:], value_lanes=DV,
        shared=jnp.asarray(runs), **{k: v for k, v in kw.items()
                                     if k != "blocks"})
    ref = paged.paged_attention_ref(
        args[0], args[1], None, *args[2:], value_lanes=DV, scale=0.2,
        window=kw["window"])
    assert np.abs(np.asarray(got) - np.asarray(ref)).max() < 2e-4


def test_a_kv_plane_takes_no_shared_runs():
    pool = jnp.zeros((9, B, 8, 128))
    with pytest.raises(ValueError, match="only a latent plane"):
        paged.paged_attention_pallas(
            jnp.zeros((2, 1, 8, 128)), pool, pool,
            jnp.zeros((2, NB), jnp.int32), jnp.zeros((2, 1), jnp.int32),
            interpret=True, shared=jnp.zeros((2, 4), jnp.int32))


def _rows(*rows):
    table = np.zeros((len(rows), 16), np.int32)
    for s, row in enumerate(rows):
        table[s, :len(row)] = row
    return table


DOC = list(range(1, 13))            # a document of 12 blocks
RUNS = {
    # name: (table rows, whole entries a slot, query rows a slot, blocks
    # an iteration) -> {leader: (run, members)}
    "two_slots_on_one_document": (
        _rows(DOC + [20, 21], DOC + [30]), [13, 12], 16, 4,
        {0: (12, [0, 1])}),
    "a_fork_cut_the_run_short_of_the_document": (
        # slot 1 forked entry 9 copy-on-write: the tables agree on 9 only
        _rows(DOC + [20], DOC[:9] + [40, 41, 42, 43]), [12, 12], 16, 4,
        {0: (8, [0, 1])}),
    "rounded_down_to_the_kernels_iteration": (
        _rows(DOC[:7] + [20], DOC[:7] + [30]), [8, 8], 16, 2,
        {0: (6, [0, 1])}),
    "shorter_than_one_iteration_is_no_run": (
        _rows(DOC[:3] + [20], DOC[:3] + [30]), [4, 4], 16, 4, {}),
    "only_entries_whole_under_every_members_position": (
        # slot 1 is still inside the document: 6 entries behind it
        _rows(DOC + [20], DOC), [12, 6], 16, 2, {0: (6, [0, 1])}),
    "a_slot_that_is_not_live_joins_nothing": (
        _rows(DOC + [20], DOC + [30], DOC + [31]), [12, 0, 12], 16, 4,
        {0: (12, [0, 2])}),
    "three_two_one": (
        _rows(DOC + [20], [50, 51, 52, 53, 54], DOC + [30],
              [50, 51, 52, 53, 60], [70, 71, 72, 73, 74], DOC + [31]),
        [12, 4, 12, 4, 5, 12], 16, 4,
        {0: (12, [0, 2, 5]), 1: (4, [1, 3])}),
    "a_leader_that_agrees_with_nobody_is_passed_over": (
        _rows([90, 91, 92, 93, 94], DOC + [20], DOC + [30]), [5, 12, 12],
        16, 4, {1: (12, [1, 2])}),
    "the_members_that_save_most": (
        # slot 3 agrees on 4 entries only: two members over 12 entries
        # spare 24 fetches, three over 4 would spare 8
        _rows(DOC + [20], DOC + [30], DOC + [31], DOC[:4] + [80, 81]),
        [12, 12, 12, 6], 16, 4, {0: (12, [0, 1, 2])}),
    "no_more_members_than_the_stack_holds": (
        # 128 query rows a slot: STACK_ROWS holds two slots' rows
        _rows(DOC + [20], DOC + [30], DOC + [31], DOC + [32]),
        [12, 12, 12, 12], 128, 4, {0: (12, [0, 1]), 2: (12, [2, 3])}),
}


@pytest.mark.parametrize("name", list(RUNS))
def test_the_hosts_runs_from_hand_made_table_rows(name):
    table, whole, rows, blocks, want = RUNS[name]
    runs = paged.shared_runs(table, np.asarray(whole), rows, blocks=blocks)
    assert runs.shape == (len(table), 2 + len(table))
    assert runs.dtype == np.int32
    got = {s: (int(r[0]), [int(i) for i in r[2:2 + r[1]]])
           for s, r in enumerate(runs) if r[1]}
    assert got == want
    # every member carries its run's length, everyone else a row of zeros
    for s, r in enumerate(runs):
        of = [n for n, members in want.values() if s in members]
        assert r[0] == (of[0] if of else 0)
        assert r[1] or not r[2:].any()


@pytest.fixture(scope="module")
def params():
    return fam.held(fam.init())


class _Req:
    def __init__(self, n):
        self.prompt, self.tokens = np.zeros(n, np.int32), []


def test_paged_entries_fetched_against_a_hand_count(params, monkeypatch):
    """``serving.paged_entries_fetched{phase=decode}``: the live entries
    less a shared run's for every member after its first; and the runs
    the engine sends, over a table written by hand."""
    monkeypatch.setattr(paged, "LATENT_BLOCKS", 2)
    eng, reg = fam.engine(params["float32"], monkeypatch,
                            prefix_reuse=True, cache_blocks=12, max_slots=4)
    eng._slots = [_Req(18), _Req(13), None, _Req(22)]
    eng._table[0, :5] = [3, 4, 5, 6, 7]       # 18 keys: 5 entries
    eng._table[1, :4] = [3, 4, 5, 9]          # 13 keys: 4, 3 agree -> 2
    eng._table[2, :2] = [3, 4]                # not live
    eng._table[3, :6] = [3, 4, 5, 6, 10, 11]  # 22 keys: 6, 4 agree
    contexts = [(0, 18), (1, 13), (3, 22)]
    runs = eng._shared_runs(contexts)
    # slots 0 and 3 agree on 4 entries (spares 4), all three on 2 (spares
    # 4 too): the first found is kept, slot 1 is left with nobody
    assert runs[:, :2].tolist() == [[4, 2], [0, 0], [0, 0], [4, 0]]
    assert runs[0, 2:4].tolist() == [0, 3]
    eng._count_paged_entries(contexts, runs)
    assert reg.value("serving.paged_entries_live") == 5 + 4 + 6
    assert reg.value("serving.paged_entries_fetched",
                     phase="decode") == 5 + 4 + 6 - 4
    # an engine without a trie tells nothing and counts nothing
    eng, reg = fam.engine(params["float32"], monkeypatch, max_slots=4)
    assert eng._shared_runs(contexts) is None
    eng._slots = [_Req(18), _Req(13), None, _Req(22)]
    eng._count_paged_entries(contexts)
    assert reg.value("serving.paged_entries_fetched", phase="decode") == 0


def test_two_slots_on_one_head_decode_through_the_told_kernel(
        params, monkeypatch):
    """The engine end to end with the Mosaic kernel (interpret mode) in
    place of the CPU's oracle: two live slots over one head, the run
    fetched once, each token what the float32 reference says."""
    monkeypatch.setattr(paged, "LATENT_BLOCKS", 2)
    seen = []

    def mosaic(*args, **how):
        seen.append("shared" in how)
        return paged.paged_attention_pallas(*args, interpret=True, **how)

    real = paged.resolve
    monkeypatch.setattr(paged, "resolve", lambda op, **kw: (
        types.SimpleNamespace(impl=types.SimpleNamespace(call=mosaic))
        if op == "paged_attention" and not kw else real(op, **kw)))
    p = params["float32"]
    eng, reg = fam.engine(p, monkeypatch, prefix_reuse=True,
                            cache_blocks=12)
    head = (3 * np.arange(18) + 2) % 128
    second = np.concatenate([head, [1, 2, 3, 4, 5]])
    third = np.concatenate([head, [11, 12, 13, 14]])
    eng.generate_many([np.concatenate([head, [9, 8, 7]])],
                      max_new_tokens=[2])
    outs = eng.generate_many([second, third], max_new_tokens=[8, 8])
    assert any(seen)
    for prompt, full in zip((second, third), outs):
        want = fam.reference(p, full)[len(prompt) - 1:len(full) - 1]
        gap = want.max(-1) - want[np.arange(len(want)), full[len(prompt):]]
        assert gap.max() < 1e-3, gap.max()
    st = eng.stats()
    live = st["serving.paged_entries_live"]
    fetched = reg.value("serving.paged_entries_fetched", phase="decode")
    # while both were live every chunk spared the head's four whole blocks
    assert 0 < live - fetched <= st["serving.paged_entries_shared"] / 2
    assert (live - fetched) % 4 == 0
