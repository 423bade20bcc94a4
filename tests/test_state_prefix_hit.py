"""A prefix hit over recurrent state, for the three architectures that
hold state BESIDE a pool (``SambaY``, ``MambaMoE``, ``DeltaMoE``; tiny
widths, float32, on the CPU): a prefill leaves ONE snapshot, at the last
block boundary on which one of its pieces ends; a later prompt's hit is
cut back to the deepest matched node that has one and its slot's rows
are written from it.  The logits of ``head + tail`` served that way are
those served with ``prefix_reuse=False``; a hit deeper than the snapshot
is cut back to it; a snapshot goes with its evicted node and the byte
budget holds; a chain a live slot references keeps its snapshot.  (Until
PR 57 the engine refused ``prefix_reuse=True`` for all three:
``test_sambay.py`` and ``test_ssm_moe.py`` pinned the refusal, and those
cases are these.)"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import tiny  # noqa: E402
from paddle_tpu.observability.metrics import MetricsRegistry  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402
from paddle_tpu.serving import batched_decode as _bd  # noqa: E402
from paddle_tpu.serving import engine as _engine  # noqa: E402

B, PIECE, T = 8, 32, 192
HEAD = 2 * PIECE


CASES = {"sambay": tiny.sambay, "mamba_moe": tiny.ssm_moe,
         "delta_moe": tiny.delta_moe}


def _case(name):
    """``(parameters, architecture, vocabulary rows)`` at the family's
    tiny size."""
    fam = CASES[name]
    return fam.init(), fam.arch(), fam.rows


def _engine_of(params, arch, rows=2, **kw):
    """An engine whose snapshot arrays have ``rows`` rows: ``cache_blocks``
    is what the byte budget derives that many from."""
    state = arch.state_bytes_per_slot(jnp.float32)
    block = B * arch.kv_bytes_per_token(4)
    kw.setdefault("prefix_reuse", True)
    if kw["prefix_reuse"]:
        kw["cache_blocks"] = -(-rows * state * _engine.SNAPSHOT_SHARE // block)
    reg = MetricsRegistry()
    eng = ServingEngine(params, arch=arch, max_len=T, max_slots=2,
                        block_tokens=B, min_bucket=8, donate=False,
                        compute_dtype="float32", registry=reg, **kw)
    assert eng.snapshot_rows == (rows if kw["prefix_reuse"] else 0)
    return eng


def _next_logits(eng, slot):
    """The logits of ``slot``'s first decode step, from the engine's own
    arrays as its prefill left them (nothing is written back)."""
    if not hasattr(eng, "_test_step"):     # one program an engine
        eng._test_step = jax.jit(
            lambda p, last, pos, pk, pv, table, state:
            _bd.paged_step_logits(p, last, pos, pk, pv, table, eng.arch,
                                  state)[0])
    return np.asarray(eng._test_step(
        eng._p, eng._last, eng._pos, eng._pk, eng._pv,
        jnp.asarray(eng._table), eng._state)[slot])


def _serve(eng, prompt, max_new=8):
    """Admit ``prompt`` by hand: ``(request, logits of its first decode
    step, its tokens)``."""
    req = eng.submit(prompt, max_new_tokens=max_new)
    eng._admit()
    slot = eng._slots.index(req)
    logits = _next_logits(eng, slot)
    while not req.done:        # other requests may stay live
        eng.step()
    return req, logits, np.asarray(req.result(timeout=0))


@pytest.mark.parametrize("name", list(CASES))
def test_a_prefix_hit_starts_from_one_state_snapshot(name, monkeypatch):
    monkeypatch.setattr(_bd, "PREFILL_PIECE", PIECE)
    params, arch, vocab = _case(name)
    rng = np.random.default_rng(11)
    draw = lambda n: rng.integers(0, vocab, n, dtype=np.int32)  # noqa: E731
    heads = [draw(HEAD) for _ in range(4)]
    plain = _engine_of(params, arch, prefix_reuse=False)
    eng = _engine_of(params, arch)
    trie = eng.prefix_trie
    per_slot = arch.state_bytes_per_slot(jnp.float32)
    assert eng.stats()["serving.state_snapshot_bytes"] == 2 * per_slot
    assert sum(a.nbytes for layer in eng._snap for a in layer) == 2 * per_slot

    # 1. a head served once: ``head + 20`` runs pieces that end at 32, 64
    # and 84: the snapshot lies at 64, the head's last block, and the trie
    # holds ten blocks of the prompt
    warm = np.concatenate([heads[0], draw(20)])
    _serve(eng, warm, max_new=2)
    assert eng.stats()["serving.state_snapshots_taken"] == 1
    assert trie.match_state(warm, len(warm) - 1)[1:] == (0, HEAD)

    # the logits of head + tail after the hit are those with no trie
    for n in (5, 13, 40):
        prompt = np.concatenate([heads[0], draw(n)])
        req, got, toks = _serve(eng, prompt)
        _, want, toks_plain = _serve(plain, prompt)
        assert req.prefix_hit == HEAD
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        assert np.array_equal(toks, toks_plain)
    st = eng.stats()
    assert st["serving.state_snapshot_hits"] == 3
    assert st["serving.state_restored_bytes"] == 3 * per_slot

    # 2. a hit deeper than the snapshot is cut back to it: sixteen tokens
    # of the warm prompt's tail are cached blocks too, with no snapshot
    deeper = np.concatenate([warm[:HEAD + 16], draw(7)])
    assert len(trie._path(deeper, len(deeper) - 1)) * B == HEAD + 16
    req, got, toks = _serve(eng, deeper)
    _, want, toks_plain = _serve(plain, deeper)
    assert req.prefix_hit == HEAD
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert np.array_equal(toks, toks_plain)
    # and with no snapshot on the path the hit is zero
    half = np.concatenate([heads[0][:PIECE], draw(9)])
    assert trie.match_state(half, len(half) - 1) == ([], None, 0)

    # 3. a chain a live slot references keeps its snapshot: a request
    # lives on head 0 while three other heads want the two rows
    live = eng.submit(np.concatenate([heads[0], draw(3)]),
                      max_new_tokens=100)
    eng._admit()
    for h in heads[1:]:
        _serve(eng, np.concatenate([h, draw(3)]), max_new=2)
        assert len(trie._snapshots) <= eng.snapshot_rows == 2
    assert trie.match_state(warm, HEAD)[1] is not None
    assert eng.stats()["serving.state_snapshot_evictions"] >= 1
    # the heads whose snapshots went are served whole again, correctly
    gone = [h for h in heads[1:]
            if trie.match_state(np.concatenate([h, h[:1]]), HEAD)[1] is None]
    assert gone
    prompt = np.concatenate([gone[0], draw(6)])
    req, got, toks = _serve(eng, prompt)
    _, want, toks_plain = _serve(plain, prompt)
    assert req.prefix_hit == 0
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert np.array_equal(toks, toks_plain)
    eng.run_until_idle()
    assert live.done

    # 4. a snapshot goes with its evicted node: every node is trie-only
    # now, and evicting them all gives every row back
    assert trie._snapshots
    before = trie.snapshot_evictions
    trie.evict_lru(len(trie))
    assert len(trie) == 0 and not trie._snapshots
    assert sorted(trie._free_rows) == [0, 1]
    assert trie.snapshot_evictions > before
    assert eng.kv_pool.blocks_in_use == 0
