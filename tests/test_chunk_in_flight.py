"""The driver keeps ONE decode chunk in flight (``ServingEngine._decode``:
``_dispatch`` sends chunk n + 1 before ``_collect`` reads chunk n): the
tokens are those of the same engine driven one chunk at a time, for every
kind of cache the engine keeps; an end the host can foresee never rides a
chunk more, an ``eos_id`` hit rides exactly one and nothing of it is
emitted; nothing returns or is failed with a chunk forgotten on the
device; and a chunk's clock pair starts where the previous one was read."""

import os
import time

import numpy as np
import pytest

import tiny
from paddle_tpu.models import transformer
from paddle_tpu.observability import trace
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.resilience import faults
from paddle_tpu.serving import ServingEngine

T, VOCAB = 64, tiny.BUILT_VOCAB
NL, NH, DM = (tiny.gpt2.sizes[k] for k in ("layers", "heads", "d"))


@pytest.fixture(scope="module")
def gpt2():
    return tiny.gpt2_built(max_len=T)


def _gpt2_engine(params, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("registry", MetricsRegistry())
    return ServingEngine(params, NL, NH, DM, max_len=T,
                         decode_chunk=4, min_bucket=4, block_tokens=4, **kw)


def _alone(params, prompt, n_new):
    """``transformer.generate`` on the one prompt: the plain reference."""
    ref, _ = transformer.generate(
        params, np.asarray(prompt)[None], max_len=T, n_layer=NL,
        n_head=NH, d_model=DM, return_logits=False)
    return np.asarray(ref)[0][:len(prompt) + n_new]


def _one_at_a_time(eng):
    """Drive ``eng`` as the engine was driven before a chunk was kept in
    flight: send one chunk, read it, and only then look at the queue."""
    def decode():
        finished = 0
        if reqs := eng._runs_on():
            eng._dispatch(reqs)
        while eng._chunks:
            finished += eng._collect()
        return finished

    eng._decode = decode
    return eng


def _engines(kind, gpt2, monkeypatch):
    """Two engines of one geometry over one set of weights, and prompts
    for them: (in flight, one at a time, prompts)."""
    if kind == "gpt2_trie":
        head = np.arange(1, 11) % VOCAB      # two full blocks and a half
        rng = np.random.default_rng(48)
        prompts = [np.concatenate([head, rng.integers(1, VOCAB, n)])
                   for n in (3, 7, 2, 5, 9, 4, 6)]
        make = lambda: _gpt2_engine(gpt2, prefix_reuse=True)     # noqa: E731
    elif kind == "sink_window":
        p = tiny.sink_window_moe.held(tiny.sink_window_moe.init())["float32"]
        prompts = [(3 * np.arange(n) + n) % 128 for n in
                   (21, 11, 13, 5, 17, 9, 12)]
        make = lambda: tiny.sink_window_moe.engine(          # noqa: E731
            p, monkeypatch)[0]
    elif kind == "sambay":
        p = tiny.sambay.init()
        prompts = [(5 * np.arange(n) + n) % 128 for n in
                   (21, 11, 17, 6, 9, 13, 4)]
        make = lambda: tiny.sambay.engine(                   # noqa: E731
            p, monkeypatch, max_slots=3)[0]
    else:
        p = tiny.retention.init()
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, tiny.retention.sizes["rows"], n,
                                dtype=np.int32)
                   for n in (140, 5, 17, 61, 9, 33, 12)]
        make = lambda: tiny.retention.engine(                # noqa: E731
            p, compute_dtype="float32")[0]
    return make(), _one_at_a_time(make()), prompts


# ragged: slots end in different chunks, some inside one, one at a
# chunk's last step (1 + 4k tokens), and seven requests go through three
# slots
MAX_NEW = [9, 14, 3, 17, 6, 21, 11]


@pytest.mark.parametrize("kind", ["gpt2_trie", "sink_window", "sambay",
                                  "retention"])
def test_tokens_are_those_of_one_chunk_at_a_time(kind, gpt2, monkeypatch):
    """(a) and (c): the same tokens as the engine driven one chunk at a
    time on each request ALONE, whatever the cache (trie and CoW forks,
    window chains, per-slot state rows, no table at all); slots are
    admitted again behind a chunk in flight; no end by ``max_new`` rides."""
    eng, plain, prompts = _engines(kind, gpt2, monkeypatch)
    behind = []
    admit = eng._prefill_into

    def admit_and_note(slot, req):
        behind.append(len(eng._chunks))
        return admit(slot, req)

    eng._prefill_into = admit_and_note
    outs = eng.generate_many(prompts, max_new_tokens=MAX_NEW)
    for p, m, out in zip(prompts, MAX_NEW, outs):
        assert len(out) == len(p) + m
        np.testing.assert_array_equal(
            out, plain.generate_many([p], max_new_tokens=m)[0])
        if kind == "gpt2_trie":
            np.testing.assert_array_equal(out, _alone(gpt2, p, m))
    st = eng.stats()
    # a freed slot was admitted again while a chunk was on the device
    assert max(behind) == 1 and behind[0] == 0
    assert st["serving.chunks_dispatched{ahead=1}"] > 0
    assert st["serving.rider_slot_steps"] == 0
    assert eng.idle and not eng._chunks
    if eng.kv_pool is not None:
        in_trie = len(eng.prefix_trie) if eng.prefix_trie is not None else 0
        assert eng.kv_pool.blocks_in_use == in_trie
        assert (eng._table == 0).all()
    if kind == "sink_window":
        assert eng.window_chains.pool.blocks_in_use == 0
    assert plain.stats().get("serving.chunks_dispatched{ahead=1}", 0) == 0


def test_an_eos_hit_rides_one_chunk_and_emits_none_of_it(gpt2):
    """(b): nobody can foresee an ``eos_id``, so the chunk sent before the
    hit was read still steps the slot; its tokens are dropped, its steps
    counted, and the request admitted into the slot next is exact."""
    rng = np.random.default_rng(5)
    b, c = (rng.integers(1, VOCAB, n) for n in (7, 6))
    # an eos inside the SECOND chunk (tokens 5..8 of the request; the
    # prefill gives token 0), which the chain has not emitted before:
    # greedy chains over random weights soon repeat, so look for a prompt
    for _ in range(50):
        a = rng.integers(1, VOCAB, 5)
        full = _alone(gpt2, a, 20)
        gen = list(full[len(a):])
        hits = [i for i in (5, 6, 7) if gen[i] not in gen[:i]]
        if hits:
            break
    else:
        pytest.fail("no prompt whose chain brings a new token in its "
                    "second chunk")
    hit = hits[0]
    eng = _gpt2_engine(gpt2, max_slots=2, prefix_reuse=False)
    ra = eng.submit(a, max_new_tokens=20, eos_id=int(gen[hit]))
    rb = eng.submit(b, max_new_tokens=24)
    rc = eng.submit(c, max_new_tokens=6)
    eng.step()          # a and b admitted, two chunks sent, the first read
    assert not ra.done and len(eng._chunks) == 1
    eng.step()          # the third sent, the second read: the hit
    assert ra.done and len(eng._chunks) == 1
    assert 0 in eng._free or 1 in eng._free
    np.testing.assert_array_equal(ra.result(timeout=0),
                                  full[:len(a) + hit + 1])
    assert eng.stats()["serving.rider_slot_steps"] == 0
    eng.step()          # c into a's slot, behind the chunk that rides
    assert eng.stats()["serving.rider_slot_steps"] == eng.decode_chunk
    eng.run_until_idle()
    assert len(ra.tokens) == hit + 1
    np.testing.assert_array_equal(rb.result(timeout=0), _alone(gpt2, b, 24))
    np.testing.assert_array_equal(rc.result(timeout=0), _alone(gpt2, c, 6))
    assert eng.stats()["serving.rider_slot_steps"] == eng.decode_chunk
    assert eng.kv_pool.blocks_in_use == 0 and eng.idle


def test_a_slot_is_where_the_device_says_and_an_end_gets_the_dead_row(gpt2):
    """(c): at every dispatch the dispatched position (``_sent``) is the
    device's own ``pos``; a slot whose ``max_new`` is reached inside the
    chunks in flight still holds its blocks and gets the dead row, so its
    first write is never past ``prompt + max_new - 2``: also where
    ``prompt + max_new`` is ``max_len``."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, VOCAB, n) for n in (T - 9, 6, 11)]
    max_new = [9, 13, 7]                       # the first ends AT max_len
    eng = _gpt2_engine(gpt2, prefix_reuse=False)
    table_of = eng._device_table
    held_dead = []

    def checked(live):
        tbl = np.asarray(table_of(live))
        pos = np.asarray(eng._pos)
        for s, req in enumerate(eng._slots):
            if req is None:
                continue
            n_p = len(req.prompt)
            if s in live:
                at = n_p + eng._sent(s, req) - 1
                assert pos[s] == at <= n_p + req.max_new - 2
                assert tbl[s, 0] != 0
            else:
                assert eng._sent(s, req) >= req.max_new
                assert eng._slot_blocks[s] and (tbl[s] == 0).all()
                held_dead.append(s)
        return table_of(live)

    eng._device_table = checked
    outs = eng.generate_many(prompts, max_new_tokens=max_new)
    for p, m, out in zip(prompts, max_new, outs):
        np.testing.assert_array_equal(out, _alone(gpt2, p, m))
    assert held_dead                # some chunk was sent past a held slot
    assert eng.stats()["serving.rider_slot_steps"] == 0


def test_stop_drains_what_is_in_flight(gpt2):
    """(d): ``stop(drain=True)`` returns with every token read."""
    eng = _gpt2_engine(gpt2, prefix_reuse=False)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, 5) for _ in range(5)]
    eng.start()
    reqs = [eng.submit(p, max_new_tokens=15) for p in prompts]
    eng.stop(drain=True)
    assert all(r.done and r.error is None for r in reqs)
    assert eng.idle and not eng._chunks
    assert eng.kv_pool.blocks_in_use == 0
    for r, p in zip(reqs, prompts):
        np.testing.assert_array_equal(r.result(timeout=0),
                                      _alone(gpt2, p, 15))


def test_an_abort_with_a_chunk_in_flight_fails_everyone_and_leaks_nothing(
        gpt2):
    """(d): the engine dies with a chunk on the device: nobody hangs."""
    eng = _gpt2_engine(gpt2, max_slots=2, prefix_reuse=False)
    rng = np.random.default_rng(4)
    reqs = [eng.submit(rng.integers(1, VOCAB, 5), max_new_tokens=15)
            for _ in range(3)]
    eng.step()
    assert len(eng._chunks) == 1 and not eng.idle

    def lost():
        raise RuntimeError("the device is gone")

    eng._collect = lost
    with pytest.raises(RuntimeError, match="the device is gone"):
        eng.step()
    assert all(r.done and r.error is not None for r in reqs)
    assert not eng._chunks and eng.idle
    assert eng.kv_pool.blocks_in_use == 0 and (eng._table == 0).all()
    with pytest.raises(RuntimeError, match="aborted"):
        eng.step()


def test_a_slot_death_reads_what_is_in_flight_first(gpt2):
    """(d): ``PADDLE_TPU_FAULT=slot_death`` at a dispatch behind a chunk
    in flight: the victim keeps every token computed for it, the others
    are exact, no block leaks."""
    eng = _gpt2_engine(gpt2, prefix_reuse=False)
    rng = np.random.default_rng(14)
    prompts = [rng.integers(1, VOCAB, 5) for _ in range(5)]
    os.environ["PADDLE_TPU_FAULT"] = "slot_death:3"
    faults.reset()
    try:
        reqs = [eng.submit(p, max_new_tokens=14) for p in prompts]
        eng.step()      # two chunks sent (arrivals 1 and 2), one read
        assert len(eng._chunks) == 1
        eng.step()      # arrival 3: the chunk in flight is read, then the
        (dead,) = [r for r in reqs if r.error is not None]     # death
        assert len(dead.tokens) == 1 + 2 * eng.decode_chunk
        eng.run_until_idle()
    finally:
        os.environ.pop("PADDLE_TPU_FAULT", None)
        faults.reset()
    for r, p in zip(reqs, prompts):
        want = _alone(gpt2, p, 14)
        if r is dead:
            np.testing.assert_array_equal(
                dead.tokens, want[len(p):len(p) + len(dead.tokens)])
        else:
            np.testing.assert_array_equal(r.result(timeout=0), want)
    st = eng.stats()
    assert st["serving.slot_deaths"] == 1 and st["serving.completed"] == 4
    assert st["serving.rider_slot_steps"] == 0
    assert eng.kv_pool.blocks_in_use == 0 and eng.idle


def _traced(run):
    t = trace.Tracer(enabled=True, registry=None)
    old = trace.set_tracer(t)
    try:
        run()
    finally:
        trace.set_tracer(old)
    return t


def test_a_chunks_clock_pair_starts_where_the_previous_one_was_read(gpt2):
    """(e): with a collect delayed on purpose, the delayed chunk's sample
    holds the delay, the next chunk's starts at that collect, no two
    pairs overlap, and nothing is stalled between back-to-back chunks."""
    reg = MetricsRegistry()
    eng = _gpt2_engine(gpt2, max_slots=1, prefix_reuse=False, registry=reg)
    eng.generate_many([np.arange(1, 6)], max_new_tokens=5)   # compiled
    reg.reset(prefix="serving.")
    collect, calls = eng._collect, []

    def late():
        calls.append(None)
        if len(calls) == 2:
            time.sleep(0.05)
        return collect()

    eng._collect = late
    req = eng.submit(np.arange(2, 9), max_new_tokens=21)     # five chunks
    _traced(eng.run_until_idle)
    pairs = req.chunks
    assert len(pairs) == 5
    assert pairs[0][0] >= req.first_token_t       # sent after the prefill
    assert pairs[1][1] - pairs[1][0] >= 0.05      # the delay is ITS time
    for before, after in zip(pairs, pairs[1:]):
        assert after[0] == before[1]              # back to back
    st = eng.stats()
    steps = st["serving.step_seconds"]
    assert steps["count"] == 5
    assert steps["sum"] * eng.decode_chunk == pytest.approx(
        sum(t1 - t0 for t0, t1 in pairs), rel=1e-6)
    # the one stall: from the first token to the first chunk's dispatch
    assert st["serving.stalled_seconds"] == pytest.approx(
        pairs[0][0] - req.first_token_t, abs=1e-9)
    assert st["serving.live_seconds"] == pytest.approx(
        pairs[-1][1] - req.first_token_t, rel=1e-6)


def test_one_decode_chunk_span_a_chunk_with_that_chunks_rows(gpt2):
    """(f): every chunk sent has ONE ``serving.dispatch`` and ONE
    ``serving.decode_chunk`` span, in the same order, the second with the
    rows live in THAT chunk (not in whatever the slots hold when it is
    read)."""
    reg = MetricsRegistry()
    eng = _gpt2_engine(gpt2, prefix_reuse=False, registry=reg)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, VOCAB, n) for n in (5, 9, 4, 7, 6)]
    t = _traced(lambda: eng.generate_many(prompts,
                                          max_new_tokens=MAX_NEW[:5]))
    sent = sorted(t.events(name="serving.dispatch"), key=lambda e: e["ts"])
    read = sorted(t.events(name="serving.decode_chunk"),
                  key=lambda e: e["ts"])
    st = eng.stats()
    chunks = (st["serving.chunks_dispatched{ahead=0}"]
              + st["serving.chunks_dispatched{ahead=1}"])
    assert len(sent) == len(read) == chunks == st[
        "serving.decode_chunk"]["count"]
    assert ([e["args"]["active"] for e in sent]
            == [e["args"]["active"] for e in read])
    assert all(e["args"]["steps"] == eng.decode_chunk for e in read)
    assert sum(e["args"]["ahead"] == "1" for e in sent) == st[
        "serving.chunks_dispatched{ahead=1}"]
    # the rows stepped cover the tokens the chunks emitted (a request's
    # first comes from its prefill), and no more than a chunk's tail each
    emitted = sum(MAX_NEW[:5]) - len(prompts)
    stepped = sum(e["args"]["active"] * e["args"]["steps"] for e in read)
    assert emitted <= stepped < emitted + len(prompts) * eng.decode_chunk
    # a fetch inside each collect, none inside a dispatch
    fetches = [e for e in t.events(name="serving.fetch")
               if e["args"].get("of") != "prefill"]
    assert len(fetches) >= len(read)
