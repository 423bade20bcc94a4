"""A request's account of its time per output token (``ServingEngine.
_account_stall``, fed where the chunks are collected): first token ->
finish is its chunks' clock pairs + its waits inside other requests'
prefill clock pairs + the rest of its waits, whatever the cache, under
speculative rounds and for an ``eos_id`` hit that rides a chunk; the ring
of finished accounts, the gauges ``stats()`` makes of it and their
product; ``serving.chunk_fit`` and ``serving.longest_stall_seconds``; and
the ``chipbench/layer_metrics`` readers of all of it."""

import json
import os

import numpy as np
import pytest

import tiny
from chipbench import run as bench_run
from chipbench import tail_account
from paddle_tpu.models import transformer
from paddle_tpu.observability import trace
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.serving import ServingEngine, depth_draft
from paddle_tpu.serving import engine as engine_mod

T, VOCAB = 64, tiny.BUILT_VOCAB
NL, NH, DM = (tiny.gpt2.sizes[k] for k in ("layers", "heads", "d"))
READERS = ("serve.tpot_p90_ms", "tail.tpot_ms", "tail.step_ms",
           "tail.live_slots", "tail.steps_per_token",
           "tail.prefill_stall_share", "tail.host_stall_share",
           "step.decode_base_ms", "step.decode_ms_per_live_slot",
           "sched.longest_stall_ms")


@pytest.fixture(scope="module")
def gpt2():
    return tiny.gpt2_built(max_len=T)


@pytest.fixture
def tracer():
    t = trace.Tracer(enabled=True, registry=None)
    old = trace.set_tracer(t)
    yield t
    trace.set_tracer(old)


def _engine(params, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("registry", MetricsRegistry())
    return ServingEngine(params, NL, NH, DM, max_len=T,
                         decode_chunk=4, min_bucket=4, block_tokens=4, **kw)


def _serve_with_arrivals(eng, prompts, max_new, eos=None):
    """Two requests first, the others one at a time while chunks are in
    flight, so that every later admission's prefill is a wait of
    somebody's."""
    eos = eos or {}
    reqs = [eng.submit(p, max_new_tokens=m, eos_id=eos.get(i))
            for i, (p, m) in enumerate(zip(prompts[:2], max_new[:2]))]
    for i in range(2, len(prompts)):
        eng.step()
        reqs.append(eng.submit(prompts[i], max_new_tokens=max_new[i],
                               eos_id=eos.get(i)))
    eng.run_until_idle()
    assert all(r.done and r.error is None for r in reqs)
    return reqs


def _prompts(seed, lens, head=None):
    rng = np.random.default_rng(seed)
    tails = [rng.integers(1, VOCAB, n) for n in lens]
    return tails if head is None else [np.concatenate([head, t])
                                      for t in tails]


def _check_identity(reqs):
    for r in reqs:
        if len(r.tokens) < 2:
            assert r.chunk_s == r.steps == 0
            continue
        account = r.chunk_s + r.stall_prefill_s + r.stall_host_s
        # the waits and the chunks tile first token -> the last collect
        assert account == pytest.approx(r.chunks[-1][1] - r.first_token_t,
                                        abs=1e-9)
        assert r.chunk_s == pytest.approx(
            sum(c1 - c0 for c0, c1 in r.chunks), abs=1e-9)
        # ... and the finish is the emit span's entry, just after it
        assert 0 <= r.finish_t - r.first_token_t - account < 5e-3
        assert r.stall_prefill_s >= 0 and r.stall_host_s >= -1e-9
        assert r.steps >= len(r.tokens) - 1
        assert r.slot_steps >= r.steps


MAX_NEW = [9, 14, 3, 17, 6, 21, 11]
LENS = [3, 7, 2, 5, 9, 4, 6]


@pytest.mark.parametrize("kind", ["plain", "trie", "spec"])
def test_first_token_to_finish_is_chunks_and_the_two_waits(kind, gpt2,
                                                           tracer):
    head = np.arange(1, 11) % VOCAB if kind == "trie" else None
    kw = dict(prefix_reuse=kind != "plain")
    if kind == "spec":
        kw.update(draft_params=depth_draft(gpt2, 1), spec_k=3)
    eng = _engine(gpt2, **kw)
    assert (eng._spec is not None) == (kind == "spec")
    assert (eng.prefix_trie is not None) == (kind != "plain")
    reqs = _serve_with_arrivals(eng, _prompts(53, LENS, head), MAX_NEW)
    _check_identity(reqs)
    st = eng.stats()
    # every wait _account_stall counted is in some finished request's
    # account, in one part or the other
    assert sum(r.stall_prefill_s + r.stall_host_s for r in reqs) == \
        pytest.approx(st["serving.stalled_seconds"], abs=1e-9)
    assert sum(r.chunk_s + r.stall_prefill_s + r.stall_host_s
               for r in reqs) == pytest.approx(st["serving.live_seconds"],
                                               abs=1e-9)
    # somebody decoding sat through a later arrival's prefill
    assert sum(r.stall_prefill_s for r in reqs) > 0
    steps = 4 if kind == "spec" else eng.decode_chunk
    assert all(r.steps % steps == 0 for r in reqs)
    assert st["serving.tpot_seconds"]["count"] == len(reqs)
    assert "serving.tok_s" not in st


def test_a_wait_inside_a_prefill_is_that_prefills_clock_pair(gpt2, tracer):
    """One request decodes alone, a second arrives: the first one's prefill
    stall IS the second's prefill clock pair, and the second's own prefill
    is no wait of its own."""
    eng = _engine(gpt2, prefix_reuse=False)
    a, b = _prompts(7, [5, 9])
    ra = eng.submit(a, max_new_tokens=30)
    eng.step()
    eng.step()
    rb = eng.submit(b, max_new_tokens=5)
    eng.run_until_idle()
    _check_identity([ra, rb])
    assert ra.stall_prefill_s == pytest.approx(
        rb.prefill_t1 - rb.prefill_t0, abs=1e-9)
    assert rb.stall_prefill_s == 0.0
    longest = eng.stats()["serving.longest_stall_seconds"]
    assert longest >= ra.stall_prefill_s
    emits = tracer.events(name="serving.emit")
    assert max(e["args"]["stall_ms"] for e in emits) == \
        pytest.approx(longest * 1e3)
    assert all(0 <= e["args"]["stall_prefill_ms"] <= e["args"]["stall_ms"]
               + 1e-9 for e in emits)
    # the lane a reader opens says what the chunks beneath it add up to
    lanes = {e["args"]["rid"]: e["args"]
             for e in tracer.events(name="serving.request")}
    for r in (ra, rb):
        args = lanes[r.rid]
        assert args["tpot_ms"] == pytest.approx(
            1e3 * (r.finish_t - r.first_token_t) / (len(r.tokens) - 1))
        assert (args["chunk_s"], args["stall_prefill_s"],
                args["stall_host_s"], args["steps"]) == (
                    r.chunk_s, r.stall_prefill_s, r.stall_host_s, r.steps)


def test_an_eos_hit_that_rides_a_chunk_keeps_its_account(gpt2, tracer):
    """The hit ends the request inside a chunk: it paid for the whole
    chunk; the chunk sent before the hit was read steps its row for
    nothing and adds nothing to the finished account."""
    rng = np.random.default_rng(5)
    for _ in range(50):
        a = rng.integers(1, VOCAB, 5)
        ref, _ = transformer.generate(
            gpt2, np.asarray(a)[None], max_len=T, n_layer=NL,
            n_head=NH, d_model=DM, return_logits=False)
        gen = list(np.asarray(ref)[0][len(a):len(a) + 20])
        hits = [i for i in (5, 6, 7) if gen[i] not in gen[:i]]
        if hits:
            break
    else:
        pytest.fail("no prompt whose chain brings a new token in its "
                    "second chunk")
    hit = hits[0]
    eng = _engine(gpt2, max_slots=2, prefix_reuse=False)
    b, c = _prompts(6, [7, 6])
    reqs = _serve_with_arrivals(eng, [a, b, c], [20, 24, 6],
                                eos={0: int(gen[hit])})
    _check_identity(reqs)
    assert len(reqs[0].tokens) == hit + 1
    assert reqs[0].steps == 2 * eng.decode_chunk
    st = eng.stats()
    assert st["serving.rider_slot_steps"] == eng.decode_chunk
    # the rider's rows were stepped: they count in the chunk's load
    assert max(r.slot_steps / r.steps for r in reqs) <= eng.max_slots


def test_stats_publishes_the_tail_and_its_factors_multiply(gpt2, tracer):
    eng = _engine(gpt2, prefix_reuse=True)
    head = np.arange(1, 11) % VOCAB
    reqs = _serve_with_arrivals(
        eng, _prompts(3, LENS + [8, 3, 5], head), MAX_NEW + [8, 12, 2])
    st = eng.stats()
    tpot = sorted((r.finish_t - r.first_token_t) / (len(r.tokens) - 1)
                  for r in reqs)
    n = len(tpot)
    assert st["serving.tpot_p90_seconds"] == tpot[int(np.ceil(0.9 * n)) - 1]
    cut = tpot[int(np.ceil(0.8 * n)) - 1]
    tail = [r for r in reqs
            if (r.finish_t - r.first_token_t) / (len(r.tokens) - 1) >= cut]
    assert st["serving.tpot_tail_requests"] == len(tail) == n - int(
        np.ceil(0.8 * n)) + 1
    N = st["serving.tpot_tail_tokens"]
    K = st["serving.tpot_tail_steps"]
    assert N == sum(len(r.tokens) - 1 for r in tail)
    assert K == sum(r.steps for r in tail)
    assert st["serving.tpot_tail_slot_steps"] == sum(r.slot_steps
                                                     for r in tail)
    parts = {p: st["serving.tpot_tail_seconds{part=%s}" % p]
             for p in tail_account.PARTS}
    assert parts["chunk"] == pytest.approx(sum(r.chunk_s for r in tail))
    assert parts["stall_prefill"] == pytest.approx(
        sum(r.stall_prefill_s for r in tail))
    C, total = parts["chunk"], sum(parts.values())
    # the step at the tail's load x the steps a token cost x the waits
    assert (C / K) * (K / N) * (total / C) == pytest.approx(total / N)
    assert cut <= total / N + 5e-3 and total / N <= tpot[-1]
    # the readers say the same
    facts = {"stats": st}
    val = {name: bench_run.load_reader(name).read(facts) for name in READERS}
    assert all(v is not None for v in val.values())
    stalls = val["tail.prefill_stall_share"] + val["tail.host_stall_share"]
    assert (val["tail.step_ms"] * val["tail.steps_per_token"] * 100
            / (100 - stalls)) == pytest.approx(val["tail.tpot_ms"])
    assert val["serve.tpot_p90_ms"] == 1e3 * st["serving.tpot_p90_seconds"]
    assert 1 <= val["tail.live_slots"] <= eng.max_slots
    assert val["tail.steps_per_token"] >= 1
    # the program's own call for "the warm pass is over" takes all of it
    eng.reset_slo_accounting()
    st = eng.stats()
    assert not [k for k in st if k.startswith("serving.tpot_tail")
                or k.startswith("serving.tpot_p90")
                or k.startswith("serving.tok_s")]
    assert st["serving.tpot_seconds"]["count"] == 0
    assert st["serving.stalled_seconds"] == st["serving.live_seconds"] == 0
    assert st["serving.longest_stall_seconds"] == 0
    assert all(st["serving.chunk_fit{sum=%s}" % k] == 0
               for k in ("n", "a", "aa", "w", "aw"))
    assert len(eng._tpot_ring) == 0
    # and the next window's account is over the next window alone
    (again,) = _serve_with_arrivals(eng, [reqs[0].prompt], [7])[:1]
    st = eng.stats()
    assert st["serving.tpot_tail_requests"] == 1
    assert st["serving.tpot_tail_tokens"] == 6
    assert st["serving.live_seconds"] == pytest.approx(
        again.chunk_s + again.stall_prefill_s + again.stall_host_s)


def test_the_ring_is_bounded(gpt2, monkeypatch):
    assert engine_mod.TPOT_RING == 4096
    assert _engine(gpt2)._tpot_ring.maxlen == 4096
    monkeypatch.setattr(engine_mod, "TPOT_RING", 3)
    eng = _engine(gpt2, prefix_reuse=False)
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(_prompts(1, [3, 4, 5, 6, 7]), [2, 3, 4, 5, 6])]
    eng.run_until_idle()
    assert len(eng._tpot_ring) == 3
    # the most recent: a request of one token is no account at all
    assert {a[1] for a in eng._tpot_ring} <= {len(r.tokens) - 1
                                              for r in reqs}
    (one,) = [eng.submit(reqs[0].prompt, max_new_tokens=1)]
    eng.run_until_idle()
    assert one.done and len(eng._tpot_ring) == 3
    assert eng.stats()["serving.tpot_tail_requests"] >= 1


def test_tracing_off_leaves_the_accounts_and_gauges_live(gpt2):
    off = trace.Tracer(enabled=False, registry=None)
    old = trace.set_tracer(off)
    try:
        eng = _engine(gpt2, prefix_reuse=False)
        reqs = _serve_with_arrivals(eng, _prompts(2, LENS), MAX_NEW)
        st = eng.stats()
    finally:
        trace.set_tracer(old)
    assert not off.events()
    for r in reqs:
        assert r.chunks == []
        account = r.chunk_s + r.stall_prefill_s + r.stall_host_s
        assert 0 <= r.finish_t - r.first_token_t - account < 5e-3
        assert r.steps >= len(r.tokens) - 1 > 0
    assert st["serving.tpot_p90_seconds"] > 0
    assert st["serving.tpot_tail_requests"] >= 1
    assert st["serving.chunk_fit{sum=n}"] >= 1
    assert st["serving.longest_stall_seconds"] > 0
    assert st["serving.tpot_seconds"]["count"] == len(reqs)


def test_chunk_fit_recovers_a_planted_line(gpt2):
    eng = _engine(gpt2)
    base, slope, steps = 4.0e-3, 0.25e-3, eng.decode_chunk
    rng = np.random.default_rng(53)
    t = 10.0
    for rode in rng.integers(1, 40, 500):
        wall = steps * (base + slope * rode)
        eng._account_stall({}, t, t + wall, steps, int(rode))
        t += wall
    st = eng.stats()
    assert st["serving.chunk_fit{sum=n}"] == 500
    got = tail_account.chunk_fit(st)
    assert got == pytest.approx((base, slope), rel=1e-6)
    facts = {"stats": st}
    assert bench_run.load_reader("step.decode_base_ms").read(facts) == \
        pytest.approx(4.0, rel=1e-6)
    assert bench_run.load_reader("step.decode_ms_per_live_slot").read(
        facts) == pytest.approx(0.25, rel=1e-6)
    # rows that never varied: no line through one abscissa
    flat = _engine(gpt2)
    for i in range(5):
        flat._account_stall({}, i, i + 0.02 + 0.001 * i, steps, 7)
    assert tail_account.chunk_fit(flat.stats()) is None
    assert bench_run.load_reader("step.decode_base_ms").read(
        {"stats": flat.stats()}) is None


def test_the_longest_stall_holds_a_planted_wait(gpt2):
    eng = _engine(gpt2)
    a, b = (engine_mod.Request(i, np.ones(3, np.int32), 8, None)
            for i in (0, 1))
    eng._slot_advanced[0] = eng._slot_advanced[1] = 100.0
    # 0.5 s of prefill clock pairs went by since slot 1 advanced
    eng._prefill_s = 0.5
    eng._slot_prefill_s[0] = 0.5
    assert eng._account_stall({0: a}, 100.001, 100.021, 4, 2) == (
        pytest.approx(0.001), 0.0)
    assert eng._account_stall({0: a, 1: b}, 100.821, 100.841, 4, 2) == (
        pytest.approx(0.821), pytest.approx(0.5))
    assert eng._account_stall({0: a, 1: b}, 100.841, 100.861, 4, 2) == (
        pytest.approx(0.0), 0.0)
    st = eng.stats()
    assert st["serving.longest_stall_seconds"] == pytest.approx(0.821)
    assert bench_run.load_reader("sched.longest_stall_ms").read(
        {"stats": st}) == pytest.approx(821.0)
    assert (b.stall_prefill_s, b.stall_host_s) == (
        pytest.approx(0.5), pytest.approx(0.321))
    assert (a.stall_prefill_s, a.stall_host_s) == (
        0.0, pytest.approx(0.001 + 0.8))
    assert a.chunk_s == pytest.approx(0.06) and a.steps == 12
    assert a.slot_steps == 24 and b.steps == 8
    assert st["serving.stalled_seconds"] == pytest.approx(0.001 + 0.8
                                                           + 0.821)


# ---- the readers, on hand-made stats -----------------------------------

def _tail_stats():
    return {
        "serving.tpot_p90_seconds": 0.0081,
        "serving.tpot_tail_requests": 12.0,
        "serving.tpot_tail_tokens": 4000.0,
        "serving.tpot_tail_steps": 4400.0,
        "serving.tpot_tail_slot_steps": 4400.0 * 17.5,
        "serving.tpot_tail_seconds{part=chunk}": 22.0,
        "serving.tpot_tail_seconds{part=stall_prefill}": 6.0,
        "serving.tpot_tail_seconds{part=stall_host}": 4.0,
        "serving.longest_stall_seconds": 0.35,
        "serving.chunk_fit{sum=n}": 3.0, "serving.chunk_fit{sum=a}": 6.0,
        "serving.chunk_fit{sum=aa}": 14.0,
        "serving.chunk_fit{sum=w}": 0.018,
        "serving.chunk_fit{sum=aw}": 0.038,
    }


EXPECTED = {
    "serve.tpot_p90_ms": 8.1, "tail.tpot_ms": 8.0, "tail.step_ms": 5.0,
    "tail.live_slots": 17.5, "tail.steps_per_token": 1.1,
    "tail.prefill_stall_share": 18.75, "tail.host_stall_share": 12.5,
    "step.decode_base_ms": 4.0, "step.decode_ms_per_live_slot": 1.0,
    "sched.longest_stall_ms": 350.0,
}
# what the parent of PR 53 publishes of the same layer
PARENT_STATS = {"serving.stalled_seconds": 1.5, "serving.live_seconds": 30.0,
                "serving.step_seconds": {"count": 9, "p50": 0.004},
                "serving.tok_s": 900.0,
                "serving.chunks_dispatched{ahead=1}": 120.0}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_hand_made_stats(name):
    reader = bench_run.load_reader(name)
    assert reader.read({"stats": _tail_stats()}) == pytest.approx(
        EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_on_the_parents_stats(name):
    reader = bench_run.load_reader(name)
    assert reader.read({"stats": PARENT_STATS}) is None
    assert reader.read({"stats": {}}) is None


def test_the_tails_shares_sum_to_100_and_the_factors_to_the_tail():
    facts = {"stats": _tail_stats()}
    val = {n: bench_run.load_reader(n).read(facts) for n in READERS}
    chunk_share = 100.0 * 22.0 / 32.0
    assert (val["tail.prefill_stall_share"] + val["tail.host_stall_share"]
            + chunk_share) == pytest.approx(100.0)
    assert (val["tail.step_ms"] * val["tail.steps_per_token"]
            * 100.0 / chunk_share) == pytest.approx(val["tail.tpot_ms"])
    # an empty tail (no request of two tokens finished): nothing to read
    empty = dict(_tail_stats(), **{"serving.tpot_tail_tokens": 0.0})
    assert bench_run.load_reader("tail.tpot_ms").read(
        {"stats": empty}) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_entry_lists_the_serving_cells_and_moves_tpot(name):
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    reader = bench_run.load_reader(name)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) \
        == (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE)
    assert entry["better"] == "lower" and reader.RUNNERS == ("serve",)
    tpot = next(m for m in bench["end_to_end"] if m["name"] == "tpot_p90_ms")
    assert entry["workloads"] == tpot["workloads"]
    assert bench["per_layer"].index(entry) >= 61
