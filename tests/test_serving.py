"""Continuous-batching serving engine (paddle_tpu/serving/) — slot
lifecycle, EOS eviction, bucketed-prefill compile bound, token-identity
vs the single-stream decode, and serving.* metrics exposure.  All on the
CPU mesh (conftest), tiny model shapes."""

import time

import numpy as np
import pytest

import tiny
from paddle_tpu.models import transformer
from paddle_tpu.observability import metrics as _obs
from paddle_tpu.serving import ServingEngine


VOCAB, T = tiny.BUILT_VOCAB, 32
NL, NH, DM = (tiny.gpt2.sizes[k] for k in ("layers", "heads", "d"))


@pytest.fixture
def params():
    return tiny.gpt2_built(VOCAB, T)


@pytest.fixture(autouse=True)
def fresh_serving_metrics():
    _obs.get_registry().clear(prefix="serving.")
    yield


def _engine(params, **kw):
    kw.setdefault("max_len", T)
    kw.setdefault("max_slots", 4)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("min_bucket", 4)
    return ServingEngine(params, NL, NH, DM, **kw)


def test_slot_admit_free_lifecycle(params):
    """More requests than slots: all admitted (continuous batching waves),
    every slot freed at the end, queue drained, counters consistent."""
    eng = _engine(params, max_slots=2)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, VOCAB, (l,)) for l in (3, 5, 2, 4, 6)]
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    assert eng.stats()["serving.queue_depth"] == 5
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    assert eng.active_slots == 0 and eng.idle
    st = eng.stats()
    assert st["serving.queue_depth"] == 0
    assert st["serving.slots_active"] == 0
    assert st["serving.admitted"] == 5
    assert st["serving.completed"] == 5
    # every request got exactly its token budget (no EOS configured)
    for r, p in zip(reqs, prompts):
        out = r.result(timeout=0)
        assert out.shape == (len(p) + 6,)
        np.testing.assert_array_equal(out[: len(p)], p)
    # finished handles surface through results() exactly once
    done = eng.results()
    assert {r.rid for r in done} == {r.rid for r in reqs}
    assert eng.results() == []


def test_eos_evicts_slot_early(params):
    """A request whose greedy chain hits EOS frees its slot early and its
    output stops AT the EOS token."""
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, VOCAB, (4,))
    # learn the chain once without EOS, then re-serve with eos_id set to
    # a token the chain is known to emit
    eng = _engine(params)
    full = eng.generate_many([prompt], max_new_tokens=12)[0]
    gen = full[4:]
    eos = int(gen[len(gen) // 2])  # a mid-stream token
    cut = list(gen).index(eos)

    _obs.get_registry().clear(prefix="serving.")  # counters are global
    eng2 = _engine(params)
    out = eng2.generate_many([prompt], max_new_tokens=12, eos_id=eos)[0]
    np.testing.assert_array_equal(out, full[: 4 + cut + 1])
    assert out[-1] == eos
    assert eng2.active_slots == 0
    # fewer decode tokens than the no-EOS run (the slot really left)
    assert eng2.stats()["serving.completed"] == 1


def test_bucketed_prefill_bounds_compiles(params):
    """50+ mixed-length requests: the prefill executables are window
    widths up to the piece width, one compile each, plus 1 decode chunk,
    regardless of request count; none compiles after the warm pass."""
    from paddle_tpu.serving import batched_decode as _bd

    eng = _engine(params, max_slots=8, min_bucket=4)
    rng = np.random.default_rng(2)
    n = 52
    lens = rng.integers(1, 14, n)  # buckets {4, 8, 16}
    prompts = [rng.integers(1, VOCAB, (int(l),)) for l in lens]
    outs = eng.generate_many(prompts, max_new_tokens=4)
    assert len(outs) == n
    widths = {w for l in lens for w in eng._piece_widths(int(l))}
    assert widths == {eng.bucket_for(int(l)) for l in lens}  # one piece
    assert widths <= {4, 8, 16, 32, 64, 128} and max(widths) <= min(
        _bd.PREFILL_PIECE, T)
    st = eng.stats()
    assert st["serving.prefill_compiles"] == len(widths) <= 3
    assert st["serving.decode_compiles"] == 1
    assert st["serving.admitted"] == n
    assert st["serving.completed"] == n
    # the counters must reflect REAL jit-cache entries: one executable
    # per width callable / per decode chunk, no silent retraces
    assert eng._decode_fn._cache_size() == 1
    assert sorted(eng._prefill_fns) == sorted(widths)
    compiled = dict(eng.compile_seconds)
    # a second wave over the same widths compiles nothing
    eng.generate_many([rng.integers(1, VOCAB, (int(l),))
                       for l in rng.integers(1, 14, 12)], max_new_tokens=4)
    assert eng.compile_seconds == compiled
    assert eng.stats()["serving.prefill_compiles"] == len(widths)
    assert all(f._cache_size() == 1 for f in eng._prefill_fns.values())


def test_batched_decode_token_identical_to_single_stream(params):
    """The acceptance bar: any request served through the batched engine
    produces exactly the tokens of running it ALONE through
    transformer.generate (greedy, same weights) — mixed lengths, slot
    reuse, mid-stream admissions and all."""
    eng = _engine(params, max_slots=3, decode_chunk=5)
    rng = np.random.default_rng(3)
    specs = [(3, 8), (7, 12), (1, 20), (9, 5), (4, 16), (12, 9), (2, 11)]
    prompts = [rng.integers(1, VOCAB, (pl,)) for pl, _ in specs]
    max_new = [mn for _, mn in specs]
    outs = eng.generate_many(prompts, max_new)
    for p, m, o in zip(prompts, max_new, outs):
        ref, _ = transformer.generate(params, p[None], max_len=T,
                                      n_layer=NL, n_head=NH, d_model=DM,
                                      return_logits=False)
        np.testing.assert_array_equal(o, np.asarray(ref)[0][: len(p) + m])


def test_engine_from_architecture_object_equals_positional(params):
    """``ServingEngine(params, arch=Gpt2(...))`` is the positional
    constructor exactly: same tokens, same pool, same gauges."""
    from paddle_tpu.serving.arch import Gpt2

    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, VOCAB, (l,)) for l in (3, 9, 1, 6)]
    outs = []
    for eng in (_engine(params, max_slots=2),
                ServingEngine(params, arch=Gpt2(NL, NH, DM), max_len=T,
                              max_slots=2, decode_chunk=4, min_bucket=4)):
        outs.append(eng.generate_many(prompts, max_new_tokens=7))
        assert (eng.n_layer, eng.n_head, eng.d_model) == (NL, NH, DM)
        assert len(eng._pk) == NL and eng._pk[0].shape == (
            eng.kv_pool.num_blocks, eng.block_tokens, NH, DM // NH)
        st = eng.stats()
        assert st["serving.kv_planes"] == NL
        assert st["serving.stack_passes"] == 1
        assert st["serving.kv_write_fill"] == 1.0
        assert st["serving.kv_pool_bytes"] == sum(
            a.nbytes for a in eng._pk + eng._pv)
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("rows,updates", [(1, 1), (4, 1), (28, 1)])
def test_engine_counts_rows_and_softmax_updates_of_live_entries(
        params, rows, updates):
    """``serving.paged_rows_live`` is the live table entries times the
    query rows an architecture's decode call sends through each
    (``arch.rows_per_entry``, the K/V group the kernel folds into its
    window); ``serving.paged_updates_live`` the softmax updates the paged
    kernel makes for them, as its own module states them
    (``softmax_updates``: one for all the rows of a block), and
    ``serving.paged_iterations_live`` the iterations its loop makes over
    them (``loop_iterations``: a table of 8 entries gives every form an
    entry an iteration)."""
    from paddle_tpu.kernels.paged_attention import (loop_iterations,
                                                    softmax_updates)
    from paddle_tpu.serving.arch import Gpt2

    class Grouped(Gpt2):
        rows_per_entry = rows

    assert Gpt2(NL, NH, DM).rows_per_entry == 1
    assert softmax_updates(rows) == updates
    eng = ServingEngine(params, arch=Grouped(NL, NH, DM), max_len=T,
                        max_slots=2, decode_chunk=4, min_bucket=4,
                        block_tokens=4, prefix_reuse=False)
    eng.generate_many([np.arange(1, 6), np.arange(1, 10)],
                      max_new_tokens=5)
    st = eng.stats()
    # one chunk: 5 + 1 and 9 + 1 tokens in entries of 4 positions
    assert st["serving.paged_entries_live"] == 2 + 3
    assert st["serving.paged_rows_live"] == (2 + 3) * rows
    assert st["serving.paged_updates_live"] == (2 + 3) * updates
    shapes = eng.arch.plane_block_shapes(0, 4, eng.compute_dtype)
    assert st["serving.paged_iterations_live"] == sum(
        loop_iterations(n, rows, shapes, eng.compute_dtype,
                        eng.blocks_per_slot) for n in (2, 3)) == 2 + 3


@pytest.mark.parametrize("rows,want", [(4, 1 + 2), (1, 2 + 3)])
def test_engine_counts_a_group_of_entries_as_one_iteration(
        params, rows, want, monkeypatch):
    """Where the kernel's rule gives a group of table entries an
    iteration the engine counts what the module states, a slot at a
    time: chains of 2 and 3 live entries in groups of 2 are 1 + 2
    iterations; one row a block keeps an entry an iteration."""
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.serving.arch import Gpt2

    class Grouped(Gpt2):
        rows_per_entry = rows

    monkeypatch.setattr(pa, "entries_per_iteration", lambda *a: 2)
    eng = ServingEngine(params, arch=Grouped(NL, NH, DM), max_len=T,
                        max_slots=2, decode_chunk=4, min_bucket=4,
                        block_tokens=4, prefix_reuse=False)
    eng.generate_many([np.arange(1, 6), np.arange(1, 10)],
                      max_new_tokens=5)
    st = eng.stats()
    assert st["serving.paged_entries_live"] == 2 + 3
    assert st["serving.paged_iterations_live"] == want


def test_bf16_weights_serve_in_bf16_and_match(params):
    """bf16 block weights: the engine infers bf16 compute (cache
    discipline) and still matches the single-stream bf16 decode."""
    import jax.numpy as jnp

    p16 = {k: (jnp.asarray(v, jnp.bfloat16)
               if (k.startswith("block") or k.startswith("lm_head"))
               and k.endswith(".w") else v)
           for k, v in params.items()}
    eng = _engine(p16)
    assert eng.compute_dtype == jnp.bfloat16
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, VOCAB, (l,)) for l in (3, 6)]
    outs = eng.generate_many(prompts, max_new_tokens=8)
    for p, o in zip(prompts, outs):
        ref, _ = transformer.generate(p16, p[None], max_len=T, n_layer=NL,
                                      n_head=NH, d_model=DM,
                                      return_logits=False)
        np.testing.assert_array_equal(o, np.asarray(ref)[0][: len(p) + 8])


def test_serving_metrics_exposed(params):
    """The telemetry contract: TTFT/e2e histograms count one observation
    per request, token counter matches emitted tokens, and everything
    reaches the Prometheus exposition."""
    eng = _engine(params)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, VOCAB, (l,)) for l in (2, 5, 3)]
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run_until_idle()
    st = eng.stats()
    assert st["serving.ttft_seconds"]["count"] == 3
    assert st["serving.e2e_seconds"]["count"] == 3
    assert st["serving.tokens"] >= 3 * 5  # budget + discarded mid-chunk
    assert st["serving.step_seconds"]["count"] >= 1
    assert st["serving.prefill_seconds"]["count"] == 3
    assert st["serving.slots_total"] == 4
    for r in reqs:
        assert r.ttft is not None and r.e2e is not None
        assert 0 <= r.ttft <= r.e2e
    text = _obs.get_registry().to_text()
    for frag in ("serving_ttft_seconds", "serving_tpot_seconds",
                 "serving_queue_depth", "serving_admitted"):
        assert frag in text, frag


def test_submit_validation(params):
    eng = _engine(params)
    with pytest.raises(ValueError):
        eng.submit([], max_new_tokens=4)
    with pytest.raises(ValueError):
        eng.submit([1, 2], max_new_tokens=0)
    with pytest.raises(ValueError):  # p_len + max_new > max_len
        eng.submit(np.ones(20, np.int32), max_new_tokens=T)


def test_engine_abort_fails_pending_requests(params):
    """A device error mid-serve is fatal (donated caches are gone): the
    engine aborts, waiters wake with ``error`` set instead of hanging,
    and further submits raise."""
    eng = _engine(params)

    def boom():
        raise RuntimeError("device gone")

    eng._admit = boom
    eng.start()
    try:
        req = eng.submit(np.asarray([1, 2, 3]), max_new_tokens=4)
        assert req.wait(timeout=60), "abort did not wake the waiter"
        assert req.error is not None
        with pytest.raises(RuntimeError):
            req.result(timeout=0)
        with pytest.raises(RuntimeError):
            eng.submit([1], max_new_tokens=1)
        (failed,) = eng.results()
        assert failed is req
        assert eng.stats()["serving.aborted"] == 1
    finally:
        eng.stop()


def test_driver_thread_death_fails_pending_requests(params):
    """ISSUE 8 satellite: a driver thread that DIES (an exception
    ``step()`` does not turn into an abort — here a ``BaseException``
    escaping the loop) must fail every pending/queued request with the
    captured exception so ``result(timeout=None)`` returns instead of
    hanging forever, and ``submit()`` after the death raises
    immediately."""
    import threading

    eng = _engine(params)

    class DriverKilled(BaseException):  # escapes step()'s Exception catch
        pass

    def boom():
        raise DriverKilled("driver thread killed")

    eng._admit = boom
    eng.start()
    try:
        req = eng.submit(np.asarray([1, 2, 3]), max_new_tokens=4)
        # result(timeout=None) is the hang the supervision removes: run
        # it on a side thread with a bounded join so a regression fails
        # the test instead of wedging the suite
        got = {}

        def wait_forever():
            try:
                got["val"] = req.result(timeout=None)
            except BaseException as e:  # noqa: BLE001
                got["err"] = e

        t = threading.Thread(target=wait_forever, daemon=True)
        t.start()
        t.join(timeout=60)
        assert not t.is_alive(), \
            "result(timeout=None) still hangs after driver death"
        assert isinstance(got.get("err"), RuntimeError)
        assert isinstance(req.error, DriverKilled)
        # the dead driver is observable and rejects new work
        for _ in range(200):
            if not eng.driver_alive():
                break
            time.sleep(0.01)
        assert not eng.driver_alive()
        with pytest.raises(RuntimeError):
            eng.submit([1], max_new_tokens=1)
        assert _obs.get_registry().value("serving.driver_deaths") == 1
    finally:
        eng.stop()  # must not hang on the drain either


def test_background_thread_driver(params):
    """start()/stop() + concurrent submit: the Poisson-load path the
    serving benchmark uses."""
    eng = _engine(params, max_slots=2)
    eng.start()
    try:
        rng = np.random.default_rng(6)
        reqs = [eng.submit(rng.integers(1, VOCAB, (3,)), max_new_tokens=6)
                for _ in range(5)]
        for r in reqs:
            assert r.wait(timeout=60), "request did not finish"
        done = eng.results()
        assert {r.rid for r in done} == {r.rid for r in reqs}
    finally:
        eng.stop()
    assert eng.idle


# -- SLO budgets + goodput (ISSUE 11: goodput-under-SLO measurement) --------

def test_slo_violations_counted(params):
    """An impossibly tight TTFT budget: every completed request is a
    violation, goodput stays zero, and each handle carries its
    verdict."""
    eng = _engine(params, ttft_slo_s=1e-9)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, VOCAB, (4,)) for _ in range(3)]
    eng.generate_many(prompts, max_new_tokens=4)
    st = eng.stats()
    assert st["serving.slo_violations"] == 3
    assert st["serving.goodput_tok_s"] == 0.0
    assert all(r.slo_ok is False for r in eng.results())


def test_goodput_counts_slo_met_tokens(params):
    """Generous budgets: zero violations, goodput > 0, verdicts True."""
    eng = _engine(params, ttft_slo_s=600.0, e2e_slo_s=600.0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, (4,)) for _ in range(3)]
    eng.generate_many(prompts, max_new_tokens=4)
    st = eng.stats()
    assert st.get("serving.slo_violations", 0) == 0
    assert st["serving.goodput_tok_s"] > 0
    assert all(r.slo_ok is True for r in eng.results())


def test_no_slo_configured_leaves_verdict_none(params):
    eng = _engine(params)
    eng.generate_many([np.arange(1, 4, dtype=np.int32)],
                      max_new_tokens=3)
    st = eng.stats()
    assert "serving.slo_violations" not in st
    assert all(r.slo_ok is None for r in eng.results())


def test_reset_slo_accounting_reopens_window(params):
    """The bench warm-pass contract: resetting after warm requests
    zeroes the violation counter and the goodput window."""
    eng = _engine(params, ttft_slo_s=1e-9)
    eng.generate_many([np.arange(1, 4, dtype=np.int32)],
                      max_new_tokens=3)
    assert eng.stats()["serving.slo_violations"] == 1
    eng.reset_slo_accounting()
    assert eng.stats()["serving.slo_violations"] == 0
    assert eng.stats()["serving.goodput_tok_s"] == 0.0
    eng.ttft_slo_s = 600.0
    eng.e2e_slo_s = 600.0
    eng.generate_many([np.arange(1, 4, dtype=np.int32)],
                      max_new_tokens=3)
    st = eng.stats()
    assert st["serving.slo_violations"] == 0
    assert st["serving.goodput_tok_s"] > 0


def test_slo_budget_validation(params):
    with pytest.raises(ValueError):
        _engine(params, ttft_slo_s=0)
    with pytest.raises(ValueError):
        _engine(params, e2e_slo_s=-1.0)
    with pytest.raises(ValueError):  # undersized pool must not
        _engine(params, cache_blocks=-1)  # construct-then-abort


def test_reset_slo_accounting_rearms_window_origin(params):
    """ISSUE 12 small fix: the goodput window ORIGIN must re-arm on
    reset — after a warm pass plus a dead gap, the timed run's
    ``serving.goodput_tok_s`` denominator starts at the timed run's
    first submit, not back at the warm pass's (which would understate
    goodput by the whole gap)."""
    eng = _engine(params, ttft_slo_s=600.0, e2e_slo_s=600.0)
    eng.generate_many([np.arange(1, 4, dtype=np.int32)],
                      max_new_tokens=3)  # warm pass (opens a window)
    time.sleep(0.3)                      # the dead gap between passes
    eng.reset_slo_accounting()
    assert eng._first_submit_t is None   # origin re-armed
    t0 = time.perf_counter()
    eng.generate_many([np.arange(1, 4, dtype=np.int32)],
                      max_new_tokens=3)
    timed_window = time.perf_counter() - t0
    good = eng.stats()["serving.goodput_tok_s"]
    # 3 good tokens over (at most) the timed window; a stale origin
    # would divide by >= 0.3s extra and land far below this bound
    assert good >= 3 / (timed_window + 0.15), \
        f"goodput {good} suggests the window origin was not re-armed"
    # the reset also zeroes the shed/prefix/CoW accounting windows
    eng.reset_slo_accounting()
    st = eng.stats()
    assert st.get("serving.prefix_hit_rate", 0.0) == 0.0
    assert st.get("serving.shed_total", 0) == 0
    assert st.get("serving.cow_copies", 0) == 0


# -- SLO scheduler: predictor, reorder, shed (ISSUE 12 control half) --------

def test_predictor_learns_and_predicts():
    from paddle_tpu.serving.scheduler import TtftPredictor

    p = TtftPredictor()
    assert not p.ready
    p.observe_prefill(8, 0.10)
    p.observe_chunk(0.05, steps=4)
    assert p.ready
    assert p.prefill_s(8) == pytest.approx(0.10)
    # unseen bucket scales by token ratio off the nearest observed one
    assert p.prefill_s(16) == pytest.approx(0.20)
    # 9 new tokens: 1 rides prefill, 8 more need 2 chunks of 4
    assert p.decode_s(9) == pytest.approx(0.10)
    assert p.min_service_s(8, 9) == pytest.approx(0.20)


def test_slo_scheduler_reorders_by_slack_and_sheds():
    import collections
    import types

    from paddle_tpu.serving.scheduler import SloScheduler, TtftPredictor

    pred = TtftPredictor()
    pred.observe_prefill(8, 0.1)
    pred.observe_chunk(0.1, steps=4)
    budgets = types.SimpleNamespace(ttft_slo_s=None, e2e_slo_s=None)
    sched = SloScheduler(pred, budgets)

    def req(rid, age, ttft_b=None, e2e_b=None, max_new=8):
        r = types.SimpleNamespace(
            rid=rid, submit_t=-age, max_new=max_new,
            ttft_slo_s=ttft_b, e2e_slo_s=e2e_b,
            prompt=np.zeros(4, np.int32))
        return r

    # tight-budget request jumps the queue (least slack first)
    q = collections.deque([req(0, age=0.0, ttft_b=10.0),
                           req(1, age=0.0, ttft_b=0.5),
                           req(2, age=0.0)])          # unbudgeted: last
    pick, shed = sched.pick(q, now=0.0, bucket_of=lambda r: 8)
    assert pick.rid == 1 and shed == []
    assert [r.rid for r in q] == [0, 2]

    # a request whose age + optimistic service already exceeds its e2e
    # budget is shed; the rest survive
    q = collections.deque([req(3, age=5.0, e2e_b=1.0),
                           req(4, age=0.0, e2e_b=60.0)])
    pick, shed = sched.pick(q, now=0.0, bucket_of=lambda r: 8)
    assert [r.rid for r in shed] == [3]
    assert pick.rid == 4 and not q

    # a COLD predictor never sheds (optimistic-bound contract)
    cold = SloScheduler(TtftPredictor(), budgets)
    q = collections.deque([req(5, age=5.0, e2e_b=0.001)])
    pick, shed = cold.pick(q, now=0.0, bucket_of=lambda r: 8)
    assert pick.rid == 5 and shed == []


def test_engine_sheds_doomed_requests(params):
    """End-to-end shed: with a warmed predictor and an impossible e2e
    budget, queued requests are refused — ``shed`` True, ``result()``
    raises SheddedRequest, ``serving.shed_total`` counts — while the
    admissible request is served."""
    from paddle_tpu.serving import SheddedRequest

    eng = _engine(params, max_slots=1)
    rng = np.random.default_rng(11)
    eng.generate_many([rng.integers(1, VOCAB, (4,))],
                      max_new_tokens=8)   # warm the predictor
    assert eng.predictor.ready
    doomed = eng.submit(rng.integers(1, VOCAB, (4,)), max_new_tokens=8,
                        e2e_slo_s=1e-6)
    fine = eng.submit(rng.integers(1, VOCAB, (4,)), max_new_tokens=8)
    eng.run_until_idle()
    assert doomed.shed and doomed.slo_ok is False
    with pytest.raises(SheddedRequest):
        doomed.result(timeout=0)
    np.testing.assert_array_equal(
        fine.result(timeout=0)[:4], fine.prompt)
    st = eng.stats()
    assert st["serving.shed_total"] == 1
    assert st["serving.completed"] == 2  # warm + fine (shed excluded)
    assert eng.idle and eng.kv_pool.blocks_in_use >= 0


def test_fifo_scheduler_is_pr2_spelling(params):
    """scheduler="fifo" + prefix_reuse=False: arrival order, no shed,
    no trie — the benchmark baseline — still token-identical."""
    eng = _engine(params, scheduler="fifo", prefix_reuse=False,
                  max_slots=2)
    assert eng.prefix_trie is None
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, VOCAB, (l,)) for l in (3, 5, 4)]
    outs = eng.generate_many(prompts, max_new_tokens=6)
    for p, o in zip(prompts, outs):
        ref, _ = transformer.generate(params, p[None], max_len=T,
                                      n_layer=NL, n_head=NH, d_model=DM,
                                      return_logits=False)
        np.testing.assert_array_equal(o, np.asarray(ref)[0][: len(p) + 6])
    assert eng.stats().get("serving.shed_total", 0) == 0


def test_per_request_budgets_override_engine_defaults(params):
    """submit(ttft_slo_s=, e2e_slo_s=) wins over the engine defaults in
    the SLO verdict."""
    eng = _engine(params, ttft_slo_s=600.0, e2e_slo_s=600.0)
    rng = np.random.default_rng(13)
    loose = eng.submit(rng.integers(1, VOCAB, (4,)), max_new_tokens=4)
    tight = eng.submit(rng.integers(1, VOCAB, (4,)), max_new_tokens=4,
                       ttft_slo_s=1e-9)
    eng.run_until_idle()
    assert loose.slo_ok is True
    assert tight.slo_ok is False
    assert eng.stats()["serving.slo_violations"] == 1


def test_generate_many_is_never_shed(params):
    """The synchronous batch front-end waits for every result, so its
    requests are exempt from scheduler shedding — an impossible e2e
    budget yields N complete outputs (judged as violations), never a
    SheddedRequest destroying the batch."""
    eng = _engine(params, e2e_slo_s=1e-6, max_slots=1)
    rng = np.random.default_rng(15)
    eng.generate_many([rng.integers(1, VOCAB, (4,))],
                      max_new_tokens=4)   # warm the predictor
    assert eng.predictor.ready
    prompts = [rng.integers(1, VOCAB, (4,)) for _ in range(3)]
    outs = eng.generate_many(prompts, max_new_tokens=4)
    assert len(outs) == 3 and all(o.shape == (8,) for o in outs)
    st = eng.stats()
    assert st.get("serving.shed_total", 0) == 0
    assert st["serving.slo_violations"] == 4  # warm + 3, all judged


def test_sched_bucket_is_reuse_aware(params):
    """The scheduler's prefill estimate probes the trie (without
    touching LRU clocks): a mostly-cached prompt is costed at its
    suffix bucket, so the shed bound stays optimistic — a request reuse
    would save is never refused on full-prefill cost."""
    eng = _engine(params, block_tokens=4)
    rng = np.random.default_rng(16)
    base = rng.integers(1, VOCAB, (12,)).astype(np.int32)
    req = eng.submit(base.copy(), max_new_tokens=4)
    assert eng._sched_bucket(req) == eng.bucket_for(12)  # cold: full
    eng.run_until_idle()
    req2 = eng.submit(base.copy(), max_new_tokens=4)
    # 11 of 12 tokens cached (2 full blocks + 3-token CoW) -> suffix 1
    assert eng._sched_bucket(req2) == eng.bucket_for(1)
    def all_clocks(trie):
        out, stack = {}, list(trie._root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            out[id(n)] = n.last_used
        return out

    before = all_clocks(eng.prefix_trie)
    eng.prefix_trie.peek_hit(base, 11)
    assert all_clocks(eng.prefix_trie) == before  # LRU untouched
    eng.run_until_idle()


def test_pool_backpressure_requeues_and_counts_wait_once(params):
    """PoolExhausted at admission re-queues the victim at the front and
    retries once decode frees blocks; its serving.queue_wait is
    observed exactly once, at the admission that sticks."""
    eng = _engine(params, max_slots=2, block_tokens=4, cache_blocks=0,
                  prefix_reuse=False)
    rng = np.random.default_rng(17)
    a = eng.submit(rng.integers(1, VOCAB, (9,)), max_new_tokens=8)
    eng.step()                         # A admitted and decoding
    hoard = eng.kv_pool.alloc(eng.kv_pool.free_blocks)  # starve the pool
    b = eng.submit(rng.integers(1, VOCAB, (9,)), max_new_tokens=8)
    eng.step()                         # B hits PoolExhausted, re-queued
    assert not b.done and b.admit_t is None
    with eng._qlock:
        assert eng._queue[0] is b
    for blk in hoard:
        eng.kv_pool.deref(blk)
    eng.run_until_idle()
    assert a.error is None and b.error is None
    assert eng.stats()["serving.queue_wait"]["count"] == 2  # once each
    assert eng.kv_pool.blocks_in_use == 0


# -- slot-death fault injection (ISSUE 12 satellite) ------------------------

def test_slot_death_reclaims_blocks_and_driver_survives(params):
    """PADDLE_TPU_FAULT=slot_death:n kills one active request
    mid-decode: its KV blocks and slot are reclaimed (pool accounting
    returns to baseline — no block leak), the victim's handle completes
    with ``error`` set, and the background driver keeps serving the
    rest of the load."""
    import os

    from paddle_tpu.resilience import faults

    eng = _engine(params, max_slots=3, prefix_reuse=False)
    rng = np.random.default_rng(14)
    baseline_in_use = eng.kv_pool.blocks_in_use
    os.environ["PADDLE_TPU_FAULT"] = "slot_death:2"
    faults.reset()
    eng.start()
    try:
        reqs = [eng.submit(rng.integers(1, VOCAB, (5,)),
                           max_new_tokens=10) for _ in range(6)]
        for r in reqs:
            assert r.wait(timeout=120), "request did not finish"
    finally:
        eng.stop()
        os.environ.pop("PADDLE_TPU_FAULT", None)
        faults.reset()
    dead = [r for r in reqs if r.error is not None]
    ok = [r for r in reqs if r.error is None]
    assert len(dead) == 1 and len(ok) == 5
    # the victim's tokens stopped mid-stream; the survivors are exact
    for r in ok:
        ref, _ = transformer.generate(params, r.prompt[None], max_len=T,
                                      n_layer=NL, n_head=NH, d_model=DM,
                                      return_logits=False)
        np.testing.assert_array_equal(
            r.result(timeout=0),
            np.asarray(ref)[0][: len(r.prompt) + 10])
    # no block leak: pool accounting back to baseline, table zeroed
    assert eng.kv_pool.blocks_in_use == baseline_in_use == 0
    assert (eng._table == 0).all()
    st = eng.stats()
    assert st["serving.slot_deaths"] == 1
    assert st["serving.completed"] == 5
    assert eng.idle


# -- the serving path reads no tuner (PR 30) ---------------------------------

def test_engine_ignores_a_planted_tune_cache(params, tmp_path, monkeypatch):
    """A tune cache left on the machine cannot move the serving path: an
    engine built under ``PADDLE_TPU_TUNE_CACHE`` pointing at a file that
    holds ``serving_decode``, ``spec_decode`` and ``paged_attention``
    entries for exactly its shape keeps the constants every benchmark
    row ran with and lowers the same decode chunk as one built without
    the file."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import tune
    from paddle_tpu.serving import batched_decode as bd, depth_draft
    from paddle_tpu.tune.space import WorkloadKey

    def build():
        eng = ServingEngine(params, NL, NH, DM, max_len=T, max_slots=4,
                            draft_params=depth_draft(params, 1))
        fn = bd.make_decode_chunk(eng.arch, eng.decode_chunk, donate=False)
        text = fn.lower(eng._p, eng._pk, eng._pv, eng._last, eng._pos,
                        jnp.asarray(eng._table)).as_text()
        return eng, text

    monkeypatch.setenv("PADDLE_TPU_TUNE", "cached")
    monkeypatch.setenv("PADDLE_TPU_TUNE_CACHE", str(tmp_path / "none.json"))
    tune.reset_cache()
    try:
        plain, plain_text = build()

        monkeypatch.setenv("PADDLE_TPU_TUNE_CACHE",
                           str(tmp_path / "tuned.json"))
        tune.reset_cache()
        cache = tune.get_cache()
        capacity = plain.blocks_per_slot * plain.block_tokens
        for op, t, config in (
                ("serving_decode", T, {"chunk": 2, "min_bucket": 16}),
                ("spec_decode", T, {"k": 2}),
                ("paged_attention", capacity,
                 {"backend": "xla_ref", "block_step": 2})):
            cache.put(WorkloadKey(op, t, DM // NH, NH, "float32",
                                  jax.default_backend(), remat="-").s,
                      config)
        cache.save()
        tune.reset_cache()
        assert len(tune.get_cache().entries) == 3  # the file is sound

        planted, planted_text = build()
        for eng in (plain, planted):
            assert (eng.decode_chunk, eng.min_bucket, eng.spec_k) == (4, 8, 4)
        assert planted_text == plain_text
    finally:
        tune.reset_cache()


def test_serving_package_imports_no_tuner_and_no_kernel_switch():
    """The arrows point one way (serving -> kernels): no module under
    ``paddle_tpu/serving/`` imports ``paddle_tpu.tune``, at any level of
    nesting, and ``batched_decode.py`` imports no ``os`` (it has no
    environment variable to read: how a row attends through the table
    is ``kernels.paged_attention.attend``'s to decide)."""
    import ast
    import pathlib

    import paddle_tpu.serving as serving

    def imported(path):
        """Absolute dotted names a file imports, relative ones resolved
        against ``paddle_tpu.serving``."""
        out = []
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                out += [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                base = ["paddle_tpu", "serving"]
                base = base[:len(base) - node.level + 1] if node.level else []
                mod = ".".join(base + ([node.module] if node.module else []))
                out += [mod] + [f"{mod}.{a.name}" for a in node.names]
        return out

    files = sorted(pathlib.Path(serving.__file__).parent.glob("*.py"))
    assert len(files) >= 7
    for path in files:
        names = imported(path)
        assert names, path
        tuners = [n for n in names if n == "paddle_tpu.tune"
                  or n.startswith("paddle_tpu.tune.")]
        assert not tuners, (path.name, tuners)
        if path.name == "batched_decode.py":
            assert not [n for n in names if n.split(".")[0] == "os"], names
            assert "paddle_tpu.kernels.paged_attention" in names
