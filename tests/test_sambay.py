"""The decoder-hybrid-decoder stack (``serving/arch.py``, ``SambaY``:
Mamba state beside the pool, window and full differential attention over
fewer K/V heads than heads, one K/V plane read by several layers, gated
memory units) on the serving path, against its plain reference
(``models/sambay_reference.py``): logits of prefill in pieces and then
decode through the cache, two slots of different lengths, a slot
released and admitted again; the comparison bites on each line of the
mathematics left out and on a lower matmul precision; the engine refuses
a draft, which recurrent state makes impossible; the published sizes count 3,852M
parameters.

Tiny sizes (d 64, 8 layers so that all five mixers occur, 4 heads over 2
K/V heads of 16, window 8, inner width 128, state 4, 128 rows), seeded
random weights, on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from paddle_tpu.observability import trace
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.serving import ServingEngine
from tiny import sambay as fam

TINY = fam.sizes
PUBLISHED = dict(rows=200064, layers=32, heads=40, kv_heads=20, d=2560,
                 f=10240, window=512, inner=5120, state=16, taps=4,
                 dt_rank=160)
T, B = fam.max_len, fam.block_tokens
# float32 engine against the float32 reference: they differ in reduction
# order (the cache attends block by block with an online softmax, a
# window's matmuls reduce in another shape, a decode step advances the
# recurrence one position a call) and in the CPU backend's default f32
# dot, through 8 layers; the worst gap seen is 7.9e-6 on logits of
# deviation 1.6, so 5e-5 leaves six times of room, and the omissions
# below move the logits by 2.2 (the lambda term), 2.6 (the memory), 5.1
# (the window bound) and 3.4 (fp8 matrices): the tests ask 200 x TOL
TOL = 5e-5
# bfloat16 engine: every matmul output, every K/V row, the conv's rows
# and the residual stream round to 8 bits of mantissa through 8 layers:
# the logits themselves differ by up to 0.63, so it is judged as the
# benchmark judges it, by how far under the reference's maximum the
# reference rates each generated token; worst seen 0.057, twice that
BF16_MARGIN = 0.12


@pytest.fixture(scope="module")
def params():
    return {dt: fam.init(dtype=dt)
            for dt in ("float32", "bfloat16")}


PROMPTS = [np.arange(3, 3 + 21) % 128, (7 * np.arange(11) + 5) % 128]


@pytest.fixture(scope="module")
def served(params):
    """The float32 and bfloat16 engines' logits through the cache, made
    once: two slots, prompts of 21 and 11 tokens (pieces 8 + 8 + 8 with
    3 rows of padding, and 8 + 4 with 1), 14 decode steps, so that both
    contexts pass the window of 8 and a block boundary."""
    mp = pytest.MonkeyPatch()
    try:
        out = {}
        for dt in ("float32", "bfloat16"):
            eng, _ = fam.engine(params[dt], mp)
            out[dt] = tiny.through_the_cache(eng, PROMPTS, 14)[0]
        return out
    finally:
        mp.undo()


@pytest.mark.parametrize("dtype,limit", [("float32", TOL),
                                         ("bfloat16", BF16_MARGIN)])
def test_prefill_in_pieces_then_decode_agrees_with_the_reference(
        served, params, dtype, limit):
    for (toks, lg), prompt in zip(served[dtype], PROMPTS):
        want = fam.reference(params[dtype], toks)[
            tiny.positions(len(prompt), lg)]
        if dtype == "float32":
            assert np.abs(lg - want).max() < limit
        else:
            # judged as the benchmark's check judges it: each generated
            # token within the margin of the reference's maximum
            gen = toks[len(prompt):]
            gap = want[:len(gen)].max(-1) - want[np.arange(len(gen)), gen]
            assert gap.max() < limit, gap.max()


OMISSIONS = {
    "lambda_term_left_out": dict(lambda_term=False),
    "memory_replaced_by_ones": dict(memory=False),
    "window_bound_left_out": dict(windowed=False),
}


@pytest.mark.parametrize("omission", list(OMISSIONS))
def test_each_line_left_out_fails_the_float32_comparison(served, params,
                                                         omission):
    worst = 0.0
    for (toks, lg), prompt in zip(served["float32"], PROMPTS):
        want = fam.reference(params["float32"], toks, **OMISSIONS[omission])
        worst = max(worst, float(np.abs(
            lg - want[tiny.positions(len(prompt), lg)]).max()))
    assert worst > 200 * TOL, worst


def test_matrices_rounded_to_fp8_fail_the_float32_comparison(params,
                                                             monkeypatch):
    low = {k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype)
               if k.endswith(".w") and v.ndim == 2 else v)
           for k, v in params["float32"].items()}
    eng, _ = fam.engine(low, monkeypatch)
    worst = 0.0
    for (toks, lg), prompt in zip(tiny.through_the_cache(eng, PROMPTS, 6)[0],
                                  PROMPTS):
        want = fam.reference(params["float32"], toks)
        worst = max(worst, float(np.abs(
            lg - want[tiny.positions(len(prompt), lg)]).max()))
    assert worst > 200 * TOL, worst


@pytest.mark.parametrize("dtype,limit", [("float32", 1e-3),
                                         ("bfloat16", BF16_MARGIN)])
def test_engine_serves_three_requests_over_two_slots(params, monkeypatch,
                                                     dtype, limit):
    """The whole engine: admission, pieces, decode chunks, a slot
    released and admitted again (three requests, two slots), the state
    arrays donated through the same executables."""
    eng, reg = fam.engine(params[dtype], monkeypatch)
    prompts = [PROMPTS[0], PROMPTS[1], (5 * np.arange(17) + 1) % 128]
    outs = eng.generate_many(prompts, max_new_tokens=[9, 14, 12])
    for prompt, full in zip(prompts, outs):
        n_p = len(prompt)
        assert np.array_equal(full[:n_p], prompt)
        want = fam.reference(params[dtype], full)[n_p - 1:len(full) - 1]
        gap = want.max(-1) - want[np.arange(len(want)), full[n_p:]]
        assert gap.max() < limit, gap.max()
    st = eng.stats()
    assert st["serving.completed"] == 3 and st["serving.admitted"] == 3
    assert eng.kv_pool.blocks_in_use == 0
    assert len(eng._prefill_fns) + 1 == (
        st["serving.prefill_compiles"] + st["serving.decode_compiles"])


def test_gauges_spans_and_the_streamed_bytes(params, monkeypatch):
    eng, reg = fam.engine(params["float32"], monkeypatch)
    arch = eng.arch
    assert arch.kinds == ("mamba", "window", "mamba", "window", "mamba",
                          "full", "gmu", "cross")
    assert arch.planes == (8, 8, None) and arch.kv_planes == 3
    assert arch.plane_reads == ((8, 2), (None, 2))
    st = eng.stats()
    assert st["serving.kv_planes"] == 3
    assert st["serving.kv_planes{kind=window}"] == 2
    assert st["serving.kv_planes{kind=full}"] == 1
    assert st["serving.kv_heads"] == 2
    assert st["serving.plane_reads_per_token"] == 4
    per_slot = 3 * (128 * 4 * 4 + 3 * 128 * 4)
    assert st["serving.state_bytes_per_slot"] == per_slot
    assert st["serving.state_bytes"] == 2 * per_slot
    assert st["serving.kv_bytes_per_token"] == 3 * 2 * 2 * 16 * 4
    # a float32 pool has no row to spare: a write covers K/V heads only
    assert st["serving.kv_write_fill"] == 1.0
    blocks = 1 + 2 * (T // B)
    assert st["serving.kv_pool_bytes"] == 3 * blocks * 2 * B * 2 * 16 * 4
    assert [a.shape for a in eng._pk] == [(blocks, B, 1, 32)] * 3
    assert [[a.shape for a in layer] for layer in eng._state] == [
        [(2, 128, 4), (2, 3, 128)]] * 3

    # twenty K/V heads in bfloat16, the published count: ten pair rows in
    # the sixteen of a sliceable block, all sixteen written
    wide = dict(TINY, heads=20, kv_heads=20, d=320)
    bf16 = ServingEngine(
        fam.init(33, jnp.bfloat16, **wide), arch=fam.arch(**wide),
        max_len=T, block_tokens=B, max_slots=2, prefix_reuse=False,
        donate=False, registry=MetricsRegistry())
    assert bf16._pk[0].shape[2:] == (16, 32)
    assert bf16.stats()["serving.kv_write_fill"] == 0.625

    tracer = trace.Tracer(enabled=True)
    old = trace.get_tracer()
    trace.set_tracer(tracer)
    try:
        eng.generate_many([PROMPTS[1]], max_new_tokens=6)
    finally:
        trace.set_tracer(old)
    spans = {e["name"]: e["args"] for e in tracer.events()
             if e["name"] in ("serving.decode_chunk", "serving.prefill")}
    for name in ("serving.decode_chunk", "serving.prefill"):
        assert spans[name]["state_layers"] == 3
        assert spans[name]["plane_reads"] == 4
    # two chunks: the first step of each attends 12 and 16 keys; a
    # window plane the last 8 of them, twice each; 2 x 2 heads x 16 x 4 B
    # a cached position a plane
    token = 2 * 2 * 16 * 4
    st = eng.stats()
    assert st["serving.paged_bytes_streamed"] == (
        2 * (8 + 8) + 2 * (12 + 16)) * token
    # entries of 4 positions: a window plane 2 (positions 4..11) and 2
    # (8..15), the full plane 3 and 4; the mean over the four calls
    assert st["serving.paged_entries_live"] == (2 * (2 + 2) + 2 * (3 + 4)) / 4
    assert st["serving.paged_entries_total"] == 2 * 2 * (T // B)
    # the query heads of a K/V row go through an entry as rows of the
    # kernel's window, which folds the block once for all of them
    assert eng.arch.rows_per_entry == 2 * eng.arch.n_head // eng.arch.kv_heads
    assert st["serving.paged_rows_live"] == (
        st["serving.paged_entries_live"] * eng.arch.rows_per_entry)
    assert st["serving.paged_updates_live"] == st["serving.paged_entries_live"]
    # tables of 4 entries: the rule gives the shared fold one an iteration
    assert st["serving.paged_iterations_live"] == st[
        "serving.paged_entries_live"]


@pytest.mark.parametrize("refused", ["draft"])
def test_what_recurrent_state_makes_impossible_is_refused(params, refused):
    """A draft: the state cannot be rolled back.  (``prefix_reuse`` was
    refused too until a trie node could name a state snapshot: that case
    is ``test_state_prefix_hit.py``'s now.)"""
    p = params["float32"]
    with pytest.raises(ValueError, match="speculative decoding cannot "
                       "serve 'sambay'.*rolled back"):
        ServingEngine(p, arch=fam.arch(), max_len=T, block_tokens=B,
                      prefix_reuse=False, draft_params=dict(p))


def test_the_published_sizes_count_3852m_parameters():
    shapes = jax.eval_shape(
        lambda: fam.init(0, jnp.bfloat16, **PUBLISHED))
    total = sum(int(np.prod(a.shape)) for a in shapes.values())
    assert abs(total - 3_852e6) < 1e6, total
    arch = fam.arch(**PUBLISHED)
    assert arch.kinds.count("mamba") == 9 and arch.kinds.count("window") == 8
    assert arch.kinds.count("gmu") == 7 and arch.kinds.count("cross") == 7
    assert arch.kinds[16] == "mamba" and arch.kinds[17] == "full"
    assert arch.planes == (512,) * 8 + (None,) and arch.kv_planes == 9
    assert arch.plane_reads == ((512, 8), (None, 8))
    assert arch.kv_bytes_per_token(2) == 9 * 5120 == 46_080
    assert arch.state_bytes_per_slot("bfloat16") == 3_225_600
    # a bf16 block: 10 rows of (k1 | k2) in the 16 a sliceable block has
    assert arch.pool_block_shape(32, "bfloat16") == (32, 16, 128)
    assert arch.pool_block_shape(32, "float32") == (32, 10, 128)
