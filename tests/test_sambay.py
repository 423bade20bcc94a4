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

from paddle_tpu.models import sambay_reference as ref
from paddle_tpu.observability import trace
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import batched_decode as _bd
from paddle_tpu.serving.arch import SambaY

TINY = dict(rows=128, layers=8, heads=4, kv_heads=2, d=64, f=96, window=8,
            inner=128, state=4, taps=4, dt_rank=4)
PUBLISHED = dict(rows=200064, layers=32, heads=40, kv_heads=20, d=2560,
                 f=10240, window=512, inner=5120, state=16, taps=4,
                 dt_rank=160)
T, B, PIECE = 64, 4, 8
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


def _init(key, z, dtype):
    """Seeded weights under ``SambaY``'s names: the family's init
    (``chipbench/families/sambay.py``) at any size, matrices at 0.2
    where the family has 0.02 so that a width of 64 gives activations of
    order one."""
    keys = iter(jax.random.split(key, 16 * z["layers"] + 1))
    d, f, n, dh = z["d"], z["f"], z["inner"], z["d"] // z["heads"]
    kv = z["kv_heads"] * dh
    std = 0.02 if d > 1000 else 0.2

    def normal(*shape, scale=std):
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)

    p = {"tok_emb.w": normal(z["rows"], d), "ln_f.scale": 1 + normal(d),
         "ln_f.bias": normal(d)}
    for i, kind in enumerate(ref.layer_kinds(z["layers"])):
        b = f"block{i}_"
        p.update({b + "ffn_gu.w": normal(d, 2 * f),
                  b + "ffn_down.w": normal(f, d)})
        for ln in ("ln1", "ln2"):
            p[b + ln + ".scale"] = 1 + normal(d)
            p[b + ln + ".bias"] = normal(d)
        if kind == "mamba":
            dt = jnp.exp(jax.random.uniform(
                next(keys), (n,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
            p.update({
                b + "ssm_in.w": normal(d, 2 * n),
                b + "ssm_x.w": normal(n, z["dt_rank"] + 2 * z["state"]),
                b + "ssm_dt.w": normal(z["dt_rank"], n),
                b + "ssm_out.w": normal(n, d),
                b + "ssm_conv.w": jax.random.uniform(
                    next(keys), (n, z["taps"]), minval=-0.5,
                    maxval=0.5).astype(dtype),
                b + "ssm_conv.b": jax.random.uniform(
                    next(keys), (n,), minval=-0.5, maxval=0.5).astype(dtype),
                b + "ssm_dt.b": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                b + "ssm_A_log.w": jnp.broadcast_to(jnp.log(jnp.arange(
                    1.0, z["state"] + 1)), (n, z["state"])).astype(dtype),
                b + "ssm_D.w": jnp.ones((n,), dtype)})
        elif kind == "gmu":
            p.update({b + "gmu_in.w": normal(d, n),
                      b + "gmu_out.w": normal(n, d)})
        else:
            if kind == "cross":
                p.update({b + "att_q.w": normal(d, d),
                          b + "att_q.b": normal(d)})
            else:
                p.update({b + "att_qkv.w": normal(d, d + 2 * kv),
                          b + "att_qkv.b": normal(d + 2 * kv)})
            p.update({b + "att_out.w": normal(d, d),
                      b + "att_out.b": normal(d),
                      b + "att_subln.scale": 1 + normal(2 * dh)})
            for v in ("q1", "k1", "q2", "k2"):
                p[b + f"att_lambda_{v}.w"] = normal(dh, scale=0.3)
    return p


@pytest.fixture(scope="module")
def params():
    return {dt: _init(jax.random.PRNGKey(32), TINY, jnp.dtype(dt))
            for dt in ("float32", "bfloat16")}


def _arch(z=TINY):
    return SambaY(z["layers"], z["heads"], z["kv_heads"], z["d"],
                  window=z["window"], d_inner=z["inner"], d_state=z["state"],
                  conv_taps=z["taps"], dt_rank=z["dt_rank"])


def _engine(p, monkeypatch, **kw):
    monkeypatch.setattr(_bd, "PREFILL_PIECE", PIECE)
    reg = MetricsRegistry()
    kw.setdefault("max_slots", 2)
    kw.setdefault("prefix_reuse", False)
    eng = ServingEngine(p, arch=_arch(), max_len=T, block_tokens=B,
                        decode_chunk=4, min_bucket=4, donate=False,
                        registry=reg, **kw)
    return eng, reg


def _reference(p, tokens, **switches):
    z = TINY
    return np.asarray(ref.forward(
        p, np.asarray(tokens)[None], z["layers"], z["heads"], z["kv_heads"],
        z["window"], d_state=z["state"], dt_rank=z["dt_rank"],
        **switches))[0]


def _through_the_cache(eng, prompts, n_new):
    """Each prompt of ``prompts`` into a slot of its own: prefilled in
    the pieces the engine would dispatch (bucket padding and all), then
    ``n_new`` greedy decode steps for ALL slots at once, as the decode
    chunk batches them.  Returns per slot (tokens, logits at every
    position from the prompt's last on)."""
    arch = eng.arch
    S, nb = len(prompts), T // B
    table = jnp.asarray(1 + np.arange(S * nb).reshape(S, nb), jnp.int32)

    @jax.jit
    def window(p, pk, pv, st, toks, at, n, row, slot):
        x, pk, pv, st, _ = _bd._window_forward(
            p, pk, pv, toks[None], at[None], (at + n - 1)[None], row[None],
            arch, st, slot)
        return arch.head(p, x[0])[n - 1], pk, pv, st

    @jax.jit
    def step(p, pk, pv, st, tok, at):
        return _bd.paged_step_logits(p, tok, at, pk, pv, table, arch,
                                     st)[:4]

    pk, pv, st = eng._pk, eng._pv, eng._state
    # a slot's last request leaves its state behind: a prompt's first
    # piece must start from zeros all the same
    st = jax.tree.map(lambda a: a + 3.0, st)
    logits = [[] for _ in prompts]
    for s, prompt in enumerate(prompts):
        pieces = eng._pieces(np.asarray(prompt), 0)
        assert len(pieces) >= 2 and pieces[-1][0] > pieces[-1][3]
        for _w, padded, at, n in pieces:
            lg, pk, pv, st = window(eng._p, pk, pv, st, padded,
                                    jnp.int32(at), jnp.int32(n), table[s],
                                    jnp.int32(s))
        logits[s].append(lg)
    toks = [list(p_) for p_ in prompts]
    for _ in range(n_new):
        last = jnp.asarray([int(jnp.argmax(l[-1])) for l in logits],
                           jnp.int32)
        at = jnp.asarray([len(t_) for t_ in toks], jnp.int32)
        for s in range(S):
            toks[s].append(int(last[s]))
        lg, pk, pv, st = step(eng._p, pk, pv, st, last, at)
        for s in range(S):
            logits[s].append(lg[s])
    return [(np.asarray(t_), np.asarray(jnp.stack(l), np.float32))
            for t_, l in zip(toks, logits)]


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
            eng, _ = _engine(params[dt], mp)
            out[dt] = _through_the_cache(eng, PROMPTS, 14)
        return out
    finally:
        mp.undo()


def _positions(prompt_len, lg):
    """Reference rows that line up with ``lg``: the prompt's last
    position and every generated one but the last token's."""
    return slice(prompt_len - 1, prompt_len - 1 + len(lg))


@pytest.mark.parametrize("dtype,limit", [("float32", TOL),
                                         ("bfloat16", BF16_MARGIN)])
def test_prefill_in_pieces_then_decode_agrees_with_the_reference(
        served, params, dtype, limit):
    for (toks, lg), prompt in zip(served[dtype], PROMPTS):
        want = _reference(params[dtype], toks)[_positions(len(prompt), lg)]
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
        want = _reference(params["float32"], toks, **OMISSIONS[omission])
        worst = max(worst, float(np.abs(
            lg - want[_positions(len(prompt), lg)]).max()))
    assert worst > 200 * TOL, worst


def test_matrices_rounded_to_fp8_fail_the_float32_comparison(params,
                                                             monkeypatch):
    low = {k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype)
               if k.endswith(".w") and v.ndim == 2 else v)
           for k, v in params["float32"].items()}
    eng, _ = _engine(low, monkeypatch)
    worst = 0.0
    for (toks, lg), prompt in zip(_through_the_cache(eng, PROMPTS, 6),
                                  PROMPTS):
        want = _reference(params["float32"], toks)
        worst = max(worst, float(np.abs(
            lg - want[_positions(len(prompt), lg)]).max()))
    assert worst > 200 * TOL, worst


@pytest.mark.parametrize("dtype,limit", [("float32", 1e-3),
                                         ("bfloat16", BF16_MARGIN)])
def test_engine_serves_three_requests_over_two_slots(params, monkeypatch,
                                                     dtype, limit):
    """The whole engine: admission, pieces, decode chunks, a slot
    released and admitted again (three requests, two slots), the state
    arrays donated through the same executables."""
    eng, reg = _engine(params[dtype], monkeypatch)
    prompts = [PROMPTS[0], PROMPTS[1], (5 * np.arange(17) + 1) % 128]
    outs = eng.generate_many(prompts, max_new_tokens=[9, 14, 12])
    for prompt, full in zip(prompts, outs):
        n_p = len(prompt)
        assert np.array_equal(full[:n_p], prompt)
        want = _reference(params[dtype], full)[n_p - 1:len(full) - 1]
        gap = want.max(-1) - want[np.arange(len(want)), full[n_p:]]
        assert gap.max() < limit, gap.max()
    st = eng.stats()
    assert st["serving.completed"] == 3 and st["serving.admitted"] == 3
    assert eng.kv_pool.blocks_in_use == 0
    assert len(eng._prefill_fns) + 1 == (
        st["serving.prefill_compiles"] + st["serving.decode_compiles"])


def test_gauges_spans_and_the_streamed_bytes(params, monkeypatch):
    eng, reg = _engine(params["float32"], monkeypatch)
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
        _init(jax.random.PRNGKey(33), wide, jnp.bfloat16), arch=_arch(wide),
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
        ServingEngine(p, arch=_arch(), max_len=T, block_tokens=B,
                      prefix_reuse=False, draft_params=dict(p))


def test_the_published_sizes_count_3852m_parameters():
    shapes = jax.eval_shape(
        lambda: _init(jax.random.PRNGKey(0), PUBLISHED, jnp.bfloat16))
    total = sum(int(np.prod(a.shape)) for a in shapes.values())
    assert abs(total - 3_852e6) < 1e6, total
    arch = _arch(PUBLISHED)
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
