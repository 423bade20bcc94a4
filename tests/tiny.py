"""The tiny model of every served family, in ONE place: its sizes, its
seeded weights, its ``serving.arch`` object, the float32 reference the
tree has for it (imported, never copied) and an engine at the family's
one geometry; and the helpers every family's tests used to re-spell
(serve prompts through the cache and return the logits by position, the
routed layer alone, a share of the uncut experts, the worst margin of a
request).  Not a test module and not a ``conftest`` plugin: a test file
that serves a family imports THIS, never another test file
(``tests/test_repo_records.py`` holds that), so a family's sizes are
known to one module and a file that serves a family builds the engine
the family's own file builds.

A family is a ``Family``: ``sizes`` (a dict), ``init(seed, dtype,
**cut)``, ``arch(**cut)``, ``reference(params, tokens, **switches)`` and
``engine(params, monkeypatch, **kw)``.  ``cut`` names entries of
``sizes`` to override (``share=(0, 4)``, ``passes=1``); what is no entry
of ``sizes`` goes on to the family's own function (a reference's
switches, ``cls=``)."""

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from chipbench.families import delta_moe as _delta_family
from chipbench.families import delta_moe_reference
from paddle_tpu.models import (
    gated_moe_reference, latent_moe_reference, ouro_reference,
    retention_reference, sambay_reference, sink_window_moe_reference,
    sparse_latent_moe_reference, ssm_moe_reference, transformer)
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import arch as arch_mod
from paddle_tpu.serving import batched_decode as _bd


class Family:
    """One served family at its tiny size.  ``serve`` is the engine's
    geometry (``max_len``, ``max_slots``, ``block_tokens``, ...), ``piece``
    the prefill piece its tests give the engine (``None``: the
    program's own)."""

    def __init__(self, name, sizes, seed, init, arch, forward, serve,
                 piece=None, route=None, routed_norm="norm2"):
        self.name, self.sizes, self.seed = name, sizes, seed
        self._init, self._arch, self._forward = init, arch, forward
        self.serve, self.piece = serve, piece
        self.route, self.routed_norm = route, routed_norm

    rows = property(lambda self: self.sizes.get(
        "rows", self.sizes.get("vocab_size")), doc="vocabulary rows")
    max_len = property(lambda self: self.serve["max_len"])
    block_tokens = property(lambda self: self.serve["block_tokens"])
    max_slots = property(lambda self: self.serve["max_slots"])

    def _cut(self, kw):
        z = dict(self.sizes)
        z.update({k: kw.pop(k) for k in list(kw) if k in z})
        return z

    def init(self, seed=None, dtype=jnp.float32, **cut):
        """Seeded weights under the architecture's names (the uncut
        experts, for a routed family: ``share`` cuts them)."""
        z = self._cut(cut)
        return self._init(z, self.seed if seed is None else seed,
                          jnp.dtype(dtype), **cut)

    def arch(self, **cut):
        z = self._cut(cut)
        return self._arch(z, **cut)

    def reference(self, params, tokens, **switches):
        """The plain reference's logits ``[t, rows]`` for one sequence."""
        z = self._cut(switches)
        return np.asarray(self._forward(z, params, np.asarray(tokens)[None],
                                        **switches))[0]

    def engine(self, params, monkeypatch=None, registry=None, arch=None,
               **kw):
        """``(engine, registry)`` at the family's geometry; ``kw``
        overrides it."""
        if self.piece is not None:
            monkeypatch.setattr(_bd, "PREFILL_PIECE", self.piece)
        reg = registry or MetricsRegistry()
        return ServingEngine(params, arch=arch or self.arch(), registry=reg,
                             **dict(self.serve, **kw)), reg

    def held(self, uncut, dtypes=("float32",)):
        """This chip's share of ``uncut``, a dtype each."""
        held = share(uncut, *self.sizes["share"])
        return {dt: {k: v.astype(dt) for k, v in held.items()}
                for dt in dtypes}


# -- what every family's tests ask --------------------------------------------

def share(p, first, count):
    """The parameters a chip holding experts ``first .. first + count -
    1`` has: the stacked experts sliced, everything else whole."""
    return {k: (v[first:first + count] if "_experts_" in k else v)
            for k, v in p.items()}


def positions(prompt_len, lg):
    """Reference rows that line up with ``lg``: the prompt's last
    position and every generated one but the last token's."""
    return slice(prompt_len - 1, prompt_len - 1 + len(lg))


def margins(want, tokens):
    """How far under the reference's maximum the reference rates each of
    ``tokens``: ``want [n, rows]`` the logits they were chosen from."""
    return want.max(-1) - want[np.arange(len(want)), tokens]


def gaps(family, params, prompts, outs, **switches):
    """The worst gap, a request, between a generated token's reference
    logit and the reference's maximum."""
    worst = []
    longest = max(len(full) for full in outs)
    for prompt, full in zip(prompts, outs):
        full = np.asarray(full)
        assert np.array_equal(full[:len(prompt)], prompt)
        # every request at ONE length (zeros behind it: a causal model's
        # logits before them are what they were), so that the reference's
        # eager operations compile for one shape and not one a request
        padded = np.concatenate([full, np.zeros(longest - len(full),
                                                full.dtype)])
        at = family.reference(params, padded, **switches)[
            len(prompt) - 1:len(full) - 1]
        worst.append(float(np.max(margins(at, full[len(prompt):]))))
    return worst


def through_the_cache(eng, prompts, n_new):
    """Each prompt into a slot of its own, prefilled in the pieces the
    engine would dispatch (bucket padding and all), then ``n_new``
    greedy decode steps for ALL slots at once, as the decode chunk
    batches them; per-slot state, where the architecture has it, starts
    from what a slot's last request left behind.  Returns per slot
    (tokens, logits at every position from the prompt's last on) and
    the counts every call tallied."""
    arch = eng.arch
    S, nb = len(prompts), eng.max_len // eng.block_tokens
    table = jnp.asarray(1 + np.arange(S * nb).reshape(S, nb), jnp.int32)
    stateful = bool(eng._state)

    @jax.jit
    def window(p, pk, pv, st, toks, at, n, row, slot):
        x, pk, pv, st, counts = _bd._window_forward(
            p, pk, pv, toks[None], at[None], (at + n - 1)[None], row[None],
            arch, *((st, slot) if stateful else ()))
        return arch.head(p, x[0])[n - 1], pk, pv, st, counts

    @jax.jit
    def step(p, pk, pv, st, tok, at):
        return _bd.paged_step_logits(p, tok, at, pk, pv, table, arch, st)

    pk, pv = eng._pk, eng._pv
    # a slot's last request leaves its state behind: a prompt's first
    # piece must start from zeros all the same
    st = jax.tree.map(lambda a: a + 3.0, eng._state)
    logits, tallied = [[] for _ in prompts], []
    for s, prompt in enumerate(prompts):
        pieces = eng._pieces(np.asarray(prompt), 0)
        assert len(pieces) >= 2 and pieces[-1][0] > pieces[-1][3]
        for _w, padded, at, n in pieces:
            lg, pk, pv, st, counts = window(
                eng._p, pk, pv, st, padded, jnp.int32(at), jnp.int32(n),
                table[s], jnp.int32(s))
            tallied.append(("prefill", n, np.asarray(counts)))
        logits[s].append(lg)
    toks = [list(p_) for p_ in prompts]
    for _ in range(n_new):
        last = jnp.asarray([int(jnp.argmax(l[-1])) for l in logits],
                           jnp.int32)
        at = jnp.asarray([len(t_) for t_ in toks], jnp.int32)
        for s in range(S):
            toks[s].append(int(last[s]))
        lg, pk, pv, st, counts = step(eng._p, pk, pv, st, last, at)
        tallied.append(("decode", S, np.asarray(counts)))
        for s in range(S):
            logits[s].append(lg[s])
    return ([(np.asarray(t_), np.asarray(jnp.stack(l), np.float32))
             for t_, l in zip(toks, logits)], tallied)


def reference_counts(family, p, served, prompts, layers):
    """What each call of ``through_the_cache`` should have tallied, from
    the float32 reference's own selections at the same positions: ``[(n
    rows x layers, pairs on a held expert, held experts touched, held
    experts x layers)]`` in the calls' order, over ``layers`` routed
    layers."""
    first, count = family.sizes["share"]
    runs, tallied = served
    sels = []
    for toks, _ in runs:
        seen = []
        family.reference(p, toks, seen=seen)
        sels.append(np.stack([np.asarray(s)[0] for s in seen]))  # [L, t, k]

    def tally(sel, n):                                           # [L, n, k]
        held = (sel >= first) & (sel < first + count)
        return [n * layers, int(held.sum()),
                sum(len(np.unique(sel[l][held[l]])) for l in range(layers)),
                count * layers]

    out, calls = [], iter(tallied)
    for s, prompt in enumerate(prompts):        # the prefill pieces
        at = 0
        while at < len(prompt):
            phase, n, _ = next(calls)
            assert phase == "prefill"
            out.append(tally(sels[s][:, at:at + n], n))
            at += n
    for j, (phase, n, _) in enumerate(calls):   # the decode steps
        assert phase == "decode"
        out.append(tally(np.stack(
            [sels[s][:, len(prompts[s]) + j] for s in range(len(prompts))],
            axis=1), n))
    return out


def through_the_window_chains(eng, prompts, admit_at, steps):
    """Each prompt into a slot of its own before decode step
    ``admit_at[s]``, prefilled in the pieces the engine would dispatch,
    then greedy decode steps for ALL slots at once (a slot not admitted
    yet is a dead one).  The full planes go through whole chains; the
    window planes through the ENGINE'S OWN window chains where it has
    them (``eng.window_chains``: blocks are given back and handed to
    whoever asks next), else through whole chains too.  Returns per slot
    (tokens, logits at every position from the prompt's last on, the
    logits of every prompt row), the window blocks slot 0 gave back that
    another slot was handed while slot 0 still decoded, and the pools."""
    arch, chains = eng.arch, eng.window_chains
    S, nb = len(prompts), eng.max_len // eng.block_tokens
    whole = 1 + np.arange(S * nb, dtype=np.int32).reshape(S, nb)
    live = np.zeros(S, bool)

    def rows(s):
        if chains is None:
            return jnp.asarray(whole[s])
        return jnp.asarray(np.stack([whole[s], chains.table[s]]))

    def table():
        full = np.where(live[:, None], whole, 0).astype(np.int32)
        if chains is None:
            return jnp.asarray(full)
        return jnp.asarray(np.stack([full, chains.table[:S]], axis=1))

    def held(s):
        return set(chains.table[s][chains.table[s] > 0].tolist())

    @jax.jit
    def window(p, pk, pv, toks, at, n, row):
        x, pk, pv, _, _ = _bd._window_forward(
            p, pk, pv, toks[None], at[None], (at + n - 1)[None], row[None],
            arch)
        return arch.head(p, x[0]), pk, pv

    @jax.jit
    def step(p, pk, pv, tok, at, tbl):
        lg, pk, pv, _, _ = _bd.paged_step_logits(p, tok, at, pk, pv, tbl,
                                                 arch)
        return lg, pk, pv

    pk, pv = eng._pk, eng._pv
    logits = [[] for _ in prompts]
    pieces_logits = [[] for _ in prompts]
    toks = [list(p_) for p_ in prompts]
    given_back, reused = set(), set()
    for j in range(steps):
        for s, prompt in enumerate(prompts):
            if admit_at[s] != j:
                continue
            pieces = eng._pieces(np.asarray(prompt), 0)
            assert len(pieces) >= 2
            for _w, padded, at, n in pieces:
                if chains is not None:
                    chains.advance(s, at, at + n - 1)
                    if s:
                        reused |= given_back & held(s)
                lg, pk, pv = window(eng._p, pk, pv, padded, jnp.int32(at),
                                    jnp.int32(n), rows(s))
                pieces_logits[s].append(np.asarray(lg[:n]))
            live[s] = True
            logits[s].append(lg[n - 1])
        last = np.zeros(S, np.int32)
        at = np.zeros(S, np.int32)
        for s in range(S):
            if live[s]:
                last[s] = int(jnp.argmax(logits[s][-1]))
                at[s] = len(toks[s])
                toks[s].append(int(last[s]))
                if chains is not None:
                    before = held(s)
                    chains.advance(s, int(at[s]), int(at[s]))
                    if s == 0:
                        given_back |= before - held(s)
                    else:
                        reused |= given_back & (held(s) - before)
        lg, pk, pv = step(eng._p, pk, pv, jnp.asarray(last), jnp.asarray(at),
                          table())
        for s in range(S):
            if live[s]:
                logits[s].append(lg[s])
    return ([(np.asarray(t_), np.asarray(jnp.stack(l), np.float32),
              np.concatenate(pl))
             for t_, l, pl in zip(toks, logits, pieces_logits)], reused,
            (pk, pv))


class Rows:
    """The cache interface's ``valid`` for a routed layer called on its
    own."""

    def __init__(self, valid):
        self.valid = valid


def routed_alone(family, p, i, x, held, valid=None):
    """``arch.routed_ffn`` as the family's architecture calls it, layer
    ``i`` on rows ``x [n, d]`` for the share ``held`` of the uncut
    parameters ``p``: ``(output, counts)``."""
    arch = family.arch(share=held)
    w = share(p, *held)
    rows = Rows(jnp.ones(x.shape[:-1], bool) if valid is None else valid)
    h = arch_mod._rms(x, w[f"block{i}_{family.routed_norm}.scale"], arch.eps)
    args, how = family.route(arch)
    y, counts = arch_mod.routed_ffn(lambda nm: w[f"block{i}_{nm}"], h, rows,
                                    arch.experts, arch.top_k, *args, **how)
    return np.asarray(y), np.asarray(counts)


def train_steps(outs, feeds, steps=5, extra_fetch=()):
    """Run `steps` batches of identical data; return loss per step."""
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    fetch = [outs["avg_cost"]] + list(extra_fetch)
    losses = []
    for _ in range(steps):
        vals = exe.run(feed=feeds, fetch_list=fetch)
        losses.append(float(np.asarray(vals[0]).ravel()[0]))
    losses = np.asarray(losses)
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"
    return losses


def _normal(keys, dtype):
    def normal(*shape, scale=0.2):
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)
    return normal


# -- gpt2 ---------------------------------------------------------------------
# random weights under the serving names: its tests compare two spellings
# of one forward, so nothing has to be trained

def _gpt2_init(z, seed, dtype):
    rng = np.random.default_rng(seed)
    d, rows = z["d"], z["rows"]

    def w(*shape, scale=0.2):
        return jnp.asarray(rng.normal(0.0, scale, shape), dtype)

    p = {"tok_emb.w": w(rows, d), "pos_emb.w.w": w(z["max_len"], d),
         "ln_f.scale": 1 + w(d), "ln_f.bias": w(d), "lm_head.w": w(d, rows)}
    for i in range(z["layers"]):
        for nm, shape in (("att_q", (d, d)), ("att_k", (d, d)),
                          ("att_v", (d, d)), ("att_out", (d, d)),
                          ("ffn1", (d, 4 * d)), ("ffn2", (4 * d, d))):
            p[f"block{i}_{nm}.w"] = w(*shape)
            p[f"block{i}_{nm}.b"] = w(shape[1], scale=0.05)
        for ln in ("ln1", "ln2"):
            p[f"block{i}_{ln}.scale"] = 1 + w(d)
            p[f"block{i}_{ln}.bias"] = w(d)
    return p


def _gpt2_forward(z, p, tokens):
    return transformer.generate(
        p, tokens, max_len=tokens.shape[1], n_layer=z["layers"],
        n_head=z["heads"], d_model=z["d"], eps=z["eps"])[1]


gpt2 = Family(
    "gpt2", {"rows": 61, "layers": 2, "heads": 2, "d": 32, "eps": 1e-5,
             "max_len": 64}, 11, _gpt2_init,
    lambda z: arch_mod.Gpt2(z["layers"], z["heads"], z["d"], z["eps"]),
    _gpt2_forward,
    dict(max_len=64, max_slots=3, block_tokens=4, decode_chunk=4,
         min_bucket=4, donate=False), piece=8)


BUILT_VOCAB = 50


def gpt2_built(vocab=BUILT_VOCAB, max_len=32, dtype="float32"):
    """The same two layers of 32 through the framework's own startup
    program (what ``transformer.extract_params`` hands a server), for the
    tests that compare with ``transformer.generate``: serving needs no
    trained model, greedy chains over random weights are deterministic."""
    z = gpt2.sizes
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        transformer.build(vocab_size=vocab, n_layer=z["layers"],
                          n_head=z["heads"], d_model=z["d"], max_len=max_len,
                          dropout_rate=0.0, dtype=dtype)
    pt.Executor().run(startup)
    return transformer.extract_params(program=main)


# -- ouro: LoopedRmsRope ------------------------------------------------------
# d 64, 4 heads of 16, f 96, 2 layers, 3 passes, 128 rows; non-unit norm
# scales

def _ouro_init(z, seed, dtype):
    rng = np.random.default_rng(seed)
    d, f, rows = z["d"], z["f"], z["rows"]

    def w(*shape, scale=0.2):
        return jnp.asarray(rng.normal(0.0, scale, shape), dtype)

    p = {"tok_emb.w": w(rows, d, scale=1.0), "norm_f.scale": 1 + w(d),
         "exit_gate.w": w(d, 1), "exit_gate.b": w(1),
         "lm_head.w": w(d, rows)}
    for i in range(z["layers"]):
        for nm, shape in (("att_q", (d, d)), ("att_k", (d, d)),
                          ("att_v", (d, d)), ("att_out", (d, d)),
                          ("ffn_gate", (d, f)), ("ffn_up", (d, f)),
                          ("ffn_down", (f, d))):
            p[f"block{i}_{nm}.w"] = w(*shape)
        for nm in ("norm1", "norm2", "norm3", "norm4"):
            p[f"block{i}_{nm}.scale"] = 1 + w(d)
    return p


ouro = Family(
    "ouro", {"rows": 128, "layers": 2, "heads": 4, "d": 64, "f": 96,
             "passes": 3, "eps": 1e-6, "theta": 1e6}, 28, _ouro_init,
    lambda z, cls=arch_mod.LoopedRmsRope: cls(
        z["layers"], z["heads"], z["d"], z["passes"], eps=z["eps"],
        rope_theta=z["theta"]),
    lambda z, p, tokens, **kw: ouro_reference.logits(
        p, tokens, z["layers"], z["heads"], eps=z["eps"],
        rope_theta=z["theta"], passes=z["passes"], **kw),
    dict(max_len=64, max_slots=3, block_tokens=4, decode_chunk=4,
         min_bucket=4, donate=False), piece=8)


# -- sambay: SambaY -----------------------------------------------------------
# d 64, 8 layers so that all five mixers occur, 4 heads over 2 K/V heads
# of 16, window 8, inner width 128, state 4, 128 rows

def _sambay_init(z, seed, dtype):
    """The family's init (``chipbench/families/sambay.py``) at any size,
    matrices at 0.2 where the family has 0.02 so that a width of 64
    gives activations of order one."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 16 * z["layers"] + 1))
    d, f, n, dh = z["d"], z["f"], z["inner"], z["d"] // z["heads"]
    kv = z["kv_heads"] * dh
    normal_ = _normal(keys, dtype)
    std = 0.02 if d > 1000 else 0.2

    def normal(*shape, scale=std):
        return normal_(*shape, scale=scale)

    p = {"tok_emb.w": normal(z["rows"], d), "ln_f.scale": 1 + normal(d),
         "ln_f.bias": normal(d)}
    for i, kind in enumerate(sambay_reference.layer_kinds(z["layers"])):
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


sambay = Family(
    "sambay", dict(rows=128, layers=8, heads=4, kv_heads=2, d=64, f=96,
                   window=8, inner=128, state=4, taps=4, dt_rank=4), 32,
    _sambay_init,
    lambda z: arch_mod.SambaY(
        z["layers"], z["heads"], z["kv_heads"], z["d"], window=z["window"],
        d_inner=z["inner"], d_state=z["state"], conv_taps=z["taps"],
        dt_rank=z["dt_rank"]),
    lambda z, p, tokens, **switches: sambay_reference.forward(
        p, tokens, z["layers"], z["heads"], z["kv_heads"], z["window"],
        d_state=z["state"], dt_rank=z["dt_rank"], **switches),
    dict(max_len=64, max_slots=2, block_tokens=4, decode_chunk=4,
         min_bucket=4, donate=False, prefix_reuse=False), piece=8)


# -- gated_moe: GatedMoE ------------------------------------------------------
# heads of 32 where d / heads is 16; 16 experts, top 4, 4 held (4..7)

def _gated_moe_init(z, seed, dtype):
    """Matrices at 0.2 (a width of 64 then gives activations of order
    one), the router at 0.3 so that its scores spread without
    saturating, gains near one before a sub-layer and ``1 / sqrt(2
    layers)`` after it; the experts stacked per layer."""
    n = len(z["types"])
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 24 * n + 4))
    d, dh, e, experts = z["d"], z["dh"], z["e"], z["experts"]
    q, kv = z["heads"] * dh, z["kv_heads"] * dh
    normal = _normal(keys, dtype)
    branch = (2 * n) ** -0.5
    p = {"tok_emb.w": normal(z["rows"], d, scale=0.1),
         "norm_f.scale": 1 + normal(d), "lm_head.w": normal(d, z["rows"])}
    for i in range(n):
        b = f"block{i}_"
        p.update({
            b + "norm1.scale": 1 + normal(d), b + "norm3.scale": 1 + normal(d),
            b + "norm2.scale": branch * (1 + normal(d)),
            b + "norm4.scale": branch * (1 + normal(d)),
            b + "att_q.w": normal(d, q), b + "att_gate.w": normal(d, q),
            b + "att_k.w": normal(d, kv), b + "att_v.w": normal(d, kv),
            b + "att_out.w": normal(q, d),
            b + "att_qnorm.scale": 1 + normal(dh),
            b + "att_knorm.scale": 1 + normal(dh)})
        if i < z["dense"]:
            p.update({b + "ffn_gate.w": normal(d, z["f"]),
                      b + "ffn_up.w": normal(d, z["f"]),
                      b + "ffn_down.w": normal(z["f"], d)})
        else:
            p.update({
                b + "router.w": normal(d, experts, scale=0.3),
                b + "router.bias": normal(experts, scale=0.05),
                b + "shared_gate.w": normal(d, e),
                b + "shared_up.w": normal(d, e),
                b + "shared_down.w": normal(e, d),
                b + "experts_gate.w": normal(experts, d, e),
                b + "experts_up.w": normal(experts, d, e),
                b + "experts_down.w": normal(experts, e, d)})
    return p


gated_moe = Family(
    "gated_moe",
    {"d": 64, "heads": 4, "kv_heads": 2, "dh": 32, "f": 128, "e": 48,
     "experts": 16, "top_k": 4, "share": (4, 4), "window": 8,
     "types": ("window", "window", "window", "window", "full"),
     "dense": 1, "rows": 128, "scale": 2.448}, 34, _gated_moe_init,
    lambda z: arch_mod.GatedMoE(
        z["types"], z["heads"], z["kv_heads"], z["dh"], z["d"],
        window=z["window"], dense_layers=z["dense"],
        router_width=z["experts"], top_k=z["top_k"], experts=z["share"],
        route_scale=z["scale"]),
    lambda z, p, tokens, **switches: gated_moe_reference.forward(
        p, tokens, z["types"], z["heads"], z["kv_heads"], z["window"],
        z["dense"], z["top_k"], z["share"], z["scale"], **switches),
    dict(max_len=48, max_slots=2, block_tokens=4, decode_chunk=4,
         min_bucket=4, donate=False, prefix_reuse=False), piece=8,
    route=lambda arch: ((arch.route_scale,), {}), routed_norm="norm3")


# -- latent_moe: LatentMoE ----------------------------------------------------
# 4 heads of 16 | 8 query lanes and 16 value lanes over a latent of 32;
# 16 experts, top 3, 4 held (4..7), the shared MLP 2 x 24 wide; a dense
# layer and three routed ones; YaRN factor 4 over an original 16

def _latent_moe_init(z, seed, dtype):
    """Matrices at 0.2, the router at 0.5 so that its softmax spreads,
    gains near one; the experts stacked per layer."""
    n = z["layers"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 24 * n + 4))
    d, e, h, experts = z["d"], z["e"], z["heads"], z["experts"]
    normal = _normal(keys, dtype)
    p = {"tok_emb.w": normal(z["rows"], d, scale=1.0),
         "norm_f.scale": 1 + normal(d), "lm_head.w": normal(d, z["rows"])}
    for i in range(n):
        b = f"block{i}_"
        p.update({
            b + "norm1.scale": 1 + normal(d), b + "norm2.scale": 1 + normal(d),
            b + "att_q.w": normal(d, h * (z["nope"] + z["rope"])),
            b + "att_kva.w": normal(d, z["rank"] + z["rope"]),
            b + "att_kvnorm.scale": 1 + normal(z["rank"]),
            b + "att_kvb.w": normal(z["rank"], h * (z["nope"] + z["v"])),
            b + "att_out.w": normal(h * z["v"], d, scale=0.1)})
        if i < z["dense"]:
            p.update({b + "ffn_gate.w": normal(d, z["f"]),
                      b + "ffn_up.w": normal(d, z["f"]),
                      b + "ffn_down.w": normal(z["f"], d, scale=0.1)})
        else:
            p.update({
                b + "router.w": normal(d, experts, scale=0.5),
                b + "shared_gate.w": normal(d, z["shared_f"]),
                b + "shared_up.w": normal(d, z["shared_f"]),
                b + "shared_down.w": normal(z["shared_f"], d, scale=0.1),
                b + "experts_gate.w": normal(experts, d, e),
                b + "experts_up.w": normal(experts, d, e),
                b + "experts_down.w": normal(experts, e, d)})
    return p


def _latent_moe_arch(z):
    theta, factor, original, fast, slow, m, m_all = z["yarn"]
    return arch_mod.LatentMoE(
        z["layers"], z["heads"], z["d"], rank=z["rank"], nope_dim=z["nope"],
        rope_dim=z["rope"], v_dim=z["v"], dense_layers=z["dense"],
        router_width=z["experts"], top_k=z["top_k"], experts=z["share"],
        route_scale=z["scale"], rope_theta=theta, rope_factor=factor,
        rope_original=original, beta_fast=fast, beta_slow=slow, mscale=m,
        mscale_all_dim=m_all)


latent_moe = Family(
    "latent_moe",
    {"d": 64, "heads": 4, "nope": 16, "rope": 8, "v": 16, "rank": 32,
     "f": 128, "e": 24, "shared_f": 48, "experts": 16, "top_k": 3,
     "share": (4, 4), "layers": 4, "dense": 1, "rows": 128, "scale": 1.0,
     "yarn": (10000.0, 4.0, 16, 32.0, 1.0, 0.707, 0.707)}, 40,
    _latent_moe_init, _latent_moe_arch,
    lambda z, p, tokens, **switches: latent_moe_reference.forward(
        p, tokens, z["layers"], z["heads"], z["rank"], z["nope"], z["rope"],
        z["v"], z["dense"], z["top_k"], z["share"], z["scale"], z["yarn"],
        **switches),
    dict(max_len=48, max_slots=2, block_tokens=4, decode_chunk=4,
         min_bucket=4, donate=False, prefix_reuse=False), piece=8,
    route=lambda arch: ((arch.route_scale,), dict(
        score="softmax", normalise=False, bias=False)))


# -- sink_window_moe: SinkWindowMoE -------------------------------------------
# keys of 24 lanes over values of 16, 8 of the 24 rotated; one K/V head
# on full planes, two on window planes; 16 experts, top 4, 4 held (4..7)

def _sink_window_moe_init(z, seed, dtype):
    """Matrices at 0.2, the router at 0.3, gains near one, sinks around
    the score of a strong key."""
    n = len(z["types"])
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16 * n + 4))
    d, dh, dv, e, experts = z["d"], z["dh"], z["dv"], z["e"], z["experts"]
    normal = _normal(keys, dtype)
    branch = (2 * n) ** -0.5
    p = {"tok_emb.w": normal(z["rows"], d, scale=1.0),
         "norm_f.scale": 1 + normal(d), "lm_head.w": normal(d, z["rows"])}
    for i, kind in enumerate(z["types"]):
        b = f"block{i}_"
        hk = z["wkv"] if kind == "window" else z["kv"]
        p.update({
            b + "norm1.scale": 1 + normal(d), b + "norm2.scale": 1 + normal(d),
            b + "att_qkv.w": normal(d, z["heads"] * dh + hk * (dh + dv)),
            b + "att_out.w": normal(z["heads"] * dv, d, scale=0.2 * branch)})
        if kind == "window":
            p[b + "att_sink.b"] = 1.0 + normal(z["heads"], scale=1.0)
        if i < z["dense"]:
            p.update({b + "ffn_gate.w": normal(d, z["f"]),
                      b + "ffn_up.w": normal(d, z["f"]),
                      b + "ffn_down.w": normal(z["f"], d,
                                               scale=0.2 * branch)})
        else:
            p.update({
                b + "router.w": normal(d, experts, scale=0.3),
                b + "router.bias": normal(experts, scale=0.05),
                b + "experts_gate.w": normal(experts, d, e),
                b + "experts_up.w": normal(experts, d, e),
                b + "experts_down.w": normal(experts, e, d)})
    return p


sink_window_moe = Family(
    "sink_window_moe",
    {"d": 64, "heads": 4, "kv": 1, "wkv": 2, "dh": 24, "dv": 16, "rot": 8,
     "f": 128, "e": 48, "experts": 16, "top_k": 4, "share": (4, 4),
     "window": 8, "scale": 0.707,
     "types": ("full", "window", "window", "full", "window"), "dense": 1,
     "rows": 128, "theta": 1e7, "wtheta": 1e4}, 46, _sink_window_moe_init,
    lambda z: arch_mod.SinkWindowMoE(
        z["types"], z["heads"], z["kv"], z["wkv"], z["dh"], z["dv"], z["d"],
        window=z["window"], rotary_lanes=z["rot"], dense_layers=z["dense"],
        router_width=z["experts"], top_k=z["top_k"], experts=z["share"],
        value_scale=z["scale"], rope_theta=z["theta"],
        window_rope_theta=z["wtheta"]),
    lambda z, p, tokens, **switches: sink_window_moe_reference.forward(
        p, tokens, z["types"], z["heads"], z["kv"], z["wkv"], z["dh"],
        z["window"], z["rot"], z["dense"], z["top_k"], z["share"],
        **dict(dict(value_scale=z["scale"], rope_theta=z["theta"],
                    window_rope_theta=z["wtheta"]), **switches)),
    dict(max_len=64, max_slots=3, block_tokens=4, decode_chunk=4,
         min_bucket=4, donate=False, prefix_reuse=False), piece=8,
    route=lambda arch: ((), dict(normalise=arch.norm_topk, shared=False)))


# -- sparse_latent_moe: SparseLatentMoE ---------------------------------------
# two latent ranks and two head counts by layer type, an ``index_topk``
# (12) and a window (9) SMALLER than the contexts

def _sparse_shapes(z):
    return sparse_latent_moe_reference.param_shapes(
        z["d"], z["rows"], z["f"], z["e"], z["experts"], z["experts"],
        z["types"], z["full"], z["sliding"], z["index_heads"],
        z["index_dim"], z["dense"])


def _sparse_latent_moe_init(z, seed, dtype):
    """Matrices at 0.2 (widths of 16-64 then give activations of order
    one), gains near one, the index LayerNorm's bias and the router's
    around zero."""
    shapes = _sparse_shapes(z)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), len(shapes)))
    branch = (2 * len(z["types"])) ** -0.5
    p = {}
    for name, shape in shapes.items():
        k = next(keys)
        if name.endswith(".scale"):
            p[name] = 1 + 0.2 * jax.random.normal(k, shape)
        elif name.endswith(".bias"):
            p[name] = 0.05 * jax.random.normal(k, shape)
        else:
            scale = 1.0 if name == "tok_emb.w" else 0.3 if name.endswith(
                "router.w") else 0.2 * branch if name.endswith(
                    ("att_out.w", "ffn_down.w")) else 0.2
            p[name] = scale * jax.random.normal(k, shape)
    return {k: v.astype(dtype) for k, v in p.items()}


sparse_latent_moe = Family(
    "sparse_latent_moe",
    {"d": 64, "f": 96, "e": 24, "experts": 16, "top_k": 4, "share": (4, 4),
     "window": 9, "index_heads": 3, "index_dim": 16, "index_topk": 12,
     "scale": 1.0,
     "types": ("full", "full", "sliding", "sliding", "sliding"),
     "dense": 1, "rows": 128,
     "full": {"heads": 4, "q_rank": 24, "rank": 16, "nope": 8, "rope": 8,
              "v": 8, "theta": 8e7},
     "sliding": {"heads": 2, "q_rank": 16, "rank": 32, "nope": 12,
                 "rope": 8, "v": 8, "theta": 5e4}}, 55,
    _sparse_latent_moe_init,
    lambda z: arch_mod.SparseLatentMoE(
        z["types"], z["d"], z["full"], z["sliding"], window=z["window"],
        index_heads=z["index_heads"], index_dim=z["index_dim"],
        index_topk=z["index_topk"], dense_layers=z["dense"],
        router_width=z["experts"], top_k=z["top_k"], experts=z["share"],
        route_scale=z["scale"]),
    lambda z, p, tokens, **switches: sparse_latent_moe_reference.forward(
        p, tokens, **dict(dict(
            layer_types=z["types"], full=z["full"], sliding=z["sliding"],
            window=z["window"], index_heads=z["index_heads"],
            index_dim=z["index_dim"], index_topk=z["index_topk"],
            dense_layers=z["dense"], top_k=z["top_k"], experts=z["share"],
            route_scale=z["scale"]), **switches)),
    dict(max_len=64, max_slots=3, block_tokens=4, decode_chunk=4,
         min_bucket=4, donate=False, prefix_reuse=False), piece=8,
    route=lambda arch: ((arch.route_scale,), arch.route_how))


# -- retention: PowerRetention ------------------------------------------------
# 3 layers x 64 wide, 6 heads over 2 K/V heads of 16

def _retention_init(z, seed, dtype, std=0.08):
    rng = np.random.default_rng(seed)
    L, H, HK, D, DH, F, V = (z[k] for k in (
        "layers", "heads", "kv_heads", "d", "dh", "f", "rows"))

    def n(*shape):
        return (std * rng.normal(size=shape)).astype(np.float32)

    ones = lambda k: np.ones(k, np.float32)                      # noqa: E731
    p = {"tok_emb.w": n(V, D), "lm_head.w": n(D, V), "norm_f.scale": ones(D)}
    for i in range(L):
        b = f"block{i}_"
        horizon = np.array([8.0, 90.0])
        p.update({
            b + "att_q.w": n(D, H * DH), b + "att_k.w": n(D, HK * DH),
            b + "att_v.w": n(D, HK * DH), b + "att_out.w": n(H * DH, D),
            b + "att_gate.w": n(D, HK),
            b + "att_gate.b": np.log(horizon - 1).astype(np.float32),
            b + "att_qnorm.scale": ones(DH), b + "att_knorm.scale": ones(DH),
            b + "norm1.scale": ones(D), b + "norm2.scale": ones(D),
            b + "ffn_gate.w": n(D, F), b + "ffn_up.w": n(D, F),
            b + "ffn_down.w": n(F, D)})
    return p


retention = Family(
    "retention", {"layers": 3, "heads": 6, "kv_heads": 2, "d": 64, "dh": 16,
                  "f": 128, "rows": 97, "theta": 1e6}, 0, _retention_init,
    lambda z: arch_mod.PowerRetention(
        z["layers"], z["heads"], z["kv_heads"], z["d"], z["dh"], z["f"],
        rope_theta=z["theta"]),
    lambda z, p, tokens, **switches: retention_reference.forward(
        p, tokens, z["layers"], z["heads"], z["kv_heads"], z["theta"],
        **switches),
    dict(max_len=400, max_slots=3, prefix_reuse=False, cache_blocks=0))


# -- ssm_moe: MambaMoE --------------------------------------------------------
# seven layers x 48 wide at widths that keep the published model's oddness:
# an expert width that is not a multiple of 128, fewer groups than heads,
# two K/V heads under eight query heads; 16 routed, top 3, 4 held (4..7)

def _ssm_moe_init(z, seed, dtype, std=0.08):
    """Float32 parameters holding the experts ``z["share"]`` of the
    router's 16: every share draws the SAME 16 experts and holds its
    own."""
    rng = np.random.default_rng(seed)
    D, V, H, P, G, N = (z[k] for k in ("d", "rows", "ssm_heads", "ssm_dh",
                                       "groups", "state"))
    inner, conv = H * P, H * P + 2 * G * N

    def n(*shape):
        return (std * rng.normal(size=shape)).astype(np.float32)

    ones = lambda k: np.ones(k, np.float32)                      # noqa: E731
    first, count = z["share"]
    p = {"tok_emb.w": n(V, D), "lm_head.w": n(D, V), "norm_f.scale": ones(D)}
    for i, kind in enumerate(z["pattern"]):
        b = f"block{i}_"
        p[b + "norm.scale"] = ones(D)
        if kind == "M":
            step = np.exp(rng.uniform(np.log(0.01), np.log(0.3), H))
            p.update({
                b + "ssm_in.w": n(D, 2 * inner + 2 * G * N + H) * 4,
                b + "ssm_conv.w": rng.uniform(
                    -0.5, 0.5, (conv, z["taps"])).astype(np.float32),
                b + "ssm_conv.b": rng.uniform(-0.5, 0.5, conv).astype(
                    np.float32),
                b + "ssm_dt.b": (step + np.log(-np.expm1(-step))).astype(
                    np.float32),
                b + "ssm_A_log.w": np.log(rng.uniform(1, 16, H)).astype(
                    np.float32),
                b + "ssm_D.w": ones(H), b + "ssm_norm.scale": ones(inner),
                b + "ssm_out.w": n(inner, D)})
        elif kind == "*":
            p.update({b + "att_qkv.w": n(
                          D, (z["heads"] + 2 * z["kv_heads"]) * z["dh"]) * 3,
                      b + "att_out.w": n(z["heads"] * z["dh"], D)})
        else:
            width, e = z["experts"], z["e"]
            up, down = n(width, e, D) * 3, n(width, e, D) * 3
            p.update({b + "router.w": n(D, width) * 5,
                      b + "router.bias": n(width),
                      b + "shared_up.w": n(D, z["shared_f"]) * 3,
                      b + "shared_down.w": n(z["shared_f"], D),
                      b + "experts_up.w": up[first:first + count],
                      b + "experts_down.w": down[first:first + count]})
    return p


ssm_moe = Family(
    "ssm_moe",
    {"pattern": "MEM*EME", "d": 48, "rows": 97, "heads": 8, "kv_heads": 2,
     "dh": 16, "ssm_heads": 8, "ssm_dh": 8, "groups": 2, "state": 16,
     "taps": 4, "e": 40, "shared_f": 72, "experts": 16, "top_k": 3,
     "scale": 2.5, "share": (4, 4)}, 0, _ssm_moe_init,
    lambda z, **kw: arch_mod.MambaMoE(
        z["pattern"], z["heads"], z["kv_heads"], z["dh"], z["d"],
        ssm_heads=z["ssm_heads"], ssm_head_dim=z["ssm_dh"],
        ssm_groups=z["groups"], ssm_state=z["state"], conv_taps=z["taps"],
        router_width=z["experts"], top_k=z["top_k"], experts=z["share"],
        route_scale=z["scale"], chunk_size=8, **kw),
    lambda z, p, tokens, **switches: ssm_moe_reference.forward(
        p, tokens, z["pattern"], z["heads"], z["kv_heads"], z["ssm_heads"],
        z["groups"], z["top_k"], z["share"], z["scale"], **switches),
    dict(max_len=400, max_slots=3, prefix_reuse=False, cache_blocks=0,
         block_tokens=8))


# -- delta_moe: DeltaMoE ------------------------------------------------------
# the published layout at a width the CPU can run: one period G D D D, 2
# K/V heads under 4 query heads, 4 of 16 experts held (4..7), top 3

def _delta_cfg(z):
    cfg = {k: v for k, v in z.items() if k != "share"}
    cfg["experts_first"], cfg["n_routed_experts"] = z["share"]
    return cfg


def _delta_moe_init(z, seed, dtype):
    """Float32 parameters holding the experts ``z["share"]`` of the
    router's 16: every share draws the SAME 16 experts and holds its
    own."""
    rng = np.random.default_rng(seed)
    first, count = z["share"]
    whole = _delta_family.shapes(dict(
        _delta_cfg(z), n_routed_experts=z["router_width"]))
    p = {}
    for name, shape in whole.items():
        kind = name.split("_", 1)[-1]
        if kind.endswith(".scale"):
            a = np.ones(shape)
        elif kind == "delta_conv.w":
            a = rng.uniform(-0.5, 0.5, shape)
        elif kind == "delta_A_log.w":
            a = np.log(rng.uniform(1, 16, shape))
        elif kind == "delta_dt.b":
            step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), shape))
            a = step + np.log(-np.expm1(-step))
        elif kind in ("router.w", "delta_beta.w"):
            a = 0.5 * rng.normal(size=shape)
        else:
            a = (1.0 if name == "tok_emb.w" else 0.15) * rng.normal(
                size=shape)
        if kind.startswith("experts_"):
            a = a[first:first + count]
        p[name] = a.astype(np.float32)
    return p


def _delta_moe_forward(z, p, tokens, **switches):
    layout = _delta_family._layout(_delta_cfg(z))
    return delta_moe_reference.forward(p, tokens, *layout, **switches)


delta_moe = Family(
    "delta_moe",
    {"hidden_size": 64, "num_hidden_layers": 4, "gqa_layers": [0],
     "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
     "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                            "num_heads": 4, "num_kv_heads": None},
     "moe_intermediate_size": 40, "router_width": 16,
     "num_experts_per_tok": 3, "routed_scaling_factor": 1,
     "norm_topk_prob": True, "kda_allow_neg_eigval": True,
     "rms_norm_eps": 1e-5, "vocab_size": 97, "compute_dtype": "float32",
     "share": (4, 4)}, 0, _delta_moe_init,
    lambda z: _delta_family._arch(_delta_cfg(z)), _delta_moe_forward,
    dict(max_len=400, max_slots=3, block_tokens=8, prefix_reuse=False,
         cache_blocks=0))


def delta_config():
    """``delta_moe``'s sizes as the configuration ``chipbench.families.
    delta_moe`` reads."""
    return _delta_cfg(delta_moe.sizes)
