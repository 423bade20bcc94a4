"""Speculative decoding over the paged serving engine — draft-model
propose, single-pass target verify, token-exact rollback
(docs/serving.md "Speculative decoding").

Plain decode emits one token per target forward, so decode wall time is
``max_new`` weight reads per request no matter how predictable the text
is.  Speculative decoding restructures the schedule, not the math:

* **Propose** — a small DRAFT model (the same ``transformer.build``
  family, depth-pruned: identical vocab / d_model / head geometry,
  fewer layers) runs ``k + 1`` cheap greedy steps per slot through the
  existing ``make_decode_chunk`` executable, writing its KV into
  SCRATCH block chains allocated from the same :class:`BlockPool` the
  real chains live in (disjoint block ids inside the same pool arrays —
  the block table is data, so the draft costs one executable, total).
* **Verify** — ONE target forward scores all ``k + 1`` window positions
  per slot (``batched_decode.make_verify_window``): the weights are
  read once for the whole window instead of once per token.  That
  parallel read amortization IS the win; everything else exists to make
  it lossless.
* **Accept** — greedy acceptance walks the longest prefix where the
  draft's proposal equals the target's argmax, then commits one BONUS
  token (the target's argmax at the first divergence).  Token-exactness
  is an induction, not a tolerance: position j's target argmax is
  computed from a prefix that is bit-identical to what sequential
  greedy decode would have consumed — the committed last token plus
  j already-verified proposals — so every committed token equals the
  sequential one, and at least one token commits per round.
* **Roll back** — scratch blocks past the new committed frontier are
  deref'd back to the pool (``serving.spec_rollback_blocks``); the
  rejected draft K/V beyond the frontier is dead data that the
  write-before-attend discipline overwrites before any future gather
  reads it, so rollback is pure host accounting — no device copy.
  Slot finish / death / abort release the whole scratch chain through
  the engine's ``_release_slot`` discipline: zero leaks, pinned by
  ``tests/test_speculative.py`` and its fault-injection regression.

Kill switch: ``PADDLE_TPU_SPEC=0`` (or ``off``/``false``) makes the
engine ignore ``draft_params`` entirely — no validation, no extra pool
blocks, no draft executables — bit-identical to the plain engine.
"""

import os

import numpy as np

from . import batched_decode as _bd
from .arch import Gpt2

__all__ = ["spec_enabled", "draft_depth",
           "depth_draft", "validate_draft", "accept_greedy",
           "SpecState"]

def spec_enabled():
    """The ``PADDLE_TPU_SPEC`` kill switch: False for ``0`` / ``off`` /
    ``false`` / ``no``; default True.  Read at engine construction —
    off means ``draft_params`` is ignored wholesale and the engine is
    bit-identical to one built without a draft."""
    v = os.environ.get("PADDLE_TPU_SPEC", "1").strip().lower()
    return v not in ("0", "off", "false", "no")


def draft_depth(params):
    """Number of transformer blocks a parameter dict carries (the
    ``block{i}_*`` naming of ``transformer.build``)."""
    depth = 0
    for k in params:
        if k.startswith("block") and "_" in k:
            head = k[len("block"):k.index("_")]
            if head.isdigit():
                depth = max(depth, int(head) + 1)
    return depth


def depth_draft(params, n_layers):
    """A depth-pruned draft from target params: the first ``n_layers``
    transformer blocks plus the shared embeddings / final LN / LM head.
    The cheapest honest draft in the ``transformer.build`` family —
    same vocab, same width, same head geometry by construction — used
    by the tests and ``chip_smoke.py``."""
    if not 1 <= int(n_layers) <= draft_depth(params):
        raise ValueError(
            f"depth_draft: n_layers {n_layers} outside [1, "
            f"{draft_depth(params)}]")
    out = {}
    for k, v in params.items():
        if k.startswith("block") and "_" in k:
            head = k[len("block"):k.index("_")]
            if head.isdigit() and int(head) >= int(n_layers):
                continue
        out[k] = v
    return out


def validate_draft(params, draft_params, arch, max_len,
                   draft_n_layer=None, draft_n_head=None):
    """Geometry checks at engine construction — the draft shares the
    target's paged pool arrays and tokenizer, so mismatches must fail
    LOUDLY here, not as silent garbage tokens at serve time.  Returns
    the validated ``draft_n_layer``.  ``arch`` is the target's
    ``serving.arch.Architecture``: anything but the GPT-2 block (a
    looped stack, whose pool arrays hold a plane per pass) is refused."""
    if arch.state_spec("float32"):
        raise ValueError(
            f"speculative decoding cannot serve {arch.name!r}: its layers "
            f"hold recurrent state beside the pool, and a REJECTED draft "
            f"token needs that state rolled back to the committed "
            f"frontier; dropping scratch blocks rolls back K/V only and "
            f"no state rollback is written")
    if not isinstance(arch, Gpt2):
        raise ValueError(
            f"speculative decoding serves the GPT-2 block only: the "
            f"target is {arch.name!r} ({arch.passes} passes over "
            f"{arch.n_layer} layers); a draft that rides the first pool "
            f"arrays of a looped target, and a verify window over it, "
            f"are not written")
    n_layer, n_head, d_model = arch.n_layer, arch.n_head, arch.d_model
    t_vocab = int(np.asarray(params["tok_emb.w"]).shape[0])
    d_vocab = int(np.asarray(draft_params["tok_emb.w"]).shape[0])
    if t_vocab != d_vocab:
        raise ValueError(
            f"speculative draft/target vocab mismatch: draft tok_emb "
            f"has {d_vocab} entries, target has {t_vocab} — the models "
            f"must share one tokenizer for acceptance to compare tokens")
    d_head_vocab = int(np.asarray(draft_params["lm_head.w"]).shape[1])
    if d_head_vocab != t_vocab:
        raise ValueError(
            f"speculative draft lm_head emits {d_head_vocab} logits, "
            f"target vocab is {t_vocab} — shared tokenizer required")
    d_width = int(np.asarray(draft_params["tok_emb.w"]).shape[1])
    if d_width != d_model:
        raise ValueError(
            f"speculative draft d_model {d_width} != target d_model "
            f"{d_model}: the draft writes its K/V into the target's "
            f"paged pool arrays, so the widths must match")
    dnh = n_head if draft_n_head is None else int(draft_n_head)
    if dnh != n_head:
        raise ValueError(
            f"speculative draft n_head {dnh} != target n_head {n_head} "
            f"(heads of {arch.head_dim} lanes, the architecture's own): "
            f"the shared pool block shape is [B, n_head, head_dim]")
    depth = draft_depth(draft_params)
    dnl = depth if draft_n_layer is None else int(draft_n_layer)
    if not 1 <= dnl <= depth:
        raise ValueError(
            f"speculative draft_n_layer {dnl} outside [1, {depth}] "
            f"(layers present in draft_params)")
    if dnl > n_layer:
        raise ValueError(
            f"speculative draft has {dnl} layers, target has {n_layer}: "
            f"the draft rides the first {n_layer} pool arrays, so it "
            f"cannot be deeper than the target")
    d_pos = int(np.asarray(draft_params["pos_emb.w.w"]).shape[0])
    if max_len > d_pos:
        raise ValueError(
            f"max_len {max_len} exceeds the draft's position-embedding "
            f"table ({d_pos} positions)")
    return dnl


def accept_greedy(drafts, target_greedy, max_commit):
    """The acceptance walk for one slot: ``drafts`` are the k proposed
    tokens, ``target_greedy`` the target's k+1 window argmaxes
    (``target_greedy[j]`` = greedy token after the prefix extended by
    ``drafts[:j]``).  Returns the committed tokens — the longest
    agreeing prefix plus the bonus token at the divergence — capped at
    ``max_commit``.  Returns ``(tokens, n_matched)`` — the committed
    tokens and how many draft proposals they contain.  Every returned
    token is bit-equal to what sequential greedy decode would emit
    (the induction in the module docstring), and at least one
    commits."""
    n = 0
    while (n < len(drafts) and n + 1 < max_commit
           and int(drafts[n]) == int(target_greedy[n])):
        n += 1
    commit = [int(t) for t in target_greedy[:n + 1]][:max_commit]
    return commit, min(n, len(commit))


class SpecState:
    """Per-engine speculative state: draft params on device, the draft
    scratch block table + chains, and the draft executables (one
    prefill per suffix bucket, one k+1-step propose chunk).  All block
    accounting flows through the engine's :class:`BlockPool`; the
    engine's ``_release_slot`` / ``_abort`` call :meth:`release` so the
    scratch chains obey the same zero-leak discipline as real chains."""

    def __init__(self, engine, draft_params, draft_n_layer, k):
        import jax
        import jax.numpy as jnp

        if int(k) < 1:
            raise ValueError(f"spec_k must be >= 1: {k}")
        self.k = int(k)
        self.n_layer = int(draft_n_layer)
        # the draft is the target's block, fewer layers of it
        self.arch = Gpt2(self.n_layer, engine.n_head, engine.d_model,
                         engine.arch.eps)
        self.p = jax.device_put(
            {kk: jnp.asarray(v, engine.compute_dtype)
             for kk, v in draft_params.items()})
        self.table = np.zeros((engine.max_slots, engine.blocks_per_slot),
                              np.int32)
        self.chains = [None] * engine.max_slots
        self._prefill_fns = {}
        self._chunk_fn = None
        self._verify_fn = None
        # cumulative accept accounting for the serving.spec_accept_rate
        # gauge (reset with the goodput window)
        self.proposed = 0
        self.accepted = 0

    # -- executables ------------------------------------------------------
    def _compile_counter(self, engine):
        return engine._reg.counter(
            "serving.spec_compiles",
            help="speculative executables built (draft prefill buckets "
                 "+ draft chunk + verify window)")

    def chunk_fn(self, engine):
        """The draft PROPOSE executable: ``k + 1`` greedy draft steps
        (the extra step writes the k-th proposal's K/V, so a fully
        accepted round leaves the draft cache current)."""
        if self._chunk_fn is None:
            self._chunk_fn = engine._aot_with_mem_telemetry(
                _bd.make_decode_chunk(self.arch, self.k + 1,
                                      donate=engine._donate),
                label="spec_draft")
            self._compile_counter(engine).inc()
        return self._chunk_fn

    def verify_fn(self, engine):
        if self._verify_fn is None:
            self._verify_fn = engine._aot_with_mem_telemetry(
                _bd.make_verify_window(engine.arch, self.k,
                                       donate=engine._donate),
                label="spec_verify")
            self._compile_counter(engine).inc()
        return self._verify_fn

    def prefill_fn(self, engine, bucket):
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            fn = engine._aot_with_mem_telemetry(
                _bd.make_prefill(self.arch, bucket,
                                 donate=engine._donate),
                label=f"spec_prefill_{bucket}")
            self._prefill_fns[bucket] = fn
            self._compile_counter(engine).inc()
        return fn

    # -- scratch-chain accounting -----------------------------------------
    def ensure_chain(self, engine, slot, n_blocks):
        """Extend slot's scratch chain to ``n_blocks`` blocks (LRU-
        evicting cached prefix chains under pressure, like admission).
        The pool is sized so drafts always fit once trie-only chains
        are evicted."""
        chain = self.chains[slot] or []
        need = n_blocks - len(chain)
        if need <= 0:
            return
        pool, trie = engine.kv_pool, engine.prefix_trie
        if need > pool.free_blocks and trie is not None:
            trie.evict_lru(need - pool.free_blocks)
        fresh = pool.alloc(need)
        chain.extend(fresh)
        self.chains[slot] = chain
        self.table[slot, :len(chain)] = chain
        engine._reg.gauge("serving.blocks_in_use").set(
            pool.blocks_in_use)

    def rollback(self, engine, slot, keep_blocks):
        """Return scratch blocks past the committed frontier to the
        pool.  The draft K/V they held was computed from REJECTED
        tokens — dead data; the next round re-proposes from the
        committed frontier and rewrites every position it attends, so
        dropping the blocks is the entire rollback."""
        chain = self.chains[slot]
        if chain is None or len(chain) <= keep_blocks:
            return 0
        dropped = chain[keep_blocks:]
        del chain[keep_blocks:]
        for b in dropped:
            engine.kv_pool.deref(b)
        self.table[slot, len(chain):] = 0
        engine._reg.counter(
            "serving.spec_rollback_blocks",
            help="draft scratch blocks rolled back to the pool after "
                 "rejection").inc(len(dropped))
        return len(dropped)

    def release(self, engine, slot):
        """Drop slot's whole scratch chain — the ``_release_slot``
        discipline (finish, injected death, abort all land here)."""
        for b in self.chains[slot] or ():
            engine.kv_pool.deref(b)
        self.chains[slot] = None
        self.table[slot] = 0

    # -- draft forward passes ---------------------------------------------
    def prefill(self, engine, slot, req):
        """Run the draft over the full prompt into the scratch chain so
        the first propose round has a complete draft KV.  No prefix
        reuse on the draft side — scratch chains are private by
        definition.  The draft's own first-token prediction is
        discarded: the committed sequence is the TARGET's."""
        import jax.numpy as jnp

        p_len = req.prompt.shape[0]
        self.ensure_chain(engine, slot,
                          -(-p_len // engine.block_tokens))
        # the draft touches only the first draft_n_layer pool arrays;
        # the target's deeper layers pass around the call untouched.
        # last/pos are donated scratch in spec mode (the round rebuilds
        # both from host mirrors); the draft's writes to them are noise
        nl = self.n_layer
        pk, pv, _first = engine._run_pieces(
            lambda width: self.prefill_fn(engine, width), self.p,
            engine._pk[:nl], engine._pv[:nl], slot,
            jnp.asarray(self.table[slot]), engine._pieces(req.prompt, 0))
        engine._pk = tuple(pk) + engine._pk[nl:]
        engine._pv = tuple(pv) + engine._pv[nl:]

    def propose(self, engine, last_h, pos_h):
        """One draft chunk: ``k + 1`` greedy steps per slot from the
        committed frontier.  Returns the proposals ``[k, S]`` (step j's
        output is the j+1'th draft token; the final step only exists to
        write the k-th proposal's K/V)."""
        import jax.numpy as jnp

        fn = self.chunk_fn(engine)
        nl = self.n_layer
        (pk, pv, engine._last, engine._pos, toks,
         _, _) = fn(self.p, engine._pk[:nl], engine._pv[:nl],
                 jnp.asarray(last_h), jnp.asarray(pos_h),
                 jnp.asarray(self.table))
        engine._pk = tuple(pk) + engine._pk[nl:]
        engine._pv = tuple(pv) + engine._pv[nl:]
        return np.asarray(toks)[:self.k]
