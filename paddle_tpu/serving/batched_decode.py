"""Paged slot-batched KV-cache decode — the pure-JAX compute under
``paddle_tpu.serving``.

KV lives in a physical block pool: per layer one
``[num_blocks, block_tokens, h, dh]`` array, and each slot's logical
sequence is a chain of block ids in a per-slot BLOCK TABLE row
(``[max_slots, blocks_per_slot]`` int32, host-managed by
``serving.kvcache``).  Position ``t`` of slot ``s`` lives at
``(table[s, t // B], t % B)``.  Every compiled entry point writes and
gathers THROUGH the table, so:

* identical prompt prefixes can share physical blocks across slots
  (prefix reuse — the table is data, not shape, so sharing costs no
  recompile);
* the compiled-executable count is bounded by shapes, never by
  requests or prompt lengths — ONE decode chunk plus one prefill per
  window WIDTH (the power-of-two buckets up to ``PREFILL_PIECE``);
* unused table entries point at physical block 0, the trash block:
  overrun steps (a finished slot riding out the chunk) and window rows
  past their ``limit`` (prefill bucket padding, a verify window
  overhanging its request) write garbage there and nowhere else.

Two forwards, three compiled entry points built once per engine:

* ``paged_step_logits`` — ONE token per slot for S slots; under
  ``make_decode_chunk`` a ``lax.scan`` of ``chunk`` batched steps
  between host syncs.  Attention consumes the block table DIRECTLY
  through the ``paged_attention`` op class (online softmax block by
  block — the ``[S, T, h, dh]`` gathered view never materializes).
  ``PADDLE_TPU_PAGED_ATTN=0`` restores the ``decode_gather`` +
  dense-softmax spelling bit-exact (the kill switch / oracle path).
* ``_window_forward`` — a teacher-forced ``[S, W]`` WINDOW in one
  pass: per layer one ``[S*W, d]`` matmul per projection (the weights
  are read once for W tokens), all W K/V rows written through the
  table before attention, row ``j`` attending ``<= pos + j``.  Two
  executables are built on it:

  - ``make_prefill`` — one executable per window width, ``S = 1``:
    the non-cached tail of a prompt (``tokens[start:start+length]``
    padded to the width) at runtime position ``start``, attending the
    slot's cached blocks through the table; the LM head runs at the
    last real row only.  A suffix longer than ``PREFILL_PIECE`` is the
    engine's loop over consecutive pieces (``ServingEngine._run_pieces``),
    each attending what the earlier ones wrote.  A request whose prefix
    is fully cached prefills only its last prompt token (the logits
    that seed decode are never cached).  The optional copy-on-write
    fork (``cow_src -> cow_dst``) is folded into the SAME executable as
    a leading whole-block copy, so CoW adds no executable (``cow_src ==
    cow_dst == 0`` is the no-op spelling — trash copied onto trash).
  - ``make_verify_window`` — the speculative-decoding verify step
    (``serving.speculative``): a ``k + 1``-token window per slot (the
    slot's committed last token followed by its k draft proposals),
    every row put through the head.  The table is data, so speculative
    decode adds exactly one executable per engine, never one per ``k``.

  Inside a window of ``DENSE_WINDOW`` rows or more, attention gathers
  the chain once and attends it densely (``_paged_attention``).

Correctness discipline (unchanged from the contiguous engine): every op
is row-wise per slot and window row, each position's K/V is written
BEFORE anything attends it (mask ``<= t``), and garbage (trash-block
content, bucket padding, CoW tail beyond the shared span) is either
overwritten before it is ever attended or masked to exactly zero
attention weight.  A window and the token steps compute the same
mathematics in the same dtypes and differ only in a matmul's reduction
shape — so greedy decode through the paged engine is token-identical
to single-stream ``transformer.generate`` in f32, prefix reuse on or
off (``tests/test_serving.py`` / ``tests/test_kvcache.py``), and agrees
with it in bf16 within the reference margin (docs/serving.md "Numerics
contract"; ``tests/test_prefill_window.py`` pins K/V rows and
first-token logits against the token steps in both dtypes).
"""

import os

import jax
import jax.numpy as jnp

__all__ = ["paged_step_logits", "make_decode_chunk", "make_prefill",
           "make_verify_window", "PREFILL_PIECE", "DENSE_WINDOW"]

# the widest window one prefill call computes; a longer suffix is
# prefilled as consecutive pieces of this width plus one bucketed
# remainder (PERF.md, PR 26, has the chip's comparison of 64/128/256)
PREFILL_PIECE = 128

# from this window width up attention gathers the slot's chain once and
# attends it densely instead of streaming blocks (``_paged_attention``)
DENSE_WINDOW = 8


def _paged_attn_on():
    """The ``PADDLE_TPU_PAGED_ATTN`` kill switch (default ON).  Read at
    TRACE time, so an engine built under ``=0`` compiles the
    gather+dense-softmax spelling verbatim — bit-exact with the
    pre-paged-attention engine."""
    return os.environ.get("PADDLE_TPU_PAGED_ATTN", "1").lower() not in (
        "0", "", "false", "off", "no")


def _gather_kv(pool, table):
    """The block-table gather, routed through the kernel registry
    (``decode_gather`` op class, docs/kernels.md): the XLA
    advanced-indexing gather off-TPU, the scalar-prefetch Pallas kernel
    on TPU.  Bit-exact across backends — a gather moves bits.

    Since the ``paged_attention`` op class landed this is the
    KILL-SWITCH / ORACLE spelling, not the fast path: attention
    normally consumes the table directly (``_paged_attention`` below)
    and the ``[S, T, h, dh]`` view this gather materializes exists only
    under ``PADDLE_TPU_PAGED_ATTN=0`` (rollback) and in the reference
    suites that pin the paged kernels' numerics against it."""
    from ..kernels import resolve

    return resolve("decode_gather").impl.call(pool, table)


def _paged_attention(qh, pool_k, pool_v, table, pos):
    """One layer's attention THROUGH the block table: resolve the
    ``paged_attention`` op class (docs/kernels.md) — ``qh [S, W, h,
    dh]``, ``pos [S, W]`` → ``[S, W, h, dh]``.

    The spelling follows the window's width, seen at trace time.  A
    NARROW window (decode's ``W = 1``, a speculative ``k + 1``) streams
    blocks with online softmax through whatever the registry resolves,
    with the tuned block-iteration geometry and backend of the
    ``op=paged_attention`` cache entry when one exists (cached-mode
    lookup: a miss never compiles).  A window of ``DENSE_WINDOW`` rows
    or more (a prefill piece) gathers the chain ONCE and attends it
    densely, the ``xla_ref`` spelling with one step over the whole
    chain: W rows share one read of K and V and the scores are MXU
    matmuls, where the streaming kernels repeat their per-block body
    once per window row."""
    from .. import tune
    from ..kernels import KernelUnavailable, resolve

    if qh.shape[1] >= DENSE_WINDOW:
        return resolve("paged_attention", backend="xla_ref").impl.call(
            qh, pool_k, pool_v, table, pos, block_step=table.shape[1])
    T = table.shape[1] * pool_k.shape[1]
    h, dh = qh.shape[-2], qh.shape[-1]
    cfg = tune.paged_attention_config(T, dh, h, str(qh.dtype)) or {}
    try:
        ker = resolve("paged_attention", backend=cfg.get("backend"))
    except (KernelUnavailable, ValueError):
        # a persisted backend name this host cannot serve (a tune cache
        # written elsewhere) degrades to auto.  Resolution compiles
        # nothing: a kernel the compiler refuses fails later, at the
        # engine's lower().compile(), and reaches the caller
        ker = resolve("paged_attention")
    return ker.impl.call(qh, pool_k, pool_v, table, pos,
                         block_step=cfg.get("block_step"))


def _ln(x, scale, bias, eps):
    # statistics in f32 even under bf16 compute (mean/var cancellation) —
    # mirrors transformer.generate's ln exactly
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    xn = ((x32 - mu) / jnp.sqrt(var + eps)).astype(x.dtype)
    return xn * scale + bias


def paged_step_logits(p, tok, t, pool_k, pool_v, table, n_layer, n_head,
                      d_model, eps=1e-5):
    """One decode step for S independent slots through the block table.

    tok [S] int32 current tokens, t [S] int32 per-slot positions,
    pool_k/pool_v tuples of n_layer [num_blocks, B, h, dh], table
    [S, NB] int32 block ids (logical capacity T = NB * B).  Writes each
    slot's K/V at ``(table[s, t_s // B], t_s % B)`` (clamped — overrun
    slots land in whatever their last table entry maps to, by
    construction the trash block or an already-consumed position),
    attends over the gathered chain masked ``<= t_s``, and returns
    ``(logits [S, vocab] f32, pool_k', pool_v')``.
    """
    S = tok.shape[0]
    NB = table.shape[1]
    B = pool_k[0].shape[1]
    T = NB * B
    dh = d_model // n_head
    rows = jnp.arange(S)
    tw = jnp.clip(t, 0, T - 1)
    blk = table[rows, tw // B]      # [S] physical write block
    off = tw % B
    x = p["tok_emb.w"][tok] + p["pos_emb.w.w"][tw]          # [S, d]
    pk_out, pv_out = [], []
    for i in range(n_layer):
        w = lambda nm: p[f"block{i}_{nm}"]
        h = _ln(x, w("ln1.scale"), w("ln1.bias"), eps)
        q = h @ w("att_q.w") + w("att_q.b")
        k = h @ w("att_k.w") + w("att_k.b")
        v = h @ w("att_v.w") + w("att_v.b")
        qh = q.reshape(S, n_head, dh)
        kh = k.reshape(S, n_head, dh)
        vh = v.reshape(S, n_head, dh)
        # per-slot scatter through the table: slot s writes its own
        # (block, offset); distinct live slots own distinct blocks, so
        # the only possible collision is overrun garbage in the trash
        # block — content nobody ever attends
        pk = pool_k[i].at[blk, off].set(kh)
        pv = pool_v[i].at[blk, off].set(vh)
        pk_out.append(pk)
        pv_out.append(pv)
        if _paged_attn_on():
            # attend THROUGH the table: paged_attention streams blocks
            # with online softmax, the [S, T, h, dh] view never exists
            ctx = _paged_attention(
                qh[:, None], pk, pv, table,
                t[:, None])[:, 0].reshape(S, d_model)
        else:
            # kill-switch spelling (PADDLE_TPU_PAGED_ATTN=0): gather
            # each slot's logical view [S, T, h, dh] through the
            # registry-routed decode_gather kernel, dense softmax —
            # bit-exact with the pre-paged-attention engine
            ck = _gather_kv(pk, table)
            cv = _gather_kv(pv, table)
            s = jnp.einsum("shd,sThd->shT", qh, ck,
                           preferred_element_type=jnp.float32)
            s = s / jnp.sqrt(float(dh))
            mask = jnp.arange(T)[None, None, :] <= t[:, None, None]
            s = jnp.where(mask, s, -1e30)
            a = jax.nn.softmax(s, axis=-1).astype(ck.dtype)
            ctx = jnp.einsum("shT,sThd->shd", a, cv).reshape(S, d_model)
        x = x + ctx @ w("att_out.w") + w("att_out.b")
        h2 = _ln(x, w("ln2.scale"), w("ln2.bias"), eps)
        # exact erf gelu, matching transformer.generate and the gelu op
        ff = jax.nn.gelu(h2 @ w("ffn1.w") + w("ffn1.b"), approximate=False)
        x = x + ff @ w("ffn2.w") + w("ffn2.b")
    x = _ln(x, p["ln_f.scale"], p["ln_f.bias"], eps)
    logits = jnp.matmul(x, p["lm_head.w"],
                        preferred_element_type=jnp.float32)
    return logits, tuple(pk_out), tuple(pv_out)


def make_decode_chunk(n_layer, n_head, d_model, chunk, eps=1e-5,
                      donate=True):
    """Build the batched decode executable: ``chunk`` greedy steps for
    every slot in one device call.

    ``fn(params, pool_k, pool_v, last_tok, pos, table) -> (pool_k',
    pool_v', last_tok', pos', toks [chunk, S] int32)`` — ``toks[j]`` is
    the token each slot emitted at its ``pos+j``'th position.  The pool
    and slot scalars are donated (updated in place on TPU); the table is
    a small host-fed int32 array (data, not donated).  Callers must
    replace their references with the outputs.
    """

    def decode_chunk(p, pool_k, pool_v, last_tok, pos, table):
        def body(carry, _):
            pk, pv, tok, t = carry
            logits, pk, pv = paged_step_logits(
                p, tok, t, pk, pv, table, n_layer, n_head, d_model, eps)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (pk, pv, nxt, t + 1), nxt

        (pk, pv, tok, t), toks = jax.lax.scan(
            body, (pool_k, pool_v, last_tok, pos), None, length=chunk)
        return pk, pv, tok, t, toks

    return jax.jit(decode_chunk,
                   donate_argnums=(1, 2, 3, 4) if donate else ())


def _window_forward(p, pool_k, pool_v, toks, pos, limit, table, n_layer,
                    n_head, d_model, eps):
    """The teacher-forced WINDOW forward both ``make_verify_window``
    and ``make_prefill`` are built on: ``toks [S, W]`` consumed at
    logical positions ``pos_s + j`` in ONE pass — per layer one
    ``[S*W, d] @ [d, .]`` matmul per projection (the weights are read
    once for W tokens), all W K/V rows scattered through the table
    BEFORE attention, window row ``j`` attending keys ``<= pos_s + j``
    (the cached chain plus the in-window rows before it).

    ``limit[s]`` is the last logical position slot ``s`` may write:
    rows beyond it (a verify window overhanging the end of a request, a
    dead slot's ``-1``, prefill's bucket padding) route their K/V to
    the trash block, so they can never scatter into a live block nor
    race a real row for a clamped table entry.  Their outputs are
    garbage nobody reads.

    Returns ``(x [S, W, d], pool_k', pool_v')`` — the trunk's output
    BEFORE the final LayerNorm: the callers differ in which rows they
    put through the head (``_head_logits``).
    """
    S, W = toks.shape
    NB = table.shape[1]
    B = pool_k[0].shape[1]
    T = NB * B
    dh = d_model // n_head
    rows = jnp.arange(S)
    P = pos[:, None] + jnp.arange(W)[None, :]                # [S, W]
    Pw = jnp.clip(P, 0, T - 1)
    writable = P <= limit[:, None]
    blk = jnp.where(writable, table[rows[:, None], Pw // B], 0)
    off = Pw % B
    x = p["tok_emb.w"][toks] + p["pos_emb.w.w"][Pw]          # [S, W, d]
    for i in range(n_layer):
        w = lambda nm: p[f"block{i}_{nm}"]
        h = _ln(x, w("ln1.scale"), w("ln1.bias"), eps)
        q = h @ w("att_q.w") + w("att_q.b")
        kk = h @ w("att_k.w") + w("att_k.b")
        v = h @ w("att_v.w") + w("att_v.b")
        qh = q.reshape(S, W, n_head, dh)
        kh = kk.reshape(S, W, n_head, dh)
        vh = v.reshape(S, W, n_head, dh)
        # all W writes land before the gather below — the same
        # write-before-attend discipline as the sequential step,
        # collapsed into one scatter (distinct live positions,
        # disjoint per-slot blocks, overruns trashed via `limit`)
        pk = pool_k[i].at[blk, off].set(kh)
        pv = pool_v[i].at[blk, off].set(vh)
        pool_k = pool_k[:i] + (pk,) + pool_k[i + 1:]
        pool_v = pool_v[:i] + (pv,) + pool_v[i + 1:]
        if _paged_attn_on():
            # window row j attends <= pos + j — the same causal
            # invariant, enforced inside the paged_attention op class
            # instead of over a gathered view
            ctx = _paged_attention(qh, pk, pv, table, P).reshape(
                S, W, d_model)
        else:
            ck = _gather_kv(pk, table)                       # [S, T, h, dh]
            cv = _gather_kv(pv, table)
            s = jnp.einsum("swhd,sThd->swhT", qh, ck,
                           preferred_element_type=jnp.float32)
            s = s / jnp.sqrt(float(dh))
            # one causal mask covers the cached chain AND the
            # in-window positions: window row j attends <= pos + j
            mask = (jnp.arange(T)[None, None, None, :]
                    <= P[:, :, None, None])
            s = jnp.where(mask, s, -1e30)
            a = jax.nn.softmax(s, axis=-1).astype(ck.dtype)
            ctx = jnp.einsum("swhT,sThd->swhd", a, cv).reshape(
                S, W, d_model)
        x = x + ctx @ w("att_out.w") + w("att_out.b")
        h2 = _ln(x, w("ln2.scale"), w("ln2.bias"), eps)
        ff = jax.nn.gelu(h2 @ w("ffn1.w") + w("ffn1.b"),
                         approximate=False)
        x = x + ff @ w("ffn2.w") + w("ffn2.b")
    return x, pool_k, pool_v


def _head_logits(p, x, eps):
    """Final LayerNorm + LM head over the rows of ``x [..., d]``, f32
    logits."""
    x = _ln(x, p["ln_f.scale"], p["ln_f.bias"], eps)
    return jnp.matmul(x, p["lm_head.w"],
                      preferred_element_type=jnp.float32)


def make_verify_window(n_layer, n_head, d_model, k, eps=1e-5,
                       donate=True):
    """Build the speculative VERIFY executable: one teacher-forced
    target forward over a ``W = k + 1``-token window for every slot.

    ``fn(params, pool_k, pool_v, toks [S, W], pos [S], limit [S],
    table [S, NB]) -> (pool_k', pool_v', greedy [S, W] int32)`` —
    ``toks[s] = [last_s, d_1 .. d_k]`` (the committed last token
    followed by the slot's draft proposals), window position ``j``
    lives at logical position ``pos_s + j``, and ``greedy[s, j]`` is
    the target's argmax after consuming ``toks[s, j]`` there — exactly
    the token sequential greedy decode would emit after the prefix
    extended by ``toks[s, :j]``.  Scoring all W positions in ONE
    forward (``_window_forward``) is the speculative win: the weights
    are read once for W tokens instead of W times.

    ``limit[s]`` is the last logical position slot ``s`` may ever
    legitimately write (``p_len + max_new - 1``; ``-1`` for a dead
    slot).  Greedy outputs at positions past ``limit`` are garbage;
    the host-side acceptance walk never commits them.
    """

    def verify(p, pool_k, pool_v, toks, pos, limit, table):
        if toks.shape[1] != k + 1:
            raise ValueError(f"verify window built for k={k} got "
                             f"{toks.shape[1]} tokens a slot")
        x, pool_k, pool_v = _window_forward(
            p, pool_k, pool_v, toks, pos, limit, table, n_layer, n_head,
            d_model, eps)
        greedy = jnp.argmax(_head_logits(p, x, eps),
                            axis=-1).astype(jnp.int32)
        return pool_k, pool_v, greedy

    return jax.jit(verify, donate_argnums=(1, 2) if donate else ())


def make_prefill(n_layer, n_head, d_model, bucket, eps=1e-5,
                 donate=True):
    """Build the prefill executable for one window WIDTH (a suffix
    bucket of at most ``PREFILL_PIECE`` tokens).

    ``fn(params, pool_k, pool_v, last_tok, pos, slot, table_row [NB],
    toks [bucket], start, length, cow_src, cow_dst) -> (pool_k',
    pool_v', last_tok', pos', first_tok)`` — first copies block
    ``cow_src`` onto ``cow_dst`` whole (the copy-on-write fork; the
    no-fork spelling passes ``0, 0``, trash onto trash), then runs ONE
    window forward (``_window_forward`` at ``S = 1``) over the padded
    tokens at positions ``start + j``, writing K/V through
    ``table_row`` and attending the slot's chain (positions ``< start``
    — the prefix shared from the trie, or what an earlier piece of the
    same prompt wrote — are read, never recomputed).  The final
    LayerNorm, LM head and argmax run at row ``length - 1`` ONLY: one
    ``[1, d] @ [d, vocab]`` matvec per call, whatever the width.  Seeds
    the slot's ``last_tok`` with that greedy token and ``pos`` with
    ``start + length``.  ``first_tok`` is also returned as a scalar so
    the scheduler can report TTFT / detect an immediate EOS without
    pulling slot state back.

    Rows past ``length`` are padding: their K/V go to the trash block
    (``limit = start + length - 1``) and their outputs are never read;
    a real row never attends them (mask ``<= start + j``).
    """

    def prefill(p, pool_k, pool_v, last_tok, pos, slot, table_row,
                toks, start, length, cow_src, cow_dst):
        if toks.shape != (bucket,):
            raise ValueError(f"prefill built for width {bucket} got "
                             f"tokens of shape {toks.shape}")
        # copy-on-write fork: duplicate the whole source block; the
        # shared tokens are the live prefix, the tail is garbage the
        # window / decode overwrites before ever attending it
        pool_k = tuple(c.at[cow_dst].set(c[cow_src]) for c in pool_k)
        pool_v = tuple(c.at[cow_dst].set(c[cow_src]) for c in pool_v)
        end = start + length
        x, pool_k, pool_v = _window_forward(
            p, pool_k, pool_v, toks[None], start[None], (end - 1)[None],
            table_row[None], n_layer, n_head, d_model, eps)
        row = jax.lax.dynamic_slice_in_dim(x[0], length - 1, 1)  # [1, d]
        first = jnp.argmax(_head_logits(p, row, eps)[0]).astype(jnp.int32)
        last_tok = last_tok.at[slot].set(first)
        pos = pos.at[slot].set(end)
        return pool_k, pool_v, last_tok, pos, first

    return jax.jit(prefill, donate_argnums=(1, 2, 3, 4) if donate else ())
