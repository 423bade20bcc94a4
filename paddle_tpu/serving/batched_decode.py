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
* the compiled-executable count keeps the PR-2 bound — ONE decode chunk
  plus one prefill per SUFFIX-length bucket (``used_buckets + 1``);
* unused table entries point at physical block 0, the trash block:
  overrun steps (a finished slot riding out the chunk, prefill bucket
  padding) write garbage there and nowhere else.

Four compiled entry points, built once per engine:

* ``make_decode_chunk`` — a ``lax.scan`` of ``chunk`` batched steps
  between host syncs; attention consumes the block table DIRECTLY
  through the ``paged_attention`` op class (online softmax block by
  block — the ``[S, T, h, dh]`` gathered view never materializes).
  ``PADDLE_TPU_PAGED_ATTN=0`` restores the ``decode_gather`` +
  dense-softmax spelling bit-exact (the kill switch / oracle path).
* ``make_prefill`` — one executable per SUFFIX bucket: scans the
  non-cached tail of the prompt (``tokens[start:start+length]`` padded
  to the bucket) through the same single-token step math, starting at
  runtime position ``start`` and attending the slot's cached blocks
  through the table.  A request whose prefix is fully cached scans only
  its last prompt token (the logits that seed decode are never cached).
  The optional copy-on-write fork (``cow_src -> cow_dst``) is folded
  into the SAME executable as a leading whole-block copy, so CoW adds
  no executable (``cow_src == cow_dst == 0`` is the no-op spelling —
  trash copied onto trash).
* ``make_verify_window`` — the speculative-decoding verify step
  (``serving.speculative``): ONE teacher-forced forward over a
  ``k + 1``-token window per slot (the slot's committed last token
  followed by its k draft proposals), scoring every window position in
  parallel through the same block-table gather.  The window rides the
  decode executable shape — the table is data — so speculative decode
  adds exactly one executable per engine, never one per ``k``.

Correctness discipline (unchanged from the contiguous engine): every op
is row-wise per slot, each step writes position ``t`` BEFORE attending
with mask ``<= t``, and garbage (trash-block content, bucket padding,
CoW tail beyond the shared span) is either overwritten before it is
ever attended or masked to exactly zero attention weight — so greedy
decode through the paged engine is bit-identical to single-stream
``transformer.generate``, prefix reuse on or off (the serving
acceptance bar, ``tests/test_serving.py`` / ``tests/test_kvcache.py``).
"""

import os

import jax
import jax.numpy as jnp

__all__ = ["paged_step_logits", "make_decode_chunk", "make_prefill",
           "make_verify_window"]


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
    ``paged_attention`` op class (docs/kernels.md) and stream blocks
    with online softmax — ``qh [S, W, h, dh]``, ``pos [S, W]`` →
    ``[S, W, h, dh]``.  The tuned block-iteration geometry and backend
    come from the ``op=paged_attention`` cache entry when one exists
    (cached-mode lookup: a miss never compiles)."""
    from .. import tune
    from ..kernels import KernelUnavailable, resolve

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
    forward (each attends the cached chain plus the in-window
    positions ``<= pos_s + j``, all written before any gather) is the
    speculative win: the weights are read once for W tokens instead of
    W times.

    ``limit[s]`` is the last logical position slot ``s`` may ever
    legitimately write (``p_len + max_new - 1``; ``-1`` for a dead
    slot): window positions beyond it route their K/V writes to the
    trash block, so a window overhanging the end of a request — or a
    slot killed mid-round — can never scatter into a live block.
    Without this, two window positions clamped to the same table entry
    would race their ``.at[].set`` writes.  Greedy outputs at
    positions past ``limit`` are garbage; the host-side acceptance
    walk never commits them.
    """
    W = k + 1

    def verify(p, pool_k, pool_v, toks, pos, limit, table):
        S = toks.shape[0]
        NB = table.shape[1]
        B = pool_k[0].shape[1]
        T = NB * B
        dh = d_model // n_head
        rows = jnp.arange(S)
        P = pos[:, None] + jnp.arange(W)[None, :]            # [S, W]
        Pw = jnp.clip(P, 0, T - 1)
        writable = P <= limit[:, None]
        blk = jnp.where(writable, table[rows[:, None], Pw // B], 0)
        off = Pw % B
        x = p["tok_emb.w"][toks] + p["pos_emb.w.w"][Pw]      # [S, W, d]
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
                # window position j attends <= pos + j — the same
                # causal invariant, enforced per block inside the
                # paged_attention kernel instead of over a gathered view
                ctx = _paged_attention(qh, pk, pv, table, P).reshape(
                    S, W, d_model)
            else:
                ck = _gather_kv(pk, table)                   # [S, T, h, dh]
                cv = _gather_kv(pv, table)
                s = jnp.einsum("swhd,sThd->swhT", qh, ck,
                               preferred_element_type=jnp.float32)
                s = s / jnp.sqrt(float(dh))
                # one causal mask covers the cached chain AND the
                # in-window positions: window slot j attends <= pos + j
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
        x = _ln(x, p["ln_f.scale"], p["ln_f.bias"], eps)
        logits = jnp.matmul(x, p["lm_head.w"],
                            preferred_element_type=jnp.float32)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return pool_k, pool_v, greedy

    return jax.jit(verify, donate_argnums=(1, 2) if donate else ())


def make_prefill(n_layer, n_head, d_model, bucket, eps=1e-5,
                 donate=True):
    """Build the prefill executable for one SUFFIX-length bucket.

    ``fn(params, pool_k, pool_v, last_tok, pos, slot, table_row [NB],
    toks [bucket], start, length, cow_src, cow_dst) -> (pool_k',
    pool_v', last_tok', pos', first_tok)`` — first copies block
    ``cow_src`` onto ``cow_dst`` whole (the copy-on-write fork; the
    no-fork spelling passes ``0, 0``, trash onto trash), then scans the
    padded prompt SUFFIX through the step math at positions ``start +
    i``, writing K/V through ``table_row`` and attending the slot's
    cached chain (positions ``< start`` were shared from the prefix
    trie and are read, never recomputed).  Seeds the slot's
    ``last_tok`` with the first generated token (greedy argmax at the
    last real prompt position, scan step ``length - 1``) and ``pos``
    with ``start + length``.  ``first_tok`` is also returned as a
    scalar so the scheduler can report TTFT / detect an immediate EOS
    without pulling slot state back.

    Steps past ``length`` process padding and write garbage at
    positions ``>= start + length`` — harmless by construction: each
    step writes BEFORE attending (mask ``<= t``), so the real steps
    never see padding writes, and decode overwrites position ``pos``
    before its first attend.
    """

    def prefill(p, pool_k, pool_v, last_tok, pos, slot, table_row,
                toks, start, length, cow_src, cow_dst):
        # copy-on-write fork: duplicate the whole source block; the
        # shared tokens are the live prefix, the tail is garbage the
        # suffix scan / decode overwrites before ever attending it
        pool_k = tuple(c.at[cow_dst].set(c[cow_src]) for c in pool_k)
        pool_v = tuple(c.at[cow_dst].set(c[cow_src]) for c in pool_v)

        def body(carry, i):
            pk, pv = carry
            tok = jax.lax.dynamic_slice_in_dim(toks, i, 1)  # [1]
            t = (start + i)[None]
            logits, pk, pv = paged_step_logits(
                p, tok, t, pk, pv, table_row[None], n_layer, n_head,
                d_model, eps)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (pk, pv), nxt[0]

        (pool_k, pool_v), nxts = jax.lax.scan(
            body, (pool_k, pool_v), jnp.arange(bucket))
        first = jax.lax.dynamic_index_in_dim(nxts, length - 1,
                                             keepdims=False)
        last_tok = last_tok.at[slot].set(first)
        pos = pos.at[slot].set(start + length)
        return pool_k, pool_v, last_tok, pos, first

    return jax.jit(prefill, donate_argnums=(1, 2, 3, 4) if donate else ())
