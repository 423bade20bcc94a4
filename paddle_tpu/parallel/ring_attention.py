"""Sequence/context parallelism: ring attention.

The reference predates attention sharding entirely (SURVEY §2.3: "TP / PP /
CP / ring-attention: ABSENT"); its long-sequence story was LoD batching.
This module supplies the missing capability TPU-natively: the sequence axis
is sharded over a mesh axis ('sp'), each device holds a Q/K/V block, and K/V
blocks rotate around the ring via ``jax.lax.ppermute`` while a numerically
stable online-softmax accumulates partial attention — compute overlaps the
ICI transfer, memory per device is O(T/sp).

Also provides single-device blockwise attention (the memory-efficient
flash-style loop via lax.scan) used as the inner kernel.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map


def _attn_block(q, k, v, bias=None, scale=None):
    """One dense block: returns (unnormalized out, row logsumexp-style stats).
    q [b, tq, h, d], k/v [b, tk, h, d]."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        logits = logits + bias
    m = jnp.max(logits, axis=-1, keepdims=True)  # [b,h,q,1]
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return o, m[..., 0], l[..., 0]  # o [b,q,h,d], m/l [b,h,q]


def _merge(o1, m1, l1, o2, m2, l2):
    """Merge two partial attention results with online softmax."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    cast = lambda x: jnp.swapaxes(x, 1, 2)[..., None]  # [b,h,q]->[b,q,h,1]
    o = o1 * cast(a1).astype(o1.dtype) + o2 * cast(a2).astype(o2.dtype)
    return o, m, l


def _finalize(o, m, l):
    return o / jnp.swapaxes(l, 1, 2)[..., None].astype(o.dtype)


def blockwise_attention(q, k, v, block_size=512, causal=False):
    """Memory-efficient attention on one device: scan over K/V blocks with
    online softmax; peak memory O(tq * block) instead of O(tq * tk)."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    nblk = max(tk // block_size, 1)
    while tk % nblk:  # tk must split evenly; shrink block count until it does
        nblk -= 1
    bs = tk // nblk
    kb = k.reshape(b, nblk, bs, h, d)
    vb = v.reshape(b, nblk, bs, h, d)

    def body(carry, blk):
        o, m, l = carry
        kk, vv, idx = blk
        bias = None
        if causal:
            qpos = jnp.arange(tq)[:, None]
            kpos = idx * bs + jnp.arange(bs)[None, :]
            bias = jnp.where(qpos >= kpos, 0.0, -1e30)[None, None]
        o2, m2, l2 = _attn_block(q, kk, vv, bias=bias)
        return _merge(o, m, l, o2, m2, l2), None

    o0 = jnp.zeros_like(q)
    m0 = jnp.full((b, h, tq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    (o, m, l), _ = jax.lax.scan(
        body,
        (o0, m0, l0),
        (jnp.swapaxes(kb, 0, 1), jnp.swapaxes(vb, 0, 1), jnp.arange(nblk)),
    )
    return _finalize(o, m, l)


def ring_attention(q, k, v, mesh, axis_name="sp", causal=False,
                   impl="dense", block_q=1024, block_k=1024):
    """Ring attention over a sequence-sharded batch.

    q/k/v: [b, t, h, d] GLOBALLY, sharded on t over ``axis_name``.  Must be
    called under the mesh (the function shard_maps itself).  Returns output
    sharded the same way.

    impl="flash" runs each device's inner block through the Pallas flash
    kernel (ops/pallas_attention.flash_attention_with_lse) and merges the
    per-step partials by their logsumexp — recommended on TPU for long
    local blocks; "dense" (default) is the XLA-composed inner block.
    """
    if impl not in ("dense", "flash"):
        raise ValueError(f"unknown ring_attention impl {impl!r}; "
                         f"choose 'dense' or 'flash'")
    if impl == "flash":
        return _ring_attention_flash(q, k, v, mesh, axis_name, causal,
                                     block_q, block_k)

    sp = mesh.shape[axis_name]
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        lambda qb, kb, vb: ring_attention_local(
            qb, kb, vb, sp, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def ring_attention_local(q_blk, k_blk, v_blk, sp, axis_name="sp",
                         causal=False):
    """The ring's per-device body, for callers ALREADY inside a
    ``shard_map`` that has ``axis_name`` as a manual mesh axis — e.g. an
    attention stage inside ``parallel.pipeline`` (pp x sp composition).
    q_blk/k_blk/v_blk are this device's [b, t/sp, h, d] shards; ``sp`` is
    the ring size (``mesh.shape[axis_name]``)."""
    b, tl, h, d = q_blk.shape
    my_idx = jax.lax.axis_index(axis_name)

    def step(carry, i):
        o, m, l, kk, vv = carry
        src_idx = (my_idx - i) % sp  # whose K/V block we hold now
        bias = None
        if causal:
            qpos = (my_idx * tl + jnp.arange(tl))[:, None]
            kpos = (src_idx * tl + jnp.arange(tl))[None, :]
            bias = jnp.where(qpos >= kpos, 0.0, -1e30)[None, None]
        o2, m2, l2 = _attn_block(q_blk, kk, vv, bias=bias)
        o, m, l = _merge(o, m, l, o2, m2, l2)
        # rotate K/V around the ring (overlaps with next block's compute)
        perm = [(j, (j + 1) % sp) for j in range(sp)]
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return (o, m, l, kk, vv), None

    o0 = jnp.zeros_like(q_blk)
    m0 = jnp.full((b, h, tl), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, tl), jnp.float32)
    (o, m, l, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k_blk, v_blk), jnp.arange(sp)
    )
    return _finalize(o, m, l)


def _ring_attention_flash(q, k, v, mesh, axis_name, causal, block_q,
                          block_k):
    """Flash-kernel inner blocks composed across the ring: each step the
    device attends its Q shard to the K/V shard it currently holds via the
    Pallas kernel (diagonal steps causal, past steps full, future steps
    skipped), and partial outputs merge by logsumexp — mathematically the
    same online softmax the dense path carries as (m, l)."""
    from ..ops.pallas_attention import flash_attention_with_lse

    sp = mesh.shape[axis_name]
    NEG = -1e30

    def local_fn(q_blk, k_blk, v_blk):
        b, tl, h, d = q_blk.shape
        my_idx = jax.lax.axis_index(axis_name)

        # every cond branch returns (o f32, lse f32) so avals match for
        # bf16 inputs too
        def fwd_full(kk, vv):
            o, lse = flash_attention_with_lse(
                q_blk, kk, vv, causal=False, block_q=block_q,
                block_k=block_k)
            return o.astype(jnp.float32), lse.astype(jnp.float32)

        def fwd_diag(kk, vv):
            o, lse = flash_attention_with_lse(
                q_blk, kk, vv, causal=True, block_q=block_q,
                block_k=block_k)
            return o.astype(jnp.float32), lse.astype(jnp.float32)

        def skip(kk, vv):
            return (jnp.zeros(q_blk.shape, jnp.float32),
                    jnp.full((b, h, tl), NEG, jnp.float32))

        def step(carry, i):
            o, lse_acc, kk, vv = carry
            src_idx = (my_idx - i) % sp
            if causal:
                o2, lse2 = jax.lax.cond(
                    src_idx == my_idx,
                    lambda: fwd_diag(kk, vv),
                    lambda: jax.lax.cond(
                        src_idx > my_idx,
                        lambda: skip(kk, vv),
                        lambda: fwd_full(kk, vv),
                    ),
                )
            else:
                o2, lse2 = fwd_full(kk, vv)
            new_lse = jnp.logaddexp(lse_acc, lse2)
            w1 = jnp.exp(lse_acc - new_lse)
            w2 = jnp.exp(lse2 - new_lse)
            cast = lambda x: jnp.swapaxes(x, 1, 2)[..., None]
            o = o * cast(w1) + o2 * cast(w2)
            perm = [(j, (j + 1) % sp) for j in range(sp)]
            kk = jax.lax.ppermute(kk, axis_name, perm)
            vv = jax.lax.ppermute(vv, axis_name, perm)
            return (o, new_lse, kk, vv), None

        o0 = jnp.zeros(q_blk.shape, jnp.float32)
        lse0 = jnp.full((b, h, tl), NEG, jnp.float32)
        (o, _, _, _), _ = jax.lax.scan(
            step, (o0, lse0, k_blk, v_blk), jnp.arange(sp))
        return o.astype(q_blk.dtype)

    spec = P(None, axis_name, None, None)
    fn = shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
