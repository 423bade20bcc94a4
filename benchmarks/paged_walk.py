"""The paged-attention kernel alone on the chip, one JSON line a geometry:
device microseconds a call (summed over the Mosaic calls of a profiler
trace), microseconds a live block, and the call's share of its roofline
(the K/V bytes the masks let through and the operations over them,
``chipbench/flops.py`` and ``chipbench/hybrid_bytes.py``; the chip's
peaks from its table).

    chiprun -- python3 benchmarks/paged_walk.py [--only agent_turns,verify_window] \
        [--calls 20] [--out chiprun_out/paged_walk.jsonl]

The geometries are the serving cells' calls and the callers no cell
runs: ``agent_turns`` (24 slots x 24 entries, 16 heads of 128, one row
a block), ``reason_decode`` (one pass's plane of the folded pool, 10
slots), ``think_decode``'s full and window-512 planes (48 slots, a K/V
group of 4 folded into four rows a block), ``chat_moe``'s (96 slots, 8
K/V heads of 128, a group of 6, window 4096 and none),
``nemotron3n.chat_ssm``'s attention plane (40 slots, 2 K/V heads in 8
pool rows, a group of 16), the speculative
verify window (5 rows at consecutive positions) and the 12-head pool that
takes the grid form, alone and under a verify window.  Live slots and their
contexts are drawn from ``--seed`` in the range each cell's traffic
reaches; a dead slot has a row of trash and ``pos = -1``.

``--entries 1,2,4,8`` times every geometry that runs the Mosaic loop of
two folded rows or more once at each count of table entries an
iteration (``kernels.paged_attention.entries_per_iteration`` overridden
HERE only; without it, once at what the rule gives); a line carries
``entries_an_iteration`` and whether it was forced.

``--only writes`` (or any ``write_*`` name) times the K/V WRITE alone
(``kernels.paged_attention.write`` into a donated pool, the device's busy
seconds of the trace over the calls): a decode step's rows and a prefill
piece's at each serving cell's pool, and for ``think_decode``, whose 10
pair rows do not fill the 16 of its bf16 pool, the partial write it
replaced (``pool.at[blk, off, :10].set(rows)``: a loop over the written
rows) beside it.  A step makes one such write for K and one for V of
every plane: 18 in ``think_decode``.

``--only latent`` (or any ``latent_*`` name) times the LATENT plane of
``dsv2lite.doc_qa_8k`` (``kernels.paged_attention.latent_attention_pallas``:
12 slots x 288 entries of 32 x 640 lanes, 16 query rows a cached row) at
one, two, four, eight and sixteen table entries an iteration, against 32 x 576 x
2 B / 819 GB/s = 0.045 us a live block; and a 128-row prefill piece over
an 8,300-token chain BOTH ways (after the rows ``latent_shared_*``: 1, 3
and 6 live slots over 1, 2 and 4 documents' heads of 256 blocks, the
kernel told which chains start alike beside the same call told nothing:
us a call and a live slot, the bytes fetched): absorbed (the queries against the
gathered latents, what ``serving.arch.LatentMoE`` runs) and expanded
(the gathered latents through ``W_kvb`` to per-head K and V first).

``--only mixed`` (or any ``long_reason_*`` name) times the two kinds of
plane of ``mimo25.long_reason`` (``serving.arch.SinkWindowMoE``: 24 slots
x 416 entries, ``[blocks, 32, 8, 192|128]``, the key stored at 256
lanes): the full plane (4 K/V heads in the 8 rows ``pool_rows`` gives,
16 query heads a K/V head) and the window-128 plane with its sink (8 K/V
heads, 8 query heads each; the table's entries under a slot's window are
the trash block, as the engine's window chains leave them), against the
PUBLISHED bytes (``chipbench/mixed_kv_bytes.py``: 2,560 and 5,120 B a
position; ``long_reason_full_short`` is the full plane at chains of
19-94 live blocks, beside the 94-375 of ``long_reason_full``: the two
together give what a slot costs whatever its length and what a block
costs); and one layer's attention of a 512-row prefill piece that
ends at 512 | 3,500 | 9,000 positions, both kinds, through ``attend``
(the chain walk of ``kernels/chain_attention.py`` since PR 47) beside the
dense spelling.  ``--only rungs`` times the same at ``think_decode``'s and
``chat_moe``'s planes by rung (128 | 256 | 512 rows over chains of 2,048
positions), the rule at zero so that every one walks: what set
``kernels.paged_attention.CHAIN_SCORE_BYTES``.

``--only slab`` (or any ``sala_*`` name) times the walk of
``sala9b.doc_qa_128k``'s HEAD-MAJOR planes (``kernels/block_sparse_attention
.walk``: one table a (row, K/V head) of 97 selected blocks, the 16 query
rows of the K/V head against its own ``[64, 128]`` slabs of K and of V,
the Mosaic kernel ``paged_slab_attention``): a decode step of 16 live
slots (32 tables) and of 32, a 512-row prefill piece (1,024 tables, eight
calls), us a call, us a table ENTRY, the share of the HBM peak the bytes
fetched make (32 KB an entry), the error against the block scan; with
``--entries 4,8,16,32`` the decode rows once at each count of slabs an
iteration.  ``write_sala_*`` (under ``--only writes``) times a
position's K write through the slab view
(``block_sparse_attention.write``) beside the scatter at ``(blk, :, off,
:)`` of the plane as it lies.

Refuses unless JAX finds a TPU: a number from a CPU run is no device
metric.
"""

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flash_walk import _timed  # noqa: E402 - device seconds of the Mosaic calls

# name -> slots, query rows before the group is folded, table entries a
# slot, pool blocks, rows a pool block has, lanes a row, query rows a K/V
# row, lower bound, live slots, their contexts (tokens attended, the new
# one included), and the configuration whose sizes count the bytes
GEOMETRIES = {
    "agent_turns": dict(S=24, W=1, NB=24, blocks=705, rows=16, dh=128,
                        group=1, window=None, live=5, ctx=(520, 760),
                        config="cerebras-gpt-1.3b"),
    "reason_decode": dict(S=10, W=1, NB=16, blocks=708, rows=16, dh=128,
                          group=1, window=None, live=5, ctx=(80, 500),
                          config="ouro-2.6b"),
    "think_decode_full": dict(S=48, W=1, NB=64, blocks=3073, rows=16,
                              dh=128, group=4, window=None, live=20,
                              ctx=(160, 2000),
                              config="phi-4-mini-flash-reasoning"),
    "think_decode_window": dict(S=48, W=1, NB=64, blocks=3073, rows=16,
                                dh=128, group=4, window=512, live=20,
                                ctx=(160, 2000),
                                config="phi-4-mini-flash-reasoning"),
    "chat_moe_full": dict(S=96, W=1, NB=64, blocks=6145, rows=8, dh=128,
                          group=6, window=None, live=20, ctx=(64, 2000),
                          config="trinity-large-preview"),
    "chat_moe_window": dict(S=96, W=1, NB=64, blocks=6145, rows=8, dh=128,
                            group=6, window=4096, live=20, ctx=(64, 2000),
                            config="trinity-large-preview"),
    # nemotron3n.chat_ssm's attention planes: 2 K/V heads in the 8 rows
    # ``pool_rows`` gives, 16 query heads a K/V head, 40 slots x 80 entries
    "chat_ssm_full": dict(S=40, W=1, NB=80, blocks=3201, rows=8, dh=128,
                          group=16, window=None, live=20, ctx=(100, 2500),
                          hk=2, config="nemotron-3-nano-30b-a3b"),
    "verify_window": dict(S=24, W=5, NB=24, blocks=705, rows=16, dh=128,
                          group=1, window=None, live=5, ctx=(520, 760),
                          config="cerebras-gpt-1.3b"),
    "grid_12_heads": dict(S=8, W=1, NB=16, blocks=200, rows=12, dh=128,
                          group=1, window=None, live=6, ctx=(64, 500),
                          config="cerebras-gpt-590m"),
    "grid_12_heads_verify": dict(S=8, W=5, NB=16, blocks=200, rows=12,
                                 dh=128, group=1, window=None, live=6,
                                 ctx=(64, 500), config="cerebras-gpt-590m"),
}
BLOCK_TOKENS = 32

# name -> pool blocks, rows of the pool's head axis, K/V rows written,
# index shape (slots, or slots x window rows) and the writes a step or a
# piece makes (K and V of every plane); where the K/V rows do not fill the
# head axis, `<name>_partial` is the write of part of it beside
WRITES = {
    "write_think_decode_step": dict(blocks=3073, rows=16, heads=10,
                                    index=(48,), a_step=18),
    "write_think_decode_piece": dict(blocks=3073, rows=16, heads=10,
                                     index=(1, 128), a_step=18),
    "write_agent_turns_step": dict(blocks=705, rows=16, heads=16,
                                   index=(24,), a_step=48),
    "write_reason_decode_step": dict(blocks=708, rows=16, heads=16,
                                     index=(10,), a_step=384),
    "write_chat_moe_step": dict(blocks=6145, rows=8, heads=8, index=(96,),
                                a_step=10),
    "write_chat_moe_piece": dict(blocks=6145, rows=8, heads=8,
                                 index=(1, 128), a_step=10),
}
WRITES.update({name + "_partial": dict(g, partial=True)
               for name, g in list(WRITES.items())
               if g["heads"] < g["rows"]})
# sala9b.doc_qa_128k's head-major planes ([blocks, 2, 64, 128]): a
# position's 2 heads through the slab view, and `_strided` the scatter at
# (blk, :, off, :) beside it; 4 writes a step (K and V of 2 planes)
WRITES.update({
    f"write_sala_{name}{how}": dict(blocks=10241, heads=2, index=index,
                                    a_step=4, head_major=how or "_slabs")
    for name, index in (("step", (32,)), ("piece", (1, 512)))
    for how in ("", "_strided")})

# the walk of sala9b.doc_qa_128k's head-major planes: `tables` (row, K/V
# head) tables of 97 selected blocks each (init 1 + topk 64 + window 32),
# 16 query rows a table, slabs of 64 x 128 lanes, K and V apart
SLAB = {
    "sala_slab_decode_16_live": dict(S=16, W=1),
    "sala_slab_decode_32_live": dict(S=32, W=1),
    "sala_slab_piece_512": dict(S=1, W=512),
}
SLAB_PLANE = dict(blocks=10241, hk=2, B=64, D=128, group=16, selected=97,
                  NB=2068)


# the latent plane of dsv2lite.doc_qa_8k: 10 of 12 slots live at
# contexts the cell's traffic reaches, `blocks` table entries an
# iteration of the Mosaic loop
LATENT = {f"latent_doc_qa_decode_{n}_a_copy": dict(
    S=12, W=1, NB=288, blocks=4609, lanes=640, heads=16, value_lanes=512,
    live=10, ctx=(8300, 9100), entries=n, config="deepseek-v2-lite")
    for n in (1, 2, 4, 8, 16)}
# the same plane where slots share a document's head (the cell's four
# heads of 8,192 tokens = 256 blocks, a question of 100-900 tokens after
# it): `live` slots over `docs` documents, slot i on document i % docs
SHARED = {f"latent_shared_{live}_live_{docs}_docs": dict(
    LATENT["latent_doc_qa_decode_8_a_copy"], live=live, docs=docs)
    for live in (1, 3, 6) for docs in (1, 2, 4) if docs == 1 or live > 1}
PIECES = {"latent_doc_qa_piece_absorbed": "absorbed",
          "latent_doc_qa_piece_expanded": "expanded"}
SCALE = 0.11472

# the planes of mimo25.long_reason by kind: 10 of 24 slots live at
# contexts the cell's traffic reaches; `hk` K/V heads in `rows` pool rows
MIXED = {
    "long_reason_full": dict(S=24, W=1, NB=416, blocks=9985, rows=8, hk=4,
                             group=16, window=None, sink=False, live=10,
                             ctx=(3000, 12000), kind="full"),
    # the full plane at the cell's shorter chains (19-94 live blocks a
    # slot): what a slot costs whatever its length shows here
    "long_reason_full_short": dict(S=24, W=1, NB=416, blocks=9985, rows=8,
                                   hk=4, group=16, window=None, sink=False,
                                   live=10, ctx=(600, 3000), kind="full"),
    "long_reason_window": dict(S=24, W=1, NB=416, blocks=505, rows=8, hk=8,
                               group=8, window=128, sink=True, live=10,
                               ctx=(3000, 12000), kind="window"),
    "long_reason_full_piece": dict(S=1, W=512, NB=416, blocks=9985, rows=8,
                                   hk=4, group=16, window=None, sink=False,
                                   live=1, ctx=(9000, 9000), kind="full",
                                   lanes=(192, 256, 128)),
    "long_reason_window_piece": dict(S=1, W=512, NB=416, blocks=505, rows=8,
                                     hk=8, group=8, window=128, sink=True,
                                     live=1, ctx=(9000, 9000),
                                     kind="window", lanes=(192, 256, 128),
                                     released=True),
}
# where a piece of long_reason ends: one that starts a prompt, the cell's
# mean, a long context
PIECE_CONTEXTS = (512, 3500, 9000)

# one layer's attention of a prefill piece through ``attend`` at the
# other paged cells' planes, by rung: the dense spelling beside the walk
# (``--only rungs``); `hk` K/V rows of 128 lanes in `rows` pool rows
RUNGS = {
    f"{cell}_{plane}_piece_{w}": dict(g, W=w, window=window)
    for cell, g in (
        ("think_decode", dict(NB=64, blocks=3073, rows=16, hk=10, group=4,
                              ctx=1500, dtype="float32")),
        ("chat_moe", dict(NB=64, blocks=6145, rows=8, hk=8, group=6,
                          ctx=1500, dtype="bfloat16")))
    for plane, window in (("full", None),
                          ("window", 512 if cell == "think_decode" else 4096))
    for w in (128, 256, 512)}


def _config(name):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


def case(name, seed):
    """The call's arguments as numpy arrays, and what it has to visit:
    ``(q, pool_k, pool_v, table, pos, how, live_blocks, attended)``."""
    import numpy as np

    g = GEOMETRIES[name]
    S, W, NB, B = g["S"], g["W"], g["NB"], BLOCK_TOKENS
    rng = np.random.default_rng(seed)
    shape = (g["blocks"], B, g["rows"], g["dh"])
    pool_k = rng.standard_normal(shape, np.float32) * 0.5
    pool_v = rng.standard_normal(shape, np.float32) * 0.5
    if "hk" in g:               # the rows ``pool_rows`` added hold zeros
        pool_k[:, :, g["hk"]:] = pool_v[:, :, g["hk"]:] = 0.0
    heads = _kv_rows(g) * g["group"]
    q = rng.standard_normal((S, W, heads, g["dh"]), np.float32) * 0.5
    table = np.zeros((S, NB), np.int32)
    pos = np.full((S, W), -1, np.int32)
    free = rng.permutation(np.arange(1, g["blocks"]))
    live_blocks = attended = 0
    for s in rng.choice(S, g["live"], replace=False):
        ctx = int(rng.integers(g["ctx"][0], g["ctx"][1] + 1))
        ctx = min(ctx, NB * B)
        pos[s] = ctx - W + np.arange(W)
        n = (ctx - 1) // B + 1
        table[s, :n], free = free[:n], free[n:]
        for at in pos[s]:
            low = 0 if g["window"] is None else max(at - g["window"] + 1, 0)
            attended += int(at) + 1 - low
        low = (0 if g["window"] is None
               else max(int(pos[s].min()) - g["window"] + 1, 0))
        live_blocks += n - low // B
    how = dict(group=g["group"], window=g["window"])
    return q, pool_k, pool_v, table, pos, how, live_blocks, attended


def _kv_rows(g):
    """Rows of a block that hold K/V (``pool_rows`` may have added
    empty ones): the hybrid's K/V heads, as many to a row as fill its
    lanes (a pair of ``think_decode``'s heads of 64)."""
    if g["group"] == 1:
        return g["rows"]
    if "hk" in g:
        return g["hk"]
    from chipbench import hybrid_bytes

    size = hybrid_bytes.sizes(_config(g["config"]))
    return size["kv_heads"] * size["head_dim"] // g["dh"]


def least_seconds(name, attended, peak):
    """The least time the chip could take for one call: the K/V of the
    positions the masks let through, once a query row of the window
    (every row of a K/V group reads the same keys: once), and the
    operations over them."""
    from chipbench import families, flops, hybrid_bytes

    g = GEOMETRIES[name]
    cfg = _config(g["config"])
    if cfg.get("family") == "ssm_moe":
        # ONE plane's call: the K and V the model caches of a position
        from chipbench import ssm_moe_bytes

        planes = ssm_moe_bytes.sizes(cfg)["attention_layers"]
        ops, nbytes = (n / planes
                       for n in ssm_moe_bytes.attention(cfg, attended))
        return flops.roofline_seconds(ops, nbytes, peak)
    if g["group"] > 1:
        size = hybrid_bytes.sizes(cfg)
        ops = 6 * size["heads"] * size["head_dim"] * attended
        nbytes = hybrid_bytes.plane_token_bytes(cfg) * attended
    else:
        size = families.sizes(cfg)
        ops = 4 * size["heads"] * size["head_dim"] * attended
        nbytes = (flops.kv_bytes_per_token(cfg) // size["kv_planes"]
                  * attended / g["W"])
    return flops.roofline_seconds(ops, nbytes, peak)


def measure(name, calls, peak, seed):
    import jax
    import jax.numpy as jnp

    import numpy as np

    from paddle_tpu.kernels.paged_attention import (
        paged_attention_pallas, paged_attention_ref)

    q, pk, pv, table, pos, how, live_blocks, attended = case(name, seed)
    fn = jax.jit(lambda *a: paged_attention_pallas(
        *a, interpret=False, out_dtype=jnp.float32, **how))
    args = (jnp.asarray(q, jnp.bfloat16), jnp.asarray(pk, jnp.bfloat16),
            jnp.asarray(pv, jnp.bfloat16), jnp.asarray(table),
            jnp.asarray(pos))
    us = _timed(fn, args, calls)
    # the live slots' rows against the block-scan oracle, on the chip
    live = pos.max(axis=1) >= 0
    got = np.asarray(fn(*args))[live]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda *a: paged_attention_ref(
            *a, out_dtype=jnp.float32, **how))(*args))[live]
    err = float(np.abs(got - want).max() / np.abs(want).max())
    least, bound = least_seconds(name, attended, peak)
    g = GEOMETRIES[name]
    return {"geometry": name, **{k: g[k] for k in (
        "S", "W", "NB", "rows", "dh", "group", "window", "live")},
        "live_blocks": live_blocks, "rows_a_block": g["W"] * g["group"],
        "us_a_call": us, "us_a_live_block": us / live_blocks,
        "roofline_pct": 100.0 * least * 1e6 / us, "bound": bound,
        "rel_err_vs_xla_ref": err}


def _latent_plane(g, rng):
    """A latent plane of geometry ``g`` with nobody live yet: queries and
    pool (the values a position holds, zeros in the lanes past them), an
    empty table, positions at -1, the free blocks in a seeded order."""
    import numpy as np

    from chipbench import latent_bytes

    S, B, L = g["S"], BLOCK_TOKENS, g["lanes"]
    values = latent_bytes.sizes(_config(g["config"]))["values_per_position"]
    pool = np.zeros((g["blocks"], B, L), np.float32)
    pool[..., :values] = rng.standard_normal(
        (g["blocks"], B, values), np.float32) * 0.5
    q = np.zeros((S, g["W"], g["heads"], L), np.float32)
    q[..., :values] = rng.standard_normal(
        (S, g["W"], g["heads"], values), np.float32) * 0.5
    return (q, pool, np.zeros((S, g["NB"]), np.int32),
            np.full((S, g["W"]), -1, np.int32),
            rng.permutation(np.arange(1, g["blocks"])))


def measure_latent(name, calls, peak, seed):
    import jax
    import jax.numpy as jnp

    import numpy as np

    from chipbench import latent_bytes
    from paddle_tpu.kernels.paged_attention import (
        latent_attention_pallas, paged_attention_ref)

    g = LATENT[name]
    S, B = g["S"], BLOCK_TOKENS
    cfg = _config(g["config"])
    rng = np.random.default_rng(seed)
    q, pool, table, pos, free = _latent_plane(g, rng)
    contexts = []
    for s in rng.choice(S, g["live"], replace=False):
        ctx = int(rng.integers(g["ctx"][0], g["ctx"][1] + 1))
        n = (ctx - 1) // B + 1
        table[s, :n], free = free[:n], free[n:]
        pos[s], contexts = ctx - 1, contexts + [ctx]
    live_blocks = sum((c - 1) // B + 1 for c in contexts)
    fn = jax.jit(lambda *a: latent_attention_pallas(
        *a, g["value_lanes"], scale=SCALE, out_dtype=jnp.float32,
        interpret=False, blocks=g["entries"]))
    args = (jnp.asarray(q, jnp.bfloat16), jnp.asarray(pool, jnp.bfloat16),
            jnp.asarray(table), jnp.asarray(pos))
    us = _timed(fn, args, calls)
    live = pos.max(axis=1) >= 0
    got = np.asarray(fn(*args))[live]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda q_, p_, t_, at: paged_attention_ref(
            q_, p_, None, t_, at, value_lanes=g["value_lanes"], scale=SCALE,
            out_dtype=jnp.float32))(*args))[live]
    err = float(np.abs(got - want).max() / np.abs(want).max())
    # ONE plane's call: latent_bytes counts all the planes a token holds
    least = latent_bytes.least_seconds(cfg, contexts, peak) / (
        latent_bytes.sizes(cfg)["planes"])
    return {"geometry": name, **{k: g[k] for k in (
        "S", "W", "NB", "lanes", "heads", "live", "entries")},
        "live_blocks": live_blocks, "rows_a_block": g["heads"],
        "us_a_call": us, "us_a_live_block": us / live_blocks,
        "roofline_pct": 100.0 * least * 1e6 / us,
        "rel_err_vs_xla_ref": err}


def measure_shared(name, calls, seed):
    """The latent kernel at ``live`` slots over ``docs`` documents' heads
    (``SHARED``), told which chains start alike (``shared_runs``) beside
    the same call told nothing: us a call and a live slot each way, the
    bytes each fetches, and that the two answer alike."""
    import jax
    import jax.numpy as jnp

    import numpy as np

    from chipbench import latent_bytes
    from paddle_tpu.kernels.paged_attention import (
        latent_attention_pallas, shared_runs)

    g = SHARED[name]
    S, B = g["S"], BLOCK_TOKENS
    values = latent_bytes.sizes(_config(g["config"]))["values_per_position"]
    rng = np.random.default_rng(seed)
    q, pool, table, pos, free = _latent_plane(g, rng)
    head = 8192 // B
    heads, free = free[:g["docs"] * head].reshape(g["docs"], head), free[
        g["docs"] * head:]
    for i, s in enumerate(sorted(rng.choice(S, g["live"], replace=False))):
        ctx = int(rng.integers(g["ctx"][0], g["ctx"][1] + 1))
        n = (ctx - 1) // B + 1
        table[s, :head] = heads[i % g["docs"]]
        table[s, head:n], free = free[:n - head], free[n - head:]
        pos[s] = ctx - 1
    runs = shared_runs(table, np.maximum(pos[:, 0], 0) // B, g["heads"])
    live_blocks = int(np.sum(pos[:, 0] // B + 1))
    fetched = live_blocks - int(np.sum(
        runs[:, 0] * np.maximum(runs[:, 1] - 1, 0)))
    args = (jnp.asarray(q, jnp.bfloat16), jnp.asarray(pool, jnp.bfloat16),
            jnp.asarray(table), jnp.asarray(pos))
    how = dict(scale=SCALE, out_dtype=jnp.float32, interpret=False)
    alone = jax.jit(lambda *a: latent_attention_pallas(
        *a, g["value_lanes"], **how))
    told = jax.jit(lambda *a: latent_attention_pallas(
        *a[:4], g["value_lanes"], shared=a[4], **how))
    us_alone = _timed(alone, args, calls)
    us_told = _timed(told, args + (jnp.asarray(runs),), calls)
    live = pos[:, 0] >= 0
    want = np.asarray(alone(*args))[live]
    got = np.asarray(told(*args, jnp.asarray(runs)))[live]
    block_bytes = B * values * 2
    return {"geometry": name, **{k: g[k] for k in (
        "S", "NB", "lanes", "heads", "live", "docs")},
        "runs": [[int(r[0]), int(r[1])] for r in runs if r[1]],
        "live_blocks": live_blocks, "fetched_blocks": fetched,
        "us_a_call_alone": us_alone, "us_a_call_told": us_told,
        "us_a_live_slot_alone": us_alone / g["live"],
        "us_a_live_slot_told": us_told / g["live"],
        "bytes_alone": live_blocks * block_bytes,
        "bytes_told": fetched * block_bytes,
        "max_abs_diff": float(np.abs(got - want).max())}


def measure_mixed(name, calls, peak, seed):
    """One paged call on a plane of ``mimo25.long_reason`` (decode: the
    Mosaic kernel against the block-scan oracle) or one layer's dense
    attention of a 512-row piece (``attend``: device busy seconds)."""
    import jax
    import jax.numpy as jnp

    import numpy as np

    from chipbench import mixed_kv_bytes
    from paddle_tpu.kernels import paged_attention as pa

    g = MIXED[name]
    S, W, NB, B = g["S"], g["W"], g["NB"], BLOCK_TOKENS
    cfg = _config("mimo-v2.5")
    rng = np.random.default_rng(seed)
    if W > 1:
        return _mixed_piece(name, calls, peak, rng)
    pool_k = np.zeros((g["blocks"], B, g["rows"], 256), np.float32)
    pool_k[:, :, :g["hk"], :192] = rng.standard_normal(
        (g["blocks"], B, g["hk"], 192), np.float32) * 0.5
    pool_v = np.zeros((g["blocks"], B, g["rows"], 128), np.float32)
    pool_v[:, :, :g["hk"]] = rng.standard_normal(
        (g["blocks"], B, g["hk"], 128), np.float32) * 0.5
    q = rng.standard_normal((S, W, 64, 192), np.float32) * 0.5
    sink = (jnp.asarray(rng.uniform(4.0, 6.5, 64), jnp.float32)
            if g["sink"] else None)
    table = np.zeros((S, NB), np.int32)
    pos = np.full((S, W), -1, np.int32)
    free = rng.permutation(np.arange(1, g["blocks"]))
    contexts, live_blocks = [], 0
    for s in rng.choice(S, g["live"], replace=False):
        ctx = int(rng.integers(g["ctx"][0], g["ctx"][1] + 1))
        pos[s] = ctx - W + np.arange(W)
        lo = (0 if g["window"] is None
              else max(int(pos[s, 0]) - g["window"] + 1, 0) // B)
        n = (ctx - 1) // B + 1
        table[s, lo:n], free = free[:n - lo], free[n - lo:]
        live_blocks += n - lo
        contexts += [int(at) + 1 for at in pos[s]]
    how = dict(group=g["group"], window=g["window"], sink=sink)
    args = (jnp.asarray(q, jnp.bfloat16), jnp.asarray(pool_k, jnp.bfloat16),
            jnp.asarray(pool_v, jnp.bfloat16), jnp.asarray(table),
            jnp.asarray(pos))
    least = sum(max(nbytes / peak["hbm_bytes_per_s"],
                    ops / peak["bf16_flops_per_s"])
                for ops, nbytes in (mixed_kv_bytes.paged_call(
                    cfg, g["kind"], n) for n in contexts))
    out = {"geometry": name, **{k: g[k] for k in (
        "S", "W", "NB", "rows", "hk", "group", "window", "sink", "live")},
        "live_blocks": live_blocks}
    qs = jnp.pad(args[0], ((0, 0),) * 3 + ((0, 64),))
    fn = jax.jit(lambda q_, *a: pa.paged_attention_pallas(
        q_, *a, interpret=False, out_dtype=jnp.float32,
        scale=192 ** -0.5, **how))
    us = _timed(fn, (qs,) + args[1:], calls)
    live = pos.max(axis=1) >= 0
    got = np.asarray(fn(qs, *args[1:]))[live]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda q_, *a: pa.paged_attention_ref(
            q_, *a, out_dtype=jnp.float32, scale=192 ** -0.5,
            **how))(qs, *args[1:]))[live]
    out.update(rel_err_vs_xla_ref=float(
        np.abs(got - want).max() / np.abs(want).max()),
        us_a_live_block=us / live_blocks, us_a_call=us,
        roofline_pct=100.0 * least * 1e6 / us)
    return out


def _mixed_piece(name, calls, peak, rng):
    """One layer's attention of a 512-row piece on a plane of
    ``mimo25.long_reason`` through ``attend``, by where the piece ends
    (``PIECE_CONTEXTS``), beside the dense spelling."""
    import jax.numpy as jnp

    from chipbench import mixed_kv_bytes

    g = MIXED[name]
    cfg = _config("mimo-v2.5")
    sink = (jnp.asarray(rng.uniform(4.0, 6.5, 64), jnp.float32)
            if g["sink"] else None)

    def least(ctx):
        # a piece's rows all read the chain once: the bytes of its last
        # row (a window plane: the window and the piece), the operations
        # of all of them
        ops = sum(mixed_kv_bytes.paged_call(cfg, g["kind"], n)[0]
                  for n in range(ctx - g["W"] + 1, ctx + 1))
        nbytes = mixed_kv_bytes.paged_call(cfg, g["kind"], ctx)[1]
        if g["window"]:
            nbytes = (min(g["W"] + g["window"], ctx)
                      * mixed_kv_bytes.position_bytes(cfg, "window"))
        return max(nbytes / peak["hbm_bytes_per_s"],
                   ops / peak["bf16_flops_per_s"])

    out = {"geometry": name, **{k: g[k] for k in (
        "S", "W", "NB", "rows", "hk", "group", "window", "sink")}}
    out.update(_piece(g, rng,
                      dict(group=g["group"], window=g["window"], sink=sink),
                      PIECE_CONTEXTS, calls, least, None))
    return out


def _piece_args(g, rng, ctx):
    """A plane's pools and ONE slot's piece of ``W`` rows that ends at
    position ``ctx - 1``: ``hk`` K/V heads in ``rows`` pool rows, keys of
    ``lanes[0]`` lanes stored at ``lanes[1]`` over values of
    ``lanes[2]`` (128 all three unless stated); with ``released`` the
    table's entries under the piece's window name the trash block."""
    import jax.numpy as jnp
    import numpy as np

    W, NB, B, hk = g["W"], g["NB"], BLOCK_TOKENS, g["hk"]
    dk, dks, dv = g.get("lanes", (128, 128, 128))
    pool_k = np.zeros((g["blocks"], B, g["rows"], dks), np.float32)
    pool_k[:, :, :hk, :dk] = rng.standard_normal(
        (g["blocks"], B, hk, dk), np.float32) * 0.5
    pool_v = np.zeros((g["blocks"], B, g["rows"], dv), np.float32)
    pool_v[:, :, :hk] = rng.standard_normal(
        (g["blocks"], B, hk, dv), np.float32) * 0.5
    q = rng.standard_normal((1, W, hk * g["group"], dk), np.float32) * 0.5
    table = np.zeros((1, NB), np.int32)
    pos = (ctx - W + np.arange(W, dtype=np.int32))[None]
    lo = (max(int(pos[0, 0]) - g["window"] + 1, 0) // B
          if g.get("released") else 0)
    n = (ctx - 1) // B + 1
    table[0, lo:n] = rng.permutation(np.arange(1, g["blocks"]))[:n - lo]
    return (jnp.asarray(q, jnp.bfloat16), jnp.asarray(pool_k, jnp.bfloat16),
            jnp.asarray(pool_v, jnp.bfloat16), jnp.asarray(table),
            jnp.asarray(pos))


def _piece(g, rng, how, contexts, calls, least, rule):
    """One layer's attention of a piece of ``g`` by where it ends, through
    ``attend`` with ``CHAIN_SCORE_BYTES`` at ``rule`` (``None``: as the
    module has it) beside the dense spelling (the rule out of reach):
    device busy microseconds a call, and their worst difference on the
    chip (the dense one at the highest matmul precision)."""
    import jax

    import numpy as np

    from paddle_tpu.kernels import paged_attention as pa

    def spelled(rule):
        def fn(*a):
            # read at trace time, like every constant of the module
            keep = pa.CHAIN_SCORE_BYTES
            pa.CHAIN_SCORE_BYTES = keep if rule is None else rule
            try:
                return pa.attend(*a, **how)
            finally:
                pa.CHAIN_SCORE_BYTES = keep
        return jax.jit(fn)

    walk, dense = spelled(rule), spelled(float("inf"))
    out = {}
    for ctx in contexts:
        args = _piece_args(g, rng, ctx)
        us, _ = _busy_us(lambda last, *a: walk(*a), None, args, calls)
        us_dense, _ = _busy_us(lambda last, *a: dense(*a), None, args,
                               calls)
        got = np.asarray(walk(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(dense(*args), np.float32)
        out[f"ctx_{ctx}"] = {
            "us_a_call": us, "us_a_row": us / args[0].shape[1],
            "us_dense": us_dense,
            "roofline_pct": 100.0 * least(ctx) * 1e6 / us if least else None,
            "rel_err_vs_dense": float(np.abs(got - want).max()
                                      / np.abs(want).max())}
    return out


def measure_rung(name, calls, seed):
    """One layer's attention of a prefill piece of ``think_decode`` or
    ``chat_moe`` through ``attend``: the dense spelling beside the walk,
    whichever ``attend`` chooses."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import paged_attention as pa

    g = RUNGS[name]
    W, NB, B = g["W"], g["NB"], BLOCK_TOKENS
    rng = np.random.default_rng(seed)
    how = dict(group=g["group"], window=g["window"],
               out_dtype=jnp.dtype(g["dtype"]))
    out = {"geometry": name, **{k: g[k] for k in (
        "W", "NB", "rows", "hk", "group", "window")},
        "dense_score_bytes": 4 * W * g["group"] * g["rows"] * NB * B,
        "attend_is": ("walk" if pa.walks_chain(W, g["group"] * g["rows"],
                                               NB * B) else "dense")}
    # the walk whatever the rule says, beside the dense spelling
    out.update(_piece(g, rng, how, (g["ctx"],), calls, None, 0))
    return out


def measure_piece(name, calls, seed):
    """One layer's attention of a 128-row prefill piece at the end of an
    8,300-token chain, absorbed or expanded (module docstring); both
    gather the chain once and attend it densely."""
    import jax
    import jax.numpy as jnp

    import tempfile

    import numpy as np

    from chipbench import trace_reduce
    from paddle_tpu.kernels.paged_attention import attend

    form = PIECES[name]
    W, NB, B, L, h, rank = 128, 288, BLOCK_TOKENS, 640, 16, 512
    nope, rope, v = 128, 64, 128
    rng = np.random.default_rng(seed)
    pool = np.zeros((4609, B, L), np.float32)
    pool[..., :rank + rope] = rng.standard_normal(
        (4609, B, rank + rope), np.float32) * 0.5
    ctx = 8300
    table = np.zeros((1, NB), np.int32)
    n = (ctx - 1) // B + 1
    table[0, :n] = rng.permutation(np.arange(1, 4609))[:n]
    pos = jnp.asarray(ctx - W + np.arange(W), jnp.int32)[None]
    q = jnp.asarray(rng.standard_normal((1, W, h, nope + rope)) * 0.5,
                    jnp.bfloat16)
    kvb = jnp.asarray(rng.standard_normal((rank, h, nope + v)) * 0.05,
                      jnp.bfloat16)

    def absorbed(pool, q, kvb, table, pos):
        q_lat = jnp.einsum("...hn,rhn->...hr", q[..., :nope], kvb[..., :nope])
        row = jnp.concatenate(
            [q_lat, q[..., nope:],
             jnp.zeros((1, W, h, L - rank - rope), q.dtype)], axis=-1)
        u = attend(row, pool, None, table, pos, value_lanes=rank, scale=SCALE)
        return jnp.einsum("...hr,rhv->...hv", u, kvb[..., nope:])

    def expanded(pool, q, kvb, table, pos):
        rows = pool[table[0]].reshape(NB * B, L)
        kv = jnp.einsum("tr,rhn->thn", rows[:, :rank], kvb)
        s = (jnp.einsum("whn,thn->hwt", q[0, :, :, :nope], kv[..., :nope],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("whn,tn->hwt", q[0, :, :, nope:],
                          rows[:, rank:rank + rope],
                          preferred_element_type=jnp.float32)) * SCALE
        keep = jnp.arange(NB * B)[None, None, :] <= pos[0][None, :, None]
        p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        return jnp.einsum("hwt,thv->whv", p.astype(q.dtype),
                          kv[..., nope:])[None]

    fn = jax.jit(absorbed if form == "absorbed" else expanded)
    args = (jnp.asarray(pool, jnp.bfloat16), q, kvb, jnp.asarray(table), pos)
    # no Mosaic call to find by name: the device's busy seconds
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory(prefix="paged_walk") as td:
        with jax.profiler.trace(td):
            jax.block_until_ready([fn(*args) for _ in range(calls)])
        (events,) = trace_reduce.chip_ops(trace_reduce.load(td)).values()
    us = 1e-3 * sum(end - start for start, end in
                    trace_reduce.busy_union(events)) / calls
    a, b = (np.asarray(jax.jit(f)(*args), np.float32)
            for f in (absorbed, expanded))
    return {"geometry": name, "form": form, "rows": W, "context": ctx,
            "us_a_layer_piece": us,
            "absorbed_vs_expanded_rel": float(
                np.abs(a - b).max() / np.abs(b).max())}


def measure_slab(name, calls, peak, seed):
    """The walk of a head-major plane at ``sala9b.doc_qa_128k``'s sizes:
    every row past the dense length, its K/V heads' selections drawn apart
    (ascending, the last two its own block and the one before), through
    ``block_sparse_attention.walk``."""
    import jax
    import jax.numpy as jnp

    import numpy as np

    from paddle_tpu.kernels import block_sparse_attention as bsa

    g, z = SLAB[name], SLAB_PLANE
    S, W, hk, B, D, n = g["S"], g["W"], z["hk"], z["B"], z["D"], z["selected"]
    rng = np.random.default_rng(seed)
    shape = (z["blocks"], hk, B, D)
    pool_k, pool_v = (jnp.asarray(rng.standard_normal(shape, np.float32)
                                  * 0.5, jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((S, W, hk * z["group"], D),
                                        np.float32) * 0.5, jnp.bfloat16)
    # a chain of distinct blocks a slot; a row selects among those under
    # its own block
    own = 2050 + (np.arange(W) // B if W > 1 else np.zeros(W, np.int64))
    chains = np.stack([rng.permutation(np.arange(1, z["blocks"]))[:z["NB"]]
                       for _ in range(S)])
    sel = np.stack([np.sort(np.concatenate([
        rng.choice(own[w] - 1, n - 2, replace=False), [own[w] - 1, own[w]]]))
        for _ in range(S) for w in range(W) for _ in range(hk)])
    sel = sel.reshape(S, W, hk, n)
    ids = jnp.asarray(np.take_along_axis(chains[:, None, None, :], sel, -1),
                      jnp.int32)
    at = jnp.asarray(np.broadcast_to(
        (n - 1) * B + (np.arange(W) % B if W > 1 else 37), (S, W)), jnp.int32)
    how = dict(group=z["group"], scale=D ** -0.5, out_dtype=jnp.float32)
    fn = jax.jit(lambda *a: bsa.walk(*a, **how))
    args = (q, pool_k, pool_v, ids, at)
    tables = S * W * hk
    if tables <= bsa.TABLE_ROWS:
        us = _timed(fn, args, calls)
    else:           # several Mosaic calls under one map: the device's busy
        us, _ = _busy_us(lambda last, *a: fn(*a), None, args, calls)
    got = np.asarray(fn(*args))
    keep = bsa._paged.attend
    bsa._paged.attend = lambda *a, **k: bsa._paged.paged_attention_ref(
        *a, **k)
    try:
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(lambda *a: bsa.walk(*a, **how))(*args))
    finally:
        bsa._paged.attend = keep
    entries = tables * n
    fetched = entries * 2 * B * D * 2
    return {"geometry": name, "S": S, "W": W, "tables": tables,
            "entries_a_table": n, "query_rows_a_table": z["group"],
            "slab": [B, D], "us_a_call": us, "us_a_table": us / tables,
            "us_an_entry": us / entries, "bytes_fetched": fetched,
            "hbm_share_pct": 100.0 * fetched / peak["hbm_bytes_per_s"]
            / (us * 1e-6),
            "rel_err_vs_xla_ref": float(np.abs(got - want).max()
                                        / np.abs(want).max())}


def _busy_us(fn, pool, args, calls):
    """Device microseconds a call of ``pool = fn(pool, *args)``: the
    busy seconds of a profiler trace over ``calls`` queued calls (a loop
    and what it holds count once)."""
    import tempfile

    import jax

    from chipbench import trace_reduce

    pool = jax.block_until_ready(fn(pool, *args))  # compile, warm
    with tempfile.TemporaryDirectory(prefix="paged_walk") as td:
        with jax.profiler.trace(td):
            for _ in range(calls):
                pool = fn(pool, *args)
            jax.block_until_ready(pool)
        chips = trace_reduce.chip_ops(trace_reduce.load(td))
    (events,) = chips.values()
    ns = sum(end - start for start, end in trace_reduce.busy_union(events))
    return 1e-3 * ns / calls, pool


def measure_write(name, calls, seed):
    import jax
    import jax.numpy as jnp

    import numpy as np

    from paddle_tpu.kernels.paged_attention import write

    g = WRITES[name]
    rng = np.random.default_rng(seed)
    index, heads, B = g["index"], g["heads"], BLOCK_TOKENS
    # distinct blocks, as live slots' are; a window's rows run on through
    # the blocks of its slot's chain
    n = int(np.prod(index))
    if len(index) == 1:
        blk = rng.permutation(np.arange(1, g["blocks"]))[:n]
        off = rng.integers(0, B, n)
    else:
        at = int(rng.integers(0, B)) + np.arange(index[1])
        chain = rng.permutation(np.arange(1, g["blocks"]))[:at[-1] // B + 1]
        blk, off = chain[at // B][None, :], (at % B)[None, :]
    blk = jnp.asarray(blk.reshape(index), jnp.int32)
    off = jnp.asarray(off.reshape(index), jnp.int32)
    rows = jnp.asarray(rng.standard_normal((*index, heads, 128)),
                       jnp.bfloat16)
    if g.get("head_major"):
        return _measure_slab_write(name, g, blk, off, rows, calls)
    pool = jnp.zeros((g["blocks"], B, g["rows"], 128), jnp.bfloat16)
    if g.get("partial"):
        spelt = lambda p, b, o, r: p.at[b, o, :heads].set(r)  # noqa: E731
    else:
        spelt = write
    us, pool = _busy_us(jax.jit(spelt, donate_argnums=0), pool,
                        (blk, off, rows), calls)
    # what was written is there, and nothing else is
    got = np.asarray(pool[blk, off], np.float32)
    ok = bool(np.array_equal(got[..., :heads, :],
                             np.asarray(rows, np.float32))
              and not got[..., heads:, :].any()
              and int(jnp.count_nonzero(pool)) == int(
                  jnp.count_nonzero(rows)))
    return {"geometry": name, **{k: g[k] for k in ("blocks", "rows", "heads")},
            "index": list(index), "rows_written": n,
            "spelling": "partial" if g.get("partial") else "whole",
            "us_a_write": us, "us_a_row": us / n,
            "writes_a_step": g["a_step"],
            "us_a_step": us * g["a_step"], "written_exactly": ok}


def _measure_slab_write(name, g, blk, off, rows, calls):
    """A position's heads into a head-major pool ``[blocks, heads, 64,
    128]``: through the slab view or at ``(blk, :, off, :)``."""
    import jax
    import jax.numpy as jnp

    import numpy as np

    from paddle_tpu.kernels import block_sparse_attention as bsa

    heads, n = g["heads"], int(np.prod(g["index"]))
    off = off * 2 + (blk % 2)       # `measure_write` drew offsets under 32
    pool = jnp.zeros((g["blocks"], heads, 64, 128), jnp.bfloat16)
    spelt = (bsa.write if g["head_major"] == "_slabs" else
             lambda p, b, o, r: p.at[b, :, o].set(r))  # noqa: E731
    us, pool = _busy_us(jax.jit(spelt, donate_argnums=0), pool,
                        (blk, off, rows), calls)
    ok = bool(jnp.array_equal(pool[blk, :, off], rows)
              and int(jnp.count_nonzero(pool)) == int(
                  jnp.count_nonzero(rows)))
    return {"geometry": name, "blocks": g["blocks"], "heads": heads,
            "index": list(g["index"]), "rows_written": n,
            "spelling": g["head_major"].strip("_"), "us_a_write": us,
            "us_a_row": us / n, "writes_a_step": g["a_step"],
            "us_a_step": us * g["a_step"], "written_exactly": ok}


def _shares_a_fold(name):
    """Whether the geometry runs the Mosaic loop of two rows or more (a
    K/V plane whose pool Mosaic slices, decode or a narrow window)."""
    if name in SLAB:
        return True
    g = GEOMETRIES.get(name) or MIXED.get(name)
    return bool(g and g["W"] * g["group"] > 1 and g["W"] < 8
                and g["rows"] % 8 == 0)


@contextlib.contextmanager
def _entries(forced):
    """``kernels.paged_attention.entries_per_iteration`` answering
    ``forced`` while a geometry is traced, or as the module has it (``None``); yields what it answers
    for a geometry by name."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import paged_attention as pa

    keep = pa.entries_per_iteration
    if forced is not None:
        pa.entries_per_iteration = lambda *a: forced

    def answer(name):
        if name in SLAB:
            z = SLAB_PLANE
            return pa.entries_per_iteration(
                z["B"], 1, z["D"], z["D"], z["group"], jnp.bfloat16,
                z["selected"])
        g = GEOMETRIES.get(name) or MIXED[name]
        dk, dv = (256, 128) if name in MIXED else (g["dh"], g["dh"])
        return pa.entries_per_iteration(
            BLOCK_TOKENS, g["rows"], dk, dv,
            g["W"] * g["group"] * g["rows"], jnp.bfloat16,
            pa.window_entries(g["NB"], BLOCK_TOKENS, g["W"], g["window"]))

    try:
        yield answer
    finally:
        pa.entries_per_iteration = keep


def _measure(name, args, peak):
    if name in WRITES:
        return measure_write(name, args.calls, args.seed)
    if name in LATENT:
        return measure_latent(name, args.calls, peak, args.seed)
    if name in SHARED:
        return measure_shared(name, args.calls, args.seed)
    if name in MIXED:
        return measure_mixed(name, args.calls, peak, args.seed)
    if name in PIECES:
        return measure_piece(name, args.calls, args.seed)
    if name in RUNGS:
        return measure_rung(name, args.calls, args.seed)
    if name in SLAB:
        return measure_slab(name, args.calls, peak, args.seed)
    return measure(name, args.calls, peak, args.seed)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default="",
                    help="comma-separated geometry names (default: all "
                         "the kernel's); `writes`: the K/V writes")
    ap.add_argument("--entries", default="",
                    help="comma-separated table entries an iteration of "
                         "the loop of two rows or more (1,2,4,8): each K/V "
                         "geometry that runs it is timed once at each, "
                         "`entries_per_iteration` overridden here only "
                         "(default: what the rule gives, once)")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=33)
    ap.add_argument("--out", default="chiprun_out/paged_walk.jsonl")
    args = ap.parse_args()

    import jax

    from chipbench import flops

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"paged_walk times the chip; JAX found {dev.platform}")
    peak = flops.peaks(dev.device_kind)
    names = [n for n in args.only.split(",") if n] or list(GEOMETRIES)
    if "writes" in names:
        names = [n for n in names if n != "writes"] + list(WRITES)
    if "mixed" in names:
        names = [n for n in names if n != "mixed"] + list(MIXED)
    if "rungs" in names:
        names = [n for n in names if n != "rungs"] + list(RUNGS)
    if "slab" in names:
        names = [n for n in names if n != "slab"] + list(SLAB)
    if "latent" in names:
        names = ([n for n in names if n != "latent"] + list(LATENT)
                 + list(SHARED) + list(PIECES))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    forced = [int(n) for n in args.entries.split(",") if n]
    with open(args.out, "a") as f:
        for name, entries in ((name, n) for name in names
                              for n in (forced if _shares_a_fold(name)
                                        and forced else [None])):
            try:
                with _entries(entries) as rule:
                    line = _measure(name, args, peak)
                    if _shares_a_fold(name):
                        line["entries_an_iteration"] = rule(name)
                        line["entries_forced"] = entries is not None
            except Exception as e:  # noqa: BLE001 - a geometry Mosaic refuses
                line = {"geometry": name, "entries_an_iteration": entries,
                        "error": repr(e)[:400]}
            line["device"] = dev.device_kind
            text = json.dumps(line)
            print(text, flush=True)
            f.write(text + "\n")


if __name__ == "__main__":
    main()
