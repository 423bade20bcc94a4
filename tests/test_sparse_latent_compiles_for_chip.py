"""What ``dots3np.doc_qa_32k`` runs, compiled for a TPU v5e that is
described and not attached, at the cell's own geometry (10 slots x
34,048 positions; full planes of 640 stored lanes read by 128 heads with
an index key of 128 lanes, sliding planes of 1,152 stored lanes read by
64 heads under a window of 513): the Mosaic latent kernel under a lower
bound at 1,152 lanes and 1,024 value lanes (640 was the only width it
had compiled at), the dense spelling of a 512-row piece that gathers the
window's entries and no more, the indexer's scores, the exact top 2,048
and the attention of the gathered rows for a decode step and for a
512-row piece, the grouped product at 32 experts of ``[5120, 1536]``,
and the whole decode chunk and widest prefill piece of the five held
layers.  Nothing runs: a compile that passes is no chip run."""

import functools
import json
import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


SLOTS, NB, B, TOPK = 10, 1064, 32, 2048
BLOCKS = 1 + SLOTS * NB + 4608


def _branches(text):
    """The branch count of every conditional of a compiled text."""
    return [len(c.split(",")) for c in re.findall(
        r" conditional\(.*branch_computations=\{([^}]*)\}", text)]


def _arg(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        tuple(shape), dtype, sharding=one_chip)


@pytest.mark.parametrize("rows", [1, 4])
def test_latent_kernel_under_a_lower_bound_compiles_for_v5e(rows, one_chip):
    """1,152 stored lanes, 1,024 value lanes, 64 heads, a window of 513:
    the loop starts at the group of the window's first entry."""
    from paddle_tpu.kernels import paged_attention as pa

    arg = _arg(one_chip)
    S = SLOTS if rows == 1 else 1
    compiled = jax.jit(lambda q, pool, t, p: pa.paged_attention_pallas(
        q, pool, None, t, p, interpret=False, value_lanes=1024,
        scale=256 ** -0.5, window=513)).lower(
        arg((S, rows, 64, 1152), jnp.bfloat16),
        arg((BLOCKS, B, 1152), jnp.bfloat16), arg((S, NB), jnp.int32),
        arg((S, rows), jnp.int32)).compile()
    assert "paged_latent_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_a_512_row_piece_gathers_its_windows_entries_and_no_more(
        one_chip, monkeypatch):
    """The dense spelling of a wide piece over a sliding plane: 33 table
    entries (1,056 positions) of the 1,064, scores ``[512, 64, 1056]``
    float32 (138 MB) where the whole chain's would be 4.5 GB."""
    from paddle_tpu.kernels import paged_attention as pa

    arg = _arg(one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pa.window_entries(NB, B, 512, 513) == 33
    compiled = jax.jit(lambda q, pool, t, p: pa.attend(
        q, pool, None, t, p, value_lanes=1024, scale=256 ** -0.5,
        window=513)).lower(
        arg((1, 512, 64, 1152), jnp.bfloat16),
        arg((BLOCKS, B, 1152), jnp.bfloat16), arg((1, NB), jnp.int32),
        arg((1, 512), jnp.int32)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 3 * 4 * 512 * 64 * 33 * B, temp


@pytest.mark.parametrize("slots,rows", [(SLOTS, 1), (1, 512)])
def test_the_sparse_call_compiles_for_v5e(slots, rows, one_chip):
    """Index scores over 34,048 positions, the exact top 2,048 and the
    gathered rows attended by 128 heads: a decode step of 10 slots and a
    512-row piece, whose pieces of query rows keep the temporaries under
    a gigabyte.  The decode step's three steps stand under ONE
    conditional, a branch a count of ``slots_run`` (none first), and in
    every branch that runs them the instructions carry the three named
    scopes the ``dsa.*`` readers and the ``step.*_busy_share`` kinds join
    on; a piece has one slot and no conditional."""
    from paddle_tpu.kernels import sparse_attention as sp

    arg = _arg(one_chip)
    compiled = jax.jit(lambda q, pool, idx, t, p, qi, wi: sp.sparse_attend(
        q, pool, idx, t, p, qi, wi, topk=TOPK, value_lanes=512,
        scale=192 ** -0.5)).lower(
        arg((slots, rows, 128, 640), jnp.bfloat16),
        arg((BLOCKS, B, 640), jnp.bfloat16),
        arg((BLOCKS, B, 128), jnp.bfloat16), arg((slots, NB), jnp.int32),
        arg((slots, rows), jnp.int32),
        arg((slots, rows, 64, 128), jnp.bfloat16),
        arg((slots, rows, 64), jnp.float32)).compile()
    text = compiled.as_text()
    names = re.findall(r"%([\w.\-]+) = ", text)
    assert not any("paged_latent_attention" in n for n in names)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    counts = sorted({sp.slots_run(n, slots) for n in range(slots + 1)})
    ran = range(1, len(counts)) if slots > 1 else ()
    assert _branches(text) == ([len(counts)] if slots > 1 else [])
    scoped = set(re.findall(
        r"op_name=\"[^\"]*/branch_(\d+)_fun/(paged_index_scores|"
        r"index_select|paged_sparse_latent_attention)/", text))
    assert scoped == {(str(i), scope) for i in ran
                      for scope in ("paged_index_scores", "index_select",
                                    "paged_sparse_latent_attention")}


def test_grouped_matmul_compiles_at_32_experts_of_5120_by_1536(one_chip):
    from paddle_tpu.kernels.grouped_matmul import grouped_matmul_pallas

    arg = _arg(one_chip)
    for rows, (k, n) in ((SLOTS * 8, (5120, 1536)), (512 * 8, (5120, 1536)),
                         (SLOTS * 8, (1536, 5120))):
        compiled = jax.jit(lambda x, w, s: grouped_matmul_pallas(
            x, w, s, interpret=False)).lower(
            arg((rows, k), jnp.bfloat16), arg((32, k, n), jnp.bfloat16),
            arg((32,), jnp.int32)).compile()
        assert "grouped_matmul" in compiled.as_text()


def _cell():
    from chipbench import families

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root,
                           "chipbench/configs/dots3-note-prev.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "chipbench/traffic/doc_qa_32k.json")) as f:
        mix = json.load(f)
    return cfg, mix, families.of(cfg, "serve")


@functools.lru_cache(maxsize=None)
def _compile_entry(entry, one_chip):
    """``(compiled, weight bytes, pool bytes)`` of the cell's decode chunk
    or widest prefill piece, from shapes alone, once a module."""
    import numpy as np

    from paddle_tpu.serving import batched_decode as bd

    cfg, mix, family = _cell()
    arch = family._arch(cfg)
    geo = mix["engine"]
    S, T, Bt = geo["max_slots"], geo["max_len"], geo["block_tokens"]
    nb = T // Bt
    assert (S, nb, Bt) == (SLOTS, NB, B)
    arg = _arg(one_chip)
    params, _ = jax.eval_shape(lambda: family.make_params_unsettled(cfg, 0))
    params = {k: arg(v.shape, v.dtype) for k, v in params.items()}
    weights = sum(int(np.prod(v.shape)) * 2 for v in params.values())
    assert weights == 2 * 4_087_154_176
    blocks = 1 + S * nb + geo["cache_blocks"]
    shapes = [arch.plane_block_shapes(i, Bt, jnp.bfloat16)
              for i in range(len(arch.planes))]
    pk = tuple(arg((blocks,) + s[0], jnp.bfloat16) for s in shapes)
    pv = tuple(arg((blocks,) + s[1], jnp.bfloat16)
               for s in shapes if len(s) > 1)
    pool = sum(int(np.prod(a.shape)) * 2 for a in pk + pv)
    assert pool == blocks * 319_488
    slots = arg((S,), jnp.int32)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        if entry == "decode":
            lowered = bd.make_decode_chunk(arch, 4).lower(
                params, pk, pv, slots, slots, arg((S, nb), jnp.int32))
        else:
            scalar = arg((), jnp.int32)
            lowered = bd.make_prefill(arch, 512).lower(
                params, pk, pv, slots, slots, scalar, arg((nb,), jnp.int32),
                arg((512,), jnp.int32), scalar, scalar, scalar, scalar)
        return lowered.compile(), weights, pool


@pytest.mark.parametrize("entry", ["decode", "prefill_512"])
def test_the_cells_executables_compile_and_fit_a_v5e(entry, one_chip):
    """The decode chunk and the widest prefill piece of the five held
    layers at 10 slots x 34,048 positions with the trie's 4,608 blocks,
    from shapes alone: weights 8.17 GB, the pool 4.87 GB, and temporaries
    that leave room on a chip of 15.75 GiB."""
    compiled, weights, pool = _compile_entry(entry, one_chip)
    text = compiled.as_text()
    assert ("paged_latent_attention" in text) == (entry == "decode")
    assert "grouped_matmul" in text
    mem = compiled.memory_analysis()
    # the pools and the slot scalars are donated: aliased, not copied
    assert mem.alias_size_in_bytes >= pool
    total = weights + pool + mem.temp_size_in_bytes
    print(entry, "temp", mem.temp_size_in_bytes, "total", total)
    assert total < 15.0 * 2 ** 30, (weights, pool, mem.temp_size_in_bytes)


def test_the_decode_chunk_reads_its_pools_in_place_under_the_branches(
        one_chip):
    """The cell's whole decode chunk with the sparse calls' branches:
    one conditional a full plane, three branches each (none, 5 and 10
    slots), the pools operands the branches only read: no copy
    of a pool array anywhere in the compiled text, the pools still
    aliased to the outputs, and temporaries within 16 MiB of what the
    chunk held before the call packed its live slots (211,302,912 B: the
    packed queries and a branch's own intermediates), so the whole stays
    at 79% of the chip."""
    compiled, weights, pool = _compile_entry("decode", one_chip)
    text = compiled.as_text()
    assert _branches(text) == [3, 3]
    pools = r"bf16\[%d,%d,(?:640|128|1152)\]" % (BLOCKS, B)
    assert re.search(pools, text)
    assert not re.search(r"= %s\S* copy\(" % pools, text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool
    assert mem.temp_size_in_bytes < 211_302_912 + (16 << 20)
    assert weights + pool + mem.temp_size_in_bytes < 0.80 * 15.75 * 2 ** 30
