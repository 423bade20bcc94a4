"""The Mosaic paged-attention kernel compiled for a TPU v5e that is
described and not attached, at the widths the serving cells and the
chip smoke run: what interpret mode cannot show (Mosaic refuses to slice
a block out of a bf16 pool with 6 or 12 heads, so those take the grid
form; ``kernels/paged_attention._block_is_sliceable``).  Nothing runs:
a compile that passes is no chip run.  One file, the topology inside a
fixture (one process may hold the TPU's library)."""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


# (slots, window rows, table entries a slot, pool blocks, K/V heads of
# the pool, head size, query heads a K/V head, lower bound)
GEOMETRIES = {
    "agent_turns_decode": (24, 1, 24, 705, 16, 128, 1, None),
    "reason_decode_folded_pool": (10, 1, 16, 708, 16, 128, 1, None),
    "verify_window": (24, 4, 24, 705, 16, 128, 1, None),
    "narrow_prefill_piece": (1, 4, 24, 705, 16, 128, 1, None),
    "smoke_6_heads_grid_form": (32, 4, 16, 545, 6, 128, 1, None),
    "cgpt590m_12_heads_grid_form": (8, 1, 16, 200, 12, 128, 1, None),
    # 40 query heads over 20 K/V heads of 64, 48 slots x 2048 positions.
    # It compiles, but heads of 64 fill half a lane tile: the compiler
    # copies both pools into the padded layout Mosaic wants at every call
    # (1.2 GB of temporaries), so no architecture holds its K/V this way
    "kv20x64_group2_grid_form_copied": (48, 1, 64, 3073, 20, 64, 2, None),
    "kv20x64_group2_window512_grid_form_copied": (48, 1, 64, 3073, 20, 64,
                                                  2, 512),
    # the same K/V as think_decode holds them: two heads of 64 to a
    # 128-lane row, 10 rows in the 16 that pool_rows gives a bf16 pool,
    # four masked half-rows of query a K/V row (serving.arch.SambaY)
    "think_decode_full_plane": (48, 1, 64, 3073, 16, 128, 4, None),
    "think_decode_window_plane": (48, 1, 64, 3073, 16, 128, 4, 512),
    # more than one row a block folds the block once for all of them,
    # scores as one MXU pass (PR 33), the values weighed in another (PR
    # 35): the widest windows `attend` streams
    # (DENSE_WINDOW - 1 rows), lanes past one tile, the grid form
    "verify_window_5_rows": (24, 5, 24, 705, 16, 128, 1, None),
    "widest_streamed_window": (24, 7, 24, 705, 16, 128, 1, None),
    "group_4_under_a_verify_window": (48, 5, 64, 3073, 16, 128, 4, 512),
    "cgpt590m_verify_window_grid_form": (8, 5, 16, 200, 12, 128, 1, None),
    # 48 query heads of 128 over 8 K/V heads, 96 slots x 2048 positions
    # (serving.arch.GatedMoE, chat_moe): 8 heads fill the sublane tiles
    # (the loop form), six query rows a K/V row, a window of 4096
    "chat_moe_window_plane": (96, 1, 64, 6145, 8, 128, 6, 4096),
    "chat_moe_full_plane": (96, 1, 64, 6145, 8, 128, 6, None),
    # chains of up to 128 live blocks a window plane (40 slots x 6144)
    "group_6_window_4096_long_chains": (40, 1, 192, 7681, 8, 128, 6, 4096),
    # both products of a block are MXU passes from two rows up (PR 35):
    # 30 rows a block (240 sublanes of scores, the weights' three
    # bfloat16 pieces 720), and a float32 pool, whose weights go whole
    "chat_moe_under_a_verify_window": (96, 5, 64, 6145, 8, 128, 6, 4096),
    "think_decode_float32_pool": (48, 1, 64, 3073, 16, 128, 4, None),
}
# table entries an iteration of the loop of two rows or more at the G
# ``entries_per_iteration`` gives each (PR 52; 1 where not listed: one
# row, the grid form, tables of 24 entries, a window of 512's 17 live
# entries, VMEM under a verify window): whatever the rule gives has to
# fit Mosaic's scoped VMEM, which only this compile shows
ENTRIES = {
    "think_decode_full_plane": 2,
    "chat_moe_window_plane": 2,
    "chat_moe_full_plane": 2,
    "group_6_window_4096_long_chains": 4,
    "chat_moe_under_a_verify_window": 2,
    "think_decode_float32_pool": 2,
}


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_paged_kernel_compiles_for_v5e(geometry, one_chip):
    from paddle_tpu.kernels.paged_attention import (
        _block_is_sliceable, entries_per_iteration, loop_iterations,
        paged_attention_pallas, window_entries)

    S, W, NB, blocks, hk, dh, group, window = GEOMETRIES[geometry]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((blocks, 32, hk, dh),
               jnp.float32 if "float32_pool" in geometry else jnp.bfloat16)
    assert _block_is_sliceable(pool) == ("grid_form" not in geometry)
    entries = ENTRIES.get(geometry, 1)
    if W * group > 1 and _block_is_sliceable(pool):
        assert entries_per_iteration(
            32, hk, dh, dh, W * group * hk, pool.dtype,
            window_entries(NB, 32, W, window)) == entries
    if W == 1:
        assert loop_iterations(41, group, ((32, hk, dh),) * 2, pool.dtype,
                               NB, window) == -(-41 // entries)
    compiled = jax.jit(
        lambda *a: paged_attention_pallas(
            *a, interpret=False, group=group, window=window)).lower(
            arg((S, W, hk * group, dh), jnp.bfloat16), pool, pool,
            arg((S, NB), jnp.int32), arg((S, W), jnp.int32)).compile()
    # the pools enter the kernel in place: no pool-sized temporary
    temp = compiled.memory_analysis().temp_size_in_bytes
    if "copied" in geometry:
        assert temp > 2 * blocks * 32 * hk * dh * 2
    else:
        assert temp < 1 << 20
    assert "paged_attention" in compiled.as_text()


# (slots, window rows, table entries a slot, pool blocks, lanes stored,
# values of them, value lanes, query rows an entry): the latent plane of
# serving.arch.LatentMoE at dsv2lite.doc_qa_8k's geometry (12 slots x
# 9216 positions, 576 values in 640 lanes, 16 heads read each row)
LATENT = {
    "doc_qa_decode": (12, 1, 288, 4609, 640, 576, 512, 16),
    "doc_qa_narrow_prefill_piece": (1, 4, 288, 4609, 640, 576, 512, 16),
    "doc_qa_widest_streamed_window": (12, 7, 288, 4609, 640, 576, 512, 16),
}


# the prefill rungs of the other paged cells by what ``attend`` chooses on
# a TPU: (window rows, pool blocks, pool rows, K/V rows, query rows a K/V
# row, lower bound, dtype of the context, walks its chain)
RUNGS = {
    "think_decode_512_rows_window_plane": (512, 3073, 16, 10, 4, 512,
                                           jnp.float32, True),
    "think_decode_256_rows_full_plane": (256, 3073, 16, 10, 4, None,
                                         jnp.float32, True),
    "think_decode_128_rows_stay_dense": (128, 3073, 16, 10, 4, None,
                                         jnp.float32, False),
    "chat_moe_512_rows_full_plane": (512, 6145, 8, 8, 6, None, None, True),
    "chat_moe_512_rows_window_4096": (512, 6145, 8, 8, 6, 4096, None, True),
    "chat_moe_256_rows_stay_dense": (256, 6145, 8, 8, 6, None, None, False),
    "agent_turns_32_rows_stay_dense": (32, 705, 16, 16, 1, None, None,
                                       False),
}


@pytest.mark.parametrize("rung", list(RUNGS))
def test_a_prefill_rung_walks_or_stays_dense_for_v5e(rung, one_chip,
                                                     monkeypatch):
    """``attend`` on a TPU at the rungs of ``think_decode`` and
    ``chat_moe`` (chains of 2,048 positions): dense float32 scores of 128
    MiB or more walk the chain (``chain_attention``, a Mosaic call and no
    float32 score in HBM), smaller ones keep the dense step and no Mosaic
    call at all."""
    from paddle_tpu.kernels import paged_attention as pa

    W, blocks, rows, hk, group, window, out, walks = RUNGS[rung]
    NB = 24 if blocks == 705 else 64

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert pa.walks_chain(W, group * rows, NB * 32) == walks
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(lambda q, k, v, t, p: pa.attend(
        q, k, v, t, p, group=group, window=window, out_dtype=out)).lower(
        arg((1, W, hk * group, 128), jnp.bfloat16),
        arg((blocks, 32, rows, 128), jnp.bfloat16),
        arg((blocks, 32, rows, 128), jnp.bfloat16),
        arg((1, NB), jnp.int32), arg((1, W), jnp.int32)).compile()
    text = compiled.as_text()
    assert ("chain_attention" in text) == walks
    assert ("tpu_custom_call" in text) == walks
    scores = 4 * W * group * rows * NB * 32
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (scores // 4 if walks else 3 * scores), (temp, scores)


@pytest.mark.parametrize("geometry", list(LATENT))
def test_latent_kernel_compiles_for_v5e(geometry, one_chip):
    """The pool enters the kernel in place (no pool-sized temporary), the
    Mosaic call carries its own name, and the write of a step's rows is
    one scatter in place."""
    import re

    from paddle_tpu.kernels.paged_attention import (
        latent_lanes, paged_attention_pallas, write)

    S, W, NB, blocks, lanes, values, dv, rows = LATENT[geometry]
    assert latent_lanes(values) == lanes

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((blocks, 32, lanes), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, p, t, at: paged_attention_pallas(
            q, p, None, t, at, interpret=False, value_lanes=dv,
            scale=0.11472)).lower(
            arg((S, W, rows, lanes), jnp.bfloat16), pool,
            arg((S, NB), jnp.int32), arg((S, W), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert "paged_latent_attention" in compiled.as_text()
    index = (S,) if W == 1 else (S, W)
    written = jax.jit(write, donate_argnums=0).lower(
        pool, arg(index, jnp.int32), arg(index, jnp.int32),
        arg((*index, values), jnp.bfloat16)).compile()
    text = written.as_text()
    assert not re.search(r"\bwhile\(", text)
    assert re.search(re.escape(f"[{blocks},32,{lanes}]") + r"\S* scatter\(",
                     text)
    assert re.search(r"input_output_alias=\{ \{\}: \(0, \{\}", text)
    assert written.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("slots", [12, 2])
def test_latent_kernel_told_shared_runs_compiles_for_v5e(slots, one_chip):
    """The decode call of ``dsv2lite.doc_qa_8k`` told which chains start
    alike (``shared [S, 2 + S]``: one more scalar-prefetch operand, DATA):
    one Mosaic call under the same name, no pool-sized temporary, the
    stacks of 2 to 12 slots' rows within Mosaic's scoped VMEM."""
    from paddle_tpu.kernels.paged_attention import (
        _stack_widths, latent_attention_pallas)

    S, W, NB, blocks, lanes, _, dv, rows = LATENT["doc_qa_decode"]
    assert _stack_widths(12, rows) == [2, 3, 4, 6, 8, 12]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, p, t, at, runs: latent_attention_pallas(
            q, p, t, at, dv, scale=0.11472, interpret=False,
            shared=runs)).lower(
            arg((slots, W, rows, lanes), jnp.bfloat16),
            arg((blocks, 32, lanes), jnp.bfloat16),
            arg((slots, NB), jnp.int32), arg((slots, W), jnp.int32),
            arg((slots, 2 + slots), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert compiled.as_text().count("paged_latent_attention") >= 1
    assert compiled.as_text().count("tpu_custom_call") >= 1


def test_a_latent_row_of_576_lanes_is_refused_by_mosaic(one_chip):
    """Why the plane stores 640 lanes: Mosaic slices a block out of the
    pool on 128-lane tiles only, and the device holds a 576-lane row in
    640 lanes whatever its logical shape says."""
    from paddle_tpu.kernels.paged_attention import latent_attention_pallas

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(lambda q, p, t, at: latent_attention_pallas(
            q, p, t, at, 512, scale=0.1, interpret=False)).lower(
            arg((12, 1, 16, 576), jnp.bfloat16),
            arg((4609, 32, 576), jnp.bfloat16), arg((12, 288), jnp.int32),
            arg((12, 1), jnp.int32)).compile()


# (pool blocks, rows of the pool's head axis, dtype, index shape, K/V
# rows written): the K/V write of each serving cell, a decode step's
# slots and a prefill piece's window
WRITES = {
    # 10 pairs of heads in the 16 rows pool_rows gives a bf16 pool: the
    # one cell whose rows do not fill the head axis.  Written as PART of
    # the axis (`pool.at[blk, off, :10].set(rows)`, until PR 37) both
    # compile to a `while` over the written rows with one
    # dynamic-update-slice each: 18 loops a step, 4 us a row on the chip
    "think_decode_step": (3073, 16, "bfloat16", (48,), 10),
    "think_decode_prefill_piece": (3073, 16, "bfloat16", (1, 128), 10),
    "agent_turns_step": (705, 16, "bfloat16", (24,), 16),
    "agent_turns_prefill_piece": (705, 16, "bfloat16", (1, 128), 16),
    "reason_decode_step_folded_pool": (708, 16, "bfloat16", (10,), 16),
    "chat_moe_step": (6145, 8, "bfloat16", (96,), 8),
    "chat_moe_prefill_piece": (6145, 8, "bfloat16", (1, 128), 8),
    "verify_window": (705, 16, "bfloat16", (24, 5), 16),
}


@pytest.mark.parametrize("geometry", list(WRITES))
def test_kv_write_is_one_scatter_in_place_for_v5e(geometry, one_chip):
    """``kernels.paged_attention.write`` into a donated pool: one scatter
    fusion that updates the pool where it lies: no loop over the
    written rows, no ``dynamic-update-slice`` of the pool, no temporary
    the size of a plane."""
    import re

    from paddle_tpu.kernels.paged_attention import pool_rows, write

    blocks, rows, dtype, index, heads = WRITES[geometry]
    assert pool_rows(heads, dtype) == rows

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(write, donate_argnums=0).lower(
        arg((blocks, 32, rows, 128), dtype), arg(index, jnp.int32),
        arg(index, jnp.int32), arg((*index, heads, 128), dtype)).compile()
    text = compiled.as_text()
    assert not re.search(r"\bwhile\(", text)
    pool = re.escape(f"[{blocks},32,{rows},128]")
    assert not re.search(pool + r"\S* dynamic-update-slice\(", text)
    assert re.search(r"input_output_alias=\{ \{\}: \(0, \{\}", text)
    assert re.search(pool + r"\S* scatter\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# (rows gathered, k, n, groups): the routed experts of chat_moe, a decode
# step's 96 x 4 rows and a prefill piece's 128 x 4, and a narrow piece
GROUPED = {
    "chat_moe_decode": (384, 3072, 3072, 32),
    "chat_moe_prefill_piece": (512, 3072, 3072, 32),
    "chat_moe_narrow_piece": (32, 3072, 3072, 32),
    # the widest rung since PR 43: 512 rows x 4
    "chat_moe_wide_piece": (2048, 3072, 3072, 32),
    # serving.arch.LatentMoE at the published widths, 16 experts held,
    # top 6: 12 slots x 6 rows a decode step, 128 x 6 a prefill piece;
    # 1408 is 11 lane tiles, so only 128-wide panels divide it
    "doc_qa_decode_gate_up": (72, 2048, 1408, 16),
    "doc_qa_decode_down": (72, 1408, 2048, 16),
    "doc_qa_prefill_piece_gate_up": (768, 2048, 1408, 16),
    "doc_qa_prefill_piece_down": (768, 1408, 2048, 16),
    "doc_qa_wide_piece_gate_up": (3072, 2048, 1408, 16),
    "doc_qa_wide_piece_down": (3072, 1408, 2048, 16),
}


@pytest.mark.parametrize("geometry", list(GROUPED))
def test_grouped_matmul_compiles_for_v5e(geometry, one_chip):
    """The grouped matrix product with its dynamic grid bound: the
    matrices enter in place (no temporary the size of an expert), and the
    Mosaic call carries the kernel's name."""
    from paddle_tpu.kernels.grouped_matmul import grouped_matmul_pallas

    m, k, n, groups = GROUPED[geometry]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda *a: grouped_matmul_pallas(*a, interpret=False)).lower(
        arg((m, k), jnp.bfloat16), arg((groups, k, n), jnp.bfloat16),
        arg((groups,), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert "grouped_matmul" in compiled.as_text()
