"""What ``mimo25.long_reason`` runs, compiled for a TPU v5e that is
described and not attached, at the cell's own geometry (24 slots x
13,312 positions, 64 query heads of 192 lanes over 4 or 8 K/V heads,
keys stored at 256 lanes over values of 128, a window of 128 with a
sink): the Mosaic paged kernel on both kinds of plane, the chain walk
of a 512-row piece (``kernels/chain_attention.py``), the grouped product
at 16 experts of ``[4096, 2048]``, and the whole decode chunk and widest
prefill piece of the seven held layers.  What interpret mode cannot show
(a 192-lane key is where Mosaic objects: it is stored at 256).  Nothing
runs: a compile that passes is no chip run."""

import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


SLOTS, NB, B = 24, 416, 32
FULL_BLOCKS = 1 + SLOTS * NB
WINDOW_BLOCKS = 1 + SLOTS * 21
# (window rows, pool blocks, query heads a K/V head, lower bound, sink,
# table entries an iteration of the shared-fold loop: eight on a full
# plane's chain of 416, one where a window of 128 keeps 5 or 6 live;
# 64 rows a K/V row under a 4-row piece leave VMEM for four)
PLANES = {
    "decode_full_plane_group_16": (1, FULL_BLOCKS, 16, None, False, 8),
    "decode_window_plane_group_8_sink": (1, WINDOW_BLOCKS, 8, 128, True, 1),
    "narrow_piece_window_plane_sink": (4, WINDOW_BLOCKS, 8, 128, True, 1),
    "narrow_piece_full_plane": (4, FULL_BLOCKS, 16, None, False, 2),
}


@pytest.mark.parametrize("plane", list(PLANES))
def test_paged_kernel_compiles_for_v5e(plane, one_chip):
    """Keys of 256 stored lanes, values of 128, 8 pool rows (the 4 heads
    of a full plane padded): the loop form, the pools in place."""
    from paddle_tpu.kernels import paged_attention as pa

    W, blocks, group, window, sink, entries = PLANES[plane]
    S = 1 if W > 1 else SLOTS
    assert pa.entries_per_iteration(
        B, 8, pa.key_lanes(192), 128, W * group * 8, jnp.bfloat16,
        pa.window_entries(NB, B, W, window)) == entries

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pk = arg((blocks, B, 8, pa.key_lanes(192)), jnp.bfloat16)
    pv = arg((blocks, B, 8, 128), jnp.bfloat16)
    assert pa._block_is_sliceable(pk)
    args = [arg((S, W, 64, 256), jnp.bfloat16), pk, pv,
            arg((S, NB), jnp.int32), arg((S, W), jnp.int32)]
    if sink:
        args.append(arg((64,), jnp.float32))
    compiled = jax.jit(
        lambda q, k, v, t, p, s=None: pa.paged_attention_pallas(
            q, k, v, t, p, interpret=False, group=group, window=window,
            scale=192 ** -0.5, sink=s)).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert "paged_attention" in compiled.as_text()


@pytest.mark.parametrize("kind,blocks,group,window", [
    ("full", FULL_BLOCKS, 16, None), ("window", WINDOW_BLOCKS, 8, 128)])
def test_a_512_row_piece_walks_its_chain(kind, blocks, group, window,
                                         one_chip, monkeypatch):
    """A 512-row piece over a chain of 13,312 positions: on a TPU
    ``attend`` takes the chain walk (``chain_attention``, whose name the
    decode kernel's readers must not find) at both plane geometries, the
    key stored at 256 lanes over values of 128, the 4 heads of a full
    plane in 8 pool rows, a window of 128 with a sink.  What it keeps in
    HBM is the gathered chain (41 MB of a full plane's 4 heads, 21 + 11
    entries of a window plane's) and the folded queries: no float32
    score, where the dense spelling held 0.45 GB a K/V head."""
    from paddle_tpu.kernels import paged_attention as pa

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = arg((1, 512, 64, 192), jnp.bfloat16)
    pk = arg((blocks, B, 8, 256), jnp.bfloat16)
    assert pa.walks_chain(512, group * 8, NB * B)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(lambda q, k, v, t, p, s: pa.attend(
        q, k, v, t, p, group=group, window=window,
        sink=s if window else None)).lower(
        q, pk, arg((blocks, B, 8, 128), jnp.bfloat16),
        arg((1, NB), jnp.int32), arg((1, 512), jnp.int32),
        arg((64,), jnp.float32)).compile()
    # the readers of the decode kernel look at an instruction's NAME
    names = re.findall(r"%([\w.\-]+) = ", compiled.as_text())
    assert any("chain_attention" in n for n in names)
    assert not any("paged_attention" in n for n in names)
    temp = compiled.memory_analysis().temp_size_in_bytes
    one_head_of_scores = 4 * 512 * group * NB * B
    assert temp < one_head_of_scores // 2 < 256 << 20, temp


def test_grouped_matmul_compiles_at_16_experts_of_4096_by_2048(one_chip):
    from paddle_tpu.kernels.grouped_matmul import grouped_matmul_pallas

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for rows, (k, n) in ((24 * 8, (4096, 2048)), (512 * 8, (4096, 2048)),
                         (24 * 8, (2048, 4096))):
        compiled = jax.jit(lambda x, w, s: grouped_matmul_pallas(
            x, w, s, interpret=False)).lower(
            arg((rows, k), jnp.bfloat16), arg((16, k, n), jnp.bfloat16),
            arg((16,), jnp.int32)).compile()
        assert "grouped_matmul" in compiled.as_text()


def _cell():
    import json

    from chipbench import families

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench/configs/mimo-v2.5.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "chipbench/traffic/long_reason.json")) as f:
        mix = json.load(f)
    return cfg, mix, families.of(cfg, "serve")


@pytest.mark.parametrize("entry", ["decode", "prefill_512"])
def test_the_cells_executables_compile_and_fit_a_v5e(entry, one_chip,
                                                     monkeypatch):
    """The decode chunk and the widest prefill piece of the seven held
    layers at 24 slots x 13,312 positions, from shapes alone: weights
    6.86 GB, the pools of both kinds, and temporaries that leave room on
    a chip of 15.75 GiB."""
    import numpy as np

    from paddle_tpu.serving import batched_decode as bd
    from paddle_tpu.serving import kvcache as kv

    cfg, mix, family = _cell()
    arch = family._arch(cfg)
    geo = mix["engine"]
    S, T, Bt = geo["max_slots"], geo["max_len"], geo["block_tokens"]
    nb = T // Bt
    per_slot = kv.window_blocks(cfg["sliding_window"], bd.PREFILL_PIECE, Bt)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params, _ = jax.eval_shape(lambda: family.make_params_unsettled(cfg, 0))
    params = {k: arg(v.shape, v.dtype) for k, v in params.items()}
    weights = sum(int(np.prod(v.shape)) * 2 for v in params.values())
    assert 6.8e9 < weights < 6.9e9
    pk, pv = [], []
    for i in range(len(arch.planes)):
        blocks = 1 + S * (per_slot if arch.chain_kind(i) else nb)
        ks, vs = arch.plane_block_shapes(i, Bt, jnp.bfloat16)
        pk.append(arg((blocks,) + ks, jnp.bfloat16))
        pv.append(arg((blocks,) + vs, jnp.bfloat16))
    pool = sum(int(np.prod(a.shape)) * 2 for a in pk + pv)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots = arg((S,), jnp.int32)
    if entry == "decode":
        lowered = bd.make_decode_chunk(arch, 4).lower(
            params, tuple(pk), tuple(pv), slots, slots,
            arg((S, 2, nb), jnp.int32))
    else:
        scalar = arg((), jnp.int32)
        lowered = bd.make_prefill(arch, 512).lower(
            params, tuple(pk), tuple(pv), slots, slots, scalar,
            arg((2, nb), jnp.int32), arg((512,), jnp.int32), scalar, scalar,
            scalar, scalar)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("paged_attention") >= (7 if entry == "decode" else 0)
    assert ("chain_attention" in text) == (entry != "decode")
    assert "grouped_matmul" in text
    mem = compiled.memory_analysis()
    if entry != "decode":
        # the piece's temporaries under what the dense spelling's were
        # (0.45 GiB, a K/V head's float32 scores over the whole chain)
        assert mem.temp_size_in_bytes < 0.45 * 2 ** 30, mem.temp_size_in_bytes
    # the pools and the slot scalars are donated: aliased, not copied
    assert mem.alias_size_in_bytes >= pool
    total = weights + pool + mem.temp_size_in_bytes
    assert total < 14.5 * 2 ** 30, (weights, pool, mem.temp_size_in_bytes)
