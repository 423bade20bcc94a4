"""What ``solaro2.doc_qa_64k`` runs, compiled for a TPU v5e that is
described and not attached, at the cell's own geometry (16 slots x 66,816
positions, three delta layers of 64 heads of 128 beside one plane of 8
K/V heads, 40 held experts 1,280 wide): the Mosaic step kernel in place,
the WY form at the rungs, the grouped product at ten lane tiles, the
paged call at group 8 on chains of 2,088 entries, and the whole decode
chunk and widest prefill piece of the four layers.  What interpret mode
cannot show: a layout Mosaic refuses, a copy of the slots' state, a
program that does not fit.  Nothing runs: a compile that passes is no
chip run."""

import json
import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


SLOTS, H, D, TAPS = 16, 64, 128, 4
BF16, F32 = jnp.bfloat16, jnp.float32


def _rows(arg, n):
    return (arg((n, H * D), BF16),) * 3 + (arg((n, H * D), F32),
                                           arg((n, H), F32))


def test_delta_step_compiles_for_v5e_in_place(one_chip):
    from paddle_tpu.kernels import delta

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s_shape, t_shape = delta.state_shapes(H, D, TAPS)
    assert s_shape == (64, 128, 128) and t_shape == (3, 24576)
    compiled = jax.jit(
        lambda S, tail, q, k, v, g, beta, valid, conv_w:
        delta.delta_step_pallas(S, tail, q, k, v, g, beta, valid,
                                conv_w=conv_w, heads=H, interpret=False),
        donate_argnums=(0, 1)).lower(
        arg((SLOTS,) + s_shape, F32), arg((SLOTS,) + t_shape, BF16),
        *_rows(arg, SLOTS), arg((SLOTS,), jnp.bool_),
        arg((3 * H * D, TAPS), BF16)).compile()
    assert "delta_step" in compiled.as_text()
    # one slot's state of one layer is 4 MiB, the slots' 64: nothing of
    # the latter size is made beside the state itself (the rows' float32
    # columns and convolution are a few MiB)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("rows", [8, 128, 512])
def test_delta_chunk_compiles_for_v5e(rows, one_chip):
    from paddle_tpu.kernels import delta

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s_shape, t_shape = delta.state_shapes(H, D, TAPS)
    compiled = jax.jit(
        lambda S, tail, slot, fresh, q, k, v, g, beta, valid, conv_w:
        delta.delta_chunk(S, tail, slot, fresh, q, k, v, g, beta, valid,
                          conv_w=conv_w, heads=H),
        donate_argnums=(0, 1)).lower(
        arg((SLOTS,) + s_shape, F32), arg((SLOTS,) + t_shape, BF16),
        arg((), jnp.int32), arg((), jnp.bool_), *_rows(arg, rows),
        arg((rows,), jnp.bool_), arg((3 * H * D, TAPS), BF16)).compile()
    # a 512-row piece's pair-by-pair ratios [8, 64, 4, 16, 16, 128]
    # float32 are 256 MiB if they are ever whole; no copy of the 16
    # slots' state (64 MiB) is made
    assert compiled.memory_analysis().temp_size_in_bytes < 768 << 20


def test_the_gqa_planes_decode_call_compiles_on_chains_of_2088(one_chip):
    """8 K/V heads, 8 query rows a K/V row, tables of 2,088 entries (the
    longest a cell has had: 1,064)."""
    from paddle_tpu.kernels import paged_attention as pa

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    nb = 66816 // 32
    blocks = 1 + SLOTS * nb + 9216
    assert nb == 2088 and pa.pool_rows(8, BF16) == 8
    pool = arg((blocks, 32, 8, 128), BF16)
    compiled = jax.jit(lambda *a: pa.paged_attention_pallas(
        *a, interpret=False, group=8)).lower(
        arg((SLOTS, 1, 64, 128), BF16), pool, pool,
        arg((SLOTS, nb), jnp.int32), arg((SLOTS, 1), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert "paged_attention" in compiled.as_text()


def test_grouped_matmul_compiles_at_40_experts_1280_wide(one_chip):
    from paddle_tpu.kernels.grouped_matmul import grouped_matmul_pallas

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for rows, shape in ((SLOTS * 8, (40, 4096, 1280)),
                        (512 * 8, (40, 4096, 1280)),
                        (SLOTS * 8, (40, 1280, 4096)),
                        (512 * 8, (40, 1280, 4096))):
        compiled = jax.jit(lambda x, w, s: grouped_matmul_pallas(
            x, w, s, interpret=False)).lower(
            arg((rows, shape[1]), BF16), arg(shape, BF16),
            arg((40,), jnp.int32)).compile()
        assert "grouped_matmul" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


def _cell():
    from chipbench import families

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/configs/solar-open2-250b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "chipbench/traffic/doc_qa_64k.json")) as f:
        mix = json.load(f)
    return cfg, mix, families.of(cfg, "serve")


@pytest.mark.parametrize("entry", ["decode", "prefill_512"])
def test_the_cells_executables_compile_and_fit_a_v5e(entry, one_chip,
                                                     monkeypatch):
    """The decode chunk and the widest prefill piece of the four layers
    at 16 slots x 66,816 positions, from shapes alone: 6.62 GB of weights,
    5.59 GB of pool, 0.21 GB of state, and temporaries that leave room on
    a chip of 15.75 GiB.  The decode step holds NO array of the slots'
    state but the layers' own."""
    import numpy as np

    from paddle_tpu.serving import batched_decode as bd

    cfg, mix, family = _cell()
    arch = family._arch(cfg)
    geo = mix["engine"]
    S, T, Bt = geo["max_slots"], geo["max_len"], geo["block_tokens"]
    nb = T // Bt
    blocks = 1 + S * nb + geo["cache_blocks"]
    assert blocks == 42625

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params, _ = jax.eval_shape(lambda: family.make_params_unsettled(cfg, 0))
    params = {k: arg(v.shape, v.dtype) for k, v in params.items()}
    count = sum(int(np.prod(v.shape)) for v in params.values())
    assert count == cfg["parameters_held"] == 3_308_377_920
    ks, vs = arch.plane_block_shapes(0, Bt, BF16)
    pk, pv = (arg((blocks,) + ks, BF16),), (arg((blocks,) + vs, BF16),)
    state = tuple(tuple(arg((S,) + tuple(shp), dt) for shp, dt in layer)
                  for layer in arch.state_spec(BF16))
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in pk + pv + tuple(a for layer in state for a in layer))
    assert 5.7e9 < held < 5.9e9
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots = arg((S,), jnp.int32)
    if entry == "decode":
        lowered = bd.make_decode_chunk(arch, 4).lower(
            params, pk, pv, slots, slots, arg((S, nb), jnp.int32), state)
    else:
        scalar = arg((), jnp.int32)
        lowered = bd.make_prefill(arch, 512).lower(
            params, pk, pv, slots, slots, scalar, arg((nb,), jnp.int32),
            arg((512,), jnp.int32), scalar, scalar, scalar, scalar, state)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "grouped_matmul" in text
    mem = compiled.memory_analysis()
    # pool, state and slot scalars are donated: aliased, not copied
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 1536 << 20, mem.temp_size_in_bytes
    total = 2 * count + held + mem.temp_size_in_bytes
    assert total < 14.6 * 2 ** 30, total
    whole = rf"f32\[{S},64,128,128\]"
    made = re.findall(rf"= {whole}\S* ([\w\-]+)\(", text)
    if entry == "decode":
        assert text.count("delta_step") >= 3 and "paged_attention" in text
        # the slots' state only ever passes through: a parameter, a loop's
        # carry; the kernel's own output is its operand, aliased
        assert set(made) <= {"parameter", "get-tuple-element"}, set(made)
    else:
        assert "chain_attention" in text
        # a piece advances ONE slot: its 4 MiB read, and written back into
        # the layer's array where it lies
        assert set(made) <= {"parameter", "get-tuple-element", "fusion",
                             "dynamic-update-slice"}, set(made)
        assert not re.search(rf"= {whole}\S* copy\(", text)
