"""What ``nemotron3n.chat_ssm`` runs, compiled for a TPU v5e that is
described and not attached, at the cell's own geometry (40 slots x 2,560
positions, 23 Mamba-2 layers of 64 heads of 64 over a state of 128, 16
held experts 1,856 wide, 32 query heads over 2 K/V heads in 8 pool
rows): the Mosaic step kernel, the chunked form, the grouped product at
a width that is 14.5 lane tiles, and the whole decode chunk and widest
prefill piece of the 52 layers.  What interpret mode cannot show: a
layout Mosaic refuses, a matrix the device lays out with its axes
swapped and then COPIES for the kernel (``transpose_rhs``), a copy of the
slots' state.  Nothing runs: a compile that passes is no chip run."""

import json
import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


SLOTS, H, P, G, N, TAPS = 40, 64, 64, 8, 128, 4
CONV = H * P + 2 * G * N
BF16, F32 = jnp.bfloat16, jnp.float32


def _layer(arg):
    return dict(conv_w=arg((CONV, TAPS), BF16), conv_b=arg((CONV,), BF16),
                dt_bias=arg((H,), BF16), A_log=arg((H,), BF16),
                D=arg((H,), BF16))


def test_ssm_step_compiles_for_v5e_in_place(one_chip):
    from paddle_tpu.kernels import ssm

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s_shape, t_shape = ssm.state_shapes(H, P, G, N, TAPS)
    assert s_shape == (32, 128, 128) and t_shape == (3, 6144)
    compiled = jax.jit(
        lambda S, tail, xbc, dt, valid, layer: ssm.ssm_step_pallas(
            S, tail, xbc, dt, valid, heads=H, groups=G, interpret=False,
            **layer), donate_argnums=(0, 1)).lower(
        arg((SLOTS,) + s_shape, F32), arg((SLOTS,) + t_shape, BF16),
        arg((SLOTS, CONV), BF16), arg((SLOTS, H), BF16),
        arg((SLOTS,), jnp.bool_), _layer(arg)).compile()
    assert "ssm_step" in compiled.as_text()
    # one slot's state of one layer is 2 MiB, the slots' 80: nothing of
    # either size is made beside the state itself
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 20


@pytest.mark.parametrize("rows", [8, 128, 512])
def test_ssm_chunked_form_compiles_for_v5e(rows, one_chip):
    from paddle_tpu.kernels import ssm

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s_shape, t_shape = ssm.state_shapes(H, P, G, N, TAPS)
    compiled = jax.jit(
        lambda S, tail, slot, fresh, xbc, dt, valid, layer: ssm.ssm_chunk(
            S, tail, slot, fresh, xbc, dt, valid, heads=H, groups=G,
            chunk_size=128, **layer), donate_argnums=(0, 1)).lower(
        arg((SLOTS,) + s_shape, F32), arg((SLOTS,) + t_shape, BF16),
        arg((), jnp.int32), arg((), jnp.bool_), arg((rows, CONV), BF16),
        arg((rows, H), BF16), arg((rows,), jnp.bool_), _layer(arg)).compile()
    # the decay masks [chunks, 128, 128, 64] float32 of a 512-row piece
    # are 16 MiB; no copy of the 40 slots' state (80 MiB) is made
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_the_attention_planes_decode_call_compiles_at_the_rules_entries(
        one_chip):
    """2 K/V heads in the 8 rows ``pool_rows`` gives, 16 query rows a
    K/V row, tables of 80 entries: the rule gives the shared-fold loop
    two table entries an iteration (``entries_per_iteration``), and the
    kernel compiles at them with the pools in place."""
    from paddle_tpu.kernels import paged_attention as pa

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    nb, blocks = 2560 // 32, 1 + SLOTS * 2560 // 32
    assert pa.pool_rows(2, BF16) == 8
    assert pa.entries_per_iteration(32, 8, 128, 128, 16 * 8, BF16, nb) == 2
    pool = arg((blocks, 32, 8, 128), BF16)
    compiled = jax.jit(lambda *a: pa.paged_attention_pallas(
        *a, interpret=False, group=16)).lower(
        arg((SLOTS, 1, 32, 128), BF16), pool, pool,
        arg((SLOTS, nb), jnp.int32), arg((SLOTS, 1), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert "paged_attention" in compiled.as_text()


def test_grouped_matmul_compiles_at_16_experts_1856_wide(one_chip):
    """The up product with the matrices held ``[16, 1856, 2688]``
    (``transpose_rhs``) and the down product over ``k`` 1,856: neither
    makes a copy of its 152 MiB of matrices (held ``[16, 2688, 1856]``
    the device swaps the two minor axes and the kernel is handed a copy,
    157.5 MiB a layer)."""
    from paddle_tpu.kernels.grouped_matmul import grouped_matmul_pallas

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for rows, k, tr in ((SLOTS * 6, 2688, True), (512 * 6, 2688, True),
                        (SLOTS * 6, 1856, False), (512 * 6, 1856, False)):
        compiled = jax.jit(lambda x, w, s: grouped_matmul_pallas(
            x, w, s, interpret=False, transpose_rhs=tr)).lower(
            arg((rows, k), BF16), arg((16, 1856, 2688), BF16),
            arg((16,), jnp.int32)).compile()
        assert "grouped_matmul" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


def _cell():
    from chipbench import families

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/configs/nemotron-3-nano-30b-a3b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "chipbench/traffic/chat_ssm.json")) as f:
        mix = json.load(f)
    return cfg, mix, families.of(cfg, "serve")


@pytest.mark.parametrize("entry", ["decode", "prefill_512"])
def test_the_cells_executables_compile_and_fit_a_v5e(entry, one_chip,
                                                     monkeypatch):
    """The decode chunk and the widest prefill piece of all 52 layers at
    40 slots x 2,560 positions, from shapes alone: 10.52 GB of weights,
    the pool as stored, 1.96 GB of state, and temporaries that leave
    room on a chip of 15.75 GiB.  The decode step holds NO array of the
    slots' state but the layers' own (no gather, no scatter, no copy
    beside the kernel's in-place update)."""
    import numpy as np

    from paddle_tpu.serving import batched_decode as bd

    cfg, mix, family = _cell()
    arch = family._arch(cfg)
    geo = mix["engine"]
    S, T, Bt = geo["max_slots"], geo["max_len"], geo["block_tokens"]
    nb = T // Bt

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params, _ = jax.eval_shape(lambda: family.make_params_unsettled(cfg, 0))
    params = {k: arg(v.shape, v.dtype) for k, v in params.items()}
    count = sum(int(np.prod(v.shape)) for v in params.values())
    assert count == cfg["parameters_held"] == 5_258_420_544
    pk, pv = [], []
    for i in range(len(arch.planes)):
        ks, vs = arch.plane_block_shapes(i, Bt, BF16)
        pk.append(arg((1 + S * nb,) + ks, BF16))
        pv.append(arg((1 + S * nb,) + vs, BF16))
    state = tuple(tuple(arg((S,) + tuple(shp), dt) for shp, dt in layer)
                  for layer in arch.state_spec(BF16))
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in pk + pv + [a for layer in state for a in layer])
    assert 4.4e9 < held < 4.6e9
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots = arg((S,), jnp.int32)
    if entry == "decode":
        lowered = bd.make_decode_chunk(arch, 4).lower(
            params, tuple(pk), tuple(pv), slots, slots,
            arg((S, nb), jnp.int32), state)
    else:
        scalar = arg((), jnp.int32)
        lowered = bd.make_prefill(arch, 512).lower(
            params, tuple(pk), tuple(pv), slots, slots, scalar,
            arg((nb,), jnp.int32), arg((512,), jnp.int32), scalar, scalar,
            scalar, scalar, state)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "grouped_matmul" in text
    mem = compiled.memory_analysis()
    # pools, state and slot scalars are donated: aliased, not copied
    assert mem.alias_size_in_bytes >= held
    # 23 expert matrices of 152 MiB each are read where they lie
    assert mem.temp_size_in_bytes < 512 << 20, mem.temp_size_in_bytes
    total = 2 * count + held + mem.temp_size_in_bytes
    assert total < 14.6 * 2 ** 30, total
    whole = rf"f32\[{S},32,128,128\]"
    made = re.findall(rf"= {whole}\S* ([\w\-]+)\(", text)
    if entry == "decode":
        assert text.count("ssm_step") >= 23 and "paged_attention" in text
        # the slots' state only ever passes through: a parameter, a loop's
        # carry; the kernel's own output is its operand, aliased
        assert set(made) <= {"parameter", "get-tuple-element"}, set(made)
    else:
        # a piece advances ONE slot: its 2 MiB read, and written back into
        # the layer's array where it lies
        assert set(made) <= {"parameter", "get-tuple-element", "fusion",
                             "dynamic-update-slice"}, set(made)
        assert not re.search(rf"= {whole}\S* copy\(", text)
