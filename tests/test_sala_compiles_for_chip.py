"""What ``sala9b.doc_qa_128k`` runs, compiled for a TPU v5e that is
described and not attached, at the cell's own geometry (32 slots x 132,352
positions in blocks of 64, two K/V planes of 2 K/V heads stored
head-major, a ``[64, 128]`` slab a head,
with a compressed plane each, six states of 32 heads of 128 x 128): the
recurrence through ``kernels/ssm.py`` at 32 groups of ONE head with no
convolution (a geometry it had never compiled at), the three steps of the
block-sparse call for a decode step and for a prefill piece, and the whole
decode chunk and widest prefill piece of the eight layers.  What interpret
mode cannot show: a layout Mosaic refuses, a gathered copy of K or V, a
copy of the slots' state, a program that does not fit.  Nothing runs: a
compile that passes is no chip run."""

import json
import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

BF16, F32 = jnp.bfloat16, jnp.float32
H, D = 32, 128


def _cell():
    from chipbench import families

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench/configs/minicpm-sala.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "chipbench/traffic/doc_qa_128k.json")) as f:
        mix = json.load(f)
    return cfg, mix, families.of(cfg, "serve")


def test_the_recurrence_steps_in_place_at_32_groups_of_one_head(one_chip):
    from paddle_tpu.kernels import ssm

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    slots = 32
    s_shape, t_shape = ssm.state_shapes(H, D, H, D, 1)
    assert s_shape == (32, 128, 128) and t_shape == (0, 3 * H * D)
    compiled = jax.jit(
        lambda S, tail, xbc, dt, valid, b, a, d: ssm.ssm_step_pallas(
            S, tail, xbc, dt, valid, conv_w=None, conv_b=None, dt_bias=b,
            A_log=a, D=d, heads=H, groups=H, interpret=False),
        donate_argnums=(0, 1)).lower(
        arg((slots,) + s_shape, F32), arg((slots,) + t_shape, BF16),
        arg((slots, 3 * H * D), BF16), arg((slots, H), F32),
        arg((slots,), jnp.bool_), *(arg((H,), F32),) * 3).compile()
    assert "ssm_step" in compiled.as_text()
    # a slot's state of one layer is 2 MiB, the slots' 64: nothing of the
    # latter size is made beside the state itself
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("rows", [8, 128, 512])
def test_the_recurrences_chunked_form_compiles(rows, one_chip):
    from paddle_tpu.kernels import ssm

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s_shape, t_shape = ssm.state_shapes(H, D, H, D, 1)
    compiled = jax.jit(
        lambda S, tail, slot, fresh, xbc, dt, valid, b, a, d: ssm.ssm_chunk(
            S, tail, slot, fresh, xbc, dt, valid, conv_w=None, conv_b=None,
            dt_bias=b, A_log=a, D=d, heads=H, groups=H, chunk_size=128),
        donate_argnums=(0, 1)).lower(
        arg((32,) + s_shape, F32), arg((32,) + t_shape, BF16),
        arg((), jnp.int32), arg((), jnp.bool_), arg((rows, 3 * H * D), BF16),
        arg((rows, H), F32), arg((rows,), jnp.bool_),
        *(arg((H,), F32),) * 3).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20


@pytest.mark.parametrize("slots, width", [(32, 1), (1, 512)])
def test_the_block_sparse_call_compiles_with_no_gathered_kv(slots, width,
                                                            one_chip,
                                                            monkeypatch):
    """A decode step's call (32 slots, one row each) and a prefill
    piece's (one slot, 512 rows, each its own selection): the selected
    blocks reach K and V through the paged kernel's table, a K/V head's
    slabs of the head-major plane and no other."""
    from paddle_tpu.kernels import block_sparse_attention as bsa

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    nb, blocks = 132352 // 64, 10241
    pool = arg((blocks, 2, 64, 128), BF16)
    compiled = jax.jit(lambda *a: bsa.attend(
        *a, group=16, stride=16, block=64, topk=64, init_blocks=1,
        window_blocks=32, scale=128 ** -0.5)).lower(
        arg((slots, width, 32, 128), BF16), pool, pool,
        arg((blocks, 4, 256), BF16), arg((slots, nb), jnp.int32),
        arg((slots, width), jnp.int32)).compile()
    text = compiled.as_text()
    assert "paged_slab_attention" in text
    # 97 blocks of K and V a (row, K/V head) gathered would be [.., 97,
    # 64, 128]: no array of a head's slabs is made
    assert not re.search(r"bf16\[[\d,]*,64,128\]\S* (gather|fusion)\(",
                         text.replace(f"bf16[{blocks},2,64,128]", "POOL")
                         .replace(f"bf16[{2 * blocks},64,128]", "POOL"))
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 << 20


@pytest.mark.parametrize("entry", ["decode", "prefill_512"])
def test_the_cells_executables_compile_and_fit_a_v5e(entry, one_chip,
                                                     monkeypatch):
    """The decode chunk and the widest prefill piece of the eight layers
    at 32 slots x 132,352 positions, from shapes alone: 5.64 GB of
    weights, 1.4 GB of pool (head-major: what the model caches and no
    more), 0.4 GB of state, and temporaries that leave
    room on a chip of 15.75 GiB.  The decode step holds NO array of the
    slots' state but the layers' own."""
    import numpy as np

    from paddle_tpu.serving import batched_decode as bd

    cfg, mix, family = _cell()
    arch = family._arch(cfg)
    geo = mix["engine"]
    S, T, Bt = geo["max_slots"], geo["max_len"], geo["block_tokens"]
    nb = T // Bt
    blocks = 1 + geo["pool_blocks"]
    assert (S, nb) == (32, 2068)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = {k: arg(shape, BF16) for k, shape in family.shapes(cfg).items()}
    count = sum(int(np.prod(v.shape)) for v in params.values())
    assert count == cfg["parameters_held"] == 2_820_544_768
    shapes = [arch.plane_block_shapes(i, Bt, BF16)
              for i in range(len(arch.planes))]
    pk = tuple(arg((blocks,) + s[0], BF16) for s in shapes)
    pv = tuple(arg((blocks,) + s[1], BF16) for s in shapes if len(s) > 1)
    assert len(pk) == 4 and len(pv) == 2
    state = tuple(tuple(arg((S,) + tuple(shp), dt) for shp, dt in layer)
                  for layer in arch.state_spec(BF16))
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in pk + pv + tuple(a for layer in state for a in layer))
    assert shapes[0] == ((2, Bt, 128),) * 2
    assert 1.7e9 < held < 1.9e9, held
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
    mem = compiled.memory_analysis()
    # pool, state and slot scalars are donated: aliased, not copied
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 2048 << 20, mem.temp_size_in_bytes
    total = 2 * count + held + mem.temp_size_in_bytes
    assert total < 14.6 * 2 ** 30, total
    whole = rf"f32\[{S},32,128,128\]"
    made = re.findall(rf"= {whole}\S* ([\w\-]+)\(", text)
    # the write, the compressed rows' read and the walk all reach a K/V
    # plane through its slab view: the compiler copies no pool array
    # into another layout (a gather of the head-major plane as it lies
    # cost 2.3 ms a decode step on the chip: PERF.md, PR 63)
    assert not re.search(rf"= bf16\[({blocks},2|{2 * blocks}),64,128\]\S* "
                         rf"copy\(", text)
    # both sides of the dense length walk slabs: no other paged kernel
    assert "paged_slab_attention" in text
    assert not re.search(r"%(chain|paged)_attention[.\d]* = ", text)
    if entry == "decode":
        assert text.count("ssm_step") >= 6
        assert set(made) <= {"parameter", "get-tuple-element"}, set(made)
    else:
        assert set(made) <= {"parameter", "get-tuple-element", "fusion",
                             "dynamic-update-slice"}, set(made)
        assert not re.search(rf"= {whole}\S* copy\(", text)
