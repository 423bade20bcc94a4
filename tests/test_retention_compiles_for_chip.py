"""Power retention's two Mosaic kernels compiled for a TPU v5e that is
described and not attached, at the geometry `brumby14b.doc_continue`
runs them (16 slots, 40 query heads over 8 K/V heads of 128, pieces of
8 to 512 rows, each ONE call): what interpret mode cannot show (a layout, a rotation or
a transpose Mosaic refuses).  Nothing runs: a compile that passes is no
chip run.  The state enters in place: no temporary the size of a slot's
state."""

import math

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


SLOTS, HEADS, KV, D = 16, 40, 8, 128


def _state(arg):
    from paddle_tpu.kernels.retention import stored_rows

    return (arg((SLOTS, KV, stored_rows(D), D), jnp.float32),
            arg((SLOTS, KV, stored_rows(D)), jnp.float32))


def test_retention_step_compiles_for_v5e(one_chip):
    from paddle_tpu.kernels.retention import retention_step_pallas

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda *a: retention_step_pallas(*a, interpret=False),
        donate_argnums=(0, 1)).lower(
        *_state(arg), arg((SLOTS, HEADS, D), jnp.bfloat16),
        arg((SLOTS, KV, D), jnp.bfloat16), arg((SLOTS, KV, D), jnp.bfloat16),
        arg((SLOTS, KV), jnp.float32), arg((SLOTS,), jnp.bool_)).compile()
    assert "retention_step" in compiled.as_text()
    # one slot's state of one layer is 34 MB: nothing of that size is made
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


@pytest.mark.parametrize("rows", [8, 32, 128, 256, 512])
def test_retention_chunk_compiles_for_v5e(rows, one_chip):
    from paddle_tpu.kernels.retention import retention_chunk_pallas

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda *a: retention_chunk_pallas(*a, interpret=False),
        donate_argnums=(0, 1)).lower(
        *_state(arg), arg((), jnp.int32), arg((), jnp.bool_),
        arg((rows, HEADS, D), jnp.bfloat16), arg((rows, KV, D), jnp.bfloat16),
        arg((rows, KV, D), jnp.bfloat16), arg((rows, KV), jnp.float32),
        arg((rows,), jnp.bool_)).compile()
    assert "retention_chunk" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


@pytest.mark.parametrize("rows", [256, 512])
def test_a_wide_piece_is_one_chunk_call_in_place_for_v5e(rows, one_chip):
    """A wide prefill window through ``_Cache.advance``: ONE Mosaic call
    a layer, named ``retention_chunk`` (the kernel walks the rows in
    tiles of ``CHUNK_ROWS`` itself), the slots' state aliased in place
    with no copy of it."""
    from paddle_tpu.kernels import retention as rt
    from paddle_tpu.serving.batched_decode import _Cache

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def piece(S, z, slot, start, q, k, v, lg, n):
        at = jnp.arange(rows)[None]
        cache = _Cache(None, None, None, None, start + at,
                       writable=at < n, slot=slot)
        y, planes = cache.advance(((), (), ((S, z),)), 0, rt, q, k, v, lg)
        return (y,) + planes[2][0]

    real = rt.chunk
    rt.chunk = lambda *a, **kw: rt.retention_chunk_pallas(
        *a, interpret=False, **kw)
    try:
        lowered = jax.jit(piece, donate_argnums=(0, 1)).lower(
            *_state(arg), arg((), jnp.int32), arg((), jnp.int32),
            arg((1, rows, HEADS, D), jnp.bfloat16),
            arg((1, rows, KV, D), jnp.bfloat16),
            arg((1, rows, KV, D), jnp.bfloat16),
            arg((1, rows, KV), jnp.float32), arg((), jnp.int32))
    finally:
        rt.chunk = real
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') \
        == len(rt.chunk_rows(rows)) == 1
    assert "retention_chunk" in text
    # 16 slots' state of a layer is 545 MB and one slot's 34: the call
    # writes it where it read it (the rows' own temporaries: rows x 40 x
    # 128 float32 outputs, the decays [8, rows, rows])
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        4 * math.prod(a.shape) for a in _state(arg))
