"""Paged decode gather as a Pallas TPU kernel — the ``pallas_tpu``
backend for the ``decode_gather`` op class.

The serving engine's decode step gathers each slot's logical KV
sequence through its block table: ``pool[table]`` (see
``serving/batched_decode.py``).  On CPU/GPU that advanced-indexing
spelling lowers to an efficient XLA gather (the ``xla_ref`` backend);
on TPU a row gather lowers poorly — the TPU-native spelling is a
``PrefetchScalarGridSpec`` kernel where the block TABLE is a scalar-
prefetch argument consumed by the input BlockSpec's index map, so each
grid cell's DMA fetches exactly the physical block the table names
(pallas_guide.md "PrefetchScalarGridSpec").  The kernel body is a pure
copy: a gather moves bits, it does not compute, so this backend is
BIT-EXACT vs the oracle in every dtype (``ORACLE_TOL`` pins 0.0).

Registered available only on real TPU — off-TPU the interpret-mode
kernel would replace one fast XLA gather with a slow per-block Python
loop; the oracle suite still exercises the kernel logic on CPU by
forcing ``interpret=True`` directly."""

import jax
import jax.numpy as jnp

from .registry import register_kernel


def decode_gather(pool, table, interpret=None):
    """``pool [num_blocks, B, h, dh]``, ``table [S, NB]`` int32 ->
    ``[S, NB*B, h, dh]``: slot ``s``'s logical view is the
    concatenation of its table's physical blocks."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    S, NB = table.shape
    _, B, h, dh = pool.shape

    def kernel(tbl, in_ref, out_ref):
        del tbl  # consumed by the index maps, not the body
        out_ref[0, 0] = in_ref[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S, NB),
        in_specs=[pl.BlockSpec(
            (1, B, h, dh), lambda s, nb, tbl: (tbl[s, nb], 0, 0, 0))],
        out_specs=pl.BlockSpec(
            (1, 1, B, h, dh), lambda s, nb, tbl: (s, nb, 0, 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, NB, B, h, dh), pool.dtype),
        interpret=bool(interpret),
        name="decode_gather",
    )(table.astype(jnp.int32), pool)
    return out.reshape(S, NB * B, h, dh)


def _tpu_available():
    try:
        backend = jax.default_backend()
    except Exception as e:  # noqa: BLE001
        return False, f"jax backend probe failed: {e}"
    if backend == "tpu":
        return True, ""
    return False, (f"not on TPU (platform {backend!r}); the XLA gather "
                   f"is the efficient spelling here")


class _GatherPallasTpu:
    call = staticmethod(decode_gather)


register_kernel("decode_gather", "pallas_tpu", _GatherPallasTpu,
                available=_tpu_available)
