"""The Mosaic flash kernels compiled for a TPU v5e that is described and
not attached, at the calls the training cell, the chip smoke and the
other layouts make: what interpret mode cannot show (a slice off the
tiling, a concatenate Mosaic refuses, more VMEM than a kernel may use).
The compiled text must also hold what the benchmark's reader looks for
(``chipbench/layer_metrics/flash_roofline.py`` finds the calls by the
shapes they return), so a change of a result's shape fails here and not
as a ``null`` in the ledger.  Nothing runs: a compile that passes is no
chip run.  One file, the topology inside a fixture (one process may hold
the TPU's library)."""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


# (batch, t, heads, head width, layout, causal)
CALLS = {
    "train_2k_cell": (2, 2048, 12, 128, "packed", True),
    "smoke_t4096": (1, 4096, 6, 128, "packed", True),
    "paired_d64": (2, 2048, 24, 64, "packed", True),
    "layout_4d": (2, 2048, 12, 128, "4d", True),
    "noncausal": (2, 2048, 12, 128, "packed", False),
}


_TEXTS = {}  # a compile is seconds: one a (call, backward spelling)


def _compiled_text(call, one_chip):
    """Optimized HLO of forward + backward at ``call``, compiled for the
    described chip."""
    from paddle_tpu.ops import pallas_attention as pa

    key = (call, pa.FUSED_BWD_PARTIAL_BYTES)
    if key not in _TEXTS:
        _TEXTS[key] = _compile(call, one_chip)
    return _TEXTS[key]


def _compile(call, one_chip):
    from paddle_tpu.ops import pallas_attention as pa

    b, t, h, d, layout, causal = CALLS[call]
    if layout == "packed":
        shape = (b, t, h * d)
        attend = lambda q, k, v: pa._pallas_flash_attention_packed(
            q, k, v, h, causal=causal, interpret=False)
    else:
        shape = (b, t, h, d)
        attend = lambda q, k, v: pa._pallas_flash_attention(
            q, k, v, causal=causal, interpret=False)

    def both(q, k, v, do):
        o, vjp = jax.vjp(attend, q, k, v)
        return o, vjp(do)

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    return jax.jit(both).lower(x, x, x, x).compile().as_text()


@pytest.mark.parametrize("call", list(CALLS))
def test_flash_kernels_compile_for_v5e(call, one_chip):
    text = _compiled_text(call, one_chip)
    assert "flash_fwd" in text and "flash_bwd_fused" in text


def test_split_backward_compiles_for_v5e(one_chip, monkeypatch):
    from paddle_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "FUSED_BWD_PARTIAL_BYTES", 0)
    text = _compiled_text("train_2k_cell", one_chip)
    assert "flash_bwd_dq" in text and "flash_bwd_dkv" in text


def test_compiled_cell_call_holds_the_readers_needles(one_chip):
    """The reader is read, never edited: what it looks for in a trace's
    operations is what the compiled step must hold, once for the forward
    and once for the fused backward."""
    from chipbench import run as chipbench_run

    cell = chipbench_run.load_cell("cgpt590m.train_2k")
    needles = chipbench_run.load_reader("flash_roofline").kernels(
        cell["config"], cell["traffic"])
    assert set(needles) == {"flash_fwd", "flash_bwd"}
    lines = _compiled_text("train_2k_cell", one_chip).splitlines()
    for label, parts in needles.items():
        found = [ln for ln in lines if all(p in ln for p in parts)]
        assert len(found) == 1, (label, parts, found)
        assert label in found[0]  # the pallas_call's own name
