"""What the seven architectures that are NOT ``MambaMoE`` lower to:
the StableHLO text of their decode chunk and of a wide and a narrow
prefill piece at the tiny size of each family's own test
(``chipbench/tests/test_<family>_family.py::TINY``), hashed; and the
jaxpr of the Mosaic paged kernels (``mosaic``) and of the Mosaic grouped
product at the three routed cells' widths (``grouped``), which the CPU's
programs do not hold.

    python tests/lowered_programs.py [root]

prints ``{family: {entry: sha256}}`` for the tree at ``root`` (this one by
default): run on a ``git archive`` of a parent commit it gives the
hashes ``tests/test_lowered_programs.py`` pins (PR 46: the commit before
planes stated their own shape and chains came in two kinds; PR 51 added
``SinkWindowMoE``'s, taken on its parent, when ``routed_ffn`` took the
expert's form and ``_Cache.retain`` became ``advance``; PR 57 added
``MambaMoE``'s and ``SparseLatentMoE``'s, taken on its parent, and
``DeltaMoE``'s own, taken on its tree; PR 61 added ``SparseLightning``'s
own, taken on its tree, and PR 63, which stored its K/V planes head-major,
took them again on its own: a family the root does not have is left out)."""

import hashlib
import importlib.util
import json
import os
import sys

FAMILIES = {"gpt2": "test_rehearsal", "ouro": "test_ouro_family",
            "sambay": "test_sambay_family", "gated_moe":
            "test_gated_moe_family", "latent_moe": "test_latent_moe_family",
            "retention": "test_retention_family",
            "sink_window_moe": "test_sink_window_moe_family",
            "ssm_moe": "test_ssm_moe_family",
            "sparse_latent_moe": "test_sparse_latent_moe_family",
            "delta_moe": "test_delta_moe_family",
            "sparse_lightning": "test_sparse_lightning_family"}
GEOMETRY = {"max_len": 64, "max_slots": 2, "block_tokens": 8,
            "cache_blocks": 0, "prefix_reuse": False}
ENTRIES = ("decode_chunk_4", "prefill_8", "prefill_32")


def _tiny(root, module):
    path = os.path.join(root, "chipbench", "tests", module + ".py")
    spec = importlib.util.spec_from_file_location("_tiny_" + module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return dict(mod.TINY)


def programs(root, only=None):
    """{family: {entry: sha256 of the lowered StableHLO}}."""
    if root not in sys.path:
        sys.path.insert(0, root)
    import jax.numpy as jnp
    import numpy as np
    from chipbench import families
    from paddle_tpu.observability.metrics import MetricsRegistry
    from paddle_tpu.serving import batched_decode as bd

    out = {}
    for name, module in FAMILIES.items():
        if only and name != only:
            continue
        if not os.path.isfile(os.path.join(root, "chipbench", "tests",
                                           module + ".py")):
            continue                # a family this root does not have yet
        cfg = _tiny(root, module)
        cfg.setdefault("family", name)
        family = families.of(cfg, "serve")
        params = family.make_params(cfg, GEOMETRY["max_len"], 1)
        eng = family.serving_engine(params, cfg, MetricsRegistry(),
                                    dict(GEOMETRY))
        arch = eng.arch
        texts = {"decode_chunk_4": bd.make_decode_chunk(arch, 4).lower(
            eng._p, eng._pk, eng._pv, eng._last, eng._pos,
            jnp.asarray(eng._table), eng._state).as_text()}
        row = jnp.asarray(eng._table[0]) if arch.planes else jnp.asarray(
            np.zeros(0, np.int32))
        for width in (8, 32):
            zero = np.int32(0)
            texts[f"prefill_{width}"] = bd.make_prefill(arch, width).lower(
                eng._p, eng._pk, eng._pv, eng._last, eng._pos, zero, row,
                jnp.zeros((width,), jnp.int32), zero, np.int32(width), zero,
                zero, eng._state).as_text()
        out[name] = {k: hashlib.sha256(v.encode()).hexdigest()[:16]
                     for k, v in texts.items()}
    return out


# (window rows, K/V rows of the pool, query heads a K/V head, lower
# bound, dtype, table entries a slot): the loop form for one row and for
# several (one table entry an iteration on a table of 4, eight on one of
# 416: ``entries_per_iteration``), the grid form
MOSAIC = {"one_row_loop": (1, 16, 1, None, "bfloat16", 4),
          "group_6_window_loop": (1, 8, 6, 64, "bfloat16", 4),
          "verify_window_grid_12_heads": (5, 12, 1, None, "bfloat16", 4),
          "float32_pool_group_4": (1, 16, 4, None, "float32", 4),
          "group_16_loop_8_entries": (1, 8, 16, None, "bfloat16", 416)}


def mosaic(root):
    """{geometry: sha256 of the Mosaic paged kernel's jaxpr} (and the
    latent sibling's), file names and line numbers left out."""
    import re

    if root not in sys.path:
        sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import paged_attention as pa

    def text(fn, *args):
        jaxpr = str(jax.make_jaxpr(fn)(*args))
        return re.sub(r"(/[\w.\-]+)+\.py(:\d+)?(:\d+)?", "<file>", jaxpr)

    out = {}
    for name, (W, hk, group, window, dtype, NB) in MOSAIC.items():
        pool = jnp.zeros((9, 8, hk, 128), dtype)
        out[name] = text(
            lambda q, k, v, t, p: pa.paged_attention_pallas(
                q, k, v, t, p, interpret=False, group=group, window=window),
            jnp.zeros((3, W, hk * group, 128), jnp.bfloat16), pool, pool,
            jnp.zeros((3, NB), jnp.int32), jnp.zeros((3, W), jnp.int32))
    out["latent"] = text(
        lambda q, k, t, p: pa.paged_attention_pallas(
            q, k, None, t, p, interpret=False, value_lanes=128, scale=0.1),
        jnp.zeros((3, 1, 4, 256), jnp.bfloat16),
        jnp.zeros((9, 8, 256), jnp.bfloat16), jnp.zeros((3, 4), jnp.int32),
        jnp.zeros((3, 1), jnp.int32))
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16]
            for k, v in out.items()}


# (experts held, d, expert width, rows) of the three routed cells the
# benchmark had before ``MambaMoE``: a decode step's rows and a prefill
# piece's, the up product ``[d, e]`` and the down product ``[e, d]``
GROUPED = {"trinitylp_32x3072x3072": (32, 3072, 3072),
           "dsv2lite_16x2048x1408": (16, 2048, 1408),
           "mimo25_16x4096x2048": (16, 4096, 2048)}


def grouped(root):
    """{geometry: sha256 of the Mosaic grouped product's jaxpr}: the
    CPU's programs resolve ``grouped_matmul`` to the oracle, so the
    kernel the chip runs is pinned here, at the published widths (shapes
    only: nothing is computed)."""
    import re

    if root not in sys.path:
        sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import grouped_matmul as gm

    out = {}
    for name, (g, d, e) in GROUPED.items():
        for rows in (48, 512):
            for which, (k, n) in (("up", (d, e)), ("down", (e, d))):
                jaxpr = str(jax.make_jaxpr(
                    lambda x, w, sizes: gm.grouped_matmul_pallas(
                        x, w, sizes, interpret=False))(
                    jax.ShapeDtypeStruct((rows, k), jnp.bfloat16),
                    jax.ShapeDtypeStruct((g, k, n), jnp.bfloat16),
                    jax.ShapeDtypeStruct((g,), jnp.int32)))
                out[f"{name}_{which}_{rows}_rows"] = re.sub(
                    r"(/[\w.\-]+)+\.py(:\d+)?(:\d+)?", "<file>", jaxpr)
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16]
            for k, v in out.items()}


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    root = os.path.abspath(sys.argv[1]) if len(sys.argv) > 1 else \
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    print(json.dumps(dict(programs(root), mosaic=mosaic(root),
                          grouped=grouped(root)), indent=1))
