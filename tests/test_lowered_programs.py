"""The nine older architectures lower to the programs they lowered to
before PR 57 (the last three families of ``PARENT`` say when theirs were
taken); the first seven of them lower to the
programs they lowered to before planes stated their own shape, chains
came in two kinds, the paged kernels took a sink and value lanes of
their own, and ``routed_ffn`` took the shared expert by statement: the
decode chunk, a narrow and a wide prefill piece of each (StableHLO, at
each family's tiny size), the Mosaic paged kernels' jaxprs and the Mosaic
grouped product's at the three routed cells' published widths, against
hashes taken on a ``git archive`` of the parent commit with
``tests/lowered_programs.py`` (PR 46; the same installation;
``SinkWindowMoE``'s on the parent of PR 51, which gave ``routed_ffn`` the
expert's form, ``grouped_matmul`` panels that overhang and ``_Cache`` ONE
in-place call for every recurrence).  A PR that
means to change one of them takes its hashes anew, the same way, and
says which program changed and why."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import lowered_programs  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARENT = {
    "gpt2": {
        "decode_chunk_4": "b3be1f063b0a4596",
        "prefill_8": "299ee30588d4facc",
        "prefill_32": "1f17ef3a3133fdc7"
    },
    "ouro": {
        "decode_chunk_4": "d6b00908a90e1564",
        "prefill_8": "00bc623174b47d7b",
        "prefill_32": "364800b7aed23fda"
    },
    "sambay": {
        "decode_chunk_4": "f97351e8f47d5000",
        "prefill_8": "d45310d8a5ec3d88",
        "prefill_32": "36ddf57ea2958758"
    },
    "gated_moe": {
        "decode_chunk_4": "eb3c24c55d6590be",
        "prefill_8": "23406f22b284a463",
        "prefill_32": "e076ada18f394c62"
    },
    "latent_moe": {
        "decode_chunk_4": "8af80918f20fbcd1",
        "prefill_8": "251bc626e049cc76",
        "prefill_32": "4102561b75c4db2e"
    },
    "retention": {
        "decode_chunk_4": "ea43177f94722d62",
        "prefill_8": "6423ceab36f723d4",
        "prefill_32": "75fe99c4f114c472"
    },
    "sink_window_moe": {
        "decode_chunk_4": "9d7913e1cd9270d3",
        "prefill_8": "5bf10dc93f438d22",
        "prefill_32": "7d64388882701c16"
    },
    # PR 57 (a prefix hit over recurrent state, a tenth architecture):
    # these two on its parent, where MambaMoE and SparseLatentMoE lower
    # to the same text as on its tree
    "ssm_moe": {
        "decode_chunk_4": "a042daf520d62106",
        "prefill_8": "0854d83fbaa33533",
        "prefill_32": "80620ef582bea7c4"
    },
    "sparse_latent_moe": {
        "decode_chunk_4": "6733abda6836e104",
        "prefill_8": "287d8ba9d93ccdba",
        "prefill_32": "dd3dfc63810476d8"
    },
    # DeltaMoE's own, on PR 57's tree: what a later PR is held to
    "delta_moe": {
        "decode_chunk_4": "646ca35220fb9a7c",
        "prefill_8": "6d8aaf3d82c9807d",
        "prefill_32": "cf6b7f589af080ca"
    },
    # SparseLightning's own, taken again on PR 63's tree (its K/V planes
    # head-major, both sides of the dense length through one walk of a K/V
    # head's slabs): what a later PR is held to
    "sparse_lightning": {
        "decode_chunk_4": "abe56f5496e22aa7",
        "prefill_8": "54679723a889b5f4",
        "prefill_32": "d97d13667746c370"
    }
}
# PR 52 gave the loop of two rows or more G table entries an iteration
# (``entries_per_iteration``): ``group_6_window_loop`` and
# ``float32_pool_group_4`` (G = 1 on their table of 4: the group's
# buffers, copies and trip count are spelled anew, the last group is
# weighed after the loop) were taken again on
# that PR's tree and ``group_16_loop_8_entries`` (G = 8) added; the
# one-row loop, the grid form and the latent kernel are the parent's.
MOSAIC = {
    "one_row_loop": "b901ca0e439058e8",
    "group_6_window_loop": "8efea920881500cc",
    "verify_window_grid_12_heads": "e35ccddc63a17f48",
    "float32_pool_group_4": "4a9625e5270b0b63",
    "group_16_loop_8_entries": "8353fc8f25108d45",
    "latent": "b4812bf81d7ed767"
}
# the Mosaic grouped product at the three routed cells' published widths
# (a decode step's rows and a piece's, up and down), on the parent of PR
# 51, which gave it panels that overhang and matrices held transposed
GROUPED = {
    "trinitylp_32x3072x3072_up_48_rows": "00d1b578cd392d30",
    "trinitylp_32x3072x3072_down_48_rows": "00d1b578cd392d30",
    "trinitylp_32x3072x3072_up_512_rows": "2a2ca7c1fd0ce8b3",
    "trinitylp_32x3072x3072_down_512_rows": "2a2ca7c1fd0ce8b3",
    "dsv2lite_16x2048x1408_up_48_rows": "25a79479efc51d4a",
    "dsv2lite_16x2048x1408_down_48_rows": "78aa1ea6f63c9d5b",
    "dsv2lite_16x2048x1408_up_512_rows": "09fb850d13ff86e6",
    "dsv2lite_16x2048x1408_down_512_rows": "80f5ed0f002dcd17",
    "mimo25_16x4096x2048_up_48_rows": "4f3ec407dd0f49b2",
    "mimo25_16x4096x2048_down_48_rows": "0ee2d2a262fa323d",
    "mimo25_16x4096x2048_up_512_rows": "8d83b3ac645a1d84",
    "mimo25_16x4096x2048_down_512_rows": "4213e76a66d92722"
}


@pytest.fixture(scope="module")
def mine():
    return {}


@pytest.mark.parametrize("entry", lowered_programs.ENTRIES)
@pytest.mark.parametrize("family", list(PARENT))
def test_the_lowered_program_is_the_parents(family, entry, mine):
    if family not in mine:
        mine.update(lowered_programs.programs(ROOT, only=family))
    assert mine[family][entry] == PARENT[family][entry]


@pytest.mark.parametrize("geometry", list(MOSAIC))
def test_the_mosaic_kernels_jaxpr_is_the_parents(geometry, mine):
    if "mosaic" not in mine:
        mine["mosaic"] = lowered_programs.mosaic(ROOT)
    assert mine["mosaic"][geometry] == MOSAIC[geometry]


@pytest.mark.parametrize("geometry", list(GROUPED))
def test_the_mosaic_grouped_products_jaxpr_is_the_parents(geometry, mine):
    if "grouped" not in mine:
        mine["grouped"] = lowered_programs.grouped(ROOT)
    assert mine["grouped"][geometry] == GROUPED[geometry]
