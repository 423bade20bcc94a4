"""``paged.fetched_entry_share`` (PR 58) on hand-made ``stats``, its entry
in ``BENCHMARK.json``, and on the counters a small engine really keeps."""

import json
import os

import numpy as np
import pytest

from chipbench import run as bench_run

NAME = "paged.fetched_entry_share"
READER = bench_run.load_reader(NAME)
FETCHED = "serving.paged_entries_fetched{phase=decode}"


@pytest.mark.parametrize("fetched,live,want", [
    (1000.0, 1000.0, 100.0),    # nothing shared: every entry its slot's own
    (740.0, 1000.0, 74.0),      # 2.15 distinct documents of 3 live slots
    (357.0, 1637.0, 100.0 * 357 / 1637),   # six slots on one document
])
def test_fetched_over_live(fetched, live, want):
    assert READER.read({"stats": {
        FETCHED: fetched, "serving.paged_entries_live": live}}) == (
            pytest.approx(want))


@pytest.mark.parametrize("stats", [
    {},                                          # no plane at all
    {"serving.paged_entries_live": 1000.0,       # the parent of PR 58
     "serving.paged_entries_shared": 510.0},
    {FETCHED: 0.0, "serving.paged_entries_live": 0.0},   # no chunk sent
])
def test_nothing_to_read_is_none(stats):
    assert READER.read({"stats": stats}) is None


def test_the_entry_in_the_benchmark_is_the_readers():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metric = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert metric == {
        "name": NAME, "unit": READER.UNIT, "better": "lower",
        "source": READER.SOURCE, "layer": READER.LAYER,
        "moves": READER.MOVES, "workloads": ["dsv2lite.doc_qa_8k"]}


def test_reads_a_small_engines_own_counters(monkeypatch):
    """Two requests on one head of four whole blocks, live together: the
    latent family's tiny engine (the CPU's oracle attends; the counter is
    the host's) tells its decode calls to fetch the head once, at two
    table entries an iteration."""
    import paddle_tpu.kernels.paged_attention as pa
    from chipbench import families
    from chipbench.tests.test_latent_moe_family import TINY
    from paddle_tpu.observability.metrics import MetricsRegistry

    monkeypatch.setattr(pa, "LATENT_BLOCKS", 2)
    cfg = dict(TINY, family="latent_moe")
    family = families.of(cfg, "serve")
    geometry = {"max_len": 64, "max_slots": 2, "block_tokens": 4,
                "cache_blocks": 12, "prefix_reuse": True, "decode_chunk": 4}
    reg = MetricsRegistry()
    eng = family.serving_engine(family.make_params(cfg, 64, 1), cfg, reg,
                                geometry)
    head = (3 * np.arange(18) + 2) % 100
    eng.generate_many([np.concatenate([head, [9, 8, 7]]).astype(np.int32)],
                      max_new_tokens=[2])
    assert READER.read({"stats": eng.stats()}) == 100.0   # one live slot
    eng.generate_many(
        [np.concatenate([head, tail]).astype(np.int32)
         for tail in ([1, 2, 3, 4, 5], [11, 12, 13, 14])],
        max_new_tokens=[8, 8])
    stats = eng.stats()
    share = READER.read({"stats": stats})
    assert 50.0 < share < 100.0
    spared = stats["serving.paged_entries_live"] - stats[FETCHED]
    assert spared > 0 and spared % 4 == 0
