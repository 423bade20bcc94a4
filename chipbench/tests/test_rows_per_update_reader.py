"""``paged.rows_per_update`` (PR 33) on hand-made ``stats``, and on the
counters a small engine really keeps."""

import numpy as np
import pytest

from chipbench import run as bench_run

READ = bench_run.load_reader("paged.rows_per_update").read


@pytest.mark.parametrize("rows,updates,want", [
    (40.0, 40.0, 1.0),          # one row a block: nothing to share
    (19200.0, 4800.0, 4.0),     # a K/V group of four folded into the window
    (0.0, 12.0, 0.0),
])
def test_rows_over_updates(rows, updates, want):
    assert READ({"stats": {"serving.paged_rows_live": rows,
                           "serving.paged_updates_live": updates}}) == want


@pytest.mark.parametrize("stats", [
    {},                                            # the parent of PR 33
    {"serving.paged_entries_live": 40.0,           # PR 29's counters alone
     "serving.paged_entries_total": 576.0},
    {"serving.paged_updates_live": 0.0},           # no chunk in the window
])
def test_nothing_to_read_is_none(stats):
    assert READ({"stats": stats}) is None


def test_reads_a_small_engines_own_counters():
    import paddle_tpu as pt
    from paddle_tpu.models import transformer
    from paddle_tpu.observability.metrics import MetricsRegistry
    from paddle_tpu.serving import ServingEngine

    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        transformer.build(vocab_size=50, n_layer=1, n_head=2, d_model=32,
                          max_len=32, dropout_rate=0.0)
    pt.Executor().run(startup)
    eng = ServingEngine(transformer.extract_params(program=main), 1, 2, 32,
                        max_len=32, max_slots=2, decode_chunk=4,
                        min_bucket=4, block_tokens=4, prefix_reuse=False,
                        registry=MetricsRegistry())
    eng.generate_many([np.arange(1, 6, dtype=np.int32)], max_new_tokens=5)
    assert READ({"stats": eng.stats()}) == 1.0
