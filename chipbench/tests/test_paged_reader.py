"""``paged.skipped_entry_share`` (PR 29) on hand-made ``stats``, and on
the counters a small engine really keeps."""

import numpy as np
import pytest

from chipbench import run as bench_run

READ = bench_run.load_reader("paged.skipped_entry_share").read


@pytest.mark.parametrize("live,total,share", [
    (40.0, 576.0, 100.0 * (1.0 - 40.0 / 576.0)),   # a few slots stepping
    (0.0, 160.0, 100.0),                           # chunks, nobody live
    (160.0, 160.0, 0.0),                           # every chain full
])
def test_share_is_one_minus_live_over_total(live, total, share):
    assert READ({"stats": {"serving.paged_entries_live": live,
                           "serving.paged_entries_total": total}}) == share


@pytest.mark.parametrize("stats", [
    {},                                            # the parent of PR 29
    {"serving.paged_entries_total": 0.0},          # no chunk in the window
    {"serving.stalled_seconds": 1.0, "serving.live_seconds": 2.0},
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
    # one request of 5 + 1 tokens at its only chunk: 2 entries of 2 x 8
    assert READ({"stats": eng.stats()}) == 100.0 * (1.0 - 2.0 / 16.0)
