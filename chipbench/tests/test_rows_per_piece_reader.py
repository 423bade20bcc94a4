"""``step.prefill_rows_per_piece`` (PR 43) on hand-made ``stats``, and on
the counters a small engine really keeps."""

import numpy as np
import pytest

from chipbench import run as bench_run

READ = bench_run.load_reader("step.prefill_rows_per_piece").read


@pytest.mark.parametrize("real,pieces,want", [
    (1000.0, {128: 7, 64: 1}, 125.0),      # eight narrow pieces
    (1000.0, {512: 1, 256: 1, 8: 2}, 250.0),
    (5.0, {8: 1}, 5.0),
])
def test_real_rows_over_window_calls(real, pieces, want):
    stats = {"serving.prefill_real_tokens": real,
             "serving.prefill_tokens": 99.0}
    stats.update({f"serving.prefill_pieces{{width={w}}}": float(n)
                  for w, n in pieces.items()})
    assert READ({"stats": stats}) == want


@pytest.mark.parametrize("stats", [
    {},                                            # a program without them
    {"serving.prefill_real_tokens": 12.0},         # no piece counted
    {"serving.prefill_pieces{width=8}": 0.0},      # no admission in the window
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
    eng.generate_many([np.arange(1, 6, dtype=np.int32),
                       np.arange(1, 4, dtype=np.int32)], max_new_tokens=3)
    # 5 and 3 real rows in two window calls, whatever their widths
    assert READ({"stats": eng.stats()}) == 4.0
