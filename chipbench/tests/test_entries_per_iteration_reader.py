"""``paged.entries_per_iteration`` (PR 52) on hand-made ``stats``, and on
the counters a small engine really keeps."""

import numpy as np
import pytest

from chipbench import run as bench_run

READ = bench_run.load_reader("paged.entries_per_iteration").read


@pytest.mark.parametrize("entries,iterations,want", [
    (40.0, 40.0, 1.0),          # one row a block: an entry an iteration
    (4560.0, 600.0, 7.6),       # chains of 190 entries in groups of 8
    (0.0, 12.0, 0.0),
])
def test_entries_over_iterations(entries, iterations, want):
    assert READ({"stats": {
        "serving.paged_entries_live": entries,
        "serving.paged_iterations_live": iterations}}) == want


@pytest.mark.parametrize("stats", [
    {},                                            # no plane at all
    {"serving.paged_entries_live": 40.0,           # the parent of PR 52
     "serving.paged_updates_live": 40.0},
    {"serving.paged_iterations_live": 0.0},        # no chunk in the window
])
def test_nothing_to_read_is_none(stats):
    assert READ({"stats": stats}) is None


@pytest.mark.parametrize("rows,want", [(1, 1.0), (4, 5 / 3)])
def test_reads_a_small_engines_own_counters(rows, want, monkeypatch):
    """One row a block takes an entry an iteration whatever the rule
    says; four rows at two entries an iteration (the rule's answer
    overridden: tables of 8 entries get one) make chains of 2 and 3
    entries 1 + 2 iterations (a bfloat16 pool of 8 heads: the loop
    form)."""
    import paddle_tpu as pt
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.models import transformer
    from paddle_tpu.observability.metrics import MetricsRegistry
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.arch import Gpt2

    class Grouped(Gpt2):
        rows_per_entry = rows

    monkeypatch.setattr(pa, "entries_per_iteration", lambda *a: 2)
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        transformer.build(vocab_size=50, n_layer=1, n_head=8, d_model=64,
                          max_len=32, dropout_rate=0.0)
    pt.Executor().run(startup)
    eng = ServingEngine(transformer.extract_params(program=main),
                        arch=Grouped(1, 8, 64), max_len=32, max_slots=2,
                        decode_chunk=4, min_bucket=4, block_tokens=4,
                        prefix_reuse=False, registry=MetricsRegistry())
    eng.generate_many([np.arange(1, 6, dtype=np.int32),
                       np.arange(1, 10, dtype=np.int32)], max_new_tokens=5)
    assert READ({"stats": eng.stats()}) == pytest.approx(want)
