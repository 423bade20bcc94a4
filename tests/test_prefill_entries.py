"""``serving.prefill_entries{kind}``: what the prefill pieces' chain walks
visit beside what a dense window attends, counted on the host once a
piece where ``kernels.paged_attention.walks_chain`` says the piece walks;
and its reader, ``prefill.attended_entry_share``, on hand-made ``stats``
and on a small engine's own counters."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from chipbench import run as bench_run  # noqa: E402
from paddle_tpu.kernels import paged_attention as pa  # noqa: E402
from paddle_tpu.serving import batched_decode as bd  # noqa: E402
from tiny import gpt2  # noqa: E402

READ = bench_run.load_reader("prefill.attended_entry_share").read
ATTENDED = "serving.prefill_entries{kind=attended}"
CHAIN = "serving.prefill_entries{kind=chain}"


@pytest.mark.parametrize("attended,chain,share", [
    (109.0, 416.0, 100.0 * 109.0 / 416.0),     # the cell's mean piece
    (416.0, 416.0, 100.0),                     # every chain full
    (0.0, 832.0, 0.0),
])
def test_share_is_attended_over_chain(attended, chain, share):
    assert READ({"stats": {ATTENDED: attended, CHAIN: chain}}) == share


@pytest.mark.parametrize("stats", [
    {},                                        # the parent of PR 47
    {CHAIN: 0.0},                              # no piece walked
    {"serving.prefill_pieces{width=512}": 3.0},
])
def test_nothing_to_read_is_none(stats):
    assert READ({"stats": stats}) is None


def _admit(monkeypatch, rule, lens):
    monkeypatch.setattr(pa, "CHAIN_SCORE_BYTES", rule)
    eng, _ = gpt2.engine(gpt2.init(), monkeypatch, prefix_reuse=False)
    rng = np.random.default_rng(3)
    eng.generate_many([rng.integers(1, gpt2.sizes["rows"], n, dtype=np.int32)
                       for n in lens], max_new_tokens=2)
    return eng.stats()


def test_engine_counts_what_the_walking_pieces_visit(monkeypatch):
    """Pieces of 8 rows over chains of 16 entries of 4 positions, two
    full planes (one a layer); the pieces of 4 rows stream (narrower
    than ``DENSE_WINDOW``) and count nothing."""
    assert bd.PREFILL_PIECE >= pa.DENSE_WINDOW == 8
    stats = _admit(monkeypatch, 0, [3, 8, 19])
    # 8 rows at 0 | 8 at 0, 8 at 8 (19 = 8 + 8 + 4): entries 0..1, 0..1,
    # 0..3 of 16, in each of the two planes
    assert stats[ATTENDED] == 2 * (2 + 2 + 4)
    assert stats[CHAIN] == 2 * 3 * (gpt2.max_len // gpt2.block_tokens)
    assert READ({"stats": stats}) == 100.0 * 8 / 48


def test_engine_counts_nothing_where_no_piece_walks(monkeypatch):
    """The rule out of reach (as for every cell whose pieces are small):
    no counter, nothing for the reader."""
    stats = _admit(monkeypatch, 1 << 40, [8, 19])
    assert ATTENDED not in stats and CHAIN not in stats
    assert READ({"stats": stats}) is None


@pytest.mark.parametrize("window,at,width,want", [
    (None, 0, 512, 16),            # a piece that starts a prompt
    (None, 3072, 512, 112),        # the cell's mean: 3,584 positions
    (None, 13000, 512, 416),       # past the chain's end: the whole chain
    (128, 3072, 512, 111 - 92 + 1),    # from the entry of 3072 - 127
    (128, 0, 512, 16),
])
def test_entries_from_the_lower_bound_to_the_last_position(window, at, width,
                                                           want):
    """The arithmetic at ``mimo25.long_reason``'s geometry (416 entries
    of 32 positions), on an engine that is nothing but its counters.
    The dense spelling of such a call (3.5 GB of scores: one K/V head at
    a time) gathers a window plane's own 21 entries, a full plane's 416."""
    from paddle_tpu.observability.metrics import MetricsRegistry
    from paddle_tpu.serving.engine import ServingEngine

    eng = ServingEngine.__new__(ServingEngine)
    eng.block_tokens, eng.blocks_per_slot = 32, 416
    eng._reg = MetricsRegistry()
    eng._piece_reads = [(window, 2, 64 * 8)]
    eng._count_prefill_entries([(width, None, at, width)])
    got = {k: v for k, v in eng._reg.snapshot().items()
           if k.startswith("serving.prefill_entries")}
    assert got == {ATTENDED: 2 * want,
                   CHAIN: 2 * (416 if window is None else 21)}


def test_the_dense_spelling_gathers_the_chain_or_a_windows_own_entries():
    # under DENSE_SCORE_BYTES one step scores the whole chain, window or not
    assert pa.dense_entries(512, 4 * 16, 64, 32, 512) == 64
    assert pa.dense_entries(512, 4 * 16, 64, 32, None) == 64
    # past it a lower bound gathers its own entries, a full plane all
    assert pa.dense_entries(512, 64 * 8, 416, 32, 128) == 21
    assert pa.dense_entries(512, 64 * 8, 416, 32, None) == 416
    assert pa.window_entries(416, 32, 512, 128) == 21
    assert pa.window_entries(16, 4, 8, 100) == 16
