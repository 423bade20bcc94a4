"""``kvcache.WindowChains``: the chains of the planes attended under a
lower bound.  After any sequence of admissions, prefill pieces, decode
chunks and releases every block is free, or held by exactly one slot
whose table names it at exactly one entry; a slot never holds more than
``window_blocks`` says; what a row's window can see is always held."""

import numpy as np
import pytest

from paddle_tpu.serving.kvcache import (BlockPool, PoolExhausted,
                                        WindowChains, window_blocks)


def _check(chains, slots, B, window, at):
    pool = chains.pool
    named = chains.table[chains.table > 0]
    assert len(named) == len(set(named.tolist())) == pool.blocks_in_use
    assert all(pool.refcount(int(b)) == 1 for b in named)
    for s in range(slots):
        row = chains.table[s]
        assert chains.held(s) == int((row > 0).sum())
        if at[s] is None:
            assert not row.any()
            continue
        # every position the next row's window can see is in a held block
        lo = max(at[s] - window, 0)
        assert all(row[p // B] > 0 for p in range(lo, at[s]))


@pytest.mark.parametrize("window,B,piece,chunk,seed", [
    (8, 4, 8, 4, 0), (128, 32, 512, 4, 1), (128, 32, 512, 16, 2),
    (5, 4, 32, 1, 3), (512, 32, 256, 8, 4), (16, 16, 16, 4, 5)])
def test_every_block_is_free_or_held_once_after_any_sequence(
        window, B, piece, chunk, seed):
    rng = np.random.default_rng(seed)
    slots, max_len = 5, 40 * B
    per_slot = window_blocks(window, max(piece, chunk), B)
    pool = BlockPool(1 + slots * per_slot, B)
    chains = WindowChains(pool, slots, max_len // B, window)
    at = [None] * slots          # next position to write, None if free
    end = [0] * slots
    released = 0
    for _ in range(400):
        s = int(rng.integers(slots))
        if at[s] is None:
            # admission: a prompt in pieces, each preceded by its advance
            n = int(rng.integers(1, max_len - 2))
            end[s] = int(rng.integers(n + 1, max_len))
            pos = 0
            while pos < n:
                rows = min(piece, n - pos)
                released += chains.advance(s, pos, pos + rows - 1)
                assert chains.held(s) <= per_slot
                pos += rows
                _check(chains, slots, B, window,
                       [pos if i == s else a for i, a in enumerate(at)])
            at[s] = n
        elif at[s] >= end[s] or rng.random() < 0.1:
            chains.release(s)
            at[s] = None
        else:
            last = min(at[s] + chunk, end[s]) - 1
            released += chains.advance(s, at[s], last)
            assert chains.held(s) <= per_slot
            at[s] = last + 1
        _check(chains, slots, B, window, at)
    assert released == chains.released > 0
    for s in range(slots):
        chains.release(s)
    assert pool.blocks_in_use == 0 and not chains.table.any()


def test_a_pool_of_the_stated_size_is_tight():
    """One block fewer than ``window_blocks`` a slot and a piece that
    straddles block edges cannot be served."""
    window, B, piece = 8, 4, 8
    per_slot = window_blocks(window, piece, B)
    assert per_slot == 5
    chains = WindowChains(BlockPool(per_slot, B), 1, 16, window)  # 4 real
    chains.advance(0, 0, 7)
    with pytest.raises(PoolExhausted):
        chains.advance(0, 9, 16)   # sees 2.., writes ..16: entries 0-4


def test_a_long_context_holds_a_few_blocks():
    chains = WindowChains(BlockPool(1 + 21, 32), 1, 416, 128)
    at = 0
    while at < 6000:
        chains.advance(0, at, min(at + 512, 6000) - 1)
        at += 512
    assert chains.held(0) <= 21
    for pos in range(6000, 6100, 4):
        chains.advance(0, pos, pos + 3)
        assert chains.held(0) <= 5
    assert chains.released >= 6000 // 32 - 5
