"""Block-table-aware paged attention: how a row attends through the
block table, decided here and nowhere else.

The serving engine's paged KV cache (``serving/kvcache.py``) stores
each slot's KV as a chain of physical blocks named by a block table.
``paged_attention`` attends THROUGH the table: online softmax block by
block, one physical block (or a small group) in flight at a time, the
logical view ``pool[table] -> [S, T, h, dh]`` never built.

The serving step (``serving/batched_decode._Cache``) calls ONE
function, :func:`attend`, which chooses among THREE spellings from the
shapes it can observe at trace time and from nothing else (no
environment variable, no tuner, no file, no architecture's name):

* a window narrower than ``DENSE_WINDOW`` rows (decode's ``W = 1``, a
  speculative ``k + 1``, a narrow prefill piece) STREAMS blocks through
  whatever the registry resolves for ``paged_attention``: the Mosaic
  kernel on a TPU (its loop or its grid form by
  ``_block_is_sliceable``), the ``xla_ref`` scan elsewhere;
* a wider window (a prefill piece) gathers the chain ONCE and attends
  it DENSELY (``dense_window``): the ``xla_ref`` spelling with one step
  over the whole chain, float32 scores ``[rows, NB x B]`` through HBM
  whatever the context.  That is the fastest spelling while the scores
  stay in the chip's fast memory (a 32-row piece over 768 positions is
  6 MB of them);
* a wide window over a K/V plane whose dense scores would reach
  ``CHAIN_SCORE_BYTES`` (``walks_chain``: folded rows x the chain's
  positions x 4 bytes) WALKS its chain instead: the ``chain_attention``
  op class (``kernels/chain_attention.py``), a blocked online-softmax
  walk in tiles from the plane's lower bound up to the context and no
  further, the scores never outside VMEM; on a TPU a Mosaic kernel (HLO
  name ``chain_attention``), off it the dense spelling above, so a CPU
  program lowers to what it always lowered to.  A LATENT plane
  (``pool_v=None``) keeps the dense spelling whatever its size.

Calling convention (both backends)::

    call(q, pool_k, pool_v, table, pos, block_step=None,
         interpret=None, group=1, window=None, scale=None,
         out_dtype=None) -> ctx

    q       [S, W, h, dh]   query window (W=1 for plain decode,
                            W=k+1 for the speculative verify window)
    pool_k  [num_blocks, B, hk, dh]  the physical K pool (one plane);
                            ``h = hk * group`` (rows past ``hk``, which
                            ``pool_rows`` may add, hold zeros)
    pool_v  [num_blocks, B, hk, dh]  the physical V pool
    table   [S, NB] int32   per-slot block chain (block 0 = trash)
    pos     [S, W]  int32   absolute position of each query; key token
                            ``j`` participates iff ``j <= pos`` (the
                            write-before-attend mask), so trash-block
                            garbage, bucket padding and CoW tails all
                            carry exactly zero attention weight
    group   Python int      query heads a K/V head: query head ``i``
                            reads K/V head ``i // group``
    window  Python int | None   the LOWER bound: with a window key ``j``
                            participates iff ``pos - window < j <= pos``
                            (a query sees itself and the ``window - 1``
                            keys before it)
    scale   Python float | None   scores' scale, ``1 / sqrt(dh)`` if None
    ctx     [S, W, h, dh]   in ``out_dtype`` (``q.dtype`` if None)

``group``, ``window``, ``scale`` and ``out_dtype`` are Python constants:
at their defaults a call lowers to the program it always lowered to.  A
K/V group is folded into the window by both backends (``_fold_group``):
the ``group`` query heads of one K/V head become ``group`` window rows
of ``hk`` heads at the same position, so the kernels below see ``h ==
hk`` always.

**A plane whose K and V arrays differ in lanes, and a sink**
(``serving.arch.SinkWindowMoE``): ``pool_v [num_blocks, B, hk, dv]`` may
have other lanes than ``pool_k [.., dk]``; scores run over ``dk``,
``ctx`` is ``[S, W, h, dv]``.  A key of fewer lanes than the K array
stores (192 in ``key_lanes(192) = 256``) is ``attend``'s to pad, query
and all, with the scale taken from the query's own width.  ``sink [h]``
float32 (a Python ``None`` by default: nothing is traced) is one logit a
query head that joins every row's softmax: ``p_j = exp(s_j - m) /
(sum_j exp(s_j - m) + exp(sink - m))``, ``m = max(max_j s_j, sink)``; it
takes mass and adds no value, so the online softmax simply STARTS at it
(maximum ``sink``, sum 1, ``acc`` 0).  A window plane's table may name
the trash block at every entry under its lower bound (an engine that
gave those blocks back: ``kvcache.WindowChains``): no spelling attends
them.

**A latent plane** (``pool_v=None``; ``serving.arch.LatentMoE``) is the
second calling convention of the same functions::

    call(q, pool, None, table, pos, value_lanes=V, scale=, out_dtype=)

    q       [S, W, h, L]    one query row a head, as wide as a cached row
    pool    [num_blocks, B, L]   ONE array a plane, NO head axis: a
                            position holds one row (``write`` puts zeros
                            in the lanes past its values; ``latent_lanes``
                            rounds ``L`` up to the 128-lane tile, which
                            Mosaic needs to slice a block out of the pool)
    ctx     [S, W, h, V]    every head reads the ONE cached row whole;
                            a position's value is its row's first
                            ``value_lanes`` lanes

``pool_rows`` and ``group`` do not apply (a group is refused); a
``window`` is the lower bound it is on a K/V plane.  ``attend`` chooses
as above: dense from ``DENSE_WINDOW`` rows up (under a lower bound: of
the ``window_entries`` the window can see and no more,
``_dense_latent_window``), else the ``xla_ref`` scan or, on a TPU, the
sibling Mosaic kernel ``latent_attention_pallas`` (HLO name
``paged_latent_attention``): the loop below with the head mask gone and
``LATENT_BLOCKS`` table entries an iteration, from the group of the
window's first live entry.  A plane whose rows are SELECTED by an
indexer is ``kernels/sparse_attention.py``'s.  Where a prefix trie hands
several live slots one document's blocks, the host says so
(``shared_runs``: which slots' chains START alike, ``shared=`` beside
the table, data) and the Mosaic kernel fetches such a run ONCE for the
slots that share it; every other spelling ignores it, and the result is
the same.

**A slab plane** (``pool_k [num_blocks, B, dk]`` WITH ``pool_v
[num_blocks, B, dv]``; ``kernels/block_sparse_attention.py``) is the
latent convention with the values in an array of their own: no head axis,
every query head of ``q [S, W, h, dk]`` reads the one slab a table entry
names, ``ctx [S, W, h, dv]``.  It is how a plane stored HEAD-MAJOR
(``[blocks, hk, B, dh]``: ``serving.arch.SparseLightning``'s K/V planes)
is walked one K/V head at a time: ``pool.reshape(blocks * hk, B, dh)`` is
a view in which entry ``table[i] * hk + j`` is head ``j``'s ``[B, dh]``
slab, and the ``group`` query heads of that K/V head are the call's
``h``.  No row is added (``pool_rows`` does not apply), no lane belongs
to another head, a group is refused.  ``attend`` always STREAMS such a
call (its caller makes one table a (row, K/V head), ``W = 1``): the
``xla_ref`` scan or, on a TPU, the latent kernel's loop with a second
buffer for V (``latent_attention_pallas(.., pool_v=)``, HLO name
``paged_slab_attention``) at the table entries an iteration that
``entries_per_iteration`` gives a slab.

Numerics conventions match the flash kernels (f32 scores via
``preferred_element_type``, ``NEG_INF`` masking, f32 ``(m, l, acc)``
online-softmax state, one normalization at the end with the
``l == 0 -> 1`` guard, output cast to the input dtype).  The blocked
reassociation means the two backends differ within
``ORACLE_TOL["paged_attention", ...]``; within one backend the op is
bit-exact run to run.  Token position ``nb*B + b`` of slot ``s`` lives
at ``(table[s, nb], b)`` — block 0 never needs zeroing because its
token positions in an unused table entry are always ``> pos``.

Backends:

* ``xla_ref`` — a ``lax.scan`` over table entries, gathering
  ``block_step`` physical blocks per step (``[S, block_step*B, h,
  dh]`` in flight).  The universal numerics reference.
* ``pallas_tpu`` — ``PrefetchScalarGridSpec`` scalar prefetch of table
  and positions; grid ``(S,)``, one step a slot, whose body loops over
  the LIVE entries of that slot's chain only: from ``f_s = max(min_w
  pos[s, w] - window + 1, 0) // B`` (0 without a window: every entry
  before ``f_s`` lies under every row's lower bound) up to ``n_s =
  clip(max_w pos[s, w] // B + 1, 0, NB)`` (every entry from ``n_s`` on
  is masked for every row); the others are neither fetched nor
  computed, each block
  copied from the pool where it lies into a VMEM buffer (two deep for
  one row, ``DEPTH`` for more) while ``(m, l, acc)`` carry in VMEM
  scratch.  A live block is folded ONCE for all the rows of the window
  (a K/V group's rows among them): their scores are one MXU pass, the
  mask, the running maximum, the ``exp`` and the sums are one update
  over one array that holds the rows on its sublanes, and ``p * v`` is
  a second MXU pass for all of them (``softmax_updates``).  The loop of
  two rows or more takes ``G`` consecutive table entries an iteration
  (``entries_per_iteration``: a rule on the shapes, 8 on a chain of 416
  entries, 1 on a window's handful): their blocks land side by side in
  one buffer and are ONE block of ``G x B`` tokens to the scores, the
  update and the value product, and a group's scores are made while
  the group before it is weighed (``loop_iterations`` states the trip
  count for whoever counts it).  A row
  with ``pos < 0`` has no visible key and returns zeros; a slot of such
  rows (a dead slot, as ``batched_decode._Cache`` names it) costs one
  empty grid step.  Registered available on real TPU only (off-TPU the
  interpret-mode grid would replace one fused XLA loop with a per-block
  Python loop); the oracle suite still covers the kernel logic on CPU by
  forcing ``interpret=True``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas_attention import LSE_LANES
from .registry import register_kernel, resolve
from .xla_ref import NEG_INF

__all__ = ["attend", "CHAIN_SCORE_BYTES", "DENSE_WINDOW", "DENSE_SCORE_BYTES",
           "dense_entries", "dense_window", "key_lanes", "walks_chain",
           "window_entries",
           "entries_per_iteration", "latent_attention_pallas", "latent_lanes",
           "loop_iterations", "paged_attention_ref", "paged_attention_pallas",
           "pool_rows", "shared_runs", "softmax_updates", "write"]

# From this window width up a window gathers its slot's chain once and
# attends it densely instead of streaming blocks.  W rows then share one
# read of K and V and both products are wide MXU matmuls, where the
# streaming kernels make two small ones a block; and the Mosaic kernel
# cannot run wide at all (18.6 MB of scoped VMEM at W = 64; PERF.md,
# PR 26).
DENSE_WINDOW = 8

# From this many bytes of dense float32 scores up (``walks_chain``:
# folded rows x the chain's positions x 4) a wide window over a K/V
# plane WALKS its chain in tiles instead (``kernels/chain_attention.py``:
# the Mosaic flash walk on a TPU; off it the op class resolves to the
# dense spelling, so nothing changes there).  The chip's VMEM: while the
# scores fit it the one dense step is the fastest spelling, past it they
# go through HBM.  Measured alone on the chip, one layer's attention of
# a piece at 1,500 positions of a 2,048-position chain, dense | walk in
# us (my chip runs, PR 47; benchmarks/paged_walk.py --only rungs,
# RESULTS.md): chat_moe's planes (8 K/V rows x group 6) 128 rows, 48 MiB:
# 82 | 86; 256 rows, 96 MiB: 153 | 157; 512 rows, 192 MiB: 913 | 315;
# think_decode's (10 K/V rows in 16 pool rows x group 4) 128 rows, 64
# MiB: 101 | 86 full, 46 window; 256 rows, 128 MiB: 636 | 131; 512 rows,
# 256 MiB: 1,198 | 237.  mimo25.long_reason's 512-row pieces over 13,312
# positions are 3.5 GB: 7,430 | 390 to 1,650 by context.
CHAIN_SCORE_BYTES = 128 << 20

# The dense spelling (``dense_window``: what the chip ran before the
# walk, and what a program off the TPU runs still) makes float32 scores
# ``[S, W x group, rows, NB x B]`` over the whole chain (the pool's
# rows, those ``pool_rows`` added among them).  Past this many bytes it
# goes one K/V head at a time over the heads the plane really has, and a
# plane with a lower bound gathers the entries its window can see and no
# others (``_dense_by_head``).  Read at trace time, like ``DENSE_WINDOW``.
DENSE_SCORE_BYTES = 1 << 30

# Buffers the Mosaic loop of two rows or more keeps in VMEM, each a group
# of ``entries_per_iteration`` blocks: the one whose values are weighed,
# the one whose scores are made, and two on their way from the pool.
# Measured alone on the chip at four rows a block and one block a buffer
# (think_decode's full plane; benchmarks/paged_walk.py, PERF.md PR 35):
# two buffers 0.70 us a live block (a copy's latency, not its bytes),
# three or four 0.62, four with the scores a block ahead 0.41, six 0.42.
# Read again at the groups PR 52 gave the buffers (0.40 at that plane's
# two entries; long_reason's full plane 0.466 at one entry a buffer, 0.304
# at eight: ``entries_per_iteration`` has the table): what more copies in
# flight did not buy, more entries a copy's wait did.
DEPTH = 4

# The loop of two rows or more takes up to MAX_ENTRIES table entries an
# iteration (``entries_per_iteration``, which has the chip's table): a
# group no more than one GROUP_SHARE-th of the entries a slot's chain can
# have live, within LOOP_VMEM_BYTES of Mosaic's default scoped 16 MiB.
MAX_ENTRIES = 8
GROUP_SHARE = 32
LOOP_VMEM_BYTES = 12 << 20
# What the lightest block of ``entries_per_iteration``'s table holds (K
# and V of 32 tokens x 8 rows x 128 lanes in bfloat16).  A SLAB, one
# head's rows alone, holds a fraction of it, and the rule counts slabs in
# entries of this size.
ENTRY_BYTES = 128 << 10

# Table entries of a LATENT plane the Mosaic loop takes in one iteration
# (``latent_attention_pallas``).  An iteration costs its chain's latency
# whatever it holds; measured alone on the chip at doc_qa_8k's geometry
# (16 rows a block of 32 x 640 lanes, chains of 270 live blocks;
# benchmarks/paged_walk.py, PERF.md PR 40): one entry 0.264 us a live
# block, two 0.147, four 0.092, eight 0.065, sixteen 0.063 (the bytes
# alone: 0.045).  Eight: sixteen gains 3% and fetches up to fifteen
# entries past a chain's end where eight fetch seven.
LATENT_BLOCKS = 8

# Query rows ONE walk of the latent kernel stacks where several slots
# share a run of table entries (``shared_runs``): the slots' rows go
# against one buffer in the same two MXU passes, and the float32 scores,
# the three pieces of ``p`` and the weighed values of a stack are VMEM
# temporaries (at 256 rows of 512 value lanes some 4 MB).
STACK_ROWS = 256


def attend(q, pool_k, pool_v, table, pos, group=1, window=None, scale=None,
           out_dtype=None, value_lanes=None, sink=None, shared=None):
    """One layer's attention THROUGH the block table, the one call the
    serving step makes: ``q [S, W, h, dh]``, ``pos [S, W]`` ->
    ``[S, W, h, dh]``; ``group``, ``window``, ``scale`` and
    ``out_dtype`` as the module docstring has them.  With ``pool_v``
    ``None`` the plane is a LATENT one (module docstring): ``pool_k
    [blocks, B, L]``, ``q [S, W, h, L]`` -> ``[S, W, h, value_lanes]``.

    The spelling follows the call's shapes and the platform, both seen
    at trace time (module docstring): under ``DENSE_WINDOW`` rows the
    window streams blocks with online softmax through the backend the
    registry resolves for ``paged_attention`` (the Mosaic loop starts at
    the window's first block); from there up it is the ``xla_ref``
    spelling with ONE step over the whole chain (``dense_window``; a
    lower bound is a mask there); and a K/V plane whose dense scores
    would reach ``CHAIN_SCORE_BYTES`` (``walks_chain``) walks its chain
    in tiles through the ``chain_attention`` op class, which off the TPU
    is that same dense step.

    A K array may store MORE lanes than a key has (``key_lanes``: a key
    of 192 lanes in 256) and a V array other lanes than the K array: the
    query is padded with zeros to the K array's lanes, scores are scaled
    by the query's OWN width unless ``scale`` says otherwise, and the
    context has the V array's lanes.  ``sink [h]`` float32 is one more
    logit a query head in every row's softmax, which takes mass and adds
    no value (module docstring).  ``shared`` (``shared_runs``'s array, or
    ``None``: nothing is traced) tells a LATENT plane's streaming call
    which slots' chains start alike; whatever it says, the result is the
    call's without it."""
    how = dict(group=group, window=window, scale=scale, out_dtype=out_dtype)
    if pool_v is None:
        how["value_lanes"] = value_lanes
        if shared is not None and q.shape[1] < DENSE_WINDOW:
            how["shared"] = shared
    elif q.shape[-1] < pool_k.shape[-1]:
        if scale is None:
            how["scale"] = 1.0 / float(q.shape[-1]) ** 0.5
        q = jnp.pad(q, ((0, 0),) * 3
                    + ((0, pool_k.shape[-1] - q.shape[-1]),))
    if sink is not None:
        how["sink"] = sink
    if pool_v is not None and pool_k.ndim == 3:
        # a slab plane (module docstring): one table a (row, K/V head)
        return resolve("paged_attention").impl.call(
            q, pool_k, pool_v, table, pos, **how)
    if pool_v is not None and walks_chain(
            q.shape[1], _folded_rows(q, pool_k, group),
            table.shape[1] * pool_k.shape[1]):
        return resolve("chain_attention").impl.call(
            q, pool_k, pool_v, table, pos, **how)
    if q.shape[1] >= DENSE_WINDOW:
        return dense_window(q, pool_k, pool_v, table, pos, **how)
    return resolve("paged_attention").impl.call(q, pool_k, pool_v, table,
                                                pos, **how)


def dense_window(q, pool_k, pool_v, table, pos, block_step=None,
                 interpret=None, **how):
    """The dense spelling of a wide window: the ``xla_ref`` paged
    spelling with ONE step over the whole chain or, for a K/V plane
    whose float32 scores would pass ``DENSE_SCORE_BYTES``, one K/V head
    at a time (``_dense_by_head``).  Also the ``xla_ref`` backend of the
    ``chain_attention`` op class (``kernels/chain_attention.py``), whose
    signature it keeps: ``block_step`` and ``interpret`` are ignored."""
    del block_step, interpret
    if pool_v is None and how.get("window") is not None:
        return _dense_latent_window(q, pool_k, table, pos, **how)
    if pool_v is not None and _score_bytes(
            q.shape[1], _folded_rows(q, pool_k, how.get("group", 1)),
            table.shape[1] * pool_k.shape[1]) > DENSE_SCORE_BYTES:
        return _dense_by_head(q, pool_k, pool_v, table, pos, **how)
    return resolve("paged_attention", backend="xla_ref").impl.call(
        q, pool_k, pool_v, table, pos, block_step=table.shape[1], **how)


def pool_rows(heads, dtype):
    """Rows a pool block needs on its head axis for ``heads`` K/V heads
    so that the Mosaic kernel can slice a block out of the pool where it
    lies (``_block_is_sliceable``) and run its loop form: ``heads``, or
    the next multiple of 8 for a packed dtype.  The rows added hold
    zeros, which ``write`` puts there, and their scores attend zeros."""
    if jnp.dtype(dtype).itemsize >= 4 or heads % 8 == 0:
        return heads
    return -(-heads // 8) * 8


def latent_lanes(values):
    """Lanes a LATENT plane stores of a position that holds ``values``
    values: the next multiple of 128.  The device tiles an array's minor
    axis in 128 lanes, so a row of 576 occupies 640 in HBM whatever its
    logical shape says; stating the 640 keeps every slice the kernel
    makes on a tile's edge, and ``write`` puts zeros in the lanes past
    the values (a zero lane adds nothing to a score)."""
    return -(-int(values) // 128) * 128


def key_lanes(lanes):
    """Lanes a K array stores of a key of ``lanes`` lanes: the next
    multiple of the 128-lane tile (a key of 192 occupies 256 whatever the
    logical shape says, and Mosaic refuses to slice part of a tile:
    PERF.md, PR 40).  ``write`` puts zeros in the lanes past the key and
    ``attend`` pads the query to match, so a spare lane adds nothing to a
    score."""
    return latent_lanes(lanes)


def softmax_updates(rows):
    """Online-softmax updates the Mosaic kernel makes for ONE live block
    that ``rows`` query rows attend (the window's rows, a K/V group
    folded in): one, however many the rows, because they sit on the
    sublanes of one array and share the mask, the maximum, the ``exp``
    and the sums.  Stated here, as a function of the folded
    width, for whoever counts the kernel's work
    (``serving.paged_updates_live``): the engine does not guess."""
    if rows < 1:
        raise ValueError(f"paged_attention: {rows} rows a block")
    return 1


def entries_per_iteration(B, h, dh, dv, N, dtype, live):
    """Table entries ONE iteration of the Mosaic loop of two folded rows
    or more takes (``paged_attention_pallas``'s ``shared_loop_kernel``),
    from the shapes the kernel sees at trace time and from nothing else:
    the largest power of two up to ``MAX_ENTRIES`` that is no more than
    ``live // GROUP_SHARE`` (``live`` the table entries a slot's chain
    can have live in one call: the table's ``NB``, or under a lower
    bound its ``window_entries``) and whose VMEM (``_loop_vmem_bytes``)
    fits ``LOOP_VMEM_BYTES``; 1 where that leaves nothing.  ONE
    algorithm that wants another parameter by geometry.

    Why a share of the chain.  An iteration costs its chain of latencies
    (copy, MXU, lane reduction, ``exp``, MXU) whatever it holds, so G
    entries an iteration divide that; but a slot pays up to ``G - 1``
    entries past its chain's end, scored and weighed under a zero weight,
    and its first group's copies with nothing to run under them, once
    each whatever its length.  Alone on the chip, us a live block at G =
    1 | 2 | 4 | 8 (bfloat16 pools, blocks of 32 tokens;
    benchmarks/paged_walk.py --entries, seed 33, my chip run, PR 52;
    RESULTS.md; a slot's live blocks in brackets):

    =====================================================  =====  =====  =====  =====
    long_reason_full: 4 heads in 8 rows, 16 query rows a
    K/V row, K 256 lanes, V 128, NB 416 [205]              0.466  0.338  0.312  0.304
    the same at shorter chains [49]                        0.488  0.370  0.357  0.374
    long_reason_window: 8 heads, 8 rows, window 128, the
    sink, 5 entries at most [4.9]; us a call                31.6   32.5   40.7   39.7
    think_decode full: 10 heads in 16 rows, 4 rows, NB 64
    [36]                                                   0.402  0.397  0.420  0.448
    think_decode window 512: 17 entries at most [16.6]     0.458  0.472  0.543  0.652
    chat_moe: 8 heads, 6 rows, NB 64 [35] (window 4096
    alike)                                                 0.351  0.249  0.259  0.256
    chat_ssm: 2 heads in 8 rows, 16 rows, NB 80 [49]       0.510  0.316  0.296  0.301
    =====================================================  =====  =====  =====  =====

    The full plane's two rows together: a call costs 1.4 us a live slot
    and 0.459 a block at G = 1, 4.5 and 0.282 at G = 8 (0.24 is its
    stored bytes at the HBM peak).  Where the work of a block already
    costs what its chain does (think_decode's 262 KB and 512 score lanes
    a block) or a slot has a handful of live blocks, a group only adds
    its tail; the rule gives 8 | 8 | 1 | 2 | 1 | 2 | 2, the best of each
    row but the second and the last (G = 4 there is 5% and 6% faster).

    A SLAB (``h`` ``None``: one K/V head's ``[B, dh]`` of K and ``[B, dv]``
    of V with NO head axis, the walk of a head-major plane) is a fetch of a
    fraction of those blocks' bytes for the same chain of latencies: 32
    KB at 64 tokens of 128 lanes, where the lightest row of the table
    holds 128 KB (``ENTRY_BYTES``).  The rule counts slabs in entries of
    that size, in the cap and in the share of the chain alike: ``light =
    ENTRY_BYTES // slab bytes`` (a power of two, 1 at least) slabs weigh
    as one entry, so a table of 97 selected blocks takes 8 slabs an
    iteration (2 by the count of entries alone: 0.3 us of latency a slab
    where its bytes are 0.04) and a chain of 128 takes 16.  Every plane
    with a head axis has ``light = 1``: the table above is what it was."""
    light = 1
    if h is None:
        h = 1
        light = max(1, ENTRY_BYTES // (
            B * (dh + dv) * jnp.dtype(dtype).itemsize))
        light = 1 << (light.bit_length() - 1)
    G = MAX_ENTRIES * light
    while G > 1 and (G * GROUP_SHARE > live * light or _loop_vmem_bytes(
            G, B, h, dh, dv, N, dtype) > LOOP_VMEM_BYTES):
        G //= 2
    return G


def _loop_vmem_bytes(G, B, h, dh, dv, N, dtype):
    """VMEM the loop of two rows or more holds at ``G`` entries an
    iteration: the K and V buffers, and a ``[N, G * B * h]`` array of
    scores four times in float32 (the scratch the next group's wait in,
    the scores being made, ``s`` and ``p`` of the update) and as
    ``_weigh``'s three bfloat16 pieces (a float32 pool: V as float32
    instead)."""
    item = jnp.dtype(dtype).itemsize
    lanes = G * B * h
    buffers = DEPTH * lanes * (dh + dv) * item
    weigh = 6 * N * lanes if item < 4 else 4 * lanes * dv
    return buffers + 16 * N * lanes + weigh


def loop_iterations(entries, rows, block_shapes, dtype, NB, window=None):
    """Iterations the Mosaic loop makes over ``entries`` live table
    entries of ONE slot's chain in one decode call (from the plane's
    first live entry: a group starts there) that sends ``rows`` query
    rows through each: ``ceil(entries / G)`` at the ``G`` of
    ``entries_per_iteration`` for two rows or more of a K/V plane whose
    pool Mosaic slices; ``entries`` for one row and for the grid form
    (an entry an iteration, a step); a latent plane's groups of
    ``LATENT_BLOCKS``; a slab plane's groups of the same rule at one head.
    ``block_shapes`` as the kernel sees a plane's blocks: ``(K, V)`` block
    shapes ``[B, h, lanes]`` (what ``plane_block_shapes`` states; ``h``
    ``None`` for the slabs of a plane stored head-major and walked a head
    at a time), or block shapes ``[B, lanes]`` with no head axis for a
    latent plane; ``NB`` the table's
    entries a slot, ``window`` the plane's lower bound.  Stated here for
    whoever counts the kernel's iterations
    (``serving.paged_iterations_live``): the engine does not guess."""
    if len(block_shapes[0]) == 2:
        G = min(LATENT_BLOCKS, NB)
    else:
        (B, h, dh), (_, _, dv) = block_shapes
        if h is None:
            G = min(NB, entries_per_iteration(
                B, None, dh, dv, rows, dtype,
                window_entries(NB, B, 1, window)))
            return -(-entries // G)
        if rows == 1 or not _rows_are_sliceable(h, dtype):
            return entries
        G = entries_per_iteration(B, h, dh, dv, rows * h, dtype,
                                  window_entries(NB, B, 1, window))
    return -(-entries // G)


def walks_chain(width, rows, positions):
    """Whether ``attend`` sends a window of ``width`` positions over a
    K/V plane to the ``chain_attention`` op class: ``rows`` the folded
    query rows of ONE position (slots x the K/V group x the pool's rows,
    those ``pool_rows`` added among them: what the dense step scores),
    ``positions`` the chain's capacity (``NB x B``).  Stated here for
    whoever counts what a piece attends
    (``ServingEngine._count_prefill_entries``): the engine does not
    guess."""
    return (width >= DENSE_WINDOW
            and _score_bytes(width, rows, positions) >= CHAIN_SCORE_BYTES)


def window_entries(entries, block, width, window):
    """Table entries that hold a key some row of a window of ``width``
    ascending rows may attend: the chain's ``entries``, or with a lower
    bound those of its ``window + width - 1`` positions (a block more at
    either end)."""
    if window is None:
        return entries
    return min(entries, (width + window - 2) // block + 2)


def dense_entries(width, rows, entries, block, window):
    """Table entries the DENSE spelling of a window gathers and scores
    (``dense_window``): the whole chain whatever the context, or, one
    K/V head at a time (past ``DENSE_SCORE_BYTES``), a lower bound's own
    entries.  ``rows`` as ``walks_chain`` takes them.  What a chain walk
    is measured against (``ServingEngine._count_prefill_entries``)."""
    if _score_bytes(width, rows, entries * block) > DENSE_SCORE_BYTES:
        return window_entries(entries, block, width, window)
    return entries


def _fold_group(q, pos, group, rows):
    """``q [S, W, hk * group, dh]`` -> ``[S, W * group, rows, dh]``, ``pos``
    repeated to match, and the inverse to apply to the context: query
    head ``i`` reads K/V head ``i // group``, so the ``group`` heads of
    one K/V head are ``group`` window rows at the same position (heads
    past ``hk``, where the pool has more ``rows``, are zeros).  With one
    head a K/V head and no row to spare nothing is traced."""
    S, W, h, dh = q.shape
    hk = h // group
    if hk * group != h or hk > rows:
        raise ValueError(f"paged_attention: {h} query heads in groups of "
                         f"{group} over a pool of {rows} K/V rows")
    if group > 1:
        q = q.reshape(S, W, hk, group, dh).transpose(0, 1, 3, 2, 4)
        q = q.reshape(S, W * group, hk, dh)
        pos = jnp.repeat(pos, group, axis=1)
    if rows > hk:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, rows - hk), (0, 0)))

    def unfold(ctx):
        if rows > hk:
            ctx = ctx[:, :, :hk]
        if group > 1:
            ctx = ctx.reshape(S, W, group, hk, ctx.shape[-1])
            ctx = ctx.transpose(0, 1, 3, 2, 4).reshape(S, W, h, -1)
        return ctx

    return q, pos, unfold


def _fold_sink(sink, group, rows, W):
    """``sink [hk * group]`` as ``_fold_group`` folds the heads it
    belongs to: ``[W * group, rows]`` float32, row ``w * group + g`` of
    K/V head ``j`` is query head ``j * group + g`` (zeros for the rows
    past ``hk``, whose queries are zeros too)."""
    sk = sink.astype(jnp.float32).reshape(-1, group).T          # [group, hk]
    if rows > sk.shape[1]:
        sk = jnp.pad(sk, ((0, 0), (0, rows - sk.shape[1])))
    return jnp.tile(sk, (W, 1))


def _score_bytes(width, rows, positions):
    """Bytes of the float32 scores the one-step dense spelling makes of
    a window of ``width`` positions, ``rows`` folded query rows each,
    over a chain of ``positions``."""
    return 4 * width * rows * positions


def _folded_rows(q, pool_k, group):
    """The folded query rows of ONE position of a call, as the dense
    step scores them: slots x the K/V group x the pool's rows."""
    return q.shape[0] * group * pool_k.shape[2]


def _dense_by_head(q, pool_k, pool_v, table, pos, group=1, window=None,
                   scale=None, out_dtype=None, sink=None):
    """The dense spelling for a window whose scores over the whole chain
    would not fit (``DENSE_SCORE_BYTES``): the same masked softmax, ONE
    K/V head at a time (``lax.map``: a head's scores ``[S, W, group,
    T]`` are made, weighed and dropped before the next head's) over the
    ``hk = h / group`` heads the plane has, not the rows ``pool_rows``
    padded it to.  A plane with a lower bound gathers, a slot, the
    ``(W + window - 2) // B + 2`` table entries that hold a key some row
    of the window can see (from the entry of ``pos[s, 0] - window + 1``:
    the rows of a window ascend) and no others: the entries under it may
    name blocks given back long ago."""
    S, W, h, dk = q.shape
    B, NB, dv = pool_k.shape[1], table.shape[1], pool_v.shape[-1]
    hk = h // group
    if hk * group != h or hk > pool_k.shape[2]:
        raise ValueError(f"paged_attention: {h} query heads in groups of "
                         f"{group} over a pool of {pool_k.shape[2]} K/V rows")
    out_dtype = q.dtype if out_dtype is None else out_dtype
    if scale is None:
        scale = 1.0 / float(dk) ** 0.5
    tbl, n = table.astype(jnp.int32), NB
    first = jnp.zeros((S,), jnp.int32)
    if window is not None:
        n = window_entries(NB, B, W, window)
        first = jnp.clip(jnp.maximum(pos[:, 0] - window + 1, 0) // B,
                         0, NB - n)
        tbl = jax.vmap(lambda row, f: jax.lax.dynamic_slice_in_dim(
            row, f, n))(tbl, first)
    T = n * B
    kb = pool_k[tbl][:, :, :, :hk].reshape(S, T, hk, dk)
    vb = pool_v[tbl][:, :, :, :hk].reshape(S, T, hk, dv)
    tok = first[:, None] * B + jnp.arange(T, dtype=jnp.int32)[None]  # [S, T]
    keep = tok[:, None, :] <= pos[:, :, None]                  # [S, W, T]
    if window is not None:
        keep &= tok[:, None, :] > pos[:, :, None] - window
    sk = (jnp.zeros((hk, group), jnp.float32) if sink is None
          else sink.astype(jnp.float32).reshape(hk, group))

    def one(head):
        qh, kh, vh, sh = head         # [S, W, g, dk], [S, T, dk | dv], [g]
        s = jnp.einsum("swgd,std->swgt", qh, kh,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(keep[:, :, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        if sink is not None:
            m = jnp.maximum(m, sh)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        if sink is not None:
            l = l + jnp.exp(sh - m)
        ctx = jnp.einsum("swgt,std->swgd", p, vh.astype(jnp.float32))
        return ctx / jnp.where(l == 0.0, 1.0, l)[..., None]

    ctx = jax.lax.map(one, (
        jnp.moveaxis(q.reshape(S, W, hk, group, dk), 2, 0),
        jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0), sk))
    return jnp.moveaxis(ctx, 0, 2).reshape(S, W, h, dv).astype(out_dtype)


def _dense_latent_window(q, pool, table, pos, window, value_lanes, scale=None,
                         out_dtype=None, group=1):
    """The dense spelling of a wide window over a LATENT plane under a
    lower bound: a slot gathers the ``window_entries`` table entries that
    hold a key some row of the window can see (``_dense_by_head``'s
    slice: from the entry of ``pos[s, 0] - window + 1``; the entries
    under it may name blocks given back long ago) and no more, and every
    head reads the gathered rows whole: scores ``[S, W, h, entries x
    B]``, never the chain's."""
    dv = _latent_plane(q, pool, group, value_lanes)
    S, W, h, L = q.shape
    B, NB = pool.shape[1], table.shape[1]
    out_dtype = q.dtype if out_dtype is None else out_dtype
    if scale is None:
        scale = 1.0 / float(L) ** 0.5
    n = window_entries(NB, B, W, window)
    first = jnp.clip(jnp.maximum(pos[:, 0] - window + 1, 0) // B, 0, NB - n)
    tbl = jax.vmap(lambda row, f: jax.lax.dynamic_slice_in_dim(row, f, n))(
        table.astype(jnp.int32), first)
    rows = pool[tbl].reshape(S, n * B, L)
    tok = first[:, None] * B + jnp.arange(n * B, dtype=jnp.int32)[None]
    keep = ((tok[:, None, :] <= pos[:, :, None])
            & (tok[:, None, :] > pos[:, :, None] - window))[:, :, None, :]
    s = jnp.einsum("swhd,std->swht", q, rows,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(keep, s, NEG_INF)
    p = jnp.where(keep, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    l = jnp.sum(p, axis=-1)
    ctx = jnp.einsum("swht,std->swhd", p, rows[..., :dv].astype(jnp.float32))
    return (ctx / jnp.where(l == 0.0, 1.0, l)[..., None]).astype(out_dtype)


def _normalize_block_step(block_step, nb, w=1):
    if block_step is None:
        # measured default: single-token decode (W=1) is fastest
        # streaming one block per step, where the memory win also lives;
        # multi-token windows (the speculative verify, W=k+1) pay the
        # scan's sequential dispatch W times over and win by consuming
        # the whole chain in one wide step instead
        block_step = 1 if w == 1 else nb
    return max(1, min(int(block_step), nb))


# -- xla_ref: the block-scan oracle ------------------------------------------

def _latent_plane(q, pool, group, value_lanes, pool_v=None):
    """The checks of a call on a plane with no head axis: a latent one
    (``pool_v is None``) or a slab plane (the latent convention with its
    values ``pool_v [blocks, B, dv]``); returns the value lanes."""
    if pool.ndim != 3 or q.shape[-1] != pool.shape[-1]:
        raise ValueError(
            f"paged_attention: a latent plane is [blocks, B, L] and its "
            f"queries [S, W, h, L]; got {pool.shape} and {q.shape}")
    if group != 1:
        raise ValueError("paged_attention: a latent plane has one row all "
                         "the heads read whole: no group")
    if pool_v is not None:
        if pool_v.ndim != 3 or pool_v.shape[:2] != pool.shape[:2]:
            raise ValueError(
                f"paged_attention: a slab plane's V array is [blocks, B, "
                f"dv] beside K's {pool.shape}; got {pool_v.shape}")
        return pool_v.shape[-1]
    if not value_lanes or not 0 < value_lanes <= pool.shape[-1]:
        raise ValueError(f"paged_attention: value_lanes {value_lanes} of a "
                         f"latent row of {pool.shape[-1]} lanes")
    return int(value_lanes)


def paged_attention_ref(q, pool_k, pool_v, table, pos, block_step=None,
                        interpret=None, group=1, window=None, scale=None,
                        out_dtype=None, value_lanes=None, sink=None,
                        shared=None):
    """The oracle spelling: ``lax.scan`` over the block chain with
    online-softmax carry — per step only ``block_step`` physical blocks
    are gathered (``[S, block_step*B, h, dh]``), never the ``T``-wide
    view.  A lower bound (``window``) is one more term of the mask.
    ``interpret`` is accepted for signature parity and ignored (no
    Pallas here).  A latent plane (``pool_v is None``) is the same lines
    with one cached row for all the heads, its values that row's first
    ``value_lanes`` lanes.  A ``sink`` is where the online softmax
    STARTS: a row's maximum at its head's sink logit and its sum at 1,
    as if it had seen one key with that score and a zero value.
    ``shared`` says what a kernel may fetch once and changes no result:
    ignored here, where every slot gathers its own chain.  A slab plane
    (``pool_k`` and ``pool_v`` with no head axis) is the latent lines
    with the values gathered from their own array."""
    del interpret, shared
    latent = pool_k.ndim == 3
    if sink is not None:
        if latent:
            raise ValueError("paged_attention: a latent plane has no sink"
                             if pool_v is None else
                             "paged_attention: a slab plane has no sink")
        sink = _fold_sink(sink, group, pool_k.shape[2], q.shape[1])[None]
    if latent:
        dv = _latent_plane(q, pool_k, group, value_lanes, pool_v)
        unfold = lambda ctx: ctx
        qk, pv = "swhd,std->swht", "swht,std->swhd"

        def gather(blk, n):
            kb = pool_k[blk].reshape(S, n * B, dh)
            if pool_v is None:
                return kb, kb[..., :dv]
            return kb, pool_v[blk].reshape(S, n * B, dv)
    else:
        q, pos, unfold = _fold_group(q, pos, group, pool_k.shape[2])
        qk, pv, dv = "swhd,sthd->swht", "swht,sthd->swhd", pool_v.shape[-1]

        def gather(blk, n):
            return (pool_k[blk].reshape(S, n * B, h, dh),
                    pool_v[blk].reshape(S, n * B, h, dv))
    out_dtype = q.dtype if out_dtype is None else out_dtype
    S, W, h, dh = q.shape
    B = pool_k.shape[1]
    NB = table.shape[1]
    T = NB * B
    bs = _normalize_block_step(block_step, NB, W)
    pad = (-NB) % bs
    tbl = table.astype(jnp.int32)
    if pad:
        # pad the chain with trash-block entries; their token positions
        # (>= T) are unconditionally masked below
        tbl = jnp.concatenate(
            [tbl, jnp.zeros((S, pad), jnp.int32)], axis=1)
    if scale is None:
        scale = 1.0 / float(dh) ** 0.5
    off = jnp.arange(bs * B, dtype=jnp.int32)

    def visible(tok):
        """``[S, W, 1, n]``: which of the keys at positions ``tok [n]``
        each row attends."""
        keep = ((tok[None, None, None, :] <= pos[:, :, None, None])
                & (tok < T)[None, None, None, :])
        if window is not None:
            keep &= tok[None, None, None, :] > (pos[:, :, None, None]
                                                - window)
        return keep

    def done(ctx):
        return unfold(ctx.astype(out_dtype))

    if (NB + pad) // bs == 1:
        # one step consumes the whole chain: skip the scan and its
        # renormalization carry — a single masked softmax over the
        # one gathered [S, bs*B, h, dh] group (same NEG_INF masking,
        # same l==0 guard; this is what the scan would compute, minus
        # the dead alpha/acc-renorm work of a length-1 carry)
        kb, vb = gather(tbl, NB + pad)
        s = jnp.einsum(qk, q, kb,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(visible(off), s, NEG_INF)
        if sink is None:
            p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            l = jnp.sum(p, axis=-1)
        else:
            m = jnp.maximum(jnp.max(s, axis=-1), sink)
            p = jnp.exp(s - m[..., None])
            l = jnp.sum(p, axis=-1) + jnp.exp(sink - m)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        ctx = jnp.einsum(pv, p, vb.astype(jnp.float32))
        return done(ctx / l_safe[..., None])

    def step(carry, i):
        m, l, acc = carry
        blk = jax.lax.dynamic_slice_in_dim(tbl, i * bs, bs, 1)  # [S, bs]
        kb, vb = gather(blk, bs)
        tok = i * (bs * B) + off                                # [bs*B]
        s = jnp.einsum(qk, q, kb,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(visible(tok), s, NEG_INF)
        m2 = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m2)
        p = jnp.exp(s - m2[..., None])
        l2 = l * alpha + jnp.sum(p, axis=-1)
        acc2 = acc * alpha[..., None] + jnp.einsum(
            pv, p, vb.astype(jnp.float32))
        return (m2, l2, acc2), None

    m0 = jnp.full((S, W, h), NEG_INF, jnp.float32)
    l0 = jnp.zeros((S, W, h), jnp.float32)
    if sink is not None:
        m0, l0 = jnp.broadcast_to(sink, (S, W, h)), jnp.ones_like(l0)
    a0 = jnp.zeros((S, W, h, dv), jnp.float32)
    nsteps = (NB + pad) // bs
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), jnp.arange(nsteps, dtype=jnp.int32))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return done(acc / l_safe[..., None])


# -- pallas_tpu: scalar-prefetch block streaming -----------------------------


def _block_is_sliceable(pool):
    """Whether Mosaic lets a kernel slice one ``[B, h, dh]`` block out of
    the pool where it lies (``memref_slice`` on an un-blocked operand).
    It refuses a packed (sub-32-bit) pool whose head count does not fill
    its sublane tiles: "Slice shape along dimension 2 must be aligned to
    tiling (8), but is 12" (bf16, 12 heads; 6 and 20 alike)."""
    return _rows_are_sliceable(pool.shape[2], pool.dtype)


def _rows_are_sliceable(rows, dtype):
    return jnp.dtype(dtype).itemsize >= 4 or rows % 8 == 0


def _matmul(a, b, dims):
    """``a x b`` contracting ``dims`` on the MXU (inside a Mosaic
    kernel): f32 out of exact products (``HIGHEST`` where an operand is
    float32)."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                   else None))


def _weigh(p, vb):
    """``p [N, tokens] x vb [tokens, dv]`` in ONE MXU pass at the
    float32 accuracy of ``p``.  A bfloat16 pool takes ``p`` as the three
    bfloat16 pieces that sum to it exactly (8 + 8 + 8 bits of its 24),
    stacked on the row axis: their products with ``vb`` are exact in
    f32, so nothing is rounded that the VPU kept."""
    f32 = jnp.float32
    if vb.dtype != jnp.bfloat16:
        return _matmul(p, vb.astype(f32), ((1,), (0,)))
    N = p.shape[0]
    pieces = []
    for _ in range(3):
        pieces.append(p.astype(jnp.bfloat16))
        p = p - pieces[-1].astype(f32)
    out = _matmul(jnp.concatenate(pieces, axis=0), vb, ((1,), (0,)))
    return out[:N] + out[N:2 * N] + out[2 * N:]


def paged_attention_pallas(q, pool_k, pool_v, table, pos, block_step=None,
                           interpret=None, group=1, window=None, scale=None,
                           out_dtype=None, value_lanes=None, sink=None,
                           shared=None):
    """The Mosaic kernel: it visits the LIVE entries of each slot's chain
    and no others.  The block TABLE and the query POSITIONS are the
    scalar-prefetch arguments (SMEM).  Slot ``s`` has ``n_s = clip(max_w
    pos[s, w] // B + 1, 0, NB)`` table entries that hold a key some row
    of its window may attend; every entry from ``n_s`` on is masked for
    every row, so it is neither fetched nor computed.  A masked block
    adds ``p = 0`` and scales by ``alpha = exp(m - m) = 1``: leaving it
    out changes no bit of a row with at least one visible key.  A row
    with ``pos < 0`` has none and returns ZEROS (``l == 0 -> 1`` over an
    ``acc`` of zeros, beside rows that do see keys too: it weighs every
    key zero); a slot whose rows are all negative costs one empty
    grid step.  That is how the serving step names a dead slot
    (``batched_decode._Cache``).  With a ``window`` the chain
    also has a FIRST live entry, ``f_s = max(min_w pos[s, w] - window +
    1, 0) // B``: every key of an entry before it lies under every row's
    lower bound, so the loop starts there and the entries before it are
    neither fetched nor computed either (in the grid form: skipped, with
    the index map clamped to ``[f_s, n_s)`` so that nothing is fetched).

    Grid ``(S,)``, one step a slot, whose body LOOPS over ``[f_s, n_s)`` of
    the slot's own chain: the pools stay where they are (``pl.ANY``: no
    BlockSpec, no pool-sized copy), iteration ``i`` copies block
    ``table[s, i]`` of K and of V into one half of a two-deep VMEM buffer
    (``make_async_copy``; block ``i + 1`` is in flight while block ``i``
    is attended; ``DEPTH`` deep with ``DEPTH - 1`` in flight for two
    rows or more, below) and folds it into the f32 ``(m, l, acc)``
    online softmax in VMEM scratch; the output writes once after the
    loop.

    Where Mosaic cannot slice a block out of the pool
    (``_block_is_sliceable``: bf16 with 6 or 12 heads) the same body runs
    under a grid ``(S, NB)`` instead: the table feeds the K/V BlockSpec
    index maps, clamped to the chain's last live entry so that the steps
    past it re-name one block and fetch nothing, and the body is skipped
    from ``n_s`` on.  Such a step still costs its share of the grid's
    pipeline, which the loop does not pay.

    The block stays in the pool's own ``[B, h, dh]`` layout (tokens on
    the untiled axis, heads on sublanes, ``dh`` on lanes), and it is
    folded ONCE for all ``W`` rows of the window (the rows a K/V group
    folded in among them), BOTH products on the MXU.  Scores are one
    pass, ``[N, dh] x [B * h, dh]^T`` with ``N = W * h`` (f32 out of
    exact products; ``HIGHEST`` for a float32 pool): row ``w`` of head
    ``j`` is sublane ``w * h + j``, token ``t`` of head ``j'`` is lane
    ``t * h + j'``, the flash kernels' layout with every lane of another
    head masked.  The scale, the mask (each sublane its own row's
    position, so the rows of a verify window keep their own masks and
    the rows of a K/V group share one; a token bound is a lane bound,
    ``t <= at`` is ``lane < (at + 1) * h``), the maximum over the lanes,
    ``exp`` and the sum then run ONCE a block over that ``[N, B * h]``
    array, and ``m`` and ``l`` in scratch hold one row a sublane,
    lane-replicated.  Every lane a row does not keep weighs EXACTLY zero
    (while the row has seen no key its maximum is taken as 0, where
    ``exp(NEG_INF - NEG_INF)`` would weigh every lane 1), so the values
    are weighed by ONE more pass for all the rows, ``p [N, B * h] x v [B
    * h, dh]`` into the f32 ``acc [N, dh]``: a masked token adds zero as
    long as it is finite, a row ``pool_rows`` added holds zeros (``write``).
    That pass keeps ``p`` at float32 accuracy: for a bfloat16 pool ``p``
    goes as the three bfloat16 pieces that sum to it exactly, stacked on
    the row axis (their products with ``v`` are exact in f32); for a
    float32 pool at ``HIGHEST``.  No transpose, no weight rounded to
    bfloat16, no key left out.  What an iteration then costs is the
    LATENCY of its chain (copy, MXU, lane reduction, ``exp``, MXU), not
    its work, so the loop form takes ``G`` consecutive table entries an
    iteration as one block of ``G x B`` tokens
    (``entries_per_iteration``, which has the chip's table), keeps
    ``DEPTH`` groups' copies ahead and two chains in flight
    (``shared_loop_kernel``: group ``g + 1``'s scores and row maxima are
    made while group ``g`` is weighed); the grid form, whose pipeline is
    Pallas's, makes one block after the other.  With ONE row (``W
    = 1`` and no group: plain decode) there is nothing to share and the
    body is the per-row program it always was: the score a lane
    reduction of ``k * q``, ``m`` and ``l`` lane-replicated, ``p * v``
    on the VPU (measured alone on the chip PR 33's shared body was 1.5%
    faster there in the loop form and 15% slower in the grid form, whose
    12 heads do not fill a sublane tile and have to be repacked for the
    MXU: ``benchmarks/paged_walk.py``, PERF.md PR 33).  ``block_step``
    is accepted for signature parity and ignored: how many blocks an
    iteration takes is this spelling's own (one for one row and in the
    grid form, ``entries_per_iteration`` for two rows or more).

    The V array may have other lanes than the K array (``dv`` of
    ``pool_v``; scores over ``dh``, values, ``acc`` and the output over
    ``dv``).  A ``sink [h]`` (one logit a query head, folded as the
    group is) enters as one more input ``[N, LSE_LANES]`` float32, a row
    a sublane: ``init`` starts a row's maximum there and its sum at 1,
    so the sink takes its share of every row's mass and no value row is
    ever read for it."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del block_step
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if pool_v is None:
        return latent_attention_pallas(
            q, pool_k, table, pos,
            _latent_plane(q, pool_k, group, value_lanes),
            scale=scale, out_dtype=out_dtype, interpret=interpret,
            window=window, shared=shared)
    if shared is not None:
        raise ValueError("paged_attention: only a latent plane's call "
                         "takes shared runs")
    if pool_k.ndim == 3:
        # a slab plane: the latent kernel's loop with V's own buffer, at
        # the entries an iteration the rule gives one head's rows
        if sink is not None:
            raise ValueError("paged_attention: a slab plane has no sink")
        dv = _latent_plane(q, pool_k, group, value_lanes, pool_v)
        (_, W, h, dk), B, NB = q.shape, pool_k.shape[1], table.shape[1]
        return latent_attention_pallas(
            q, pool_k, table, pos, dv, scale=scale, out_dtype=out_dtype,
            interpret=interpret, window=window, pool_v=pool_v,
            blocks=entries_per_iteration(
                B, None, dk, dv, W * h, pool_k.dtype,
                window_entries(NB, B, W, window)))
    if sink is not None:
        sink = _fold_sink(sink, group, pool_k.shape[2], q.shape[1])
    width = q.shape[1]              # positions, before a group is folded in
    q, pos, unfold = _fold_group(q, pos, group, pool_k.shape[2])
    out_dtype = q.dtype if out_dtype is None else out_dtype
    S, W, h, dh = q.shape
    dv = pool_v.shape[-1]
    B = pool_k.shape[1]
    NB = table.shape[1]
    if scale is None:
        scale = 1.0 / float(dh) ** 0.5

    def live_entries(pos_ref, s_id):
        top = pos_ref[s_id, 0]
        for w in range(1, W):
            top = jnp.maximum(top, pos_ref[s_id, w])
        # floor(top / B) + 1, and 0 for a window with no row at a
        # position >= 0
        return jnp.minimum(jax.lax.div(jnp.maximum(top, -1) + B, B), NB)

    def first_entry(pos_ref, s_id):
        """The entry that holds the lowest key any row's lower bound
        lets through; the Python constant 0 without a window."""
        if window is None:
            return 0
        low = pos_ref[s_id, 0]
        for w in range(1, W):
            low = jnp.minimum(low, pos_ref[s_id, w])
        return jax.lax.div(jnp.maximum(low - window + 1, 0), B)

    # One row a block (plain decode with a K/V head a query head) has
    # nothing to share: it keeps the per-row program this kernel always
    # was, statistics lane-replicated in scratch.  From two rows up the
    # rows share ONE update a block and both products are MXU passes:
    # row w of head j is sublane ``w * h + j`` of ``[N, B * h]`` arrays
    # whose lane ``t * h + j'`` is token ``t`` of head ``j'``, and so of
    # ``m``, ``l`` (lane-replicated) and ``acc [N, dh]`` in scratch.
    # ``st`` below is the three refs ``(m_ref, l_ref, acc_ref)``.
    N = W * h
    f32 = jnp.float32
    # table entries an iteration of the loop of two rows or more
    G = entries_per_iteration(B, h, dh, dv, N, pool_k.dtype,
                              window_entries(NB, B, width, window))

    def init(m_ref, l_ref, acc_ref, sink_ref=None):
        if sink_ref is None:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
        else:
            # the sink: a key every row has already seen, with no value
            m_ref[...] = sink_ref[...].reshape(m_ref.shape)
            l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def with_sink(kernel):
        """``kernel`` with the sink's ref, which follows the pools among
        the inputs, taken out of its arguments and handed to ``init``."""
        if sink is None:
            return kernel

        def entry(tbl, pos_ref, q_ref, k, v, sink_ref, *rest):
            return kernel(tbl, pos_ref, q_ref, k, v, *rest,
                          sink_ref=sink_ref)
        return entry

    def fold_row(i, kb, vb, s_id, pos_ref, q_ref, m_ref, l_ref, acc_ref):
        """Block ``i`` into the state of a window of ONE row."""
        kb = kb.astype(f32)                            # [B, h, dh]
        vb = vb.astype(f32)
        tok = i * B + jax.lax.broadcasted_iota(jnp.int32, (B, h, 1), 0)
        qw = q_ref[0, 0].astype(f32)                   # [h, dh]
        s = jnp.sum(kb * qw[None], axis=-1, keepdims=True) * scale
        keep = tok <= pos_ref[s_id, 0]
        if window is not None:
            keep &= tok > pos_ref[s_id, 0] - window
        s = jnp.where(keep, s, NEG_INF)                 # [B, h, 1]
        m = m_ref[0][:, :1]                            # [h, 1]
        m2 = jnp.maximum(m, jnp.max(s, axis=0))
        alpha = jnp.exp(m - m2)
        p = jnp.exp(s - m2[None])
        l2 = l_ref[0][:, :1] * alpha + jnp.sum(p, axis=0)
        acc_ref[0] = acc_ref[0] * alpha + jnp.sum(p * vb, axis=0)
        m_ref[0] = jnp.broadcast_to(m2, (h, LSE_LANES))
        l_ref[0] = jnp.broadcast_to(l2, (h, LSE_LANES))

    def scores(i, kb, s_id, pos_ref, q_ref):
        """Every row's masked scores against the tokens of ``kb [T, h,
        dh]``, which start at entry ``i`` of slot ``s_id``'s chain (one
        block, or the ``G`` consecutive entries of a group: consecutive
        entries hold consecutive positions), ``[N, T * h]``, and each
        row's maximum."""
        T = kb.shape[0]
        # every row against every token and head of the block in ONE MXU
        # pass; a row's own head is every h-th lane
        dt = jnp.promote_types(kb.dtype, q_ref.dtype)
        s = _matmul(q_ref[0].reshape(N, dh).astype(dt),
                   kb.reshape(T * h, dh).astype(dt), ((1,), (1,))) * scale
        # row (w, j) keeps the lanes of head j whose token its position
        # lets through: token t <= at is lane < (at + 1) * h
        row = jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0)
        top = jnp.full((N, 1), (pos_ref[s_id, 0] + 1 - i * B) * h)
        for w in range(1, W):
            top = jnp.where(row >= w * h,
                            (pos_ref[s_id, w] + 1 - i * B) * h, top)
        lane = jax.lax.broadcasted_iota(jnp.int32, (N, T * h), 1)
        keep = (jax.lax.rem(lane, h) == jax.lax.rem(row, h)) & (lane < top)
        if window is not None:
            keep &= lane >= top - window * h
        s = jnp.where(keep, s, NEG_INF)
        return s, jnp.max(s, axis=-1, keepdims=True)

    def update(s, peak, vb, m_ref, l_ref, acc_ref):
        """A block's scores ``s`` (their row maxima ``peak``) and values
        ``vb`` into the state of all the rows: the ONE softmax update."""
        m = m_ref[...][:, :1]                              # [N, 1]
        m2 = jnp.maximum(m, peak)
        alpha = jnp.exp(m - m2)
        # every lane a row does not keep weighs EXACTLY zero, also while
        # the row has seen no key (exp(NEG_INF - NEG_INF) would be 1):
        # the product below sums over all of them
        p = jnp.exp(s - jnp.where(m2 == NEG_INF, 0.0, m2))
        l2 = l_ref[...][:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _weigh(
            p, vb.reshape(vb.shape[0] * h, dv))
        m_ref[...] = jnp.broadcast_to(m2, (N, LSE_LANES))
        l_ref[...] = jnp.broadcast_to(l2, (N, LSE_LANES))

    def fold(i, kb, vb, s_id, pos_ref, q_ref, *st):
        """Block ``i`` of slot ``s_id``'s chain into the slot's state,
        once for all the rows of the window."""
        if W == 1:
            fold_row(i, kb, vb, s_id, pos_ref, q_ref, *st)
        else:
            update(*scores(i, kb, s_id, pos_ref, q_ref), vb, *st)

    def finish(o_ref, m_ref, l_ref, acc_ref):
        del m_ref
        if W == 1:
            l = l_ref[0][:, :1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            out = acc_ref[0] / l_safe
            o_ref[0, 0] = out.astype(o_ref.dtype)
            return
        l = l_ref[...][:, :1]
        out = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
        for w in range(W):
            o_ref[0, w] = out[w * h:(w + 1) * h]

    def loop_kernel(tbl, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                    k_buf, v_buf, sem, *st, sink_ref=None):
        """The loop over ``[f_s, n_s)`` for ONE row a block: two buffers,
        a block folded after it has landed."""
        s_id = pl.program_id(0)
        n = live_entries(pos_ref, s_id)
        first = first_entry(pos_ref, s_id)

        def fetch(i, half):
            blk = tbl[s_id, i]
            return (pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[half],
                                          sem.at[0, half]),
                    pltpu.make_async_copy(v_hbm.at[blk], v_buf.at[half],
                                          sem.at[1, half]))

        init(*st, sink_ref)

        @pl.when(n > 0)
        def _first():
            for c in fetch(first, 0):
                c.start()

        def block(i, _):
            half = jax.lax.rem(i if window is None else i - first, 2)

            @pl.when(i + 1 < n)
            def _next():
                for c in fetch(i + 1, 1 - half):
                    c.start()

            for c in fetch(i, half):
                c.wait()
            fold(i, k_buf[half], v_buf[half], s_id, pos_ref, q_ref, *st)

        jax.lax.fori_loop(first, n, block, None)
        finish(o_ref, *st)

    def shared_loop_kernel(tbl, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                           k_buf, v_buf, sem, s_ref, peak_ref, *st,
                           sink_ref=None):
        """The loop over ``[f_s, n_s)`` for two rows or more, ``G``
        table entries an iteration (``entries_per_iteration``).  With
        both products on the MXU an iteration is a CHAIN of latencies
        (copy, MXU, lane reduction, ``exp``, MXU) and not much work, so
        a group of ``G`` consecutive entries lands side by side in ONE
        buffer ``[G * B, h, dh]`` and is scored, masked and weighed as
        one block of ``G * B`` tokens (consecutive entries hold
        consecutive positions), and the loop keeps two chains in
        flight: while group ``g``'s values are weighed, group ``g +
        1``'s scores and row maxima are made and left in scratch for
        the next iteration, and ``DEPTH`` groups' copies run ahead (K is
        needed a group before V); the last group, which has no next, is
        weighed after the loop (a slot's cost whatever its length, which
        a chain of a few entries feels: benchmarks/paged_walk.py,
        ``long_reason_full_short``).  Group ``g`` holds entries ``f_s + g *
        G ..``; those of the last group past ``n_s`` are fetched too
        (the index clamped to the table's last entry: whatever block it
        names holds finite values, which every row's mask weighs zero),
        so that no lane of a buffer holds what was never written."""
        s_id = pl.program_id(0)
        first = first_entry(pos_ref, s_id)
        groups = jax.lax.div(
            jnp.maximum(live_entries(pos_ref, s_id) - first, 0) + G - 1, G)

        def copies(g, plane):
            hbm, buf = ((k_hbm, k_buf), (v_hbm, v_buf))[plane]
            slot = jax.lax.rem(g, DEPTH)
            return [pltpu.make_async_copy(
                hbm.at[tbl[s_id, jnp.minimum(first + g * G + j, NB - 1)]],
                buf.at[slot, pl.ds(j * B, B)], sem.at[plane, slot, j])
                for j in range(G)]

        def score(g):
            s, peak = scores(first + g * G, k_buf[jax.lax.rem(g, DEPTH)],
                             s_id, pos_ref, q_ref)
            s_ref[...] = s
            peak_ref[...] = jnp.broadcast_to(peak, (N, LSE_LANES))

        init(*st, sink_ref)
        for ahead in range(DEPTH - 1):
            @pl.when(ahead < groups)
            def _start(ahead=ahead):
                for plane in (0, 1):
                    for c in copies(ahead, plane):
                        c.start()

        @pl.when(groups > 0)
        def _first():
            for c in copies(0, 0):
                c.wait()
            score(0)

        def group(g, _):
            @pl.when(g + DEPTH - 1 < groups)
            def _ahead():
                for plane in (0, 1):
                    for c in copies(g + DEPTH - 1, plane):
                        c.start()

            for c in copies(g + 1, 0):
                c.wait()
            for c in copies(g, 1):
                c.wait()
            s, peak = s_ref[...], peak_ref[...][:, :1]
            score(g + 1)
            update(s, peak, v_buf[jax.lax.rem(g, DEPTH)], *st)

        # every group but the last is weighed while the next one's
        # scores are made, no branch between the two chains; the last
        # has no next and is weighed alone
        jax.lax.fori_loop(0, groups - 1, group, None)

        @pl.when(groups > 0)
        def _last():
            for c in copies(groups - 1, 1):
                c.wait()
            update(s_ref[...], peak_ref[...][:, :1],
                   v_buf[jax.lax.rem(groups - 1, DEPTH)], *st)

        finish(o_ref, *st)

    def grid_kernel(tbl, pos_ref, q_ref, k_ref, v_ref, o_ref, *st,
                    sink_ref=None):
        del tbl  # consumed by the index maps, not the body
        s_id = pl.program_id(0)
        nb = pl.program_id(1)

        @pl.when(nb == 0)
        def _init():
            init(*st, sink_ref)

        live = nb < live_entries(pos_ref, s_id)
        if window is not None:
            live &= nb >= first_entry(pos_ref, s_id)

        @pl.when(live)
        def _live():
            fold(nb, k_ref[0], v_ref[0], s_id, pos_ref, q_ref, *st)

        @pl.when(nb == NB - 1)
        def _finish():
            finish(o_ref, *st)

    stat, acc = (((1, h, LSE_LANES), (1, h, dv)) if W == 1
                 else ((N, LSE_LANES), (N, dv)))
    stats = [pltpu.VMEM(stat, jnp.float32), pltpu.VMEM(stat, jnp.float32),
             pltpu.VMEM(acc, jnp.float32)]
    if _block_is_sliceable(pool_k):
        grid, semantics = (S,), ("parallel",)
        kv_spec = pl.BlockSpec(memory_space=pl.ANY)
        if W == 1:
            kernel = loop_kernel
            scratch = [pltpu.VMEM((2, B, h, dh), pool_k.dtype),
                       pltpu.VMEM((2, B, h, dv), pool_v.dtype),
                       pltpu.SemaphoreType.DMA((2, 2))] + stats
        else:
            kernel = shared_loop_kernel
            scratch = [pltpu.VMEM((DEPTH, G * B, h, dh), pool_k.dtype),
                       pltpu.VMEM((DEPTH, G * B, h, dv), pool_v.dtype),
                       pltpu.SemaphoreType.DMA((2, DEPTH, G)),
                       pltpu.VMEM((N, G * B * h), jnp.float32),
                       pltpu.VMEM((N, LSE_LANES), jnp.float32)] + stats
    else:
        def last_live(s, nb, tbl, pos):
            i = jnp.minimum(nb, jnp.maximum(live_entries(pos, s) - 1, 0))
            if window is not None:
                i = jnp.maximum(i, first_entry(pos, s))
            return (tbl[s, i], 0, 0, 0)

        kernel, grid, semantics = grid_kernel, (S, NB), ("parallel",
                                                         "arbitrary")
        kv_spec = pl.BlockSpec((1, B, h, dh), last_live)
        v_spec = pl.BlockSpec((1, B, h, dv), last_live)
        scratch = stats
    if _block_is_sliceable(pool_k):
        v_spec = kv_spec
    row = pl.BlockSpec((1, W, h, dh), lambda s, *_: (s, 0, 0, 0))
    out_row = pl.BlockSpec((1, W, h, dv), lambda s, *_: (s, 0, 0, 0))
    extra, extra_specs = (), []
    if sink is not None:
        extra = (jnp.broadcast_to(sink.reshape(N, 1), (N, LSE_LANES)),)
        extra_specs = [pl.BlockSpec((N, LSE_LANES), lambda s, *_: (0, 0))]
    ctx = pl.pallas_call(
        with_sink(kernel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[row, kv_spec, v_spec] + extra_specs,
            out_specs=out_row, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((S, W, h, dv), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
        interpret=bool(interpret),
        name="paged_attention",
    )(table.astype(jnp.int32), pos.astype(jnp.int32), q, pool_k, pool_v,
      *extra)
    return unfold(ctx)


# ``latent_attention_pallas``'s calls that were told of shared runs, by
# everything their kernel's body reads: one jitted function a geometry
_TOLD_CALLS = {}


def _latent_entries(blocks, NB):
    """Table entries an iteration of the latent kernel's loop takes on a
    table of ``NB``: ``blocks``, ``LATENT_BLOCKS`` if None."""
    return max(1, min(int(LATENT_BLOCKS if blocks is None else blocks), NB))


def _stack_slots(slots, rows):
    """Slots whose rows ONE walk of the latent kernel may stack: what
    ``STACK_ROWS`` holds of ``rows`` query rows a slot, two at least, no
    more than the call has."""
    return min(int(slots), max(2, STACK_ROWS // int(rows)))


def _stack_widths(slots, rows):
    """The widths (in slots) a shared run's walk is compiled at: 2, 3 and
    their doublings under ``_stack_slots``, and that; a run of ``n``
    slots takes the narrowest that holds them (the MXU's passes grow
    with the stacked rows: alone on the chip a run of 256 entries costs
    17 us walked by one slot, 22 by two stacked, 30 by four, 44 by
    eight; PERF.md, PR 58)."""
    cap = _stack_slots(slots, rows)
    return sorted({w << i for w in (2, 3) for i in range(cap.bit_length())
                   if w << i < cap} | {cap})


def shared_runs(table, whole, rows, blocks=None):
    """Which live slots' chains START alike, for the latent kernel to
    fetch such a run once (``latent_attention_pallas``'s ``shared``), from
    the host's table: ``table [S, NB]`` (NumPy), ``whole [S]`` the entries
    of each slot's chain that lie WHOLE under its position (0 for a slot
    that is not live; a block two tables name is immutable, and only
    entries every member has behind it are shared), ``rows`` the query
    rows a slot sends (``_stack_slots`` bounds a run's members).

    Returns ``[S, 2 + S]`` int32, a row a slot: ``[n, members, ids..]``.
    ``n`` is the run's length in table entries, a multiple of ``blocks``
    (the kernel's entries an iteration), for EVERY slot of a run and 0
    for a slot in none; ``members`` is the count of the run's slots in
    the row of its LEADER (its lowest slot, whose grid step walks the run
    before any member's own) and 0 elsewhere, ``ids`` those slots
    ascending, the leader first.  Greedy from the lowest pending slot:
    of the others, by how far each agrees with it, the ``k`` that agree
    longest share their shortest agreement, and the ``k`` is taken that
    saves most fetches (``n x k``); who is left over is grouped next.  A
    copy-on-write fork ends the agreement at the forked entry."""
    table, whole = np.asarray(table), np.asarray(whole)
    S, NB = table.shape
    G = _latent_entries(blocks, NB)
    cap = _stack_slots(S, rows)
    out = np.zeros((S, 2 + S), np.int32)
    pending = [s for s in range(S) if whole[s] >= G]
    while len(pending) > 1:
        lead, rest = pending[0], np.asarray(pending[1:])
        differ = table[rest] != table[lead]
        agree = np.where(differ.any(1), differ.argmax(1), NB)
        agree = np.minimum(agree, np.minimum(whole[rest], whole[lead]))
        agree = agree // G * G
        order = np.argsort(-agree, kind="stable")[:cap - 1]
        saved = agree[order] * np.arange(1, len(order) + 1)
        k = int(saved.argmax()) + 1
        n = int(agree[order[k - 1]])
        if n == 0:
            pending = pending[1:]
            continue
        members = [lead] + sorted(int(s) for s in rest[order[:k]])
        out[members, 0] = n
        out[lead, 1] = len(members)
        out[lead, 2:2 + len(members)] = members
        pending = [s for s in pending if s not in members]
    return out


def latent_attention_pallas(q, pool, table, pos, value_lanes, scale=None,
                            out_dtype=None, interpret=None, blocks=None,
                            window=None, shared=None, pool_v=None):
    """The Mosaic kernel of a LATENT plane, a sibling of the loop above
    under its own name (``paged_latent_attention``): ``pool [blocks, B,
    L]`` holds ONE row a cached position, every one of the ``h`` query
    heads of ``q [S, W, h, L]`` reads it whole, and a position's value
    is its row's first ``value_lanes`` lanes, so nothing is fetched
    twice and no lane belongs to another head.  ``[S, W, h,
    value_lanes]`` comes back.

    Grid ``(S,)``, one step a slot, a loop over the slot's LIVE entries
    (``n_s`` as above; with a ``window`` from the group that holds the
    first entry some row's lower bound lets through, the keys under a
    row's bound masked) taken ``blocks`` at a time (``LATENT_BLOCKS`` if
    None): one iteration copies ``blocks`` table entries
    side by side into one ``[blocks * B, L]`` buffer (``DEPTH`` such
    buffers, ``DEPTH - 1`` groups on their way), scores all ``N = W *
    h`` rows against it in ONE MXU pass ``[N, L] x [blocks * B, L]^T``,
    masks by token (row ``w * h + a`` keeps ``j <= pos[s, w]``), makes
    ONE softmax update and weighs the values in a second pass ``p x
    buffer[:, :value_lanes]`` (``_weigh``: float32 accuracy).  As in the
    loop above a group's scores are made while the group before it is
    weighed.  What an iteration costs is the latency of that chain, not
    its work (PERF.md, PR 35), so several blocks an iteration divide it
    (``LATENT_BLOCKS`` has the chip's table).  The last group's
    entries past ``n_s`` are fetched too (whatever the table names
    there, the trash block for an entry never used: finite values under
    a zero weight), so that no lane of the buffer holds what was never
    written.  A slot whose rows are all at ``pos < 0`` fetches nothing
    and returns zeros.

    **A run several slots share is fetched ONCE** (``shared [S, 2 + S]``
    int32, ``shared_runs``'s; DATA: whatever it holds, one program).
    The grid step of a run's leader first walks the run's entries ``[0,
    n)`` of its own table row with the members' rows STACKED (``members x
    N`` rows against the one buffer: the same two MXU passes and the same
    one update an iteration, at the narrowest of ``_stack_widths`` that
    holds the members) and leaves each member's running ``(m, l, acc)``
    in VMEM scratch, which lives across the grid's steps (so the grid is
    ``arbitrary``); a member's own walk then STARTS from that state at
    entry ``n`` instead of from nothing at entry 0, as the loop above
    starts at a sink.  A row folds the same groups of entries in the same
    order with or without a run, so its output is the same.  A slot in
    no run (a row of zeros) walks ``[0, n_s)`` as ever.  Only decode rows
    of a plane attended whole take a run: with a ``window`` or ``W > 1``
    ``shared`` is dropped and the program is the one without it.

    **A slab plane** (``pool_v [blocks, B, value_lanes]``: the values in
    an array of their own, module docstring) is the same loop under the
    name ``paged_slab_attention``: a group's V slabs are copied beside
    its K slabs into a second buffer, awaited where the group is weighed
    (K is needed a group before V), and ``p`` is weighed against them
    instead of the key rows' first lanes.  Tokens stay on sublanes and no
    lane belongs to another head, so the scores of ``N`` query rows
    against ``blocks`` slabs are ``[N, blocks * B]`` and nothing more.
    It takes no shared runs."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out_dtype = q.dtype if out_dtype is None else out_dtype
    S, W, h, L = q.shape
    B, NB = pool.shape[1], table.shape[1]
    G = _latent_entries(blocks, NB)
    T, N, dv = G * B, W * h, int(value_lanes)
    if scale is None:
        scale = 1.0 / float(L) ** 0.5
    f32 = jnp.float32
    # only decode rows of a plane attended whole take a run (a Python
    # flag: the body below reads nothing of the traced array)
    if pool_v is not None and shared is not None:
        raise ValueError("paged_attention: a slab plane takes no shared runs")
    told = shared is not None and window is None and W == 1 and S > 1
    # rows the softmax's scratch holds: one slot's, or the widest stack's
    widths = _stack_widths(S, N) if told else []
    R = N * (widths[-1] if widths else 1)

    def rows(ref, n):
        return ref if n == ref.shape[0] else ref.at[pl.ds(0, n)]

    def kernel(tbl, pos_ref, *refs):
        if pool_v is not None:
            (q_ref, pool_hbm, v_hbm, o_ref, buf, sem, s_ref, peak_ref,
             m_ref, l_ref, acc_ref, v_buf, v_sem) = refs
        elif not told:
            (q_ref, pool_hbm, o_ref, buf, sem, s_ref, peak_ref, m_ref,
             l_ref, acc_ref) = refs
        else:
            (shr, q_ref, pool_hbm, o_ref, buf, sem, s_ref, peak_ref, m_ref,
             l_ref, acc_ref, qs_ref, m_run, l_run, acc_run) = refs
        s_id = pl.program_id(0)
        top = pos_ref[s_id, 0]
        for w in range(1, W):
            top = jnp.maximum(top, pos_ref[s_id, w])
        live = jnp.minimum(jax.lax.div(jnp.maximum(top, -1) + B, B), NB)
        groups = jax.lax.div(live + G - 1, G)
        # the first group a row's lower bound lets through; the Python
        # constant 0 without a window
        g0 = 0
        if window is not None:
            low = pos_ref[s_id, 0]
            for w in range(1, W):
                low = jnp.minimum(low, pos_ref[s_id, w])
            g0 = jax.lax.div(jnp.maximum(low - window + 1, 0), G * B)

        def copies(g):
            slot = jax.lax.rem(g, DEPTH)
            return [pltpu.make_async_copy(
                pool_hbm.at[tbl[s_id, jnp.minimum(g * G + j, NB - 1)]],
                buf.at[slot, pl.ds(j * B, B)], sem.at[slot, j])
                for j in range(G)]

        def values(g):
            """Group ``g``'s V slabs on their way (a slab plane's; a
            latent row's values are lanes of the row ``copies`` brings)."""
            if pool_v is None:
                return []
            slot = jax.lax.rem(g, DEPTH)
            return [pltpu.make_async_copy(
                v_hbm.at[tbl[s_id, jnp.minimum(g * G + j, NB - 1)]],
                v_buf.at[slot, pl.ds(j * B, B)], v_sem.at[slot, j])
                for j in range(G)]

        def fresh(n):
            for ref, start in ((m_ref, NEG_INF), (l_ref, 0.0),
                               (acc_ref, 0.0)):
                ref = rows(ref, n)
                ref[...] = jnp.full(ref.shape, start, f32)

        def walk(n, query, at_rows, g0, groups):
            """Rows ``[0, n)`` of the softmax's scratch folded over the
            groups ``[g0, groups)`` of THIS slot's table row: ``query()``
            their ``[n, L]`` rows, ``at_rows()`` ``[(first row, position)]``
            ascending."""
            s_w, peak_w, m_w, l_w, acc_w = (
                rows(r, n) for r in (s_ref, peak_ref, m_ref, l_ref, acc_ref))

            def score(g):
                kb = buf[jax.lax.rem(g, DEPTH)]                # [T, L]
                dt = jnp.promote_types(kb.dtype, q_ref.dtype)
                s = _matmul(query().astype(dt), kb.astype(dt),
                            ((1,), (1,))) * scale              # [n, T]
                row = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
                (_, at), *later = at_rows()
                at = jnp.full((n, 1), at)
                for first, at_row in later:
                    at = jnp.where(row >= first, at_row, at)
                tok = g * T + jax.lax.broadcasted_iota(jnp.int32, (n, T), 1)
                keep = tok <= at
                if window is not None:
                    keep &= tok > at - window
                s = jnp.where(keep, s, NEG_INF)
                s_w[...] = s
                peak_w[...] = jnp.broadcast_to(
                    jnp.max(s, axis=-1, keepdims=True), (n, LSE_LANES))

            for ahead in range(DEPTH - 1):
                @pl.when(g0 + ahead < groups)
                def _start(ahead=ahead):
                    g = g0 + ahead
                    for c in copies(g) + values(g):
                        c.start()

            @pl.when(groups > g0)
            def _first():
                for c in copies(g0):
                    c.wait()
                score(g0)

            def group(g, _):
                @pl.when(g + DEPTH - 1 < groups)
                def _ahead():
                    ahead = g + DEPTH - 1
                    for c in copies(ahead) + values(ahead):
                        c.start()

                @pl.when(g + 1 < groups)
                def _next():
                    for c in copies(g + 1):
                        c.wait()

                for c in values(g):
                    c.wait()
                s, peak = s_w[...], peak_w[...][:, :1]
                # the last group's scores are made once more and dropped:
                # no branch between the two chains
                score(jnp.minimum(g + 1, groups - 1))
                m = m_w[...][:, :1]
                m2 = jnp.maximum(m, peak)
                alpha = jnp.exp(m - m2)
                # a lane a row does not keep weighs EXACTLY zero, also
                # while the row has seen no key (the loop above)
                p = jnp.exp(s - jnp.where(m2 == NEG_INF, 0.0, m2))
                l2 = (l_w[...][:, :1] * alpha
                      + jnp.sum(p, axis=-1, keepdims=True))
                acc_w[...] = acc_w[...] * alpha + _weigh(
                    p, buf[jax.lax.rem(g, DEPTH)][:, :dv] if pool_v is None
                    else v_buf[jax.lax.rem(g, DEPTH)])
                m_w[...] = jnp.broadcast_to(m2, (n, LSE_LANES))
                l_w[...] = jnp.broadcast_to(l2, (n, LSE_LANES))

            jax.lax.fori_loop(g0, groups, group, None)

        if not told:
            fresh(N)
            walk(N, lambda: q_ref[0],
                 lambda: [(w * h, pos_ref[s_id, w]) for w in range(W)],
                 g0, groups)
        else:
            # a run this slot leads: its members' rows stacked over the
            # run's entries, each member's state kept for its own walk
            run, count = jax.lax.div(shr[s_id, 0], G), shr[s_id, 1]
            for under, width in zip([1] + widths, widths):
                @pl.when((run > 0) & (count > under) & (count <= width))
                def _run(width=width):
                    n = width * N
                    ids = [jnp.where(j < count, shr[s_id, 2 + j], s_id)
                           for j in range(width)]
                    for j, member in enumerate(ids):
                        qs_ref[pl.ds(j * N, N)] = q_ref[member]
                    fresh(n)
                    walk(n, lambda: rows(qs_ref, n)[...],
                         lambda: [(j * N, pos_ref[member, 0])
                                  for j, member in enumerate(ids)], 0, run)
                    for j, member in enumerate(ids):
                        @pl.when(j < count)
                        def _keep(j=j, member=member):
                            for kept, ref in ((m_run, m_ref), (l_run, l_ref),
                                              (acc_run, acc_ref)):
                                kept[member] = ref[pl.ds(j * N, N)]

            @pl.when(run == 0)
            def _alone():
                fresh(N)

            @pl.when(run > 0)
            def _resume():
                for kept, ref in ((m_run, m_ref), (l_run, l_ref),
                                  (acc_run, acc_ref)):
                    ref[pl.ds(0, N)] = kept[s_id]

            walk(N, lambda: q_ref[s_id], lambda: [(0, pos_ref[s_id, 0])],
                 run, groups)
        l = rows(l_ref, N)[...][:, :1]
        o_ref[0] = (rows(acc_ref, N)[...]
                    / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    scratch = [
        pltpu.VMEM((DEPTH, T, L), pool.dtype),
        pltpu.SemaphoreType.DMA((DEPTH, G)),
        pltpu.VMEM((R, T), f32), pltpu.VMEM((R, LSE_LANES), f32),
        pltpu.VMEM((R, LSE_LANES), f32),
        pltpu.VMEM((R, LSE_LANES), f32), pltpu.VMEM((R, dv), f32)]
    prefetch = [table.astype(jnp.int32), pos.astype(jnp.int32)]
    q_spec = pl.BlockSpec((1, N, L), lambda s, *_: (s, 0, 0))
    pools = [pool]
    if pool_v is not None:
        pools.append(pool_v)
        scratch += [pltpu.VMEM((DEPTH, T, dv), pool_v.dtype),
                    pltpu.SemaphoreType.DMA((DEPTH, G))]
    if told:
        # every slot's rows stay in VMEM for whoever leads a run, and
        # the members' states between their leader's step and their own
        prefetch.append(shared.astype(jnp.int32))
        q_spec = pl.BlockSpec((S, N, L), lambda s, *_: (0, 0, 0))
        scratch += [pltpu.VMEM((R, L), q.dtype),
                    pltpu.VMEM((S, N, LSE_LANES), f32),
                    pltpu.VMEM((S, N, LSE_LANES), f32),
                    pltpu.VMEM((S, N, dv), f32)]
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(S,),
            in_specs=[q_spec] + [pl.BlockSpec(memory_space=pl.ANY)
                                 for _ in pools],
            out_specs=pl.BlockSpec((1, N, dv), lambda s, *_: (s, 0, 0)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((S, N, dv), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if told else "parallel",)),
        interpret=bool(interpret),
        name=("paged_latent_attention" if pool_v is None
              else "paged_slab_attention"),
    )
    if told:
        # a walk a stack width is several times the body to trace and to
        # lower: every plane of a program calls ONE function, made once
        # (the first such call's, kept by everything the body reads)
        call = _TOLD_CALLS.setdefault(
            (q.shape, str(q.dtype), pool.shape[1:], str(pool.dtype), NB, G,
             dv, float(scale), str(jnp.dtype(out_dtype)), bool(interpret),
             tuple(widths)), jax.jit(call))
    ctx = call(*prefetch, q.reshape(S, N, L), *pools)
    return ctx.reshape(S, W, h, dv)


def write(pool, blk, off, rows):
    """``rows [*blk.shape, heads, dh]`` written into ``pool [blocks, B,
    hk, dh]`` at ``(blk, off)`` (a decode step's ``[S]`` indices or a
    window's ``[S, W]``): ONE scatter that covers the pool's whole row.
    Where ``pool_rows`` gave the pool more rows than ``heads``, the rows
    past ``heads`` are written as zeros.  A write of PART of the head
    axis compiles to a serial loop over the written rows with one
    ``dynamic-update-slice`` each, 4.2 us a row on a v5e where the whole
    row costs 0.13 (PERF.md, PR 37).  A LATENT plane (``pool [blocks, B,
    L]``, ``rows [*blk.shape, values]``) has no head axis: the lanes past
    the ``values`` a row carries are written as zeros; so are the lanes a
    K array stores past its key (``key_lanes``)."""
    if pool.ndim == 3:
        spare = pool.shape[2] - rows.shape[-1]
        if spare:
            rows = jnp.pad(rows, ((0, 0),) * (rows.ndim - 1) + ((0, spare),))
        return pool.at[blk, off].set(rows)
    spare, lanes = (pool.shape[2] - rows.shape[-2],
                    pool.shape[3] - rows.shape[-1])
    if spare or lanes:
        rows = jnp.pad(rows, ((0, 0),) * (rows.ndim - 2)
                       + ((0, spare), (0, lanes)))
    return pool.at[blk, off].set(rows)


def _tpu_available():
    try:
        backend = jax.default_backend()
    except Exception as e:  # noqa: BLE001
        return False, f"jax backend probe failed: {e}"
    if backend == "tpu":
        return True, ""
    return False, (f"not on TPU (platform {backend!r}); the block-scan "
                   f"XLA oracle is the efficient spelling here")


# -- registration ------------------------------------------------------------

class _PagedXlaRef:
    call = staticmethod(paged_attention_ref)


class _PagedPallasTpu:
    call = staticmethod(paged_attention_pallas)


register_kernel("paged_attention", "xla_ref", _PagedXlaRef)
register_kernel("paged_attention", "pallas_tpu", _PagedPallasTpu,
                available=_tpu_available)
