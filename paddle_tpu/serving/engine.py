"""Continuous-batching serving engine — slot-scheduled multi-request
decode over a PAGED, prefix-shared KV cache with SLO-aware goodput
scheduling.

``models/transformer.py generate`` turned decode into a single jitted
scan, but it serves exactly one request per call: chip utilization
collapses under real traffic (many concurrent, variable-length
requests).  Decode is HBM-bandwidth-bound on WEIGHT reads, so batching
``S`` requests into one step re-reads the same weights for ``S`` tokens
— nearly free throughput.  The engine keeps one fixed-capacity batched
decode step saturated across many requests:

* **Paged slot pool** — KV lives in a physical block pool
  (``serving.kvcache``): fixed-size blocks of ``block_tokens``
  positions, indexed per slot by a block table the compiled step
  gathers through.  A slot is a chain of blocks, not a contiguous row;
  blocks are reference-counted and returned to the pool the moment
  nothing uses them.
* **Prefix reuse** — identical prompt prefixes (system prompts,
  few-shot templates — the dominant production traffic shape) map
  through a trie to SHARED block chains: a request whose prefix is
  cached skips that portion of prefill entirely (full blocks shared by
  refcount; a divergence inside a cached block forks it copy-on-write).
  ``serving.prefix_hit_rate`` / ``serving.cow_copies`` /
  ``serving.blocks_in_use`` expose it live.
* **Continuous batching** — queued requests are admitted into free
  slots BETWEEN decode chunks, not at batch boundaries: a long request
  never holds the batch hostage, a short one never waits for stragglers.
* **Bucketed prefill** — the NON-CACHED prompt suffix is one
  teacher-forced window forward through the block table, padded to the
  narrowest rung that covers it (``batched_decode.prefill_rungs``:
  from ``min_bucket`` a factor of four apart under the chip's ridge,
  doublings from there to the piece width
  ``batched_decode.PREFILL_PIECE``, twice the ridge);
  a longer suffix runs as whole pieces and one remainder inside the
  same admission.  The compile cache is
  bounded by the widths (TVM-style static shape buckets), never by the
  request count or the prompt length: total executables =
  ``len(used widths) + 1`` decode chunk — the copy-on-write fork rides
  inside the prefill executable.
* **Chunked decode** — ``decode_chunk`` steps run per device call
  (one ``lax.scan``), amortizing dispatch + host sync.  EOS is detected
  on the host after the chunk.
* **One chunk in flight** — chunk n + 1 is sent before chunk n's
  tokens are read (``_decode``: ``_dispatch`` / ``_collect``), so the
  device goes from one chunk to the next while the host fetches, emits,
  polls the queue and builds the next table.  An end the host can
  foresee (``max_new``) never rides a chunk more; an EOS hit rides one,
  its rows thrown away (docs/serving.md "The driver loop keeps one
  chunk in flight").
* **SLO-aware scheduling** — the CONTROL half of the goodput loop
  (``serving.scheduler``; PR 11 shipped the measurement half): the
  queue is admitted by least predicted-TTFT slack and requests that
  provably cannot meet their e2e budget are SHED immediately
  (``serving.shed_total``) instead of burning decode capacity on
  tokens nobody receives on time.  ``scheduler="fifo"`` keeps the PR-2
  policy as the benchmark baseline.

Greedy decode through the engine computes each request as
``transformer.generate`` would alone — prefix reuse on or off (same
per-row math and dtypes, token-identical in f32; see ``batched_decode``
and docs/serving.md "Numerics contract").  Telemetry flows through
the global observability registry under ``serving.*``; with tracing
enabled every finished request lays a span tree on its own timeline
lane (submit -> queue -> prefill(bucket, prefix_hit) -> per-decode-
chunk -> evict) exported to Chrome-trace via ``trace.save(path)``.
"""

import collections
import threading
import time

import numpy as np

from ..kernels import paged_attention as _paged
from ..kernels import retention as _retention
from ..kernels import sparse_attention as _sparse
from ..observability import flight as _flight
from ..observability import metrics as _obs
from ..observability import trace as _trace
from ..resilience import faults as _faults
from . import arch as _arch
from . import batched_decode as _bd
from . import kvcache as _kv
from . import scheduler as _sched
from . import speculative as _spec

__all__ = ["Request", "ServingEngine"]


# One decode chunk between its dispatch and its collect: the tokens and
# the stack's tallies its executable returned (on the device until read),
# {slot: request} of the rows live in it as of dispatch, and when it was sent
_Chunk = collections.namedtuple("_Chunk", "toks counts reqs sent_t")

# finished requests whose accounts the engine keeps for the tail's
# make-up: the histograms' reservoir
TPOT_RING = 4096
# the state snapshots' share of the prefix cache: their arrays may hold
# one byte for every SNAPSHOT_SHARE bytes of K/V that cache_blocks blocks
# hold.  A snapshot is what makes a cached chain worth reading again for
# an architecture with recurrent state, and one is worth the whole chain
# under it, so the share is set by what a chip can spare, not by what a
# snapshot saves: an eighth is 11 snapshots of 13 MB beside four cached
# heads of 65,536 tokens (1.2 GB of K/V), room for every head and for the
# snapshots that prompts ending on a block boundary leave behind them
SNAPSHOT_SHARE = 8
# name prefixes of the gauges stats() publishes from that ring
_TAIL_GAUGES = ("serving.tpot_p90_seconds", "serving.tpot_tail_")


class Request:
    """One submitted generation request and its (eventual) result.

    ``tokens`` holds only GENERATED tokens (EOS included when hit);
    ``result()`` returns prompt + generated as one int32 array.  Handles
    are thread-safe: ``wait``/``result`` may be called from any thread
    while the engine runs in another.  If the engine aborts (a device
    error mid-serve), the handle completes with ``error`` set and
    ``result()`` re-raises it instead of hanging waiters forever; a
    request the SLO scheduler sheds completes with ``shed`` True and a
    ``SheddedRequest`` error.
    """

    __slots__ = ("rid", "prompt", "max_new", "eos_id", "tokens",
                 "submit_t", "first_token_t", "finish_t", "error",
                 "admit_t", "prefill_t0", "prefill_t1", "bucket",
                 "chunks", "slo_ok", "ttft_slo_s", "e2e_slo_s",
                 "shed", "sheddable", "prefix_hit",
                 "spec_proposed", "spec_accepted",
                 "chunk_s", "stall_prefill_s", "stall_host_s", "steps",
                 "slot_steps", "_done")

    def __init__(self, rid, prompt, max_new, eos_id,
                 ttft_slo_s=None, e2e_slo_s=None, sheddable=True):
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new
        self.eos_id = eos_id
        self.tokens = []
        self.submit_t = time.perf_counter()
        self.first_token_t = None
        self.finish_t = None
        self.error = None
        # span-tree timestamps (observability.trace): queue pop, prefill
        # window, prefill bucket, and the decode-chunk windows this
        # request was live for — the request's timeline lane is emitted
        # from these when it finishes
        self.admit_t = None
        self.prefill_t0 = None
        self.prefill_t1 = None
        self.bucket = None
        self.chunks = []
        # SLO verdict at finish: True (met), False (violated/shed), or
        # None (no SLO budgets configured); per-request budgets override
        # the engine-level defaults
        self.slo_ok = None
        self.ttft_slo_s = ttft_slo_s
        self.e2e_slo_s = e2e_slo_s
        self.shed = False
        # False exempts the request from scheduler shedding (it is
        # still judged against its budgets at finish) — the synchronous
        # generate_many front-end uses this: its caller waits for every
        # result, so refusing one only destroys output
        self.sheddable = sheddable
        # prompt tokens whose prefill was skipped via the prefix trie
        self.prefix_hit = 0
        # speculative accounting (0 when the engine has no draft):
        # draft tokens proposed for / accepted by this request
        self.spec_proposed = 0
        self.spec_accepted = 0
        # the account of its time per output token, fed at every collect
        # (ServingEngine._account_stall) whether the tracer is on or
        # off: first token -> finish is chunk_s (its chunks' clock
        # pairs) + stall_prefill_s (waits inside another request's
        # prefill clock pair) + stall_host_s (the rest of its waits);
        # the steps its chunks ran, and the rows the device stepped
        # beside it x those steps
        self.chunk_s = 0.0
        self.stall_prefill_s = 0.0
        self.stall_host_s = 0.0
        self.steps = 0
        self.slot_steps = 0
        self._done = threading.Event()

    @property
    def done(self):
        return self._done.is_set()

    def wait(self, timeout=None):
        return self._done.wait(timeout)

    def result(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.rid} not finished")
        if self.error is not None:
            if isinstance(self.error, _sched.SheddedRequest):
                raise self.error
            # the cause names what actually happened — an engine abort,
            # an injected slot death (engine still serving), a driver
            # death — don't claim more than "this request failed"
            raise RuntimeError(
                f"request {self.rid} failed: "
                f"{type(self.error).__name__}: {self.error}") \
                from self.error
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])

    @property
    def ttft(self):
        """Submit -> first generated token, seconds (None until then)."""
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    @property
    def e2e(self):
        """Submit -> finished, seconds (None until finished)."""
        if self.finish_t is None:
            return None
        return self.finish_t - self.submit_t


class ServingEngine:
    """Slot-scheduled continuous-batching front-end over the paged
    batched decode kernels.

    params   name->array dict with the Program's parameter names (e.g.
             ``transformer.extract_params()``); cast once to
             ``compute_dtype`` (default: the dtype the block/lm_head
             matmul weights imply — bf16-trained weights serve in bf16).
    n_layer / n_head / d_model / eps   the GPT-2 block of
             ``transformer.build`` (the positional spelling), OR
    arch     a ``serving.arch.Architecture``: heads and head size, the
             K/V planes a token holds (``n_layer * passes`` unless it
             lists them), the per-slot state it holds beside the pool,
             and the forward the compiled entry points run
             (docs/serving.md "Architectures").  One that holds
             recurrent state refuses a draft; with ``prefix_reuse=True``
             it is served from ONE state snapshot a cached head, taken
             where a prefill piece ended on a block boundary
             (docs/serving.md "Prefix reuse over recurrent state"),
             unless it has no plane at all (no pool, no trie: refused).
             ``ServingEngine(params, arch=Gpt2(L, h, d, eps), ...)`` is
             the positional spelling exactly.
    max_len  per-slot logical KV capacity; every request needs
             ``len(prompt) + max_new_tokens <= max_len``.
    max_slots     concurrent sequences in the batched step.
    decode_chunk  decode steps fused per device call (tokens reach
             the host in chunks of this many).
    min_bucket    narrowest prefill window; prompt SUFFIXES (after prefix
             reuse) pad to the narrowest rung that covers them (this
             and its multiples a factor of four apart up to 128,
             doublings from there to the piece width), and run as
             several pieces beyond it (compile-count bound).
    block_tokens  tokens per physical KV block (paging granularity —
             also the prefix-sharing granularity: only whole blocks are
             shared, a partial overlap forks copy-on-write).
    cache_blocks  prefix-cache capacity budget: blocks the trie may
             keep alive beyond live requests (LRU-evicted under
             pressure).  Default ``2 * ceil(max_len / block_tokens)``.
    pool_blocks  blocks the pool holds beside the trash block, where the
             deployment states them.  Default (``None``): every slot's
             worst-case chain and the cache budget, so that admission
             can always allocate.  A deployment whose slots share long
             cached documents and own short tails states less (32 slots
             of 132,352 positions would reserve 66,176 blocks for chains
             that are 2,048 shared blocks and 20 of their own): a request
             whose chain finds no room once the trie has evicted what it
             may waits for a slot to finish (``PoolExhausted``: it keeps
             its place in the queue).  At least one whole chain.
    prefix_reuse  False disables the trie (every request pays full
             prefill — the PR-2 spelling; bit-exactness is gated in
             BOTH modes).
    scheduler  "slo" (default: least-TTFT-slack admission + e2e-doomed
             shedding; with no budgets configured it degrades to FIFO
             order) or "fifo" (the PR-2 baseline policy).
    eos_id   default EOS token id (per-request override in ``submit``).
    draft_params  parameter dict of a small DRAFT model (same
             ``transformer.build`` family: identical vocab / d_model /
             head geometry, fewer layers — e.g.
             ``speculative.depth_draft``).  When given (and
             ``PADDLE_TPU_SPEC`` is not off), decode runs SPECULATIVE
             rounds: the draft proposes ``spec_k`` tokens per slot into
             scratch block chains, one target verify forward scores the
             whole window, greedy acceptance commits the agreeing
             prefix + bonus token — TOKEN-EXACT vs plain greedy decode
             (docs/serving.md "Speculative decoding").  Geometry
             mismatches raise at construction.
    draft_n_layer / draft_n_head  the draft's depth / head count
             (default: inferred depth / the target's ``n_head``; a
             differing head count is rejected — the draft shares the
             target's paged pool arrays).
    spec_k   draft tokens proposed per round.
    ttft_slo_s / e2e_slo_s   per-request latency budgets (seconds),
             overridable per request in ``submit``.  When set, every
             finished request is judged at finish time
             (``Request.slo_ok``): a breach counts
             ``serving.slo_violations`` and its tokens are EXCLUDED
             from the ``serving.goodput_tok_s`` gauge — and the SLO
             scheduler admits/sheds against the same budgets, so
             goodput (not raw tok/s) is what the engine maximizes.

    Drive it synchronously (``generate_many`` / ``step`` +
    ``results``) or from a background thread (``start``/``stop``) with
    producers calling ``submit`` concurrently.
    """

    def __init__(self, params, n_layer=None, n_head=None, d_model=None,
                 max_len=128,
                 max_slots=8, decode_chunk=4, min_bucket=8,
                 eos_id=None, compute_dtype=None, eps=1e-5, donate=True,
                 registry=None, ttft_slo_s=None, e2e_slo_s=None,
                 block_tokens=16, cache_blocks=None, prefix_reuse=True,
                 scheduler="slo", draft_params=None, draft_n_layer=None,
                 draft_n_head=None, spec_k=4, arch=None, pool_blocks=None):
        import jax
        import jax.numpy as jnp

        from ..models.transformer import infer_compute_dtype

        if arch is None:
            if None in (n_layer, n_head, d_model):
                raise ValueError(
                    "ServingEngine needs an architecture: arch=..., or "
                    "the GPT-2 block's n_layer, n_head, d_model")
            arch = _arch.Gpt2(n_layer, n_head, d_model, eps)
        elif (n_layer, n_head, d_model) != (None, None, None):
            raise ValueError("ServingEngine takes arch=... or n_layer, "
                             "n_head, d_model, not both")
        self.arch = arch
        n_layer, n_head, d_model = arch.n_layer, arch.n_head, arch.d_model
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1: {max_slots}")
        if block_tokens < 1:
            raise ValueError(f"block_tokens must be >= 1: {block_tokens}")
        self.n_layer, self.n_head, self.d_model = n_layer, n_head, d_model
        self.max_len, self.max_slots = int(max_len), int(max_slots)
        self.eos_id = eos_id
        self._donate = donate
        if ttft_slo_s is not None and ttft_slo_s <= 0:
            raise ValueError(f"ttft_slo_s must be > 0: {ttft_slo_s}")
        if e2e_slo_s is not None and e2e_slo_s <= 0:
            raise ValueError(f"e2e_slo_s must be > 0: {e2e_slo_s}")
        self.ttft_slo_s = ttft_slo_s
        self.e2e_slo_s = e2e_slo_s
        self._good_tokens = 0       # tokens of SLO-met completions
        self._first_submit_t = None  # goodput window opens here
        if compute_dtype is None:
            compute_dtype = infer_compute_dtype(params)
        self.compute_dtype = jnp.dtype(compute_dtype)
        if decode_chunk < 1 or min_bucket < 1:
            raise ValueError("decode_chunk and min_bucket must be >= 1")
        self.decode_chunk = int(decode_chunk)
        self.min_bucket = int(min_bucket)
        # the window widths prefill may compile; the last is the piece
        self._rungs = _bd.prefill_rungs(self.min_bucket, self.max_len)
        arch.check_params(params, self.max_len)
        state_spec = arch.state_spec(self.compute_dtype)
        if state_spec and prefix_reuse and not arch.planes:
            raise ValueError(
                f"prefix_reuse=True cannot serve {arch.name!r}: all "
                f"{len(state_spec)} of its layers hold recurrent state and "
                f"none a K/V plane, so there is no block pool and no trie "
                f"whose node could name a state snapshot (a trie with no "
                f"blocks is not built).  Pass prefix_reuse=False")
        if not arch.planes and cache_blocks:
            raise ValueError(
                f"cache_blocks={cache_blocks} cannot serve {arch.name!r}: "
                f"it caches no K/V plane, so there is no block pool to "
                f"give a cache budget.  Pass cache_blocks=0")
        self._p = jax.device_put(
            {k: jnp.asarray(v, self.compute_dtype)
             for k, v in params.items()})

        # -- speculative decoding (serving.speculative): with a draft
        # model and the PADDLE_TPU_SPEC switch on, decode runs
        # propose/verify/commit rounds.  Off (or no draft): none of
        # this exists — no validation, no extra pool blocks, no draft
        # executables — bit-identical to the plain engine.
        spec_on = draft_params is not None and _spec.spec_enabled()
        if spec_on:
            draft_n_layer = _spec.validate_draft(
                params, draft_params, arch, self.max_len,
                draft_n_layer=draft_n_layer,
                draft_n_head=draft_n_head)
        self.spec_k = int(spec_k) if spec_on else None

        # -- paged KV state (kvcache.py): pool arrays + host accounting
        self.block_tokens = int(block_tokens)
        self.blocks_per_slot = -(-self.max_len // self.block_tokens)
        if cache_blocks is None:
            cache_blocks = 2 * self.blocks_per_slot if prefix_reuse else 0
        if cache_blocks < 0:
            raise ValueError(f"cache_blocks must be >= 0: {cache_blocks}")
        self.cache_blocks = int(cache_blocks)
        # trash block + every slot's worst-case chain + the cache
        # budget: admission can ALWAYS allocate a full chain once the
        # trie evicts its unreferenced tail (kvcache.py invariants).
        # Speculative mode reserves a second worst-case chain per slot
        # for the draft's scratch blocks, so a propose round can never
        # starve admission.  A block id names the same block_tokens
        # positions in every one of the arch.kv_planes planes, so one
        # block costs kv_planes x what the plane's own shape holds of
        # block_tokens positions (arch.pool_block_shape in each of its
        # arch.pool_arrays: 48 MiB for 192 K and V planes of 32 x 2048
        # bf16, 6 MiB for 24, 1.05 MiB for 27 latent planes of 640
        # lanes): the pool's bytes, not its block count, are what fills
        # a chip (gauge serving.kv_pool_bytes)
        num_blocks = (1 + self.max_slots * self.blocks_per_slot
                      + self.cache_blocks)
        if spec_on:
            num_blocks += self.max_slots * self.blocks_per_slot
        if pool_blocks is not None:
            if spec_on or not (self.blocks_per_slot <= pool_blocks
                               < num_blocks - 1):
                raise ValueError(
                    f"pool_blocks {pool_blocks}: a stated pool holds at "
                    f"least one whole chain ({self.blocks_per_slot} blocks) "
                    f"and fewer than the {num_blocks - 1} that every slot's "
                    f"worst case and the cache budget reserve; a draft's "
                    f"scratch chains are not stated")
            num_blocks = 1 + int(pool_blocks)
        # TWO KINDS OF CHAIN.  A plane attended under a lower bound
        # needs, of a slot's positions, the last ``window`` only.  Where
        # the architecture says its window planes may hold just that
        # (arch.window_chains) and nothing needs them whole (a prefix hit
        # has to find the head's last window in them, a draft shares the
        # target's planes), they get a pool and a table of their own: a
        # slot holds there the blocks its window can still see and what
        # the rows in flight write, at most window_blocks of them, and
        # gives the others back (kvcache.WindowChains).  A block id then
        # names positions in the planes of its own kind only.  Admission
        # can ALWAYS allocate: a whole chain of the full planes as
        # before, and max_slots x window_blocks of the window planes'.
        windows = [w for w in arch.planes if w is not None]
        self._windowed = bool(
            arch.window_chains and not prefix_reuse and not spec_on
            and windows and len(windows) < len(arch.planes))
        self.window_chains = None
        if self._windowed:
            self.window_blocks_per_slot = _kv.window_blocks(
                max(windows), max(self._rungs[-1], self.decode_chunk),
                self.block_tokens)
            self.window_chains = _kv.WindowChains(
                _kv.BlockPool(
                    1 + self.max_slots * self.window_blocks_per_slot,
                    self.block_tokens),
                self.max_slots, self.blocks_per_slot, max(windows))
        if arch.planes:
            self.kv_pool = _kv.BlockPool(num_blocks, self.block_tokens)
        else:
            # an architecture that caches no K/V plane: no pool, no
            # table and no block to count; a slot is all a request
            # holds, and max_len bounds its positions and nothing else
            num_blocks, self.blocks_per_slot, self.kv_pool = 1, 0, None
        # A PREFIX HIT OVER RECURRENT STATE.  An architecture that holds
        # state beside its planes is served from snapshots: a prefill
        # leaves ONE, at the last block boundary on which one of its
        # pieces ends (a device-side copy of the slot's row of every
        # state array into row r of the snapshot arrays, queued behind
        # that piece); the trie node at that depth names r; a hit is cut
        # back to the deepest matched node that has one and the slot's
        # rows are written from it before the suffix's first piece.  The
        # budget is in bytes and follows the cache's: the snapshot arrays
        # may hold 1 / SNAPSHOT_SHARE of what cache_blocks blocks hold of
        # K/V, in whole snapshots, at least one.
        state_slot = arch.state_bytes_per_slot(self.compute_dtype)
        self._state_slot_bytes = state_slot
        self.snapshot_rows = 0
        if prefix_reuse and state_spec:
            self.snapshot_rows = max(1, int(
                self.cache_blocks * self.block_tokens
                * arch.kv_bytes_per_token(self.compute_dtype.itemsize)
                // SNAPSHOT_SHARE // state_slot))
        self.prefix_trie = (_kv.PrefixTrie(self.kv_pool, self.cache_blocks,
                                           self.snapshot_rows)
                            if prefix_reuse else None)
        self.prefix_reuse = bool(prefix_reuse)
        # one array a plane of arch.planes (a layer, unless the
        # architecture lists them otherwise); a stack that runs
        # arch.passes times keeps each pass's plane in its own num_blocks
        # of the block axis (batched_decode._Cache).  A plane states its
        # own block shapes (arch.plane_block_shapes: K/V heads and lanes
        # may differ by plane, a K array's lanes from its V array's) and,
        # under two kinds of chain, has the blocks of its kind's pool
        def blocks_of(i):
            if self._windowed and arch.chain_kind(i):
                return self.window_chains.pool.num_blocks
            return num_blocks

        shapes = [tuple((arch.passes * blocks_of(i),) + tuple(shape)
                        for shape in arch.plane_block_shapes(
                            i, self.block_tokens, self.compute_dtype))
                  for i in range(len(arch.planes))]
        self._pk = tuple(jnp.zeros(shape[0], self.compute_dtype)
                         for shape in shapes)
        # no V array where a position's value is lanes of its key row
        self._pv = tuple(jnp.zeros(shape[1], self.compute_dtype)
                         for shape in shapes if len(shape) > 1)
        # what a slot holds BESIDE the pool (recurrent state): arrays
        # indexed by slot, donated through the same executables; a
        # prompt's first prefill piece starts its row from zeros, so a
        # released slot's row needs no clearing
        self._state = tuple(
            tuple(jnp.zeros((self.max_slots,) + tuple(shp), dt)
                  for shp, dt in layer) for layer in state_spec)
        # the snapshots a trie node may name: the state arrays again, a
        # row a snapshot
        self._snap = tuple(
            tuple(jnp.zeros((self.snapshot_rows,) + tuple(shp), dt)
                  for shp, dt in layer)
            for layer in (state_spec if self.snapshot_rows else ()))
        self._snap_evicted = 0      # of the trie's evictions, published
        self._last = jnp.zeros((self.max_slots,), jnp.int32)
        self._pos = jnp.zeros((self.max_slots,), jnp.int32)
        # host-side block table: unused entries -> trash block 0.  With
        # no plane there is none: the compiled steps take ``[max_slots]``
        # int32 in its place, nonzero where the slot is live
        self._table = np.zeros(
            (self.max_slots, self.blocks_per_slot) if arch.planes
            else (self.max_slots,), np.int32)
        self._slot_blocks = [None] * self.max_slots  # bids a slot holds
        # when each slot's request last advanced (first token, then the
        # end of every chunk): serving.stalled_seconds counts from it
        self._slot_advanced = [0.0] * self.max_slots
        # the seconds inside prefill clock pairs so far, and what that
        # read when each slot last advanced: the difference at a chunk's
        # start is the part of the slot's wait that prompts' pieces took
        self._prefill_s = 0.0
        self._slot_prefill_s = [0.0] * self.max_slots
        # the accounts of the requests that finished, TPOT_RING most
        # recent: stats() publishes the slowest fifth's make-up from it
        self._tpot_ring = collections.deque(maxlen=TPOT_RING)
        # the decode chunks sent and not yet read, oldest first: at most
        # the one waited for and one queued behind it (_decode)
        self._chunks = collections.deque()
        self._collected_t = 0.0       # when the last chunk's tokens arrived
        self._spec = (_spec.SpecState(self, draft_params, draft_n_layer,
                                      spec_k) if spec_on else None)

        self._slots = [None] * self.max_slots     # Request or None
        self._free = list(range(self.max_slots))  # LIFO free list
        self._queue = collections.deque()
        self._completed = collections.deque()
        self._qlock = threading.Lock()    # queue/completed/counters
        self._dlock = threading.RLock()   # the device state (one driver)
        self._next_rid = 0
        self._prefill_fns = {}            # window width -> compiled fn
        self._decode_fn = None
        # entry-point label -> the kernel-backend selections the kernel
        # registry recorded while that executable traced (so operators
        # can see WHICH paged-attention backend each compile used)
        self.kernel_backends = {}
        # entry-point label -> seconds its one lower().compile() took
        self.compile_seconds = {}
        self._thread = None
        self._stop = threading.Event()
        self._error = None                # fatal error: engine is dead
        self._inflight = 0                # popped from queue, not yet
                                          # slotted (visible to idle)
        self._req_lane_ends = []          # trace lane i -> last finish_t
        # the SLO control loop: measured-latency predictor + scheduler
        self.predictor = _sched.TtftPredictor()
        self._sched = _sched.make_scheduler(scheduler, self.predictor,
                                            budgets=self)
        # prefix-hit accounting window (reset with the goodput window)
        self._hit_tokens = 0
        self._prompt_tokens = 0

        self._reg = registry or _obs.get_registry()
        itemsize = self.compute_dtype.itemsize
        self._reg.gauge("serving.slots_total").set(self.max_slots)
        self._reg.gauge("serving.slots_active").set(0)
        self._reg.gauge("serving.queue_depth").set(0)
        self._reg.gauge(
            "serving.kv_blocks_total",
            help="physical KV blocks in the paged pool (excl. trash)",
        ).set(num_blocks - 1 + (
            self.window_chains.pool.num_blocks - 1 if self._windowed else 0))
        self._reg.gauge("serving.blocks_in_use").set(0)
        self._reg.gauge(
            "serving.kv_planes",
            help="K/V planes a cached token holds (layers x passes of "
                 "the stack)").set(arch.kv_planes)
        for kind, n in (("window", sum(w is not None for w in arch.planes)),
                        ("full", sum(w is None for w in arch.planes))):
            self._reg.gauge(
                "serving.kv_planes", kind=kind,
                help="of them, planes attended under a lower bound "
                     "(window) and planes attended whole (full)",
            ).set(n * arch.passes)
        self._reg.gauge(
            "serving.kv_heads",
            help="K/V heads a plane holds of each position").set(
                arch.kv_heads)
        # by kind of plane, where the planes are not alike; per token: a
        # full plane a position, a window plane a slot's constant
        by_kind = {}
        for i, w in enumerate(arch.planes):
            by_kind.setdefault("full" if w is None else "window", i)
        for kind, i in by_kind.items():
            self._reg.gauge(
                "serving.kv_heads", kind=kind,
                help="K/V heads a plane of this kind holds of each "
                     "position").set(arch.plane_kv_heads(i))
            one = arch.plane_block_bytes(i, 1, itemsize)
            self._reg.gauge(
                "serving.kv_bytes_per_token", kind=kind,
                help="bytes of what the model caches in ONE plane of this "
                     "kind: kind=full a cached position; kind=window, "
                     "under window chains, what a SLOT holds whatever its "
                     "context (the window's positions), else a position",
            ).set(one * (arch.planes[i] if self._windowed
                         and arch.planes[i] else 1))
        if self._windowed:
            self._reg.gauge(
                "serving.window_blocks_per_slot",
                help="blocks a slot can hold at most in the window planes' "
                     "chain: the window and the widest run of rows in "
                     "flight (a prefill piece), kvcache.window_blocks",
            ).set(self.window_blocks_per_slot)
        self._reads_per_token = sum(n for _, n in arch.plane_reads)
        self._reg.gauge(
            "serving.plane_reads_per_token",
            help="paged-attention calls one token makes (a plane that "
                 "several layers read counts once a reader)").set(
                     self._reads_per_token)
        self._reg.gauge(
            "serving.state_bytes_per_slot",
            help="bytes of recurrent state a slot holds beside the pool "
                 "(fixed, whatever the context)").set(state_slot)
        self._reg.gauge(
            "serving.state_bytes",
            help="bytes the per-slot state arrays hold on the device",
        ).set(state_slot * self.max_slots)
        if self.snapshot_rows:
            self._reg.gauge(
                "serving.state_snapshot_bytes",
                help="bytes the state-snapshot arrays hold on the device: "
                     "snapshot rows x state_bytes_per_slot (the budget: 1 / "
                     "SNAPSHOT_SHARE of the K/V bytes of cache_blocks)",
            ).set(state_slot * self.snapshot_rows)
            # compiled here, not at the first hit: a hit's first is in
            # the serving window
            self._snap_take = self._aot_with_mem_telemetry(
                _bd.make_state_copy(self._donate), "state_snapshot_take")
            self._snap_restore = self._aot_with_mem_telemetry(
                _bd.make_state_copy(self._donate), "state_snapshot_restore")
            zero = np.int32(0)
            self._snap_take.prepare(self._snap, self._state, zero, zero)
            self._snap_restore.prepare(self._state, self._snap, zero, zero)
        self._reg.gauge(
            "serving.stack_passes",
            help="times the stack runs over the same weights for one "
                 "token").set(arch.passes)
        self._reg.gauge(
            "serving.kv_bytes_per_token",
            help="bytes of one cached token across its planes (K and V, "
                 "or the one latent row a plane stores)",
        ).set(arch.kv_bytes_per_token(itemsize))
        self._reg.gauge(
            "serving.kv_write_fill",
            help="values of a pool row that carry what the model caches "
                 "/ values a write covers: a write covers the pool's "
                 "whole row (kernels.paged_attention.write), the rows "
                 "pool_rows added, the lanes latent_lanes added, as zeros",
        ).set(sum(sum(arch.plane_written_values(i))
                  for i in range(len(arch.planes)))
              / sum(int(np.prod(shape)) // shape[arch.plane_tokens_axis(i)]
                    for i in range(len(arch.planes))
                    for shape in arch.plane_block_shapes(
                        i, self.block_tokens, self.compute_dtype))
              if self._pk else 0.0)
        self._reg.gauge(
            "serving.kv_pool_bytes",
            help="bytes the paged pool holds on the device: planes x "
                 "blocks (trash included) x block bytes",
        ).set(sum(a.nbytes for a in self._pk + self._pv))
        self._arch_gauges = arch.gauges(self._p)
        self._publish_arch_gauges()
        # (window, calls, bytes a cached position) of the paged calls a
        # token makes, for _count_paged_entries
        # and the query rows a call sends through an entry and the
        # plane's block shapes, each the fact of a plane with that bound
        bound = {}
        for i, w in enumerate(arch.planes):
            bound.setdefault(w, i)

        def seen(i):
            """Plane ``i``'s block shapes as the paged kernel sees them:
            a head-major plane (``plane_tokens_axis`` 1) is walked a K/V
            head at a time, slabs ``[B, None, lanes]`` with no head axis
            (``paged_attention.loop_iterations``)."""
            shapes = arch.plane_block_shapes(i, self.block_tokens,
                                             self.compute_dtype)
            if arch.plane_tokens_axis(i):
                shapes = tuple((B, None, lanes) for _, B, lanes in shapes)
            return shapes

        self._plane_reads = [
            (w, n, arch.plane_block_bytes(bound[w], 1, itemsize),
             arch.plane_rows_per_entry(bound[w]), seen(bound[w]))
            for w, n in arch.plane_reads]
        # (window, calls, folded query rows a position) of a prefill
        # piece's calls on K/V planes, for _count_prefill_entries (a
        # head-major plane has no wide spelling: its rows walk slabs)
        self._piece_reads = [] if arch.latent_planes else [
            (w, n, arch.plane_rows_per_entry(bound[w]) * seen(bound[w])[0][1])
            for w, n in arch.plane_reads
            if not arch.plane_tokens_axis(bound[w])]
        # span attributes that say in which form attention runs, for an
        # architecture with latent planes or with retention layers
        self._form_attrs = (
            dict(latent_planes=arch.latent_planes, attn_form=arch.attn_form,
                 **(dict(index_planes=arch.index_planes,
                         latent_window_planes=(arch.latent_planes
                                               - arch.index_planes))
                    if arch.index_planes else {}))
            if arch.latent_planes else
            dict(retention_layers=arch.retention_layers,
                 attn_form=arch.attn_form)
            if arch.retention_layers else
            dict(kv_kinds=1 + self._windowed,
                 sink_planes=arch.sink_planes)
            if arch.sink_planes else
            dict(ssm_layers=arch.ssm_layers)
            if arch.ssm_layers else
            dict(delta_layers=arch.delta_layers)
            if arch.delta_layers else
            dict(sparse_layers=arch.sparse_layers,
                 lightning_layers=arch.lightning_layers,
                 sparse_topk=arch.sparse["topk"])
            if arch.sparse_layers or arch.lightning_layers else {})

    @property
    def _tracer(self):
        # resolved per call, not bound at construction, so a tracer
        # installed via trace.set_tracer() after the engine exists (the
        # test pattern) still receives the request span trees
        return _trace.get_tracer()

    def _span(self, name, phase, of=None, histogram=None, event=True,
              **attrs):
        """One driver-loop span: a timeline event and profiler annotation
        whose self seconds land in ``serving.driver_seconds{phase=...}``
        of the engine's registry — the driver thread is always inside
        one (``serving.idle``, or a ``serving.step`` whose own seconds
        are ``phase=loop`` and whose children are the rest), so the
        phases sum to its wall time.  ``of`` tells a fetch inside a
        prefill from one inside a decode chunk."""
        labels = {"phase": phase} if of is None else {"phase": phase,
                                                       "of": of}
        return self._tracer.span(
            name, cat="serving", registry=self._reg, histogram=histogram,
            counter=("serving.driver_seconds", labels), event=event,
            **attrs)

    def _account_stall(self, live, t0, t1, steps, rode):
        """A decode chunk's clock pair is ``[t0, t1]``: every request it
        advanced (``live``, ``{slot: request}``) was last advanced at its
        previous chunk's collect (or its first token), waited until
        ``t0`` while the device had no chunk of its to run, and is
        advanced again at ``t1``.  Between back-to-back chunks ``t0`` IS
        the previous collect, so nothing is stalled.  The chunk ran
        ``steps`` steps for ``rode`` rows (the live ones and those whose
        request had ended by the time it was read).

        Each request's account (``Request.chunk_s`` ...) grows by the
        chunk and by the wait, split in the part that lay inside some
        request's prefill clock pair and the rest; the chunk's step
        against its rows feeds ``serving.chunk_fit``.  Returns the
        longest wait a live request met before this chunk and the
        prefill's part of it, in seconds."""
        chunk = t1 - t0
        stalled = live_s = longest = longest_prefill = 0.0
        for s, req in live.items():
            wait = t0 - self._slot_advanced[s]
            # the pairs lie inside the wait: min() only against rounding
            in_prefill = min(self._prefill_s - self._slot_prefill_s[s], wait)
            req.chunk_s += chunk
            req.stall_prefill_s += in_prefill
            req.stall_host_s += wait - in_prefill
            req.steps += steps
            req.slot_steps += rode * steps
            if wait > longest:
                longest, longest_prefill = wait, in_prefill
            stalled += wait
            live_s += t1 - self._slot_advanced[s]
            self._slot_advanced[s] = t1
            self._slot_prefill_s[s] = self._prefill_s
        self._reg.counter(
            "serving.stalled_seconds",
            help="seconds decoding requests waited between their chunks "
                 "while the device had none of theirs to run: inside "
                 "another request's prefill clock pair (its pieces and "
                 "its first-token fetch: a request's stall_prefill_s) or "
                 "behind the driver's own work (the queue pick, the trie, "
                 "block allocation, the table and the dispatch; an emit "
                 "only where no chunk was queued behind the one read: "
                 "stall_host_s)").inc(max(0.0, stalled))
        self._reg.counter(
            "serving.live_seconds",
            help="seconds decoding requests spent from one advance to "
                 "the next (both parts of their waits + their chunks): "
                 "stalled_seconds' denominator").inc(max(0.0, live_s))
        # w = base + slope x a over every chunk of the window: five sums
        w = chunk / steps
        for key, v in (("n", 1.0), ("a", rode), ("aa", rode * rode),
                       ("w", w), ("aw", rode * w)):
            self._reg.counter(
                "serving.chunk_fit", sum=key,
                help="sums over the decode chunks collected, for the "
                     "least-squares line of a step's seconds (w: clock "
                     "pair / steps) against the rows the chunk stepped "
                     "(a): n, a, a*a, w, a*w").inc(v)
        self._reg.gauge(
            "serving.longest_stall_seconds",
            help="the longest single wait before a chunk that any live "
                 "request met since the last accounting reset"
        ).set_max(longest)
        return longest, longest_prefill

    def _count_prefill_entries(self, pieces):
        """Prefill pieces were dispatched: for every call that WALKS its
        chain (``kernels.paged_attention.walks_chain``: a wide window
        over a K/V plane), the table entries it had to visit (from the
        entry of the first row's lower bound to the entry of the piece's
        last position) beside the entries the dense spelling of the same
        call gathers and scores whatever the context
        (``paged_attention.dense_entries``: the whole chain, or a lower
        bound's own entries where it goes one K/V head at a time)."""
        B, NB = self.block_tokens, self.blocks_per_slot
        attended = dense = 0
        for w, _, at, _ in pieces:
            for window, n, rows in self._piece_reads:
                if not _paged.walks_chain(w, rows, NB * B):
                    continue
                first = 0 if window is None else max(at - window + 1, 0)
                last = min(at + w, NB * B) - 1
                attended += n * (last // B - first // B + 1)
                dense += n * _paged.dense_entries(w, rows, NB, B, window)
        if dense:
            text = ("table entries of the prefill pieces' chain walks, a "
                    "call: kind=attended from a piece's lower bound to its "
                    "last position, kind=chain what the dense spelling of "
                    "the call gathers (the whole chain, or a lower bound's "
                    "own entries one K/V head at a time)")
            self._reg.counter("serving.prefill_entries", kind="attended",
                              help=text).inc(attended)
            self._reg.counter("serving.prefill_entries", kind="chain",
                              help=text).inc(dense)

    def _shared_runs(self, contexts):
        """Which of the live slots' chains START alike (``contexts`` as
        ``_count_paged_entries`` takes them), for the decode calls of an
        architecture whose planes fetch such a run once
        (``arch.shares_runs``) in an engine whose trie hands several slots
        one block: ``kernels.paged_attention.shared_runs`` over the
        host's table, the entries whole under each slot's position; else
        ``None``, and the decode chunk is the program without it."""
        if not self.arch.shares_runs or self.prefix_trie is None:
            return None
        whole = np.zeros(self.max_slots, np.int64)
        for s, ctx in contexts:
            whole[s] = (ctx - 1) // self.block_tokens
        return _paged.shared_runs(self._table, whole,
                                  self.arch.rows_per_entry)

    def _count_paged_entries(self, contexts, runs=None):
        """A decode chunk is being sent for ``contexts``, ``[(slot, keys
        its first step attends)]`` of the rows live in it (``prompt +
        _sent``: the slot's DISPATCHED position): of the ``max_slots x
        blocks_per_slot`` table entries each paged-attention call spans,
        how many hold such a key (the last and everything before it down
        to the plane's lower bound), as the mean over the calls a
        token makes; the query rows a call sends through each, the
        softmax updates the kernel makes for them and the iterations its
        loop makes over them; and the K/V bytes those calls have to
        read; with ``runs`` (``_shared_runs``) the entries the calls
        were told to fetch, a shared run once.  For retention layers,
        which have no table: the states the chunk's steps read and
        write."""
        if self.arch.retention_layers:
            self._reg.counter(
                "serving.retention_slot_steps",
                help="states a decode chunk's retention calls read and "
                     "wrote in place: live slots x retention layers x the "
                     "chunk's steps (a slot that finishes inside a chunk "
                     "rides it out on the device)").inc(
                         len(contexts) * self.arch.retention_layers
                         * self.decode_chunk)
        if self.arch.delta_layers:
            self._reg.counter(
                "serving.delta_slot_steps",
                help="states a decode chunk's delta-rule calls read and "
                     "wrote in place: live slots x delta layers x the "
                     "chunk's steps").inc(
                         len(contexts) * self.arch.delta_layers
                         * self.decode_chunk)
        if self.arch.lightning_layers:
            self._reg.counter(
                "serving.lightning_calls", fresh="0", phase="decode",
                help="calls of the constant-decay recurrence a slot: live "
                     "slots x lightning layers x a decode chunk's steps "
                     "(phase=decode), a prefill piece x lightning layers "
                     "(phase=prefill; fresh=1 started a prompt and never "
                     "read the slot's state)").inc(
                         len(contexts) * self.arch.lightning_layers
                         * self.decode_chunk)
        if not self.arch.planes:
            return                  # no table entry, no K/V byte to count
        B = self.block_tokens
        live = streamed = shared = rows_live = updates = iterations = 0
        live_by_kind = {"full": 0, "window": 0}
        # only the trie hands two slots one block
        sharing = self.prefix_trie is not None and len(contexts) > 1
        for window, n, token_bytes, rows, shapes in self._plane_reads:
            chains = []
            for s, ctx in contexts:                   # ctx keys attended
                first = 0 if window is None else max(ctx - window, 0)
                one = (ctx - 1) // B - first // B + 1     # a call's
                entries = n * one
                live += entries
                live_by_kind["full" if window is None else "window"] += entries
                rows_live += entries * rows
                updates += entries * _paged.softmax_updates(rows)
                iterations += n * _paged.loop_iterations(
                    one, rows, shapes, self.compute_dtype,
                    self.blocks_per_slot, window)
                streamed += n * (ctx - first) * token_bytes
                if sharing:
                    chains.append(
                        self._table[s, first // B:(ctx - 1) // B + 1])
            if sharing:
                # a slot's chain names a block once, so a block named
                # twice is named by two slots
                ids = np.concatenate(chains)
                shared += n * int(np.sum(np.bincount(ids)[ids] > 1))
        if self.arch.latent_planes:
            # every step of the chunk attends one position more, a slot
            # that finishes inside the chunk rides it out on the device
            self._count_latent_positions(
                "decode", [(ctx, self.decode_chunk) for _, ctx in contexts],
                calls=self.decode_chunk)
            self._count_sparse_slots(len(contexts))
        self._reg.counter(
            "serving.paged_entries_live",
            help="block-table entries a paged-attention call had to "
                 "visit, summed over decode chunks (live slots, from "
                 "the plane's lower bound up to each one's position at "
                 "the chunk's start; the mean over a token's calls)",
        ).inc(live / self._reads_per_token)
        if runs is not None:
            # a run's leader row holds its members' count: every member
            # after the first is spared the run's entries, a full plane
            again = int(np.sum(runs[:, 0] * np.maximum(runs[:, 1] - 1, 0)))
            self._reg.counter(
                "serving.paged_entries_fetched", phase="decode",
                help="of paged_entries_live, the entries the decode calls "
                     "were told to fetch: a run of entries several live "
                     "slots share (kernels.paged_attention.shared_runs) "
                     "once for all of them").inc(
                         (live - again * sum(
                             n for window, n, *_ in self._plane_reads
                             if window is None)) / self._reads_per_token)
        if self._windowed:
            for kind, entries in live_by_kind.items():
                self._reg.counter(
                    "serving.paged_entries_live", kind=kind,
                    help="of paged_entries_live, the entries of the calls "
                         "on planes of this kind (their sum, not the mean "
                         "over a token's calls)").inc(entries)
            # what the window planes' chains hold now, and what whole
            # chains would hold of the same slots: a block for every
            # entry up to each slot's position
            self._reg.counter(
                "serving.window_blocks_held",
                help="blocks the live slots hold in the window planes' "
                     "chain, summed over decode chunks").inc(
                         sum(self.window_chains.held(s)
                             for s, _ in contexts))
            self._reg.counter(
                "serving.window_blocks_whole",
                help="blocks whole chains would hold of the same slots "
                     "(every entry up to each slot's position), summed "
                     "over decode chunks: window_blocks_held's "
                     "denominator").inc(
                         sum((ctx - 1) // B + 1 for _, ctx in contexts))
        self._reg.counter(
            "serving.paged_entries_shared",
            help="of paged_entries_live, the entries whose block more "
                 "than one live slot's table names (a shared prefix of "
                 "the trie): what a read that fetches a chain once for "
                 "the slots that share it would not fetch again",
        ).inc(shared / self._reads_per_token)
        self._reg.counter(
            "serving.paged_rows_live",
            help="query rows the paged-attention calls sent through the "
                 "entries of paged_entries_live: a decode call folds the "
                 "query heads of one K/V row into that many rows of its "
                 "window (arch.rows_per_entry)",
        ).inc(rows_live / self._reads_per_token)
        self._reg.counter(
            "serving.paged_updates_live",
            help="online-softmax updates the paged kernel made for the "
                 "entries of paged_entries_live "
                 "(kernels.paged_attention.softmax_updates of the rows a "
                 "call sends through an entry): paged_rows_live's "
                 "denominator",
        ).inc(updates / self._reads_per_token)
        self._reg.counter(
            "serving.paged_iterations_live",
            help="iterations the paged kernel's loop made over the "
                 "entries of paged_entries_live "
                 "(kernels.paged_attention.loop_iterations of each live "
                 "slot's entries: a group of table entries an iteration "
                 "where rows share a fold or the plane is a latent one, "
                 "an entry an iteration else); the mean over a token's "
                 "calls",
        ).inc(iterations / self._reads_per_token)
        self._reg.counter(
            "serving.paged_entries_total",
            help="block-table entries a paged-attention call spans "
                 "(max_slots x blocks_per_slot), summed over decode "
                 "chunks: paged_entries_live's denominator").inc(
                     self.max_slots * self.blocks_per_slot)
        self._reg.counter(
            "serving.paged_bytes_streamed",
            help="K/V bytes the paged-attention calls of one decode "
                 "step have to read for the live slots (clipped to each "
                 "plane's window, a shared plane once a reader), at "
                 "every decode chunk's first step").inc(streamed)

    def _count_latent_positions(self, phase, runs, calls):
        """``runs``: ``[(positions the first row attends at most, rows)]``
        of consecutive rows, each attending one position more than the
        row before it (a slot's steps of a decode chunk, a prefill
        piece's real rows); ``calls`` the attention calls a latent plane
        made for them.  Counted by how many cached positions a plane
        lets a row read (``arch.latent_reads``: every one, an indexer's
        ``index_topk``, a window)."""
        read = scored = picked = 0
        index_planes = self.arch.index_planes
        for first, rows in runs:
            at = np.arange(first, first + rows, dtype=np.int64)
            for n, bound in self.arch.latent_reads:
                read += n * int((at if bound is None
                                 else np.minimum(at, bound)).sum())
            if index_planes:
                scored += int(at.sum())
                picked += int(np.minimum(at, self.arch.index_topk).sum())
        self._reg.counter(
            "serving.latent_positions_read", phase=phase,
            help="cached positions the latent planes' attention read: "
                 "positions attended x latent planes, every step of a "
                 "decode chunk (phase=decode), every real row of a "
                 "prefill piece (phase=prefill); a plane under a lower "
                 "bound or an indexer counts what it lets a row attend"
        ).inc(read)
        if not index_planes:
            return
        self._reg.counter(
            "serving.index_positions_scored", phase=phase,
            help="cached positions whose index key a full plane's "
                 "indexer scored: every position up to the row's own x "
                 "index planes").inc(index_planes * scored)
        self._reg.counter(
            "serving.sparse_positions_attended", phase=phase,
            help="cached positions the full planes' attention read after "
                 "the selection: min(positions, index_topk) x index "
                 "planes").inc(index_planes * picked)
        self._reg.counter(
            "serving.latent_window_calls", phase=phase,
            help="attention calls on latent planes under a lower bound: "
                 "a decode chunk's steps or a prefill's pieces x such "
                 "planes").inc(
                     calls * (self.arch.latent_planes - index_planes))

    def _count_sparse_slots(self, live):
        """A decode chunk's sparse calls (one a full plane a step, where
        the table is wider than ``index_topk``: ``sparse_attend``) for
        ``live`` slots: the slots that were live and the slots the calls
        ran their three steps for (``sparse_attention.slots_run``)."""
        calls = self.arch.index_planes * self.decode_chunk
        if (not calls or self.blocks_per_slot * self.block_tokens
                <= self.arch.index_topk):
            return
        self._reg.counter(
            "serving.sparse_slots_live",
            help="live slots of the decode chunks' sparse calls: live "
                 "slots x steps x index planes").inc(live * calls)
        self._reg.counter(
            "serving.sparse_slots_run",
            help="slots the decode chunks' sparse calls scored, selected "
                 "and gathered for (sparse_attention.slots_run of the "
                 "live ones) x steps x index planes: sparse_slots_live's "
                 "denominator").inc(
                     _sparse.slots_run(live, self.max_slots) * calls)

    def _count_tallies(self, phase, counts):
        """What the compiled steps of one decode chunk or one
        admission's prefill pieces tallied beside their tokens
        (``arch.count_names``; a list of device vectors), fed to the
        counters ``serving.<name>{phase}``."""
        total = np.sum([np.asarray(c) for c in counts], axis=0)
        for name, value in zip(self.arch.count_names, total):
            # a name may carry labels: (name, ((label, value), ...))
            name, labels = name if isinstance(name, tuple) else (name, ())
            self._reg.counter(
                "serving." + name, phase=phase, **dict(labels),
                help="what the architecture's stack tallied a step, "
                     "summed over steps (arch.count_names; arch.GatedMoE: "
                     "live rows, row-expert pairs on a held expert, held "
                     "experts with a live row, held experts visited, each "
                     "x routed layers; arch.SparseLightning: (row, K/V "
                     "head) pairs that read densely or selected, the "
                     "blocks selected and cached, compressed rows "
                     "written)").inc(int(value))

    # -- request intake ---------------------------------------------------
    def submit(self, prompt, max_new_tokens=16, eos_id=None,
               ttft_slo_s=None, e2e_slo_s=None, sheddable=True):
        """Queue one request; returns its ``Request`` handle.  Thread-safe
        (producers may submit while the engine decodes).  Per-request
        ``ttft_slo_s``/``e2e_slo_s`` budgets override the engine
        defaults for both the SLO verdict and the scheduler;
        ``sheddable=False`` exempts the request from scheduler shedding
        (it is still judged at finish)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        p_len = prompt.shape[0]
        if p_len < 1:
            raise ValueError("empty prompt")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1: {max_new}")
        if p_len + max_new > self.max_len:
            raise ValueError(
                f"prompt ({p_len}) + max_new_tokens ({max_new}) exceeds "
                f"the slot KV capacity max_len={self.max_len}")
        t = self._thread
        if t is not None and not t.is_alive() and not self._stop.is_set():
            # started driver died (supervision normally aborts first,
            # which the _error check below catches; this closes the
            # window where the thread is gone but the abort hasn't
            # landed) — never queue onto a dead driver
            raise RuntimeError(
                "serving driver thread is dead") from self._error
        with self._qlock:
            # _error is set under _qlock in _abort, so checking it here
            # closes the submit-after-abort window (a request appended
            # after the abort drained the queue would hang forever)
            if self._error is not None:
                raise RuntimeError(
                    "serving engine aborted") from self._error
            rid = self._next_rid
            self._next_rid += 1
            req = Request(rid, prompt, max_new,
                          self.eos_id if eos_id is None else eos_id,
                          ttft_slo_s=ttft_slo_s, e2e_slo_s=e2e_slo_s,
                          sheddable=sheddable)
            if self._first_submit_t is None:
                self._first_submit_t = req.submit_t
            self._queue.append(req)
            self._reg.gauge("serving.queue_depth").set(len(self._queue))
        return req

    def results(self, block=False, timeout=None):
        """Drain finished requests (FIFO completion order; aborted and
        shed requests surface here too, with ``error`` set).  With
        ``block=True``, waits up to ``timeout`` seconds for at least one
        (``timeout=0`` = poll once; ``None`` = wait indefinitely)."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        while True:
            with self._qlock:
                out = list(self._completed)
                self._completed.clear()
            if out or not block:
                return out
            if deadline is not None and time.monotonic() >= deadline:
                return []
            time.sleep(0.001)

    # -- scheduler --------------------------------------------------------
    @property
    def active_slots(self):
        return self.max_slots - len(self._free)

    @property
    def idle(self):
        with self._qlock:
            pending = bool(self._queue) or self._inflight > 0
        # a chunk in flight is work outstanding, whoever its rows were for
        return (not pending and self.active_slots == 0
                and not self._chunks)

    def step(self):
        """One scheduler iteration: admit queued requests into free slots
        (scheduler-ordered, bucketed suffix prefill), SEND decode chunks
        until one is queued behind the one waited for (while a slot is
        still live after them), then READ the oldest chunk's tokens
        (``_decode``).  So the tokens a chunk computes reach their
        requests one iteration after it was sent, and ``idle`` is False
        while any are on the device.  Returns the number of requests
        finished this iteration (shed requests count — they completed,
        with ``error`` set).

        A device error mid-step leaves the donated pool unusable, so
        it is fatal: the engine aborts — every queued and in-flight
        request completes with ``error`` set (waiters wake instead of
        hanging) and further ``submit``/``step`` calls raise."""
        if self._error is not None:
            raise RuntimeError("serving engine aborted") from self._error
        # the step's own seconds (phase=loop) are what lies between its
        # phase spans: the lock, the table upload, the stall accounting
        with self._span("serving.step", "loop"), self._dlock:
            try:
                with self._span("serving.admit", "admit"):
                    finished = self._admit()
                if self.active_slots or self._chunks:
                    finished += self._decode()
            except Exception as e:
                self._abort(e)
                raise
        return finished

    def _abort(self, exc):
        """Fail every pending request and mark the engine dead."""
        with self._qlock:
            self._error = exc
            self._inflight = 0
            pending = list(self._queue)
            self._queue.clear()
            for s, req in enumerate(self._slots):
                if req is not None:
                    pending.append(req)
                    self._slots[s] = None
                for b in self._slot_blocks[s] or ():
                    self.kv_pool.deref(b)
                self._slot_blocks[s] = None
                if self._windowed:
                    self.window_chains.release(s)
                if self._spec is not None:
                    self._spec.release(self, s)
            self._table[:] = 0
            self._free = list(range(self.max_slots))
            # a chunk in flight dies with the engine (the donated pool it
            # runs on is not usable again): every request it names is
            # failed here or had finished before
            self._chunks.clear()
            for req in pending:
                req.error = exc
                req.finish_t = time.perf_counter()
                self._completed.append(req)
            self._reg.gauge("serving.queue_depth").set(0)
            self._reg.gauge("serving.slots_active").set(0)
            self._reg.counter("serving.aborted").inc(len(pending))
        for req in pending:
            req._done.set()
        # post-mortem: the abort (device error mid-step or driver
        # death) dumps the flight bundle — recent spans carry the
        # request/decode timeline that led here
        _flight.dump("serving_abort",
                     error=f"{type(exc).__name__}: {exc}"[:300],
                     failed_requests=len(pending))

    def run_until_idle(self):
        """Drive ``step`` until the queue and every slot are empty."""
        n = 0
        while not self.idle:
            n += self.step()
        return n

    def generate_many(self, prompts, max_new_tokens=16, eos_id=None):
        """Synchronous batch front-end: submit every prompt, run to
        completion, return one prompt+generated int32 array per prompt
        (order preserved).  ``max_new_tokens`` may be a scalar or a
        per-prompt sequence."""
        if np.ndim(max_new_tokens) == 0:
            max_new_tokens = [max_new_tokens] * len(prompts)
        if len(max_new_tokens) != len(prompts):
            raise ValueError(
                f"max_new_tokens has {len(max_new_tokens)} entries for "
                f"{len(prompts)} prompts")
        # unsheddable: this caller waits for EVERY result, so a
        # deadline shed could only destroy the batch's other outputs —
        # budgets still judge each request at finish (slo_ok)
        reqs = [self.submit(p, m, eos_id, sheddable=False)
                for p, m in zip(prompts, max_new_tokens)]
        self.run_until_idle()
        # drain OWN handles from the completion queue (a concurrent
        # submit()+results() producer must still see its completions)
        mine = {id(r) for r in reqs}
        with self._qlock:
            kept = [r for r in self._completed if id(r) not in mine]
            self._completed.clear()
            self._completed.extend(kept)
        return [r.result(timeout=0) for r in reqs]

    # -- background driver ------------------------------------------------
    def start(self):
        """Run the scheduler loop on a daemon thread until ``stop()``.

        The driver is SUPERVISED: if the thread dies for ANY reason —
        not just a device error ``step()`` already turns into an abort,
        but any exception escaping the loop itself (``BaseException``
        included) — every queued and in-flight request is failed with
        the captured exception, so ``Request.result(timeout=None)``
        wakes instead of hanging forever and later ``submit()`` calls
        raise immediately."""
        if self._thread is not None:
            raise RuntimeError("engine already started")
        self._stop.clear()

        def loop():
            try:
                while not self._stop.is_set():
                    if self.idle:
                        # closed every ~20 ms, so that the idle counter
                        # and a profiler session that starts mid-stretch
                        # are at most that far behind; counter and
                        # annotation only, or an idle engine would wrap
                        # the event buffer and drop its last burst (on
                        # the timeline idle is the gap between steps)
                        with self._span("serving.idle", "idle",
                                        event=False):
                            for _ in range(20):
                                time.sleep(0.001)
                                if not self.idle or self._stop.is_set():
                                    break
                        continue
                    self.step()
            except BaseException as e:  # noqa: BLE001 — supervision:
                # the driver is dying; step() aborts on Exception itself
                # (self._error set), anything else must not strand the
                # pending requests behind a silently-dead thread
                if self._error is None:
                    self._abort(e)
                self._reg.counter(
                    "serving.driver_deaths",
                    help="serving driver threads that died (requests "
                         "failed over, not stranded)").inc()

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="pt-serving-engine")
        self._thread.start()

    def driver_alive(self):
        """True while the background driver thread is running."""
        t = self._thread
        return t is not None and t.is_alive()

    def stop(self, drain=True):
        """Stop the background loop (``drain=True`` serves out queued and
        active work first; a dead or aborted driver ends the drain
        immediately — its pending requests are already failed)."""
        if self._thread is None:
            return
        if drain:
            while not self.idle:
                if self._error is not None or not self._thread.is_alive():
                    break  # nothing will ever drain the rest
                time.sleep(0.001)
        self._stop.set()
        self._thread.join()
        self._thread = None

    # -- internals --------------------------------------------------------
    def _aot_with_mem_telemetry(self, fn, label):
        """Wrap a jitted entry point so its FIRST call compiles AOT
        (``lower().compile()`` — the same single compile the lazy jit
        path would do) and the executable's ``memory_analysis()`` lands
        in the ``serving.hbm_high_water_bytes`` / ``serving.temp_bytes``
        gauges; later calls reuse the executable.  Every call site feeds
        fixed shapes (bucketed prefill, the decode chunk), so the AOT
        executable serves all of them.  A compile error (a kernel the
        compiler refuses) propagates: the engine aborts rather than
        serve through some other spelling."""
        from .. import kernels as _kernels
        from ..analysis.hlo_tools import compiled_memory_stats

        box = {}

        def prepare(*args):
            # compile (once) SEPARABLY from execution: call sites that
            # time their call and feed the wall into the scheduler's
            # latency predictor invoke this first, outside the timed
            # window — an EMA seeded with a one-time compile wall would
            # shed every arrival against a regime that no longer exists
            if "c" in box:
                return
            _kernels.reset_selected()
            t0 = time.perf_counter()
            c = fn.lower(*args).compile()
            self.compile_seconds[label] = time.perf_counter() - t0
            # which kernel backend this executable traced with, per
            # entry point
            sel = _kernels.selected_backends()
            if sel:
                self.kernel_backends[label] = sel
            box["c"] = c
            # the device half of the span primitive: the executable, for
            # whoever asks which sub-layer an instruction belongs to
            _trace.register_executable(label, c)
            stats = compiled_memory_stats(c)
            if stats:
                self._reg.gauge(
                    "serving.hbm_high_water_bytes", label=label,
                    help="compiled-executable HBM high-water "
                         "(memory_analysis)",
                ).set_max(stats["hbm_high_water_bytes"])
                self._reg.gauge(
                    "serving.temp_bytes", label=label,
                    help="compiled-executable HLO temp bytes",
                ).set_max(stats["temp_bytes"])

        def call(*args):
            prepare(*args)
            return box["c"](*args)

        # executable count, same contract as jit's _cache_size(): the
        # compile-bound tests assert exactly one per entry point
        call._cache_size = lambda: int("c" in box)
        call.prepare = prepare
        return call

    def _piece_widths(self, n):
        """Window widths that prefill a suffix of ``n`` tokens
        (``batched_decode.piece_widths`` on this engine's rungs)."""
        return _bd.piece_widths(n, self._rungs)

    def bucket_for(self, p_len):
        """Padded tokens prefill computes for a (suffix) length: the
        sum of its window widths.  Up to the piece width that is the
        one rung; two lengths with the same value run the same
        executables."""
        return sum(self._piece_widths(p_len))

    def _pieces(self, toks, start):
        """The window calls that prefill ``toks`` at positions
        ``start..``: ``[(width, tokens [width] padded, position of the
        first token, real tokens)]``."""
        import jax.numpy as jnp

        out, off = [], 0
        for w in self._piece_widths(len(toks)):
            n = min(w, len(toks) - off)
            padded = np.zeros(w, np.int32)
            padded[:n] = toks[off:off + n]
            out.append((w, jnp.asarray(padded), start + off, n))
            off += n
        return out

    def _run_pieces(self, fn_of, params, pk, pv, slot, row, pieces,
                    cow=(0, 0), compile_only=False, tally=None,
                    snapshot=None):
        """Dispatch ``pieces`` in order through the prefill executables
        ``fn_of(width)``, each attending what the earlier ones wrote;
        the CoW fork rides in the first.  Nothing is fetched: returns
        ``(pool_k', pool_v', first_tok)`` of the LAST piece, still on
        the device.  ``row`` is the slot's table row, or a list of them,
        one a piece (two kinds of chain: the window planes' row moves
        from piece to piece).  ``compile_only`` builds what is not compiled yet
        and runs nothing.  ``tally`` (a list) receives what each piece's
        stack counted, on the device too.  ``snapshot = (i, r)`` queues,
        behind piece ``i``, the copy of the slot's state into row ``r`` of
        the snapshot arrays."""
        first = None
        rows = row if isinstance(row, list) else [row] * len(pieces)
        for i, (w, toks, at, n) in enumerate(pieces):
            src, dst = cow if i == 0 else (0, 0)
            args = (params, pk, pv, self._last, self._pos,
                    np.int32(slot), rows[i], toks, np.int32(at), np.int32(n),
                    np.int32(src), np.int32(dst), self._state)
            if compile_only:
                fn_of(w).prepare(*args)
            else:
                (pk, pv, self._last, self._pos, first,
                 self._state, counts) = fn_of(w)(*args)
                if tally is not None:
                    tally.append(counts)
                if snapshot is not None and snapshot[0] == i:
                    self._snap = self._snap_take(
                        self._snap, self._state, np.int32(snapshot[1]),
                        np.int32(slot))
        return pk, pv, first

    def _prefill_fn(self, bucket):
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            fn = self._aot_with_mem_telemetry(
                _bd.make_prefill(self.arch, bucket=bucket,
                                 donate=self._donate),
                label=f"prefill_{bucket}")
            self._prefill_fns[bucket] = fn
            self._reg.counter(
                "serving.prefill_compiles",
                help="prefill executables built (one per window width)",
            ).inc()
        return fn

    def _device_table(self, live):
        """The block table a decode chunk reads: ``[max_slots, NB]``, or
        ``[max_slots, 2, NB]`` under two kinds of chain (kind 0 the whole
        chains, kind 1 the window planes': ``arch.chain_kind``); with no
        plane the live mask ``[max_slots]``.  A slot not among ``live``
        gets the dead row (every entry the trash block) in every kind:
        a slot whose request ends inside the chunks already sent still
        holds its blocks on the host, and must not be stepped again."""
        import jax.numpy as jnp

        keep = np.zeros(self.max_slots, np.int32)
        keep[list(live)] = 1
        if self._table.ndim == 1:
            return jnp.asarray(self._table * keep)
        if not self._windowed:
            return jnp.asarray(self._table * keep[:, None])
        return jnp.asarray(np.stack(
            [self._table, self.window_chains.table], axis=1)
            * keep[:, None, None])

    def _advance_window(self, slot, first_pos, last_pos):
        """Rows ``first_pos .. last_pos`` of ``slot`` are next: its
        window chain gives back what no later row can see and allocates
        what these write (``kvcache.WindowChains.advance``)."""
        freed = self.window_chains.advance(slot, first_pos, last_pos)
        if freed:
            self._reg.counter(
                "serving.window_blocks_released",
                help="blocks of the window planes' chain given back while "
                     "their slot went on: every later query's lower bound "
                     "had passed them").inc(freed)

    def _count_snapshot_evictions(self):
        """Publish what the trie dropped since the last call."""
        gone = self.prefix_trie.snapshot_evictions - self._snap_evicted
        if gone:
            self._snap_evicted += gone
            self._reg.counter(
                "serving.state_snapshot_evictions",
                help="snapshots dropped: with their evicted node, or for "
                     "their row (least recently used, no live slot on "
                     "the chain)").inc(gone)

    def _blocks_in_use(self):
        return (self.kv_pool.blocks_in_use
                + (self.window_chains.pool.blocks_in_use
                   if self._windowed else 0))

    def _release_slot(self, slot):
        """Return a slot and every KV block it references to the pool
        (shared blocks just drop one ref; private ones free).  The
        single reclamation path — eviction, immediate-EOS, slot death
        and abort all route here or mirror it exactly, so the fault
        test's no-leak invariant has one owner."""
        for b in self._slot_blocks[slot] or ():
            self.kv_pool.deref(b)
        self._slot_blocks[slot] = None
        self._table[slot] = 0
        if self._windowed:
            self.window_chains.release(slot)
        if self._spec is not None:
            # the slot's draft scratch chain obeys the same discipline
            self._spec.release(self, slot)
        self._slots[slot] = None
        self._free.append(slot)
        if self.prefix_trie is not None:
            # blocks this slot shared with the trie are now trie-only:
            # re-apply the cache capacity budget
            self.prefix_trie.enforce_budget()
            if self.snapshot_rows:
                self._count_snapshot_evictions()
        if self.kv_pool is not None:
            self._reg.gauge("serving.blocks_in_use").set(
                self._blocks_in_use())

    def _sent(self, slot, req):
        """Tokens of ``req``, live in ``slot``, that exist: those emitted
        and the steps of the chunks in flight that name it.  THE
        definition of where a slot is: its dispatched position (the
        position its next chunk's first step writes) is ``prompt + _sent
        - 1``, and it needs another chunk while ``_sent < max_new``.
        ``len(req.tokens)`` alone is one chunk behind once a chunk is in
        flight."""
        return len(req.tokens) + self.decode_chunk * sum(
            c.reqs.get(slot) is req for c in self._chunks)

    def _runs_on(self):
        """The slots a chunk sent NOW would step, ``{slot: request}``:
        those whose request does not end by ``max_new`` inside the chunks
        already sent.  An end the host can foresee never rides a chunk
        more; an ``eos_id`` hit, which nobody can foresee, rides one."""
        return {s: req for s, req in enumerate(self._slots)
                if req is not None and self._sent(s, req) < req.max_new}

    def _decode(self):
        """The decode half of a driver iteration, with ONE chunk kept in
        flight: send chunks until one is queued behind the one waited for
        (as long as some slot is still live after those sent), then read
        the OLDEST.  In steady decode chunk n + 1 is on the device's
        queue before chunk n's tokens are asked for, so the device goes
        from one to the next while the host fetches, emits, polls the
        queue and builds the next table.  The depth is a fact of the
        mechanism, not a setting: what is sent beyond the chunk waited
        for is computed for requests that may have ended (an ``eos_id``)
        and delays an arrival's admission by a chunk."""
        if self._spec is not None:
            # the host decides every round's acceptance: a serial loop
            return self._spec_decode()
        finished = 0
        while len(self._chunks) < 2 and (reqs := self._runs_on()):
            # fault injection point (PADDLE_TPU_FAULT=slot_death:n): the
            # n-th decode chunk kills one active request mid-decode — its
            # slot and KV blocks must be reclaimed and the driver survive.
            # What is in flight is read first, so that nobody is failed
            # with tokens of theirs still on the device
            if _faults.maybe_fault("serving.decode") == "slot_death":
                while self._chunks:
                    finished += self._collect()
                self._kill_one_slot()
                continue
            self._dispatch(reqs)
        if self._chunks:
            finished += self._collect()
        return finished

    def _dispatch(self, reqs):
        """The FIRST half of a decode chunk for the rows ``reqs``
        (``_runs_on``), everything the host does
        before the device can run it: move the window chains, build and
        upload the table, count what the chunk will read, call the
        executable and start its results' copies to the host.  Every
        quantity "at the chunk's start" is taken at the slots' dispatched
        positions (``_sent``).  Nothing here waits for the device: the
        pool, the slot scalars and the state it passes are the previous
        call's outputs, futures while that call runs."""
        import jax.numpy as jnp

        if self._decode_fn is None:
            self._decode_fn = self._aot_with_mem_telemetry(
                _bd.make_decode_chunk(self.arch, chunk=self.decode_chunk,
                                      donate=self._donate),
                label="decode")
            self._reg.counter(
                "serving.decode_compiles",
                help="decode-chunk executables built (one per engine)",
            ).inc()
        ahead = "1" if self._chunks else "0"
        with self._span("serving.dispatch", "decode",
                        steps=self.decode_chunk, active=len(reqs),
                        ahead=ahead):
            # keys each row's first step attends: its own position last
            contexts = [(s, req.prompt.shape[0] + self._sent(s, req))
                        for s, req in reqs.items()]
            if self._windowed:
                # the chunk's steps write positions pos .. pos + chunk - 1
                # of each live slot (none past its request's last): the
                # window chains move there first.  A block given back
                # here may be one a chunk in flight still reads: whoever
                # is handed it writes it in a LATER call, and the device
                # runs the calls in order
                for s, ctx in contexts:
                    req = reqs[s]
                    self._advance_window(
                        s, ctx - 1, min(ctx - 1 + self.decode_chunk,
                                        req.prompt.shape[0] + req.max_new)
                        - 1)
                self._reg.gauge("serving.blocks_in_use").set(
                    self._blocks_in_use())
            tbl = self._device_table(reqs)
            # which chains start alike rides beside the table, as data
            runs = self._shared_runs(contexts)
            told = () if runs is None else (jnp.asarray(runs),)
            # one-time AOT compile lands here, outside the chunk's clock
            # pair, which the predictor consumes
            self._decode_fn.prepare(self._p, self._pk, self._pv,
                                    self._last, self._pos, tbl, self._state,
                                    *told)
            self._count_paged_entries(contexts, runs)
            sent_t = time.perf_counter()
            (self._pk, self._pv, self._last, self._pos, toks,
             self._state, counts) = self._decode_fn(
                 self._p, self._pk, self._pv, self._last, self._pos, tbl,
                 self._state, *told)
            # queued behind the compute, not asked for after it
            toks.copy_to_host_async()
            if self.arch.count_names:
                counts.copy_to_host_async()
        self._reg.counter(
            "serving.chunks_dispatched", ahead=ahead,
            help="decode chunks sent to the device: ahead=1 while another "
                 "was still unread (the device goes from that one to this "
                 "with no host in between), ahead=0 with nothing in "
                 "flight (the first after an admission or an idle "
                 "stretch)").inc()
        self._chunks.append(_Chunk(toks, counts, reqs, sent_t))

    def _collect(self):
        """The SECOND half, of the oldest chunk in flight: wait for its
        tokens (and what its stack tallied: there by then), emit them to
        the requests of ITS snapshot, release and finish what ended.  A
        row whose request is no longer the slot's (an ``eos_id`` hit in
        the chunk before, read after this one was sent) was computed for
        nothing: its tokens are dropped and counted.

        The chunk's CLOCK PAIR is ``(max(its dispatch, the previous
        chunk's collect), this collect)``: the interval in which the
        device could be running it.  Between back-to-back chunks that is
        collect to collect: the chunk's time on the device plus whatever
        host latency was not hidden under it."""
        c = self._chunks.popleft()
        # ONE live span a chunk, around the wait, with THAT chunk's facts
        with self._span("serving.decode_chunk", "decode",
                        histogram="serving.decode_chunk",
                        steps=self.decode_chunk,
                        active=len(c.reqs),
                        passes=self.arch.passes,
                        state_layers=len(self._state),
                        plane_reads=self._reads_per_token,
                        moe_layers=self.arch.moe_layers,
                        experts_held=self.arch.experts_held,
                        **self._form_attrs) as sp:
            with self._span("serving.fetch", "fetch", of="decode"):
                toks = np.asarray(c.toks)  # host sync: [chunk, S]
                if self.arch.count_names:
                    self._count_tallies("decode", [c.counts])
        t0, t1 = max(c.sent_t, self._collected_t), sp.t1
        self._collected_t = t1
        wall = t1 - t0
        self._reg.histogram("serving.step_seconds").observe(
            wall / self.decode_chunk)
        self.predictor.observe_chunk(wall, self.decode_chunk)
        live = {s: req for s, req in c.reqs.items()
                if self._slots[s] is req}
        self._reg.counter(
            "serving.rider_slot_steps",
            help="slot-steps of whole chunks computed for a request that "
                 "had ended before the chunk was read (an eos_id hit in "
                 "the chunk before it): thrown away").inc(
                     (len(c.reqs) - len(live)) * self.decode_chunk)
        stall, stall_prefill = self._account_stall(
            live, t0, t1, self.decode_chunk, len(c.reqs))
        if self._tracer.enabled:
            # per-request chunk windows feed only the finish-time lane
            # emission, which is skipped when tracing is off — don't
            # grow the lists on the disabled hot path
            for req in live.values():
                req.chunks.append((t0, t1))
        emitted = 0
        finished = 0
        # how long this chunk's rows had waited for it, and for what: on
        # the profiler's host plane an idle gap can be laid against it
        with self._span("serving.emit", "emit", stall_ms=stall * 1e3,
                        stall_prefill_ms=stall_prefill * 1e3) as em:
            now = em.t0
            for j in range(self.decode_chunk):
                for s, req in live.items():
                    if self._slots[s] is not req:
                        continue            # ended at an earlier step
                    tok = int(toks[j, s])
                    req.tokens.append(tok)
                    emitted += 1
                    if ((req.eos_id is not None and tok == req.eos_id)
                            or len(req.tokens) >= req.max_new):
                        self._release_slot(s)
                        self._finish(req, now)
                        finished += 1
        self._reg.counter("serving.tokens").inc(emitted)
        self._reg.gauge("serving.slots_active").set(self.active_slots)
        return finished

    def _spec_decode(self):
        """One speculative round (serving.speculative): the draft
        proposes ``spec_k`` tokens per slot into scratch chains, ONE
        target verify forward scores every slot's ``k + 1``-token
        window, greedy acceptance commits the agreeing prefix plus the
        bonus token (token-exact vs plain greedy by induction), and
        scratch blocks past the committed frontier roll back to the
        pool.  At least one token commits per live slot per round, so
        progress is guaranteed even under a hostile draft."""
        import jax.numpy as jnp

        sp = self._spec
        k = sp.k
        B = self.block_tokens
        S = self.max_slots
        # per-slot committed frontier, rebuilt from host truth each
        # round: last committed token + its position + the last
        # position the slot may ever write (the verify write limit)
        last_h = np.zeros(S, np.int32)
        pos_h = np.zeros(S, np.int32)
        limit_h = np.full(S, -1, np.int32)
        for s, req in enumerate(self._slots):
            if req is None:
                continue
            p_len = req.prompt.shape[0]
            last_h[s] = req.tokens[-1]
            pos_h[s] = p_len + len(req.tokens) - 1
            limit_h[s] = p_len + req.max_new - 1
            hi = min(pos_h[s] + k, limit_h[s])
            sp.ensure_chain(self, s, int(hi) // B + 1)
        # the draft-chunk / verify-window one-time AOT compiles land
        # here, outside the timed window the predictor consumes (same
        # contract as the plain decode chunk); lowering needs only
        # shapes, so the verify prepares against a placeholder window
        nl = sp.n_layer
        sp.chunk_fn(self).prepare(
            sp.p, self._pk[:nl], self._pv[:nl], jnp.asarray(last_h),
            jnp.asarray(pos_h), jnp.asarray(sp.table))
        sp.verify_fn(self).prepare(
            self._p, self._pk, self._pv,
            jnp.asarray(np.zeros((S, k + 1), np.int32)),
            jnp.asarray(pos_h), jnp.asarray(limit_h),
            jnp.asarray(self._table))
        # one live span per round (the speculative engine's decode
        # phase): propose, verify and the blocking fetch of the verdict
        with self._span("serving.spec_round", "decode",
                        histogram="serving.decode_chunk", k=k,
                        active=self.active_slots) as rnd:
            drafts = sp.propose(self, last_h, pos_h)       # [k, S] host
            self._reg.gauge(
                "serving.spec_draft_ms",
                help="draft propose wall time per speculative round (ms)",
            ).set((time.perf_counter() - rnd.t0) * 1000.0)
            # fault injection point (PADDLE_TPU_FAULT=slot_death:n): in
            # speculative mode the decode-point death fires MID-VERIFY —
            # between propose and commit, the widest window of in-flight
            # scratch state.  The killed slot's real AND draft chains are
            # reclaimed (_release_slot), its table rows zero, and the
            # verify below runs with its write limit dropped to -1, so
            # the dead slot scatters only into the trash block.
            if _faults.maybe_fault("serving.decode") == "slot_death":
                self._kill_one_slot()
                if not self.active_slots:
                    return 0
            for s in range(S):
                if self._slots[s] is None:
                    limit_h[s] = -1
            U = np.zeros((S, k + 1), np.int32)
            U[:, 0] = last_h
            U[:, 1:] = drafts.T
            (self._pk, self._pv, greedy) = sp.verify_fn(self)(
                self._p, self._pk, self._pv, jnp.asarray(U),
                jnp.asarray(pos_h), jnp.asarray(limit_h),
                jnp.asarray(self._table))
            with self._span("serving.fetch", "fetch", of="decode"):
                greedy = np.asarray(greedy)            # host sync [S, k+1]
        t0, t1 = rnd.t0, rnd.t1
        wall = t1 - t0
        active = self.active_slots
        live = {s: req for s, req in enumerate(self._slots)
                if req is not None}
        # a round verifies k + 1 positions a slot: its steps
        stall, stall_prefill = self._account_stall(live, t0, t1, k + 1,
                                                   len(live))
        if self._tracer.enabled:
            for req in live.values():
                req.chunks.append((t0, t1))
        emitted = 0
        finished = 0
        round_acc = 0
        with self._span("serving.emit", "emit", stall_ms=stall * 1e3,
                        stall_prefill_ms=stall_prefill * 1e3) as em:
            now = em.t0
            for s, req in enumerate(self._slots):
                if req is None:
                    continue
                remaining = req.max_new - len(req.tokens)
                commit, n_matched = _spec.accept_greedy(
                    drafts[:, s], greedy[s], remaining)
                done = False
                appended = 0
                for tok in commit:
                    req.tokens.append(tok)
                    emitted += 1
                    appended += 1
                    if ((req.eos_id is not None and tok == req.eos_id)
                            or len(req.tokens) >= req.max_new):
                        done = True
                        break
                acc = min(n_matched, appended)
                # acceptance is judged over the draft tokens that COULD
                # have committed (the request's remaining window), not the
                # full k — end-of-request rounds would otherwise dilute the
                # rate and make the predictor's steps-per-round estimate,
                # and the reported draft quality, look worse than they are
                eff = min(k, max(0, remaining - 1))
                sp.proposed += eff
                sp.accepted += acc
                req.spec_proposed += eff
                req.spec_accepted += acc
                round_acc += acc
                if done:
                    self._release_slot(s)
                    self._finish(req, now)
                    finished += 1
                else:
                    # the draft KV is valid through the new frontier - 1;
                    # scratch blocks past it held rejected-token state
                    pos2 = req.prompt.shape[0] + len(req.tokens) - 1
                    sp.rollback(self, s, (int(pos2) - 1) // B + 1)
            # what the round committed is known here, after its span
            # (the chunk-latency sample) closed: its emit span says it
            em.set(emitted=emitted, accepted=round_acc)
        self._reg.counter("serving.tokens").inc(emitted)
        if sp.proposed:
            self._reg.gauge(
                "serving.spec_accept_rate",
                help="draft tokens accepted / proposed since the last "
                     "accounting reset",
            ).set(sp.accepted / sp.proposed)
        self._reg.histogram("serving.step_seconds").observe(
            wall / (k + 1))
        if active:
            # steps-per-round for the predictor: the steady-state
            # expectation 1 + accept_rate * k, not this round's
            # emitted/active — single rounds are noisy (slots finishing
            # mid-window report 1-2 tokens) and the predictor keeps
            # only the LAST steps value, so a noisy round would swing
            # predicted decode time by k x and mis-shed arrivals
            if sp.proposed:
                steps = 1 + k * (sp.accepted / sp.proposed)
            else:
                steps = emitted / active
            self.predictor.observe_chunk(wall, max(1, int(round(steps))))
        self._reg.gauge("serving.blocks_in_use").set(
            self.kv_pool.blocks_in_use)
        self._reg.gauge("serving.slots_active").set(self.active_slots)
        return finished

    def _kill_one_slot(self):
        """Injected mid-decode slot death: fail the first active
        request, reclaim its slot and KV blocks (the no-leak
        regression), keep the driver alive."""
        for s, req in enumerate(self._slots):
            if req is None:
                continue
            req.error = RuntimeError(
                f"injected slot death (PADDLE_TPU_FAULT) — request "
                f"{req.rid} died in slot {s} mid-decode")
            req.finish_t = time.perf_counter()
            self._release_slot(s)
            self._reg.counter(
                "serving.slot_deaths",
                help="requests killed by injected mid-decode slot "
                     "death (blocks + slot reclaimed)").inc()
            self._reg.gauge("serving.slots_active").set(self.active_slots)
            with self._qlock:
                self._completed.append(req)
            req._done.set()
            return True
        return False

    def _sched_bucket(self, req):
        """The scheduler's prefill-bucket estimate for a queued request
        — REUSE-AWARE via a non-mutating trie probe (``peek_hit``
        touches no LRU clock), so a mostly-cached long prompt is costed
        at its real suffix bucket and never shed on the strength of a
        full prefill it would not pay.  The probe can only overestimate
        the eventual hit if the chain is evicted before admission —
        which under-sheds, the safe direction for the optimistic-bound
        contract."""
        p_len = req.prompt.shape[0]
        hit = 0
        if self.prefix_trie is not None:
            hit = self.prefix_trie.peek_hit(req.prompt, p_len - 1)
        return self.bucket_for(p_len - hit)

    def _admit(self):
        """Move queued requests into free slots (continuous batching:
        runs between decode chunks), in SCHEDULER order — the SLO
        scheduler pops by least TTFT slack and sheds e2e-doomed
        requests.  Returns requests finished AT admission (immediate
        EOS / max_new == 1 / shed)."""
        finished = 0
        while self._free:
            now = time.perf_counter()
            with self._qlock:
                if not self._queue:
                    break
                req, shed = self._sched.pick(self._queue, now,
                                             self._sched_bucket)
                if req is not None:
                    # in-flight until slotted/finished, so idle never
                    # reads True while an admission is mid-prefill
                    self._inflight += 1
                self._reg.gauge("serving.queue_depth").set(
                    len(self._queue))
            for victim in shed:
                self._shed(victim)
                finished += 1
            if req is None:
                if shed:
                    continue  # more queue may be schedulable next pass
                break
            # queue-wait: submit -> popped for admission.  With the
            # prefill window below this decomposes TTFT into queue time
            # vs prefill compute — the measurement the SLO-aware
            # admission schedules against.  Observed AFTER the
            # admission sticks: a PoolExhausted re-queue clears
            # admit_t, so a victim's wait is counted once, at its
            # final (successful) admission.
            req.admit_t = time.perf_counter()
            slot = self._free.pop()
            try:
                finished += self._prefill_into(slot, req)
                self._reg.histogram("serving.queue_wait").observe(
                    req.admit_t - req.submit_t)
                with self._qlock:
                    self._inflight -= 1
            except _kv.PoolExhausted:
                # every evictable cached chain is already gone and the
                # live slots hold the rest: back off until decode frees
                # blocks (put the victim back at the FRONT — it keeps
                # its place)
                self._free.append(slot)
                with self._qlock:
                    self._queue.appendleft(req)
                    self._inflight -= 1
                req.admit_t = None
                if self.active_slots == 0:
                    raise  # nothing will ever free blocks: fatal
                break
            except Exception:
                # put the victim back where _abort (called by step) can
                # see and fail it with everything else
                self._free.append(slot)
                with self._qlock:
                    self._queue.appendleft(req)
                    self._inflight -= 1
                raise
        self._reg.gauge("serving.slots_active").set(self.active_slots)
        return finished

    def _prefill_into(self, slot, req):
        """Admit one request into ``slot``: match the prefix trie,
        reference shared blocks, allocate the private tail (LRU-evicting
        cached chains under pressure), run the bucketed SUFFIX prefill
        (with the copy-on-write fork folded in), then register the
        prompt's full blocks in the trie.  Returns the requests finished
        meanwhile: this one if it finished at prefill (immediate EOS /
        max_new == 1), and those that ended in a chunk in flight, which
        is read while the pieces run."""
        import jax.numpy as jnp

        pool, trie = self.kv_pool, self.prefix_trie
        p_len = req.prompt.shape[0]
        # an architecture with no plane holds no block: an empty row
        shared, priv, cow, hit = [], [], None, 0
        hit_row = take = None
        cow_src = cow_dst = 0
        row = np.zeros(self.blocks_per_slot, np.int32)
        if pool is not None:
            n_total = -(-(p_len + req.max_new) // self.block_tokens)
            if self.snapshot_rows:
                # recurrent state: the hit is cut back to the deepest
                # matched node that has a snapshot, whole blocks only
                shared, hit_row, hit = trie.match_state(req.prompt,
                                                        p_len - 1)
            elif trie is not None:
                shared, cow, hit = trie.match(req.prompt, p_len - 1)
            # hold every matched block across the eviction/alloc window so
            # LRU pressure can never free a chain we are about to attend
            hold = list(shared) + ([cow[0]] if cow else [])
            for b in hold:
                pool.ref(b)
            need = n_total - len(shared)
            try:
                if need > pool.free_blocks and trie is not None:
                    trie.evict_lru(need - pool.free_blocks)
                priv = pool.alloc(need)
            except _kv.PoolExhausted:
                for b in hold:
                    pool.deref(b)
                raise
            row[:len(shared)] = shared
            row[len(shared):n_total] = priv
            if cow is not None:
                # fork the partially-matched cached block copy-on-write:
                # the fork target is the first private block (logical block
                # len(shared)); the copy itself rides inside the prefill
                # executable, so CoW costs zero extra compiles
                cow_src, cow_dst = cow[0], priv[0]
                self._reg.counter(
                    "serving.cow_copies",
                    help="prefix-cache blocks forked copy-on-write").inc()
        start = int(hit)
        suffix = p_len - start
        pieces = self._pieces(req.prompt[start:], start)
        bucket = sum(w for w, *_ in pieces)
        req.bucket = bucket
        req.prefix_hit = start
        if self.snapshot_rows:
            # ONE snapshot a prefill: at the last block boundary on which
            # one of its pieces ends (none: it leaves none)
            ends = [(i, at + n) for i, (_, _, at, n) in enumerate(pieces)
                    if (at + n) % self.block_tokens == 0]
            to = trie.reserve_snapshot() if ends else None
            if to is not None:
                take, take_depth = (ends[-1][0], to), ends[-1][1]
            if hit_row is not None:
                # the slot's rows are the snapshot's before the suffix's
                # first piece, which does not start a prompt (start > 0)
                self._state = self._snap_restore(
                    self._state, self._snap, np.int32(slot),
                    np.int32(hit_row))
                self._reg.counter(
                    "serving.state_snapshot_hits",
                    help="admissions that started from a state snapshot "
                         "(the hit cut back to its node)").inc()
                self._reg.counter(
                    "serving.state_restored_bytes",
                    help="bytes of state copied from snapshots into "
                         "slots' rows").inc(self._state_slot_bytes)
        if self._windowed:
            # a row a piece: before each piece the window chain gives
            # back what its first row cannot see and allocates what its
            # rows write, so a later piece's row names, at a higher
            # entry, a block an earlier piece's named (the device runs
            # the pieces in order)
            row_d = []
            for _, _, at, n in pieces:
                self._advance_window(slot, at, at + n - 1)
                row_d.append(jnp.asarray(np.stack(
                    [row, self.window_chains.table[slot]])))
        else:
            row_d = jnp.asarray(row)
        # a width's one-time AOT compile lands here, outside the timed
        # window the predictor consumes
        self._run_pieces(self._prefill_fn, self._p, self._pk, self._pv,
                         slot, row_d, pieces, compile_only=True)
        # ONE span per admission, whatever the number of pieces; only
        # the last piece's first token is fetched
        with self._span("serving.prefill", "prefill",
                        histogram="serving.prefill_seconds", rid=req.rid,
                        bucket=bucket, pieces=len(pieces), slot=slot,
                        prefix_hit=start, passes=self.arch.passes,
                        state_layers=len(self._state),
                        plane_reads=self._reads_per_token,
                        moe_layers=self.arch.moe_layers,
                        experts_held=self.arch.experts_held,
                        **(dict(state_hit_tokens=start)
                           if self.snapshot_rows else {}),
                        **self._form_attrs) as sp:
            tally = []
            self._pk, self._pv, first = self._run_pieces(
                self._prefill_fn, self._p, self._pk, self._pv, slot,
                row_d, pieces, cow=(cow_src, cow_dst), tally=tally,
                snapshot=take)
            # the pieces are queued BEHIND a chunk in flight (the pools
            # they take are its outputs).  Its tokens are read while the
            # pieces run, not after them: a request that ends in it is
            # finished on time, and its clock pair ends where the
            # device left it for the pieces
            finished = 0
            while self._chunks:
                finished += self._collect()
            with self._span("serving.fetch", "fetch", of="prefill"):
                first = int(np.asarray(first))  # host sync
                if self.arch.count_names:
                    self._count_tallies("prefill", tally)
        if self.arch.latent_planes:
            # real row j of a piece at ``at`` attends at + j + 1 positions
            self._count_latent_positions(
                "prefill", [(at + 1, n) for _, _, at, n in pieces],
                calls=len(pieces))
        # the prefill's clock pair, as a chunk's: from when the device
        # could start it
        t_p0, now = max(sp.t0, self._collected_t), sp.t1
        # the CoW source was held only for the copy window
        if cow is not None:
            pool.deref(cow[0])
        self._table[slot] = row if pool is not None else 1
        self._slot_blocks[slot] = list(shared) + list(priv)
        if self._spec is not None:
            # draft prefill: scan the FULL prompt through the draft
            # into the slot's scratch chain so the first propose round
            # has a complete draft KV.  Runs before any request-state
            # mutation below so an (unlikely — the pool reserves a
            # draft chain per slot) PoolExhausted re-queues cleanly.
            try:
                self._spec.prefill(self, slot, req)
            except _kv.PoolExhausted:
                for b in self._slot_blocks[slot] or ():
                    pool.deref(b)
                self._slot_blocks[slot] = None
                self._table[slot] = 0
                self._spec.release(self, slot)
                if trie is not None:
                    trie.enforce_budget()
                self._reg.gauge("serving.blocks_in_use").set(
                    pool.blocks_in_use)
                raise
        if trie is not None:
            # register the prompt's FULL blocks (shared ones are
            # already cached and skipped; our private full blocks
            # become reusable by the next identical prefix)
            trie.insert(req.prompt, [int(b) for b in row[:p_len
                                                         // self.block_tokens]])
            if take is not None and trie.attach_snapshot(
                    req.prompt, take_depth, take[1]):
                self._reg.counter(
                    "serving.state_snapshots_taken",
                    help="state snapshots a trie node came to name (one a "
                         "prefill at most: the last block boundary on "
                         "which one of its pieces ended)").inc()
            self._count_snapshot_evictions()
        req.prefill_t0, req.prefill_t1 = t_p0, now
        self.predictor.observe_prefill(bucket, now - t_p0)
        req.first_token_t = now
        # this prompt's clock pair is a prefill stall of every slot that
        # waits through it, and no part of the slot's own account
        self._prefill_s += now - t_p0
        self._slot_advanced[slot] = now
        self._slot_prefill_s[slot] = self._prefill_s
        req.tokens.append(first)
        self._reg.counter("serving.admitted").inc()
        self._reg.counter("serving.tokens").inc()
        self._reg.counter(
            "serving.prefill_tokens",
            help="prompt-suffix tokens prefill computed (padded to the "
                 "window widths; prefix hits subtract from this)",
        ).inc(bucket)
        self._reg.counter(
            "serving.prefill_real_tokens",
            help="prompt-suffix tokens prefill computed, padding left "
                 "out").inc(suffix)
        self._count_prefill_entries(pieces)
        for w, _, at, _ in pieces:
            self._reg.counter(
                "serving.prefill_pieces", width=w,
                help="prefill window calls dispatched, by width (an "
                     "admission is one or more pieces)").inc()
            if self.arch.delta_layers:
                self._reg.counter(
                    "serving.delta_piece_rows", width=w,
                    help="rows of the prefill pieces' delta-rule calls, by "
                         "the piece's width (ONE call a layer a piece, "
                         "padding included: a call computes its width), a "
                         "layer").inc(w)
            if self.arch.lightning_layers:
                self._reg.counter(
                    "serving.lightning_calls", fresh=str(int(at == 0)),
                    phase="prefill").inc(self.arch.lightning_layers)
                self._reg.counter(
                    "serving.lightning_piece_rows", width=w,
                    help="rows of the prefill pieces' constant-decay "
                         "calls, by the piece's width (ONE call a layer a "
                         "piece, padding included), a layer").inc(w)
            if self.arch.retention_layers:
                for i, rows in enumerate(_retention.chunk_rows(w)):
                    self._reg.counter(
                        "serving.retention_piece_rows", width=rows,
                        help="rows of the prefill pieces' retention "
                             "calls, by the CALL's width (a piece is "
                             "ONE up to kernels.retention.CALL_ROWS; "
                             "padding included: a call computes its "
                             "width), a layer").inc(rows)
                    self._reg.counter(
                        "serving.retention_calls",
                        fresh=str(int(at == 0 and i == 0)),
                        help="the prefill pieces' retention calls a "
                             "layer; fresh=1 started a prompt and never "
                             "read the slot's state").inc()
        self._reg.histogram("serving.ttft_seconds").observe(
            now - req.submit_t)
        with self._qlock:
            self._hit_tokens += start
            self._prompt_tokens += p_len
            hit_rate = (self._hit_tokens / self._prompt_tokens
                        if self._prompt_tokens else 0.0)
        self._reg.counter(
            "serving.prefix_hit_tokens",
            help="prompt tokens served from the prefix cache "
                 "(prefill skipped)").inc(start)
        self._reg.gauge(
            "serving.prefix_hit_rate",
            help="cumulative prefix-cache hit rate over prompt tokens "
                 "(since the last accounting reset)").set(hit_rate)
        if pool is not None:
            self._reg.gauge("serving.blocks_in_use").set(
                self._blocks_in_use())
        if ((req.eos_id is not None and first == req.eos_id)
                or req.max_new == 1):
            self._release_slot(slot)
            # _release_slot re-appended the slot; the caller's _free
            # bookkeeping is already consistent (slot was popped there)
            self._finish(req, now)
            return finished + 1
        self._slots[slot] = req
        return finished

    def _shed(self, req):
        """Fail a request the scheduler refused (cannot meet its e2e
        budget): it completes immediately with ``shed`` True and a
        ``SheddedRequest`` error — capacity goes to requests that can
        still meet their deadlines."""
        now = time.perf_counter()
        req.shed = True
        req.slo_ok = False
        req.error = _sched.SheddedRequest(
            f"request {req.rid} shed after {now - req.submit_t:.3f}s in "
            f"queue: predicted completion exceeds its e2e budget")
        req.finish_t = now
        self._reg.counter(
            "serving.shed_total",
            help="requests shed by the SLO scheduler (could no longer "
                 "meet their e2e budget)").inc()
        self._tracer.instant("serving.shed", cat="serving", rid=req.rid)
        with self._qlock:
            self._completed.append(req)
        req._done.set()

    def _finish(self, req, now):
        req.finish_t = now
        self._reg.counter("serving.completed").inc()
        self._reg.histogram("serving.e2e_seconds").observe(req.e2e)
        self._judge_slo(req, now)
        self._emit_request_trace(req)
        account = None
        if len(req.tokens) > 1 and req.error is None:
            decode_s, gaps = now - req.first_token_t, len(req.tokens) - 1
            self._reg.histogram(
                "serving.tpot_seconds",
                help="a finished request's time per output token: first "
                     "token -> finish over the tokens after the first",
            ).observe(decode_s / gaps)
            account = (decode_s, gaps, req.chunk_s, req.stall_prefill_s,
                       req.stall_host_s, req.steps, req.slot_steps)
        with self._qlock:
            if account is not None:
                self._tpot_ring.append(account)
            self._completed.append(req)
        req._done.set()

    def reset_slo_accounting(self):
        """Re-open the goodput window and zero the violation/shed
        counters and the prefix-hit window — benchmarks call this after
        their warm pass so compile-time TTFT breaches (and warm-pass
        trie traffic) don't charge the timed run.  The window ORIGIN is
        re-armed too: the next ``submit`` starts a fresh
        since-first-submit window, so a warm pass can never deflate the
        timed run's ``serving.goodput_tok_s`` denominator."""
        with self._qlock:
            self._good_tokens = 0
            self._first_submit_t = None
            self._hit_tokens = 0
            self._prompt_tokens = 0
            if self._spec is not None:
                self._spec.proposed = 0
                self._spec.accepted = 0
            self._tpot_ring.clear()
        for nm in ("serving.slo_violations", "serving.goodput_tok_s",
                   "serving.shed_total", "serving.prefix_hit_rate",
                   "serving.prefix_hit_tokens", "serving.prefill_tokens",
                   "serving.prefill_real_tokens", "serving.cow_copies",
                   "serving.spec_accept_rate", "serving.spec_draft_ms",
                   "serving.spec_rollback_blocks",
                   # the stall share and the tail's account cover the
                   # same window, whoever resets the registry or does not
                   "serving.stalled_seconds", "serving.live_seconds",
                   "serving.tpot_seconds", "serving.longest_stall_seconds"):
            m = self._reg.get(nm)
            if m is not None:
                m.reset()
        for prefix in ("serving.prefill_pieces", "serving.chunk_fit"):
            for m in self._reg.metrics(prefix):
                m.reset()
        # what stats() made of the ring: gone with it
        for prefix in _TAIL_GAUGES:
            self._reg.clear(prefix)

    def _judge_slo(self, req, now):
        """SLO verdict at completion: a TTFT or e2e budget breach counts
        ``serving.slo_violations``; tokens of SLO-met requests feed the
        ``serving.goodput_tok_s`` gauge (good tokens over the window
        since the first submit — what the fleet delivered WITHIN budget,
        not what it merely emitted).  Per-request budgets win over the
        engine defaults."""
        ttft_b = (req.ttft_slo_s if req.ttft_slo_s is not None
                  else self.ttft_slo_s)
        e2e_b = (req.e2e_slo_s if req.e2e_slo_s is not None
                 else self.e2e_slo_s)
        if ttft_b is None and e2e_b is None:
            return
        ok = True
        if ttft_b is not None and (req.ttft is None or req.ttft > ttft_b):
            ok = False
        if e2e_b is not None and (req.e2e is None or req.e2e > e2e_b):
            ok = False
        req.slo_ok = ok
        if not ok:
            self._reg.counter(
                "serving.slo_violations",
                help="completed requests that breached their TTFT/e2e "
                     "SLO budget").inc()
        # _good_tokens/_first_submit_t are shared with submit() and
        # reset_slo_accounting() (which zeroes them under _qlock from
        # the caller's thread while the driver finishes requests) — the
        # read-modify-write must hold the same lock or a reset can lose
        # or resurrect warm-pass tokens
        with self._qlock:
            if ok:
                self._good_tokens += len(req.tokens)
            good, t0 = self._good_tokens, self._first_submit_t
        window = now - t0 if t0 is not None else 0.0
        if window > 0:
            self._reg.gauge(
                "serving.goodput_tok_s",
                help="tokens/sec from SLO-met requests since the first "
                     "submit (goodput under SLO, ROADMAP 1c)",
            ).set(good / window)

    def _emit_request_trace(self, req):
        """Lay the finished request's span tree on its own timeline lane:
        ``serving.request`` (submit -> finish) containing
        ``serving.req.queue`` / ``serving.req.prefill`` / one
        ``serving.req.decode_chunk`` per chunk the request was live for,
        closed by a zero-duration ``serving.req.evict`` marker.  These
        lane spans RE-present intervals the dedicated histograms
        (``serving.queue_wait`` / ``prefill_seconds`` /
        ``decode_chunk`` / ``e2e_seconds``) and the driver-thread
        operational spans already observed — one decode chunk is shared
        by every live request — so they are timeline-only
        (``timer=False``): folding them into ``host_timer.`` would
        multi-count the same wall seconds in the aggregate view."""
        tr = self._tracer
        if not tr.enabled or req.error is not None or req.admit_t is None:
            return
        lane = f"serving req {self._req_lane(req)}"
        spec_attrs = ({"spec_proposed": req.spec_proposed,
                       "spec_accepted": req.spec_accepted}
                      if req.spec_proposed else {})
        # what the chunks beneath it add up to (nothing to say of a
        # request that ended at its first token)
        account = ({"tpot_ms": 1e3 * (req.finish_t - req.first_token_t)
                    / (len(req.tokens) - 1),
                    "chunk_s": req.chunk_s,
                    "stall_prefill_s": req.stall_prefill_s,
                    "stall_host_s": req.stall_host_s, "steps": req.steps}
                   if len(req.tokens) > 1 else {})
        tr.add_span("serving.request", req.submit_t, req.finish_t,
                    cat="serving", lane=lane, timer=False, rid=req.rid,
                    prompt_len=int(req.prompt.shape[0]),
                    tokens=len(req.tokens),
                    prefix_hit=req.prefix_hit, **spec_attrs, **account)
        tr.add_span("serving.req.queue", req.submit_t, req.admit_t,
                    cat="serving", lane=lane, timer=False, rid=req.rid)
        if req.prefill_t0 is not None:
            tr.add_span("serving.req.prefill", req.prefill_t0,
                        req.prefill_t1, cat="serving", lane=lane,
                        timer=False, rid=req.rid, bucket=req.bucket,
                        prefix_hit=req.prefix_hit)
        for c0, c1 in req.chunks:
            tr.add_span("serving.req.decode_chunk", c0, c1,
                        cat="serving", lane=lane, timer=False,
                        rid=req.rid)
        tr.add_span("serving.req.evict", req.finish_t, req.finish_t,
                    cat="serving", lane=lane, timer=False, rid=req.rid)

    def _req_lane(self, req):
        """Pick a timeline lane whose previous occupant finished before
        this request was submitted, so overlapping requests NEVER share
        a lane (Chrome/Perfetto derive nesting purely from ts/dur
        containment within a tid — two live requests on one lane would
        render as one false tree).  Lanes are reused once free, keeping
        the lane count at the peak request concurrency; only past 64
        simultaneously-live requests does reuse fall back to the
        least-recently-freed lane.  Driver-thread only (called from
        ``_finish``), so no lock."""
        ends = self._req_lane_ends
        for i, end in enumerate(ends):
            if end <= req.submit_t:
                ends[i] = req.finish_t
                return i
        if len(ends) < 64:
            ends.append(req.finish_t)
            return len(ends) - 1
        i = min(range(len(ends)), key=ends.__getitem__)
        ends[i] = req.finish_t
        return i

    def _publish_tail(self):
        """The ring of finished requests' accounts as gauges: the 90th
        percentile of their time per output token, and over the TAIL SET
        (those at or above the ring's 80th percentile, the slowest fifth,
        which the 90th percentile bisects) the sums whose ratios are the
        tail's factors.  With T seconds, N tokens after the first, K
        steps and C seconds inside chunks, T / N = (C / K) x (K / N) x
        (T / C): the step at the load the tail met x the steps a token
        cost x what waiting added.  Nothing is published from an empty
        ring."""
        with self._qlock:
            ring = list(self._tpot_ring)
        if not ring:
            return
        rank = _obs.Histogram._rank
        tpot = [decode_s / gaps for decode_s, gaps, *_ in ring]
        ranked = sorted(tpot)
        cut = rank(ranked, 80)
        tail = [a for a, v in zip(ring, tpot) if v >= cut]
        _, gaps, chunk_s, prefill_s, host_s, steps, slot_steps = (
            sum(col) for col in zip(*tail))
        gauge = self._reg.gauge
        gauge("serving.tpot_p90_seconds",
              help="90th percentile (nearest rank) of the finished "
                   "requests' time per output token, over the last "
                   f"{TPOT_RING} since the accounting reset").set(
                       rank(ranked, 90))
        tail_help = ("over the finished requests at or above the 80th "
                     "percentile of time per output token (the slowest "
                     "fifth): ")
        gauge("serving.tpot_tail_requests",
              help=tail_help + "how many they are").set(len(tail))
        gauge("serving.tpot_tail_tokens",
              help=tail_help + "their tokens after the first").set(gaps)
        gauge("serving.tpot_tail_steps",
              help=tail_help + "the steps their chunks ran").set(steps)
        gauge("serving.tpot_tail_slot_steps",
              help=tail_help + "the rows their chunks stepped x the "
                               "steps").set(slot_steps)
        for part, v in (("chunk", chunk_s), ("stall_prefill", prefill_s),
                        ("stall_host", host_s)):
            gauge("serving.tpot_tail_seconds", part=part,
                  help=tail_help + "their seconds from first token to "
                       "finish: inside their chunks' clock pairs, waiting "
                       "inside another request's prefill clock pair, "
                       "waiting for the rest").set(v)

    def _publish_arch_gauges(self):
        """What the architecture itself wants shown (a routed FFN's share
        of the experts: ``arch.GatedMoE``; what a walk's copy moves:
        ``arch.SparseLightning``); nothing for most.  Facts of the engine,
        not of a window: set when it is built and again by ``stats``, so a
        registry zeroed in between (a benchmark's warm pass) shows them."""
        for name, (value, text) in self._arch_gauges.items():
            name, labels = name if isinstance(name, tuple) else (name, ())
            self._reg.gauge("serving." + name, help=text,
                            **dict(labels)).set(value)

    def stats(self):
        """Snapshot of the engine's ``serving.*`` metrics, the tail's
        make-up (``_publish_tail``) and the architecture's own gauges
        published first."""
        self._publish_tail()
        self._publish_arch_gauges()
        return self._reg.snapshot(prefix="serving.")
