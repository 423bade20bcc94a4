"""Continuous-batching serving — multi-request decode over a paged,
prefix-shared KV cache with SLO-aware goodput scheduling
(`docs/serving.md`).

``ServingEngine`` keeps one fixed-capacity batched decode step (compiled
once) saturated across many concurrent, variable-length requests: a
slot pool over a PAGED block KV cache (``kvcache``: fixed-size physical
blocks, per-slot block tables, reference-counted prefix reuse with
copy-on-write forks and LRU cache eviction), admission between decode
chunks (continuous batching) ordered by the SLO scheduler
(``scheduler``: least predicted-TTFT slack, e2e-doomed requests shed),
shape-bucketed SUFFIX prefill (a short ladder of window widths, the
widest past the chip's ridge) so compile count is bounded by the rungs, and full ``serving.*`` telemetry through the
observability registry.  What a model is made of reaches it as one
``arch.Architecture`` (the GPT-2 block, or a looped RMSNorm / rotary /
gated-FFN stack with a K/V plane per pass).
"""

from . import arch, batched_decode, kvcache, scheduler, speculative
from .engine import Request, ServingEngine
from .kvcache import BlockPool, PoolExhausted, PrefixTrie
from .scheduler import (FifoScheduler, SheddedRequest, SloScheduler,
                        TtftPredictor)
from .speculative import depth_draft, spec_enabled

__all__ = [
    "Request", "ServingEngine", "arch", "batched_decode", "kvcache",
    "scheduler", "speculative", "depth_draft", "spec_enabled",
    "BlockPool", "PoolExhausted", "PrefixTrie",
    "FifoScheduler", "SheddedRequest", "SloScheduler", "TtftPredictor",
]
