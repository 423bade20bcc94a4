"""Bytes and operations of a stack whose latent planes are of TWO kinds
(full planes whose query attends the ``index_topk`` cached rows a learned
indexer selects, sliding planes attended under a window) and whose FFN
is routed, on the serving path, computed from sizes and from the
requests' contexts: whatever implements the layers, this is what they
cannot avoid.  ``chipbench/DSA.md`` has the arithmetic at the published
sizes.

A cached position holds, in a FULL plane, one row of ``stored`` lanes
(the latent and the rotary key, 576 values in 640) and an index key of
``index_lanes``; in a SLIDING plane one row of ``stored`` lanes (1,088 in
1,152).  Bytes are STORED bytes (a row is read whole, its zero lanes
with it), operations those of the values the model has, so no reading
against them passes 100.  A decode position of context ``n``:

* the indexer scores ``n`` index keys a full plane: ``n x index_lanes``
  values read, ``2 x index_heads x index_lanes x n`` operations;
* the sparse attention reads ``k = min(n, index_topk)`` rows a full
  plane, ``2 x heads x (written + value_lanes) x k`` operations;
* a sliding plane reads ``min(n, window)`` rows, the same arithmetic at
  its own heads and lanes.

The sizes come from the family's ``dsa_sizes(config)`` and
``moe_sizes(config)``; the counts from the requests' own lengths and the
program's routing counters (``moe_bytes.counts``, which asks no sizes).
"""

from . import families


def sizes(config):
    """``dsa_sizes`` and ``moe_sizes`` of the configuration's family in
    one dict; ``None`` for a family with no indexer."""
    family = families.of(config)
    if not hasattr(family, "dsa_sizes"):
        return None
    return dict(family.dsa_sizes(config), **family.moe_sizes(config))


def index_call(config, contexts, itemsize=2):
    """(operations, bytes) of ONE full plane's index scores for one new
    token per entry of ``contexts`` (the positions scored, itself among
    them)."""
    size = sizes(config)
    n = sum(contexts)
    return (2 * size["index_heads"] * size["index_lanes"] * n,
            n * size["index_lanes"] * itemsize)


def _rows_call(plane, attended, itemsize):
    return (2 * plane["heads"] * (plane["written"] + plane["value_lanes"])
            * attended, attended * plane["stored"] * itemsize)


def sparse_call(config, contexts, itemsize=2):
    """(operations, bytes) of ONE full plane's attention of the selected
    rows: ``min(context, index_topk)`` rows a token."""
    size = sizes(config)
    return _rows_call(size["full"], sum(min(n, size["index_topk"])
                                        for n in contexts), itemsize)


def window_call(config, contexts, itemsize=2):
    """(operations, bytes) of ONE sliding plane's attention:
    ``min(context, window)`` rows a token."""
    size = sizes(config)
    return _rows_call(size["sliding"], sum(min(n, size["window"])
                                           for n in contexts), itemsize)


def least_seconds(call, peak):
    """The least seconds the chip could take over ``call = (operations,
    bytes)``: the larger of reading and of multiplying."""
    ops, nbytes = call
    return max(nbytes / peak["hbm_bytes_per_s"],
               ops / peak["bf16_flops_per_s"])


def attention_bytes(config, contexts, itemsize=2):
    """Bytes the attention of every plane reads for one new token per
    entry of ``contexts``: index keys, selected rows, window rows."""
    size = sizes(config)
    return (size["full"]["planes"]
            * (index_call(config, contexts, itemsize)[1]
               + sparse_call(config, contexts, itemsize)[1])
            + size["sliding"]["planes"]
            * window_call(config, contexts, itemsize)[1])


def steps(config, count):
    """Decode steps the routing counters of one phase were summed over
    (every step visits every held expert of every routed layer once)."""
    size = sizes(config)
    return count["visits"] / (size["experts_held"] * size["moe_layers"])


def decode_step_bytes(config, touched_per_step, contexts, n_steps,
                      itemsize=2):
    """Bytes ONE batched decode step cannot avoid, as the mean over
    ``n_steps`` steps that together processed ``contexts`` and touched
    ``touched_per_step`` (expert, layer) pairs a step: every matmul
    parameter OUTSIDE the routed experts once for the whole batch, the
    matrices of the experts touched, and what the attention reads."""
    size = sizes(config)
    return (itemsize * (size["outside_params"]
                        + size["expert_params"] * touched_per_step)
            + attention_bytes(config, contexts, itemsize) / n_steps)
