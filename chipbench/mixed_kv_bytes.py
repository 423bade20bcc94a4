"""Bytes and operations of a stack whose K/V planes are of TWO kinds (full
planes of few K/V heads beside window planes of more, keys of more lanes
than values) and whose FFN is routed, on the serving path, computed from
sizes and from the requests' contexts at the PUBLISHED values whatever
the pool stores (rows ``pool_rows`` added and the lanes a key is padded
to are the layout's cost, not the model's): whatever implements the
layers, this is what they cannot avoid, so no reading against it passes
100.  ``chipbench/MIXED_KV.md`` has the arithmetic at the published
sizes.

A cached position holds, in a plane of kind ``k``, ``kv_heads[k] x
(key_lanes + value_lanes)`` values.  A decode position of context ``n``
reads ``n`` positions of every full plane and ``min(n, window)`` of every
window plane; each of ``heads`` query heads scores a key over
``key_lanes`` and weighs a value of ``value_lanes``: ``2 x heads x
(key_lanes + value_lanes)`` operations a position a plane.  The sizes
come from the family's ``mixed_sizes(config)`` and ``moe_sizes(config)``;
the counts from the requests' own lengths and the program's routing
counters (``moe_bytes.counts``, which asks no sizes).
"""

from . import families

KINDS = ("full", "window")


def sizes(config):
    """``mixed_sizes`` and ``moe_sizes`` of the configuration's family in
    one dict; ``None`` for a family whose planes are of one kind."""
    family = families.of(config)
    if not hasattr(family, "mixed_sizes"):
        return None
    return dict(family.mixed_sizes(config), **family.moe_sizes(config))


def position_bytes(config, kind, itemsize=2):
    """One cached position in ONE plane of ``kind``."""
    size = sizes(config)
    return (size["kv_heads"][kind]
            * (size["key_lanes"] + size["value_lanes"]) * itemsize)


def attended(config, kind, context):
    """Cached positions ONE plane of ``kind`` gives a new token at a
    context of ``context`` attended tokens (itself included)."""
    return (context if kind == "full"
            else min(context, sizes(config)["window"]))


def paged_call(config, kind, context, itemsize=2):
    """(operations, bytes) of ONE paged call on a plane of ``kind`` for
    one decode position of ``context``."""
    size = sizes(config)
    n = attended(config, kind, context)
    return (2 * size["heads"] * (size["key_lanes"] + size["value_lanes"]) * n,
            n * position_bytes(config, kind, itemsize))


def kv_bytes(config, contexts, itemsize=2):
    """K/V bytes the paged calls of all the planes read for one new token
    per entry of ``contexts``."""
    size = sizes(config)
    return sum(size["planes"][kind] * paged_call(config, kind, n,
                                                 itemsize)[1]
               for kind in KINDS for n in contexts)


def least_seconds(config, contexts, peak, itemsize=2):
    """The least seconds the chip could take over the paged calls of
    ``contexts``: for every decode position and plane the larger of
    reading what its mask lets through and of multiplying it."""
    size = sizes(config)
    total = 0.0
    for kind in KINDS:
        for n in contexts:
            ops, nbytes = paged_call(config, kind, n, itemsize)
            total += size["planes"][kind] * max(
                nbytes / peak["hbm_bytes_per_s"],
                ops / peak["bf16_flops_per_s"])
    return total


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
    matrices of the experts touched, and the K/V the masks let through
    (a full plane every position, a window plane its window)."""
    size = sizes(config)
    return (itemsize * (size["outside_params"]
                        + size["expert_params"] * touched_per_step)
            + kv_bytes(config, contexts, itemsize) / n_steps)
