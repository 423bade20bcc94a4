"""Bytes and operations of a latent plane's attention on the serving
path, and of the decode step around it, computed from sizes and from the
requests' contexts: whatever implements the layer, this is what it cannot
avoid.  ``chipbench/LATENT.md`` has the arithmetic at the published sizes.

A latent plane holds ONE row a cached position: ``values_per_position``
values (the normed latent and the one rotary key all heads share).  A
decode position of context ``n`` reads ``n`` rows in each of ``planes``
planes, ``values_per_position x itemsize`` bytes each, WHATEVER the pool
pads a row to (zero lanes are the layout's cost, not the model's); each
of ``heads`` query rows scores a cached row over ``values_per_position``
lanes and weighs ``value_lanes`` of them: ``2 x heads x
(values_per_position + value_lanes)`` operations a position a plane.
The sizes come from the family's ``latent_sizes(config)`` and
``moe_sizes(config)``; the counts from the requests' own lengths and the
program's routing counters (``moe_bytes.counts``, which asks no sizes).
"""

from . import families

PHASES = ("decode", "prefill")


def sizes(config):
    """``latent_sizes`` and (where the family routes) ``moe_sizes`` of
    the configuration's family in one dict; ``None`` for a family with no
    latent plane."""
    family = families.of(config)
    if not hasattr(family, "latent_sizes"):
        return None
    out = dict(family.latent_sizes(config))
    if hasattr(family, "moe_sizes"):
        out.update(family.moe_sizes(config))
    return out


def position_bytes(config, itemsize=2):
    """One cached position in ONE plane."""
    return sizes(config)["values_per_position"] * itemsize


def attended(config, contexts, itemsize=2):
    """(operations, bytes) of attending one new token per entry of
    ``contexts`` (the tokens it attends, itself included) in every
    latent plane."""
    size = sizes(config)
    positions = size["planes"] * sum(contexts)
    ops = 2 * size["heads"] * (size["values_per_position"]
                               + size["value_lanes"]) * positions
    return ops, positions * position_bytes(config, itemsize)


def least_seconds(config, contexts, peak, itemsize=2):
    """The least seconds the chip could take to attend ``contexts``:
    the larger of reading the rows and of multiplying them."""
    ops, nbytes = attended(config, contexts, itemsize)
    return max(nbytes / peak["hbm_bytes_per_s"],
               ops / peak["bf16_flops_per_s"])


def decode_step_bytes(config, touched_per_step, contexts, n_steps,
                      itemsize=2):
    """Bytes ONE batched decode step cannot avoid, as the mean over
    ``n_steps`` steps that together processed ``contexts`` and touched
    ``touched_per_step`` (expert, layer) pairs a step: every matmul
    parameter OUTSIDE the routed experts once for the whole batch, the
    matrices of the experts touched, and the latent rows of the live
    contexts."""
    size = sizes(config)
    _, latent = attended(config, contexts, itemsize)
    return (itemsize * (size["outside_params"]
                        + size["expert_params"] * touched_per_step)
            + latent / n_steps)
