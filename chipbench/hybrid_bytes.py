"""Bytes and operations of a hybrid stack's decode step, computed from
sizes alone: a stack whose K/V planes are not all alike (window planes
beside a full one, fewer K/V heads than heads, one plane read by several
layers) and which holds recurrent state beside them.

What ``flops.kv_bytes_per_token`` and ``flops.paged_attention_live``
count for a stack of like planes (planes x heads x head size, every
plane read once and whole) is counted here from the family's
``sizes(config)`` and its further answer ``hybrid_sizes(config)``:
``kv_heads``, ``window``, ``window_planes``, ``full_plane_reads`` and
``state_bytes_per_slot``.  Nothing is read from the program.
"""

from . import families, flops


def sizes(config):
    """``sizes`` and ``hybrid_sizes`` of the configuration's family in one
    dict; a family without ``hybrid_sizes`` is not a hybrid one."""
    family = families.of(config)
    if not hasattr(family, "hybrid_sizes"):
        raise SystemExit(f"chipbench: family {family.__name__!r} gives no "
                         f"hybrid_sizes (configuration "
                         f"{config.get('name')!r})")
    return dict(family.sizes(config), **family.hybrid_sizes(config))


def plane_token_bytes(config, itemsize=2):
    """K and V of one cached position in ONE plane."""
    size = sizes(config)
    return 2 * size["kv_heads"] * size["head_dim"] * itemsize


def kv_bytes_per_token(config, itemsize=2):
    """K and V of one cached token across the planes that hold it, every
    plane kept whole: the window planes and the one full plane."""
    return ((sizes(config)["window_planes"] + 1)
            * plane_token_bytes(config, itemsize))


def attended(config, context):
    """Cached positions the paged calls of ONE new token read, summed
    over the calls, at a context of ``context`` attended tokens (the new
    one included): a window plane gives its last ``window`` at most, the
    full plane gives all of them to each of its readers."""
    size = sizes(config)
    return (size["window_planes"] * min(context, size["window"])
            + size["full_plane_reads"] * context)


def paged_live(config, contexts, itemsize=2):
    """(operations, bytes) of attending one new token per entry of
    ``contexts`` across all the paged calls of a step, reading only the
    keys each call's mask lets through.  A call scores ``heads`` query
    heads of ``head_dim`` (2 operations a lane) and weighs values twice
    as wide (a pair's two softmaxes share the pair's joined value): 6 x
    heads x head_dim operations an attended position a call."""
    size = sizes(config)
    positions = sum(attended(config, n) for n in contexts)
    ops = 6 * size["heads"] * size["head_dim"] * positions
    return ops, positions * plane_token_bytes(config, itemsize)


def decode_step_bytes(config, contexts, steps, itemsize=2):
    """Bytes ONE batched decode step cannot avoid, as the mean over
    ``steps`` steps that together processed ``contexts`` (one entry a
    decode position: the tokens it attended): every matmul parameter at
    2 bytes once for the whole batch, the K/V the masks let through, and
    each live slot's recurrent state read and written back."""
    size = sizes(config)
    _, kv = paged_live(config, contexts, itemsize)
    state = 2 * size["state_bytes_per_slot"] * len(contexts)
    return 2 * flops.matmul_params(config) + (kv + state) / steps
