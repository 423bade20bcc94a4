"""Bytes and operations of a delta-rule mixture-of-experts stack on the
serving path (``families/delta_moe.py``), from sizes and from counts of
what was routed: whatever implements a layer, this is what it cannot
avoid.  ``chipbench/KDA.md`` has the arithmetic at the published sizes.

A delta layer holds, a slot, the state ``S [H, D, D]`` float32 (4,194,304
B at 64 heads of 128).  A decode step reads it once and writes it once
for every LIVE slot in every such layer: it decays it (one operation a
value), takes ``Sb^T k`` (two), adds the rank-one correction (two) and
reads it through ``q`` (two).  A prefill piece of ``n`` rows reads and
writes the ONE slot's state once a layer, takes its rows in (``q | k |
v`` at 2 bytes a value, the log decay of every key lane and ``beta`` in
float32) and gives the read out (float32); in the WY form at tiles of
``TILE`` rows it scores each row's key AND its query against the keys of
its tile up to itself under the lanes' decay ratios (two operations a
lane a pair, twice), solves the tile's unit triangular system against
``V`` and the decayed keys (half of ``TILE x (D + D)`` products a row)
and makes three products against the carried state a row (``2 D D``
each) and one against the tile's corrections.  A routed layer's THREE
grouped products read the three matrices of each expert TOUCHED and
multiply the (row, expert) pairs that fell on a held expert (``6 d e`` a
pair).  A GQA layer's decode position reads the K and V the model caches
of every position it attends.  The sizes come from the family's
``delta_sizes(config)``; the routing counts from the program's counters,
read as ``moe_bytes.counts`` reads them.

``spans_inside`` is how this family's readers count the program's spans:
those that START inside the traced window's interval, on the trace's own
clock, since the device seconds they are held against are cut to that
interval; and ``share`` refuses a roofline share over 105, which no
kernel reaches: the count was too high or the seconds left out part of
the work (the ledger's note on ``retention.step_kernel_roofline``).
"""

import sys

from . import families, moe_bytes

PHASES = moe_bytes.PHASES
# the widest piece the engine prefills (serving.batched_decode.
# PREFILL_PIECE): all pieces of an admission but its last are this wide
PIECE = 512
# rows a tile of the WY form solves together (kernels.delta.TILE)
TILE = 64


def sizes(config):
    """``delta_sizes`` of the configuration's family, or ``None`` for a
    family with no such layers."""
    family = families.of(config)
    if not hasattr(family, "delta_sizes"):
        return None
    return family.delta_sizes(config)


def least_seconds(ops, nbytes, peak):
    return max(nbytes / peak["hbm_bytes_per_s"],
               ops / peak["bf16_flops_per_s"])


def step(config):
    """(operations, bytes) of ONE slot's step in ONE delta layer."""
    size = sizes(config)
    return 7 * size["state_bytes"] // 4, 2 * size["state_bytes"]


def piece(config, rows, itemsize=2):
    """(operations, bytes) of the WY form over ONE prefill piece of
    ``rows`` rows in ONE delta layer."""
    size = sizes(config)
    H, D = size["delta_heads"], size["delta_head_dim"]
    C = min(TILE, rows)
    ops = rows * H * (2 * 2 * D * (C + 1)        # k.k and q.k, a pair a lane
                      + C * 2 * D                # the solve against V and K
                      + 3 * 2 * D * D            # three products with S
                      + C * D)                   # the corrections read by q
    nbytes = (2 * size["state_bytes"]
              + rows * (3 * H * D * itemsize + H * D * 4 + H * 4)
              + rows * H * D * 4)
    return ops, nbytes


def attention(config, attended):
    """(operations, bytes) of the decode positions that together attend
    ``attended`` cached positions, over the GQA layers: the K and V the
    model caches of each, and every query head's score and weighted value
    (``4 x head size`` a head a position)."""
    size = sizes(config)
    return (4 * size["gqa_layers"] * size["query_lanes"] * attended,
            attended * size["kv_bytes_per_token"])


def steps(config, count):
    """Steps (decode) or pieces (prefill) the routing counters of one
    phase were summed over."""
    size = sizes(config)
    return count["visits"] / (size["experts_held"] * size["moe_layers"])


def expert_call_seconds(config, touched, assignments, peak, itemsize=2):
    """The least seconds ONE routed layer's three grouped products can
    take on ``touched`` experts with ``assignments`` (row, expert)
    pairs."""
    size = sizes(config)
    return least_seconds(size["expert_ops_per_row"] * assignments,
                         touched * size["expert_params"] * itemsize, peak)


def decode_step_bytes(config, touched_per_step, live_slots, attended,
                      itemsize=2):
    """Bytes ONE batched decode step cannot avoid at ``live_slots`` slots
    live that together attend ``attended`` cached positions and touch
    ``touched_per_step`` (expert, layer) pairs: every matmul parameter
    OUTSIDE the routed experts once for the whole batch, the matrices of
    the experts touched, the live slots' state of every delta layer read
    and written, and the K/V the model caches of the positions
    attended."""
    size = sizes(config)
    return (itemsize * (size["outside_params"]
                        + size["expert_params"] * touched_per_step)
            + live_slots * size["delta_layers"] * 2 * size["state_bytes"]
            + attended * size["kv_bytes_per_token"])


def spans_inside(profile, interval, name, *attrs):
    """The attributes ``attrs`` of every host span called ``name`` that
    carries them all and STARTS inside ``interval`` (start and end in
    nanoseconds on the trace's clock), one tuple a span."""
    lo, hi = interval
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name and lo <= e.start_ns < hi:
                    stats = dict(e.stats)
                    if all(a in stats for a in attrs):
                        out.append(tuple(stats[a] for a in attrs))
    return out


def share(name, percent):
    """``percent``, or ``None`` (and a line on standard error) where a
    share of a roofline reads over 105: a fault of the count, not a
    reading."""
    if percent > 105.0:
        print(f"chipbench: {name} read {percent!r}%: over 105, a fault of "
              f"the count; left out", file=sys.stderr)
        return None
    return percent
