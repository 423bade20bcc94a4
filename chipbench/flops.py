"""Operations and bytes the algorithms need, computed from shapes alone.

Every function here takes sizes (from a configuration file and a traffic
file) and returns counts; nothing is read from the program, so a PR that
changes the program cannot change what its work is worth.  What a model
is made of comes from its family's ``sizes(config)``
(``chipbench/families/``), never from a configuration's own keys.  Peaks
come from ``peaks.json`` beside this file, keyed by ``device_kind``; a
device that is not in the table is an error, never a default.
"""

import json
import os

from . import families

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind):
    """{"bf16_flops_per_s", "hbm_bytes_per_s", "hbm_bytes"} of one chip."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {_PEAKS}: add a "
            f"row with its source, do not guess")
    return table[device_kind]


def vocab_rows(config):
    """Rows the embedding table and the head hold (the padded vocabulary)."""
    return families.sizes(config)["vocab_rows"]


def matmul_params(config):
    """Matmul parameters applied to every token: the blocks' projections
    and FFN matrices, each as often as it runs, and the head."""
    return families.sizes(config)["matmul_params"]


def train_flops_per_token(config, seq_len):
    """Forward + backward operations one trained token requires.

    6 per matmul parameter (2 forward, 4 backward), plus causal
    attention: QK^T and PV are 2 * 2 * d operations per (query, key)
    pair forward and twice that backward (d the heads' width together),
    a token of a length-t sequence attends (t + 1) / 2 keys on average,
    so 6 * L * d * t over the L attention applications.  PaLM's
    appendix counts 12 * L * d * t, every pair of the square: half of
    those pairs are masked and no causal kernel has to compute them, so
    the smaller figure is used and utilization is never flattered.
    Recomputed operations (remat) are not counted.
    """
    size = families.sizes(config)
    d = size["heads"] * size["head_dim"]
    return (6 * size["matmul_params"]
            + 6 * size["attention_passes"] * d * seq_len)


def flash_fwd(batch, n_head, head_dim, seq_len, itemsize=2):
    """(operations, bytes) of one causal flash-attention forward call:
    two matmuls over the lower triangle; reads Q, K, V and writes O once
    (the row statistics are O(t) and left out)."""
    pairs = batch * n_head * seq_len * (seq_len + 1) // 2
    ops = 2 * 2 * pairs * head_dim
    nbytes = 4 * batch * n_head * seq_len * head_dim * itemsize
    return ops, nbytes


def flash_bwd(batch, n_head, head_dim, seq_len, itemsize=2):
    """(operations, bytes) of one causal flash-attention backward call:
    five matmuls over the lower triangle (S is recomputed by design of
    the algorithm, then dP, dV, dQ, dK); reads Q, K, V, O, dO and writes
    dQ, dK, dV."""
    pairs = batch * n_head * seq_len * (seq_len + 1) // 2
    ops = 5 * 2 * pairs * head_dim
    nbytes = 8 * batch * n_head * seq_len * head_dim * itemsize
    return ops, nbytes


def roofline_seconds(ops, nbytes, peak):
    """The least time one chip could take, and which peak bounds it."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return max(t_ops, t_mem), ("compute" if t_ops >= t_mem else "memory")


def kv_bytes_per_token(config, itemsize=2):
    """K and V of one cached token across all its planes."""
    size = families.sizes(config)
    return (2 * size["kv_planes"] * size["heads"] * size["head_dim"]
            * itemsize)


def paged_attention_live(config, contexts, itemsize=2):
    """(operations, bytes) of attending one new token per entry of
    ``contexts`` (each entry the number of tokens attended, itself
    included) across all layers, reading only the live tokens' K and V:
    2 * 2 * d operations per attended token per attention application
    and 2 * d * itemsize bytes per attended token per plane."""
    size = families.sizes(config)
    attended = int(sum(contexts))
    ops = (4 * size["attention_passes"] * size["heads"] * size["head_dim"]
           * attended)
    return ops, attended * kv_bytes_per_token(config, itemsize)
