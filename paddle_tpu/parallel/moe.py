"""Expert parallelism (mesh axis ``ep``) — mixture-of-experts FFN.

No ancestor in the reference (SURVEY §2.3: EP absent); this supplies the
capability TPU-natively.  Design follows the standard TPU MoE recipe
(Mesh-TensorFlow / GShard lineage): experts are sharded over the ``ep``
mesh axis, tokens are sharded over the same axis (data-parallel shards),
and two ``all_to_all`` collectives over ICI move each token to the device
owning its routed expert and back.  Routing is top-k gating with a fixed
per-expert capacity (static shapes — XLA requirement); overflow tokens
fall through the residual path.  A load-balancing auxiliary loss
(mean gate fraction × mean routed fraction per expert) is returned for
the trainer to add to the objective.

This is the PROGRAM path's layer: softmax top-k gating with a fixed
capacity, so tokens past an expert's capacity are DROPPED, and the
exchange between devices is part of it.  The serving engine's routed
layer is another one and shares nothing with it: dropless, told which
share of the router's experts it holds, sigmoid scores with a selecting
bias, the held experts' rows through a grouped matrix product
(``serving/arch.py``: ``GatedMoE`` and ``route``;
``kernels/grouped_matmul.py``; ``docs/serving.md``, "The routed layer").
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

__all__ = ["init_moe_params", "moe_ffn"]


def init_moe_params(key, num_experts, d_model, d_hidden, dtype=jnp.float32):
    """Returns a dict of MoE FFN params; shard the ``w1``/``b1``/``w2``/``b2``
    leading (expert) axis over ``ep``; ``gate`` stays replicated."""
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = (2.0 / d_model) ** 0.5
    return {
        "gate": (jax.random.normal(k1, (d_model, num_experts)) * s1).astype(dtype),
        "w1": (jax.random.normal(k2, (num_experts, d_model, d_hidden)) * s1).astype(dtype),
        "b1": jnp.zeros((num_experts, d_hidden), dtype),
        "w2": (jax.random.normal(k3, (num_experts, d_hidden, d_model))
               * (2.0 / d_hidden) ** 0.5).astype(dtype),
        "b2": jnp.zeros((num_experts, d_model), dtype),
    }


def _top2_dispatch(logits, capacity):
    """Build dispatch/combine tensors from gating logits.

    logits [n, E] -> dispatch [n, E, C] one-hot-ish bool, combine [n, E, C]
    weights, aux load-balance loss.  Pure jnp: positions within each
    expert's buffer are cumulative counts, tokens past capacity dropped.
    """
    n, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    g1_idx = jnp.argmax(probs, axis=-1)                       # [n]
    mask1 = jax.nn.one_hot(g1_idx, e, dtype=probs.dtype)      # [n, E]
    probs2 = probs * (1.0 - mask1)
    g2_idx = jnp.argmax(probs2, axis=-1)
    mask2 = jax.nn.one_hot(g2_idx, e, dtype=probs.dtype)

    # positions in each expert buffer (first-come order)
    pos1 = (jnp.cumsum(mask1, axis=0) - mask1)                # [n, E]
    keep1 = mask1 * (pos1 < capacity)
    pos2 = (jnp.cumsum(mask2, axis=0) - mask2) + jnp.sum(keep1, axis=0)
    keep2 = mask2 * (pos2 < capacity)

    w1 = jnp.sum(probs * keep1, axis=-1)                      # [n]
    w2 = jnp.sum(probs * keep2, axis=-1)
    denom = jnp.maximum(w1 + w2, 1e-9)
    w1, w2 = w1 / denom, w2 / denom

    def scatter(keep, pos, w):
        # [n, E, C]: token i -> slot pos[i, e] of expert e
        slot = jax.nn.one_hot(
            jnp.sum(pos * keep, axis=-1).astype(jnp.int32), capacity,
            dtype=probs.dtype)                                # [n, C]
        return keep[:, :, None] * slot[:, None, :], \
            (w[:, None, None] * keep[:, :, None]) * slot[:, None, :]

    d1, c1 = scatter(keep1, pos1, w1)
    d2, c2 = scatter(keep2, pos2, w2)
    dispatch = d1 + d2                                        # [n, E, C]
    combine = c1 + c2

    # GShard aux loss: E * mean_e(fraction routed) . mean_e(gate prob)
    density = jnp.mean(mask1, axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * e
    return dispatch, combine, aux


def moe_ffn(params, x, mesh, axis_name="ep", capacity_factor=2.0,
            activation=jax.nn.relu):
    """Top-2 MoE feed-forward over a token batch.

    x ``[n_tokens, d_model]`` globally, sharded on tokens over ``ep``.
    params from ``init_moe_params`` (expert leaves sharded over ``ep``).
    Returns (y ``[n_tokens, d_model]`` same sharding, aux_loss scalar).
    """
    ep = mesh.shape[axis_name]
    e = params["w1"].shape[0]
    if e % ep:
        raise ValueError(f"{e} experts not divisible by ep={ep}")
    e_local = e // ep

    def local_fn(params, x_local):
        n_local, d = x_local.shape
        cap = int(max(1, capacity_factor * n_local / e))
        logits = x_local @ params["gate"].astype(x_local.dtype)
        dispatch, combine, aux = _top2_dispatch(logits, cap)

        # gather expert inputs: [E, C, d] on each (token-shard) device
        expert_in = jnp.einsum(
            "nec,nd->ecd", dispatch.astype(x_local.dtype), x_local)
        # ship token blocks to expert owners: [E, C, d] -> [ep, e_l, C, d]
        expert_in = expert_in.reshape(ep, e_local, cap, d)
        expert_in = jax.lax.all_to_all(
            expert_in, axis_name, split_axis=0, concat_axis=0, tiled=False)
        # now [ep(source shard), e_l, C, d]: all devices' tokens for MY
        # experts — bring the expert axis out front before flattening the
        # per-expert token buffers
        expert_in = expert_in.swapaxes(0, 1).reshape(e_local, ep * cap, d)

        # expert leaves arrive as local shards [e_local, ...]
        w1 = params["w1"].astype(x_local.dtype)
        b1 = params["b1"].astype(x_local.dtype)
        w2 = params["w2"].astype(x_local.dtype)
        b2 = params["b2"].astype(x_local.dtype)
        h = activation(jnp.einsum("end,edf->enf", expert_in, w1)
                       + b1[:, None, :])
        y = jnp.einsum("enf,efd->end", h, w2) + b2[:, None, :]

        # ship results back and un-scatter
        y = y.reshape(e_local, ep, cap, d).swapaxes(0, 1)     # [ep, e_l, C, d]
        y = jax.lax.all_to_all(
            y, axis_name, split_axis=0, concat_axis=0, tiled=False)
        y = y.reshape(e, cap, d)
        out = jnp.einsum("nec,ecd->nd", combine.astype(y.dtype), y)
        return out, jax.lax.pmean(aux, axis_name)

    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=({"gate": P(), "w1": P(axis_name), "b1": P(axis_name),
                   "w2": P(axis_name), "b2": P(axis_name)}, P(axis_name)),
        out_specs=(P(axis_name), P()),
        check_vma=False,
    )
    return fn(params, x)
