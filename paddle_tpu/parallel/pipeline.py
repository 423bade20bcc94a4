"""Pipeline parallelism (mesh axis ``pp``).

The reference's closest ancestor is per-layer device placement with
pipeline threads (``ParallelNeuralNetwork.cpp:45-47`` — layers carry a
``deviceId``, a task queue ships TASK_FORWARD/TASK_BACKWARD between
compute threads).  The TPU-native design has no threads and no queues:
the repeated stage is expressed ONCE, its parameters are stacked with a
leading stage axis sharded over the mesh, and a ``lax.scan`` of
"pipeline ticks" inside ``shard_map`` moves microbatch activations to
the next stage with ``ppermute`` — pipeline scheduling as a pure,
jittable, differentiable program (the backward pass is the autodiff
transpose of the scan, so the reverse ticks come for free).

Two schedules, shared by every entry point via ``_pipeline_ticks``:

* GPipe (``virtual_stages=1``): ``pp`` stages, one per device; ticks =
  ``m + pp - 1``; bubble ``pp - 1`` ticks.
* Interleaved / circular (``virtual_stages=v``): ``v*pp`` stages, stage
  ``s`` on device ``s % pp`` (round-robin, the Megatron "virtual
  pipeline" placement); every microbatch makes ``v`` laps around the
  ring, re-entering through a device-0 buffer.  Ticks =
  ``v*m + pp - 1`` at one-stage-per-tick cost, so the bubble stays
  ``pp - 1`` compute-ticks instead of GPipe's ``v*(pp - 1)`` for the
  same ``v*pp``-layer model.

``pipeline_lm`` runs unequal first/last layers (embedding and loss head)
INSIDE the pipelined region: the embedding is a cheap masked gather in
the ingest hook and the head runs behind a ``lax.cond`` in the emit hook
so only the final stage's device pays for its FLOPs.
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

__all__ = ["pipeline", "pipeline_lm", "stack_stage_params"]


def stack_stage_params(params_list):
    """Stack per-stage parameter pytrees (all the same structure) into one
    pytree whose leaves carry a leading stage axis — shard that axis over
    the ``pp`` mesh axis (``P('pp', ...)``) so each device owns one stage
    (or, with ``virtual_stages=v``, ``v`` round-robin stages)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def _validate(stacked_params, pp, v, m, b, axis_name, what):
    if b % m:
        raise ValueError(f"batch {b} not divisible by {m} microbatches")
    if v > 1 and m < pp:
        raise ValueError(
            f"interleaved schedule needs num_microbatches >= pp "
            f"({m} < {pp}): lap r of a microbatch re-enters device 0 at "
            f"tick r*m + j, which must not precede its lap-(r-1) arrival")
    dims = {p.shape[0] for p in jax.tree.leaves(stacked_params)}
    if dims != {v * pp}:
        raise ValueError(
            f"stacked stage params have leading dim(s) {sorted(dims)} but "
            f"{what} needs exactly {v * pp} stages on mesh axis "
            f"{axis_name!r} (see stack_stage_params)"
        )


def _split_laps(stacked_params, v, pp):
    """[v*pp, ...] -> [v, pp, ...]: stage s = r*pp + d (round-robin)."""
    return jax.tree.map(
        lambda p: p.reshape(v, pp, *p.shape[1:]), stacked_params)


def _pipeline_ticks(stage_fn, params, ingest, emit, acc0, wire_proto,
                    axis_name, pp, v, m):
    """The shared schedule: runs inside shard_map on per-device values.

    params        pytree, local leaves [v, ...] (this device's laps)
    ingest(j)     wire value for a microbatch entering stage 0, lap 0
    emit(acc, h, j, pred)  fold one final-stage output into ``acc``;
                  ``pred`` is this device's emit predicate this tick
    Returns the final ``acc`` (still device-local — mask/psum it).
    """
    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % pp) for i in range(pp)]
    n_buf = m if v > 1 else 1

    def tick(carry, t):
        state, buf, acc = carry
        k = t - idx                      # this device's wave index
        active = (k >= 0) & (k < v * m)
        r = jnp.clip(k // m, 0, v - 1)   # lap
        j = jnp.clip(k % m, 0, m - 1)    # microbatch
        if v > 1:
            # device 0: bank the lap-(r-1) arrival that ppermute delivered
            # this tick (wave t - pp); consumed at wave r*m + j >= bank
            # tick because m >= pp.  Final-lap outputs are never banked.
            arr_valid = (idx == 0) & (t >= pp) & (t - pp < (v - 1) * m)
            arr_j = jnp.clip(jnp.mod(t - pp, m), 0, m - 1)
            buf = jnp.where(arr_valid, buf.at[arr_j].set(state), buf)
            inp0 = jnp.where(r == 0, ingest(j), buf[j])
        else:
            inp0 = ingest(j)
        h_in = jnp.where(idx == 0, inp0, state)
        p_r = jax.tree.map(lambda p: jnp.take(p, r, axis=0), params)
        h = stage_fn(p_r, h_in)
        pred = (idx == pp - 1) & (r == v - 1) & active
        acc = emit(acc, h, j, pred)
        h = jax.lax.ppermute(h, axis_name, fwd)
        return (h, buf, acc), None

    state0 = jnp.zeros_like(wire_proto)
    buf0 = jnp.zeros((n_buf, *wire_proto.shape), wire_proto.dtype)
    (_, _, acc), _ = jax.lax.scan(
        tick, (state0, buf0, acc0), jnp.arange(v * m + pp - 1))
    return acc


def pipeline(stage_fn, stacked_params, x, mesh, axis_name="pp",
             num_microbatches=None, batch_axis=None, virtual_stages=1,
             wire_spec=None):
    """Run stacked copies of ``stage_fn`` as a pipeline.

    stage_fn(params, h) -> h        one stage, shape-preserving
    stacked_params                  pytree, leaves ``[v*pp, ...]``
    x                               ``[batch, ...]`` activations
    num_microbatches                must divide batch; default = pp;
                                    must be >= pp when virtual_stages > 1
    batch_axis                      optional mesh axis name to ALSO shard
                                    the microbatch dim over (dp×pp)
    virtual_stages                  v: stages per device (interleaved
                                    round-robin placement when > 1)
    wire_spec                       optional tuple of mesh-axis names (or
                                    None) for x's dims AFTER batch — e.g.
                                    ``("sp", None)`` seq-shards a
                                    [batch, t, d] wire so stage_fn sees
                                    [mb, t/sp, d] and can run ring
                                    attention over the manual ``sp`` axis
                                    (pp x sp composition); overrides
                                    batch_axis-only sharding

    Returns ``[batch, ...]`` outputs (replicated over ``pp``, sharded
    over ``batch_axis``/``wire_spec`` if given).
    """
    pp = mesh.shape[axis_name]
    v = virtual_stages
    m = num_microbatches or pp
    b = x.shape[0]
    _validate(stacked_params, pp, v, m, b, axis_name, f"pipeline(v={v})")
    xm = x.reshape(m, b // m, *x.shape[1:])
    stacked_params = _split_laps(stacked_params, v, pp)

    def local_fn(params, xm):
        params = jax.tree.map(lambda p: jnp.squeeze(p, 1), params)
        out_buf = _pipeline_ticks(
            stage_fn, params,
            ingest=lambda j: xm[j],
            emit=lambda acc, h, j, pred: jnp.where(
                pred, acc.at[j].set(h), acc),
            acc0=jnp.zeros_like(xm), wire_proto=xm[0],
            axis_name=axis_name, pp=pp, v=v, m=m)
        # only the last stage holds real outputs; replicate via masked psum
        idx = jax.lax.axis_index(axis_name)
        return jax.lax.psum(
            jnp.where(idx == pp - 1, out_buf, jnp.zeros_like(out_buf)),
            axis_name)

    if wire_spec is not None:
        xspec = P(None, batch_axis, *wire_spec)
    else:
        xspec = P(None, batch_axis) if batch_axis else P()
    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, axis_name), xspec), out_specs=xspec,
        check_vma=False,
    )
    out = fn(stacked_params, xm)
    return out.reshape(b, *x.shape[1:])


def pipeline_lm(embed_fn, stage_fn, head_loss_fn, embed_params,
                stacked_params, head_params, tokens, targets, mesh,
                axis_name="pp", num_microbatches=None, batch_axis=None,
                virtual_stages=1):
    """Pipeline with the UNEQUAL first/last layers inside the pipelined
    region — the full LM training objective as one program.

    embed_fn(embed_params, tok [mb, t]) -> h [mb, t, d]
    stage_fn(params, h) -> h                 shape-preserving block
    head_loss_fn(head_params, h, tgt) -> ()  per-microbatch mean loss
    tokens, targets                          [batch, t] int arrays

    Embedding runs in the ingest hook (a cheap masked gather; only stage
    0's result is consumed).  The head — the expensive [d, vocab] matmul
    — runs under ``lax.cond`` with a per-device predicate, so devices
    other than the last stage skip its FLOPs entirely (head_loss_fn must
    therefore contain no collectives).  Returns the scalar mean loss over
    all microbatches (and over ``batch_axis`` shards if given).
    """
    pp = mesh.shape[axis_name]
    v = virtual_stages
    m = num_microbatches or pp
    b = tokens.shape[0]
    _validate(stacked_params, pp, v, m, b, axis_name,
              f"pipeline_lm(v={v})")
    tok_m = tokens.reshape(m, b // m, *tokens.shape[1:])
    tgt_m = targets.reshape(m, b // m, *targets.shape[1:])
    stacked_params = _split_laps(stacked_params, v, pp)

    def local_fn(embed_params, params, head_params, tok_m, tgt_m):
        params = jax.tree.map(lambda p: jnp.squeeze(p, 1), params)

        def emit(losses, h, j, pred):
            loss_j = jax.lax.cond(
                pred,
                lambda: head_loss_fn(head_params, h, tgt_m[j])
                .astype(jnp.float32),
                lambda: jnp.zeros((), jnp.float32),
            )
            return jnp.where(pred, losses.at[j].set(loss_j), losses)

        losses = _pipeline_ticks(
            stage_fn, params,
            ingest=lambda j: embed_fn(embed_params, tok_m[j]),
            emit=emit,
            acc0=jnp.zeros((m,), jnp.float32),
            wire_proto=jax.eval_shape(embed_fn, embed_params, tok_m[0]),
            axis_name=axis_name, pp=pp, v=v, m=m)
        idx = jax.lax.axis_index(axis_name)
        losses = jax.lax.psum(
            jnp.where(idx == pp - 1, losses, jnp.zeros_like(losses)),
            axis_name)
        loss = jnp.mean(losses)
        if batch_axis:
            loss = jax.lax.pmean(loss, batch_axis)
        return loss

    xspec = P(None, batch_axis) if batch_axis else P()
    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(None, axis_name), P(), xspec, xspec),
        out_specs=P(),
        check_vma=False,
    )
    return fn(embed_params, stacked_params, head_params, tok_m, tgt_m)
