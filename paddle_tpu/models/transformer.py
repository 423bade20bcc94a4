"""Decoder-only transformer language model — the long-context flagship.

The reference predates transformers (its attention is composed fc+softmax,
``trainer_config_helpers/networks.py simple_attention``); this model is the
framework's NEW long-context capability built the TPU way: fused flash
attention (``ops/pallas_attention.py``), pre-LN residual blocks, bf16
matmuls on the MXU, remat via ``memory_optimize``, and mesh-ready — batch
axis shards over ``dp`` (``parallel.data_parallel``), QKV/FFN weights
column/row-shard over ``tp`` (``parallel.shard_parameters_by_rule``), the
sequence axis over ``sp`` (``parallel.ring_attention``), experts over
``ep`` (``parallel.moe``).
"""

from .. import layers, optimizer as opt
from ..layers import tensor as ltensor


def transformer_block(x, d_model, n_head, d_ff, dropout_rate, is_test,
                      name, attn_block_q=None, attn_block_k=None,
                      attn_packed=None):
    """Pre-LN block: x + MHA(LN(x)) then x + FFN(LN(x))."""
    ln1 = layers.layer_norm(x, begin_norm_axis=2, name=name + "_ln1")
    att = layers.multi_head_attention(
        ln1, ln1, ln1, d_model=d_model, n_head=n_head,
        dropout_rate=dropout_rate, causal=True, is_test=is_test,
        block_q=attn_block_q, block_k=attn_block_k, packed=attn_packed,
        name=name + "_att")
    x = x + att
    ln2 = layers.layer_norm(x, begin_norm_axis=2, name=name + "_ln2")
    ff = layers.fc(ln2, d_ff, num_flatten_dims=2, act="gelu",
                   name=name + "_ffn1")
    ff = layers.fc(ff, d_model, num_flatten_dims=2, name=name + "_ffn2")
    if dropout_rate:
        ff = layers.dropout(ff, dropout_rate, is_test=is_test)
    return x + ff


def gpt_trunk(tokens, vocab_size, n_layer=4, n_head=8, d_model=256,
              d_ff=None, max_len=128, dropout_rate=0.1, is_test=False,
              dtype="bfloat16", attn_block_q=None, attn_block_k=None,
              attn_packed=None):
    """Causal LM trunk up to the final layer norm: [batch, time, d_model]
    hidden states in ``dtype`` (the head is attached by the caller).
    ``attn_block_q``/``attn_block_k`` tune the flash-attention kernel tile
    sizes (smaller q tiles shrink the triangular diagonal band — see
    ops/pallas_attention.py causal_flash_flops)."""
    d_ff = d_ff or 4 * d_model
    b, t = tokens.shape[0], tokens.shape[1]
    emb = layers.embedding(tokens, size=[vocab_size, d_model],
                           param_attr="tok_emb.w")
    pos = ltensor.create_parameter([t, d_model], dtype="float32",
                                   name="pos_emb.w")
    x = emb + pos
    x = ltensor.cast(x, dtype)
    if dropout_rate:
        x = layers.dropout(x, dropout_rate, is_test=is_test)
    for i in range(n_layer):
        x = transformer_block(x, d_model, n_head, d_ff, dropout_rate,
                              is_test, name=f"block{i}",
                              attn_block_q=attn_block_q,
                              attn_block_k=attn_block_k,
                              attn_packed=attn_packed)
    return layers.layer_norm(x, begin_norm_axis=2, name="ln_f")


def gpt(tokens, vocab_size, n_layer=4, n_head=8, d_model=256, d_ff=None,
        max_len=128, dropout_rate=0.1, is_test=False, dtype="bfloat16",
        attn_block_q=None, attn_block_k=None, attn_packed=None):
    """Causal LM trunk: returns [batch, time, vocab] logits (float32)."""
    x = gpt_trunk(tokens, vocab_size, n_layer=n_layer, n_head=n_head,
                  d_model=d_model, d_ff=d_ff, max_len=max_len,
                  dropout_rate=dropout_rate, is_test=is_test, dtype=dtype,
                  attn_block_q=attn_block_q, attn_block_k=attn_block_k,
                  attn_packed=attn_packed)
    logits = layers.fc(x, vocab_size, num_flatten_dims=2, bias_attr=False,
                       name="lm_head")
    return ltensor.cast(logits, "float32")


def tp_rules():
    """Tensor-parallel sharding rules for the flagship transformer
    (apply with ``parallel.shard_parameters_by_rule`` on a mesh with a
    'tp' axis; requires n_head % tp == 0 and vocab % tp == 0):

    - QKV projections column-shard (= whole heads per shard: the packed
      feature dim is the head dim), so the flash kernel runs via
      shard_map over local heads with no cross-shard traffic
      (``flash_attention_packed`` op's tp path);
    - the attention out-projection and FFN2 row-shard (XLA inserts the
      one all-reduce per block pair);
    - FFN1 column-shards;
    - the LM head vocab-shards — the fused CE head merges shard
      softmaxes by logsumexp (``fused_softmax_ce_head`` op's tp path),
      so the [tokens, vocab] logits stay sharded AND off-HBM;
    - everything else (LN, embeddings, remaining biases) replicates.

    The reference's model parallelism is per-layer device placement
    (``ParallelNeuralNetwork.cpp:45``); this is the same capability as
    sharding annotations + compiler collectives instead of threads."""
    from jax.sharding import PartitionSpec as P

    return [
        (r"_att_(q|k|v)\.w$", P(None, "tp")),
        (r"_att_(q|k|v)\.b$", P("tp")),
        (r"_att_out\.w$", P("tp", None)),
        (r"_ffn1\.w$", P(None, "tp")),
        (r"_ffn1\.b$", P("tp")),
        (r"_ffn2\.w$", P("tp", None)),
        (r"^lm_head\.w$", P(None, "tp")),
    ]


def extract_params(scope=None, program=None):
    """Pull the model weights (not optimizer state) out of a scope as the
    name->array dict `generate` consumes."""
    import numpy as np

    from ..core.program import default_main_program
    from ..core.scope import global_scope

    scope = scope or global_scope()
    program = program or default_main_program()
    # weights are Parameter instances; optimizer accumulators are plain
    # persistable vars — all_parameters() is exactly the model weights.
    return {
        p.name: np.asarray(scope.get(p.name))
        for p in program.all_parameters()
        if scope.find_var(p.name) is not None
    }


def infer_compute_dtype(params):
    """The serving dtype the weights imply: the narrowest floating dtype
    among the transformer-block / lm_head MATMUL weights (``block*...w`` /
    ``lm_head.w``).  The embedding tables are deliberately f32 in training
    (master-precision rows, cast after gather), so they must not promote
    the decode; conversely a stray low-precision adapter matrix somewhere
    else in the dict (an fp8/f16 LoRA bolted on later) must not silently
    downgrade the whole decode and its KV caches — hence the scan is
    restricted to the block/head weights that actually feed the MXU.
    Falls back to any >=2-D floating weight when no block/head names
    match (renamed or weight-tied heads), then float32.

    Every architecture the serving engine runs (``serving/arch.py``)
    names its layers' matrices ``block{i}_<name>.w`` and its head
    ``lm_head.w`` (the looped RMSNorm/rotary stack too:
    ``block{i}_att_q.w`` ... ``block{i}_ffn_down.w``), so this answers
    for all of them; norm scales end in ``.scale`` and an exit gate is
    ``exit_gate.w``, outside the scan."""
    import numpy as np

    import jax.numpy as jnp

    def _mats(keys):
        # metadata-only inspection: never jnp.asarray the weights here
        # (that would device-transfer every array just to read dtypes)
        out = []
        for k in keys:
            v = params[k]
            if not (hasattr(v, "dtype") and hasattr(v, "shape")):
                v = np.asarray(v)
            if len(v.shape) >= 2 and jnp.issubdtype(v.dtype, jnp.floating):
                out.append(jnp.dtype(v.dtype))
        return out

    mats = _mats([k for k in params
                  if (k.startswith("block") or k.startswith("lm_head"))
                  and k.endswith(".w")])
    if not mats:
        mats = _mats(list(params))
    return (min(mats, key=lambda d: jnp.dtype(d).itemsize)
            if mats else jnp.float32)


def generate(params, prompt, max_len, n_layer, n_head, d_model,
             temperature=0.0, key=None, eps=1e-5, compute_dtype=None,
             return_logits=True):
    """Jitted autoregressive decoding with a KV cache (pure-JAX serving
    path over the trained Program parameters — train with the Program,
    serve with `jax.jit(generate)`-style incremental decode; the analog
    of the reference's RecurrentGradientMachine.generateSequence,
    `RecurrentGradientMachine.h:307`, re-designed around lax.scan).

    params   name->array mapping with the Program's parameter names
             (e.g. ``scope.to_dict()`` or ``io.load_persistables``);
             works with float32 or bfloat16 weights.
    prompt   [batch, p_len] int32/int64 prompt tokens (p_len >= 1).
    max_len  total sequence length to produce (>= p_len).
    temperature  0.0 = greedy argmax; otherwise softmax sampling
             (``key`` required).

    compute_dtype  matmul/cache dtype.  Default: the params' own dtype —
             bf16-trained weights decode in bf16 (the serving win:
             decode is HBM-bandwidth-bound on weight reads, and bf16
             halves them).  LayerNorm statistics, softmax and the
             emitted logits stay float32 regardless.
    return_logits  False skips stacking the per-step [batch, vocab]
             logits (for max_len=512/vocab=32k that is ~1 GB of scan
             output) — the serving path that only needs tokens.

    Returns ``(tokens, logits)``: tokens [batch, max_len] int32 (prompt
    prefix included verbatim), logits [batch, max_len, vocab] float32
    (position t's next-token distribution; ``None`` when
    ``return_logits=False``).
    """
    import jax
    import jax.numpy as jnp

    if temperature and key is None:
        raise ValueError("temperature > 0 sampling requires a PRNG `key`")
    if compute_dtype is None:
        # the block/lm_head matmul weights decide the serving dtype
        # (see infer_compute_dtype: f32 embedding tables must not promote
        # the decode, stray low-precision adapters must not downgrade it)
        compute_dtype = infer_compute_dtype(params)
    p = {k: jnp.asarray(v, compute_dtype) for k, v in params.items()}
    b, p_len = prompt.shape
    dh = d_model // n_head
    prompt = jnp.asarray(prompt, jnp.int32)
    table_len = p["pos_emb.w.w"].shape[0]
    if max_len > table_len:
        # XLA clamps out-of-range gathers, which would silently reuse the
        # last position embedding past the trained length — fail instead.
        raise ValueError(
            f"max_len {max_len} exceeds the trained position-embedding "
            f"table ({table_len} positions)")
    pos_emb = p["pos_emb.w.w"][:max_len]

    def ln(x, scale, bias):
        # statistics in f32 even under bf16 compute (mean/var cancellation)
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        xn = ((x32 - mu) / jnp.sqrt(var + eps)).astype(x.dtype)
        return xn * scale + bias

    # Layers stay UNROLLED in the step body: each layer's [b, T, h, dh]
    # cache is a separate while-loop carry that XLA updates in place.
    # (A lax.scan over stacked layers was tried and profiled 2.5x slower:
    # the stacked [L, b, T, h, dh] carry forced two full-cache copies
    # per token plus per-layer slice/update churn — 60% of decode time.
    # HLO size is not a reason to scan: pass params as jit ARGUMENTS,
    # closing over them bakes the weights into the HLO as constants.)
    def step_logits(tok, t, cache_k, cache_v):
        """One token [b] at position t -> (logits [b, vocab], caches').
        cache_k/cache_v: tuples of n_layer [b, T, h, dh] arrays."""
        x = p["tok_emb.w"][tok] + pos_emb[t]          # [b, d]
        ck_out, cv_out = [], []
        for i in range(n_layer):
            w = lambda nm: p[f"block{i}_{nm}"]
            h = ln(x, w("ln1.scale"), w("ln1.bias"))
            q = h @ w("att_q.w") + w("att_q.b")
            k = h @ w("att_k.w") + w("att_k.b")
            v = h @ w("att_v.w") + w("att_v.b")
            qh = q.reshape(b, n_head, dh)
            kh = k.reshape(b, n_head, dh)
            vh = v.reshape(b, n_head, dh)
            ck = jax.lax.dynamic_update_index_in_dim(
                cache_k[i], kh, t, axis=1)
            cv = jax.lax.dynamic_update_index_in_dim(
                cache_v[i], vh, t, axis=1)
            ck_out.append(ck)
            cv_out.append(cv)
            s = jnp.einsum("bhd,bThd->bhT", qh, ck,
                           preferred_element_type=jnp.float32)
            s = s / jnp.sqrt(float(dh))
            mask = jnp.arange(max_len)[None, None, :] <= t
            s = jnp.where(mask, s, -1e30)
            a = jax.nn.softmax(s, axis=-1).astype(ck.dtype)
            ctx = jnp.einsum("bhT,bThd->bhd", a, cv).reshape(b, d_model)
            x = x + ctx @ w("att_out.w") + w("att_out.b")
            h2 = ln(x, w("ln2.scale"), w("ln2.bias"))
            # approximate=False is the function the training program's
            # gelu op computes (the exact x * Phi(x); how the op evaluates
            # it for a 16-bit input: ops/activation_ops.py)
            ff = jax.nn.gelu(h2 @ w("ffn1.w") + w("ffn1.b"),
                             approximate=False)
            x = x + ff @ w("ffn2.w") + w("ffn2.b")
        x = ln(x, p["ln_f.scale"], p["ln_f.bias"])
        logits = jnp.matmul(x, p["lm_head.w"],
                            preferred_element_type=jnp.float32)
        return logits, tuple(ck_out), tuple(cv_out)

    cache_k = tuple(jnp.zeros((b, max_len, n_head, dh), compute_dtype)
                    for _ in range(n_layer))
    cache_v = tuple(jnp.zeros((b, max_len, n_head, dh), compute_dtype)
                    for _ in range(n_layer))

    def scan_body(carry, t):
        tokens, cache_k, cache_v, key = carry
        tok = tokens[:, t]
        logits, cache_k, cache_v = step_logits(tok, t, cache_k, cache_v)
        if temperature and key is not None:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, logits / temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        # positions < p_len keep the prompt; after that, append samples;
        # the final step (t+1 == max_len) writes nothing (identity write
        # at the clamped index keeps the last token intact).
        write_to = jnp.minimum(t + 1, max_len - 1)
        cur = tokens[:, write_to]
        writable = ((t + 1) >= p_len) & ((t + 1) < max_len)
        new = jnp.where(writable, nxt.astype(jnp.int32), cur)
        tokens = jax.lax.dynamic_update_index_in_dim(
            tokens, new, write_to, axis=1)
        return (tokens, cache_k, cache_v, key), (
            logits if return_logits else None)

    tokens0 = jnp.zeros((b, max_len), jnp.int32)
    tokens0 = jax.lax.dynamic_update_slice(tokens0, prompt, (0, 0))
    if key is None:
        key = jax.random.PRNGKey(0)
    (tokens, _, _, _), logits = jax.lax.scan(
        scan_body, (tokens0, cache_k, cache_v, key), jnp.arange(max_len))
    if not return_logits:
        return tokens, None
    return tokens, jnp.swapaxes(logits, 0, 1)  # [b, T] , [b, T, vocab]


def build(vocab_size=1000, n_layer=4, n_head=8, d_model=256, d_ff=None,
          max_len=128, dropout_rate=0.1, is_test=False,
          learning_rate=1e-3, dtype="bfloat16", fused_head=False,
          attn_block_q=None, attn_block_k=None, attn_packed=None):
    """Next-token-prediction training program.

    Feeds: tokens [batch, max_len] int64, labels [batch, max_len] int64
    (tokens shifted left by one, label -1 = padding, masked out of the
    loss).

    ``fused_head=True`` replaces the fc + softmax_with_cross_entropy head
    with the Pallas fused head (``layers.fused_softmax_ce_head``): no
    ``[b, t, vocab]`` logits ever hit HBM, which is the difference between
    an HBM-bound and an MXU-bound loss at 32k-vocab flagship shapes.  The
    head weight keeps the name/shape ``lm_head.w [d_model, vocab]`` either
    way, so ``generate`` serves both.  With the fused head ``logits`` is
    None (not materializing them is the point)."""
    tokens = layers.data("tokens", shape=[max_len], dtype="int64")
    labels = layers.data("labels", shape=[max_len], dtype="int64")
    mask2d = ltensor.cast(
        layers.greater_equal(labels, ltensor.fill_constant(
            shape=[1], dtype="int64", value=0)), "float32")
    safe2d = layers.elementwise_max(
        labels, ltensor.fill_constant(shape=[1], dtype="int64", value=0))
    logits = None
    if fused_head:
        x = gpt_trunk(tokens, vocab_size, n_layer=n_layer, n_head=n_head,
                      d_model=d_model, d_ff=d_ff, max_len=max_len,
                      dropout_rate=dropout_rate, is_test=is_test,
                      dtype=dtype, attn_block_q=attn_block_q,
                      attn_block_k=attn_block_k,
                      attn_packed=attn_packed)
        loss = layers.fused_softmax_ce_head(x, safe2d, vocab_size,
                                            name="lm_head")
        masked = ltensor.reshape(loss, [-1, 1]) * ltensor.reshape(
            mask2d, [-1, 1])
    else:
        logits = gpt(tokens, vocab_size, n_layer=n_layer, n_head=n_head,
                     d_model=d_model, d_ff=d_ff, max_len=max_len,
                     dropout_rate=dropout_rate, is_test=is_test,
                     dtype=dtype, attn_block_q=attn_block_q,
                     attn_block_k=attn_block_k, attn_packed=attn_packed)
        flat_logits = ltensor.reshape(logits, [-1, vocab_size])
        flat_labels = ltensor.reshape(safe2d, [-1, 1])
        loss = layers.softmax_with_cross_entropy(flat_logits, flat_labels)
        masked = loss * ltensor.reshape(mask2d, [-1, 1])
    mask = ltensor.reshape(mask2d, [-1, 1])
    avg_cost = layers.reduce_sum(masked) / (
        layers.reduce_sum(mask) + 1e-8)
    optimizer = opt.Adam(learning_rate=learning_rate)
    optimizer.minimize(avg_cost)
    return {"feed": [tokens, labels], "logits": logits,
            "avg_cost": avg_cost}
