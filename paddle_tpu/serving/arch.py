"""What the serving engine reads of a model: one ``Architecture`` object.

The engine (``serving/engine.py``) and its compiled entry points
(``serving/batched_decode.py``) know blocks, tables, slots and windows;
what a model is made of they take from here:

* the attention geometry (``n_head``, ``kv_heads``, ``head_dim``) and
  the K/V planes a cached token holds: ``planes`` lists the pool arrays,
  each with its lower bound (``window``) or ``None``, and ``kv_planes =
  len(planes) * passes`` (one array a layer unless the architecture says
  otherwise; a stack that runs ``passes`` times over the same weights
  caches its own K and V in every pass), which size the pool;
* the state a slot holds BESIDE the pool (``state_spec``): per-slot
  arrays of fixed shape that never grow with the context, what a
  recurrent layer carries from one token to the next;
* the forward, written ONCE per architecture as a function of
  (parameters, rows, positions, a cache interface): ``embed`` makes the
  rows, ``stack`` runs the layers and ``head`` turns rows into float32
  logits.  ``stack`` is shape-agnostic in the leading axes of ``x`` (the
  decode step feeds ``[S, d]``, a window ``[S, W, d]``), so the decode
  chunk, prefill and the speculative verify window run the same lines.

The cache interface is one object the entry points build
(``batched_decode._Cache``), called for attention::

    ctx, planes = attend(planes, plane, pass_idx, q, k, v, **how)

It writes ``k`` and ``v`` (``[..., kv_heads, head_dim]``) into pool
array ``plane`` (of pass ``pass_idx``) through the block table, attends
everything that plane holds up to each row's position, and returns the
context in ``q``'s shape together with the updated planes.  ``pass_idx``
is the Python integer 0 in a stack that runs once, and may be a traced
scalar inside a loop over passes.  ``how`` is what
``kernels.paged_attention.attend`` takes beyond the table: ``group``,
``window``, ``scale``, ``out_dtype``.  With ``k`` and ``v`` ``None``
nothing is written: a READ-ONLY attend of a plane another layer wrote.
With ``v`` alone ``None`` the plane is a latent one (``pool_arrays``
1): ``k [..., values]`` is the one row written, and ``how`` carries
``value_lanes`` (and a ``window`` where the plane has a lower bound).
A latent plane whose rows an INDEXER selects is attended through::

    ctx, planes = attend.sparse(planes, plane, q, row, q_idx, w_idx, k_idx,
                                topk=, value_lanes=, scale=)

which writes the row and the index key ``k_idx`` (the plane's two
arrays, one block id), scores every cached position's key with ``q_idx
[..., H_I, d_I]`` and ``w_idx [..., H_I]``, and attends the ``topk``
rows of largest score.

It has a STATE side for per-slot recurrent state, ``planes``' third
member, one tuple of arrays per state layer::

    rows = attend.state(planes, i)        # each [S, ...]: the slots' rows
    planes = attend.put_state(planes, i, rows')
    attend.valid                          # [S] or [S, W] bool

``state`` gives the rows of the slots this call computes (zeros where a
prompt's first piece starts), ``put_state`` writes them back, and
``valid`` says which rows are real: a recurrence advances its state over
those ONLY (bucket padding, a dead slot's rows leave it as it was).

It also takes the COUNTS a step reports beside its tokens::

    attend.tally(counts)                  # int32 [len(arch.count_names)]

adds to what the compiled decode chunk and prefill piece return as their
last output (``arch.count_names`` names the entries; an architecture
with none never calls it and its programs have no such output).

Eleven architectures are here: ``Gpt2`` (the block of
``models/transformer.py``: pre-LayerNorm, learned absolute positions,
GELU FFN, biases; arithmetic and dtypes exactly those the engine always
served) and ``LoopedRmsRope`` (RMSNorm before AND after each sub-layer,
rotary positions, a gated SiLU FFN, no biases, the whole stack run
``passes`` times over the same weights with the final norm closing every
pass and an exit gate whose weights are held: the Ouro / LoopLM layout,
arXiv:2510.25741).  ``models/ouro_reference.py`` is the second one's
plain reference.  ``SambaY`` is a stack that is NOT one repeated block:
the decoder-hybrid-decoder of arXiv:2507.06607 with differential
attention (arXiv:2410.05258), five kinds of mixer in a fixed order,
fewer K/V heads than heads, window and full planes, one plane read by
several layers, and Mamba state beside the pool;
``models/sambay_reference.py`` is its plain reference.  ``GatedMoE`` is
a stack whose layers differ in BOTH halves: rotary window or
position-free full attention, gated lane by lane, query heads wider
than ``d_model / n_head`` over fewer K/V heads; and a dense FFN or a
ROUTED one, a shared expert beside the share ``(first, count)`` of the
router's experts that this chip holds, every row routed over all of
them and none dropped (``route``, ``kernels/grouped_matmul.py``);
``models/gated_moe_reference.py`` is its plain reference.
``LatentMoE`` caches no K and no V at all: a position holds ONE latent
row a layer (512 + 64 values in the published model) in a pool array
with no head axis (``pool_arrays`` 1, ``attend(.., pool_v=None,
value_lanes=..)``), read by every head through queries absorbed into
the latent's width; its routed layers are ``GatedMoE``'s
(``routed_ffn``, ``route`` with softmax scores left unnormalised);
``models/latent_moe_reference.py`` is its plain reference, in the
per-head form.  ``PowerRetention`` has NO plane at all (``planes ==
()``): every layer's mixer is power retention (arXiv:2507.04239), whose
memory of the context is a state of fixed size a slot a K/V head, read
and written in place for the live slots only (``attend.advance``,
``kernels/retention.py``); ``models/retention_reference.py`` is its
plain reference, in the quadratic form.  ``SinkWindowMoE`` is the first
whose planes are NOT alike: full planes of few K/V heads beside window
planes of more, keys of more lanes than values, a learned sink on the
window planes; what the others state once (``kv_heads``,
``pool_block_shape``, ``written_values``, ``kv_block_bytes``,
``rows_per_entry``) it states a plane (``plane_*``), and its window
planes' chains may hold only their window (``window_chains``);
``models/sink_window_moe_reference.py`` is its plain reference.
``MambaMoE`` is the first whose layers are ONE sub-layer each, a mixer
OR an FFN by a pattern string: Mamba-2 mixers whose state MATRIX a head
is advanced in place (``kernels/ssm.py``), routed FFNs of un-gated
``relu ** 2`` experts (``routed_ffn``'s ``form``), and position-free
attention over two K/V heads; ``models/ssm_moe_reference.py`` is its
plain reference.  ``SparseLatentMoE`` shares ``LatentMoE``'s base
(``_Latent``) and is the first whose latent planes have TWO shapes by
layer type, one of them with a SECOND array that is no V (the index
keys of a learned indexer, ``second_array``): a full layer's query
attends the ``index_topk`` cached positions its indexer scores highest
(``attend.sparse``, ``kernels/sparse_attention.py``), a sliding layer's
a window of latent rows (``attend(.., pool_v=None, window=)``);
``models/sparse_latent_moe_reference.py`` is its plain reference.
``DeltaMoE`` is the first whose recurrence is a DELTA RULE: three layers
in four advance a state matrix a head that first forgets, a key lane at
a time, then writes only what it does not hold of the key yet
(``kernels/delta.py``), the fourth is ``GatedMoE``'s position-free gated
plane, every layer is routed, and because it holds BOTH a state and a
plane it is what a prefix hit over recurrent state was built for
(``serving/engine.py``: one snapshot at the end of a shared head); its
plain reference is ``chipbench/families/delta_moe_reference.py``.
``SparseLightning`` is the first whose K/V planes are read in BLOCKS a
query selects: a layer of block-sparse grouped-query attention caches,
beside K and V, a plane of COMPRESSED keys at another rate than one row a
position (``attend.block_sparse``, ``kernels/block_sparse_attention.py``:
dense under ``dense_len``, the selected blocks as a shorter table a K/V
head from there on), its other layers a recurrence of CONSTANT decay a
head through ``kernels/ssm.py`` (Lightning Attention-2), the table, every
residual branch and the head's input scaled by the model's own
constants; its plain reference is
``chipbench/families/sparse_lightning_reference.py``.

An architecture whose state is large asks for it IN PLACE::

    y, planes = attend.advance(planes, i, kernel, *rows, **how)

hands state layer ``i``'s arrays WHOLE to ``kernel`` (``kernels.
retention`` with rows ``q, k, v, lg``; ``kernels.ssm`` with rows ``xbc,
dt``; a decode step: every slot's row and ``valid``; a piece: the one
slot's rows and whether it starts a prompt), which advances the live
slots' state where it lies and returns the rows' outputs; no ``[S,
...]`` copy of the state exists on either side, where ``state`` /
``put_state`` gather and scatter one.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import block_sparse_attention as _block_sparse
from ..kernels import delta as _delta_rule
from ..kernels import paged_attention as _paged
from ..kernels import retention as _retention
from ..kernels import ssm as _ssm
from ..kernels.grouped_matmul import grouped_matmul as _grouped_matmul
# the named scopes of the lowered program (op_name metadata, read by
# ``observability.trace.device_scopes``): the entry points run a stack
# under STACK_SCOPE, and every architecture enters ``sublayer(kind)`` at
# its sub-layer boundaries, the kinds from trace.KINDS
from ..observability.trace import STACK_SCOPE, sublayer

__all__ = ["Architecture", "Gpt2", "LoopedRmsRope", "SambaY", "GatedMoE",
           "LatentMoE", "PowerRetention", "SinkWindowMoE", "MambaMoE",
           "SparseLatentMoE", "DeltaMoE", "SparseLightning",
           "route", "routed_ffn", "yarn_inv_freq", "yarn_mscale",
           "MOE_COUNTS", "EXPERT_FORMS", "STACK_SCOPE"]


class Architecture:
    """The base: geometry, pool arithmetic and the checks every
    architecture shares.  A subclass gives ``embed``, ``stack``,
    ``head`` and ``check_params``."""

    name = "architecture"
    # what the counts a step reports beside its tokens are
    # (``attend.tally``); none by default
    count_names = ()
    # layers whose FFN is routed over experts, and the experts such a
    # layer holds here (``GatedMoE``); span attributes of the engine
    moe_layers = 0
    experts_held = 0
    # arrays a plane holds: a K and a V array, or ONE where a position's
    # value is lanes of its key row (a latent plane); and, of the planes,
    # how many are latent and in which form their attention runs (span
    # attributes of the engine)
    pool_arrays = 2
    latent_planes = 0
    attn_form = None
    # whether the decode calls of its planes can fetch ONCE a run of
    # table entries several live slots share (latent planes attended
    # whole: ``kernels.paged_attention.shared_runs``); an engine with a
    # prefix trie then says at every dispatch which slots share what
    shares_runs = False
    # layers whose mixer is power retention (a state, no plane)
    retention_layers = 0
    # latent planes that hold an index key beside the row and are attended
    # at the positions an indexer selects (``SparseLatentMoE``)
    index_planes = 0
    # planes whose attention has a learned sink logit a query head
    sink_planes = 0
    # layers whose mixer is a state-space recurrence advanced in place
    # (``kernels/ssm.py``)
    ssm_layers = 0
    # layers whose mixer is a gated delta rule advanced in place
    # (``kernels/delta.py``)
    delta_layers = 0
    # layers whose K/V plane is read in the blocks a query selects, and
    # layers whose mixer is a recurrence of constant decay
    # (``SparseLightning``)
    sparse_layers = 0
    lightning_layers = 0

    def __init__(self, n_layer, n_head, d_model, passes=1, head_dim=None):
        if head_dim is None:
            # heads that split the model's width between them
            if d_model % n_head:
                raise ValueError(
                    f"d_model {d_model} % n_head {n_head} != 0: heads that "
                    f"are not d_model / n_head wide are the architecture's "
                    f"to state (head_dim=)")
            head_dim = d_model // n_head
        if n_layer < 1 or passes < 1 or head_dim < 1:
            raise ValueError(f"n_layer {n_layer}, passes {passes} and "
                             f"head_dim {head_dim} must be >= 1")
        self.n_layer, self.n_head = int(n_layer), int(n_head)
        self.d_model, self.passes = int(d_model), int(passes)
        # a fact of the architecture: query heads may be wider (or
        # narrower) than d_model / n_head
        self.head_dim = int(head_dim)

    @property
    def kv_heads(self):
        """K/V heads a plane holds of each position (``n_head`` unless
        query heads share them)."""
        return self.n_head

    @property
    def planes(self):
        """One entry a pool array: the plane's lower bound (a position
        attends itself and the ``window - 1`` before it) or ``None`` for
        a plane attended whole.  One full plane a layer by default."""
        return (None,) * self.n_layer

    @property
    def kv_planes(self):
        """K/V planes one cached token holds: a plane of its own for
        every (pass, pool array)."""
        return len(self.planes) * self.passes

    @property
    def plane_reads(self):
        """The paged-attention calls one token makes, as ``((window,
        calls), ...)``: every plane once unless layers share one."""
        reads = {}
        for window in self.planes:
            reads[window] = reads.get(window, 0) + self.passes
        return tuple(reads.items())

    @property
    def rows_per_entry(self):
        """Query rows a decode call sends through each block it attends:
        the ``group`` its ``attend`` names (query heads a K/V row, which
        the paged kernel folds into its window), 1 where every query
        head has a K/V head of its own."""
        return 1

    def pool_block_shape(self, block_tokens, dtype):
        """The shape of one block of a pool array in ``dtype``:
        ``[block_tokens, rows, lanes]`` (K/V heads on rows), or
        ``[block_tokens, lanes]`` for a plane with no head axis."""
        return (block_tokens, self.kv_heads, self.head_dim)

    @property
    def written_values(self):
        """Values of one position that a write puts into ONE pool array
        (what ``pool_block_shape`` holds beyond them is zeros)."""
        return self.kv_heads * self.head_dim

    def kv_block_bytes(self, block_tokens, itemsize):
        """Bytes one block of one plane holds of what the model caches:
        ``written_values`` of ``block_tokens`` positions in each of the
        plane's ``pool_arrays``.  An architecture whose plane is shaped
        otherwise says so itself."""
        return (self.pool_arrays * block_tokens * self.written_values
                * itemsize)

    def kv_bytes_per_token(self, itemsize):
        """What one cached token holds across all its planes."""
        return self.passes * sum(self.plane_block_bytes(i, 1, itemsize)
                                 for i in range(len(self.planes)))

    # -- the facts of ONE plane: the architecture's own unless a plane
    # says otherwise (``SinkWindowMoE``: full planes of 4 K/V heads beside
    # window planes of 8, keys of more lanes than values) ----------------
    # whether the window planes' chains may hold ONLY their window (the
    # engine gives a block back once every later query's lower bound has
    # passed it: ``kvcache.WindowChains``) or stay whole, as the chains
    # of an engine with a prefix trie or a draft always do
    window_chains = False

    def plane_kv_heads(self, plane):
        return self.kv_heads

    def plane_rows_per_entry(self, plane):
        return self.rows_per_entry

    def plane_block_shapes(self, plane, block_tokens, dtype):
        """The block shapes of plane ``plane``'s pool arrays: ``(K, V)``,
        or ``(rows,)`` for a plane of ONE array."""
        return (tuple(self.pool_block_shape(block_tokens, dtype)),
                ) * self.pool_arrays

    def plane_tokens_axis(self, plane):
        """The axis of plane ``plane``'s block shapes that counts
        POSITIONS: 0 (``[block_tokens, ...]``) unless a plane's block is
        laid out otherwise (``SparseLightning``'s K/V planes are
        head-major, ``[kv_heads, block_tokens, head_dim]``: 1)."""
        return 0

    def plane_written_values(self, plane):
        """Values of one position a write puts into EACH of the plane's
        pool arrays."""
        return (self.written_values,) * self.pool_arrays

    def second_array(self, plane):
        """Where plane ``plane``'s SECOND pool array lies among the
        engine's second arrays (one for each plane that has two, in the
        planes' order), or ``None`` for a plane of one array."""
        return plane if self.pool_arrays == 2 else None

    def plane_block_bytes(self, plane, block_tokens, itemsize):
        return self.kv_block_bytes(block_tokens, itemsize)

    def chain_kind(self, plane):
        """Which of an engine's chains plane ``plane`` is written and
        read through where it keeps two (``window_chains``): 0 the whole
        chain, 1 the window planes'."""
        return int(self.planes[plane] is not None)

    def state_spec(self, dtype):
        """Per-slot state beside the pool: one tuple of ``(shape,
        dtype)`` per state layer (the engine holds ``[max_slots, *shape]``
        of each); nothing by default.  ``dtype`` is the compute dtype."""
        return ()

    def state_bytes_per_slot(self, dtype):
        return sum(int(np.prod(shape)) * jnp.dtype(dt).itemsize
                   for layer in self.state_spec(dtype)
                   for shape, dt in layer)

    def gauges(self, params):
        """``{name: (value, help)}`` of what the architecture wants set
        as ``serving.<name>`` gauges beside the engine's own, given the
        engine's parameters (``name`` may be ``(name, {label: value})``);
        nothing by default."""
        return {}

    def heads(self, x):
        """``[..., n_head * head_dim] -> [..., n_head, head_dim]``."""
        return x.reshape(*x.shape[:-1], self.n_head, self.head_dim)

    # -- what a subclass answers ------------------------------------------
    def check_params(self, params, max_len):
        """Raise ``ValueError`` where ``params`` cannot serve ``max_len``
        positions under this architecture."""

    def embed(self, p, toks, pos):
        """Rows ``[..., d]`` of tokens ``toks [...]`` at (clipped)
        positions ``pos [...]``."""
        raise NotImplementedError

    def stack(self, p, x, pos, planes, attend):
        """All layers, all passes: ``(x', planes')``; ``x'`` is what
        ``head`` consumes."""
        raise NotImplementedError

    def head(self, p, x):
        """Float32 logits of rows ``x [..., d]``."""
        raise NotImplementedError


def _ln(x, scale, bias, eps):
    # statistics in f32 even under bf16 compute (mean/var cancellation) —
    # mirrors transformer.generate's ln exactly
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    xn = ((x32 - mu) / jnp.sqrt(var + eps)).astype(x.dtype)
    return xn * scale + bias


class Gpt2(Architecture):
    """The ``transformer.build`` block under its parameter names
    (``block{i}_ln1.scale`` ... ``lm_head.w``)."""

    name = "gpt2"

    def __init__(self, n_layer, n_head, d_model, eps=1e-5):
        super().__init__(n_layer, n_head, d_model)
        self.eps = eps

    def check_params(self, params, max_len):
        table_len = np.shape(params["pos_emb.w.w"])[0]
        if max_len > table_len:
            raise ValueError(
                f"max_len {max_len} exceeds the trained position-"
                f"embedding table ({table_len} positions)")

    def embed(self, p, toks, pos):
        with sublayer("embed"):
            return p["tok_emb.w"][toks] + p["pos_emb.w.w"][pos]

    def stack(self, p, x, pos, planes, attend):
        eps = self.eps
        for i in range(self.n_layer):
            w = lambda nm: p[f"block{i}_{nm}"]
            with sublayer("norm"):
                h = _ln(x, w("ln1.scale"), w("ln1.bias"), eps)
            with sublayer("attn.proj"):
                q = h @ w("att_q.w") + w("att_q.b")
                k = h @ w("att_k.w") + w("att_k.b")
                v = h @ w("att_v.w") + w("att_v.b")
            with sublayer("attn.core"):
                ctx, planes = attend(planes, i, 0, self.heads(q),
                                     self.heads(k), self.heads(v))
            with sublayer("attn.proj"):
                x = (x + ctx.reshape(x.shape) @ w("att_out.w")
                     + w("att_out.b"))
            with sublayer("norm"):
                h2 = _ln(x, w("ln2.scale"), w("ln2.bias"), eps)
            with sublayer("ffn"):
                # exact erf gelu, matching transformer.generate and the
                # gelu op
                ff = jax.nn.gelu(h2 @ w("ffn1.w") + w("ffn1.b"),
                                 approximate=False)
                x = x + ff @ w("ffn2.w") + w("ffn2.b")
        return x, planes

    def head(self, p, x):
        with sublayer("head"):
            x = _ln(x, p["ln_f.scale"], p["ln_f.bias"], self.eps)
            return jnp.matmul(x, p["lm_head.w"],
                              preferred_element_type=jnp.float32)


def _rms(x, scale, eps, gain=None):
    # x / sqrt(mean(x^2) + eps) * scale, statistics in f32 (the published
    # RMSNorm upcasts, normalizes, casts back, then scales); ``gain`` (a
    # Python float) multiplies the normalized row while it is float32
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    xn = x32 * jax.lax.rsqrt(ms + eps)
    if gain is not None:
        xn = xn * gain
    return xn.astype(x.dtype) * scale


def _rope_angles(pos, head_dim, theta):
    """``(cos, sin)`` ``[..., 1, head_dim]`` float32 of positions ``pos``
    for ``_rope``."""
    inv = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = pos.astype(jnp.float32)[..., None] * inv              # [..., dh/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[..., None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _gated_silu(x, gate, up, down):
    """The gated SiLU FFN ``(silu(x W_gate) * (x W_up)) W_down``."""
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _rope(x, cos, sin):
    """Rotate-half rotary position: dimension ``i`` pairs with
    ``i + head_dim / 2``.  ``x [..., h, dh]``, ``cos``/``sin``
    ``[..., 1, dh]`` float32; computed in float32, returned in
    ``x.dtype``."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos + rot * sin).astype(x.dtype)


class LoopedRmsRope(Architecture):
    """A stack of sandwich-normed RMSNorm / rotary / gated-SiLU blocks
    run ``passes`` times over the same weights.

    Parameter names: ``tok_emb.w [V, d]``, per layer
    ``block{i}_norm1.scale`` (before attention), ``att_q.w``, ``att_k.w``,
    ``att_v.w``, ``att_out.w`` (``[d, d]``, no biases),
    ``norm2.scale`` (after attention, before the residual add),
    ``norm3.scale`` (before the FFN), ``ffn_gate.w``, ``ffn_up.w``
    (``[d, f]``), ``ffn_down.w`` (``[f, d]``), ``norm4.scale`` (after the
    FFN); then ``norm_f.scale`` (closes EVERY pass and feeds the next),
    ``exit_gate.w [d, 1]`` and ``exit_gate.b [1]`` (held, not applied:
    at ``early_exit_threshold`` 1 every token leaves at the last pass)
    and ``lm_head.w [d, V]`` (not tied).

    The passes are ONE loop in the compiled program (``lax.fori_loop``
    with the planes in the carry): the lowered executables hold one copy
    of the ``n_layer`` bodies whatever ``passes`` is.
    """

    name = "looped_rms_rope"

    def __init__(self, n_layer, n_head, d_model, passes, eps=1e-6,
                 rope_theta=10000.0, early_exit_threshold=1.0):
        super().__init__(n_layer, n_head, d_model, passes)
        if early_exit_threshold < 1.0:
            raise ValueError(
                f"early_exit_threshold {early_exit_threshold} < 1: tokens "
                f"would leave the stack at different passes, and the "
                f"engine has no decode step in which slots leave the "
                f"stack at different passes (every slot runs all "
                f"{passes} passes); serve at threshold 1")
        if self.head_dim % 2:
            raise ValueError(f"rotary positions need an even head_dim, "
                             f"got {self.head_dim}")
        self.eps, self.rope_theta = eps, float(rope_theta)

    def check_params(self, params, max_len):
        missing = [k for k in ("norm_f.scale", "exit_gate.w", "exit_gate.b",
                               "lm_head.w", "tok_emb.w",
                               f"block{self.n_layer - 1}_norm4.scale")
                   if k not in params]
        if missing:
            raise ValueError(f"{self.name}: parameters lack "
                             f"{', '.join(missing)}")

    def embed(self, p, toks, pos):
        with sublayer("embed"):
            return p["tok_emb.w"][toks]

    def _angles(self, pos):
        with sublayer("attn.proj"):
            return _rope_angles(pos, self.head_dim, self.rope_theta)

    def one_pass(self, p, i_pass, x, rope, planes, attend):
        """The ``n_layer`` blocks and the closing norm, once."""
        eps = self.eps
        cos, sin = rope
        for i in range(self.n_layer):
            w = lambda nm: p[f"block{i}_{nm}"]
            with sublayer("norm"):
                a = _rms(x, w("norm1.scale"), eps)
            with sublayer("attn.proj"):
                q = _rope(self.heads(a @ w("att_q.w")), cos, sin)
                k = _rope(self.heads(a @ w("att_k.w")), cos, sin)
                v = self.heads(a @ w("att_v.w"))
            with sublayer("attn.core"):
                ctx, planes = attend(planes, i, i_pass, q, k, v)
            with sublayer("attn.proj"):
                o = ctx.reshape(x.shape) @ w("att_out.w")
            with sublayer("norm"):
                x = x + _rms(o, w("norm2.scale"), eps)
                m = _rms(x, w("norm3.scale"), eps)
            with sublayer("ffn"):
                ff = (jax.nn.silu(m @ w("ffn_gate.w"))
                      * (m @ w("ffn_up.w"))) @ w("ffn_down.w")
            with sublayer("norm"):
                x = x + _rms(ff, w("norm4.scale"), eps)
        with sublayer("norm"):
            # the final norm closes every pass and feeds the next
            return _rms(x, p["norm_f.scale"], eps), planes

    def stack(self, p, x, pos, planes, attend):
        rope = self._angles(pos)

        def one(i_pass, carry):
            x, planes = carry
            return self.one_pass(p, i_pass, x, rope, planes, attend)

        return jax.lax.fori_loop(0, self.passes, one, (x, planes))

    def head(self, p, x):
        # the final norm already closed the last pass
        with sublayer("head"):
            return jnp.matmul(x, p["lm_head.w"],
                              preferred_element_type=jnp.float32)


class SambaY(Architecture):
    """The decoder-hybrid-decoder layout (SambaY, arXiv:2507.06607) with
    differential attention (arXiv:2410.05258): ``n_layer`` pre-LayerNorm
    layers, each a mixer and a gated SiLU MLP, the mixer by layer index
    (``half = n_layer // 2``):

    ====================  =====================  ========================
    layer ``i``           mixer                  holds
    ====================  =====================  ========================
    even, ``<= half``     Mamba-1                per-slot state (no K/V)
    odd, ``< half``       differential           a K/V plane, ``window``
                          attention, windowed
    ``half + 1``          differential           a K/V plane, whole
                          attention, full
    even, ``> half``      gated memory unit      nothing (reads layer
                                                 ``half``'s scan output of
                                                 the same token)
    odd, ``> half + 1``   cross differential     nothing (READS layer
                          attention              ``half + 1``'s plane)
    ====================  =====================  ========================

    ``models/sambay_reference.py`` writes the equations down; this class
    computes the same mathematics through the cache interface.

    **How a pair goes through the paged kernel.**  Heads pair as
    neighbours: query pair ``a`` is heads ``(2a, 2a + 1)``, K pair ``c``
    is K/V heads ``(2c, 2c + 1)``, the joined value ``v_c`` is those two
    V heads side by side, and pair ``a`` reads ``c = a // (n_head //
    kv_heads)``.  A plane therefore holds ``kv_heads / 2`` ROWS of ``2 *
    head_dim`` lanes, K row ``(k1_c | k2_c)`` and V row ``v_c``, and
    every row a query attends is one ``[rows, 2 * head_dim]`` tile with
    no lane left empty (at heads of 64 a ``[.., kv_heads, 64]`` pool
    would leave half of every lane tile empty, and the compiler copies
    such a pool at every call: ``tests/test_paged_compiles_for_chip``).
    A query pair becomes TWO query rows of the same width, ``(q1_a |
    0)`` and ``(0 | q2_a)``: a full-lane dot with the K row is then the
    half's score, each row has a softmax of its own and both weigh the
    whole ``v_c``.  That is ``attend(..., group=2 * n_head // kv_heads,
    scale=head_dim ** -0.5, out_dtype=float32)`` of the one paged kernel
    every architecture calls; ``A1 v - lambda A2 v``, the RMSNorm over
    ``2 * head_dim`` and ``(1 - lambda_0)`` follow here in float32.

    Parameter names: ``tok_emb.w [V, d]`` (also the head: tied),
    ``ln_f.scale/.bias``; per layer ``block{i}_ln1.scale/.bias``,
    ``ln2.scale/.bias``, ``ffn_gu.w [d, 2f]`` (gate | up),
    ``ffn_down.w [f, d]``; a Mamba layer ``ssm_in.w [d, 2n]`` (a | z),
    ``ssm_conv.w [n, taps]``, ``ssm_conv.b [n]``, ``ssm_x.w [n, r + 2s]``
    (delta | B | C), ``ssm_dt.w [r, n]``, ``ssm_dt.b [n]``,
    ``ssm_A_log.w [n, s]``, ``ssm_D.w [n]``, ``ssm_out.w [n, d]``; an
    attention layer ``att_qkv.w [d, d + 2 kv]``, ``att_qkv.b`` (a cross
    layer ``att_q.w [d, d]``, ``att_q.b``), ``att_out.w [d, d]``,
    ``att_out.b``, ``att_lambda_q1/_k1/_q2/_k2.w [head_dim]``,
    ``att_subln.scale [2 head_dim]``; a gated memory unit ``gmu_in.w
    [d, n]``, ``gmu_out.w [n, d]``.
    """

    name = "sambay"

    def __init__(self, n_layer, n_head, kv_heads, d_model, window,
                 d_inner, d_state=16, conv_taps=4, dt_rank=None, eps=1e-5):
        super().__init__(n_layer, n_head, d_model)
        if n_layer < 6 or n_layer % 2:
            raise ValueError(
                f"{self.name}: n_layer {n_layer} must be even and >= 6 "
                f"(a self-decoder with a Mamba and a window layer, the "
                f"full layer, a memory unit and a cross layer)")
        if kv_heads % 2 or n_head % kv_heads:
            raise ValueError(
                f"{self.name}: differential attention pairs heads: "
                f"kv_heads {kv_heads} must be even and divide n_head "
                f"{n_head}")
        self._kv_heads, self.window = int(kv_heads), int(window)
        self.d_inner, self.d_state = int(d_inner), int(d_state)
        self.conv_taps = int(conv_taps)
        self.dt_rank = int(dt_rank or -(-d_model // 16))
        self.eps = eps
        half = n_layer // 2
        self.memory_layer, self.full_layer = half, half + 1
        kinds = []
        for i in range(n_layer):
            if i % 2 == 0:
                kinds.append("mamba" if i <= half else "gmu")
            elif i < half:
                kinds.append("window")
            else:
                kinds.append("full" if i == half + 1 else "cross")
        self.kinds = tuple(kinds)
        # pool array of each layer that owns one, state index of each
        # Mamba layer; the cross layers read the full layer's array
        owners = [i for i, k in enumerate(kinds) if k in ("window", "full")]
        self.plane_of = {i: n for n, i in enumerate(owners)}
        self.state_of = {i: n for n, i in enumerate(
            i for i, k in enumerate(kinds) if k == "mamba")}

    @property
    def kv_heads(self):
        return self._kv_heads

    @property
    def planes(self):
        return tuple(self.window if self.kinds[i] == "window" else None
                     for i in self.plane_of)

    @property
    def plane_reads(self):
        return ((self.window, self.kinds.count("window")),
                (None, 1 + self.kinds.count("cross")))

    @property
    def rows_per_entry(self):
        # two query pairs a K/V row, the two halves of a pair a row each
        return 2 * self.n_head // self.kv_heads

    def pool_block_shape(self, block_tokens, dtype):
        # a row is a PAIR of K/V heads (class docstring)
        return (block_tokens,
                _paged.pool_rows(self.kv_heads // 2, dtype),
                2 * self.head_dim)

    def state_spec(self, dtype):
        one = (((self.d_inner, self.d_state), jnp.float32),
               ((self.conv_taps - 1, self.d_inner), jnp.dtype(dtype)))
        return (one,) * len(self.state_of)

    def check_params(self, params, max_len):
        last = self.n_layer - 1
        need = ["tok_emb.w", "ln_f.scale", "ln_f.bias",
                f"block{last}_ffn_down.w", f"block{last}_att_q.w",
                "block0_ssm_A_log.w", "block1_att_qkv.w",
                f"block{self.full_layer}_att_subln.scale",
                f"block{last - 1}_gmu_out.w"]
        missing = [k for k in need if k not in params]
        if missing:
            raise ValueError(f"{self.name}: parameters lack "
                             f"{', '.join(missing)}")

    def embed(self, p, toks, pos):
        with sublayer("embed"):
            return p["tok_emb.w"][toks]      # no positional encoding

    # -- the mixers -------------------------------------------------------
    def _mamba(self, w, h, planes, cache, n_state):
        """Mamba-1 over the rows ``h [S, d]`` (a decode step) or ``[S,
        W, d]`` (a window): returns ``(out, y, planes)``, ``y`` the scan
        output before gating (float32), the memory of layer ``half``."""
        f32 = jnp.float32
        step = h.ndim == 2
        hw = h[:, None] if step else h                      # [S, W, d]
        valid = cache.valid[:, None] if step else cache.valid
        S, W, _ = hw.shape
        n, taps = self.d_inner, self.conv_taps
        s, conv = cache.state(planes, n_state)
        az = hw @ w("ssm_in.w")
        a, z = az[..., :n], az[..., n:]
        # causal depthwise conv over (the slot's last taps-1 rows, a)
        xs = jnp.concatenate([conv, a], axis=1)             # [S, taps-1+W, n]
        cw = w("ssm_conv.w").astype(f32)
        ac = w("ssm_conv.b").astype(f32) + sum(
            xs[:, k:k + W].astype(f32) * cw[:, k] for k in range(taps))
        # the rows the NEXT call sees: the taps-1 that end at the last
        # real row (real rows are a prefix of the window); with no real
        # row that is ``conv`` itself
        n_real = jnp.sum(valid, axis=1).astype(jnp.int32)
        conv = jax.vmap(lambda rows, k: jax.lax.dynamic_slice_in_dim(
            rows, k, taps - 1, axis=0))(xs, n_real)
        a = jax.nn.silu(ac)                                 # [S, W, n] f32
        r, ds = self.dt_rank, self.d_state
        dbc = a.astype(h.dtype) @ w("ssm_x.w")
        delta = jax.nn.softplus(
            (dbc[..., :r] @ w("ssm_dt.w")).astype(f32)
            + w("ssm_dt.b").astype(f32))
        # a row that is not real advances nothing: exp(0 A) = 1, 0 a B = 0
        delta = jnp.where(valid[..., None], delta, 0.0)
        Bm = dbc[..., r:r + ds].astype(f32)
        Cm = dbc[..., r + ds:].astype(f32)
        A = -jnp.exp(w("ssm_A_log.w").astype(f32))          # [n, s]

        def one(s, row):
            d_t, a_t, b_t, c_t = row                        # [S, n] / [S, s]
            s = (jnp.exp(d_t[..., None] * A) * s
                 + (d_t * a_t)[..., None] * b_t[:, None, :])
            return s, jnp.sum(s * c_t[:, None, :], axis=-1)

        if W == 1:
            s, y = one(s, (delta[:, 0], a[:, 0], Bm[:, 0], Cm[:, 0]))
            y = y[:, None]
        else:
            s, y = jax.lax.scan(one, s, tuple(
                jnp.moveaxis(v, 1, 0) for v in (delta, a, Bm, Cm)))
            y = jnp.moveaxis(y, 0, 1)
        y = y + w("ssm_D.w").astype(f32) * a                # [S, W, n] f32
        out = (y * jax.nn.silu(z.astype(f32))).astype(h.dtype) @ w("ssm_out.w")
        planes = cache.put_state(planes, n_state, (s, conv))
        if step:
            out, y = out[:, 0], y[:, 0]
        return out, y, planes

    def _diff_attention(self, w, i, h, planes, cache, plane, own, window):
        """Differential attention of layer ``i`` over pool array
        ``plane``: its own K and V written first (``own``), or another
        layer's read."""
        f32 = jnp.float32
        d, dh = self.d_model, self.head_dim
        kv = self.kv_heads * dh
        lead = h.shape[:-1]
        pairs = self.n_head // 2
        with sublayer("attn.proj"):
            if own:
                qkv = h @ w("att_qkv.w") + w("att_qkv.b")
                q = qkv[..., :d]
                k = qkv[..., d:d + kv].reshape(
                    *lead, self.kv_heads // 2, 2 * dh)
                v = qkv[..., d + kv:].reshape(
                    *lead, self.kv_heads // 2, 2 * dh)
            else:
                q, k, v = h @ w("att_q.w") + w("att_q.b"), None, None
            # (q1_a | q2_a) -> the rows (q1_a | 0) and (0 | q2_a)
            lane_half = jnp.arange(2 * dh) // dh == jnp.arange(2)[:, None]
            rows = jnp.where(lane_half,
                             q.reshape(*lead, pairs, 1, 2 * dh), 0)
        with sublayer("attn.core"):
            ctx, planes = cache(
                planes, plane, 0, rows.reshape(*lead, 2 * pairs, 2 * dh),
                k, v, group=self.rows_per_entry, window=window,
                scale=dh ** -0.5, out_dtype=f32)
            ctx = ctx.reshape(*lead, pairs, 2, 2 * dh)
            lam0 = 0.8 - 0.6 * float(np.exp(-0.3 * i))
            lam = (jnp.exp(jnp.sum(w("att_lambda_q1.w").astype(f32)
                                   * w("att_lambda_k1.w").astype(f32)))
                   - jnp.exp(jnp.sum(w("att_lambda_q2.w").astype(f32)
                                     * w("att_lambda_k2.w").astype(f32)))
                   + lam0)
            o = ctx[..., 0, :] - lam * ctx[..., 1, :]
            o = (o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                            keepdims=True) + self.eps)
                 * w("att_subln.scale").astype(f32) * (1.0 - lam0))
        with sublayer("attn.proj"):
            return (o.reshape(*lead, d).astype(h.dtype) @ w("att_out.w")
                    + w("att_out.b")), planes

    def stack(self, p, x, pos, planes, attend):
        eps, memory = self.eps, None
        for i, kind in enumerate(self.kinds):
            w = lambda nm: p[f"block{i}_{nm}"]
            with sublayer("norm"):
                h = _ln(x, w("ln1.scale"), w("ln1.bias"), eps)
            if kind == "mamba":
                with sublayer("mixer"):
                    mix, y, planes = self._mamba(w, h, planes, attend,
                                                 self.state_of[i])
                if i == self.memory_layer:
                    memory = y
            elif kind == "gmu":
                with sublayer("mixer"):
                    gate = jax.nn.silu(
                        (h @ w("gmu_in.w")).astype(jnp.float32))
                    mix = (memory * gate).astype(x.dtype) @ w("gmu_out.w")
            else:
                own = kind != "cross"
                mix, planes = self._diff_attention(
                    w, i, h, planes, attend,
                    self.plane_of[i if own else self.full_layer], own,
                    self.window if kind == "window" else None)
            x = x + mix
            with sublayer("norm"):
                h2 = _ln(x, w("ln2.scale"), w("ln2.bias"), eps)
            with sublayer("ffn"):
                gu = h2 @ w("ffn_gu.w")
                f = gu.shape[-1] // 2
                ff = (gu[..., f:].astype(jnp.float32)
                      * jax.nn.silu(gu[..., :f].astype(jnp.float32)))
                x = x + ff.astype(x.dtype) @ w("ffn_down.w")
        return x, planes

    def head(self, p, x):
        with sublayer("head"):
            x = _ln(x, p["ln_f.scale"], p["ln_f.bias"], self.eps)
            return jnp.einsum("...d,vd->...v", x, p["tok_emb.w"],
                              preferred_element_type=jnp.float32)


def route(h, w_router, bias, top_k, scale, score="sigmoid", normalise=True):
    """The routing of a top-k router, written once: rows ``h [..., d]``,
    ``w_router [d, E]``, ``bias [E]`` or ``None`` -> ``(sel [..., top_k]
    int32, w [..., top_k] float32)``.

    Scores are ``sigmoid(h W_r)`` or (``score="softmax"``) ``softmax(h
    W_r)`` over all ``E`` experts, in float32 (the product accumulates
    in float32 whatever the rows' dtype); the bias SELECTS only
    (``top_k`` of ``score + bias``) and is not in the weight; the
    weights are the selected scores, over their sum (``+ 1e-20``) where
    ``normalise`` (over all ``top_k`` whether or not this chip holds the
    expert), times ``scale``.  No capacity, no group limit, nothing
    dropped."""
    if score not in ("sigmoid", "softmax"):
        raise ValueError(f"route: score {score!r} is not 'sigmoid' or "
                         f"'softmax'")
    act = jax.nn.sigmoid if score == "sigmoid" else jax.nn.softmax
    s = act(jnp.matmul(h, w_router, preferred_element_type=jnp.float32))
    _, sel = jax.lax.top_k(
        s if bias is None else s + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(s, sel, axis=-1)
    if normalise:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), picked * scale


MOE_COUNTS = ("moe_rows", "moe_assignments_held", "moe_experts_touched",
              "moe_expert_visits")


# an expert's form: the matrices it holds, in the order they are applied,
# and how each routed one lies, ``[count, k, n]`` (False) or TRANSPOSED,
# ``[count, n, k]`` (True): an ``n`` that is not whole lane tiles (1,856
# is 14.5) is held as the matrix's major axis (kernels/grouped_matmul.py)
EXPERT_FORMS = {
    "gated_silu": (("gate", False), ("up", False), ("down", False)),
    "relu2": (("up", True), ("down", False)),
}


def _relu2(x, up, down):
    """The un-gated FFN ``relu(x W_up) ** 2 W_down``."""
    return jnp.square(jax.nn.relu(x @ up)) @ down


def routed_ffn(w, h, attend, experts, top_k, scale=1.0, score="sigmoid",
               normalise=True, bias=True, shared=True, form="gated_silu"):
    """The routed FFN both routed architectures run, over rows ``h [...,
    d]``: ``(y, counts)``.  ``w(name)`` gives the layer's ``router.w
    [d, width]`` (and ``router.bias`` where ``bias``), ``shared_gate.w``,
    ``shared_up.w``, ``shared_down.w`` and the held experts stacked,
    ``experts_gate.w``, ``experts_up.w [count, d, e]``,
    ``experts_down.w [count, e, d]``; ``experts = (first, count)`` is
    this chip's share of the router's width; ``score``, ``normalise``
    and ``scale`` are ``route``'s.  ``shared`` is the architecture's
    statement: one MLP added for every row (``shared_*.w``), or
    (``False``) none, and then no such matrix is read.  ``form`` is the
    architecture's too (``EXPERT_FORMS``): ``"gated_silu"``, ``(silu(x
    W_gate) * (x W_up)) W_down`` in three grouped products, or
    ``"relu2"``, ``relu(x W_up) ** 2 W_down`` in two (no ``*_gate.w`` is
    read); ``EXPERT_FORMS`` says beside each matrix whether the routed
    experts hold it transposed; the shared expert has the routed
    experts' form.

    Every row is routed over all the router's experts, the held ones add
    their weighted part for the rows that selected them and the shared
    expert adds its own for every row.  No capacity and no dropped token:
    the rows are gathered by expert and go through
    ``kernels.grouped_matmul`` three times (gate, up, down), one buffer
    of ``rows x top_k`` rows whatever the routing.  ``counts`` is
    ``MOE_COUNTS``: the live rows, the row-expert pairs that fell on a
    held expert, the held experts with at least one live row, and
    ``count``."""
    if form not in EXPERT_FORMS:
        raise ValueError(f"routed_ffn: form {form!r} is not one of "
                         f"{sorted(EXPERT_FORMS)}")
    f32, i32 = jnp.float32, jnp.int32
    first, count = experts
    k, d = top_k, h.shape[-1]
    rows = h.reshape(-1, d)
    valid = attend.valid.reshape(-1)
    with sublayer("moe.route"):
        sel, weight = route(rows, w("router.w"),
                            w("router.bias") if bias else None, k, scale,
                            score, normalise)
        # a pair (row, selection) is HELD where the selected expert
        # is one of this chip's and the row is real: a dead slot's
        # row, a window's padding touch no expert
        held = ((sel >= first) & (sel < first + count)
                & valid[:, None])
        expert = jnp.where(held, sel - first, count).reshape(-1)
        # the held pairs first, those of one expert together
        order = jnp.argsort(expert, stable=True)
        sizes = jnp.sum(expert[:, None] == jnp.arange(count, dtype=i32),
                        axis=0, dtype=i32)
        gathered = rows[order // k]
    transposed = dict(EXPERT_FORMS[form])

    def product(x, name):
        return _grouped_matmul(x, w(f"experts_{name}.w"), sizes,
                               transpose_rhs=transposed[name])

    with sublayer("moe.experts"):
        if form == "gated_silu":
            act = (jax.nn.silu(product(gathered, "gate"))
                   * product(gathered, "up"))
        else:
            act = jnp.square(jax.nn.relu(product(gathered, "up")))
        out = product(act, "down")
        # back to (row, selection) order, weighted; a pair that is
        # not held has a zero row of the product and a zero weight
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        y = jnp.sum(out[back].reshape(-1, k, d).astype(f32)
                    * jnp.where(held, weight, 0.0)[..., None], axis=1)
    part = None
    if shared:
        with sublayer("moe.shared"):
            if form == "gated_silu":
                part = _gated_silu(h, w("shared_gate.w"), w("shared_up.w"),
                                   w("shared_down.w"))
            else:
                part = _relu2(h, w("shared_up.w"), w("shared_down.w"))
    counts = jnp.stack([jnp.sum(valid, dtype=i32),
                        jnp.sum(held, dtype=i32),
                        jnp.sum(sizes > 0, dtype=i32),
                        jnp.asarray(count, i32)])
    y = y.astype(h.dtype).reshape(h.shape)
    return (y if part is None else part + y), counts


def _check_share(name, experts, router_width, top_k):
    """``(first, count)`` of a routed architecture's share, checked."""
    first, count = (int(v) for v in experts)
    if not (0 <= first and 1 <= count and first + count <= router_width):
        raise ValueError(
            f"{name}: the share ({first}, {count}) is not "
            f"inside a router of {router_width} experts")
    if not 1 <= top_k <= count:
        # the buffer of gathered rows is rows x top_k: a row's
        # selections are distinct experts, so no more of them than
        # top_k (and than count) can be held
        raise ValueError(f"{name}: top_k {top_k} must lie in "
                         f"[1, experts held {count}]")
    return first, count


def _moe_gauges(arch, params):
    """The ``serving.moe_*`` gauges of a routed architecture."""
    if not arch.moe_layers:
        return {}
    last = arch.last_routed
    matrices = [name for name, _ in EXPERT_FORMS[arch.expert_form]]
    one_expert = sum(
        int(np.prod(np.shape(params[f"block{last}_experts_{m}.w"])[1:]))
        * params[f"block{last}_experts_{m}.w"].dtype.itemsize
        for m in matrices)
    return {
        "moe_layers": (arch.moe_layers, "layers whose FFN is routed"),
        "moe_experts_held": (
            arch.experts_held, "routed experts a routed layer holds "
            "HERE (the chip's share of the router's)"),
        "moe_router_width": (
            arch.router_width, "experts a row is routed over (held "
            "here or not)"),
        "moe_top_k": (arch.top_k, "experts a row selects"),
        "moe_expert_bytes": (
            one_expert, f"bytes of ONE routed expert's "
            f"{ {2: 'two', 3: 'three'}[len(matrices)]} matrices"),
    }


class _Routed:
    """What the routed architectures say of their share of the router's
    experts (``self.experts = (first, count)``, ``router_width``,
    ``top_k``; which layers are routed: ``moe_layers`` of them, the last
    ``last_routed``, by default all after ``dense_layers`` leading dense
    ones; and the experts' form, ``expert_form`` of ``EXPERT_FORMS``):
    the counts their stacks tally, the span attributes and gauges of the
    engine, and the check that the parameters hold that share."""

    count_names = MOE_COUNTS
    expert_form = "gated_silu"

    @property
    def moe_layers(self):
        return self.n_layer - self.dense_layers

    @property
    def last_routed(self):
        """A routed layer's index: the one whose parameters are read for
        what a routed layer holds."""
        return self.n_layer - 1

    @property
    def experts_held(self):
        return self.experts[1] if self.moe_layers else 0

    def _check_experts(self, params):
        last = self.last_routed
        held = np.shape(params[f"block{last}_experts_down.w"])[0]
        width = np.shape(params[f"block{last}_router.w"])[1]
        if (held, width) != (self.experts[1], self.router_width):
            raise ValueError(
                f"{self.name}: parameters hold {held} experts under a "
                f"router of {width}; the architecture says "
                f"{self.experts[1]} of {self.router_width}")


class GatedMoE(_Routed, Architecture):
    """Sandwich-normed layers of gated grouped-query attention and a
    dense or ROUTED gated-SiLU FFN (the ``afmoe`` layout of Arcee's
    Trinity models; ``models/gated_moe_reference.py`` writes the
    equations down and lists what the published configuration has no
    key for).

    ``layer_types[i]`` is ``"window"`` (rotary positions on q and k, a
    query sees itself and the ``window - 1`` keys before it) or
    ``"full"`` (NO positional encoding, causal); every layer owns one
    K/V plane of ``kv_heads`` heads of ``head_dim`` lanes, and query
    head ``a`` reads K/V head ``a // (n_head // kv_heads)``.  q and k
    are RMS-normed over a head's lanes (before the rotation), and the
    joined heads are gated lane by lane, ``ctx * sigmoid(h W_g)``,
    before the output projection.  The table is scaled by ``sqrt(d)``.

    The first ``dense_layers`` layers have a dense FFN; the others a
    routed one.  ``experts = (first, count)`` is THIS chip's share of the
    ``router_width`` experts a routed layer has: every row is routed
    over all ``router_width`` (``route``: sigmoid scores, a bias that
    selects only, ``top_k`` of them, weights normalised over the
    selected and scaled by ``route_scale``), the experts ``first ..
    first + count - 1`` add their weighted part for the rows that
    selected them, and the shared expert adds its own for every row.
    What the experts held elsewhere would add is left out, here and in
    the reference alike; at ``(0, router_width)`` it is the whole model.
    No capacity and no dropped token: the rows are gathered by expert
    and go through ``kernels.grouped_matmul`` (one buffer of ``rows x
    top_k`` rows whatever the routing: the worst case, every selection
    held; the kernel reads the matrices of the experts that got a row).

    The stack tallies, a step (``count_names``): the live rows, the
    row-expert pairs that fell on a held expert, the held experts with
    at least one live row, and the held experts a step could have
    touched at most (``count``: the last's denominator), each summed
    over the routed layers.

    Parameter names: ``tok_emb.w [V, d]``, ``norm_f.scale``,
    ``lm_head.w [d, V]`` (untied); per layer ``block{i}_norm1.scale``
    (before attention), ``att_q.w`` and ``att_gate.w [d, n_head *
    head_dim]``, ``att_k.w`` and ``att_v.w [d, kv_heads * head_dim]``,
    ``att_qnorm.scale`` and ``att_knorm.scale [head_dim]``, ``att_out.w
    [n_head * head_dim, d]``, ``norm2.scale`` (after attention),
    ``norm3.scale`` (before the FFN), ``norm4.scale`` (after it); a
    dense layer ``ffn_gate.w``, ``ffn_up.w [d, f]``, ``ffn_down.w [f,
    d]``; a routed layer ``router.w [d, router_width]``, ``router.bias
    [router_width]``, ``shared_gate.w``, ``shared_up.w [d, e]``,
    ``shared_down.w [e, d]`` and the held experts stacked,
    ``experts_gate.w``, ``experts_up.w [count, d, e]``,
    ``experts_down.w [count, e, d]``.  No biases on the matrices.
    """

    name = "gated_moe"

    def __init__(self, layer_types, n_head, kv_heads, head_dim, d_model,
                 window, dense_layers, router_width, top_k, experts,
                 route_scale=1.0, eps=1e-5, rope_theta=10000.0):
        super().__init__(len(layer_types), n_head, d_model,
                         head_dim=head_dim)
        bad = sorted(set(layer_types) - {"window", "full"})
        if bad:
            raise ValueError(f"{self.name}: layer types {bad}; a layer "
                             f"is 'window' or 'full'")
        if n_head % kv_heads:
            raise ValueError(f"{self.name}: kv_heads {kv_heads} must "
                             f"divide n_head {n_head}")
        if self.head_dim % 2:
            raise ValueError(f"rotary positions need an even head_dim, "
                             f"got {self.head_dim}")
        experts = _check_share(self.name, experts, router_width, top_k)
        if not 0 <= dense_layers <= self.n_layer:
            raise ValueError(f"{self.name}: dense_layers {dense_layers} "
                             f"of {self.n_layer} layers")
        self.layer_types = tuple(layer_types)
        self._kv_heads, self.window = int(kv_heads), int(window)
        self.dense_layers = int(dense_layers)
        self.router_width, self.top_k = int(router_width), int(top_k)
        self.experts = experts
        self.route_scale = float(route_scale)
        self.eps, self.rope_theta = eps, float(rope_theta)

    @property
    def kv_heads(self):
        return self._kv_heads

    @property
    def planes(self):
        return tuple(self.window if kind == "window" else None
                     for kind in self.layer_types)

    @property
    def rows_per_entry(self):
        return self.n_head // self.kv_heads

    def gauges(self, params):
        return _moe_gauges(self, params)

    def pool_block_shape(self, block_tokens, dtype):
        return (block_tokens, _paged.pool_rows(self.kv_heads, dtype),
                self.head_dim)

    def check_params(self, params, max_len):
        last = self.n_layer - 1
        need = ["tok_emb.w", "norm_f.scale", "lm_head.w",
                f"block{last}_att_gate.w", f"block{last}_att_qnorm.scale",
                f"block{last}_norm4.scale"]
        if self.dense_layers:
            need.append("block0_ffn_down.w")
        if self.moe_layers:
            need += [f"block{last}_router.w", f"block{last}_router.bias",
                     f"block{last}_shared_down.w",
                     f"block{last}_experts_down.w"]
        missing = [k for k in need if k not in params]
        if missing:
            raise ValueError(f"{self.name}: parameters lack "
                             f"{', '.join(missing)}")
        if self.moe_layers:
            self._check_experts(params)

    def embed(self, p, toks, pos):
        table = p["tok_emb.w"]
        with sublayer("embed"):
            return table[toks] * jnp.asarray(self.d_model ** 0.5,
                                             table.dtype)

    def _attention(self, w, i, x, rope, planes, attend):
        f32 = jnp.float32
        kind = self.layer_types[i]
        with sublayer("norm"):
            a = _rms(x, w("norm1.scale"), self.eps)
        lead = a.shape[:-1]
        kv = (*lead, self.kv_heads, self.head_dim)
        with sublayer("attn.proj"):
            q = _rms(self.heads(a @ w("att_q.w")), w("att_qnorm.scale"),
                     self.eps)
            k = _rms((a @ w("att_k.w")).reshape(kv), w("att_knorm.scale"),
                     self.eps)
            v = (a @ w("att_v.w")).reshape(kv)
            if kind == "window":
                q, k = _rope(q, *rope), _rope(k, *rope)
            gate = jax.nn.sigmoid((a @ w("att_gate.w")).astype(f32))
        with sublayer("attn.core"):
            ctx, planes = attend(
                planes, i, 0, q, k, v, group=self.rows_per_entry,
                window=self.window if kind == "window" else None)
            # the head gate, lane by lane
            o = (ctx.reshape(*lead, -1).astype(f32) * gate).astype(x.dtype)
        with sublayer("attn.proj"):
            o = o @ w("att_out.w")
        with sublayer("norm"):
            return _rms(o, w("norm2.scale"), self.eps), planes

    def stack(self, p, x, pos, planes, attend):
        with sublayer("attn.proj"):
            rope = _rope_angles(pos, self.head_dim, self.rope_theta)
        for i in range(self.n_layer):
            w = lambda nm: p[f"block{i}_{nm}"]
            a, planes = self._attention(w, i, x, rope, planes, attend)
            x = x + a
            with sublayer("norm"):
                m = _rms(x, w("norm3.scale"), self.eps)
            if i < self.dense_layers:
                with sublayer("ffn"):
                    ff = _gated_silu(m, w("ffn_gate.w"), w("ffn_up.w"),
                                     w("ffn_down.w"))
            else:
                ff, counts = routed_ffn(w, m, attend, self.experts,
                                        self.top_k, self.route_scale)
                attend.tally(counts)
            with sublayer("norm"):
                x = x + _rms(ff, w("norm4.scale"), self.eps)
        return x, planes

    def head(self, p, x):
        with sublayer("head"):
            return jnp.matmul(_rms(x, p["norm_f.scale"], self.eps),
                              p["lm_head.w"],
                              preferred_element_type=jnp.float32)


def yarn_mscale(factor, m):
    """YaRN's attention scale ``0.1 m ln(factor) + 1`` (1 for a factor
    of 1 or less)."""
    return 1.0 if factor <= 1 else 0.1 * m * float(np.log(factor)) + 1.0


def yarn_inv_freq(lanes, theta, factor, original, beta_fast, beta_slow):
    """The ``lanes / 2`` rotary frequencies under YaRN (float32 NumPy):
    frequency ``i`` is ``theta ** (-2 i / lanes)`` where it turns more
    than ``beta_fast`` times over the ``original`` positions (kept),
    that over ``factor`` where it turns fewer than ``beta_slow`` times
    (interpolated), and a linear blend between the two bounds ``lo``,
    ``hi`` (floor and ceiling of ``lanes ln(original / (beta 2 pi)) /
    (2 ln theta)``, clipped to the lanes)."""
    extra = theta ** (-np.arange(0, lanes, 2, dtype=np.float64) / lanes)
    if factor <= 1:
        return extra.astype(np.float32)

    def bound(beta):
        return lanes * np.log(original / (beta * 2 * np.pi)) / (
            2 * np.log(theta))

    lo = max(int(np.floor(bound(beta_fast))), 0)
    hi = min(int(np.ceil(bound(beta_slow))), lanes - 1)
    ramp = np.clip((np.arange(lanes // 2) - lo)
                   / (hi - lo if hi > lo else 0.001), 0.0, 1.0)
    keep = 1.0 - ramp
    return (extra / factor * (1.0 - keep) + extra * keep).astype(np.float32)


class _Latent(_Routed, Architecture):
    """What the architectures that cache ONE latent row a position
    share (``LatentMoE``, ``SparseLatentMoE``): planes of one array with
    no head axis that every head reads whole, queries ABSORBED into the
    cached row's width, a pre-normed stack whose FFN is dense in the
    first ``dense_layers`` layers and routed after (``routed_ffn`` as
    ``route_how`` states it), table not scaled, head untied.  A subclass
    gives ``_angles(pos)`` (what its ``_attention`` takes as ``rope``)
    and ``_attention(w, i, x, rope, planes, attend) -> (a, planes)``."""

    pool_arrays = 1
    attn_form = "absorbed"
    # what ``routed_ffn`` takes beyond the share: the architecture's own
    route_how = {}

    @property
    def kv_heads(self):
        # ONE cached row, which every head reads
        return 1

    @property
    def latent_planes(self):
        return self.n_layer

    @property
    def latent_reads(self):
        """``((planes, bound), ...)``: the latent planes by how many
        cached positions a row reads of them at most (``None``: every
        one up to its own), for whoever counts positions read."""
        return ((self.latent_planes, None),)

    @staticmethod
    def _query_rows(q, kvb, rope, nope, spare):
        """The heads' queries as rows of the cached row's width: ``W_UK``
        absorbed into ``q``'s position-free lanes (``kvb [rank, h, nope |
        v]``), its rotary lanes rotated, ``spare`` zero lanes up to what
        the pool stores."""
        q_lat = jnp.einsum("...hn,rhn->...hr", q[..., :nope],
                           kvb[..., :nope])
        return jnp.concatenate(
            [q_lat, _rope(q[..., nope:], *rope)]
            + ([jnp.zeros((*q.shape[:-1], spare), q.dtype)]
               if spare else []), axis=-1)

    def embed(self, p, toks, pos):
        with sublayer("embed"):
            return p["tok_emb.w"][toks]

    def stack(self, p, x, pos, planes, attend):
        with sublayer("attn.proj"):
            rope = self._angles(pos)
        for i in range(self.n_layer):
            w = lambda nm: p[f"block{i}_{nm}"]
            a, planes = self._attention(w, i, x, rope, planes, attend)
            x = x + a
            with sublayer("norm"):
                m = _rms(x, w("norm2.scale"), self.eps)
            if i < self.dense_layers:
                with sublayer("ffn"):
                    ff = _gated_silu(m, w("ffn_gate.w"), w("ffn_up.w"),
                                     w("ffn_down.w"))
            else:
                ff, counts = routed_ffn(
                    w, m, attend, self.experts, self.top_k,
                    self.route_scale, **self.route_how)
                attend.tally(counts)
            x = x + ff
        return x, planes

    def head(self, p, x):
        with sublayer("head"):
            return jnp.matmul(_rms(x, p["norm_f.scale"], self.eps),
                              p["lm_head.w"],
                              preferred_element_type=jnp.float32)


class LatentMoE(_Latent):
    """Pre-normed layers of LATENT attention and a dense or ROUTED
    gated-SiLU FFN (the ``deepseek_v2`` layout, arXiv:2405.04434;
    ``models/latent_moe_reference.py`` writes the equations down in
    their plain per-head form and lists what the published configuration
    has no key for).

    **What is cached.**  A position holds, a layer, ONE row: the normed
    latent ``c = RMS_kv(h W_kva[:, :rank])`` (``rank`` values) and the
    one rotary key ``k_pe = rope(h W_kva[:, rank:])`` (``rope_dim``
    values) that every head shares: ``rank + rope_dim`` values in one
    pool array ``[blocks, B, L]`` with no head axis and no V array
    (``pool_arrays`` 1; ``L = kernels.paged_attention.latent_lanes``:
    the next multiple of 128, the lanes past the values zeros).

    **How it is attended: absorbed**, decode step and prefill piece
    alike (``attn_form``).  With ``W_kvb [rank, n_head * (nope_dim +
    v_dim)]`` read as ``W_UK_a [rank, nope_dim] | W_UV_a [rank, v_dim]``
    a head, a query head becomes ``q_lat_a = q_nope_a W_UK_a^T`` beside
    its rotated ``q_pe_a``: one row of ``rank + rope_dim`` lanes whose
    dot with a cached row IS the head's score, ``(q_nope_a . k_nope_a(j)
    + q_pe_a . k_pe(j))``.  ``attend(.., pool_v=None, value_lanes=rank)``
    weighs the cached rows' first ``rank`` lanes, ``u_a = sum_j P_j
    c(j)``, and ``o_a = u_a W_UV_a``: no per-head K or V of the context
    is ever made.  At a 128-row piece over 8,300 cached positions the
    absorbed form multiplies 39 GFLOP a layer where expanding the
    gathered latents through ``W_kvb`` multiplies 46 (35 of them the
    expansion, again for every piece), so one form serves both widths.

    Rotary positions take YaRN's frequencies (``yarn_inv_freq``) over
    the ``rope_dim`` lanes, the halves convention of ``_rope``; scores
    are scaled by ``(nope_dim + rope_dim) ** -0.5 * yarn_mscale(factor,
    mscale_all_dim) ** 2``.

    The first ``dense_layers`` layers have a dense FFN, the others the
    routed one ``GatedMoE`` has (``routed_ffn``): ``route`` with softmax
    scores, the selected weights NOT normalised, no bias, ``experts =
    (first, count)`` this chip's share of ``router_width``; the shared
    experts are one gated MLP.  The stack tallies ``MOE_COUNTS``.

    Parameter names: ``tok_emb.w [V, d]``, ``norm_f.scale``,
    ``lm_head.w [d, V]`` (untied); per layer ``block{i}_norm1.scale``,
    ``att_q.w [d, n_head * (nope_dim + rope_dim)]``, ``att_kva.w [d,
    rank + rope_dim]``, ``att_kvnorm.scale [rank]``, ``att_kvb.w [rank,
    n_head * (nope_dim + v_dim)]``, ``att_out.w [n_head * v_dim, d]``,
    ``norm2.scale`` (before the FFN); a dense layer ``ffn_gate.w``,
    ``ffn_up.w [d, f]``, ``ffn_down.w [f, d]``; a routed layer
    ``router.w [d, router_width]`` and ``routed_ffn``'s.  No biases.
    """

    name = "latent_moe"
    route_how = dict(score="softmax", normalise=False, bias=False)
    # every plane is latent and attended whole
    shares_runs = True

    def __init__(self, n_layer, n_head, d_model, rank, nope_dim, rope_dim,
                 v_dim, dense_layers, router_width, top_k, experts,
                 route_scale=1.0, eps=1e-6, rope_theta=10000.0,
                 rope_factor=1.0, rope_original=4096, beta_fast=32.0,
                 beta_slow=1.0, mscale=1.0, mscale_all_dim=0.0):
        super().__init__(n_layer, n_head, d_model,
                         head_dim=nope_dim + rope_dim)
        if rope_dim % 2:
            raise ValueError(f"rotary positions need an even rope_dim, "
                             f"got {rope_dim}")
        if not 0 <= dense_layers <= self.n_layer:
            raise ValueError(f"{self.name}: dense_layers {dense_layers} "
                             f"of {self.n_layer} layers")
        self.rank, self.nope_dim = int(rank), int(nope_dim)
        self.rope_dim, self.v_dim = int(rope_dim), int(v_dim)
        self.dense_layers = int(dense_layers)
        self.router_width, self.top_k = int(router_width), int(top_k)
        self.experts = _check_share(self.name, experts, router_width, top_k)
        self.route_scale, self.eps = float(route_scale), eps
        self.inv_freq = yarn_inv_freq(self.rope_dim, float(rope_theta),
                                      rope_factor, rope_original, beta_fast,
                                      beta_slow)
        # cos and sin times mscale / mscale_all_dim (1 where they are
        # alike), the scores times mscale_all_dim's square
        self.rope_gain = (yarn_mscale(rope_factor, mscale)
                          / yarn_mscale(rope_factor, mscale_all_dim))
        self.scale = (self.head_dim ** -0.5
                      * yarn_mscale(rope_factor, mscale_all_dim) ** 2)
        self.lanes = _paged.latent_lanes(self.written_values)

    @property
    def rows_per_entry(self):
        return self.n_head

    @property
    def written_values(self):
        return self.rank + self.rope_dim

    def pool_block_shape(self, block_tokens, dtype):
        return (block_tokens, self.lanes)

    def kv_block_bytes(self, block_tokens, itemsize):
        # what the plane STORES of a position: ``lanes``, of which
        # ``written_values`` carry the latent and the rotary key
        return block_tokens * self.lanes * itemsize

    def gauges(self, params):
        return dict(_moe_gauges(self, params), **{
            "latent_planes": (self.latent_planes, "planes that cache ONE "
                              "latent row a position (no head axis, no V "
                              "array)"),
            "latent_rank": (self.rank, "lanes of a cached row that are "
                            "the normed latent: the values"),
            "latent_rope_lanes": (self.rope_dim, "lanes of a cached row "
                                  "that are the rotary key all heads share"),
            "latent_lanes_stored": (self.lanes, "lanes a pool row holds: "
                                    "latent_rank + latent_rope_lanes and "
                                    "zeros up to the device's 128-lane "
                                    "tile"),
        })

    def check_params(self, params, max_len):
        last = self.n_layer - 1
        need = ["tok_emb.w", "norm_f.scale", "lm_head.w",
                f"block{last}_att_kva.w", f"block{last}_att_kvb.w",
                f"block{last}_att_kvnorm.scale", f"block{last}_norm2.scale"]
        if self.dense_layers:
            need.append("block0_ffn_down.w")
        if self.moe_layers:
            need += [f"block{last}_router.w", f"block{last}_shared_down.w",
                     f"block{last}_experts_down.w"]
        missing = [k for k in need if k not in params]
        if missing:
            raise ValueError(f"{self.name}: parameters lack "
                             f"{', '.join(missing)}")
        if self.moe_layers:
            self._check_experts(params)

    def _angles(self, pos):
        ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(self.inv_freq)
        ang = jnp.concatenate([ang, ang], axis=-1)[..., None, :]
        return jnp.cos(ang) * self.rope_gain, jnp.sin(ang) * self.rope_gain

    def _attention(self, w, i, x, rope, planes, attend):
        rank, nope = self.rank, self.nope_dim
        with sublayer("norm"):
            a = _rms(x, w("norm1.scale"), self.eps)
        lead = a.shape[:-1]
        with sublayer("attn.proj"):
            q = self.heads(a @ w("att_q.w"))          # [.., h, nope | rope]
            kva = a @ w("att_kva.w")                  # [.., rank | rope]
            c = _rms(kva[..., :rank], w("att_kvnorm.scale"), self.eps)
            k_pe = _rope(kva[..., None, rank:], *rope)[..., 0, :]
            kvb = w("att_kvb.w").reshape(rank, self.n_head, -1)
            # absorb W_UK into the query: a row of the cached row's width
            q_row = self._query_rows(q, kvb, rope, nope,
                                     self.lanes - self.written_values)
            row = jnp.concatenate([c, k_pe], axis=-1)
        with sublayer("attn.core"):
            u, planes = attend(planes, i, 0, q_row, row, None,
                               value_lanes=rank, scale=self.scale)
        with sublayer("attn.proj"):
            o = jnp.einsum("...hr,rhv->...hv", u, kvb[..., nope:])
            return o.reshape(*lead, -1) @ w("att_out.w"), planes


class SparseLatentMoE(_Latent):
    """Pre-normed layers of latent attention in TWO geometries by layer
    type, one of them SPARSE by a learned indexer, and a dense or routed
    gated-SiLU FFN beside a shared expert (the ``dots3_note`` layout's
    language model, whose attention keys are DeepSeek-V3.2's plus one
    ``swa_*`` copy; ``models/sparse_latent_moe_reference.py`` writes the
    equations down per head and lists what the published configuration
    has no key for).

    ``layer_types[i]`` is ``"full"`` or ``"sliding"``; ``full`` and
    ``sliding`` are each a geometry ``{heads, q_rank, rank, nope, rope,
    v, theta}``.  Every layer projects its normed input to a QUERY
    LATENT ``c_q = r_q RMS_q(a W_qa)`` (``q_rank`` values) and a K/V
    latent ``c = r_kv RMS_kv(..)`` beside one rotary key, with ``r = (d /
    rank) ** 0.5`` (the lora rescale), reads the cached rows through
    absorbed queries (``_Latent``), and gates each head's context by
    ``sigmoid(a W_g)`` before ``W_o``.

    **What is cached, by plane** (``plane_block_shapes``).  A SLIDING
    plane holds one array of ``rank + rope`` values a position (1,024 +
    64 in ``latent_lanes`` = 1,152 stored), attended under the lower
    bound ``window`` (a query sees itself and the ``window - 1`` rows
    before it: ``attend(.., pool_v=None, window=)``).  A FULL plane
    holds TWO arrays under one block id: the latent row (512 + 64 in
    640) and the INDEX KEY ``k_I = rope(LayerNorm(a W_Ik))``
    (``index_dim`` values).  Its attention is ``attend.sparse``
    (``kernels/sparse_attention.py``): ``I(t, j) = sum_h w_h relu(q_Ih .
    k_I(j))`` over every cached position with ``q_I = c_q W_Iqb``
    (``index_heads`` heads) and ``w = float32(a W_Iw) index_heads^-1/2
    index_dim^-1/2``, the ``index_topk`` positions of largest ``I``, and
    ONE softmax over those rows for all the heads.  While a table holds
    no more than ``index_topk`` positions the call is the dense one.
    ``window_chains``: an engine without a prefix trie keeps, of a
    sliding plane, the blocks its window can still see.

    The first ``dense_layers`` layers have a dense FFN, the others the
    routed one (``routed_ffn``: sigmoid scores, a bias that selects
    only, the selected weights normalised where ``norm_topk``, times
    ``route_scale``) beside ONE shared expert of the experts' width.

    Parameter names: ``tok_emb.w [V, d]``, ``norm_f.scale``, ``lm_head.w
    [d, V]``; per layer ``block{i}_norm1.scale``, ``att_qa.w [d,
    q_rank]``, ``att_qnorm.scale``, ``att_qb.w [q_rank, h * (nope +
    rope)]``, ``att_kva.w [d, rank + rope]``, ``att_kvnorm.scale``,
    ``att_kvb.w [rank, h * (nope + v)]``, ``att_gate.w [d, h]``,
    ``att_out.w [h * v, d]``, ``norm2.scale``; a full layer ``idx_qb.w
    [q_rank, index_heads * index_dim]``, ``idx_k.w [d, index_dim]``,
    ``idx_knorm.scale``, ``idx_knorm.bias``, ``idx_w.w [d,
    index_heads]``; the FFN's as ``LatentMoE``'s, with ``router.bias``.
    """

    name = "sparse_latent_moe"
    window_chains = True
    INDEX_EPS = 1e-6

    def __init__(self, layer_types, d_model, full, sliding, window,
                 index_heads, index_dim, index_topk, dense_layers,
                 router_width, top_k, experts, route_scale=1.0,
                 norm_topk=True, eps=1e-5):
        bad = sorted(set(layer_types) - {"full", "sliding"})
        if bad or not layer_types:
            raise ValueError(f"{self.name}: layer types {bad}; a layer is "
                             f"'full' or 'sliding'")
        self.layer_types = tuple(layer_types)
        self._geo = {"full": dict(full), "sliding": dict(sliding)}
        first = self._geo[self.layer_types[0]]
        super().__init__(len(layer_types), first["heads"], d_model,
                         head_dim=first["nope"] + first["rope"])
        for kind, g in self._geo.items():
            if g["rope"] % 2:
                raise ValueError(f"{self.name}: rotary positions need an "
                                 f"even rope width, {kind} has {g['rope']}")
            g["lanes"] = _paged.latent_lanes(g["rank"] + g["rope"])
            g["scale"] = float(g["nope"] + g["rope"]) ** -0.5
            g["r_q"] = (d_model / g["q_rank"]) ** 0.5
            g["r_kv"] = (d_model / g["rank"]) ** 0.5
        if not 0 <= dense_layers <= self.n_layer:
            raise ValueError(f"{self.name}: dense_layers {dense_layers} "
                             f"of {self.n_layer} layers")
        if index_topk < 1 or window < 1:
            raise ValueError(f"{self.name}: index_topk {index_topk} and "
                             f"window {window} must be >= 1")
        if self._geo["full"]["rope"] > index_dim:
            raise ValueError(f"{self.name}: the indexer rotates the full "
                             f"layers' {self._geo['full']['rope']} lanes "
                             f"of {index_dim}")
        self.window, self.index_topk = int(window), int(index_topk)
        self.index_heads, self.index_dim = int(index_heads), int(index_dim)
        self.dense_layers = int(dense_layers)
        self.router_width, self.top_k = int(router_width), int(top_k)
        self.experts = _check_share(self.name, experts, router_width, top_k)
        self.route_scale, self.eps = float(route_scale), eps
        self.route_how = dict(score="sigmoid", normalise=bool(norm_topk),
                              bias=True, shared=True)

    def _of(self, plane):
        return self._geo[self.layer_types[plane]]

    @property
    def planes(self):
        return tuple(self.window if kind == "sliding" else None
                     for kind in self.layer_types)

    @property
    def index_planes(self):
        """Planes that hold an index key beside the latent row."""
        return self.layer_types.count("full")

    @property
    def latent_reads(self):
        return ((self.index_planes, self.index_topk),
                (self.n_layer - self.index_planes, self.window))

    def plane_rows_per_entry(self, plane):
        return self._of(plane)["heads"]

    def plane_block_shapes(self, plane, block_tokens, dtype):
        row = (block_tokens, self._of(plane)["lanes"])
        if self.layer_types[plane] == "sliding":
            return (row,)
        return (row, (block_tokens, self.index_dim))

    def plane_written_values(self, plane):
        g = self._of(plane)
        if self.layer_types[plane] == "sliding":
            return (g["rank"] + g["rope"],)
        return (g["rank"] + g["rope"], self.index_dim)

    def plane_block_bytes(self, plane, block_tokens, itemsize):
        # what the model caches of a position: the published values
        return block_tokens * sum(self.plane_written_values(plane)) * itemsize

    def second_array(self, plane):
        if self.layer_types[plane] == "sliding":
            return None
        return self.layer_types[:plane].count("full")

    def gauges(self, params):
        out = dict(_moe_gauges(self, params), **{
            "latent_planes": (self.latent_planes, "planes that cache ONE "
                              "latent row a position (no head axis, no V "
                              "array)"),
            "index_planes": (self.index_planes, "of them, planes that hold "
                             "an index key beside the row and are attended "
                             "at the positions their indexer selects"),
            "latent_window_planes": (
                self.n_layer - self.index_planes, "of them, planes "
                "attended under the lower bound latent_window"),
            "latent_window": (self.window, "positions a sliding plane's "
                              "query attends (itself among them)"),
            "index_topk": (self.index_topk, "cached positions a full "
                           "plane's query attends at most"),
            "index_lanes_stored": (self.index_dim, "lanes of an index key"),
            "kv_stored_bytes_per_token": (
                sum(int(np.prod(shape[1:])) for i in range(self.n_layer)
                    for shape in self.plane_block_shapes(i, 1, None))
                * params["tok_emb.w"].dtype.itemsize,
                "bytes the pool arrays STORE of one cached token across "
                "its planes: rows up to the 128-lane tile, the index keys "
                "among them"),
        })
        for kind in sorted(set(self.layer_types)):
            g, labels = self._geo[kind], (("kind", kind),)
            out[("latent_lanes_stored", labels)] = (
                g["lanes"], "lanes a pool row of a plane of this kind "
                "holds: rank + rotary lanes and zeros up to the tile")
            out[("latent_rank", labels)] = (
                g["rank"], "lanes of a cached row that are the normed "
                "latent: the values")
        return out

    def check_params(self, params, max_len):
        need = ["tok_emb.w", "norm_f.scale", "lm_head.w"]
        for i, kind in enumerate(self.layer_types):
            need += [f"block{i}_{k}" for k in (
                "norm1.scale", "att_qa.w", "att_qnorm.scale", "att_qb.w",
                "att_kva.w", "att_kvnorm.scale", "att_kvb.w", "att_gate.w",
                "att_out.w", "norm2.scale")]
            if kind == "full":
                need += [f"block{i}_{k}" for k in (
                    "idx_qb.w", "idx_k.w", "idx_knorm.scale",
                    "idx_knorm.bias", "idx_w.w")]
            need += [f"block{i}_{k}" for k in (
                ("ffn_down.w",) if i < self.dense_layers else
                ("router.w", "router.bias", "shared_down.w",
                 "experts_down.w"))]
        missing = [k for k in need if k not in params]
        if missing:
            raise ValueError(f"{self.name}: parameters lack "
                             f"{', '.join(missing)}")
        for i in range(self.n_layer):
            g = self._of(i)
            want = {"att_qb.w": (g["q_rank"],
                                 g["heads"] * (g["nope"] + g["rope"])),
                    "att_kva.w": (self.d_model, g["rank"] + g["rope"]),
                    "att_kvb.w": (g["rank"],
                                  g["heads"] * (g["nope"] + g["v"]))}
            for k, shape in want.items():
                got = tuple(np.shape(params[f"block{i}_{k}"]))
                if got != shape:
                    raise ValueError(
                        f"{self.name}: layer {i} ({self.layer_types[i]}) "
                        f"holds {k} {got}; its geometry says {shape}")
        if self.moe_layers:
            self._check_experts(params)

    def _angles(self, pos):
        return {kind: _rope_angles(pos, g["rope"], g["theta"])
                for kind, g in self._geo.items() if kind in self.layer_types}

    def _attention(self, w, i, x, ropes, planes, attend):
        kind = self.layer_types[i]
        g, rope = self._geo[kind], ropes[kind]
        rank, nope, h = g["rank"], g["nope"], g["heads"]
        with sublayer("norm"):
            a = _rms(x, w("norm1.scale"), self.eps)
        lead = a.shape[:-1]
        with sublayer("attn.proj"):
            # the lora rescale rides the norm's float32 row: 5 ** 0.5 and
            # 10 ** 0.5 are not bfloat16 numbers
            c_q = _rms(a @ w("att_qa.w"), w("att_qnorm.scale"), self.eps,
                       gain=g["r_q"])
            q = (c_q @ w("att_qb.w")).reshape(*lead, h, -1)
            kva = a @ w("att_kva.w")                  # [.., rank | rope]
            c = _rms(kva[..., :rank], w("att_kvnorm.scale"), self.eps,
                     gain=g["r_kv"])
            k_pe = _rope(kva[..., None, rank:], *rope)[..., 0, :]
            kvb = w("att_kvb.w").reshape(rank, h, -1)
            q_row = self._query_rows(q, kvb, rope, nope,
                                     g["lanes"] - rank - g["rope"])
            row = jnp.concatenate([c, k_pe], axis=-1)
            gate = jax.nn.sigmoid(a @ w("att_gate.w"))           # [.., h]
            if kind == "full":
                r = g["rope"]
                q_i = (c_q @ w("idx_qb.w")).reshape(
                    *lead, self.index_heads, self.index_dim)
                q_i = jnp.concatenate(
                    [_rope(q_i[..., :r], *rope), q_i[..., r:]], axis=-1)
                k_i = _ln(a @ w("idx_k.w"), w("idx_knorm.scale"),
                          w("idx_knorm.bias"), self.INDEX_EPS)
                k_i = jnp.concatenate(
                    [_rope(k_i[..., None, :r], *rope)[..., 0, :],
                     k_i[..., r:]], axis=-1)
                w_i = jnp.matmul(
                    a, w("idx_w.w"), preferred_element_type=jnp.float32
                ) * (self.index_heads ** -0.5 * self.index_dim ** -0.5)
        with sublayer("attn.core"):
            if kind == "full":
                u, planes = attend.sparse(
                    planes, i, q_row, row, q_i, w_i, k_i,
                    topk=self.index_topk, value_lanes=rank,
                    scale=g["scale"])
            else:
                u, planes = attend(planes, i, 0, q_row, row, None,
                                   value_lanes=rank, scale=g["scale"],
                                   window=self.window)
        with sublayer("attn.proj"):
            o = jnp.einsum("...hr,rhv->...hv", u, kvb[..., nope:])
            o = o * gate[..., None]
            return o.reshape(*lead, -1) @ w("att_out.w"), planes


class SinkWindowMoE(_Routed, Architecture):
    """Pre-normed layers of grouped-query attention in TWO geometries
    and a dense or ROUTED gated-SiLU FFN with no shared expert (the
    ``mimo_v2`` layout of Xiaomi's MiMo-V2 models;
    ``models/sink_window_moe_reference.py`` writes the equations down
    and lists what the published configuration has no key for).

    ``layer_types[i]`` is ``"full"`` (causal over the whole context,
    ``kv_heads`` K/V heads, rotary ``rope_theta``) or ``"window"`` (a
    query sees itself and the ``window - 1`` keys before it,
    ``window_kv_heads`` K/V heads, rotary ``window_rope_theta``, and a
    learned SINK: one logit a query head that joins every row's softmax,
    takes its share of the mass and adds no value).  Every layer has
    ``n_head`` query heads of ``head_dim`` lanes over keys of
    ``head_dim`` and VALUES of ``v_dim`` lanes (192 over 128 in the
    published model); q and k are rotated in their first
    ``rotary_lanes`` lanes only (the halves convention of ``_rope`` over
    those lanes), the others are position-free; v is scaled by
    ``value_scale`` before it is cached; scores are scaled by
    ``head_dim ** -0.5``; no q/k norm, no gate, no bias.

    **Planes that are not alike** (the ``plane_*`` facts): a full plane
    holds ``kv_heads`` rows a position and a window plane
    ``window_kv_heads``, each ``pool_rows`` of them; a plane's K array
    stores ``kernels.paged_attention.key_lanes(head_dim)`` lanes (256
    for 192: the lanes past the key zeros, which ``write`` puts there
    and ``attend`` pads the query to) and its V array ``v_dim``.
    ``window_chains``: an engine without a prefix trie and without a
    draft keeps, of a window plane, the blocks its window can still see
    and gives the others back (``kvcache.WindowChains``).

    The first ``dense_layers`` layers have a dense FFN, the others the
    routed one (``routed_ffn`` with ``shared=False``): ``route`` with
    sigmoid scores, a bias that selects only, ``top_k`` of
    ``router_width``, the weights normalised over the selected
    (``norm_topk``) and not scaled; ``experts = (first, count)`` is this
    chip's share.  The stack tallies ``MOE_COUNTS``.

    Parameter names: ``tok_emb.w [V, d]`` (not scaled), ``norm_f.scale``,
    ``lm_head.w [d, V]`` (untied); per layer ``block{i}_norm1.scale``,
    ``att_qkv.w [d, n_head * head_dim + hk * head_dim + hk * v_dim]`` (q
    | k | v, ``hk`` the layer's K/V heads), ``att_out.w [n_head * v_dim,
    d]``, a window layer ``att_sink.b [n_head]``; ``norm2.scale``; a
    dense layer ``ffn_gate.w``, ``ffn_up.w [d, f]``, ``ffn_down.w [f,
    d]``; a routed layer ``router.w [d, router_width]``, ``router.bias
    [router_width]``, ``experts_gate.w``, ``experts_up.w [count, d,
    e]``, ``experts_down.w [count, e, d]``.
    """

    name = "sink_window_moe"
    window_chains = True

    def __init__(self, layer_types, n_head, kv_heads, window_kv_heads,
                 head_dim, v_dim, d_model, window, rotary_lanes,
                 dense_layers, router_width, top_k, experts,
                 value_scale=1.0, norm_topk=True, eps=1e-5,
                 rope_theta=10000000.0, window_rope_theta=10000.0):
        super().__init__(len(layer_types), n_head, d_model,
                         head_dim=head_dim)
        bad = sorted(set(layer_types) - {"window", "full"})
        if bad:
            raise ValueError(f"{self.name}: layer types {bad}; a layer "
                             f"is 'window' or 'full'")
        for hk in (kv_heads, window_kv_heads):
            if n_head % hk:
                raise ValueError(f"{self.name}: K/V heads {hk} must "
                                 f"divide n_head {n_head}")
        if rotary_lanes % 2 or not 0 < rotary_lanes <= self.head_dim:
            raise ValueError(f"{self.name}: rotary_lanes {rotary_lanes} "
                             f"must be even and within head_dim "
                             f"{self.head_dim}")
        if not 0 <= dense_layers <= self.n_layer:
            raise ValueError(f"{self.name}: dense_layers {dense_layers} "
                             f"of {self.n_layer} layers")
        self.layer_types = tuple(layer_types)
        self._kv = {"full": int(kv_heads), "window": int(window_kv_heads)}
        self.v_dim, self.window = int(v_dim), int(window)
        self.rotary_lanes = int(rotary_lanes)
        self.dense_layers = int(dense_layers)
        self.router_width, self.top_k = int(router_width), int(top_k)
        self.experts = _check_share(self.name, experts, router_width, top_k)
        self.value_scale, self.norm_topk = float(value_scale), bool(norm_topk)
        self.eps = eps
        self._theta = {"full": float(rope_theta),
                       "window": float(window_rope_theta)}

    @property
    def kv_heads(self):
        """The FULL planes' K/V heads; a window plane states its own
        (``plane_kv_heads``)."""
        return self._kv["full"]

    @property
    def planes(self):
        return tuple(self.window if kind == "window" else None
                     for kind in self.layer_types)

    @property
    def sink_planes(self):
        return self.layer_types.count("window")

    def plane_kv_heads(self, plane):
        return self._kv[self.layer_types[plane]]

    def plane_rows_per_entry(self, plane):
        return self.n_head // self.plane_kv_heads(plane)

    def plane_block_shapes(self, plane, block_tokens, dtype):
        rows = _paged.pool_rows(self.plane_kv_heads(plane), dtype)
        return ((block_tokens, rows, _paged.key_lanes(self.head_dim)),
                (block_tokens, rows, self.v_dim))

    def plane_written_values(self, plane):
        hk = self.plane_kv_heads(plane)
        return (hk * self.head_dim, hk * self.v_dim)

    def plane_block_bytes(self, plane, block_tokens, itemsize):
        # what the model caches of a position: the published lanes
        return block_tokens * sum(self.plane_written_values(plane)) * itemsize

    def gauges(self, params):
        lanes = ("lanes of one K/V head's row: what the model caches "
                 "(form=published) and what the pool array stores "
                 "(form=stored: the key's lanes up to the 128-lane tile, "
                 "kernels.paged_attention.key_lanes)")
        out = dict(_moe_gauges(self, params))
        for array, pub, stored in (
                ("k", self.head_dim, _paged.key_lanes(self.head_dim)),
                ("v", self.v_dim, self.v_dim)):
            out[("kv_lanes", (("array", array), ("form", "published")))] = (
                pub, lanes)
            out[("kv_lanes", (("array", array), ("form", "stored")))] = (
                stored, lanes)
        out["attn_sink_planes"] = (
            self.sink_planes, "planes whose attention has a learned sink "
            "logit a query head (mass, no value)")
        return out

    def check_params(self, params, max_len):
        last = self.n_layer - 1
        need = ["tok_emb.w", "norm_f.scale", "lm_head.w",
                f"block{last}_att_qkv.w", f"block{last}_att_out.w",
                f"block{last}_norm2.scale"]
        need += [f"block{i}_att_sink.b"
                 for i, kind in enumerate(self.layer_types)
                 if kind == "window"]
        if self.dense_layers:
            need.append("block0_ffn_down.w")
        if self.moe_layers:
            need += [f"block{last}_router.w", f"block{last}_router.bias",
                     f"block{last}_experts_down.w"]
        missing = [k for k in need if k not in params]
        if missing:
            raise ValueError(f"{self.name}: parameters lack "
                             f"{', '.join(missing)}")
        for i, kind in enumerate(self.layer_types):
            hk = self._kv[kind]
            want = self.n_head * self.head_dim + hk * (self.head_dim
                                                       + self.v_dim)
            got = np.shape(params[f"block{i}_att_qkv.w"])[1]
            if got != want:
                raise ValueError(
                    f"{self.name}: layer {i} ({kind}) projects to {got} "
                    f"lanes; q | k | v of {self.n_head} x {self.head_dim} "
                    f"over {hk} x ({self.head_dim} | {self.v_dim}) is "
                    f"{want}")
        if self.moe_layers:
            self._check_experts(params)

    def embed(self, p, toks, pos):
        with sublayer("embed"):
            return p["tok_emb.w"][toks]

    def _rotate(self, x, rope):
        """Rotary on the first ``rotary_lanes`` lanes, the rest as is."""
        r = self.rotary_lanes
        if r == x.shape[-1]:
            return _rope(x, *rope)
        return jnp.concatenate([_rope(x[..., :r], *rope), x[..., r:]],
                               axis=-1)

    def _attention(self, w, i, x, rope, planes, attend):
        kind = self.layer_types[i]
        hk, dh, dv = self._kv[kind], self.head_dim, self.v_dim
        with sublayer("norm"):
            a = _rms(x, w("norm1.scale"), self.eps)
        lead = a.shape[:-1]
        with sublayer("attn.proj"):
            qkv = a @ w("att_qkv.w")
            nq, nk = self.n_head * dh, hk * dh
            q = self._rotate(qkv[..., :nq].reshape(*lead, self.n_head, dh),
                             rope)
            k = self._rotate(qkv[..., nq:nq + nk].reshape(*lead, hk, dh),
                             rope)
            v = qkv[..., nq + nk:].reshape(*lead, hk, dv)
        with sublayer("attn.core"):
            v = v * jnp.asarray(self.value_scale, v.dtype)
            how = dict(group=self.n_head // hk, scale=dh ** -0.5)
            if kind == "window":
                how.update(window=self.window,
                           sink=w("att_sink.b").astype(jnp.float32))
            ctx, planes = attend(planes, i, 0, q, k, v, **how)
        with sublayer("attn.proj"):
            return ctx.reshape(*lead, -1) @ w("att_out.w"), planes

    def stack(self, p, x, pos, planes, attend):
        with sublayer("attn.proj"):
            ropes = {kind: _rope_angles(pos, self.rotary_lanes, theta)
                     for kind, theta in self._theta.items()
                     if kind in self.layer_types}
        for i in range(self.n_layer):
            w = lambda nm: p[f"block{i}_{nm}"]
            a, planes = self._attention(
                w, i, x, ropes[self.layer_types[i]], planes, attend)
            x = x + a
            with sublayer("norm"):
                m = _rms(x, w("norm2.scale"), self.eps)
            if i < self.dense_layers:
                with sublayer("ffn"):
                    ff = _gated_silu(m, w("ffn_gate.w"), w("ffn_up.w"),
                                     w("ffn_down.w"))
            else:
                ff, counts = routed_ffn(
                    w, m, attend, self.experts, self.top_k,
                    normalise=self.norm_topk, shared=False)
                attend.tally(counts)
            x = x + ff
        return x, planes

    def head(self, p, x):
        with sublayer("head"):
            return jnp.matmul(_rms(x, p["norm_f.scale"], self.eps),
                              p["lm_head.w"],
                              preferred_element_type=jnp.float32)

class MambaMoE(_Routed, Architecture):
    """Pre-normed layers that are ONE sub-layer each, by a pattern
    string: ``M`` a Mamba-2 mixer, ``E`` a routed FFN of un-gated
    ``relu ** 2`` experts, ``*`` grouped-query attention with NO
    positional signal (the ``nemotron_h`` layout, arXiv:2504.03624;
    ``models/ssm_moe_reference.py`` writes the equations down and lists
    what the published configuration has no key for).  Every layer is ``x
    <- x + f(RMSNorm(x))``; one RMSNorm before the untied head; the table
    is not scaled; positions come from the recurrences alone.

    **``M``** (arXiv:2405.21060): ``[z | xBC | dt] = u W_in`` (widths
    ``H P | H P + 2 G N | H``); ``kernels/ssm.py`` does the rest IN PLACE
    through ``attend.advance`` (the convolution of ``conv_taps`` taps
    over ``xBC`` with the slot's tails, SiLU, ``delta = softplus(dt +
    dt_bias)``, the state ``S [H, P, N]`` float32 under a scalar decay a
    head, ``y = S C + D x``): a decode step hands the layer's arrays
    whole to the step kernel, which reads and writes the live slots'
    state where it lies; a prefill piece advances its one slot in the
    chunked form at ``chunk_size`` rows.  Then the gated group norm: ``y
    <- y * silu(z)``, RMSNorm over each of the ``G`` groups of lanes
    (the gate BEFORE the norm, one gain of ``H P``), ``out = y W_out``.
    A slot holds ``kernels.ssm.state_shapes``: the state and ``conv_taps
    - 1`` rows of ``xBC`` in the compute dtype (``state_spec``).

    **``*``**: ``n_head`` query heads of ``head_dim`` lanes over
    ``kv_heads`` K/V heads (16 a K/V head in the published model), causal
    softmax at ``head_dim ** -0.5`` over the whole context, no rotary, no
    bias; one full plane a ``*`` layer (``pool_rows`` of its K/V heads a
    position: what a position STORES is the gauge
    ``kv_stored_bytes_per_token`` beside ``kv_bytes_per_token``).

    **``E``**: ``routed_ffn`` at ``form="relu2"``: ``route`` with sigmoid
    scores, a bias that selects only, ``top_k`` of ``router_width``,
    weights normalised over the selected (``norm_topk``) and scaled by
    ``route_scale``; ``experts = (first, count)`` this chip's share; the
    shared expert the same form at its own width.  The stack tallies
    ``MOE_COUNTS``.

    Parameter names: ``tok_emb.w [V, d]``, ``norm_f.scale``, ``lm_head.w
    [d, V]``; per layer ``block{i}_norm.scale``; an ``M`` layer
    ``ssm_in.w [d, 2 H P + 2 G N + H]``, ``ssm_conv.w [H P + 2 G N,
    taps]``, ``ssm_conv.b``, ``ssm_dt.b``, ``ssm_A_log.w``, ``ssm_D.w``
    (``[H]`` each), ``ssm_norm.scale [H P]``, ``ssm_out.w [H P, d]``; a
    ``*`` layer ``att_qkv.w [d, (n_head + 2 kv_heads) head_dim]`` (q | k |
    v), ``att_out.w [n_head head_dim, d]``; an ``E`` layer ``router.w [d,
    router_width]``, ``router.bias``, ``shared_up.w [d, s]``,
    ``shared_down.w [s, d]``, ``experts_up.w [count, e, d]`` (TRANSPOSED:
    ``routed_ffn``), ``experts_down.w [count, e, d]``.
    """

    name = "mamba_moe"
    expert_form = "relu2"

    def __init__(self, pattern, n_head, kv_heads, head_dim, d_model,
                 ssm_heads, ssm_head_dim, ssm_groups, ssm_state, conv_taps,
                 router_width, top_k, experts, route_scale=1.0,
                 norm_topk=True, chunk_size=128, eps=1e-5):
        super().__init__(len(pattern), n_head, d_model, head_dim=head_dim)
        bad = sorted(set(pattern) - set("ME*"))
        if bad:
            raise ValueError(f"{self.name}: pattern characters {bad}; a "
                             f"layer is 'M', 'E' or '*'")
        if n_head % kv_heads:
            raise ValueError(f"{self.name}: kv_heads {kv_heads} must "
                             f"divide n_head {n_head}")
        self.pattern = str(pattern)
        self._kv_heads = int(kv_heads)
        self.ssm_heads, self.ssm_head_dim = int(ssm_heads), int(ssm_head_dim)
        self.ssm_groups, self.ssm_state = int(ssm_groups), int(ssm_state)
        self.conv_taps, self.chunk_size = int(conv_taps), int(chunk_size)
        # refuses a geometry the state's layout cannot hold
        _ssm.heads_per_row(self.ssm_heads, self.ssm_head_dim,
                           self.ssm_groups)
        self.router_width, self.top_k = int(router_width), int(top_k)
        self.experts = _check_share(self.name, experts, router_width, top_k)
        self.route_scale, self.norm_topk = float(route_scale), bool(norm_topk)
        self.eps = eps
        of = lambda kind: {i: n for n, i in enumerate(          # noqa: E731
            i for i, c in enumerate(self.pattern) if c == kind)}
        self.state_of, self.plane_of = of("M"), of("*")

    @property
    def kv_heads(self):
        return self._kv_heads

    @property
    def planes(self):
        return (None,) * len(self.plane_of)

    @property
    def rows_per_entry(self):
        return self.n_head // self.kv_heads

    @property
    def ssm_layers(self):
        return len(self.state_of)

    @property
    def moe_layers(self):
        return self.pattern.count("E")

    @property
    def last_routed(self):
        return self.pattern.rindex("E")

    @property
    def ssm_inner(self):
        return self.ssm_heads * self.ssm_head_dim

    def pool_block_shape(self, block_tokens, dtype):
        return (block_tokens, _paged.pool_rows(self.kv_heads, dtype),
                self.head_dim)

    def state_spec(self, dtype):
        S, tail = _ssm.state_shapes(self.ssm_heads, self.ssm_head_dim,
                                    self.ssm_groups, self.ssm_state,
                                    self.conv_taps)
        return (((S, jnp.float32), (tail, jnp.dtype(dtype))),
                ) * self.ssm_layers

    def gauges(self, params):
        dtype = params["tok_emb.w"].dtype
        stored = sum(int(np.prod(shape[1:]))
                     for i in range(len(self.planes))
                     for shape in self.plane_block_shapes(i, 1, dtype))
        return dict(_moe_gauges(self, params) if self.moe_layers else {}, **{
            "ssm_layers": (self.ssm_layers, "layers whose mixer is a "
                           "Mamba-2 recurrence (a state a slot, advanced "
                           "in place; no K/V plane)"),
            "ssm_state_bytes_per_slot": (
                self.state_bytes_per_slot(dtype), "bytes one slot holds "
                "beside the pool: the float32 state and the convolution's "
                "tails of every Mamba-2 layer"),
            "kv_stored_bytes_per_token": (
                stored * dtype.itemsize, "bytes a cached position STORES "
                "across its planes: kernels.paged_attention.pool_rows of "
                "K/V heads a plane (kv_bytes_per_token is what the model "
                "caches of it)"),
        })

    def check_params(self, params, max_len):
        need = ["tok_emb.w", "norm_f.scale", "lm_head.w"]
        for kind, names in (("M", ("ssm_in.w", "ssm_conv.w", "ssm_conv.b",
                                   "ssm_dt.b", "ssm_A_log.w", "ssm_D.w",
                                   "ssm_norm.scale", "ssm_out.w")),
                            ("*", ("att_qkv.w", "att_out.w")),
                            ("E", ("router.w", "router.bias", "shared_up.w",
                                   "shared_down.w", "experts_up.w",
                                   "experts_down.w"))):
            if kind in self.pattern:
                last = self.pattern.rindex(kind)
                need += [f"block{last}_norm.scale"] + [
                    f"block{last}_{n}" for n in names]
        missing = [k for k in need if k not in params]
        if missing:
            raise ValueError(f"{self.name}: parameters lack "
                             f"{', '.join(missing)}")
        if "M" in self.pattern:
            last = self.pattern.rindex("M")
            want = (2 * self.ssm_inner + 2 * self.ssm_groups * self.ssm_state
                    + self.ssm_heads)
            got = np.shape(params[f"block{last}_ssm_in.w"])[1]
            if got != want:
                raise ValueError(
                    f"{self.name}: layer {last} projects to {got} lanes; z "
                    f"| xBC | dt of {self.ssm_heads} heads of "
                    f"{self.ssm_head_dim} in {self.ssm_groups} groups of "
                    f"state {self.ssm_state} is {want}")
        if self.moe_layers:
            self._check_experts(params)

    def embed(self, p, toks, pos):
        with sublayer("embed"):
            return p["tok_emb.w"][toks]      # no positional encoding

    def _mamba(self, w, h, planes, attend, n_state):
        f32 = jnp.float32
        inner, G = self.ssm_inner, self.ssm_groups
        xbc_end = 2 * inner + 2 * G * self.ssm_state
        with sublayer("mixer"):
            zxd = h @ w("ssm_in.w")
            y, planes = attend.advance(
                planes, n_state, _ssm, zxd[..., inner:xbc_end],
                zxd[..., xbc_end:], conv_w=w("ssm_conv.w"),
                conv_b=w("ssm_conv.b"), dt_bias=w("ssm_dt.b"),
                A_log=w("ssm_A_log.w"), D=w("ssm_D.w"),
                heads=self.ssm_heads, groups=G, chunk_size=self.chunk_size)
            # the gate BEFORE the norm, the norm a group
            y = y * jax.nn.silu(zxd[..., :inner].astype(f32))
            yg = y.reshape(*y.shape[:-1], G, inner // G)
            yg = yg * jax.lax.rsqrt(
                jnp.mean(jnp.square(yg), axis=-1, keepdims=True) + self.eps)
            y = yg.reshape(y.shape).astype(h.dtype) * w("ssm_norm.scale")
            return y @ w("ssm_out.w"), planes

    def _attention(self, w, h, planes, attend, plane):
        hk, dh = self.kv_heads, self.head_dim
        lead = h.shape[:-1]
        with sublayer("attn.proj"):
            qkv = h @ w("att_qkv.w")
            nq, nk = self.n_head * dh, hk * dh
            q = qkv[..., :nq].reshape(*lead, self.n_head, dh)
            k = qkv[..., nq:nq + nk].reshape(*lead, hk, dh)
            v = qkv[..., nq + nk:].reshape(*lead, hk, dh)
        with sublayer("attn.core"):
            ctx, planes = attend(planes, plane, 0, q, k, v,
                                 group=self.rows_per_entry, scale=dh ** -0.5)
        with sublayer("attn.proj"):
            return ctx.reshape(*lead, -1) @ w("att_out.w"), planes

    def stack(self, p, x, pos, planes, attend):
        for i, kind in enumerate(self.pattern):
            w = lambda nm: p[f"block{i}_{nm}"]
            with sublayer("norm"):
                h = _rms(x, w("norm.scale"), self.eps)
            if kind == "M":
                mix, planes = self._mamba(w, h, planes, attend,
                                          self.state_of[i])
            elif kind == "*":
                mix, planes = self._attention(w, h, planes, attend,
                                              self.plane_of[i])
            else:
                mix, counts = routed_ffn(
                    w, h, attend, self.experts, self.top_k,
                    self.route_scale, normalise=self.norm_topk,
                    form=self.expert_form)
                attend.tally(counts)
            x = x + mix
        return x, planes

    def head(self, p, x):
        with sublayer("head"):
            return jnp.matmul(_rms(x, p["norm_f.scale"], self.eps),
                              p["lm_head.w"],
                              preferred_element_type=jnp.float32)


class PowerRetention(Architecture):
    """Pre-normed layers of POWER RETENTION and a gated SiLU FFN (the
    ``brumby`` layout: Qwen3's block with its attention replaced;
    arXiv:2507.04239; ``models/retention_reference.py`` writes the
    equations down in the quadratic form and lists what the published
    configuration has no key for).

    **What a slot holds.**  No K and no V of any position
    (``planes == ()``: the engine builds no pool and no table): a layer
    holds, a K/V head, the state ``S`` and the normaliser's ``z`` of
    ``kernels/retention.py`` (``state_spec``: ``(kv_heads, stored_rows,
    head_dim)`` and ``(kv_heads, stored_rows)`` float32, whatever the
    compute dtype), 34 MB a layer at 8 heads of 128, whatever the
    context.  The five query heads of a K/V head's group read the one
    state.

    **A layer.**  ``q``, ``k`` are RMS-normed over a head's lanes and
    rotated (the halves convention of ``_rope``, all lanes), the gate is
    ``lg = log sigmoid(h W_g + b_g)``, one a K/V head, in float32, and
    ``attend.advance`` does the rest: a decode step decays the live
    slots' state, adds ``phi(k) v^T`` and reads it through ``phi(q)``; a
    prefill piece attends itself in the quadratic form and the state
    before it through ``phi(q)``.  ``degree`` is 2: the features ``phi``
    are the degree's, and no other is written down here.

    Parameter names: ``tok_emb.w [V, d]``, ``norm_f.scale``,
    ``lm_head.w [d, V]`` (untied); per layer ``block{i}_norm1.scale``,
    ``att_q.w [d, n_head * head_dim]``, ``att_k.w`` and ``att_v.w [d,
    kv_heads * head_dim]``, ``att_qnorm.scale`` and ``att_knorm.scale
    [head_dim]``, ``att_gate.w [d, kv_heads]``, ``att_gate.b
    [kv_heads]``, ``att_out.w [n_head * head_dim, d]``, ``norm2.scale``,
    ``ffn_gate.w``, ``ffn_up.w [d, f]``, ``ffn_down.w [f, d]``.  No
    other bias.
    """

    name = "power_retention"
    attn_form = "retention"

    def __init__(self, n_layer, n_head, kv_heads, d_model, head_dim, d_ff,
                 degree=2, eps=1e-6, rope_theta=10000.0, norm_eps=1e-6):
        super().__init__(n_layer, n_head, d_model, head_dim=head_dim)
        if degree != 2:
            raise ValueError(
                f"{self.name}: degree {degree}: the features "
                f"kernels.retention.phi makes are the second degree's "
                f"(the upper triangle of u u^T); no other is written")
        if n_head % kv_heads or n_head // kv_heads > 7:
            raise ValueError(
                f"{self.name}: kv_heads {kv_heads} must divide n_head "
                f"{n_head} into groups of at most 7 (a K/V head's key row "
                f"and its query rows share one 8-row tile)")
        if self.head_dim % 8:
            raise ValueError(f"{self.name}: head_dim {self.head_dim} must "
                             f"be a multiple of 8")
        self._kv_heads, self.d_ff = int(kv_heads), int(d_ff)
        self.degree, self.eps = int(degree), float(eps)
        self.norm_eps, self.rope_theta = norm_eps, float(rope_theta)

    @property
    def kv_heads(self):
        return self._kv_heads

    @property
    def planes(self):
        return ()

    @property
    def retention_layers(self):
        return self.n_layer

    def state_spec(self, dtype):
        rows = _retention.stored_rows(self.head_dim)
        one = (((self.kv_heads, rows, self.head_dim), jnp.float32),
               ((self.kv_heads, rows), jnp.float32))
        return (one,) * self.n_layer

    def gauges(self, params):
        rows = "features a K/V head's state holds: the upper triangle " \
               "of a head's lanes (published) and what the layout of " \
               "cyclic diagonals stores (kernels.retention)"
        return {
            "retention_layers": (self.n_layer, "layers whose mixer is "
                                 "power retention (a state, no K/V plane)"),
            "retention_degree": (self.degree, "the power of the scores"),
            ("retention_state_rows", (("kind", "published"),)): (
                _retention.published_rows(self.head_dim), rows),
            ("retention_state_rows", (("kind", "stored"),)): (
                _retention.stored_rows(self.head_dim), rows),
        }

    def check_params(self, params, max_len):
        last = self.n_layer - 1
        need = ["tok_emb.w", "norm_f.scale", "lm_head.w",
                f"block{last}_att_gate.w", f"block{last}_att_gate.b",
                f"block{last}_att_qnorm.scale", f"block{last}_norm2.scale",
                f"block{last}_ffn_down.w"]
        missing = [k for k in need if k not in params]
        if missing:
            raise ValueError(f"{self.name}: parameters lack "
                             f"{', '.join(missing)}")
        width = np.shape(params[f"block{last}_ffn_down.w"])[0]
        if width != self.d_ff:
            raise ValueError(f"{self.name}: the parameters' FFN is {width} "
                             f"wide; the architecture says {self.d_ff}")

    def embed(self, p, toks, pos):
        with sublayer("embed"):
            return p["tok_emb.w"][toks]

    def stack(self, p, x, pos, planes, attend):
        f32 = jnp.float32
        with sublayer("attn.proj"):
            rope = _rope_angles(pos, self.head_dim, self.rope_theta)
        for i in range(self.n_layer):
            w = lambda nm: p[f"block{i}_{nm}"]
            with sublayer("norm"):
                a = _rms(x, w("norm1.scale"), self.norm_eps)
            kv = (*a.shape[:-1], self.kv_heads, self.head_dim)
            with sublayer("attn.proj"):
                q = _rope(_rms(self.heads(a @ w("att_q.w")),
                               w("att_qnorm.scale"), self.norm_eps), *rope)
                k = _rope(_rms((a @ w("att_k.w")).reshape(kv),
                               w("att_knorm.scale"), self.norm_eps), *rope)
                v = (a @ w("att_v.w")).reshape(kv)
                lg = jax.nn.log_sigmoid(
                    jnp.matmul(a, w("att_gate.w"),
                               preferred_element_type=f32)
                    + w("att_gate.b").astype(f32))
            with sublayer("attn.core"):
                y, planes = attend.advance(planes, i, _retention, q, k, v,
                                           lg, eps=self.eps)
            with sublayer("attn.proj"):
                x = x + (y.reshape(*x.shape[:-1], -1).astype(x.dtype)
                         @ w("att_out.w"))
            with sublayer("norm"):
                m = _rms(x, w("norm2.scale"), self.norm_eps)
            with sublayer("ffn"):
                x = x + _gated_silu(m, w("ffn_gate.w"), w("ffn_up.w"),
                                    w("ffn_down.w"))
        return x, planes

    def head(self, p, x):
        with sublayer("head"):
            return jnp.matmul(_rms(x, p["norm_f.scale"], self.norm_eps),
                              p["lm_head.w"],
                              preferred_element_type=jnp.float32)


class DeltaMoE(_Routed, Architecture):
    """Pre-normed layers of a GATED DELTA RULE or of gated position-free
    grouped-query attention, every one followed by a routed gated-SiLU
    FFN (the ``solar_open2`` layout: Kimi Delta Attention,
    arXiv:2510.26692, three layers in four;
    ``chipbench/families/delta_moe_reference.py`` writes the equations
    down and lists what the published configuration has no key for).
    Every layer is ``x <- x + Mix(RMS(x))``, ``x <- x + MoE(RMS(x))``; one
    RMSNorm before the untied head; the table is not scaled; NO rotary
    anywhere: positions come from the recurrences alone.

    **A layer of ``gqa_layers``**: ``GatedMoE``'s full plane with no q/k
    norm: ``n_head`` query heads of ``head_dim`` over ``kv_heads`` K/V
    heads, causal softmax over the whole context at ``head_dim ** -0.5``,
    the joined heads gated lane by lane, ``ctx * sigmoid(a W_g)``, before
    the output projection.  One full plane such a layer.

    **Every other layer** (``kernels/delta.py`` does the recurrence IN
    PLACE through ``attend.advance``): ``q, k, v = a W_q, a W_k, a W_v``
    (``delta_heads`` heads of ``delta_head_dim`` each), a causal
    depthwise convolution of ``conv_taps`` taps and SiLU, q and k of
    length 1; the LOG decay of every key lane, float32, ``g = -exp(A_log)
    softplus((a W_fa) W_fb + dt_bias)``; the write strength ``beta =
    beta_scale sigmoid(a W_b)`` (``beta_scale`` 2 allows ``I - beta k
    k^T`` a negative eigenvalue); then per head RMSNorm of the read over
    its ``delta_head_dim`` lanes, the gate ``sigmoid((a W_ga) W_gb +
    b_g)`` lane by lane, ``W_o``.  A slot holds ``kernels.delta.
    state_shapes``: the float32 state and ``conv_taps - 1`` rows of ``q |
    k | v`` in the compute dtype (``state_spec``).

    **The FFN**: ``routed_ffn``: ``route`` with sigmoid scores, a bias
    that selects only, ``top_k`` of ``router_width``, weights normalised
    over the selected (``norm_topk``) and scaled by ``route_scale``;
    ``experts = (first, count)`` this chip's share; one shared expert of
    the routed experts' form.  The stack tallies ``MOE_COUNTS``.

    Parameter names: ``tok_emb.w [V, d]``, ``norm_f.scale``, ``lm_head.w
    [d, V]``; per layer ``block{i}_norm1.scale`` (before the mixer),
    ``norm2.scale`` (before the FFN), ``router.w [d, router_width]``,
    ``router.bias``, ``shared_gate.w``, ``shared_up.w [d, e]``,
    ``shared_down.w [e, d]``, ``experts_gate.w``, ``experts_up.w [count,
    d, e]``, ``experts_down.w [count, e, d]``; a GQA layer ``att_q.w``
    and ``att_gate.w [d, n_head head_dim]``, ``att_k.w`` and ``att_v.w
    [d, kv_heads head_dim]``, ``att_out.w``; a delta layer ``delta_q.w``,
    ``delta_k.w``, ``delta_v.w [d, H D]``, ``delta_conv.w [3 H D,
    taps]``, ``delta_fa.w [d, r]``, ``delta_fb.w [r, H D]``,
    ``delta_dt.b [H D]``, ``delta_A_log.w [H]``, ``delta_beta.w [d, H]``,
    ``delta_ga.w [d, r]``, ``delta_gb.w [r, H D]``, ``delta_gb.b [H D]``
    (the one bias), ``delta_onorm.scale [D]``, ``delta_out.w [H D, d]``.
    """

    name = "delta_moe"
    dense_layers = 0

    def __init__(self, n_layer, gqa_layers, n_head, kv_heads, head_dim,
                 d_model, delta_heads, delta_head_dim, conv_taps,
                 router_width, top_k, experts, route_scale=1.0,
                 norm_topk=True, beta_scale=2.0, eps=1e-5):
        super().__init__(n_layer, n_head, d_model, head_dim=head_dim)
        gqa = tuple(sorted(int(i) for i in gqa_layers))
        if not gqa or gqa[0] < 0 or gqa[-1] >= self.n_layer \
                or len(set(gqa)) != len(gqa):
            raise ValueError(f"{self.name}: gqa_layers {list(gqa_layers)} "
                             f"must name at least one of the {self.n_layer} "
                             f"layers, each once")
        if n_head % kv_heads:
            raise ValueError(f"{self.name}: kv_heads {kv_heads} must "
                             f"divide n_head {n_head}")
        self.gqa_layers = gqa
        self._kv_heads = int(kv_heads)
        self.delta_heads = int(delta_heads)
        self.delta_head_dim = int(delta_head_dim)
        self.conv_taps = int(conv_taps)
        self.router_width, self.top_k = int(router_width), int(top_k)
        self.experts = _check_share(self.name, experts, router_width, top_k)
        self.route_scale, self.norm_topk = float(route_scale), bool(norm_topk)
        self.beta_scale, self.eps = float(beta_scale), eps
        self.plane_of = {i: n for n, i in enumerate(gqa)}
        self.state_of = {i: n for n, i in enumerate(
            i for i in range(self.n_layer) if i not in self.plane_of)}

    @property
    def kv_heads(self):
        return self._kv_heads

    @property
    def planes(self):
        return (None,) * len(self.plane_of)

    @property
    def rows_per_entry(self):
        return self.n_head // self.kv_heads

    @property
    def delta_layers(self):
        return len(self.state_of)

    def pool_block_shape(self, block_tokens, dtype):
        return (block_tokens, _paged.pool_rows(self.kv_heads, dtype),
                self.head_dim)

    def state_spec(self, dtype):
        S, tail = _delta_rule.state_shapes(
            self.delta_heads, self.delta_head_dim, self.conv_taps)
        return (((S, jnp.float32), (tail, jnp.dtype(dtype))),
                ) * self.delta_layers

    def gauges(self, params):
        dtype = params["tok_emb.w"].dtype
        return dict(_moe_gauges(self, params), **{
            "delta_layers": (self.delta_layers, "layers whose mixer is a "
                             "gated delta rule (a state a slot, advanced "
                             "in place; no K/V plane)"),
            "delta_heads": (self.delta_heads, "heads of a delta layer, "
                            "each a float32 state of delta_head_dim ** 2"),
            "delta_state_bytes_per_slot": (
                self.state_bytes_per_slot(dtype), "bytes one slot holds "
                "beside the pool: the float32 state and the convolution's "
                "tails of every delta layer (what ONE snapshot holds)"),
        })

    def check_params(self, params, max_len):
        need = ["tok_emb.w", "norm_f.scale", "lm_head.w"]
        every = ("norm1.scale", "norm2.scale", "router.w", "router.bias",
                 "shared_down.w", "experts_down.w")
        for layers, names in (
                (self.plane_of, ("att_q.w", "att_k.w", "att_v.w",
                                 "att_gate.w", "att_out.w")),
                (self.state_of, ("delta_q.w", "delta_k.w", "delta_v.w",
                                 "delta_conv.w", "delta_fa.w", "delta_fb.w",
                                 "delta_dt.b", "delta_A_log.w",
                                 "delta_beta.w", "delta_ga.w", "delta_gb.w",
                                 "delta_gb.b", "delta_onorm.scale",
                                 "delta_out.w"))):
            if layers:
                last = max(layers)
                need += [f"block{last}_{n}" for n in every + names]
        missing = [k for k in need if k not in params]
        if missing:
            raise ValueError(f"{self.name}: parameters lack "
                             f"{', '.join(missing)}")
        if self.state_of:
            last = max(self.state_of)
            want = self.delta_heads * self.delta_head_dim
            got = np.shape(params[f"block{last}_delta_k.w"])[1]
            if got != want:
                raise ValueError(
                    f"{self.name}: layer {last} projects keys to {got} "
                    f"lanes; {self.delta_heads} heads of "
                    f"{self.delta_head_dim} is {want}")
        self._check_experts(params)

    def embed(self, p, toks, pos):
        with sublayer("embed"):
            return p["tok_emb.w"][toks]      # no positional encoding

    def _attention(self, w, a, planes, attend, plane):
        f32 = jnp.float32
        lead = a.shape[:-1]
        kv = (*lead, self.kv_heads, self.head_dim)
        with sublayer("attn.proj"):
            q = self.heads(a @ w("att_q.w"))
            k = (a @ w("att_k.w")).reshape(kv)
            v = (a @ w("att_v.w")).reshape(kv)
            gate = jax.nn.sigmoid((a @ w("att_gate.w")).astype(f32))
        with sublayer("attn.core"):
            ctx, planes = attend(planes, plane, 0, q, k, v,
                                 group=self.rows_per_entry,
                                 scale=self.head_dim ** -0.5)
            # the head gate, lane by lane
            o = (ctx.reshape(*lead, -1).astype(f32) * gate).astype(a.dtype)
        with sublayer("attn.proj"):
            return o @ w("att_out.w"), planes

    def _delta(self, w, a, planes, attend, n_state):
        f32 = jnp.float32
        H, D = self.delta_heads, self.delta_head_dim
        lead = a.shape[:-1]
        with sublayer("mixer"):
            decay = jnp.matmul(a @ w("delta_fa.w"), w("delta_fb.w"),
                               preferred_element_type=f32)
            g = -(jnp.repeat(jnp.exp(w("delta_A_log.w").astype(f32)), D)
                  * jax.nn.softplus(decay + w("delta_dt.b").astype(f32)))
            beta = self.beta_scale * jax.nn.sigmoid(jnp.matmul(
                a, w("delta_beta.w"), preferred_element_type=f32))
            o, planes = attend.advance(
                planes, n_state, _delta_rule, a @ w("delta_q.w"),
                a @ w("delta_k.w"), a @ w("delta_v.w"), g, beta,
                conv_w=w("delta_conv.w"), heads=H)
            # the read is normed a head, then gated lane by lane
            o = o.reshape(*lead, H, D)
            o = o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True) + self.eps)
            o = o * w("delta_onorm.scale").astype(f32)
            gate = jax.nn.sigmoid(
                jnp.matmul(a @ w("delta_ga.w"), w("delta_gb.w"),
                           preferred_element_type=f32)
                + w("delta_gb.b").astype(f32))
            o = (o.reshape(*lead, H * D) * gate).astype(a.dtype)
            return o @ w("delta_out.w"), planes

    def stack(self, p, x, pos, planes, attend):
        for i in range(self.n_layer):
            w = lambda nm: p[f"block{i}_{nm}"]
            with sublayer("norm"):
                a = _rms(x, w("norm1.scale"), self.eps)
            if i in self.plane_of:
                mix, planes = self._attention(w, a, planes, attend,
                                              self.plane_of[i])
            else:
                mix, planes = self._delta(w, a, planes, attend,
                                          self.state_of[i])
            x = x + mix
            with sublayer("norm"):
                m = _rms(x, w("norm2.scale"), self.eps)
            ff, counts = routed_ffn(w, m, attend, self.experts, self.top_k,
                                    self.route_scale,
                                    normalise=self.norm_topk)
            attend.tally(counts)
            x = x + ff
        return x, planes

    def head(self, p, x):
        with sublayer("head"):
            return jnp.matmul(_rms(x, p["norm_f.scale"], self.eps),
                              p["lm_head.w"],
                              preferred_element_type=jnp.float32)


class SparseLightning(Architecture):
    """Pre-normed layers of BLOCK-SPARSE grouped-query attention (``S``)
    or of a recurrence of constant decay (``L``), each followed by a
    dense gated-SiLU FFN (the ``minicpm_sala`` layout: InfLLM-V2,
    arXiv:2509.24663, in one layer of four; Lightning Attention-2,
    arXiv:2401.04658, in the others;
    ``chipbench/families/sparse_lightning_reference.py`` writes the
    equations down and lists what the published configuration has no key
    for).  ``h0 = embed_scale E[id]``; a layer is ``h += r Mix(RMS(h))``,
    ``h += r FFN(RMS(h))`` with ``r = residual_scale``; ``logits = W_head
    (RMS(h) head_scale)``, the head untied.

    **``S``** (``attend.block_sparse``): ``n_head`` query heads of
    ``head_dim`` over ``kv_heads`` K/V heads, NO rotary, scores at
    ``head_dim ** -0.5``, the joined heads gated lane by lane, ``ctx *
    sigmoid(a W_gate)``, before ``W_o``.  A query at a position under
    ``sparse["dense_len"]`` attends the whole chain; from there on the
    ``init_blocks + topk + window_blocks`` blocks its K/V head's queries
    select on the layer's COMPRESSED keys (the mean of every ``2 stride``
    keys, a row every ``stride`` positions).  A layer holds TWO planes:
    K and V, stored HEAD-MAJOR (a block is ``[kv_heads, B, head_dim]``:
    one ``[B, head_dim]`` slab a K/V head and no row beside them, so a
    head's walk fetches its own slabs alone,
    ``kernels/block_sparse_attention.py``), and the
    compressed keys, one array of ``B / stride`` rows a block under the
    same block ids; the K/V planes come first in ``planes``.

    **``L``** (``kernels/ssm.py`` IN PLACE through ``attend.advance``):
    ``q, k, v = a W_q, a W_k, a W_v`` (``lin_heads`` heads of
    ``lin_head_dim``), RMSNorm a head on q and k, rotary on both, then
    ``S_t = exp(-s_h) S_{t-1} + k_t^T v_t``, ``o_t = (q_t D ** -0.5)
    S_t``: Mamba-2's state matrix under one scalar decay with ``x = v``,
    ``B = k``, ``C = q``, a group a head, ``delta`` 1, ``A = -s_h`` the
    head's slope in THIS layer (``slopes``, a row a layer: constants, not
    parameters), ``D`` 0 and no convolution; RMSNorm a head of the read,
    the gate ``sigmoid(a W_gate)`` lane by lane, ``W_o``.  A slot holds
    ``kernels.ssm.state_shapes`` at one tap: the float32 state ``[H, D,
    D]`` and tails of no row.

    The stack tallies ``count_names``: the (row, K/V head) pairs that
    read densely and that selected, the blocks the latter attended and
    had cached, the compressed rows written.

    Parameter names: ``tok_emb.w [V, d]``, ``norm_f.scale``, ``lm_head.w
    [d, V]``; per layer ``block{i}_norm1.scale``, ``norm2.scale``,
    ``ffn_gate.w``, ``ffn_up.w [d, f]``, ``ffn_down.w [f, d]``; an ``S``
    layer ``att_q.w`` and ``att_gate.w [d, n_head head_dim]``, ``att_k.w``
    and ``att_v.w [d, kv_heads head_dim]``, ``att_out.w``; an ``L`` layer
    ``lin_q.w``, ``lin_k.w``, ``lin_v.w``, ``lin_gate.w [d, H D]``,
    ``lin_out.w [H D, d]``, ``lin_qnorm.scale``, ``lin_knorm.scale``,
    ``lin_onorm.scale [D]``.
    """

    name = "sparse_lightning"
    COUNTS = (("sparse_calls", (("form", "dense"),)),
              ("sparse_calls", (("form", "sparse"),)),
              "sparse_blocks_selected", "sparse_blocks_live",
              "compressed_rows_written")
    SPARSE_KEYS = ("stride", "block", "topk", "init_blocks", "window_blocks",
                   "dense_len")

    def __init__(self, mixers, n_head, kv_heads, head_dim, d_model,
                 lin_heads, lin_head_dim, slopes, sparse, embed_scale=1.0,
                 residual_scale=1.0, head_scale=1.0, rope_theta=10000.0,
                 chunk_size=128, eps=1e-6):
        super().__init__(len(mixers), n_head, d_model, head_dim=head_dim)
        bad = sorted(set(mixers) - set("SL"))
        if bad:
            raise ValueError(f"{self.name}: mixers {bad}; a layer is 'S' "
                             f"or 'L'")
        if n_head % kv_heads:
            raise ValueError(f"{self.name}: kv_heads {kv_heads} must "
                             f"divide n_head {n_head}")
        self.mixers = "".join(mixers)
        self._kv_heads = int(kv_heads)
        self.lin_heads, self.lin_head_dim = int(lin_heads), int(lin_head_dim)
        self.sparse = {k: int(sparse[k]) for k in self.SPARSE_KEYS}
        z = self.sparse
        if int(sparse.get("kernel", 2 * z["stride"])) != 2 * z["stride"] \
                or z["block"] % z["stride"]:
            raise ValueError(
                f"{self.name}: a compressed key is the mean of 2 strides "
                f"(kernel {sparse.get('kernel')}, stride {z['stride']}) and "
                f"a block of {z['block']} holds whole strides")
        held = _block_sparse.selected_blocks(
            z["init_blocks"], z["topk"], z["window_blocks"])
        if z["dense_len"] < held * z["block"]:
            raise ValueError(
                f"{self.name}: dense_len {z['dense_len']} must hold the "
                f"{held} blocks of {z['block']} a query selects")
        of = lambda kind: {i: n for n, i in enumerate(          # noqa: E731
            i for i, c in enumerate(self.mixers) if c == kind)}
        self.plane_of, self.state_of = of("S"), of("L")
        self.slopes = np.asarray(slopes, np.float32).reshape(
            len(self.state_of), self.lin_heads)
        if self.state_of and not (self.slopes > 0).all():
            raise ValueError(f"{self.name}: a slope is a positive constant "
                             f"(A_log is its logarithm)")
        # refuses a geometry the state's layout cannot hold
        _ssm.heads_per_row(self.lin_heads, self.lin_head_dim, self.lin_heads)
        self.embed_scale = float(embed_scale)
        self.residual_scale = float(residual_scale)
        self.head_scale = float(head_scale)
        self.rope_theta, self.chunk_size = float(rope_theta), int(chunk_size)
        self.eps = eps

    @property
    def kv_heads(self):
        return self._kv_heads

    @property
    def count_names(self):
        # only an ``S`` layer tallies
        return self.COUNTS if self.plane_of else ()

    @property
    def sparse_layers(self):
        return len(self.plane_of)

    @property
    def lightning_layers(self):
        return len(self.state_of)

    @property
    def planes(self):
        """The ``S`` layers' K/V planes, then their compressed planes."""
        return (None,) * (2 * self.sparse_layers)

    @property
    def plane_reads(self):
        # a token's paged calls walk the K/V planes
        return ((None, self.sparse_layers),) if self.sparse_layers else ()

    @property
    def rows_per_entry(self):
        return self.n_head // self.kv_heads

    def _compressed(self, plane):
        return plane >= self.sparse_layers

    def plane_block_shapes(self, plane, block_tokens, dtype):
        if self._compressed(plane):
            stride = self.sparse["stride"]
            if block_tokens % stride or self.sparse["block"] % block_tokens:
                raise ValueError(
                    f"{self.name}: block_tokens {block_tokens} must hold "
                    f"whole strides of {stride} and divide a selection "
                    f"block of {self.sparse['block']}")
            return ((block_tokens // stride,
                     self.kv_heads * self.head_dim),)
        # head-major: a K/V head's [B, D] slab is what its walk fetches
        return ((self.kv_heads, block_tokens, self.head_dim),) * 2

    def plane_tokens_axis(self, plane):
        return 0 if self._compressed(plane) else 1

    def plane_written_values(self, plane):
        values = self.kv_heads * self.head_dim
        return (values,) if self._compressed(plane) else (values,) * 2

    def second_array(self, plane):
        return None if self._compressed(plane) else plane

    def plane_block_bytes(self, plane, block_tokens, itemsize):
        values = self.kv_heads * self.head_dim * itemsize
        if self._compressed(plane):
            return block_tokens * values // self.sparse["stride"]
        return 2 * block_tokens * values

    def kv_bytes_per_token(self, itemsize):
        """K and V of the ``S`` layers and their share of a compressed
        row (one every ``stride`` positions)."""
        stride = self.sparse["stride"]
        return sum(self.plane_block_bytes(i, stride, itemsize)
                   for i in range(len(self.planes))) // stride

    def state_spec(self, dtype):
        S, tail = _ssm.state_shapes(self.lin_heads, self.lin_head_dim,
                                    self.lin_heads, self.lin_head_dim, 1)
        return (((S, jnp.float32), (tail, jnp.dtype(dtype))),
                ) * self.lightning_layers

    def gauges(self, params):
        dtype = params["tok_emb.w"].dtype
        z = self.sparse
        stored = sum(
            int(np.prod(shape)) for i in range(len(self.planes))
            for shape in self.plane_block_shapes(i, z["stride"], dtype)
        ) // z["stride"]
        _, B, D = self.plane_block_shapes(0, z["block"], dtype)[0]
        return {
            "sparse_layers": (self.sparse_layers, "layers whose K/V plane "
                              "is read in the blocks a query selects past "
                              "dense_len (a compressed-key plane beside it)"),
            "lightning_layers": (self.lightning_layers, "layers whose mixer "
                                 "is a recurrence of constant decay (a "
                                 "state a slot, advanced in place; no K/V "
                                 "plane)"),
            "sparse_blocks_per_query": (
                _block_sparse.selected_blocks(
                    z["init_blocks"], z["topk"], z["window_blocks"]),
                "blocks of sparse block positions a (query row, K/V head) "
                "attends past dense_len: init + topk + window"),
            "lightning_state_bytes_per_slot": (
                self.state_bytes_per_slot(dtype), "bytes one slot holds "
                "beside the pool: the float32 state of every lightning "
                "layer (what ONE snapshot holds)"),
            "kv_stored_bytes_per_token": (
                stored * dtype.itemsize, "bytes a cached position STORES "
                "across its planes, from their block shapes: a K/V plane's "
                "heads and its share of a compressed row "
                "(kv_bytes_per_token is what the model caches of it)"),
            "sparse_walk_bytes_per_block": (
                2 * B * D * dtype.itemsize, "bytes the walk of ONE K/V "
                "head copies for one selected block of sparse block "
                "positions, K and V: that head's slabs of a head-major "
                "plane's blocks"),
        }

    def check_params(self, params, max_len):
        need = ["tok_emb.w", "norm_f.scale", "lm_head.w"]
        every = ("norm1.scale", "norm2.scale", "ffn_gate.w", "ffn_up.w",
                 "ffn_down.w")
        for layers, names in (
                (self.plane_of, ("att_q.w", "att_k.w", "att_v.w",
                                 "att_gate.w", "att_out.w")),
                (self.state_of, ("lin_q.w", "lin_k.w", "lin_v.w",
                                 "lin_gate.w", "lin_out.w",
                                 "lin_qnorm.scale", "lin_knorm.scale",
                                 "lin_onorm.scale"))):
            if layers:
                last = max(layers)
                need += [f"block{last}_{n}" for n in every + names]
        missing = [k for k in need if k not in params]
        if missing:
            raise ValueError(f"{self.name}: parameters lack "
                             f"{', '.join(missing)}")
        if self.state_of:
            last = max(self.state_of)
            want = self.lin_heads * self.lin_head_dim
            got = np.shape(params[f"block{last}_lin_k.w"])[1]
            if got != want:
                raise ValueError(
                    f"{self.name}: layer {last} projects keys to {got} "
                    f"lanes; {self.lin_heads} heads of {self.lin_head_dim} "
                    f"is {want}")

    def embed(self, p, toks, pos):
        with sublayer("embed"):
            rows = p["tok_emb.w"][toks]
            return (rows.astype(jnp.float32) * self.embed_scale).astype(
                rows.dtype)

    def _attention(self, w, a, planes, attend, plane):
        f32 = jnp.float32
        lead = a.shape[:-1]
        kv = (*lead, self.kv_heads, self.head_dim)
        with sublayer("attn.proj"):
            q = self.heads(a @ w("att_q.w"))
            k = (a @ w("att_k.w")).reshape(kv)
            v = (a @ w("att_v.w")).reshape(kv)
            gate = jax.nn.sigmoid((a @ w("att_gate.w")).astype(f32))
        with sublayer("attn.core"):
            ctx, planes, counts = attend.block_sparse(
                planes, plane, self.sparse_layers + plane, q, k, v,
                group=self.rows_per_entry, scale=self.head_dim ** -0.5,
                **self.sparse)
            attend.tally(counts)
            o = (ctx.reshape(*lead, -1).astype(f32) * gate).astype(a.dtype)
        with sublayer("attn.proj"):
            return o @ w("att_out.w"), planes

    def _lightning(self, w, a, rope, planes, attend, n_state):
        f32 = jnp.float32
        H, D = self.lin_heads, self.lin_head_dim
        lead = a.shape[:-1]
        with sublayer("mixer"):
            heads = lambda name: (a @ w(name)).reshape(*lead, H, D)  # noqa
            q = _rope(_rms(heads("lin_q.w"), w("lin_qnorm.scale"), self.eps),
                      *rope)
            k = _rope(_rms(heads("lin_k.w"), w("lin_knorm.scale"), self.eps),
                      *rope)
            q = (q.astype(f32) * D ** -0.5).astype(a.dtype)
            # x = v | B = k | C = q, a group a head; delta = softplus(0 +
            # log(e - 1)) = 1, A = -slope
            xbc = jnp.concatenate([a @ w("lin_v.w"), k.reshape(*lead, H * D),
                                   q.reshape(*lead, H * D)], axis=-1)
            one = jnp.full((H,), np.log(np.e - 1.0), f32)
            o, planes = attend.advance(
                planes, n_state, _ssm, xbc, jnp.zeros((*lead, H), f32),
                conv_w=None, conv_b=None, dt_bias=one,
                A_log=jnp.asarray(np.log(self.slopes[n_state])),
                D=jnp.zeros((H,), f32), heads=H, groups=H,
                chunk_size=self.chunk_size)
            # the read is normed a head, then gated lane by lane
            o = o.reshape(*lead, H, D)
            o = o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True) + self.eps)
            o = o * w("lin_onorm.scale").astype(f32)
            gate = jax.nn.sigmoid((a @ w("lin_gate.w")).astype(f32))
            o = (o.reshape(*lead, H * D) * gate).astype(a.dtype)
            return o @ w("lin_out.w"), planes

    def _scaled(self, x, branch):
        """``x + residual_scale * branch``, the product in float32."""
        return x + (branch.astype(jnp.float32) * self.residual_scale
                    ).astype(x.dtype)

    def stack(self, p, x, pos, planes, attend):
        if self.state_of:
            with sublayer("mixer"):
                rope = _rope_angles(pos, self.lin_head_dim, self.rope_theta)
        for i, kind in enumerate(self.mixers):
            w = lambda nm: p[f"block{i}_{nm}"]
            with sublayer("norm"):
                a = _rms(x, w("norm1.scale"), self.eps)
            if kind == "S":
                mix, planes = self._attention(w, a, planes, attend,
                                              self.plane_of[i])
            else:
                mix, planes = self._lightning(w, a, rope, planes, attend,
                                              self.state_of[i])
            x = self._scaled(x, mix)
            with sublayer("norm"):
                m = _rms(x, w("norm2.scale"), self.eps)
            with sublayer("ffn"):
                x = self._scaled(x, _gated_silu(
                    m, w("ffn_gate.w"), w("ffn_up.w"), w("ffn_down.w")))
        return x, planes

    def head(self, p, x):
        with sublayer("head"):
            return jnp.matmul(
                _rms(x, p["norm_f.scale"], self.eps, gain=self.head_scale),
                p["lm_head.w"], preferred_element_type=jnp.float32)
