"""The device half of the span primitive (``observability/trace.py``):
the sub-layers are named where the model is written, every executable
keeps the map from HLO instruction to named scope, and nothing is built
until someone asks.

The four serving architectures are lowered at toy sizes from abstract
arguments (no weight is made), the decode chunk and one prefill piece
each; the Program path is a two-block GPT train step."""

import collections

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu.analysis.hlo_tools import (  # noqa: E402
    called_computations, iter_instructions)
from paddle_tpu.models import transformer  # noqa: E402
from paddle_tpu.observability import trace  # noqa: E402
from paddle_tpu.serving import arch as arch_mod  # noqa: E402
from paddle_tpu.serving import batched_decode as _bd  # noqa: E402

D, F, V, T, B, S, NB = 64, 96, 128, 32, 4, 2, 9
DT = jnp.float32


def _shapes(**named):
    return {k: jax.ShapeDtypeStruct(tuple(v), DT) for k, v in named.items()}


def _gpt2():
    arch = arch_mod.Gpt2(2, 4, D)
    p = _shapes(**{"tok_emb.w": (V, D), "pos_emb.w.w": (T, D),
                   "ln_f.scale": (D,), "ln_f.bias": (D,),
                   "lm_head.w": (D, V)})
    for i in range(2):
        b = f"block{i}_"
        for ln in ("ln1", "ln2"):
            p.update(_shapes(**{b + ln + ".scale": (D,),
                                b + ln + ".bias": (D,)}))
        for m in ("att_q", "att_k", "att_v", "att_out"):
            p.update(_shapes(**{b + m + ".w": (D, D), b + m + ".b": (D,)}))
        p.update(_shapes(**{b + "ffn1.w": (D, F), b + "ffn1.b": (F,),
                            b + "ffn2.w": (F, D), b + "ffn2.b": (D,)}))
    return arch, p


def _looped():
    arch = arch_mod.LoopedRmsRope(2, 4, D, passes=2)
    p = _shapes(**{"tok_emb.w": (V, D), "norm_f.scale": (D,),
                   "exit_gate.w": (D, 1), "exit_gate.b": (1,),
                   "lm_head.w": (D, V)})
    for i in range(2):
        b = f"block{i}_"
        for n in range(1, 5):
            p.update(_shapes(**{b + f"norm{n}.scale": (D,)}))
        for m in ("att_q", "att_k", "att_v", "att_out"):
            p.update(_shapes(**{b + m + ".w": (D, D)}))
        p.update(_shapes(**{b + "ffn_gate.w": (D, F), b + "ffn_up.w": (D, F),
                            b + "ffn_down.w": (F, D)}))
    return arch, p


def _sambay():
    heads, kv, n, s, taps, r = 4, 2, 128, 4, 4, 4
    arch = arch_mod.SambaY(8, heads, kv, D, window=8, d_inner=n,
                           d_state=s, conv_taps=taps, dt_rank=r)
    dh = D // heads
    p = _shapes(**{"tok_emb.w": (V, D), "ln_f.scale": (D,),
                   "ln_f.bias": (D,)})
    for i, kind in enumerate(arch.kinds):
        b = f"block{i}_"
        for ln in ("ln1", "ln2"):
            p.update(_shapes(**{b + ln + ".scale": (D,),
                                b + ln + ".bias": (D,)}))
        p.update(_shapes(**{b + "ffn_gu.w": (D, 2 * F),
                            b + "ffn_down.w": (F, D)}))
        if kind == "mamba":
            p.update(_shapes(**{
                b + "ssm_in.w": (D, 2 * n), b + "ssm_conv.w": (n, taps),
                b + "ssm_conv.b": (n,), b + "ssm_x.w": (n, r + 2 * s),
                b + "ssm_dt.w": (r, n), b + "ssm_dt.b": (n,),
                b + "ssm_A_log.w": (n, s), b + "ssm_D.w": (n,),
                b + "ssm_out.w": (n, D)}))
        elif kind == "gmu":
            p.update(_shapes(**{b + "gmu_in.w": (D, n),
                                b + "gmu_out.w": (n, D)}))
        else:
            if kind == "cross":
                p.update(_shapes(**{b + "att_q.w": (D, D),
                                    b + "att_q.b": (D,)}))
            else:
                width = D + 2 * kv * dh
                p.update(_shapes(**{b + "att_qkv.w": (D, width),
                                    b + "att_qkv.b": (width,)}))
            p.update(_shapes(**{b + "att_out.w": (D, D),
                                b + "att_out.b": (D,),
                                b + "att_subln.scale": (2 * dh,)}))
            for v in ("q1", "k1", "q2", "k2"):
                p.update(_shapes(**{b + f"att_lambda_{v}.w": (dh,)}))
    return arch, p


def _gated_moe():
    heads, kv, dh, e, width, held = 4, 2, 32, 48, 16, 4
    types = ("window", "window", "full")
    arch = arch_mod.GatedMoE(types, heads, kv, dh, D, window=8,
                             dense_layers=1, router_width=width, top_k=4,
                             experts=(4, held), route_scale=2.448)
    p = _shapes(**{"tok_emb.w": (V, D), "norm_f.scale": (D,),
                   "lm_head.w": (D, V)})
    for i in range(len(types)):
        b = f"block{i}_"
        for n in range(1, 5):
            p.update(_shapes(**{b + f"norm{n}.scale": (D,)}))
        p.update(_shapes(**{
            b + "att_q.w": (D, heads * dh), b + "att_gate.w": (D, heads * dh),
            b + "att_k.w": (D, kv * dh), b + "att_v.w": (D, kv * dh),
            b + "att_out.w": (heads * dh, D),
            b + "att_qnorm.scale": (dh,), b + "att_knorm.scale": (dh,)}))
        if i < 1:
            p.update(_shapes(**{b + "ffn_gate.w": (D, F),
                                b + "ffn_up.w": (D, F),
                                b + "ffn_down.w": (F, D)}))
        else:
            p.update(_shapes(**{
                b + "router.w": (D, width), b + "router.bias": (width,),
                b + "shared_gate.w": (D, e), b + "shared_up.w": (D, e),
                b + "shared_down.w": (e, D),
                b + "experts_gate.w": (held, D, e),
                b + "experts_up.w": (held, D, e),
                b + "experts_down.w": (held, e, D)}))
    return arch, p


ARCHS = {"gpt2": _gpt2, "looped": _looped, "sambay": _sambay,
         "gated_moe": _gated_moe}
EVERY = {"embed", "norm", "attn.proj", "attn.core", "head", "cache"}
MUST = {"gpt2": EVERY | {"ffn"}, "looped": EVERY | {"ffn"},
        "sambay": EVERY | {"ffn", "mixer"},
        "gated_moe": EVERY | {"ffn", "moe.route", "moe.experts",
                              "moe.shared"}}
ONLY = {"mixer": {"sambay"}, "moe.route": {"gated_moe"},
        "moe.experts": {"gated_moe"}, "moe.shared": {"gated_moe"}}


def _compiled(name, entry):
    arch, p = ARCHS[name]()
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    blocks = arch.passes * (1 + S * NB)
    pool = tuple(jax.ShapeDtypeStruct(
        (blocks, *arch.pool_block_shape(B, DT)), DT) for _ in arch.planes)
    state = tuple(tuple(jax.ShapeDtypeStruct((S, *shape), dt)
                        for shape, dt in layer)
                  for layer in arch.state_spec(DT))
    if entry == "decode_chunk":
        fn = _bd.make_decode_chunk(arch, 2, donate=False)
        args = (p, pool, pool, i32(S), i32(S), i32(S, NB), state)
    else:
        fn = _bd.make_prefill(arch, bucket=8, donate=False)
        args = (p, pool, pool, i32(S), i32(S), i32(), i32(NB), i32(8),
                i32(), i32(), i32(), i32(), state)
    return fn.lower(*args).compile()


@pytest.mark.parametrize("entry", ["decode_chunk", "prefill"])
@pytest.mark.parametrize("name", sorted(ARCHS))
def test_every_matmul_of_a_serving_executable_has_a_kind(name, entry):
    text = _compiled(name, entry).as_text()
    module, scopes = trace.scopes_of_hlo(text)
    assert module == f"jit_{entry}"
    instrs = list(iter_instructions(text))
    assert len({i.name for i in instrs}) == len(instrs)  # one entry each
    by_comp = collections.defaultdict(list)
    for i in instrs:
        by_comp[i.comp].append(i)
    work = ("dot", "convolution", "custom-call")
    checked = 0
    for i in instrs:
        if i.name not in scopes:   # inside a fusion, or computes nothing
            continue
        scope = scopes[i.name]
        assert scope.kind is None or scope.kind in trace.KINDS
        assert scope.kind not in scope.mixed
        if i.opcode in ("while", "conditional", "call"):
            assert scope.kind is None, (i.name, scope)
        does_work = i.opcode in work or (i.opcode == "fusion" and any(
            b.opcode in work for c in called_computations(i.head)
            for b in by_comp[c]))
        if does_work:
            assert scope.kind in trace.KINDS, (i.name, i.op_name, scope)
            assert scope.phase == "forward"
            checked += 1
    assert checked >= 8
    kinds = {s.kind for s in scopes.values()} - {None}
    assert MUST[name] <= kinds, MUST[name] - kinds
    for kind, archs in ONLY.items():
        assert (kind in kinds) == (name in archs), kind
    # the stack's instructions sit under the stack's scope
    assert all(trace.STACK_SCOPE in s.path for s in scopes.values()
               if s.kind in ("attn.proj", "attn.core", "ffn", "mixer"))


def _train_step(monkeypatch, scan_remat):
    if not scan_remat:
        monkeypatch.setenv("PADDLE_TPU_SCAN_REMAT", "0")
    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        model = transformer.build(
            vocab_size=128, max_len=16, n_layer=2, d_model=32, n_head=2,
            dtype="float32", learning_rate=1e-3, dropout_rate=0.0,
            fused_head=True)
        pt.memory_optimize(main, policy="selective")
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope)
    feed = {"tokens": np.zeros((4, 16), np.int64),
            "labels": np.ones((4, 16), np.int64)}
    return exe, main, feed, model["avg_cost"], scope


@pytest.mark.parametrize("scan_remat", [True, False],
                         ids=["jax_checkpoint", "barrier_fallback"])
def test_a_train_step_names_kind_and_phase(monkeypatch, scan_remat):
    exe, main, feed, cost, scope = _train_step(monkeypatch, scan_remat)
    exe.run(main, feed=feed, fetch_list=[cost], scope=scope)
    entry = trace.device_scopes()[-1]
    assert entry["module"] == "jit_step"
    seen = collections.defaultdict(set)
    for s in entry["instructions"].values():
        seen[(s.kind, s.phase)].add(s.path)
    # adam -> optimizer; _ffn1's backward -> ffn/backward; what the
    # remat wrapper (jax.checkpoint in the scan over layers, the
    # executor's own re-trace in the fallback) computes again -> recompute
    assert any("adam:block1_ffn1.w" in p for p in seen["optimizer", "forward"])
    assert any("_ffn1" in p for p in seen["ffn", "backward"])
    assert any("_ffn1" in p for p in seen["ffn", "forward"])
    assert seen["ffn", "recompute"] and seen["norm", "recompute"]
    marker = ("rematted_computation" if scan_remat
              else trace.RECOMPUTE_SCOPE)
    assert all(marker in p for p in seen["ffn", "recompute"])
    assert any("flash_attention" in p for p in seen["attn.core", "backward"])
    assert any("fused_softmax_ce_head:lm_head" in p
               for p in seen["head", "forward"])
    assert seen["embed", "forward"] and seen["attn.proj", "backward"]
    assert not seen["optimizer", "backward"]


@pytest.mark.parametrize("op_type,layer,kind", [
    ("adam", "block3_ffn1.w", "optimizer"), ("sgd", "lm_head.w", "optimizer"),
    ("layer_norm", "block0_ln2", "norm"), ("layer_norm", "ln_f", "head"),
    ("mul", "lm_head", "head"), ("fused_softmax_ce_head", "lm_head", "head"),
    ("flash_attention", "flash_attention_0", "attn.core"),
    ("mul", "block3_att_q", "attn.proj"),
    ("elementwise_add", "block3_att_out", "attn.proj"),
    ("gelu", "block3_ffn1", "ffn"), ("lookup_table", "embedding_0", "embed"),
    ("reshape", "reshape_4", None), ("elementwise_add", "elementwise_add_2",
                                     None)])
def test_the_table_from_op_to_kind(op_type, layer, kind):
    assert trace.kind_of_op(op_type, layer) == kind
    assert kind is None or kind in trace.KINDS


def test_nothing_is_built_by_compiling_or_running(monkeypatch):
    """One append a compile: ``as_text`` is not read and no map is parsed
    until ``device_scopes()`` is called, and then once an executable."""
    import jax.stages

    from paddle_tpu.observability.metrics import MetricsRegistry
    from paddle_tpu.serving import ServingEngine

    calls = collections.Counter()
    as_text = jax.stages.Compiled.as_text
    parse = trace.scopes_of_hlo
    monkeypatch.setattr(
        jax.stages.Compiled, "as_text",
        lambda self, *a, **k: (calls.update(as_text=1),
                               as_text(self, *a, **k))[1])
    monkeypatch.setattr(
        trace, "scopes_of_hlo",
        lambda text: (calls.update(parse=1), parse(text))[1])
    monkeypatch.setattr(trace, "_executables", collections.deque(maxlen=8))

    arch, shapes = _gpt2()
    rng = np.random.default_rng(0)
    params = {k: jnp.asarray(0.05 * rng.standard_normal(v.shape), DT)
              for k, v in shapes.items()}
    eng = ServingEngine(params, arch=arch, max_len=T, block_tokens=B,
                        max_slots=S, decode_chunk=2, min_bucket=4,
                        donate=False, registry=MetricsRegistry())
    out = eng.generate_many([np.arange(1, 6, dtype=np.int32)],
                            max_new_tokens=5)
    assert len(out[0]) == 10
    labels = [e[0] for e in trace._executables]
    assert "decode" in labels and any(
        lb.startswith("prefill_") for lb in labels)
    assert not calls and all(e[2] is None for e in trace._executables)
    # the registry holds the executable alone: no engine, no pool
    del eng, out
    first = trace.device_scopes()
    assert calls["as_text"] == calls["parse"] == len(labels)
    assert trace.device_scopes() == first and calls["parse"] == len(labels)
    assert {e["module"] for e in first} == {"jit_decode_chunk",
                                            "jit_prefill"}


def test_the_join_names_what_it_can_and_returns_the_rest():
    """A synthetic ops table against a map: self seconds (a loop is not
    counted with its body), the module by containment in time, the
    executable of a shared module name by shape, and the remainder."""
    def scope(kind, phase, shape):
        return trace.DeviceScope(kind, phase, "jit(f)/" + str(kind), shape,
                                 ())

    scopes = [
        {"label": "decode_chunk", "module": "jit_decode_chunk",
         "instructions": {
             "while.1": scope(None, "forward", "(f32[4])"),
             "fusion.1": scope("ffn", "forward", "f32[4,8]"),
             "fusion.2": scope("attn.core", "forward", "f32[4]"),
             "copy.3": trace.DeviceScope(None, None, "", "f32[4]", ())}},
        {"label": "prefill_8", "module": "jit_prefill",
         "instructions": {"fusion.1": scope("ffn", "forward", "f32[8,8]")}},
        {"label": "prefill_16", "module": "jit_prefill",
         "instructions": {"fusion.1": scope("head", "forward",
                                            "f32[16,8]")}},
    ]
    op = lambda name, shape, opcode="fusion": (
        f"%{name} = {shape}{{1,0:T(8,128)}} {opcode}(f32[4]{{0}} %x)")
    modules = [(0, 100, "jit_decode_chunk(11)"), (200, 260, "jit_prefill(7)"),
               (300, 360, "jit_prefill(9)")]
    ops = [(0, 100, op("while.1", "(f32[4])", "while")),
           (10, 40, op("fusion.1", "f32[4,8]")),
           (40, 60, op("fusion.2", "f32[4]")),
           (60, 70, op("copy.3", "f32[4]", "copy")),
           (70, 75, op("fusion.99", "f32[2]")),
           (200, 260, op("fusion.1", "f32[16,8]")),
           (300, 350, op("fusion.1", "f32[8,8]")),
           (400, 410, op("fusion.1", "f32[4,8]"))]   # outside every module
    got = trace.join_device_ops([(modules, ops)], scopes)
    assert got["seconds"] == {
        ("jit_decode_chunk", None, "forward"): 35,   # the loop less its body
        ("jit_decode_chunk", "ffn", "forward"): 30,
        ("jit_decode_chunk", "attn.core", "forward"): 20,
        ("jit_prefill", "head", "forward"): 60,
        ("jit_prefill", "ffn", "forward"): 50}
    assert got["unnamed"] == {("jit_decode_chunk", "copy.3"): 10,
                              ("jit_decode_chunk", "fusion.99"): 5,
                              ("", "fusion.1"): 10}
    assert got["total"] == 100 + 60 + 50 + 10
    # read back from JSON (a saved timeline's metadata) it joins the same
    import json

    assert trace.join_device_ops(
        [(modules, ops)], json.loads(json.dumps(scopes)))["seconds"] == {
            k: v for k, v in got["seconds"].items()}


def test_a_saved_timeline_carries_the_map(tmp_path, monkeypatch):
    import json

    monkeypatch.setattr(trace, "_executables", collections.deque(maxlen=8))
    f = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8))).compile()
    trace.register_executable("square", f)
    tracer = trace.Tracer(enabled=True, registry=None)
    with tracer.span("work"):
        pass
    tracer.save(str(tmp_path / "t.json"))
    saved = json.loads((tmp_path / "t.json").read_text())
    (entry,) = saved["metadata"]["device_scopes"]
    assert entry["label"] == "square" and entry["instructions"]
    # and a tracer in a process that compiled nothing writes no metadata
    monkeypatch.setattr(trace, "_executables", collections.deque(maxlen=8))
    assert "metadata" not in tracer.to_chrome_trace()
