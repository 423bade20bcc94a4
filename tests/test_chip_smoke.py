"""What keeps the chip path up between chip runs (CPU): ``chip_smoke.py``
refuses a host with no TPU, the compile cache resolves to one fixed
place, nothing prices or places a device it does not know, and every
``pallas_tpu`` kernel on the smoke's path cross-lowers for TPU at the
smoke's geometry — a BlockSpec the chip would refuse fails here instead
of passing interpreted."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402 — the repo root is not a package


_SHOW_CACHE = ("import jax, paddle_tpu\n"
               "from paddle_tpu.core import compile_cache\n"
               "print(compile_cache.cache_dir())\n"
               "print(jax.config.jax_compilation_cache_dir)\n"
               "print(jax.config.jax_persistent_cache_min_compile_time_secs)"
               "\n")


@pytest.fixture(scope="module")
def children():
    """The four child processes these tests need, started together (each
    spends ~2 s importing JAX): name -> (returncode, stdout, stderr)."""
    def spawn(args, **env):
        full = {k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"}
        full.update(env)
        return subprocess.Popen(
            [sys.executable] + args, cwd=REPO, env=full, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE)

    # JAX_PLATFORMS empty = a host that may have a chip; importing the
    # package initializes no backend, so these run anywhere
    procs = {
        "smoke_on_cpu": spawn([os.path.join(REPO, "chip_smoke.py")],
                              JAX_PLATFORMS="cpu"),
        "cache_first": spawn(["-c", _SHOW_CACHE], JAX_PLATFORMS=""),
        "cache_second": spawn(["-c", _SHOW_CACHE], JAX_PLATFORMS=""),
        "cache_placed": spawn(["-c", _SHOW_CACHE], JAX_PLATFORMS="",
                              JAX_COMPILATION_CACHE_DIR="/somewhere/else"),
    }
    out = {}
    for name, proc in procs.items():
        stdout, stderr = proc.communicate(timeout=300)
        out[name] = (proc.returncode, stdout, stderr)
    return out


def test_smoke_refuses_a_host_without_tpu(children):
    code, out, err = children["smoke_on_cpu"]
    assert code != 0
    assert "needs a TPU" in err
    # it stopped before importing the package or building anything:
    # no header, no phase, no result line
    assert out == ""


def test_result_line_holds_the_contract_keys_and_no_others():
    import json

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    for ok in (True, False):
        line = chip_smoke.result_line(ok, dict(device, extra="dropped"))
        assert "\n" not in line
        assert json.loads(line) == {"ok": ok, "device": device}
    # the result is the last thing main() writes on either way out
    src = open(chip_smoke.__file__).read()
    assert src.count("print(result_line(") == 2
    tail = src[src.index("print(result_line(True"):]
    assert "print(" not in tail[len("print("):tail.index("return 0")]


def test_compile_cache_resolves_to_one_place(children):
    from paddle_tpu.core import compile_cache

    default = os.path.join(REPO, ".jax_cache")
    for name in ("cache_first", "cache_second", "cache_placed"):
        assert children[name][0] == 0, children[name][2]
    # unset: <checkout>/.jax_cache, set in code, the same in two processes
    assert (children["cache_first"][1].split()
            == children["cache_second"][1].split()
            == [default, default, "0.0"])
    # placed from outside: JAX reads the variable itself
    # (every executable persists either way: what a run leaves in the
    # cache must not depend on which compiles took a second)
    assert children["cache_placed"][1].split() == ["/somewhere/else"] * 2 + [
        "0.0"]
    # a process pinned to the CPU (this one) gets no cache from code
    assert compile_cache.cache_dir() == default
    assert jax.config.jax_compilation_cache_dir is None


class _FakeDevice:
    platform = "tpu"
    device_kind = "TPU v9 prototype"


def test_unknown_accelerator_is_an_error_not_a_default():
    from paddle_tpu.observability import hardware

    for fn in (hardware.device_peak_flops, hardware.device_hbm_bandwidth):
        with pytest.raises(ValueError, match="TPU v9 prototype"):
            fn(_FakeDevice())
        assert fn(jax.devices("cpu")[0]) > 0


def test_tpu_place_raises_without_an_accelerator():
    with pytest.raises(RuntimeError, match="no accelerator"):
        pt.TPUPlace(0).get_device()
    assert pt.CPUPlace().get_device().platform == "cpu"


def _lowers_for_tpu(fn, *shapes, names):
    """Cross-lowers ``fn`` for TPU and finds one Mosaic call per kernel
    name in ``names``, each under that name: the call's location
    (``jit(f)/transpose(jvp(fused_ce_dw))/pallas_call``) is what the TPU
    compiler names the HLO instruction after (``%transpose_jvp_fused_ce_
    dw__.3``), and the instruction text is all a device trace says of
    where an operation comes from."""
    import re

    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert text.count("@tpu_custom_call") == len(names)
    calls = re.findall(r'loc\("([^"]*)/pallas_call"', text)
    for name in names:
        assert any(re.search(rf"\b{name}\b", c) for c in calls), (name, calls)


def test_path_kernels_cross_lower_for_tpu_at_smoke_geometry(monkeypatch):
    from paddle_tpu import tune
    from paddle_tpu.ops import pallas_attention
    from paddle_tpu.kernels.paged_attention import paged_attention_pallas
    from paddle_tpu.ops.pallas_attention import (
        _pallas_flash_attention_packed)
    from paddle_tpu.ops.pallas_ce import _pallas_ce

    dims = tune.flagship_dims()
    h, dm, vocab, b = (dims["n_head"], dims["d_model"], dims["vocab"],
                       dims["batch"])
    bf16, i32 = jnp.bfloat16, jnp.int32
    t = chip_smoke.SEQ

    def flash_loss(q, k, v):
        return jnp.sum(_pallas_flash_attention_packed(
            q, k, v, h, causal=True, interpret=False).astype(jnp.float32))

    _lowers_for_tpu(jax.grad(flash_loss, (0, 1, 2)),
                    *[((b, t, dm), bf16)] * 3,
                    names=("flash_fwd", "flash_bwd_fused"))

    # long sequences (dq partials over budget) split the backward in two
    monkeypatch.setattr(pallas_attention, "FUSED_BWD_PARTIAL_BYTES", 0)
    _lowers_for_tpu(jax.grad(flash_loss, (0, 1, 2)),
                    *[((b, t, dm), bf16)] * 3,
                    names=("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    monkeypatch.undo()

    def ce_loss(x, w, y):
        return jnp.sum(_pallas_ce(x, w, y, interpret=False))

    _lowers_for_tpu(jax.grad(ce_loss, (0, 1)), ((b * t, dm), bf16),
                    ((dm, vocab), bf16), ((b * t,), i32),
                    names=("fused_ce_fwd", "fused_ce_dx", "fused_ce_dw"))

    nb = chip_smoke.MAX_LEN // chip_smoke.BLOCK_TOKENS
    pool = ((1 + chip_smoke.SLOTS * nb + 2 * nb, chip_smoke.BLOCK_TOKENS,
             h, dm // h), bf16)
    # decode, a verify window, and a prefill piece too narrow to attend
    # densely (kernels.paged_attention.DENSE_WINDOW)
    for slots, width in ((chip_smoke.SLOTS, 1), (chip_smoke.SLOTS, 4),
                         (1, 4)):
        _lowers_for_tpu(
            lambda *a: paged_attention_pallas(*a, interpret=False),
            ((slots, width, h, dm // h), bf16), pool, pool,
            ((slots, nb), i32), ((slots, width), i32),
            names=("paged_attention",))


@pytest.mark.parametrize("width", [4, 16, 128])
def test_prefill_cross_lowers_for_tpu_dense_from_the_wide_window_up(
        width, monkeypatch):
    """The whole prefill executable, traced as the chip would (backend
    "tpu", kernels native) at the smoke's block geometry: a window of
    ``DENSE_WINDOW`` rows or more holds no Mosaic call at all (the chain
    is gathered once and attended on the MXU), a narrower one holds the
    streaming kernel once per layer."""
    from paddle_tpu.kernels.paged_attention import DENSE_WINDOW
    from paddle_tpu.serving import batched_decode as _bd
    from paddle_tpu.serving.arch import Gpt2

    n_layer, h, dm, vocab = 2, 2, 256, 512
    nb = chip_smoke.MAX_LEN // chip_smoke.BLOCK_TOKENS
    bf16, i32 = jnp.bfloat16, jnp.int32
    sds = jax.ShapeDtypeStruct
    p = {"tok_emb.w": sds((vocab, dm), bf16),
         "pos_emb.w.w": sds((chip_smoke.MAX_LEN, dm), bf16),
         "ln_f.scale": sds((dm,), bf16), "ln_f.bias": sds((dm,), bf16),
         "lm_head.w": sds((dm, vocab), bf16)}
    for i in range(n_layer):
        for nm, (a, b) in (("att_q", (dm, dm)), ("att_k", (dm, dm)),
                           ("att_v", (dm, dm)), ("att_out", (dm, dm)),
                           ("ffn1", (dm, 4 * dm)), ("ffn2", (4 * dm, dm))):
            p[f"block{i}_{nm}.w"] = sds((a, b), bf16)
            p[f"block{i}_{nm}.b"] = sds((b,), bf16)
        for ln in ("ln1", "ln2"):
            p[f"block{i}_{ln}.scale"] = sds((dm,), bf16)
            p[f"block{i}_{ln}.bias"] = sds((dm,), bf16)
    pool = tuple(sds((1 + 3 * nb, chip_smoke.BLOCK_TOKENS, h, dm // h), bf16)
                 for _ in range(n_layer))
    scalar = sds((), i32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn = _bd.make_prefill(Gpt2(n_layer, h, dm), width, donate=False)
    text = fn.trace(p, pool, pool, sds((3,), i32), sds((3,), i32), scalar,
                    sds((nb,), i32), sds((width,), i32), scalar, scalar,
                    scalar, scalar).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("@tpu_custom_call") == (
        0 if width >= DENSE_WINDOW else n_layer)


@pytest.mark.parametrize("recipe", ["fsdp", "tp"])
def test_mesh_step_cross_lowers_for_tpu(recipe, monkeypatch):
    """GSPMD cannot partition a Mosaic custom call; on a mesh every
    natively compiled kernel must sit in a shard_map.  Interpreted
    kernels hide this (they are plain XLA ops), so trace the train step
    as the chip would — backend "tpu", kernels native — and lower it for
    TPU from the CPU mesh."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.models import transformer

    monkeypatch.setattr(chip_smoke, "SEQ", 256)
    dims = {"n_layer": 2, "d_model": 256, "n_head": 2, "vocab": 512,
            "batch": 8}
    axes = {"dp": 2, "tp": 2} if recipe == "tp" else {"dp": 2, "fsdp": 2}
    mesh = pt.parallel.make_mesh(axes, devices=jax.devices()[:4])
    main, startup, avg_cost = chip_smoke._build_gpt(
        pt, dims, mesh_recipe=(recipe == "fsdp"))
    if recipe == "tp":
        pt.parallel.data_parallel(main, "dp", programs=(startup,))
        for prog in (main, startup):
            pt.parallel.api.shard_parameters_by_rule(
                prog, transformer.tp_rules())
    scope = pt.Scope()
    exe = pt.Executor(mesh=mesh)
    exe.run(startup, scope=scope)
    tokens = np.zeros((dims["batch"], 256), np.int32)
    sharding = NamedSharding(mesh, P("dp"))
    feed = {"tokens": jax.device_put(tokens, sharding),
            "labels": jax.device_put(tokens + 1, sharding)}
    (program, scope, feed_names, fetch_names, feed_vals, state_names,
     state, _) = exe._prepare(main, feed, [avg_cost], scope)
    jitted = exe._compile(program, feed_names, fetch_names, state_names)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = jitted.trace(state, *feed_vals).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
