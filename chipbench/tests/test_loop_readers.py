"""The three readers of the looped stack's cell on hand-made facts and on
the small trace recorded on a TPU v5e (``testdata/serve_spans_v5e``, a
program whose stack runs once)."""

import json
import os

import pytest

from chipbench import flops, trace_reduce
from chipbench import run as bench_run

SPANS = os.path.join(bench_run.HERE, "testdata", "serve_spans_v5e.xplane.pb")
OURO = bench_run._read_json(bench_run.HERE, "configs", "ouro-2.6b.json")
MIX = bench_run._read_json(bench_run.HERE, "traffic", "reason_decode.json")
PEAK = flops.peaks("TPU v5 lite")


def _reader(name):
    return bench_run.load_reader(name)


def _request(prompt_len, out, first=1.0, finish=2.0):
    return {"prompt_len": prompt_len, "prefix_hit": 0, "out": out,
            "prefill_t0": first - 0.1, "prefill_t1": first,
            "first": first, "finish": finish}


def test_decode_stream_roofline_counts_a_looped_weight_every_pass():
    reader = _reader("step.decode_stream_roofline")
    weights_s = 2 * 9_965_666_304 / 819e9           # 24.3 ms of stream
    assert weights_s == pytest.approx(0.024336, rel=1e-3)
    facts = {"stats": {"serving.step_seconds": {"count": 10,
                                                "p50": 2 * weights_s}},
             "decode_chunk": 4, "peak": PEAK, "config": OURO,
             "requests": [_request(8, 1)]}           # nothing decoded
    assert reader.read(facts) == pytest.approx(50.0)
    # 40 steps; two requests decode 20 tokens each after their first
    facts["requests"] = [_request(100, 21), _request(50, 21),
                         dict(_request(9, 5), first=None)]
    live = (sum(100 + i for i in range(1, 21))
            + sum(50 + i for i in range(1, 21))) / 40
    assert reader.live_tokens_per_step(facts["requests"], 40) == live
    want = (2 * 9_965_666_304 + live * 1536 * 1024) / 819e9 / (2 * weights_s)
    assert reader.read(facts) == pytest.approx(100.0 * want)
    assert 50.0 < reader.read(facts) < 51.0
    # no decode step, or an untraced run (no peaks): nothing to read
    assert reader.read(dict(facts, stats={})) is None
    assert reader.read({k: v for k, v in facts.items()
                        if k != "peak"}) is None
    # a stack that runs once streams its weights once
    gpt = bench_run._read_json(bench_run.HERE, "configs",
                               "cerebras-gpt-1.3b.json")
    facts.update(config=gpt, requests=[_request(8, 1)])
    assert reader.read(facts) == pytest.approx(
        100.0 * 2 * flops.matmul_params(gpt) / 819e9 / (2 * weights_s))


PLANE = "bf16[708,32,16,128]{3,2,1,0:T(8,128)(2,1)}"      # 4 x 177 blocks


def _while(name, carried):
    return (f"%{name} = (s32[], bf16[10,2048], {carried}) while((s32[], "
            f"bf16[10,2048], {carried}) %tuple.1), condition=%cond.1, "
            f"body=%body.1")


def test_stack_busy_share_is_the_innermost_loops_that_carry_a_folded_plane():
    reader = _reader("loop.stack_busy_share")
    assert reader.pool_blocks(MIX) == 177
    folded = reader.folded_plane(OURO, MIX)
    assert reader.stack_passes(OURO) == 4
    assert folded(PLANE) and folded("x " + PLANE + " y")
    assert not folded("bf16[177,32,16,128]")       # a stack that runs once
    assert not folded("bf16[354,32,16,128]")       # two passes, not four
    assert not folded("bf16[709,32,16,128]")
    assert not folded("bf16[708,32,8,128]")
    fusion = "%fusion.3 = bf16[10,2048] fusion(bf16[10,2048] %x), kind=kLoop"
    events = [
        # a decode chunk: the loop over steps holds two pass loops
        (0, 1000, "%while.9", _while("while.9", PLANE)),
        (10, 60, "%fusion.3", fusion),
        (100, 400, "%while.5", _while("while.5", PLANE)),
        (150, 200, "%fusion.3", fusion),
        (500, 900, "%while.5", _while("while.5", PLANE)),
        (950, 990, "%fusion.3", fusion + " while( in an operand"),
        # a prefill: its pass loop is the only loop
        (2000, 2600, "%while.2", _while("while.2", PLANE)),
        # a loop of some other kind
        (3000, 3500, "%while.7", _while("while.7", "f32[8]")),
    ]
    assert reader.pass_loop_seconds(events, folded) == pytest.approx(
        (300 + 400 + 600) * 1e-9)
    assert reader.pass_loop_seconds(events, lambda prov: False) == 0.0


def test_stack_busy_share_fails_loudly_where_a_looped_stack_shows_no_loop():
    """The recorded trace is of a program whose stack runs once: read
    under the looped configuration it holds no pass loop, and that is an
    error there, where under its own configuration it is nothing to read."""
    reader = _reader("loop.stack_busy_share")
    summary = trace_reduce.reduce(trace_reduce.load(SPANS))
    facts = {"trace": summary, "trace_path": SPANS, "config": OURO,
             "traffic": MIX}
    with pytest.raises(RuntimeError, match="runs 4 times.*0.0 s of"):
        reader.read(facts)
    # and a loop cannot outlast the device's busy time
    chips = trace_reduce.chip_ops(trace_reduce.load(SPANS))
    loops = sum(reader.pass_loop_seconds(events, lambda prov: True)
                for events in chips.values()) / len(chips)
    assert 0 < loops <= summary["busy_s"]
    short = dict(summary, busy_s=loops / 2)
    always = lambda cfg, mix: (lambda prov: True)       # noqa: E731
    real, reader.folded_plane = reader.folded_plane, always
    try:
        with pytest.raises(RuntimeError, match="busy"):
            reader.read(dict(facts, trace=short))
        assert reader.read(facts) == pytest.approx(
            100.0 * loops / summary["busy_s"])
    finally:
        reader.folded_plane = real


def test_stack_busy_share_and_named_roofline_on_a_recorded_trace():
    """A program whose stack runs once (the GPT-2 block, 1 layer, pool
    bf16[9,32,2,128]): no folded plane, so no stack share; its kernel
    calls are found by name, the same calls the shape finds."""
    profile = trace_reduce.load(SPANS)
    summary = trace_reduce.reduce(profile)
    tiny = {"name": "tiny", "n_embd": 256, "n_layer": 1, "n_head": 2,
            "n_inner": 1024, "vocab_size": 250, "changed": {"vocab_rows": 256}}
    mix = {"engine": {"max_len": 64, "max_slots": 2, "block_tokens": 32,
                      "cache_blocks": 4}}
    facts = {"trace": summary, "trace_path": SPANS, "config": tiny,
             "traffic": mix, "trace_span": (0.0, 1.0), "peak": PEAK,
             "requests": [_request(5, 9, first=0.2, finish=0.6)]}
    stack = _reader("loop.stack_busy_share")
    assert stack.pool_blocks(mix) == 9
    assert stack.stack_passes(tiny) == 1
    assert stack.read(facts) is None
    assert stack.read({"trace": None}) is None
    named = _reader("paged_attention_named_roofline")
    by_shape = _reader("paged_attention_roofline")
    spent = named.call_seconds(summary)
    assert spent and spent > 0
    _calls, same = trace_reduce.matching(
        summary, *by_shape.kernels(tiny, mix)["paged_attention"])
    assert spent == pytest.approx(same)
    # decode positions only: 8 tokens after the first, contexts 6 .. 13
    assert named.decode_contexts(facts["requests"], 0.0, 1.0) == list(
        range(6, 14))
    assert by_shape.live_contexts(facts["requests"], 0.0, 1.0)[:5] == [
        1, 2, 3, 4, 5]
    ops, nbytes = flops.paged_attention_live(tiny, list(range(6, 14)))
    least, bound = flops.roofline_seconds(ops, nbytes, PEAK)
    assert bound == "memory"
    assert named.read(facts) == pytest.approx(100.0 * least / spent)
    assert 0 < named.read(facts) < 100
    # a trace whose calls carry no such name, or no trace
    unnamed = {"busy_s": 1.0, "ops": {k: dict(
        v, provenance=v["provenance"].replace("paged_attention", "call"))
        for k, v in summary["ops"].items()}}
    assert named.read(dict(facts, trace=unnamed)) is None
    assert named.read({"trace": None}) is None
    assert named.kernels({}, {}) == {
        "paged_attention": ("%paged_attention", named.CALL)}


def test_the_new_cell_is_wired_to_its_readers():
    bench = json.load(open(os.path.join(bench_run.ROOT, "BENCHMARK.json")))
    ouro = bench_run.load_cell("ouro2p6b.reason_decode")
    names = lambda key: {m["name"] for m in ouro[key]}  # noqa: E731
    assert names("end_to_end") == {
        "tpot_p90_ms", "serve_tokens_per_s", "setup_s"}
    assert {"step.decode_ms", "compile.seconds", "compile.cache_misses",
            "serve.ttft_p90_ms", "device.idle_share.serve",
            "step.decode_stream_roofline", "loop.stack_busy_share",
            "paged_attention_named_roofline"} <= names("per_layer")
    # nothing is shared, and the folded pool hides the kernel's calls from
    # the reader that finds them by shape
    assert not {"sched.prefix_hit_share",
                "paged_attention_roofline"} & names("per_layer")
    assert ouro["chips"] == 1
    assert MIX["shared_heads"]["count"] == 0
    assert (MIX["prompt_tail"]["max"] + MIX["output"]["max"]
            <= MIX["engine"]["max_len"])
    # compile.seconds now lists its cells: every cell that reports setup_s
    listed = next(m for m in bench["per_layer"]
                  if m["name"] == "compile.seconds")["workloads"]
    assert listed == [w["name"] for w in bench["workloads"]]
