"""Both runners end to end on the CPU at a tiny size: paths, arguments
and control flow, and the checks that decide ``correct``.  No time or
rate from here is a device metric, and none is printed."""

import json

import pytest

from chipbench import run as bench_run

TINY = {"name": "tiny", "n_embd": 256, "n_layer": 2, "n_head": 2,
        "n_inner": 512, "n_positions": 64, "vocab_size": 250,
        "layer_norm_epsilon": 1e-5, "compute_dtype": "bfloat16",
        "changed": {"vocab_rows": 256}}
TRAIN = {"runner": "train", "chips": 1, "mesh": None, "seq_len": 64,
         "sequences_per_step": 4, "micro_steps": 2,
         "memory_optimize": "selective", "learning_rate": 3e-3,
         "data": {"branching": 2, "zipf_s": 1.0}, "warmup_steps": 2,
         "check": {"loss_abs_tol": 0.005}}
SERVE = {"runner": "serve", "chips": 1,
         "engine": {"max_len": 64, "max_slots": 4, "block_tokens": 8,
                    "cache_blocks": 16, "prefix_reuse": True},
         "rate_per_s": 8.0, "schedule_seed": 5,
         "shared_heads": {"count": 2, "tokens": 16, "zipf_s": 1.0},
         "prompt_tail": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                         "min": 2, "max": 12},
         "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                    "min": 4, "max": 16},
         "drain_seconds": 60, "warmup_timeout_s": 300,
         "trace_seconds": 1.0, "check": {"sample": 4, "logit_margin": 0.25}}


def _cell(traffic):
    bench = json.load(open(bench_run.ROOT + "/BENCHMARK.json"))
    return {"name": "tiny." + traffic["runner"], "chips": 1, "config": TINY,
            "traffic": traffic, "end_to_end": bench["end_to_end"],
            "per_layer": bench["per_layer"]}


def test_train_runner_rehearsal():
    from chipbench.runners import train

    cell = _cell(TRAIN)
    result = train.run(cell, seed=2 ** 31 + 11, seconds=1.5, tracer=None)
    facts = result["facts"]
    assert result["correct"], (facts["loss_err"], facts["losses"])
    assert result["attempted"] == facts["steps"] >= 2
    assert result["failed"] == 0
    assert facts["loss_err"] <= TRAIN["check"]["loss_abs_tol"]
    assert bench_run.compared_lines(result).splitlines() == [
        f"chipbench: compared loss_err = {facts['loss_err']!r}, limit 0.005",
        "chipbench: compared loss_fell = True, limit True"]
    assert set(result["end_to_end"]) == {"train_tokens_per_s"}
    # the step after the checked one trains on the chain's own labels
    assert facts["losses"][0] < facts["losses"][1] - 0.5
    # the readers that need no trace read these facts
    facts.update(config=TINY, traffic=TRAIN, chips=1, trace=None,
                 trace_window_s=None,
                 peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    got = bench_run.layer_metrics(cell, facts)
    assert {"executor.dispatch_ms", "train.mfu",
            "compile.seconds"} <= set(got)
    assert not any(k.startswith(("device.", "flash")) for k in got)


def test_one_wrong_layer_fails_the_training_check(monkeypatch):
    """The program and the reference differing in ONE layer's output
    projection, at the tolerance the right program passes."""
    from chipbench import reference
    from chipbench.runners import train

    right = reference.greedy_loss

    def wrong(params, *args):
        params = dict(params)
        params["block1_ffn2.w"] = -params["block1_ffn2.w"]
        return right(params, *args)

    monkeypatch.setattr(reference, "greedy_loss", wrong)
    result = train.run(_cell(TRAIN), seed=5, seconds=0.5, tracer=None)
    assert not result["correct"]
    assert result["facts"]["loss_fell"]
    assert result["facts"]["loss_err"] > 4 * TRAIN["check"]["loss_abs_tol"]


def test_serve_runner_rehearsal():
    from chipbench.runners import serve

    cell = _cell(SERVE)
    result = serve.run(cell, seed=2 ** 31 + 11, seconds=2.0, tracer=None)
    facts = result["facts"]
    assert result["correct"], facts["worst_logit_margin"]
    assert result["attempted"] == 16 and result["failed"] == 0
    assert facts["compiled_in_window"] == 0
    assert result["compared"] == [
        ("worst_logit_margin", facts["worst_logit_margin"], 0.25),
        ("compiled_in_window", 0, 0)]
    assert set(result["end_to_end"]) == {
        "ttft_p90_ms", "tpot_p90_ms", "serve_tokens_per_s"}
    assert all(r["out"] >= 4 for r in facts["requests"])
    # the shared heads were hot: most prompt tokens came from the cache
    assert facts["stats"]["serving.prefix_hit_rate"] > 0.5
    facts.update(config=TINY, traffic=SERVE, chips=1, trace=None,
                 trace_window_s=None,
                 peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    got = bench_run.layer_metrics(cell, facts)
    assert {"sched.queue_wait_p50_ms", "sched.prefix_hit_share",
            "sched.slot_occupancy_peak", "sched.block_occupancy_peak",
            "step.prefill_ms_per_token", "step.decode_ms",
            "compile.seconds", "gen.late_ms_p90"} <= set(got)
    assert not any(k.startswith(("device.", "paged")) for k in got)
    # the per-layer tail is the runner's own number, to the digit
    assert got["serve.ttft_p90_ms"]["value"] == pytest.approx(
        result["end_to_end"]["ttft_p90_ms"], rel=1e-9)
    # read 20 times a second: at this size a request can come and go
    # between two readings, so 0 is a possible peak here
    assert 0 <= got["sched.slot_occupancy_peak"]["value"] <= 100
    # the two hot heads alone hold 4 of the pool's 48 blocks
    assert 8 < got["sched.block_occupancy_peak"]["value"] <= 100
    # a margin no bf16 server can meet fails the check
    cell["traffic"] = dict(SERVE, check={"sample": 2, "logit_margin": -1.0})
    assert not serve.run(cell, seed=3, seconds=1.0, tracer=None)["correct"]


def test_refuses_without_a_tpu(capsys):
    assert bench_run.main(["--workload", "cgpt1p3b.agent_turns", "--seed", "1",
                           "--seconds", "1", "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err
    with pytest.raises(SystemExit):
        bench_run.load_cell("no.such.cell")


def test_train_runner_on_a_mesh_rehearsal():
    """The traffic file's ``mesh`` axes on four virtual CPU devices: the
    path the four-chip cell will take (not yet run on four chips)."""
    import jax

    if jax.device_count() < 4:
        pytest.skip("needs four (virtual) devices")
    from chipbench.runners import train

    cell = _cell(dict(TRAIN, chips=4, mesh={"dp": 2, "fsdp": 2}))
    cell["chips"] = 4
    result = train.run(cell, seed=9, seconds=1.0, tracer=None)
    assert result["correct"], result["facts"]["loss_err"]
    assert result["attempted"] >= 2
