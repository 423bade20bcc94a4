"""The flash-attention kernels' share of their roofline over the traced
steps: the least time the chip could take for the calls the trace holds
(operations and bytes from the shapes, ``chipbench/flops.py``; forward
and backward calls counted from the trace) over the device time those
calls took.  Causal, packed heads of 128: compute-bound at t = 2048.

The trace gives a Mosaic call no name of the kernel's (``%closed_call.N``,
``kernel_metadata={}``), so the calls are found by the shapes of what
they return: forward (o [b, t, d], lse [b * h, t, 1]), backward (dq in
two parts [2, b, t, d], dk, dv).  A kernel that returns other shapes is
not found and the metric is left out, not guessed."""

from chipbench import families, flops, trace_reduce

NAME = "flash_roofline"
LAYER = "Kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
RUNNERS = ("train",)


def _shape(cfg, mix):
    size = families.sizes(cfg)
    return (mix["sequences_per_step"] // mix["micro_steps"], size["heads"],
            size["head_dim"], mix["seq_len"])


def kernels(cfg, mix):
    b, h, dh, t = _shape(cfg, mix)
    x = f"bf16[{b},{t},{h * dh}]"
    call = 'custom_call_target="tpu_custom_call"'
    return {"flash_fwd": (call, f"= ({x}", f"f32[{b * h},{t},1]", ") custom-call("),
            "flash_bwd": (call, f"= (bf16[2,{b},{t},{h * dh}]",
                          ") custom-call(")}


def read(facts):
    trace = facts.get("trace")
    if not trace:
        return None
    cfg, mix = facts["config"], facts["traffic"]
    needles = kernels(cfg, mix)
    least, spent = 0.0, 0.0
    for label, count in (("flash_fwd", flops.flash_fwd),
                         ("flash_bwd", flops.flash_bwd)):
        calls, seconds = trace_reduce.matching(trace, *needles[label])
        if not calls:
            return None
        least += calls * flops.roofline_seconds(*count(*_shape(cfg, mix)),
                                                facts["peak"])[0]
        spent += seconds
    return 100.0 * least / spent
