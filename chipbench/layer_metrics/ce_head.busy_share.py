"""The fused cross-entropy head's share of the chip's busy time over the
traced steps: device seconds of the Mosaic calls whose HLO instruction is
named after a ``fused_ce_*`` kernel (``fused_ce_fwd``, ``fused_ce_dx``,
``fused_ce_dw``: the names the program gives its ``pallas_call``s; the
instruction's own name, not its operands') over the busy union.  A trace
in which no call carries such a name gives nothing to read."""

NAME = "ce_head.busy_share"
LAYER = "Kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
RUNNERS = ("train",)

NEEDLE = "fused_ce_"


CALL = 'custom_call_target="tpu_custom_call"'


def kernels(cfg, mix):
    return {"fused_ce": (NEEDLE, CALL)}


def read(facts):
    trace = facts.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    # the instruction's own name, not its operands': a fusion that reads
    # a kernel's result has the kernel's name in its text too
    seconds = [rec["self"] for rec in trace["ops"].values()
               if NEEDLE in rec["provenance"].split(" = ")[0]
               and CALL in rec["provenance"]]
    if not seconds:
        return None
    return 100.0 * sum(seconds) / trace["busy_s"]
