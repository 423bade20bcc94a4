"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device-operation intervals) / (traced window), from
the profiler's trace through ``chipbench/trace_reduce.py``."""

from chipbench import trace_reduce

NAME = "device.idle_share.train"
LAYER = "Device"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
RUNNERS = ("train",)


def read(facts):
    return trace_reduce.idle_share_percent(facts)
