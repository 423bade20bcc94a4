"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device-operation intervals) / (traced window), from
the profiler's trace through ``chipbench/trace_reduce.py``."""

from chipbench import trace_reduce

NAME = "device.idle_share.serve"
LAYER = "Device"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def read(facts):
    return trace_reduce.idle_share_percent(facts)
