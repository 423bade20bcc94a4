"""Time to the first token, 90th percentile (Harrell-Davis) over ALL the
requests due in the window, each from when it was DUE: what the runner
computes as ``ttft_p90_ms``.  A request with no first token by the end
of the drain counts to that end.

Per layer in a cell where the host's two dispatch speeds alone move it
by more than a bound can hold (PERF.md section 2); a cell whose tail the
device sets may carry the runner's own ``ttft_p90_ms`` end to end."""

from chipbench import stats

NAME = "serve.ttft_p90_ms"
LAYER = "Entry points"
UNIT = "ms"
MOVES = "tpot_p90_ms"
SOURCE = "host_clock"
RUNNERS = ("serve",)


def read(facts):
    requests = facts["requests"]
    if not requests:
        return None
    drained = facts["seconds"] + facts.get("drain_s", 0.0)
    ttft = [(drained if r["first"] is None else r["first"]) - r["due"]
            for r in requests]
    return stats.quantile_hd(ttft, 0.9) * 1e3
