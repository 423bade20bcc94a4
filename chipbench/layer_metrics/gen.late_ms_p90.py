"""How late the load generator ran: 90th percentile of submission time
less due time.  A starved generator must not read as a fast server."""

from chipbench import stats

NAME = "gen.late_ms_p90"
LAYER = "Entry points"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "host_clock"
RUNNERS = ("serve",)


def read(facts):
    late = [r["submit"] - r["due"] for r in facts["requests"]]
    return stats.quantile_hd(late, 0.9) * 1e3 if late else None
