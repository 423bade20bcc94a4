"""Share of the driver thread's wall time over the window that went to
prefill calls and their blocking first-token fetch:
``serving.driver_seconds{phase=prefill}`` plus ``{of=prefill,phase=fetch}``
over the sum of every phase (idle, loop, admit, prefill, decode, fetch,
emit: they tile the thread's timeline, from the registry's reset at the
window's start to the snapshot after the drain) less the runner's
``drain_s``, the time after the window closed.  In a traced run that is
mostly the driver idling while ``stop_trace`` holds the load generator
for tens of seconds; left in, it would halve the share."""

NAME = "sched.driver_prefill_share"
LAYER = "Serving scheduler"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)

PREFIX = "serving.driver_seconds{"


def phases(stats):
    """{labels: seconds} of the engine's per-phase driver counter."""
    return {k[len(PREFIX):-1]: v for k, v in stats.items()
            if k.startswith(PREFIX)}


def read(facts):
    by_phase = phases(facts["stats"])
    total = sum(by_phase.values()) - facts.get("drain_s", 0.0)
    if total <= 0:
        return None
    prefill = (by_phase.get("phase=prefill", 0.0)
               + by_phase.get("of=prefill,phase=fetch", 0.0))
    return 100.0 * prefill / total
