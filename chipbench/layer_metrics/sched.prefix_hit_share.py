"""Share of the window's prompt tokens served from the prefix cache:
the engine's ``serving.prefix_hit_rate`` gauge, whose accounting the
runner re-opens after the warm pass."""

NAME = "sched.prefix_hit_share"
LAYER = "Serving scheduler"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def read(facts):
    rate = facts["stats"].get("serving.prefix_hit_rate")
    return None if rate is None else 100.0 * rate
