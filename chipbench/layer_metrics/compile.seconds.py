"""Seconds the run's executables spent in ``lower().compile()`` (trace
through the op interpreter, lowering, XLA or a cache load), summed over
the executables as the program reports them."""

NAME = "compile.seconds"
LAYER = "Compile cache"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"
RUNNERS = ("train", "serve")


def read(facts):
    seconds = facts.get("compile_seconds") or {}
    return sum(seconds.values()) if seconds else None
