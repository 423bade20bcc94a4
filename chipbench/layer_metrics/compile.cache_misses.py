"""Executables this process compiled and wrote to the persistent compile
cache, whichever entry point compiled them: the ``compile.cache_misses``
counter that ``core/compile_cache.py`` feeds from JAX's own
``/jax/compilation_cache/cache_misses`` event, over the whole process.
0 on a warm cache; the executables' count on the first run.  A program
without the listener gives nothing to read."""

NAME = "compile.cache_misses"
LAYER = "Compile cache"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"
RUNNERS = ("train", "serve")


def read(facts):
    from paddle_tpu.observability import get_registry

    counter = get_registry().get("compile.cache_misses", kind="counter")
    return None if counter is None else counter.value
