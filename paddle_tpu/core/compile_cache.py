"""The one persistent XLA compile cache (README "Running").

Every executable this package builds — ``Executor._aot_compile``, the
serving engine's ``lower().compile()``, plain ``jax.jit`` — goes through
JAX's persistent compilation cache, so a second process on the same
machine loads the 12-layer step instead of compiling it again.

Where it lives is decided from outside: if ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set in code.  Otherwise the
cache is ``<checkout>/.jax_cache``, derived from this file's location —
a fixed path, never a temporary name, a pid or the time, because a
directory that moves between runs never hits.  Every executable is kept,
however quick its compile, so that what a run leaves behind does not
depend on a timing.

What is NOT in the cache's key: the ``op_name`` metadata, so neither
the named scopes a model enters (``observability.trace.KINDS``) nor the
source lines (``jax_compilation_cache_include_metadata_in_key`` is off,
JAX's default, and nothing here turns it on: with it every moved line
would be a cold start).  A tree that only renames or adds scopes loads
what the tree before it compiled, with THAT tree's scopes in its
``as_text()``: ``trace.device_scopes()`` then names what the older tree
named, the ``*.unnamed_busy_share`` metrics read high, and the cure is a
cold cache (another ``JAX_COMPILATION_CACHE_DIR``), not a knob.

A process pinned to the CPU (``JAX_PLATFORMS=cpu``: the tests) gets no
cache from here: XLA:CPU executables are cheap to rebuild, and its AOT
loader logs a machine-feature error on every cache hit.

What compiling costs, whichever entry point did it, is counted from
JAX's own ``jax.monitoring`` events into the global metrics registry:
``compile.cache_hits`` / ``compile.cache_misses`` (executables loaded
from, and written to, the persistent cache), ``compile.trace_seconds``
(jaxpr tracing), ``compile.lower_seconds`` (jaxpr to StableHLO),
``compile.backend_seconds`` (XLA, or the cache lookup and load in its
place) and ``compile.cache_load_seconds`` (the load alone).
"""

import os

import jax
from jax import monitoring

from ..observability import metrics as _metrics

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir():
    """The directory compiled executables persist in."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


_COUNTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}
_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        "compile.lower_seconds",
    "/jax/core/compile/backend_compile_duration": "compile.backend_seconds",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "compile.cache_load_seconds",
}
_listening = False


def _listen():
    """Feed JAX's compile events into the global registry, once."""
    global _listening
    if _listening:
        return
    _listening = True
    reg = _metrics.get_registry()
    for name in (*_COUNTS.values(), *_SECONDS.values()):
        reg.counter(name)  # a run that compiled nothing reads 0, not absent

    def on_event(event, **_):
        name = _COUNTS.get(event)
        if name is not None:
            reg.counter(name).inc()

    def on_duration(event, duration_secs, **_):
        name = _SECONDS.get(event)
        if name is not None:
            reg.counter(name).inc(max(0.0, duration_secs))

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def configure():
    """Point JAX at :func:`cache_dir` unless the environment already
    did or pins the CPU, and count what compiling costs.  Called once,
    at package import; reads the environment only, so no backend is
    initialized."""
    _listen()
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu":
        return
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    # JAX persists only what took a second to compile, so an executable
    # near that line is written by one run and not by the next; persist
    # everything, and a second run adds no entry (chip_smoke.py counts)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def entry_count():
    """Executables currently in the cache (JAX writes one ``*-cache``
    file per entry); 0 when the directory does not exist yet."""
    try:
        return sum(1 for n in os.listdir(cache_dir())
                   if n.endswith("-cache"))
    except FileNotFoundError:
        return 0
