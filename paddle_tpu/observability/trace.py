"""Span-based tracing runtime: one span primitive for the host timeline,
the device trace and the counters.

The reference wraps every executor op in a ``platform/profiler``
RecordEvent and aggregates them with ParseEvents; paddle_tpu's PR-1
equivalent (``profiler.timer`` -> ``host_timer.*`` histograms) kept the
aggregation but lost the *timeline* — there was no way to see where a
step or a serving request actually spends its time.  This module is
that timeline:

* **Spans** — nested named intervals with a category and key/value
  attributes (``tracer.span("trainer.dispatch", cat="trainer",
  batch=3)``), thread-safe (per-thread nesting stacks, one locked
  bounded event buffer).  A live span reads ``time.perf_counter`` once
  per edge and hands that one pair (``span.t0``, ``span.t1``) to every
  consumer: the event buffer, a duration histogram, a self-seconds
  counter, and the caller.
* **Profiler annotations** — a live span also enters a
  ``jax.profiler.TraceAnnotation`` of the same name and attributes, so
  inside any profiler session (``jax.profiler.start_trace``,
  ``Trainer.train(trace_dir=)``, ``profiler()``) the program's spans
  lie on the host plane of the ``.xplane.pb``, in the device trace's
  clock, on the thread that did the work.  Outside a session the
  annotation is a flag check.
* **Counters** — ``span(..., registry=reg, histogram="x.seconds")``
  observes the duration under that name in that registry, and
  ``counter=("x.driver_seconds", {"phase": "decode"})`` adds the span's
  SELF seconds (its duration less its child spans' on the same thread)
  to that counter, so a span and its children sum to the span's wall
  time.  Either one is the span's aggregate: it does not fold into
  ``host_timer.`` as well.
* **Instants** — zero-duration markers (``tracer.instant(
  "nan_guard_trip", var="fc_0.w")``) for events like a debug_nans
  abort.
* **Retroactive spans** — ``tracer.add_span(name, t0, t1, lane=...)``
  re-presents an interval from timestamps recorded elsewhere (never a
  profiler annotation); the serving engine lays each finished request's
  span tree (queue -> prefill -> decode chunks) on its own virtual
  timeline lane with it.
* **One aggregation path** — a finished span with no histogram or
  counter of its own observes its duration into the tracer's registry as
  ``host_timer.<name>``, the same namespace ``profiler.timer`` uses, so
  ``print_profiler`` tables, Prometheus exposition and the JSONL run
  log read the same numbers as the timeline.
* **Export** — ``tracer.save(path)`` (or module-level ``trace.save``)
  writes Chrome-trace JSON (``{"traceEvents": [...]}``): complete
  ``ph="X"`` events with ``ts``/``dur`` in microseconds plus
  ``thread_name`` metadata, viewable in ``chrome://tracing``,
  https://ui.perfetto.dev, or ``about:tracing``.

Disabled mode: ``PADDLE_TPU_TRACE=0`` (or ``Tracer(enabled=False)``)
turns the event buffer and the ``host_timer.`` fold-in off.  A span that
feeds nothing else is then one shared reusable null context manager —
no allocation, no lock, no clock read.  A span that names a histogram
or a counter stays live (annotation, clock pair, its metrics): what an
operator's dashboard or a benchmark reader takes from the program does
not depend on the flag.  ``span(..., event=False)`` is the same for one
span: annotation and metrics, no event — for a span that recurs while
nothing happens (the serving driver's idle wait) and would otherwise
push the last burst out of the bounded buffer.

The event buffer is bounded (``PADDLE_TPU_TRACE_EVENTS``, default
100k); when full the oldest events drop and ``tracer.dropped`` counts
them — a flight recorder keeps the most recent window, not the warmup.

**The device half.**  A span inside a jitted program is a
``jax.named_scope``: it costs nothing at run time and reaches the
compiled executable as the ``op_name`` metadata of every instruction
made under it.  The sub-layers of a model are named from ONE vocabulary
(``KINDS``), where the model is written: ``sublayer(kind)`` on the
serving path (``serving/arch.py``, ``serving/batched_decode.py``, under
``STACK_SCOPE``), ``op_scope(op_type, name)`` round every op the
Executor lowers (the reference's per-op ``RecordEvent``; ``OP_KINDS``
and ``NAME_KINDS`` map an op to its kind).  The device trace of this
runtime carries the HLO instruction and not its ``op_name``, so every
compile site hands its executable to ``register_executable(label,
compiled)``: one append, nothing parsed.  ``device_scopes()`` builds,
when someone asks and once per executable, the map from instruction to
``DeviceScope(kind, phase, path, shape, mixed)`` out of
``compiled.as_text()``; ``device_seconds_by_scope(xplane_path)`` joins a
profiler trace to it by (module, instruction) and returns the device's
seconds under the names the program gave them, with what it could not
name.  ``docs/observability.md`` ("The device's seconds by sub-layer")
has the vocabulary and the join's rules.
"""

import bisect
import collections
import json
import os
import re
import threading
import time

import jax
from jax.profiler import TraceAnnotation as _Annotation

from . import metrics as _metrics

__all__ = [
    "Tracer", "get_tracer", "set_tracer", "tracing_enabled",
    "span", "instant", "add_span", "save", "clear",
    "KINDS", "PHASES", "STACK_SCOPE", "RECOMPUTE_SCOPE", "sublayer",
    "op_scope", "kind_of_op", "DeviceScope", "register_executable",
    "device_scopes", "scopes_of_hlo", "join_device_ops",
    "device_seconds_by_scope",
]

# span durations aggregate under the SAME namespace as profiler.timer
TIMER_PREFIX = "host_timer."

class _NullSpan:
    """The disabled-mode span: one shared reusable context manager that
    yields ITSELF with a no-op ``set`` — so call sites written against
    the live-span API (``with tracer.span(...) as s: s.set(k=v)``) keep
    working verbatim when ``PADDLE_TPU_TRACE=0``."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NULL_CTX = _NullSpan()  # shared: the disabled-mode span


def _env_enabled():
    return os.environ.get("PADDLE_TPU_TRACE", "1").lower() not in (
        "0", "", "false", "off", "no")


class _Span:
    """A live span handle (the object ``with tracer.span(...)`` yields).
    ``set(**attrs)`` attaches attributes after entry; after exit ``t0``,
    ``t1`` and ``seconds`` hold the span's one clock pair for whatever
    else the caller feeds from the same interval."""

    __slots__ = ("_tracer", "name", "cat", "args", "_timer", "_registry",
                 "_histogram", "_counter", "_event", "_ann", "_child",
                 "t0", "t1")

    def __init__(self, tracer, name, cat, args, timer=True, registry=None,
                 histogram=None, counter=None, event=True):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._timer = timer
        self._registry = registry
        self._histogram = histogram
        self._counter = counter
        self._event = event

    def set(self, **attrs):
        self.args.update(attrs)
        self._ann.set_metadata(**attrs)
        return self

    @property
    def seconds(self):
        return self.t1 - self.t0

    def __enter__(self):
        self._ann = _Annotation(self.name, **self.args)
        self._ann.__enter__()
        self._child = 0.0
        self._tracer._stack().append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)  # before the bookkeeping: the same interval
        self._tracer._finish(self)
        return False


class Tracer:
    """Thread-safe span recorder with Chrome-trace export.

    enabled     None (default) reads ``PADDLE_TPU_TRACE`` (on unless
                "0"); True/False pins it.
    registry    metrics registry receiving ``host_timer.<name>``
                duration histograms and whatever a span names without a
                registry of its own (default: the global one); None
                disables the fold-in.
    max_events  bounded buffer size (default ``PADDLE_TPU_TRACE_EVENTS``
                or 100000); oldest events drop when full.
    """

    def __init__(self, enabled=None, registry=0, max_events=None):
        self.enabled = _env_enabled() if enabled is None else bool(enabled)
        # sentinel 0 = "the global registry", None = "no fold-in"
        self._registry = (_metrics.get_registry() if registry == 0
                          else registry)
        if max_events is None:
            max_events = int(os.environ.get(
                "PADDLE_TPU_TRACE_EVENTS", "100000"))
        self._max_events = max(1, int(max_events))
        self._lock = threading.Lock()
        self._events = []
        self.dropped = 0
        self._t0 = time.perf_counter()  # export epoch: ts are relative
        self._pid = os.getpid()
        self._tids = {}       # lane label -> virtual tid
        self._tid_names = {}  # tid -> display name
        self._next_tid = 1
        # per-thread-OBJECT tid cache: threading.get_ident() values are
        # reused once a thread exits, which would merge a later thread
        # onto a dead thread's timeline lane under its stale name
        self._tls = threading.local()

    # -- recording --------------------------------------------------------
    def _tid(self):
        tid = getattr(self._tls, "tid", None)
        if tid is None:
            with self._lock:
                tid = self._next_tid
                self._next_tid += 1
                self._tid_names[tid] = threading.current_thread().name
            self._tls.tid = tid
        return tid

    def lane(self, label):
        """A virtual timeline lane (Chrome tid) for events that don't
        belong to a host thread — e.g. one lane per serving request."""
        tid = self._tids.get(label)
        if tid is None:
            with self._lock:
                tid = self._tids.get(label)
                if tid is None:
                    tid = 10000 + len(self._tids)
                    self._tids[label] = tid
                    self._tid_names[tid] = str(label)
        return tid

    def _push(self, ev):
        with self._lock:
            if len(self._events) >= self._max_events:
                # drop the oldest half in one slice (amortized O(1)
                # per event) — a flight recorder keeps the recent window
                drop = self._max_events // 2 or 1
                del self._events[:drop]
                self.dropped += drop
            self._events.append(ev)

    def _record(self, name, cat, t0, t1, args, tid=None, timer=True):
        # nesting needs no explicit parent links: Chrome/Perfetto derive
        # it from ts/dur containment within a tid
        self._push({
            "ph": "X", "name": name, "cat": cat,
            "ts": (t0 - self._t0) * 1e6, "dur": (t1 - t0) * 1e6,
            "pid": self._pid, "tid": tid if tid is not None else self._tid(),
            "args": dict(args) if args else {},
        })
        if timer and self._registry is not None:
            self._registry.histogram(TIMER_PREFIX + name).observe(t1 - t0)

    def _stack(self):
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _finish(self, sp):
        """Hand a closed span's one clock pair to each of its consumers."""
        stack = self._tls.stack
        stack.remove(sp)  # the top, unless spans were closed out of order
        seconds = sp.t1 - sp.t0
        if stack:
            stack[-1]._child += seconds
        reg = sp._registry
        if reg is None:
            reg = (self._registry if self._registry is not None
                   else _metrics.get_registry())
        if sp._histogram is not None:
            reg.histogram(sp._histogram).observe(seconds)
        if sp._counter is not None:
            name, labels = sp._counter
            reg.counter(name, **labels).inc(max(0.0, seconds - sp._child))
        if self.enabled and sp._event:
            # a span that names a histogram or a counter has its
            # aggregate: no host_timer duplicate of the same seconds
            self._record(sp.name, sp.cat, sp.t0, sp.t1, sp.args,
                         timer=(sp._timer and sp._histogram is None
                                and sp._counter is None))

    # -- public API -------------------------------------------------------
    def span(self, name, cat="host", timer=True, registry=None,
             histogram=None, counter=None, event=True, **attrs):
        """Context manager recording a nested interval, also a
        ``jax.profiler.TraceAnnotation`` of the same name and attributes.

        ``histogram`` names the histogram that observes the duration and
        ``counter`` a ``(name, labels)`` counter that gains the span's
        self seconds, both in ``registry`` (default: the tracer's).
        ``event=False`` keeps the span out of the event buffer
        (annotation and metrics only): for one that recurs while
        nothing happens, which would wrap the bounded buffer.  A
        span that names neither folds into ``host_timer.<name>`` while
        the event buffer is on; ``timer=False`` keeps it timeline-only
        — for spans that RE-present an interval other spans or timers
        already observe (e.g. a parent whose children cover the same
        window), which would otherwise multi-count the same wall
        seconds in the aggregate view.  Disabled mode returns one shared
        null context (no allocation, no clock read) unless the span
        names a histogram or a counter."""
        if not ((self.enabled and event) or histogram or counter):
            return _NULL_CTX
        return _Span(self, name, cat, attrs, timer=timer, registry=registry,
                     histogram=histogram, counter=counter, event=event)

    def instant(self, name, cat="host", **attrs):
        """Zero-duration marker (Chrome ``ph="i"``), e.g. a nan trip."""
        if not self.enabled:
            return
        self._push({
            "ph": "i", "name": name, "cat": cat, "s": "t",
            "ts": (time.perf_counter() - self._t0) * 1e6,
            "pid": self._pid, "tid": self._tid(),
            "args": dict(attrs) if attrs else {},
        })

    def add_span(self, name, t0, t1, cat="host", lane=None, timer=True,
                 **attrs):
        """Re-present an interval from ``time.perf_counter`` timestamps
        captured elsewhere (a live span's ``t0``/``t1``): event buffer
        only, never a profiler annotation.  ``lane`` places it on a virtual
        timeline (see :meth:`lane`) instead of the calling thread.
        ``timer=False`` skips the ``host_timer.`` fold-in — for spans
        that RE-present an interval some other span or histogram
        already observed (e.g. a request's lane re-emitting the decode
        chunks it was live for), which would otherwise multi-count the
        same wall time in the aggregate view."""
        if not self.enabled:
            return
        tid = self.lane(lane) if lane is not None else None
        self._record(name, cat, t0, t1, attrs, tid=tid, timer=timer)

    def events(self, name=None, cat=None):
        """Snapshot of recorded events (dicts), optionally filtered."""
        with self._lock:
            evs = list(self._events)
        if name is not None:
            evs = [e for e in evs if e["name"] == name]
        if cat is not None:
            evs = [e for e in evs if e.get("cat") == cat]
        return evs

    def clear(self):
        with self._lock:
            self._events = []
            self.dropped = 0

    # -- export -----------------------------------------------------------
    def to_chrome_trace(self):
        """The Chrome-trace object: metadata + events sorted by ts."""
        with self._lock:
            evs = sorted(self._events, key=lambda e: e["ts"])
            names = dict(self._tid_names)
        meta = [{"ph": "M", "name": "process_name", "pid": self._pid,
                 "tid": 0, "args": {"name": "paddle_tpu"}}]
        for tid, label in sorted(names.items()):
            meta.append({"ph": "M", "name": "thread_name",
                         "pid": self._pid, "tid": tid,
                         "args": {"name": label}})
        obj = {"traceEvents": meta + evs, "displayTimeUnit": "ms"}
        scopes = device_scopes()
        if scopes:
            # the map from HLO instruction to named scope of what this
            # process compiled, so that a saved timeline can be joined
            # to a device trace later (join_device_ops reads it back)
            obj["metadata"] = {"device_scopes": scopes}
        return obj

    def save(self, path):
        """Write Chrome-trace JSON; returns the event count (metadata
        records excluded)."""
        obj = self.to_chrome_trace()
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(obj, fh)
        return sum(1 for e in obj["traceEvents"] if e["ph"] != "M")


_global_tracer = None
_global_lock = threading.Lock()


def get_tracer():
    """The process-global tracer (created on first use; enabled unless
    ``PADDLE_TPU_TRACE=0``)."""
    global _global_tracer
    if _global_tracer is None:
        with _global_lock:
            if _global_tracer is None:
                _global_tracer = Tracer()
    return _global_tracer


def set_tracer(tracer):
    """Swap the process-global tracer; returns the previous one (tests
    install a private tracer and restore the old on exit)."""
    global _global_tracer
    with _global_lock:
        prev, _global_tracer = _global_tracer, tracer
    return prev


def tracing_enabled():
    return get_tracer().enabled


# module-level conveniences over the global tracer ----------------------
def span(name, **kwargs):
    return get_tracer().span(name, **kwargs)


def instant(name, cat="host", **attrs):
    return get_tracer().instant(name, cat=cat, **attrs)


def add_span(name, t0, t1, cat="host", lane=None, timer=True, **attrs):
    return get_tracer().add_span(name, t0, t1, cat=cat, lane=lane,
                                 timer=timer, **attrs)


def save(path):
    return get_tracer().save(path)


def clear():
    return get_tracer().clear()


# -- the device half: named scopes, and the map from instruction to scope --
# the jax.named_scope every architecture's stack runs under on the
# serving path (stack vs. embedding, head and argmax)
STACK_SCOPE = "serving.stack_pass"
# the scope the Executor's own checkpoint equivalent re-traces a segment
# under inside its backward, where JAX writes no remat marker
RECOMPUTE_SCOPE = "recompute"
# the sub-layer kinds, the ONE vocabulary both paths name their work from
KINDS = (
    "embed",        # token and position tables
    "norm",         # LayerNorm / RMSNorm round a sub-layer
    "attn.proj",    # q/k/v/out projections, attention gates, rotary
    "attn.core",    # the paged or dense attention call and what the
                    # architecture does to its output (differential
                    # lambda arithmetic, sub-layer norm, head gate)
    "mixer",        # Mamba, gated memory unit: projections, convolution,
                    # recurrence
    "ffn",          # dense FFN / gated MLP
    "moe.route",    # router scores, top-k, sort and gather
    "moe.experts",  # the grouped products and the weighted scatter back
    "moe.shared",   # the shared expert
    "head",         # final norm, logits, argmax or the fused CE
    "cache",        # K/V writes into the pool, table gathers, state rows
    "optimizer",    # the update ops
)
PHASES = ("forward", "backward", "recompute")
_KIND_SET = frozenset(KINDS)


def sublayer(kind):
    """``jax.named_scope(kind)`` for a kind of the vocabulary: what a
    model enters at a sub-layer boundary.  Metadata only: nothing is
    read at run time and the compiled program is the same."""
    if kind not in _KIND_SET:
        raise ValueError(f"{kind!r} is not a sub-layer kind: {KINDS}")
    return jax.named_scope(kind)


# (op type -> kind) and (marker in the layer's name -> kind) for the
# Program path.  A name decides before a type where it says more (the
# final LayerNorm and the head's matmul are the head's), else the type,
# else the markers models/transformer.py gives its layers.
OP_KINDS = {
    "layer_norm": "norm", "lookup_table": "embed",
    "flash_attention": "attn.core", "flash_attention_packed": "attn.core",
    "fused_softmax_ce_head": "head", "softmax_with_cross_entropy": "head",
    **dict.fromkeys((
        "sgd", "momentum", "adagrad", "adam", "adamax", "adadelta",
        "decayed_adagrad", "rmsprop", "ftrl", "proximal_gd",
        "proximal_adagrad"), "optimizer"),
}
NAME_KINDS = {"ln_f": "head", "lm_head": "head"}
NAME_MARKERS = (("_att_", "attn.proj"), ("_ffn", "ffn"),
                ("tok_emb", "embed"), ("pos_emb", "embed"))


def kind_of_op(op_type, layer):
    """The kind of one Program op, from its type and the name the model
    gave its layer (``block3_ffn1``, ``ln_f``, ``lm_head``); None for
    an op outside every kind (a reshape, a residual add)."""
    kind = NAME_KINDS.get(layer) or OP_KINDS.get(op_type)
    return kind or next(
        (k for marker, k in NAME_MARKERS if marker in layer), None)


def op_scope(op_type, out_name):
    """The named scope one Program op lowers under: its kind (where it
    has one), then ``<op type>:<layer>``, the layer being the op's first
    output less its ``.tmp_N`` (``mul:block3_ffn1``, ``adam:lm_head.w``).
    Backward and recompute need none of their own: JAX wraps the scope
    in ``transpose(jvp(...))`` and puts ``rematted_computation`` beside
    it."""
    layer = out_name.split(".tmp_")[0]
    kind = kind_of_op(op_type, layer)
    label = f"{op_type}:{layer}"
    return jax.named_scope(f"{kind}/{label}" if kind else label)


DeviceScope = collections.namedtuple(
    "DeviceScope", "kind phase path shape mixed")
DeviceScope.__doc__ = """What the program said of one HLO instruction:
``kind`` (of ``KINDS``; None for a named scope outside every kind),
``phase`` (of ``PHASES``; None where the compiler wrote no ``op_name``:
its own copies and async starts), ``path`` (the ``op_name`` less the
primitive), ``shape`` (the result's, layouts dropped: tells two
executables of one module name apart) and ``mixed`` (the OTHER kinds a
fusion's body spans)."""

# the executables compiled in this process, newest last: (label,
# compiled, parsed map or None).  Bounded like the event buffer: a
# flight recorder keeps the recent ones, and a process that compiles
# thousands (the tests) keeps no more than these alive.
MAX_EXECUTABLES = 64
_executables = collections.deque(maxlen=MAX_EXECUTABLES)

_WRAPPER_RE = re.compile(r"[\w.\-]+\(|\)")
_LAYOUT_RE = re.compile(r"\{[^{}]*\}")
# ``%name`` references of an instruction's operands, once the
# computations it calls are taken out of its text
_REF_RE = re.compile(r"%([\w.\-]+)")
_CALLED_RE = re.compile(
    r"\b(?:calls|body|condition|to_apply)=%?[\w.\-]+"
    r"|branch_computations=\{[^}]*\}")
# an ``XLA Ops`` event's name: ``%name = <shape> opcode(``
_TRACE_OP_RE = re.compile(r"^%?([\w.\-]+) = (.*?) [\w\-]+\(")
_HOLDERS = frozenset(("while", "conditional", "call"))
# what does a fusion's work: a matrix unit or a kernel, then a
# reduction or data movement, then elementwise; what computes nothing
_OPCODE_RANK = {
    **dict.fromkeys(("dot", "convolution", "custom-call"), 3),
    **dict.fromkeys(("reduce", "reduce-window", "scatter", "gather",
                     "sort", "select-and-scatter", "dynamic-slice",
                     "dynamic-update-slice"), 2),
    **dict.fromkeys(("parameter", "constant", "tuple", "get-tuple-element",
                     "bitcast"), 0),
}


def register_executable(label, compiled):
    """Keep ``compiled`` (a ``jax.stages.Compiled``) for
    ``device_scopes``: a reference to the compiled object and nothing
    else (no argument, pool, weight or engine), one append a compile.
    Nothing is read of it until someone asks."""
    _executables.append([str(label), compiled, None])


def _classify(op_name):
    """``(kind, phase, path)`` of an ``op_name``."""
    if not op_name:
        return None, None, ""
    raw = op_name.split("/")
    parts = [_WRAPPER_RE.sub("", c) for c in raw]
    kind = next((c for c in reversed(parts) if c in _KIND_SET), None)
    if RECOMPUTE_SCOPE in parts or "rematted_computation" in parts:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return kind, phase, "/".join(raw[:-1])


def scopes_of_hlo(text):
    """``(module name, {instruction name: DeviceScope})`` of optimized
    HLO ``text``: every instruction the device trace can show (those of
    the entry, loop bodies, branches and called computations; not the
    insides of a fusion or of a reducer).

    A fusion takes the scope of the instruction inside it that does the
    work: a ``dot``, ``convolution`` or ``custom-call`` before a
    reduction or a gather/scatter before elementwise, the root on a tie,
    the first that has a kind; ``mixed`` lists the other kinds its body
    spans.  ``while``, ``conditional`` and ``call`` hold others and have
    no kind of their own: their self seconds are the loop's own
    bookkeeping.  A Mosaic custom call keeps its kernel's name as the
    instruction's and takes the scope it was called under.  An
    instruction the COMPILER made and gave no ``op_name`` (a layout
    copy, an async start and its done, a dot it rewrote) takes the scope
    of the first instruction of its computation that uses its result,
    else of the first whose result it uses, else of the ``while`` or
    ``call`` that holds its computation, and says so at the end of its
    ``path`` (``<- %user``); one with none of these stays unnamed.  The
    walk is ``analysis.hlo_tools.iter_instructions``, the comm plan's."""
    from ..analysis.hlo_tools import called_computations, iter_instructions

    m = re.match(r"HloModule\s+([\w.\-]+)", text)
    module = m.group(1) if m else ""
    comps, inner, holder = {}, set(), {}
    for ins in iter_instructions(text):
        comps.setdefault(ins.comp, []).append(ins)
        if ins.opcode == "fusion" or "to_apply=" in ins.head:
            inner.update(called_computations(ins.head))
        elif ins.opcode in _HOLDERS:
            holder.update(dict.fromkeys(called_computations(ins.head), ins))
    out = {}
    for comp, instrs in comps.items():
        if comp in inner:
            continue
        for ins in instrs:
            if not _OPCODE_RANK.get(ins.opcode, 1):
                continue    # computes nothing: never an event of a trace
            kind, phase, path = _classify(ins.op_name)
            mixed = ()
            if ins.opcode in _HOLDERS:
                kind = None
            elif ins.opcode == "fusion":
                body = [b for c in called_computations(ins.head)
                        for b in comps.get(c, ())]
                ranked = sorted(
                    ((_OPCODE_RANK.get(b.opcode, 1), b.root,
                      _classify(b.op_name)) for b in body),
                    key=lambda r: (-r[0], not r[1]))
                named = [r[2] for r in ranked if r[0] and r[2][0]]
                if named:
                    kind, phase, path = named[0]
                    mixed = tuple(sorted({n[0] for n in named} - {kind}))
                elif not ins.op_name:
                    # the fusion's own line says nothing: any name at all
                    kind, phase, path = next(
                        (r[2] for r in ranked if r[2][1]), (None, None, ""))
            out[ins.name] = DeviceScope(
                kind, phase, path, _LAYOUT_RE.sub("", ins.shape), mixed)
        _adopt_unnamed(instrs, out)
    for comp, ins in holder.items():
        kind, phase, path = _classify(ins.op_name)
        if phase is not None:
            for i in comps.get(comp, ()):
                if i.name in out and out[i.name].phase is None:
                    out[i.name] = out[i.name]._replace(
                        kind=kind, phase=phase, path=f"{path} <- %{ins.name}")
    return module, out


def _adopt_unnamed(instrs, out):
    """Give the instructions of one computation that have no ``op_name``
    the scope of a neighbour (``scopes_of_hlo``): users first, then
    operands, through chains of unnamed ones (a start, its done, the
    fusion that reads it)."""
    operands = {i.name: [r for r in _REF_RE.findall(
        _CALLED_RE.sub("", i.head.split(" = ", 1)[-1]))
        if r != i.name] for i in instrs if i.name in out}
    users = {}
    for name, refs in operands.items():
        for r in refs:
            users.setdefault(r, []).append(name)
    unnamed = [n for n in operands if out[n].phase is None]
    for _ in range(4):
        left = []
        for n in unnamed:
            near = next((m for m in users.get(n, []) + operands[n]
                         if m in out and out[m].phase is not None), None)
            if near is None:
                left.append(n)
                continue
            src = out[near]
            out[n] = out[n]._replace(
                kind=src.kind, phase=src.phase,
                path=f"{src.path.split(' <- ')[0]} <- %{near}")
        if len(left) == len(unnamed):
            break
        unnamed = left


def device_scopes():
    """``[{"label", "module", "instructions": {name: DeviceScope}}]`` for
    the executables registered in this process, oldest first.  LAZY and
    cached: ``compiled.as_text()`` is read and parsed on the first call
    after a compile, once per executable; compiling and running steps
    build nothing."""
    out = []
    for entry in list(_executables):
        if entry[2] is None:
            module, instructions = scopes_of_hlo(entry[1].as_text() or "")
            entry[2] = {"label": entry[0], "module": module,
                        "instructions": instructions}
        out.append(entry[2])
    return out


def join_device_ops(chips, scopes):
    """Join device events to a scope map.

    ``chips`` is ``[(modules, ops)]``, a pair per chip: ``modules``
    ``[(start, end, name)]`` from the trace's ``XLA Modules`` line (the
    name ends in ``(<fingerprint>)``), ``ops`` ``[(start, end, text)]``
    from its ``XLA Ops`` line, ``text`` the whole HLO instruction (``%name
    = shape opcode(...)``); times in any one unit.  ``scopes`` is what
    ``device_scopes()`` returns (or that, read back from JSON).

    An op belongs to the module event that contains its start; the
    executable is the one of that module name (where several share a
    name, as the prefill widths do, the one whose instructions agree
    with most of the event's by name AND shape).  Seconds are SELF
    seconds: an op's duration less the ops it holds, so a loop is not
    counted with its body.  Returns ``{"seconds": {(module, kind,
    phase): t}, "unnamed": {(module, instruction): t}, "ops": {(module,
    instruction): (t, kind, phase, path)}, "total": t}`` in
    the unit given, averaged over the chips: ``kind`` None is work under
    a named scope outside every kind, ``unnamed`` what has no scope or no
    match in the map (the instrument's blind share); everything sums to
    ``total``, the chips' busy time.  An executable in whose map NO
    instruction has a kind was loaded from a compile cache that a tree
    without the vocabulary wrote (scope names are not in the cache's
    key): all its seconds are unnamed."""
    by_module = {}
    for entry in scopes:
        table = entry["instructions"]
        if any(s[0] for s in table.values()):
            by_module.setdefault(entry["module"], []).append(table)
    seconds = collections.Counter()
    unnamed = collections.Counter()
    named = {}
    n = max(1, len(chips))
    for modules, ops in chips:
        modules = sorted(modules)
        starts = [m[0] for m in modules]
        # self time, as chipbench/trace_reduce.py takes it
        rows, stack = [], []
        for start, end, text in sorted(ops, key=lambda e: (e[0], -e[1])):
            row = [text, end - start, start]
            while stack and stack[-1][0] <= start:
                stack.pop()
            if stack:
                stack[-1][1][1] -= end - start
            stack.append((end, row))
            rows.append(row)
        per_event = {}
        for text, self_t, start in rows:
            i = bisect.bisect_right(starts, start) - 1
            inside = i >= 0 and start < modules[i][1]
            m = _TRACE_OP_RE.match(text)
            name, shape = ((m.group(1), _LAYOUT_RE.sub("", m.group(2)))
                           if m else (text[:80], ""))
            per_event.setdefault(modules[i][2] if inside else "", []).append(
                (name, shape, self_t))
        for event, items in per_event.items():
            module = event.split("(")[0]
            candidates = by_module.get(module, ())
            table = max(candidates, default={}, key=lambda c: sum(
                1 for name, shape, _ in items
                if name in c and c[name][3] == shape))
            for name, _shape, self_t in items:
                scope = table.get(name)
                if scope is None or scope[1] is None:
                    unnamed[(module, name)] += self_t / n
                else:
                    seconds[(module, scope[0],
                             scope[1])] += self_t / n
                    was = named.get((module, name), (0.0,))[0]
                    named[(module, name)] = (was + self_t / n, *scope[:3])
    return {"seconds": dict(seconds), "unnamed": dict(unnamed),
            "ops": named,
            "total": sum(seconds.values()) + sum(unnamed.values())}


def device_seconds_by_scope(xplane_path, scopes=None):
    """The device's seconds in a profiler trace (``.xplane.pb``, from a
    ``jax.profiler`` session round a live engine or training loop) under
    the names the program gave its sub-layers: ``join_device_ops`` over
    every TPU plane of the trace and ``scopes`` (default: this
    process's ``device_scopes()``), in seconds."""
    from jax.profiler import ProfileData

    chips = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        ops = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                e.name) for e in lines["XLA Ops"].events]
        modules = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                    e.name) for e in
                   (lines["XLA Modules"].events
                    if "XLA Modules" in lines else ())]
        if ops:
            chips.append((modules, ops))
    return join_device_ops(
        chips, device_scopes() if scopes is None else scopes)
