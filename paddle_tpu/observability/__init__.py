"""Run-telemetry subsystem — the instrument panel for the whole stack.

The reference ships a real observability layer (platform/profiler
RecordEvent + aggregated tables, utils/Stat.h REGISTER_TIMER,
FLAGS_check_nan_inf); on TPU the op loop is compiled away, so the
equivalents are structural: a metrics registry every subsystem reports
into, compile/step tracing at the Executor, MFU/throughput accounting at
the Trainer, and device-memory high-water sampling.

Modules:

* ``metrics``  — Counter/Gauge/Histogram + the global `MetricsRegistry`
  (Prometheus text exposition, optional HTTP endpoint);
* ``runlog``   — `RunLog` JSONL structured event log + ``read_jsonl``,
  and `run_stamp` (schema_version / run_id / git sha) a run's records
  carry;
* ``hardware`` — chip peak-FLOPs table, `mfu`, `device_memory_stats`,
  `sample_memory` HBM high-water gauges;
* ``reporter`` — `MetricsReporter`, the Trainer event handler emitting
  one-line summaries + JSONL step records;
* ``trace``    — span-based tracing runtime (`Tracer`: nested spans,
  instants, per-request lanes) with Chrome-trace/Perfetto export; span
  durations fold into the ``host_timer.`` histogram namespace;
* ``attribution`` — per-op-class performance attribution over every
  compiled step's HLO (flops/bytes/roofline ms per class,
  ``exe.last_attribution``; the learned-cost-model corpus);
* ``corpus`` — the cross-run measurement store: trainer JSONL and
  tune-cache measured candidates read back into one row shape the
  learned cost model (``tune/costmodel.py``) fits on — malformed rows
  classified, never crashed;
* ``flight`` — the crash flight recorder: a bounded ring of recent
  step records dumped as one post-mortem JSON bundle on watchdog /
  NaN / OOM / driver-death / trainer-exception trips.

Quick start::

    import paddle_tpu as pt
    from paddle_tpu.observability import MetricsReporter, get_registry

    reporter = MetricsReporter(log_every_n=10, jsonl_path="run.jsonl")
    trainer.train(reader, event_handler=reporter)
    print(get_registry().to_text())   # or start_metrics_server(9464)
"""

from . import (
    attribution, corpus, flight, hardware, metrics, reporter, runlog,
    trace,
)
from .corpus import Corpus
from .flight import FlightRecorder, get_recorder, set_recorder
from .hardware import (
    device_memory_stats, device_peak_flops, mfu, sample_memory,
    total_peak_flops,
)
from .metrics import (
    Counter, Gauge, Histogram, MetricsRegistry, get_registry,
    start_metrics_server,
)
from .reporter import MetricsReporter
from .runlog import RunLog, read_jsonl, run_stamp
from .trace import Tracer, get_tracer, set_tracer

__all__ = [
    "metrics", "runlog", "hardware", "reporter", "trace",
    "attribution", "flight", "corpus", "Corpus",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "start_metrics_server", "RunLog", "read_jsonl", "MetricsReporter",
    "device_peak_flops", "total_peak_flops", "mfu",
    "device_memory_stats", "sample_memory",
    "Tracer", "get_tracer", "set_tracer", "run_stamp",
    "FlightRecorder", "get_recorder", "set_recorder",
]
