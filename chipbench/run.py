"""One run of one cell.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it refuses unless JAX finds a TPU with the chips the cell
asks for, loads the cell by name (``BENCHMARK.json`` -> configuration
file, traffic file, the per-layer readers), runs the traffic file's
runner, and prints the result as ONE JSON object on the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, when traced, ``breakdown``.  With ``--trace 0`` the
metrics are the cell's end-to-end metrics; with ``--trace 1`` a short
sub-window is profiled and the metrics are the cell's per-layer ones.
Nothing here is edited to take a new cell, mix, configuration or metric:
see ``chipbench/README.md``.
"""

import time

_T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")
if __package__ in (None, ""):  # run as a file: make the package importable
    sys.path.insert(0, ROOT)
    __package__ = "chipbench"


def _read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name, root=ROOT):
    """Everything a run of cell ``name`` needs, found by name."""
    bench = _read_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                         f"(has: {', '.join(sorted(cells))})")
    cell = dict(cells[name])
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cell["config"] = _read_json(root, config["file"])
    cell["traffic"] = _read_json(HERE, "traffic", cell["traffic"] + ".json")

    def applies(metric):
        return name in metric.get("workloads", [name])

    cell["end_to_end"] = [m for m in bench["end_to_end"] if applies(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if applies(m)]
    return cell


def load_reader(metric_name):
    """The module ``layer_metrics/<metric_name>.py``."""
    path = os.path.join(HERE, "layer_metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench.layer_metrics." + metric_name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Tracer:
    """jax.profiler around a sub-window, started and stopped by a runner."""

    def __init__(self, directory):
        self.directory = directory
        self.running = False
        self.done = False
        self.window_s = 0.0
        shutil.rmtree(directory, ignore_errors=True)

    def start(self):
        import jax

        jax.profiler.start_trace(self.directory)
        self.running = True
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        self.window_s = time.perf_counter() - self.t0
        jax.profiler.stop_trace()
        self.running, self.done = False, True


def kernel_labels(cell):
    """{label: needles} from every reader of the cell that names kernels
    (``kernels(config, traffic)``), for the breakdown's device_ops."""
    labels = {}
    for metric in cell["per_layer"]:
        reader = load_reader(metric["name"])
        if hasattr(reader, "kernels"):
            labels.update(reader.kernels(cell["config"], cell["traffic"]))
    return labels


def layer_metrics(cell, facts):
    """{name: value} from each of the cell's per-layer readers; a reader
    that finds nothing to read returns None and is left out."""
    out = {}
    for metric in cell["per_layer"]:
        reader = load_reader(metric["name"])
        if facts["runner"] not in reader.RUNNERS:
            continue
        value = reader.read(facts)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def compared_lines(result):
    """Each number the check compared, beside its limit: the end of
    standard error, which a record of a run that was not correct keeps."""
    return "\n".join(f"chipbench: compared {name} = {value!r}, limit {limit!r}"
                     for name, value, limit in result["compared"])


def result_line(result, metrics, device, breakdown=None):
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chipbench: needs a TPU, JAX found {jax.default_backend()!r}"
              f" (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    # alone with BENCHMARK.json and chipbench/ this raises, before any
    # line is printed; the package also points JAX's persistent compile
    # cache at <checkout>/.jax_cache (core/compile_cache.py)
    import paddle_tpu  # noqa: F401

    from chipbench import device as device_info, flops, trace_reduce

    kind = devices[0].device_kind
    peak = flops.peaks(kind)  # an unknown device is an error, here
    runner = importlib.import_module(
        "chipbench.runners." + cell["traffic"]["runner"])
    tracer = Tracer(os.path.join(TRACE_DIR, args.workload)) \
        if args.trace else None
    result = runner.run(cell, args.seed, args.seconds, tracer)
    facts = result["facts"]
    device = device_info.describe(devices, result["memory_peak_bytes"])

    print(json.dumps({k: v for k, v in facts.items()
                      if k not in ("requests", "stats")
                      and not k.endswith("_seen")}, default=str),
          file=sys.stderr)
    print(compared_lines(result), file=sys.stderr, flush=True)
    if not args.trace:
        values = dict(result["end_to_end"],
                      setup_s=result["window_start"] - _T_PROCESS)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]
                   if values.get(m["name"]) is not None}
        print(result_line(result, metrics, device), flush=True)
        return 0

    summary = trace_reduce.reduce(trace_reduce.load(tracer.directory),
                                  labels=kernel_labels(cell))
    if summary is None:
        print("chipbench: the trace holds no operation on any chip",
              file=sys.stderr)
        return 3
    t0 = tracer.t0 - result["window_start"]
    facts.update(trace=summary, trace_window_s=tracer.window_s, peak=peak,
                 trace_span=(t0, t0 + tracer.window_s),
                 trace_path=trace_reduce.find_xplane(tracer.directory),
                 config=cell["config"], traffic=cell["traffic"],
                 chips=cell["chips"])
    device.update(busy_s=summary["busy_s"], window_s=tracer.window_s)
    breakdown = {"device_ops": summary["device_ops"],
                 "idle_gaps": summary["idle_gaps"]}
    print(result_line(result, layer_metrics(cell, facts), device, breakdown),
          flush=True)
    shutil.rmtree(tracer.directory, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
