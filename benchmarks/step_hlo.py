"""A training cell's step as the chip's compiler leaves it, read WITHOUT
a chip (the recipe of PERF.md section 3 as a script; nothing a cell runs).

    JAX_PLATFORMS=cpu python3 benchmarks/step_hlo.py \
        [--workload cgpt590m.train_2k] [--out /root/scratch/step.hlo.txt]

Builds the cell's Program as ``chipbench/runners/train.py::_build`` does,
runs its startup on the CPU, takes the jitted step and its arguments
where ``Executor._aot_compile`` receives them, lowers it for one device
of a described ``v5e:2x2`` with ``jax.default_backend`` answering
``"tpu"`` (the kernels' own ``interpret = backend != "tpu"``) and writes
the optimized HLO.  The instruction names are the chip's to the number,
so a fusion of a traced run's ``breakdown`` can be opened there.

Prints, for the forward and the backward scan body (the computations
that hold ``flash_fwd`` / ``flash_bwd_fused``), the WIDE instructions
(an output that is a whole activation of a layer-micro-batch, or a stack
of them over the layers) with their ``op_name``, the sum of the
compiler's ``estimated_cycles`` over the body's instructions that carry
one, and ``memory_analysis().temp_size_in_bytes``.  Nothing runs: what a
pass COSTS is a traced run's to say, and the estimate over-reads memory
passes (take its sign and its list, not its size).  The startup holds
the cell's weights and optimizer state in this machine's memory (7 GB
for ``cgpt590m``); the compile takes 40-120 s on eight cores.
"""

import argparse
import collections
import math
import os
import re
import sys
import time
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
_DIMS = re.compile(r"\w+\[([0-9,]*)\]")
_LAYOUT = re.compile(r"\{[^}]*\}")


class _Caught(Exception):
    pass


def catch_step(workload):
    """(jitted step, its arguments, the cell, the executor) of
    ``workload``, caught where ``Executor._aot_compile`` receives them."""
    import numpy as np

    import paddle_tpu as pt
    from chipbench import families
    from chipbench import run as bench_run
    from chipbench.runners import train

    cell = bench_run.load_cell(workload)
    cfg, mix = cell["config"], cell["traffic"]
    if mix["runner"] != "train" or mix.get("mesh"):
        raise SystemExit(f"{workload}: a one-chip training cell is needed")
    main, startup, avg_cost = train._build(
        pt, families.of(cfg, "train"), cfg, mix, None)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)
    shape = (mix["sequences_per_step"], mix["seq_len"])
    feed = {"tokens": np.zeros(shape, np.int64),
            "labels": np.zeros(shape, np.int64)}
    caught = []

    def receive(jitted, args, *_a, **_k):
        caught.append((jitted, args))
        raise _Caught

    with mock.patch.object(exe, "_aot_compile", receive):
        try:
            exe.compile_only(main, feed=feed, fetch_list=[avg_cost],
                             scope=scope)
        except _Caught:
            pass
    (jitted, args), = caught
    return jitted, args, cell, exe


def compile_for_v5e(jitted, args):
    """The step compiled for one chip of a described ``v5e:2x2``."""
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = jitted.lower(*shapes)
    return lowered.compile()


def scan_bodies(hlo):
    """{"forward" | "backward": the computation that holds the flash
    kernel's call of that pass}."""
    from paddle_tpu.analysis.hlo_tools import iter_instructions

    bodies = {}
    for i in iter_instructions(hlo):
        if i.opcode != "custom-call":
            continue
        for which, needle in (("forward", "flash_fwd"),
                              ("backward", "flash_bwd_fused")):
            if needle in i.head or needle in i.op_name:
                bodies.setdefault(which, i.comp)
    return bodies


def wide_instructions(hlo, comp, row_elements):
    """The instructions of ``comp`` with an output of at least
    ``row_elements`` elements (every member of a tuple counts), and the
    sum of ``estimated_cycles`` over all of the computation's."""
    from paddle_tpu.analysis.hlo_tools import iter_instructions

    lines = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        if m:
            lines.setdefault(m.group(1), line)
    wide, cycles = [], 0
    for i in iter_instructions(hlo):
        if i.comp != comp:
            continue
        found = _CYCLES.search(lines.get(i.name, ""))
        estimate = int(found.group(1)) if found else None
        cycles += estimate or 0
        # an async start's tuple names its operand beside its result
        if i.opcode in ("parameter", "get-tuple-element", "tuple", "bitcast",
                        "constant", "while") or i.opcode.endswith(
                            ("-start", "-done")):
            continue
        sizes = [math.prod(int(n) for n in d.split(",") if n)
                 for d in _DIMS.findall(i.shape)]
        if sizes and max(sizes) >= row_elements:
            wide.append((i, estimate))
    return wide, cycles


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="cgpt590m.train_2k")
    ap.add_argument("--out", default=None,
                    help="where to write the optimized HLO text")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    jitted, step_args, cell, exe = catch_step(args.workload)
    t1 = time.perf_counter()
    compiled = compile_for_v5e(jitted, step_args)
    t2 = time.perf_counter()
    hlo = compiled.as_text()
    if args.out:
        with open(args.out, "w") as f:
            f.write(hlo)
    print(f"{args.workload}: built and caught in {t1 - t0:.1f} s, compiled "
          f"for v5e:2x2 in {t2 - t1:.1f} s"
          + (f", HLO in {args.out}" if args.out else ""))
    memory = compiled.memory_analysis()
    print(f"temp_size_in_bytes {memory.temp_size_in_bytes:,}  "
          f"argument_size_in_bytes {memory.argument_size_in_bytes:,}")

    for group in exe.last_remat_plan:
        print(f"scan group of {group['count']} x {group['period']} segments: "
              f"{len(group.get('over_rows', ()))} products over the rows as "
              f"they stand, {len(group['reading'])} reading")

    cfg, mix = cell["config"], cell["traffic"]
    rows = (mix["sequences_per_step"] // mix["micro_steps"]) * mix["seq_len"]
    row_elements = rows * cfg["n_embd"]
    for which, comp in sorted(scan_bodies(hlo).items()):
        wide, cycles = wide_instructions(hlo, comp, row_elements)
        print(f"\n{which} scan body %{comp}: {len(wide)} wide instructions, "
              f"estimated_cycles {cycles:,}")
        opcodes = collections.Counter()
        for i, estimate in wide:
            opcodes[re.sub(r"[.\d]+$", "", i.name)] += 1
            print(f"  {i.name:<44} {_LAYOUT.sub('', i.shape):<60} "
                  f"{estimate if estimate is not None else '-':>9} "
                  f"{i.op_name[-100:]}")
        print("  by name: " + ", ".join(
            f"{n} x{c}" for n, c in sorted(opcodes.items())))


if __name__ == "__main__":
    main()
