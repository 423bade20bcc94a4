"""CTR-DNN through the pserver path — the BASELINE config-5 perf story.

The reference's pserver generation was built for this workload (sparse
CTR models over big embedding tables, ``benchmark/cluster/ctr``); this
measures OUR path end to end in loopback: CTR-DNN with sparse embedding
slots, block-sharded in-process parameter servers, prefetch +
send_sparse_grad for the tables, blockwise dense send + conditional
delta fetch for the tower, serial vs pipelined updater, 1 vs 4 servers.

Loopback (in-process) servers measure the framework machinery — block
routing, per-row server-side optimizers, fan-out pools, pipelining —
without a real DCN in the middle; bytes/step is reported so the DCN
cost model is explicit: step_time(dcn) ~ max(compute, bytes/bandwidth
+ latency) with the pipelined updater, sum without it.

Usage: JAX_PLATFORMS=cpu python benchmarks/ctr_pserver.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def run_config(n_servers, mode, steps=30, vocab=100_000, emb=16,
               slots=4, batch=256, ids_per_slot=1, rpc_delay_ms=0.0):
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.distributed.pserver import ParameterServer
    from paddle_tpu.distributed.transpiler import (
        DistributeTranspiler, DistributedTrainer)
    from paddle_tpu.models import ctr_dnn

    class DelayedServer(ParameterServer):
        """Each RPC pays a simulated DCN latency; the client's per-server
        connections serialize calls, so with one server the block calls
        queue and with four they fan out — the scaling the real network
        path exhibits."""

        def _nap(self):
            time.sleep(rpc_delay_ms / 1e3)

        def send_grad(self, *a, **k):
            self._nap()
            return super().send_grad(*a, **k)

        def get_param_if_newer(self, *a, **k):
            self._nap()
            return super().get_param_if_newer(*a, **k)

        def get_param_rows(self, *a, **k):
            self._nap()
            return super().get_param_rows(*a, **k)

        def send_sparse_grad(self, *a, **k):
            self._nap()
            return super().send_sparse_grad(*a, **k)

    server_cls = DelayedServer if rpc_delay_ms else ParameterServer

    pt.core.unique_name.reset()
    main, startup = pt.Program(), pt.Program()
    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    try:
        with pt.program_guard(main, startup):
            outs = ctr_dnn.build(sparse_feature_dim=vocab, num_slots=slots,
                                 embedding_size=emb, dense_dim=13,
                                 hidden=(256, 128), learning_rate=1e-3)
        exe = pt.Executor()
        exe.run(startup)
        emb_params = [p.name for p in main.all_parameters()
                      if tuple(p.shape) == (vocab, emb)]
        t = DistributeTranspiler()
        t.transpile(main, pservers=n_servers, trainers=1)
        servers = [server_cls(index=i, num_trainers=1)
                   for i in range(n_servers)]
        dt = DistributedTrainer(
            t, exe, servers, learning_rate=1e-3, mode=mode,
            sparse_params={p: f"slot_{i}"
                           for i, p in enumerate(emb_params)})
        dt.init_params_on_pservers()

        rng = np.random.default_rng(0)

        def make_feed():
            feed = {"dense_feature":
                    rng.normal(size=(batch, 13)).astype(np.float32),
                    "click": rng.integers(0, 2, (batch, 1)).astype(np.int64)}
            for s in range(slots):
                feed[f"slot_{s}"] = rng.integers(
                    0, vocab, (batch, ids_per_slot)).astype(np.int64)
            return feed

        feeds = [make_feed() for _ in range(8)]
        # warm: one-time XLA compiles (the step + one eager kernel per
        # distinct block shape) spread over the first few steps; keep
        # them out of the steady-state timing
        for f in feeds[:5]:
            dt.train_step(f)
        dt.flush()

        dense_bytes = sum(
            np.prod(main.global_block().var(n).shape) * 4
            for n in dt.dense_names)
        sparse_rows = batch * ids_per_slot * slots  # upper bound/step
        sparse_bytes = sparse_rows * emb * 4

        t0 = time.perf_counter()
        # last_step_fetch_bytes lags one step in pipelined mode; the
        # cumulative counter delta across the timed region (read after
        # the final flush() lands the last in-flight round trip) is
        # exact for both modes
        fetch_total0 = dt.total_fetch_bytes
        for i in range(steps):
            dt.train_step(feeds[i % len(feeds)])
        dt.flush()
        fetch_bytes = dt.total_fetch_bytes - fetch_total0
        dtot = time.perf_counter() - t0
        dt.close()
        return {
            "servers": n_servers,
            "mode": mode,
            "rpc_delay_ms": rpc_delay_ms,
            "steps_per_s": round(steps / dtot, 1),
            "ms_per_step": round(dtot / steps * 1e3, 2),
            "dense_send_bytes_per_step": int(dense_bytes),
            "dense_fetch_bytes_per_step": int(fetch_bytes / steps),
            "sparse_touched_bytes_per_step_ub": int(2 * sparse_bytes),
            "batch": batch,
            "vocab": vocab,
        }
    finally:
        pt.core.scope._scope_stack.pop()


def main():
    # the pserver path is host code: pin the CPU backend, whatever the
    # environment selects
    import jax

    jax.config.update("jax_platforms", "cpu")
    results = []
    # loopback (zero network): the framework machinery's own cost
    for n in (1, 4):
        for mode in ("serial", "pipelined"):
            r = run_config(n, mode)
            results.append(r)
            print(json.dumps(r))
    # simulated 2 ms/RPC DCN: where server fan-out and pipelining pay
    for n in (1, 4):
        for mode in ("serial", "pipelined"):
            r = run_config(n, mode, rpc_delay_ms=2.0)
            results.append(r)
            print(json.dumps(r))

    def pick(n, mode, delay):
        return next(r for r in results if r["servers"] == n
                    and r["mode"] == mode and r["rpc_delay_ms"] == delay)

    print(json.dumps({
        "metric": "ctr_pserver_dcn_scaling_1_to_4_servers",
        "value": round(pick(4, "serial", 2.0)["steps_per_s"]
                       / pick(1, "serial", 2.0)["steps_per_s"], 3),
        "unit": "x",
        "extra": {
            "loopback_steps_per_s": pick(1, "serial", 0.0)["steps_per_s"],
            "dcn_pipelined_vs_serial": round(
                pick(4, "pipelined", 2.0)["steps_per_s"]
                / pick(4, "serial", 2.0)["steps_per_s"], 3),
        },
    }))


if __name__ == "__main__":
    main()
