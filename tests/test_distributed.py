"""Distributed-layer tests, following the reference's in-process patterns:
client+servers in one process (pserver/test/test_ParameterServer2.cpp), RPC
layer alone (test_ProtoServer.cpp), master with the in-mem store
(go/master/service_internal_test.go), TTL'd discovery
(go/pserver/etcd_client_test.go)."""

import os
import pickle
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.distributed import rpc
from paddle_tpu.distributed.master import MasterClient, MasterService
from paddle_tpu.distributed.pserver import (
    ParameterServer,
    PServerClient,
    assign_server,
)
from paddle_tpu.distributed.store import (
    FileStore,
    InMemStore,
    discover_services,
    register_service,
)
from paddle_tpu.distributed.transpiler import (
    DistributedTrainer,
    DistributeTranspiler,
)
from paddle_tpu.native import recordio


# ------------------------------------------------------------------ rpc
class _Echo:
    def echo(self, x):
        return x

    def add(self, a, b=0):
        return a + b

    def boom(self):
        raise ValueError("boom")


def test_rpc_roundtrip_and_errors():
    server = rpc.Server(_Echo()).start()
    try:
        c = rpc.Client(server.endpoint)
        assert c.call("echo", {"a": np.arange(3)})["a"].tolist() == [0, 1, 2]
        assert c.call("add", 2, b=3) == 5
        with pytest.raises(RuntimeError, match="boom"):
            c.call("boom")
        # still usable after a remote error
        assert c.call("add", 1, b=1) == 2
        c.close()
    finally:
        server.stop()


def test_rpc_large_payload():
    server = rpc.Server(_Echo()).start()
    try:
        c = rpc.Client(server.endpoint)
        big = np.random.rand(1 << 20)  # 8 MB
        np.testing.assert_array_equal(c.call("echo", big), big)
        c.close()
    finally:
        server.stop()


# ---------------------------------------------------------------- store
def test_inmem_store_ttl_and_cas():
    s = InMemStore()
    s.put("a", 1)
    assert s.get("a") == 1
    s.put("b", 2, ttl=0.05)
    assert s.get("b") == 2
    time.sleep(0.1)
    assert s.get("b") is None
    assert s.cas("a", 1, 10)
    assert not s.cas("a", 1, 20)
    assert s.get("a") == 10
    assert s.keys() == ["a"]


def test_file_store(tmp_path):
    s = FileStore(str(tmp_path))
    s.put("x/y", {"v": 1})
    assert s.get("x/y") == {"v": 1}
    assert s.keys("x/") == ["x/y"]
    s.delete("x/y")
    assert s.get("x/y") is None


def test_service_discovery_ttl():
    s = InMemStore()
    stop = register_service(s, "pserver", "127.0.0.1:9000", ttl=0.3)
    time.sleep(0.05)
    assert discover_services(s, "pserver") == ["127.0.0.1:9000"]
    stop()
    time.sleep(0.1)
    assert discover_services(s, "pserver") == []


# --------------------------------------------------------------- master
def _write_dataset(tmp_path, n_files=2, recs_per_file=40):
    paths, all_recs = [], []
    for i in range(n_files):
        p = tmp_path / f"data-{i:05d}"
        with recordio.Writer(p, max_chunk_bytes=256) as w:
            for j in range(recs_per_file):
                rec = pickle.dumps((i, j))
                w.write(rec)
                all_recs.append(rec)
        paths.append(str(p))
    return paths, all_recs


def test_master_chunk_partition_and_pass(tmp_path):
    paths, all_recs = _write_dataset(tmp_path)
    svc = MasterService(timeout_sec=60)
    svc.set_dataset(paths)
    n_chunks = sum(len(recordio.index(p)) for p in paths)
    assert len(svc.todo) == n_chunks

    client = MasterClient(svc)
    client.set_dataset(paths)
    got = []
    while True:
        r = client.next_record()
        if r is None:
            break
        got.append(r)
    assert sorted(got) == sorted(all_recs)
    # next pass serves everything again
    assert svc.num_passes_finished() >= 0
    got2 = []
    while True:
        r = client.next_record()
        if r is None:
            break
        got2.append(r)
    assert sorted(got2) == sorted(all_recs)


def test_master_failure_poison_drop(tmp_path):
    paths, _ = _write_dataset(tmp_path, n_files=1, recs_per_file=4)
    svc = MasterService(timeout_sec=60, failure_max=2)
    svc.set_dataset(paths)
    t1 = svc.get_task()
    assert svc.task_failed(t1["id"])
    t2 = svc.get_task()
    assert t2["id"] == t1["id"]  # requeued
    svc.task_failed(t2["id"])
    # failure_max reached -> dropped to failed, not todo
    assert all(t.id != t1["id"] for t in svc.todo)
    assert any(t.id == t1["id"] for t in svc.failed)


def test_master_timeout_requeue(tmp_path):
    paths, _ = _write_dataset(tmp_path, n_files=1, recs_per_file=4)
    svc = MasterService(timeout_sec=0.2, failure_max=5)
    svc.set_dataset(paths)
    t = svc.get_task()
    deadline = time.time() + 5
    while not svc.todo and time.time() < deadline:
        time.sleep(0.05)
    assert any(x.id == t["id"] for x in svc.todo), "task not requeued"


def test_master_snapshot_recover(tmp_path):
    paths, all_recs = _write_dataset(tmp_path, n_files=1, recs_per_file=10)
    store = InMemStore()
    svc = MasterService(store=store, timeout_sec=60)
    svc.set_dataset(paths)
    leased = svc.get_task()
    assert leased is not None
    # master dies; a new one recovers from the store: pending -> todo
    svc2 = MasterService(store=store, timeout_sec=60)
    ids = {t.id for t in svc2.todo}
    assert leased["id"] in ids


def test_master_save_model_election():
    svc = MasterService(timeout_sec=60)
    assert svc.request_save_model("t0", block_sec=5)
    assert not svc.request_save_model("t1", block_sec=5)
    assert svc.request_save_model("t0", block_sec=5)


def test_cloud_reader(tmp_path):
    from paddle_tpu.reader.creator import cloud_reader

    paths, all_recs = _write_dataset(tmp_path, n_files=1, recs_per_file=12)
    svc = MasterService(timeout_sec=60)
    reader = cloud_reader(paths, etcd_endpoints=svc)
    got = list(reader())
    assert sorted(map(str, got)) == sorted(
        str(pickle.loads(r)) for r in all_recs
    )


# -------------------------------------------------------------- pserver
def test_pserver_sync_barrier_two_trainers():
    ps = ParameterServer(num_trainers=2, sync=True)
    ps.init_param("w", np.zeros(4, np.float32), optimizer="sgd", lr=0.5)
    ps.finish_init_params()

    def trainer(grad):
        ps.send_grad("w", np.full(4, grad, np.float32))

    t1 = threading.Thread(target=trainer, args=(1.0,))
    t2 = threading.Thread(target=trainer, args=(3.0,))
    t1.start(); t2.start(); t1.join(); t2.join()
    # averaged grad = 2.0, lr 0.5 -> w = -1
    np.testing.assert_allclose(ps.get_param("w"), -np.ones(4), rtol=1e-6)


def test_pserver_async_and_sparse():
    ps = ParameterServer(num_trainers=1, sync=False)
    ps.init_param("emb", np.ones((10, 2), np.float32), optimizer="sgd", lr=1.0)
    ps.finish_init_params()
    ps.send_sparse_grad("emb", np.array([1, 3]), np.ones((2, 2), np.float32))
    p = ps.get_param("emb")
    np.testing.assert_allclose(p[1], [0, 0])
    np.testing.assert_allclose(p[0], [1, 1])
    rows = ps.get_param_rows("emb", [3])
    np.testing.assert_allclose(rows, [[0, 0]])


def test_pserver_adam_server_side():
    ps = ParameterServer(num_trainers=1, sync=True)
    w0 = np.ones(3, np.float32)
    ps.init_param("w", w0, optimizer="adam", lr=0.1)
    ps.finish_init_params()
    ps.send_grad("w", np.ones(3, np.float32))
    w1 = ps.get_param("w")
    assert np.all(w1 < w0)  # moved against the gradient
    assert np.isfinite(w1).all()


def test_pserver_checkpoint_recover(tmp_path):
    store = InMemStore()
    ps = ParameterServer(index=0, num_trainers=1, sync=False, store=store,
                         checkpoint_dir=str(tmp_path),
                         checkpoint_every_n_updates=1)
    ps.init_param("w", np.zeros(2, np.float32), optimizer="momentum", lr=0.1,
                  attrs={"mu": 0.9})
    ps.finish_init_params()
    ps.send_grad("w", np.ones(2, np.float32))
    w_after = ps.get_param("w").copy()
    # new server instance on same store+dir recovers params AND momentum
    ps2 = ParameterServer(index=0, num_trainers=1, sync=False, store=store,
                          checkpoint_dir=str(tmp_path))
    assert ps2.ready()
    np.testing.assert_allclose(ps2.get_param("w"), w_after)
    ps2.send_grad("w", np.ones(2, np.float32))
    # momentum state survived: second step larger than first
    step2 = np.abs(ps2.get_param("w") - w_after)
    assert np.all(step2 > np.abs(w_after) * 1.5)


def test_pserver_client_over_rpc_sharded():
    servers = [ParameterServer(index=i, num_trainers=1) for i in range(2)]
    rpc_servers = [rpc.Server(s).start() for s in servers]
    try:
        client = PServerClient([s.endpoint for s in rpc_servers])
        params = {f"p{i}": np.full(2, float(i), np.float32) for i in range(5)}
        client.init_params(params, optimizer="sgd", lr=1.0)
        client.send_grads({n: np.ones(2, np.float32) for n in params})
        fresh = client.get_params(list(params))
        for i in range(5):
            np.testing.assert_allclose(fresh[f"p{i}"], float(i) - 1.0)
        # shards actually split across the two servers
        counts = [len(s.params) for s in servers]
        assert sum(counts) == 5 and all(c > 0 for c in counts)
    finally:
        for s in rpc_servers:
            s.stop()


# ----------------------------------------------------------- transpiler
def test_transpiler_end_to_end_training():
    """fit_a_line via 2 in-process pservers: the fluid transpiler book-test
    pattern (book_distribute/notest_*_dist.py) without real processes."""
    x = layers.data("x", shape=[3])
    y = layers.data("y", shape=[1])
    pred = layers.fc(input=x, size=1, bias_attr=False)
    loss = layers.mean(layers.square_error_cost(pred, y))
    pt.optimizer.SGD(learning_rate=0.05).minimize(loss)
    main = pt.default_main_program()

    t = DistributeTranspiler()
    t.transpile(main, pservers=2, trainers=1)
    # optimizer ops stripped from the trainer half
    trainer_prog = t.get_trainer_program()
    assert all(op.type != "sgd" for op in trainer_prog.global_block().ops)
    # every param assigned to some pserver; both halves cover all params
    cfg0 = t.get_pserver_config(0)
    cfg1 = t.get_pserver_config(1)
    assert set(cfg0) | set(cfg1) == set(t.optimize_info)

    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    servers = [ParameterServer(index=i, num_trainers=1) for i in range(2)]
    dt = DistributedTrainer(t, exe, servers, learning_rate=0.05)
    dt.init_params_on_pservers()

    rng = np.random.default_rng(0)
    xs = rng.normal(size=(16, 3)).astype(np.float32)
    w_true = np.array([[1.0], [-2.0], [0.5]], np.float32)
    ys = xs @ w_true
    losses = []
    for _ in range(10):
        out = dt.train_step({"x": xs, "y": ys}, extra_fetch=[loss])
        losses.append(float(np.asarray(out[0]).ravel()[0]))
    assert losses[-1] < losses[0] * 0.7, losses


def test_assign_server_stable():
    assert assign_server("w", 4) == assign_server("w", 4)
    spread = {assign_server(f"p{i}", 4) for i in range(32)}
    assert len(spread) == 4


def test_transpiler_conv_model_dist():
    """recognize_digits_conv via the pserver path (reference
    book_distribute/notest_recognize_digits_conv_dist.py): a real conv
    model's params sharded over 2 in-process pservers, server-side SGD."""
    from paddle_tpu.models import lenet

    outs = lenet.build(learning_rate=0.003)
    main = pt.default_main_program()

    t = DistributeTranspiler()
    t.transpile(main, pservers=2, trainers=1)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    servers = [ParameterServer(index=i, num_trainers=1) for i in range(2)]
    dt = DistributedTrainer(t, exe, servers, learning_rate=0.003)
    dt.init_params_on_pservers()

    rng = np.random.default_rng(3)
    img = rng.normal(size=(8, 1, 28, 28)).astype(np.float32)
    lbl = rng.integers(0, 10, (8, 1)).astype(np.int64)
    losses = []
    for _ in range(6):
        out = dt.train_step({"img": img, "label": lbl},
                            extra_fetch=[outs["avg_cost"]])
        losses.append(float(np.asarray(out[0]).ravel()[0]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_launch_single_host_and_mesh():
    from paddle_tpu.distributed import launch

    launch.init_multihost()  # single host: no-op success
    assert launch.is_initialized()
    mesh = launch.global_mesh({"dp": -1, "tp": 2})
    assert mesh.shape["tp"] == 2
    assert mesh.shape["dp"] * 2 == len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        launch.global_mesh({"dp": 3, "tp": 5})
    with pytest.raises(ValueError, match="one mesh axis"):
        launch.global_mesh({"dp": -1, "tp": -1})


def _reap(procs):
    """Terminate subprocess(es), never raising out of a finally block."""
    if not isinstance(procs, (list, tuple)):
        procs = [procs]
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait(timeout=10)


def _spawn_cli(cli_args, store_path):
    """Spawn `python -m paddle_tpu <args>` and wait (bounded even if the
    child hangs silently: stdout is drained on a helper thread) for its
    'serving on <endpoint>' line; returns (proc, endpoint)."""
    import os
    import queue
    import re
    import sys

    repo_root = os.path.dirname(os.path.dirname(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    prev = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = repo_root + (os.pathsep + prev if prev else "")
    p = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu", *cli_args,
         "--store", str(store_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)

    q = queue.Queue()

    def drain():
        for line in p.stdout:
            q.put(line)
        q.put(None)

    threading.Thread(target=drain, daemon=True).start()
    deadline = time.time() + 60
    lines = []
    while time.time() < deadline:
        try:
            line = q.get(timeout=max(0.1, deadline - time.time()))
        except queue.Empty:
            break
        if line is None:
            break
        lines.append(line)
        m = re.search(r"serving on (\S+)", line)
        if m:
            return p, m.group(1)
    _reap(p)
    raise AssertionError(f"no endpoint from {cli_args}: {lines!r}")


def test_cli_pserver_processes_end_to_end(tmp_path):
    """REAL multi-process distributed training: two `python -m paddle_tpu
    pserver` subprocesses over TCP, trainer in this process (the reference
    book_distribute pattern with actual processes, SURVEY §4)."""
    procs, endpoints = [], []
    try:
        for i in range(2):
            p, ep = _spawn_cli(
                ["pserver", "--index", str(i), "--num-trainers", "1",
                 "--port", "0"], tmp_path / "store")
            procs.append(p)
            endpoints.append(ep)

        client = PServerClient(endpoints)
        rng = np.random.default_rng(0)
        w = {"w_a": rng.normal(size=(4,)).astype(np.float32),
             "w_b": rng.normal(size=(3,)).astype(np.float32)}
        client.init_params(w, optimizer="sgd", lr=0.1, attrs={})
        for _ in range(3):
            grads = {k: np.ones_like(v) for k, v in w.items()}
            client.send_grads(grads)
        fresh = client.get_params(list(w))
        for k in w:
            np.testing.assert_allclose(
                fresh[k], w[k] - 0.1 * 3 * np.ones_like(w[k]), rtol=1e-5)
    finally:
        _reap(procs)


def test_cli_master_process_end_to_end(tmp_path):
    """`python -m paddle_tpu master` subprocess serving a RecordIO dataset
    over TCP; records consumed via MasterClient from this process."""
    paths, all_recs = _write_dataset(tmp_path, n_files=2, recs_per_file=10)
    p, endpoint = _spawn_cli(
        ["master", "--port", "0", "--dataset", *paths], tmp_path / "store")
    try:
        client = MasterClient(endpoint)
        got = []
        while True:
            rec = client.next_record()
            if rec is None:
                break
            got.append(rec)
        assert sorted(got) == sorted(all_recs)
    finally:
        _reap(p)


def test_multihost_two_process_cpu(tmp_path):
    """REAL 2-process multi-host run over the JAX coordination service
    (CPU backend): launch.init_multihost on each process, a global mesh
    spanning both, a cross-process psum, and 2 data-parallel Executor
    steps whose replicated state agrees bit-for-bit across processes
    (reference analog: cluster_train_v2 launchers + --trainer_id)."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coordinator = f"127.0.0.1:{port}"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONSAFEPATH", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [repo] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append("--xla_force_host_platform_device_count=2")
    env["XLA_FLAGS"] = " ".join(flags)

    runner = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "multihost_runner.py")
    procs = [
        subprocess.Popen(
            [sys.executable, runner, coordinator, "2", str(i)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
        if any("Multiprocess computations aren't implemented" in out
               for out in outs):
            # this image's jaxlib CPU backend cannot execute
            # cross-process computations at all (the PJRT CPU client
            # raises UNIMPLEMENTED on the first collective) — the same
            # environment limitation test_multihost_midpass_kill_resume
            # already skips on.  Nothing in-repo can fix a jaxlib
            # build; ROADMAP item 4c tracks running this gate on a
            # capable jaxlib.
            pytest.skip("this jaxlib's CPU backend cannot run "
                        "cross-process computations")
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {i} failed:\n{out}"
        oks = [
            [l for l in out.splitlines() if l.startswith("MULTIHOST_OK")]
            for out in outs
        ]
        assert all(len(o) == 1 for o in oks), outs
        # replicated loss and params identical across the two processes
        assert oks[0][0].split()[2:] == oks[1][0].split()[2:], oks
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


# ------------------------------------------------- block sharding (round 3)
def test_split_param_plan_balance():
    """[1e6, 64] embedding over 4 servers: 4 contiguous row blocks within
    one row of even (reference split_dense_variable,
    distribute_transpiler.py:106-145), all servers used, deterministic."""
    from paddle_tpu.distributed.pserver import split_param

    plan = split_param("emb.w", (1_000_000, 64), 4)
    assert len(plan) == 4
    assert {s for s, _, _ in plan} == {0, 1, 2, 3}
    sizes = [r1 - r0 for _, r0, r1 in plan]
    assert max(sizes) - min(sizes) <= 1
    spans = sorted((r0, r1) for _, r0, r1 in plan)
    assert spans[0][0] == 0 and spans[-1][1] == 1_000_000
    for (a0, a1), (b0, b1) in zip(spans, spans[1:]):
        assert a1 == b0
    assert plan == split_param("emb.w", (1_000_000, 64), 4)
    # small params stay whole (min_block guard)
    assert len(split_param("fc.b", (10,), 4)) == 1
    assert len(split_param("w", (3, 3), 4)) == 1


def test_block_sharded_init_fetch_train():
    """A [100, 8] param splits into 4 blocks on 4 servers; fetch
    reassembles exactly; a dense SGD step applies blockwise."""
    servers = [ParameterServer(index=i, num_trainers=1) for i in range(4)]
    client = PServerClient(servers, min_block_elems=64)
    w = np.arange(100 * 8, dtype=np.float32).reshape(100, 8)
    client.init_params({"w": w}, optimizer="sgd", lr=0.5)
    assert [len(s.params) for s in servers] == [1, 1, 1, 1]
    np.testing.assert_array_equal(client.get_params(["w"])["w"], w)
    client.send_grads({"w": np.ones_like(w)})
    np.testing.assert_allclose(client.get_params(["w"])["w"], w - 0.5)


def test_block_sharded_training_matches_single_server():
    """Same gradient stream through a 1-server client and a 4-server
    block-sharded client (momentum): bit-equal trajectories."""
    rng = np.random.default_rng(0)
    w0 = rng.normal(size=(64, 4)).astype(np.float32)
    single = PServerClient([ParameterServer(index=0, num_trainers=1)])
    sharded = PServerClient(
        [ParameterServer(index=i, num_trainers=1) for i in range(4)],
        min_block_elems=32)
    for c in (single, sharded):
        c.init_params({"w": w0.copy()}, optimizer="momentum", lr=0.1,
                      attrs={"mu": 0.9})
    for step in range(5):
        g = rng.normal(size=w0.shape).astype(np.float32)
        single.send_grads({"w": g})
        sharded.send_grads({"w": g})
    np.testing.assert_array_equal(single.get_params(["w"])["w"],
                                  sharded.get_params(["w"])["w"])


def test_parallel_scatter_overlaps_servers():
    """The client's scatter/gather overlaps across servers (the
    sendParallel analog, ParameterClient2.cpp:146): measured by a
    max-in-flight counter across 4 slow servers, not wall-clock (which
    flakes under CI load)."""
    in_flight = [0]
    peak = [0]
    lock = threading.Lock()

    class SlowServer(ParameterServer):
        def send_grad(self, name, grad):
            with lock:
                in_flight[0] += 1
                peak[0] = max(peak[0], in_flight[0])
            time.sleep(0.03)
            try:
                return super().send_grad(name, grad)
            finally:
                with lock:
                    in_flight[0] -= 1

    servers = [SlowServer(index=i, num_trainers=1) for i in range(4)]
    client = PServerClient(servers, min_block_elems=32)
    w = np.zeros((64, 4), np.float32)
    client.init_params({"w": w}, optimizer="sgd", lr=0.1)
    client.send_grads({"w": np.ones_like(w)})
    assert peak[0] >= 2, f"sends never overlapped (peak={peak[0]})"


def test_sparse_rows_adam_matches_dense_when_all_rows_touched():
    """Lazy sparse adam == dense adam when every row is touched every
    step (per-row pows advance in lockstep with the global pow)."""
    from paddle_tpu.distributed.pserver import _OptimizerState

    rng = np.random.default_rng(1)
    n, d = 12, 4
    p_dense = rng.normal(size=(n, d)).astype(np.float32)
    p_sparse = p_dense.copy()
    os_d = _OptimizerState("adam", 0.01, {})
    os_s = _OptimizerState("adam", 0.01, {})
    for _ in range(5):
        g = rng.normal(size=(n, d)).astype(np.float32)
        p_dense = os_d.step(p_dense, g)
        os_s.step_rows(p_sparse, np.arange(n), g)
    np.testing.assert_allclose(p_sparse, p_dense, rtol=1e-5, atol=1e-6)


def test_sparse_rows_lazy_per_row_state():
    """Rows touched at different rates carry their OWN bias correction:
    row 5 touched 3x must equal a dense adam run of 3 steps on that row
    alone; untouched rows stay bit-identical."""
    from paddle_tpu.distributed.pserver import _OptimizerState

    rng = np.random.default_rng(2)
    n, d = 8, 3
    p = rng.normal(size=(n, d)).astype(np.float32)
    p0 = p.copy()
    os_s = _OptimizerState("adam", 0.05, {})
    grads = [rng.normal(size=(1, d)).astype(np.float32) for _ in range(3)]
    for g in grads:
        os_s.step_rows(p, np.array([5]), g)
    # dense single-row reference
    ref = p0[5:6].copy()
    os_d = _OptimizerState("adam", 0.05, {})
    for g in grads:
        ref = os_d.step(ref, g)
    np.testing.assert_allclose(p[5:6], ref, rtol=1e-5, atol=1e-6)
    mask = np.ones(n, bool)
    mask[5] = False
    np.testing.assert_array_equal(p[mask], p0[mask])


def test_sparse_rows_generic_optimizer_and_merge():
    """The pow-free path runs the registered op impl on row slices
    (momentum), and duplicate rows merge-add first (SelectedRows merge);
    negative rows (padding) are dropped."""
    from paddle_tpu.distributed.pserver import _OptimizerState

    p = np.zeros((4, 2), np.float32)
    st = _OptimizerState("momentum", 1.0, {"mu": 0.5})
    st.step_rows(p, np.array([1, 1, -1]),
                 np.array([[1., 1.], [2., 2.], [9., 9.]], np.float32))
    # merged grad = 3 -> velocity 3 -> p = -3
    np.testing.assert_allclose(p[1], [-3., -3.])
    np.testing.assert_array_equal(p[0], [0., 0.])
    st.step_rows(p, np.array([1]), np.ones((1, 2), np.float32))
    # velocity = 0.5*3 + 1 = 2.5 -> p = -5.5
    np.testing.assert_allclose(p[1], [-5.5, -5.5])


def test_pserver_dense_adamax_and_proximal():
    """Every optimizer the transpiler routes to the pserver has dense
    state slots (adamax/proximal_* were missing)."""
    for opt, attrs in [("adamax", {}), ("proximal_gd", {}),
                       ("proximal_adagrad", {})]:
        ps = ParameterServer(num_trainers=1, sync=False)
        w0 = np.ones(3, np.float32)
        ps.init_param("w", w0, optimizer=opt, lr=0.1, attrs=attrs)
        ps.finish_init_params()
        ps.send_grad("w", np.ones(3, np.float32))
        w1 = ps.get_param("w")
        assert np.isfinite(w1).all() and np.all(w1 < w0), (opt, w1)


def test_sparse_rows_handles_readonly_param():
    """np.asarray views of jax Arrays are read-only and pickle PRESERVES
    that flag — a sparse update on a param that arrived as such a view
    must copy, not crash (caught driving the RPC path end-to-end)."""
    from paddle_tpu.distributed.pserver import _OptimizerState

    p = np.zeros((4, 2), np.float32)
    p.setflags(write=False)
    st = _OptimizerState("adam", 0.1, {})
    out = st.step_rows(p, np.array([1]), np.ones((1, 2), np.float32))
    assert out.flags.writeable
    assert np.all(out[1] < 0)


def test_pserver_sparse_send_respects_configured_optimizer():
    """send_sparse_grad no longer hardcodes SGD: an adagrad server's
    sparse update uses the adagrad rule."""
    ps = ParameterServer(num_trainers=1, sync=False)
    ps.init_param("emb", np.ones((4, 2), np.float32),
                  optimizer="adagrad", lr=1.0, attrs={"epsilon": 1e-6})
    ps.finish_init_params()
    g = np.full((1, 2), 2.0, np.float32)
    ps.send_sparse_grad("emb", np.array([2]), g)
    # adagrad: moment = 4, update = 2/sqrt(4) = 1 -> 1 - 1 = 0
    np.testing.assert_allclose(ps.get_param("emb")[2], [0., 0.], atol=1e-5)
    np.testing.assert_allclose(ps.get_param("emb")[0], [1., 1.])


def test_ctr_dnn_distributed_sparse_matches_local_adam():
    """CTR-DNN via the block-sharded sparse pserver path vs the SAME
    program trained locally: with every vocab row touched each step the
    lazy sparse adam must match local dense adam (VERDICT round-2 item 3
    acceptance).  Embeddings go through prefetch + send_sparse_grad;
    the dense tower through blockwise send_grads."""
    from paddle_tpu.models import ctr_dnn

    vocab, emb, slots = 16, 4, 2
    outs = ctr_dnn.build(sparse_feature_dim=vocab, num_slots=slots,
                         embedding_size=emb, dense_dim=3, hidden=(8,),
                         learning_rate=1e-2)
    main = pt.default_main_program()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    scope = pt.core.scope.global_scope()
    emb_params = [p.name for p in main.all_parameters()
                  if tuple(p.shape) == (vocab, emb)]
    assert len(emb_params) == slots
    snapshot = {p.name: np.array(scope.get(p.name))
                for p in main.all_parameters()}

    rng = np.random.default_rng(0)
    batch = vocab  # every row of every slot appears in every batch
    feeds = []
    for _ in range(4):
        feed = {"dense_feature":
                rng.normal(size=(batch, 3)).astype(np.float32),
                "click": rng.integers(0, 2, (batch, 1)).astype(np.int64)}
        for s in range(slots):
            ids = np.arange(vocab)
            rng.shuffle(ids)
            feed[f"slot_{s}"] = ids.reshape(-1, 1).astype(np.int64)
        feeds.append(feed)

    # local run
    for feed in feeds:
        exe.run(main, feed=feed, fetch_list=[outs["avg_cost"]])
    local = {n: np.array(scope.get(n)) for n in snapshot}

    # reset scope, distributed run (4 servers, sparse embeddings)
    for n, v in snapshot.items():
        scope.set(n, v.copy())
    t = DistributeTranspiler()
    t.transpile(main, pservers=4, trainers=1)
    servers = [ParameterServer(index=i, num_trainers=1) for i in range(4)]
    dt = DistributedTrainer(
        t, exe, servers, learning_rate=1e-2,
        sparse_params={p: f"slot_{i}" for i, p in enumerate(emb_params)})
    dt.init_params_on_pservers()
    for feed in feeds:
        dt.train_step(feed)
    # every param (sparse and dense) lives on the servers; fetch back
    for name in snapshot:
        got = dt.client.get_params([name])[name]
        np.testing.assert_allclose(
            got, local[name], rtol=2e-4, atol=2e-5,
            err_msg=f"param {name} diverged between local and sparse-PS")


def _multihost_env(n_virtual=2):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONSAFEPATH", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [repo] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n_virtual}")
    # bit-identical runs need load-independent reduction splits: XLA CPU
    # partitions multithreaded reductions by available threads, so a busy
    # machine changes summation order and the last few mantissa bits
    flags.append("--xla_cpu_multi_thread_eigen=false")
    env["XLA_FLAGS"] = " ".join(flags)
    env["OMP_NUM_THREADS"] = "1"
    return env


def _run_multihost_phase(mode, ckpt_dir, env):
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coordinator = f"127.0.0.1:{port}"
    runner = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "multihost_runner.py")
    procs = [
        subprocess.Popen(
            [sys.executable, runner, coordinator, "2", str(i), mode,
             str(ckpt_dir)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"{mode} rank {i} failed:\n{out}"
    oks = [[l for l in out.splitlines()
            if l.startswith("MULTIHOST_CKPT_OK")] for out in outs]
    assert all(len(o) == 1 for o in oks), outs
    return [o[0].split()[2:] for o in oks]  # [loss=..., state=...] per rank


def test_multihost_sharded_checkpoint_resume(tmp_path):
    """Multi-host-safe checkpoint of cross-process PARTITIONED state
    (round-2 VERDICT item 5): a 2-process run whose fc weight is
    tp-sharded across the processes saves at step 1 (one shard file per
    process), the processes die, fresh processes restore (each reading
    only ITS shard) and continue — final params bit-identical to an
    uninterrupted 3-step run on both ranks."""
    env = _multihost_env(2)
    ckpt = tmp_path / "ckpt"
    try:
        ref = _run_multihost_phase("ckpt_ref", ckpt, env)
    except AssertionError as e:
        # same jaxlib limitation as test_multihost_two_process_cpu /
        # test_multihost_midpass_kill_resume: the CPU PJRT client
        # raises UNIMPLEMENTED on any cross-process computation
        if "Multiprocess computations aren't implemented" in str(e):
            pytest.skip("this jaxlib's CPU backend cannot run "
                        "cross-process computations")
        raise
    saved = _run_multihost_phase("ckpt_save", ckpt, env)
    # the checkpoint really is per-process shard files
    files = os.listdir(ckpt)
    assert any(".shard0." in f for f in files), files
    assert any(".shard1." in f for f in files), files
    resumed = _run_multihost_phase("ckpt_resume", ckpt, env)
    # all three runs agree on final loss and state digest, per rank
    assert ref == saved == resumed, (ref, saved, resumed)
    # and the replicated loss agrees ACROSS ranks (one global SPMD
    # computation, not two process-local ones)
    assert ref[0][0] == ref[1][0], ref


def _run_multihost_kill_phase(mode, ckpt_dir, env):
    """Like _run_multihost_phase but EXPECTS both ranks to die by
    SIGKILL after checkpointing; returns the outputs."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coordinator = f"127.0.0.1:{port}"
    runner = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "multihost_runner.py")
    procs = [
        subprocess.Popen(
            [sys.executable, runner, coordinator, "2", str(i), mode,
             str(ckpt_dir)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    import signal

    for i, (p, out) in enumerate(zip(procs, outs)):
        assert "MULTIHOST_KILL_READY" in out, \
            f"{mode} rank {i} died before checkpointing:\n{out}"
        assert p.returncode == -signal.SIGKILL, \
            f"{mode} rank {i} rc={p.returncode} (expected SIGKILL):\n{out}"
    return outs


def test_multihost_midpass_kill_resume(tmp_path):
    """ISSUE 8 satellite (ROADMAP item 4's gate at multi-host scale):
    kill-and-resume across the 2-process tp-sharded mesh.  Both ranks
    save a FULL-state checkpoint (per-process shard files + RNG/step
    sidecar) at step 2 of 4, SIGKILL themselves mid-pass, and fresh
    processes restore + finish — final loss and the digest over EVERY
    persistable (momentum included) bit-identical to the uninterrupted
    4-step run on both ranks."""
    env = _multihost_env(2)
    ckpt = tmp_path / "ckpt"
    try:
        ref = _run_multihost_phase("ckpt_mid_ref", ckpt, env)
    except AssertionError as e:
        if "Multiprocess computations aren't implemented" in str(e):
            pytest.skip("this jaxlib's CPU backend cannot run "
                        "cross-process computations")
        raise
    _run_multihost_kill_phase("ckpt_mid_kill", ckpt, env)
    # the checkpoint on disk is per-process shard files + the sidecar
    files = os.listdir(ckpt)
    assert any(".shard0." in f for f in files), files
    assert any(".shard1." in f for f in files), files
    assert "__train_state__.pkl" in files, files
    resumed = _run_multihost_phase("ckpt_mid_resume", ckpt, env)
    assert ref == resumed, (ref, resumed)
    # one global SPMD computation: the replicated loss agrees ACROSS ranks
    assert ref[0][0] == ref[1][0], ref


def test_late_attach_client_recovers_block_plan():
    """A client that never called init_params (eval-only trainer)
    rebuilds the block plan from the hash server's param meta and
    fetches/updates a block-sharded param correctly."""
    servers = [ParameterServer(index=i, num_trainers=1) for i in range(4)]
    first = PServerClient(servers, min_block_elems=64)
    w = np.arange(100 * 8, dtype=np.float32).reshape(100, 8)
    first.init_params({"w": w}, optimizer="sgd", lr=0.5)
    # the late client has a DIFFERENT (default) block-size knob: the plan
    # must come from the initializer's recorded meta, not local config
    late = PServerClient(servers)
    np.testing.assert_array_equal(late.get_params(["w"])["w"], w)
    rows = late.get_param_rows("w", np.array([0, 50, 99]))
    np.testing.assert_array_equal(rows, w[[0, 50, 99]])
    # empty query returns (0, row_width) once the plan/shape is known
    empty = late.get_param_rows("w", np.array([], np.int64))
    assert empty.shape == (0, 8)
    with pytest.raises(IndexError):
        late.send_sparse_grad("w", np.array([100]),
                              np.ones((1, 8), np.float32))
    late.close()
    first.close()


def test_client_handles_scalar_and_aliasing():
    """0-d (scalar) params go whole (no row slicing), and in-process
    servers must COPY init values — a sparse update must never mutate
    the caller's original array."""
    servers = [ParameterServer(index=i, num_trainers=1) for i in range(2)]
    client = PServerClient(servers, min_block_elems=4)
    w = np.zeros((8, 2), np.float32)
    s = np.float32(2.0)
    client.init_params({"w": w, "step": s}, optimizer="sgd", lr=1.0)
    client.send_grads({"step": np.float32(1.0)})
    np.testing.assert_allclose(client.get_params(["step"])["step"], 1.0)
    client.send_sparse_grad("w", np.array([3]), np.ones((1, 2), np.float32))
    np.testing.assert_array_equal(w, np.zeros((8, 2), np.float32))
    np.testing.assert_allclose(client.get_params(["w"])["w"][3], [-1, -1])
    client.close()


def test_checkpoint_completion_markers(tmp_path):
    """A checkpoint missing a process's completion marker (writer died
    mid-save) must refuse to load rather than restore torn state."""
    x = layers.data("x", shape=[3])
    pred = layers.fc(input=x, size=2)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    d = str(tmp_path / "ck")
    pt.io.save_persistables(exe, d, pt.default_main_program())
    # healthy load works
    pt.io.load_persistables(exe, d, pt.default_main_program())
    os.remove(os.path.join(d, "__done0__"))
    with pytest.raises(IOError, match="incomplete checkpoint"):
        pt.io.load_persistables(exe, d, pt.default_main_program())


def test_recovered_legacy_whole_param_server():
    """Servers recovered from a pre-block-sharding checkpoint hold params
    WHOLE under bare names: a round-3 client must detect the meta refusal
    and route whole, not to block keys that don't exist."""
    # pick a name whose hash server is index 0
    name = next(n for n in (f"w{i}" for i in range(64))
                if assign_server(n, 4) == 0)
    legacy = ParameterServer(index=0, num_trainers=1, sync=False)
    legacy.init_param(name, np.zeros((100, 8), np.float32),
                      optimizer="sgd", lr=0.5)
    legacy.finish_init_params()  # = recovered: whole param, no meta
    servers = [legacy] + [ParameterServer(index=i, num_trainers=1,
                                          sync=False) for i in range(1, 4)]
    client = PServerClient(servers, min_block_elems=64)
    client.init_params({name: np.zeros((100, 8), np.float32)},
                       optimizer="sgd", lr=0.5)
    client.send_grads({name: np.ones((100, 8), np.float32)})
    np.testing.assert_allclose(client.get_params([name])[name], -0.5)


# --------------------------------------------- pipelined updater + delta fetch
def test_delta_fetch_moves_zero_bytes_when_idle():
    """get_params_delta (the version check the reference's dense trainer
    lacks): a second fetch with no server-side update omits the param
    and transfers zero payload; an update makes it move again."""
    server = ParameterServer(index=0, num_trainers=1)
    client = PServerClient([server])
    w = np.arange(12, dtype=np.float32).reshape(3, 4)
    client.init_params({"w": w}, optimizer="sgd", lr=0.1)

    first = client.get_params_delta(["w"])
    np.testing.assert_allclose(first["w"], w)
    assert client.last_delta_bytes == w.nbytes

    second = client.get_params_delta(["w"])
    assert second == {}
    assert client.last_delta_bytes == 0

    client.send_grads({"w": np.ones_like(w)})
    third = client.get_params_delta(["w"])
    np.testing.assert_allclose(third["w"], w - 0.1)
    assert client.last_delta_bytes == w.nbytes
    np.testing.assert_allclose(third["w"], client.get_params(["w"])["w"])
    client.close()


def test_delta_fetch_refetches_after_server_restart():
    """Version epochs: a restarted server (recovered params, fresh
    counters) must NOT be mistaken for 'unchanged'."""
    server = ParameterServer(index=0, num_trainers=1)
    client = PServerClient([server])
    w = np.ones((4, 2), np.float32)
    client.init_params({"w": w}, optimizer="sgd", lr=0.1)
    client.get_params_delta(["w"])
    assert client.get_params_delta(["w"]) == {}

    # simulate restart: new server object with the same params
    server2 = ParameterServer(index=0, num_trainers=1)
    server2.init_param("w", w * 3)
    server2.finish_init_params()
    client._shards[0] = server2
    again = client.get_params_delta(["w"])
    np.testing.assert_allclose(again["w"], w * 3)
    client.close()


def _fit_line_setup(mode, lr=0.05, n_servers=2):
    x = layers.data("x", shape=[3])
    y = layers.data("y", shape=[1])
    pred = layers.fc(input=x, size=1, bias_attr=False)
    loss = layers.mean(layers.square_error_cost(pred, y))
    pt.optimizer.SGD(learning_rate=lr).minimize(loss)
    main = pt.default_main_program()
    t = DistributeTranspiler()
    t.transpile(main, pservers=n_servers, trainers=1)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    servers = [ParameterServer(index=i, num_trainers=1)
               for i in range(n_servers)]
    dt = DistributedTrainer(t, exe, servers, learning_rate=lr, mode=mode)
    dt.init_params_on_pservers()
    return dt, loss, servers


def test_pipelined_trainer_converges_and_flush_syncs():
    """Pipelined mode (ConcurrentRemoteParameterUpdater design): params
    are one step stale, training still converges, and flush() makes the
    local scope bit-match the servers."""
    dt, loss, servers = _fit_line_setup("pipelined")
    rng = np.random.default_rng(1)
    xs = rng.normal(size=(16, 3)).astype(np.float32)
    ys = xs @ np.array([[1.0], [-2.0], [0.5]], np.float32)
    losses = []
    for _ in range(12):
        out = dt.train_step({"x": xs, "y": ys}, extra_fetch=[loss])
        losses.append(float(np.asarray(out[0]).ravel()[0]))
    dt.flush()
    assert losses[-1] < losses[0] * 0.7, losses
    # after flush the scope view equals the server state exactly
    from paddle_tpu.core.scope import global_scope
    fresh = dt.client.get_params(dt.dense_names)
    for n in dt.dense_names:
        np.testing.assert_array_equal(
            np.asarray(global_scope().get(n), np.float32), fresh[n])
    dt.close()


def test_pipelined_overlaps_rpc_with_compute():
    """The RPC round trip of step N runs WHILE step N+1's compute runs
    (VERDICT r3 item 3 'done' bar: step ~ max(compute, RPC), not the
    sum).  Asserted via interval overlap between server calls and
    executor compute — not wall-clock ratios, which flake under CI load
    (the test_parallel_scatter_overlaps_servers convention)."""
    import time as _time

    delay = 0.05
    rpc_spans = []
    exe_spans = []

    class SlowServer(ParameterServer):
        """Server whose round-trip-bound calls carry a DCN-like delay
        and record their active interval."""

        def send_grad(self, *a, **k):
            t0 = _time.perf_counter()
            _time.sleep(delay)
            r = super().send_grad(*a, **k)
            rpc_spans.append((t0, _time.perf_counter()))
            return r

    class SlowExe:
        def __init__(self, inner):
            self._inner = inner

        def run(self, *a, **k):
            t0 = _time.perf_counter()
            _time.sleep(delay)
            r = self._inner.run(*a, **k)
            exe_spans.append((t0, _time.perf_counter()))
            return r

        def __getattr__(self, name):
            return getattr(self._inner, name)

    def overlap_count():
        return sum(
            1 for r0, r1 in rpc_spans for e0, e1 in exe_spans
            if max(r0, e0) < min(r1, e1)
        )

    rng = np.random.default_rng(2)
    xs = rng.normal(size=(8, 3)).astype(np.float32)
    ys = xs @ np.array([[1.0], [-2.0], [0.5]], np.float32)

    def run_mode(mode):
        rpc_spans.clear()
        exe_spans.clear()
        pt.core.unique_name.reset()
        main, startup = pt.Program(), pt.Program()
        scope = pt.Scope()
        pt.core.scope._scope_stack.append(scope)
        try:
            with pt.program_guard(main, startup):
                x = layers.data("x", shape=[3])
                y = layers.data("y", shape=[1])
                pred = layers.fc(input=x, size=1, bias_attr=False)
                loss = layers.mean(layers.square_error_cost(pred, y))
                pt.optimizer.SGD(learning_rate=0.01).minimize(loss)
            t = DistributeTranspiler()
            t.transpile(main, pservers=1, trainers=1)
            exe = pt.Executor()
            exe.run(startup)
            servers = [SlowServer(index=0, num_trainers=1)]
            dt = DistributedTrainer(t, SlowExe(exe), servers,
                                    learning_rate=0.01, mode=mode)
            dt.init_params_on_pservers()
            rpc_spans.clear()
            exe_spans.clear()
            for _ in range(5):
                dt.train_step({"x": xs, "y": ys})
            dt.flush()
            dt.close()
            return overlap_count()
        finally:
            pt.core.scope._scope_stack.pop()

    # serial: every RPC strictly between compute phases — zero overlap
    assert run_mode("serial") == 0
    # pipelined: the in-flight round trip spans the next step's compute
    assert run_mode("pipelined") >= 3


def test_pipelined_bytes_drop_when_idle_servers():
    """last_step_fetch_bytes reflects the conditional fetch: training
    steps move bytes; a step against already-converged (zero-grad)
    params still moves bytes only if the optimizer changed them."""
    dt, loss, servers = _fit_line_setup("serial", lr=0.0)
    rng = np.random.default_rng(3)
    xs = rng.normal(size=(4, 3)).astype(np.float32)
    ys = xs @ np.array([[1.0], [-2.0], [0.5]], np.float32)
    dt.train_step({"x": xs, "y": ys})
    first_bytes = dt.last_step_fetch_bytes
    # lr=0: SGD with zero learning rate still bumps the version (an
    # update ran), so bytes move; now fetch again with NO update at all
    dt.client.get_params_delta(dt.dense_names)
    assert dt.client.last_delta_bytes == 0
    assert first_bytes > 0
    dt.close()


def test_dense_step_preserves_param_dtype():
    """Regression: the numpy dense optimizer must not drift a non-f32
    param to float32 (the step_rows contract applies to step too)."""
    server = ParameterServer(index=0, num_trainers=1)
    client = PServerClient([server])
    w = np.ones((4, 4), np.float16)
    client.init_params({"w": w}, optimizer="adam", lr=0.01)
    client.send_grads({"w": np.ones_like(w, np.float32)})
    got = client.get_params(["w"])["w"]
    assert got.dtype == np.float16, got.dtype


def test_delta_fetch_degrades_on_legacy_server():
    """A server build without get_param_if_newer must degrade to the
    full fetch (the _meta_lookup missing-method discipline), not crash."""
    class LegacyServer(ParameterServer):
        def __getattribute__(self, name):
            if name == "get_param_if_newer":
                raise AttributeError(name)
            return super().__getattribute__(name)

    server = LegacyServer(index=0, num_trainers=1)
    client = PServerClient([server])
    w = np.arange(8, dtype=np.float32).reshape(2, 4)
    client.init_params({"w": w}, optimizer="sgd", lr=0.1)
    out = client.get_params_delta(["w"])
    np.testing.assert_allclose(out["w"], w)
    assert client.last_delta_bytes == w.nbytes
    # degraded mode: always a full fetch, bytes never drop to 0
    out2 = client.get_params_delta(["w"])
    np.testing.assert_allclose(out2["w"], w)
    assert client.last_delta_bytes == w.nbytes
    client.close()
