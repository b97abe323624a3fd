"""Two-process jax.distributed validation of parallel/multihost.py
(reference role: tests/nightly/dist_sync_kvstore.py — prove the dist
wiring actually forms a job, not just that the module imports)."""
import os
import socket
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import sys, os
    sys.path.insert(0, {repo!r})
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")

    from mxnet_tpu.parallel import multihost

    pid = int(sys.argv[1])
    multihost.initialize(coordinator_address={coord!r},
                         num_processes=2, process_id=pid)
    assert multihost.is_initialized()
    assert multihost.process_count() == 2, multihost.process_count()
    assert multihost.process_index() == pid
    assert multihost.is_primary() == (pid == 0)
    assert jax.device_count() == 4, jax.device_count()  # 2 procs x 2 dev

    # broadcast: every process must see process 0's value
    import numpy as np
    mine = np.full((3,), float(pid + 1), np.float32)
    got = multihost.broadcast_from_primary(mine)
    assert np.allclose(np.asarray(got), 1.0), got

    # global allreduce across hosts through a psum on the global mesh
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()).reshape(4), ("dp",))
    def f(x):
        return jax.lax.psum(x, "dp")
    xs = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")),
        np.arange(2 * pid, 2 * pid + 2, dtype=np.float32).reshape(2))
    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"),
                            out_specs=P()))(xs)
    local = np.asarray(out.addressable_shards[0].data)
    assert np.allclose(local, 0 + 1 + 2 + 3), local

    multihost.sync_global_devices("done")
    print("WORKER_OK", pid)
""")


TRAIN_WORKER = textwrap.dedent("""
    import sys, os
    sys.path.insert(0, {repo!r})
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from mxnet_tpu.parallel import multihost
    pid = int(sys.argv[1])
    multihost.initialize(coordinator_address={coord!r},
                         num_processes=2, process_id=pid)

    from jax.sharding import NamedSharding, PartitionSpec as P
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep

    # identical init on every process (same seed)
    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(8, in_units=4, activation="relu"),
            mx.gluon.nn.Dense(2, in_units=8))
    net.initialize()

    rs = np.random.RandomState(7)
    X = rs.rand(8, 4).astype(np.float32)       # GLOBAL batch
    Y = rs.randint(0, 2, 8).astype(np.int32)

    mesh = make_mesh([4], ["dp"])              # 2 procs x 2 devices
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    step = FusedTrainStep(net, loss_fn,
                          mx.optimizer.SGD(learning_rate=0.5), mesh=mesh)

    sh = NamedSharding(mesh, P("dp"))
    lo = pid * 4
    gx = jax.make_array_from_process_local_data(sh, X[lo:lo + 4])
    gy = jax.make_array_from_process_local_data(sh, Y[lo:lo + 4])
    for _ in range(5):
        step(NDArray(gx), NDArray(gy))
    step.sync_to_params()
    w_dist = [p.data().asnumpy()
              for p in net.collect_params().values()]

    # single-process reference: same seed, full batch, plain train loop
    mx.random.seed(0)
    ref = mx.gluon.nn.HybridSequential()
    ref.add(mx.gluon.nn.Dense(8, in_units=4, activation="relu"),
            mx.gluon.nn.Dense(2, in_units=8))
    ref.initialize()
    tr = mx.gluon.Trainer(ref.collect_params(), "sgd",
                          {{"learning_rate": 0.5}})
    xs, ys = mx.nd.array(X), mx.nd.array(Y)
    for _ in range(5):
        with mx.autograd.record():
            l = loss_fn(ref(xs), ys).mean()
        l.backward()
        tr.step(1)
    w_ref = [p.data().asnumpy() for p in ref.collect_params().values()]
    for a, b in zip(w_dist, w_ref):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    multihost.sync_global_devices("trained")
    print("TRAIN_PARITY_OK", pid)
""")


@pytest.mark.slow
def test_two_process_training_matches_single_process(tmp_path):
    """DP training across 2 processes lands bit-for-bit on the
    single-process weights — multihost upgraded from 'wiring verified'
    to 'training verified' (reference role:
    tests/nightly/dist_sync_kvstore.py)."""
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    script = tmp_path / "train_worker.py"
    script.write_text(TRAIN_WORKER.format(repo=REPO, coord=coord))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    procs = [subprocess.Popen(
        [sys.executable, "-u", str(script), str(pid)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for pid in (0, 1)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=110)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("two-process training hung:\n" + "\n".join(outs))
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"TRAIN_PARITY_OK {pid}" in out, out


@pytest.mark.slow
def test_two_process_distributed_init(tmp_path):
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    script = tmp_path / "worker.py"
    script.write_text(WORKER.format(repo=REPO, coord=coord))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    procs = [subprocess.Popen(
        [sys.executable, "-u", str(script), str(pid)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for pid in (0, 1)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=150)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("two-process job hung:\n" + "\n".join(outs))
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"WORKER_OK {pid}" in out, out


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# -- dryrun honesty (round-4 verdict item 3): the driver-facing
# two_process signal must distinguish environmental skips from real
# multihost regressions, and the latter must turn the dryrun red. ----

@pytest.mark.slow
def test_dryrun_two_process_leg_red_when_multihost_broken(monkeypatch):
    """A deliberately broken multihost.initialize (fault injection via
    MXNET_TPU_BREAK_MULTIHOST) must RAISE out of the dryrun leg — not
    be swallowed as 'skipped' — so MULTICHIP_r*.json can never record
    ok=true over a broken multihost path."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
            "__graft_entry__.py"))
    ge = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ge)

    monkeypatch.setenv("MXNET_TPU_BREAK_MULTIHOST", "1")
    with pytest.raises(RuntimeError, match="deliberately broken"):
        ge._two_process_leg(timeout_s=150)


@pytest.mark.slow
def test_dryrun_two_process_leg_classifies_timeout_as_skip():
    """Environmental failure (timeout) records skipped:, not a raise."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
            "__graft_entry__.py"))
    ge = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ge)

    status = ge._two_process_leg(timeout_s=0.01)
    assert status.startswith("skipped:"), status


@pytest.mark.slow
def test_dryrun_zero2_kill_restart_leg():
    """The promoted leg (7): a 2-process ZeRO-2 gang checkpointing to a
    shared directory survives one process being SIGKILLed mid-step by
    the step.kill fault site — the restarted gang resumes from the last
    committed step and lands on the uninterrupted pair's weights."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
            "__graft_entry__.py"))
    ge = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ge)

    status = ge._two_process_zero2_kr_leg(timeout_s=200)
    # environmental skip is tolerated (loaded CI host); a worker
    # failure raises out of the leg and fails this test
    assert status == "ok" or status.startswith("skipped:"), status


@pytest.mark.slow
def test_dryrun_two_process_telemetry_leg():
    """The promoted leg (8): two coordination-service processes train
    locally with a host.slow straggler armed on process 1 — the primary
    aggregates the merged registry, serves it at /metrics, and fingers
    process 1 via step_time_skew()/stragglers()."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
            "__graft_entry__.py"))
    ge = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ge)

    status = ge._two_process_telemetry_leg(timeout_s=200)
    # environmental skip is tolerated (loaded CI host); a worker
    # failure raises out of the leg and fails this test
    assert status == "ok" or status.startswith("skipped:"), status


@pytest.mark.slow
def test_dryrun_two_process_pp_leg():
    """The promoted leg (9): a pp=2 ParallelPlan over a 2-process gloo
    mesh with ONE device per process, so every 1F1B ppermute hop
    crosses the wire between processes. Workers self-verify 5-step
    loss parity against a local single-device unpipelined reference."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
            "__graft_entry__.py"))
    ge = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ge)

    status = ge._two_process_pp_leg(timeout_s=200)
    # environmental skip is tolerated (loaded CI host); a worker
    # failure raises out of the leg and fails this test
    assert status == "ok" or status.startswith("skipped:"), status
