"""Multi-node launcher master tier (round-4 verdict missing #3).

Reference analogue: launch/controllers/master.py (HTTPMaster sync_peers +
ETCDMaster heartbeat/watch) + job/pod.py lifecycle. Emulation: two REAL
controller processes ("hosts"), each spawning 2 REAL worker processes,
rendezvous through one C++ TCPStore master — node ranks auto-assigned by
registration order, world of 4 bootstraps jax.distributed on CPU, and the
elastic path recovers from a worker SIGKILL on one pod (restart epoch
observed by the OTHER pod too).
"""

import os
import socket
import subprocess
import sys
import textwrap
import threading
import time

import pytest

import paddle_tpu
from paddle_tpu.distributed.launch.master import Master

pytestmark = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    paddle_tpu.__file__)))


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


# --- Master service unit coverage -----------------------------------------

class TestMasterService:
    def test_sync_peers_assigns_ranks_by_registration(self):
        port = _free_port()
        server = Master("127.0.0.1", port, "t1", is_server=True)
        results = {}

        def join(name, delay):
            time.sleep(delay)
            m = Master("127.0.0.1", port, "t1")
            peers, rank = m.sync_peers(name, nnodes=3, epoch=0)
            results[name] = (peers, rank)

        ts = [threading.Thread(target=join, args=(f"pod{i}", 0.1 * i))
              for i in range(1, 3)]
        for t in ts:
            t.start()
        peers, rank = server.sync_peers("pod0", nnodes=3, epoch=0)
        for t in ts:
            t.join()
        assert rank == 0                    # registered first
        assert peers == ["pod0", "pod1", "pod2"]
        assert results["pod1"][1] == 1 and results["pod2"][1] == 2
        assert results["pod1"][0] == peers

    def test_heartbeat_ttl(self):
        port = _free_port()
        m = Master("127.0.0.1", port, "t2", is_server=True)
        m.heartbeat("a")
        assert m.dead_pods(["a", "never-seen"], ttl=5.0) == []
        time.sleep(0.3)
        assert m.dead_pods(["a"], ttl=0.1) == ["a"]
        m.heartbeat("a")
        assert m.dead_pods(["a"], ttl=5.0) == []

    def test_restart_epoch_watch(self):
        port = _free_port()
        m = Master("127.0.0.1", port, "t3", is_server=True)
        c = Master("127.0.0.1", port, "t3")
        e0 = c.restart_epoch()
        m.bump_epoch()
        assert c.restart_epoch() == e0 + 1

    def test_client_retries_until_server_up(self):
        port = _free_port()
        got = {}

        def late_server():
            time.sleep(1.0)
            got["server"] = Master("127.0.0.1", port, "t4", is_server=True)

        t = threading.Thread(target=late_server)
        t.start()
        c = Master("127.0.0.1", port, "t4", connect_retry_s=15.0)
        t.join()
        c.store.set("x", "1")
        assert got["server"].store.get("x") == b"1"


# --- 2 "hosts" x 2 workers end to end -------------------------------------

_WORKER4 = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)
    sys.path.insert(0, {repo!r})
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.parallel.mesh import init_parallel_env, pod_bootstrap_env

    kw = pod_bootstrap_env()
    assert kw is not None and kw["num_processes"] == 4, kw
    hm = init_parallel_env(dp=4)
    assert jax.process_count() == 4, jax.process_count()
    mesh = hm.mesh

    @jax.jit
    def allsum(x):
        return jax.shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                             in_specs=P("dp"), out_specs=P())(x)

    x = jax.device_put(jnp.arange(4, dtype=jnp.float32),
                       NamedSharding(mesh, P("dp")))
    out = np.asarray(jax.device_get(allsum(x)))
    assert out[0] == 6.0, out              # 0+1+2+3
    print("POD4_OK rank", jax.process_index(), flush=True)
""").format(repo=_REPO)


def _controller_cmd(tmp_path, script, master, node_tag, max_restarts=0):
    return [sys.executable, "-m", "paddle_tpu.distributed.launch",
            "--nnodes", "2", "--nproc_per_node", "2",
            "--master", master, "--job_id", "jm",
            "--max_restarts", str(max_restarts),
            "--log_dir", str(tmp_path / f"log_{node_tag}"), script]


def _run_controllers(tmp_path, script, max_restarts=0, timeout=240):
    master = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        _controller_cmd(tmp_path, script, master, tag, max_restarts),
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for tag in ("a", "b")]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out)
    return [p.returncode for p in procs], outs


class TestTwoHostLaunch:
    def test_4proc_2host_bootstrap(self, tmp_path):
        script = tmp_path / "worker.py"
        script.write_text(_WORKER4)
        codes, outs = _run_controllers(tmp_path, str(script))
        logs = ""
        for d in ("log_a", "log_b"):
            for f in sorted(os.listdir(tmp_path / d)):
                logs += open(tmp_path / d / f).read()
        assert codes == [0, 0], (codes, outs, logs[-3000:])
        assert logs.count("POD4_OK") == 4, logs[-3000:]

    def test_worker_kill_restarts_both_pods(self, tmp_path):
        # worker 3 (pod B) SIGKILLs itself once; pod B's controller bumps
        # the restart epoch, pod A observes it and restarts too, the
        # second epoch completes on all 4 workers
        script = tmp_path / "flaky.py"
        script.write_text(textwrap.dedent("""
            import os, signal, time
            rank = os.environ["PADDLE_TRAINER_ID"]
            marker = os.path.join({d!r}, "died_once")
            if rank == "3" and not os.path.exists(marker):
                open(marker, "w").write("x")
                os.kill(os.getpid(), signal.SIGKILL)
            time.sleep(1.0)
            print("EPOCH_WORKER_OK", rank, flush=True)
        """).format(d=str(tmp_path)))
        codes, outs = _run_controllers(tmp_path, str(script),
                                       max_restarts=2)
        assert codes == [0, 0], (codes, outs)
        assert os.path.exists(tmp_path / "died_once")
        ctrl = "".join(outs)
        assert "signaling restart" in ctrl          # pod B detected
        assert "peer signaled restart" in ctrl      # pod A observed
        logs = ""
        for d in ("log_a", "log_b"):
            for f in sorted(os.listdir(tmp_path / d)):
                logs += open(tmp_path / d / f).read()
        # all four ranks complete in the recovery epoch
        for r in "0123":
            assert f"EPOCH_WORKER_OK {r}" in logs, logs[-3000:]
