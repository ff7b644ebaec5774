"""Launcher + elastic tests.

Reference coverage model: test/legacy_test launch tests + fleet/elastic unit
tests (SURVEY.md §2.11/2.12) — real subprocesses, single host.
"""
import os
import subprocess
import sys
import time

import pytest

from paddle_tpu.distributed.launch import (Container, KVClient, KVServer,
                                           Pod, Watcher, launch)
from paddle_tpu.distributed.fleet.elastic import ElasticManager, ElasticStatus

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_launcher_parent_takes_no_device():
    """One process per chip: the launcher starts the trainers and must not
    hold the chip they need. The package root imports jax, which takes
    nothing; what takes the chip is starting a backend, and importing the
    launcher must not do that."""
    code = ("import paddle_tpu.distributed.launch.main\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_kv_server_roundtrip():
    server = KVServer().start()
    try:
        c = KVClient(server.endpoint)
        assert c.get("missing") is None
        c.put("ep/0", "host0:1234")
        assert c.get("ep/0") == "host0:1234"
        assert c.get_all()["ep/0"] == "host0:1234"
        assert c.wait("ep/0", timeout=1) == "host0:1234"
        with pytest.raises(TimeoutError):
            c.wait("never", timeout=0.5)
    finally:
        server.stop()


def test_container_and_pod(tmp_path):
    ok = Container([sys.executable, "-c", "print('hello rank')"],
                   env={}, log_path=str(tmp_path / "log.0"), rank=0)
    bad = Container([sys.executable, "-c", "import sys; sys.exit(3)"],
                    env={}, rank=1)
    pod = Pod()
    pod.add_container(ok)
    pod.add_container(bad)
    pod.deploy()
    code = pod.join()
    assert code == 3
    assert "hello rank" in ok.logs()


def test_launch_cli_success(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(
        "import os\n"
        "print('rank', os.environ['PADDLE_TRAINER_ID'],\n"
        "      'of', os.environ['PADDLE_TRAINERS_NUM'])\n")
    code = launch(["--nproc_per_node", "2", "--log_dir", str(tmp_path),
                   str(script)])
    assert code == 0
    logs = sorted(p.name for p in tmp_path.glob("workerlog.*"))
    assert logs == ["workerlog.0", "workerlog.1"]
    assert "rank 0 of 2" in (tmp_path / "workerlog.0").read_text()


def test_launch_cli_restart_budget(tmp_path):
    script = tmp_path / "fail.py"
    script.write_text("import sys; sys.exit(7)\n")
    t0 = time.time()
    code = launch(["--max_restarts", "1", "--log_dir", str(tmp_path),
                   str(script)])
    assert code == 7
    assert time.time() - t0 < 60


def test_watcher_detects_dead_peer():
    server = KVServer().start()
    try:
        c = KVClient(server.endpoint)
        w0 = Watcher(c, my_rank=0, nnodes=2, ttl=1.0)
        w1 = Watcher(c, my_rank=1, nnodes=2, ttl=1.0)
        w0.heartbeat()
        w1.heartbeat()
        assert w0.dead_peers() == []
        time.sleep(1.2)
        w0.heartbeat()  # rank 1 stops beating
        assert w0.dead_peers() == [1]
    finally:
        server.stop()


def test_elastic_manager_membership_and_scale():
    server = KVServer().start()
    try:
        managers = [ElasticManager(server.endpoint, "job1", r, np=3,
                                   min_np=2, max_np=4, heartbeat_ttl=1.0)
                    for r in range(3)]
        for i, m in enumerate(managers):
            m.register(f"host{i}:80")
        m0 = managers[0]
        assert m0.alive_nodes() == [0, 1, 2]
        assert not m0.need_scale()
        assert m0.status() == ElasticStatus.HOLD

        # rank 2 dies: 2 alive, within [min_np, max_np] -> RESTART (scale-in)
        time.sleep(1.2)
        managers[0].heartbeat()
        managers[1].heartbeat()
        assert m0.alive_nodes() == [0, 1]
        assert m0.need_scale()
        assert m0.status() == ElasticStatus.RESTART

        # below quorum -> HOLD for peers
        time.sleep(1.2)
        managers[0].heartbeat()
        assert m0.status() == ElasticStatus.HOLD

        assert m0.wait_for_np(1, timeout=2)
    finally:
        server.stop()


def test_launch_elastic_restarts_on_elastic_exit(tmp_path):
    """launch_elastic: elastic exit code triggers a restart; a marker file
    makes the second attempt succeed."""
    from paddle_tpu.distributed.launch.main import Context, _parse

    server = KVServer().start()
    try:
        script = tmp_path / "flaky.py"
        marker = tmp_path / "ran_once"
        script.write_text(
            "import os, sys\n"
            f"m = {str(repr(str(marker)))}\n"
            "if not os.path.exists(m):\n"
            "    open(m, 'w').close()\n"
            "    sys.exit(101)\n"  # ELASTIC_EXIT_CODE
            "print('recovered')\n")
        args, script_args = _parse(["--max_restarts", "2",
                                    "--log_dir", str(tmp_path), str(script)])
        ctx = Context(args, script_args)
        ctx.master = server.endpoint
        mgr = ElasticManager(server.endpoint, "job-el", 0, np=1,
                             heartbeat_ttl=5.0)
        from paddle_tpu.distributed.fleet.elastic import launch_elastic
        assert launch_elastic(ctx, manager=mgr) == 0
        assert "recovered" in (tmp_path / "workerlog.0").read_text()
    finally:
        server.stop()


def test_launch_elastic_plain_failure_propagates(tmp_path):
    from paddle_tpu.distributed.launch.main import Context, _parse

    server = KVServer().start()
    try:
        script = tmp_path / "fail.py"
        script.write_text("import sys; sys.exit(9)\n")
        args, script_args = _parse(["--max_restarts", "2", str(script)])
        ctx = Context(args, script_args)
        ctx.master = server.endpoint
        mgr = ElasticManager(server.endpoint, "job-el2", 0, np=1,
                             heartbeat_ttl=5.0)
        from paddle_tpu.distributed.fleet.elastic import launch_elastic
        assert launch_elastic(ctx, manager=mgr) == 9
    finally:
        server.stop()


def test_per_rank_log_collation(tmp_path):
    """The launcher merges per-rank workerlogs into one rank-prefixed
    collated.log (reference launcher log aggregation)."""
    import subprocess
    import sys
    script = tmp_path / "w.py"
    script.write_text(
        "import os\n"
        "print('hello from rank', os.environ['PADDLE_TRAINER_ID'], "
        "flush=True)\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(tmp_path / "logs"),
         str(script)],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-1500:]
    collated = (tmp_path / "logs" / "collated.log").read_text()
    assert "[rank 0] hello from rank 0" in collated
    assert "[rank 1] hello from rank 1" in collated


def test_monitor_gauges_and_peaks():
    from paddle_tpu.utils import monitor
    monitor.stat_reset("test.gauge")
    monitor.stat_update("test.gauge", 5)
    monitor.stat_update("test.gauge", 3)
    monitor.stat_update("test.gauge", -6)
    assert monitor.stat_get("test.gauge") == 2
    assert monitor.stat_peak("test.gauge") == 8
    assert monitor.get_monitor_values().get("test.gauge") == 2
    mem = monitor.sample_device_memory()
    assert isinstance(mem, dict)
    monitor.stat_reset("test.gauge")
    assert monitor.stat_get("test.gauge") == 0
