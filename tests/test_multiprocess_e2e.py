"""Multi-process E2E: real ranks, real jax.distributed world (VERDICT #3).

Uses the launch CLI to spawn 2 processes on CPU; each forms the world via
init_parallel_env (PJRT distributed runtime + TCPStore control plane), runs
every eager collective across ranks (Gloo transport on CPU — ICI on TPU),
and round-trips a sharded checkpoint. Reference model:
test/collective/test_communication_api_base.py:59-74.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "helpers", "mp_worker.py")


def _launch_env():
    """Child env: 1 CPU device per process."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # auto-arm the collective watchdog from env (the worker re-arms
    # manually too, exercising the disable-then-enable path)
    env["PADDLE_COLLECTIVE_WATCHDOG"] = "1"
    env.pop("XLA_FLAGS", None)  # conftest's 8-device forcing: 1 dev/proc here
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    return env


def _run_launch(tmp_path, script, *args, launch_args=()):
    """Launch `script` across 2 ranks; return (proc, merged worker logs)."""
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(tmp_path / "logs"),
         *launch_args, script, *args],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=_launch_env())
    logs = ""
    log_root = tmp_path / "logs"
    if log_root.exists():
        for f in sorted(log_root.iterdir()):
            logs += f"\n--- {f.name} ---\n" + f.read_text()
    return proc, logs


def test_two_rank_world(tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    proc, logs = _run_launch(tmp_path, WORKER, ckpt_dir)
    assert proc.returncode == 0, (
        f"launch failed rc={proc.returncode}\nstdout:{proc.stdout[-2000:]}\n"
        f"stderr:{proc.stderr[-2000:]}\nlogs:{logs[-4000:]}")
    for r in range(2):
        assert f"MPWORKER_OK rank={r}/2" in logs, (
            f"rank {r} did not finish\n{logs[-4000:]}")


PIPE_WORKER = os.path.join(REPO, "tests", "helpers", "mp_pipeline_worker.py")


def test_two_rank_pipeline(tmp_path):
    """Per-rank pipeline parallelism across REAL processes: activations
    forward / cotangents back over eager p2p, per-stage tape backward —
    the reference's multi-host PP seam (pipeline_parallel.py:440) on the
    multi-process runtime."""
    proc, logs = _run_launch(tmp_path, PIPE_WORKER)
    assert proc.returncode == 0, (
        f"rc={proc.returncode}\nstdout:{proc.stdout[-1500:]}\n"
        f"stderr:{proc.stderr[-1500:]}\nlogs:{logs[-4000:]}")
    assert "MPPIPE_OK rank=0" in logs and "MPPIPE_OK rank=1" in logs, logs
    assert "MPPIPE_LOSSES" in logs


def test_two_node_launch(tmp_path):
    """Multi-NODE path: two launcher invocations (--nnodes 2, distinct
    --node_rank, shared --master) each spawn their node's worker; rank 0's
    launcher binds the KV master, peers connect — the real pod topology on
    one host."""
    import socket

    def _three_port_base():
        # the job binds p (KV), p+1 (coordinator), p+2 (TCPStore)
        for _ in range(32):
            with socket.socket() as probe:
                probe.bind(("127.0.0.1", 0))
                base = probe.getsockname()[1]
            socks = []
            try:
                for off in range(3):
                    s = socket.socket()
                    s.bind(("127.0.0.1", base + off))
                    socks.append(s)
                return base
            except OSError:
                continue
            finally:
                for s in socks:
                    s.close()
        raise RuntimeError("no free 3-port window")

    def _attempt(attempt_dir):
        """One two-launcher run on a freshly probed port window. The probe
        closes its sockets before the launchers bind (unavoidable TOCTOU),
        so the CALLER retries on bind-race signatures rather than trusting
        one window."""
        import signal as _signal
        import time as _time

        port = _three_port_base()
        ckpt = str(attempt_dir / "ckpt")
        env = _launch_env()
        procs = []
        for node in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "paddle_tpu.distributed.launch",
                 "--nnodes", "2", "--node_rank", str(node),
                 "--master", f"127.0.0.1:{port}",
                 "--log_dir", str(attempt_dir / f"logs{node}"),
                 WORKER, ckpt],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
                cwd=REPO, env=env, start_new_session=True))

        def _kill_group(p):
            # each launcher leads its own session; killing the GROUP takes
            # its spawned rank workers down too (a bare p.kill() would
            # orphan them to spin through the remaining attempts)
            try:
                os.killpg(os.getpgid(p.pid), _signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass

        # poll both: when one launcher dies nonzero (e.g. the master lost
        # the bind race), take its sibling down immediately instead of
        # letting it wait out the full timeout against a dead master
        deadline = _time.monotonic() + 300
        while _time.monotonic() < deadline:
            rcs = [p.poll() for p in procs]
            if all(rc is not None for rc in rcs):
                break
            if any(rc not in (None, 0) for rc in rcs):
                _time.sleep(5)  # grace for the sibling to notice on its own
                for p in procs:
                    if p.poll() is None:
                        _kill_group(p)
                break
            _time.sleep(0.5)
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                _kill_group(p)
                out, _ = p.communicate()
            outs.append(out or "")
        logs = ""
        for node in range(2):
            root = attempt_dir / f"logs{node}"
            if root.exists():
                for f in sorted(root.iterdir()):
                    logs += f"\n--- node{node}/{f.name} ---\n" + f.read_text()
        return procs, outs, logs

    for attempt in range(3):
        adir = tmp_path / f"attempt{attempt}"
        adir.mkdir()
        procs, outs, logs = _attempt(adir)
        if all(p.returncode == 0 for p in procs):
            break
        blob = "".join(outs) + logs
        if "Address already in use" not in blob and "EADDRINUSE" not in blob:
            break  # a real failure, not the port race — report it
    assert all(p.returncode == 0 for p in procs), (
        f"rcs={[p.returncode for p in procs]}\n"
        f"out0:{outs[0][-1500:]}\nout1:{outs[1][-1500:]}\nlogs:{logs[-4000:]}")
    for r in range(2):
        assert f"MPWORKER_OK rank={r}/2" in logs, logs[-4000:]


KILL_WORKER = os.path.join(REPO, "tests", "helpers", "mp_kill_worker.py")


def test_kill_a_rank_watchdog_detects_and_elastic_restarts(tmp_path):
    """VERDICT r3 #8: rank 1 goes dead mid-step (hangs — no clean exit);
    rank 0's collective watchdog flags the frozen peer and aborts; the
    launch controller's watch loop restarts the pod; the restarted world
    completes training. Reference: comm_task_manager.cc +
    launch/controllers/collective.py:272."""
    marker_dir = str(tmp_path / "markers")
    proc, logs = _run_launch(tmp_path, KILL_WORKER, marker_dir,
                             launch_args=("--max_restarts", "2"))
    assert proc.returncode == 0, (
        f"launch failed rc={proc.returncode}\nstdout:{proc.stdout[-2000:]}\n"
        f"stderr:{proc.stderr[-2000:]}\nlogs:{logs[-4000:]}")
    # attempt 1: rank 1 died, rank 0's watchdog named the frozen peer
    assert "MPKILL_DYING rank=1" in logs, logs[-4000:]
    assert "MPKILL_WATCHDOG rank=0" in logs, logs[-4000:]
    assert "'kind': 'stuck'" in logs, logs[-4000:]
    # the controller restarted rather than giving up
    assert "restarting pod (attempt 1" in proc.stderr, proc.stderr[-2000:]
    # attempt 2: the restarted world trained to completion on every rank
    for r in range(2):
        assert f"MPKILL_OK rank={r}/2" in logs, logs[-4000:]
