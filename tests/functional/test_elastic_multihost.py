"""Multi-host elastic recovery.

The reference tolerated losing a SLAVE mid-run (nn_units.py:210-211,
nn_rollback.py:87-97 re-queued its pending work); synchronous SPMD is
gang-scheduled, so the job-level replacement must survive the
MULTI-PROCESS case: a 2-process ``jax.distributed`` CPU run is
SIGKILLed mid-epoch (worker first — the survivor blocks on the next
collective, as a real host loss would — then the gang), restarted with
``--auto-resume``, and its per-epoch integer trajectory must equal the
uninterrupted 2-process run's.  Snapshots are written by process 0
only (core/snapshotter.py) and restored by every process from the
shared directory.
"""

import os
import signal
import socket
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_EPOCH_RE = __import__("re").compile(
    r"Epoch (\d+) class (\w+) n_err (\d+) of (\d+)")


def _epoch_trajectory(text):
    return [tuple(int(g) if g.isdigit() else g for g in m.groups())
            for m in _EPOCH_RE.finditer(text)]


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _cli(snapdir, extra=()):
    return [sys.executable, "-m", "znicz_tpu", "mnist",
            "--fused", "mesh=hybrid,window=4",
            "--config", "mnistr.loader.synthetic_train=2000",
            "--config", "mnistr.loader.synthetic_valid=400",
            "--config", "mnistr.loader.minibatch_size=20",
            "--config", "mnistr.decision.max_epochs=4",
            "--config", "mnistr.decision.fail_iterations=50",
            "--config", "mnistr.snapshotter.directory=%s" % snapdir,
            "--config", "mnistr.snapshotter.compression=",
            ] + list(extra)


def _spawn_gang(snapdir, port, extra=()):
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(JAX_PLATFORMS="cpu",
                   # PYTHONPATH is the checkout alone: anything that
                   # initializes the backend at interpreter start would
                   # latch jax.process_count() to 1 before
                   # jax.distributed.initialize can run
                   PYTHONPATH=REPO,
                   JAX_COORDINATOR_ADDRESS="127.0.0.1:%d" % port,
                   JAX_NUM_PROCESSES="2", JAX_PROCESS_ID=str(pid),
                   XLA_FLAGS="--xla_force_host_platform_device_count=1")
        procs.append(subprocess.Popen(
            _cli(snapdir, extra), env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    return procs


def _finish_gang(procs, timeout=900):
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            outs.append((p.returncode, out + err))
    finally:
        for p in procs:  # never leak a live trainer on timeout/failure
            if p.poll() is None:
                p.kill()
                p.wait(timeout=60)
    return outs


def test_two_process_sigkill_then_auto_resume_matches_straight(tmp_path):
    straight_dir = str(tmp_path / "straight")
    killed_dir = str(tmp_path / "killed")
    os.makedirs(straight_dir)
    os.makedirs(killed_dir)

    # 1) uninterrupted 2-process run
    outs = _finish_gang(_spawn_gang(straight_dir, _free_port()))
    for rc, text in outs:
        assert rc == 0, text[-3000:]
    assert "jax.distributed up: process 0 of 2" in outs[0][1]
    ref_traj = {(e, c): (n, t)
                for e, c, n, t in _epoch_trajectory(outs[0][1])}
    assert ref_traj, outs[0][1][-3000:]
    # single-writer snapshots: every file came from process 0's pid
    pids = {f.rsplit(".", 2)[-2] for f in os.listdir(straight_dir)
            if f.endswith(".pickle")}
    assert len(pids) == 1, pids

    # 2) identical gang, worker (process 1) SIGKILLed after the first
    # snapshot lands, then the blocked survivor — a host loss takes the
    # whole gang down (SPMD is gang-scheduled; the scheduler restarts
    # the job, which is step 3).  try/finally: a failed assertion must
    # not leak live training subprocesses
    procs = _spawn_gang(killed_dir, _free_port())
    try:
        deadline = time.time() + 600
        snap_seen = False
        while time.time() < deadline and \
                all(p.poll() is None for p in procs):
            if any(f.endswith(".pickle")
                   for f in os.listdir(killed_dir)):
                snap_seen = True
                break
            time.sleep(0.05)
        assert snap_seen, "no snapshot appeared before the deadline"
        assert all(p.poll() is None for p in procs), \
            "gang finished before the kill — grow the dataset"
        procs[1].send_signal(signal.SIGKILL)
        time.sleep(1.0)
        procs[0].send_signal(signal.SIGKILL)
        for p in procs:
            p.wait(timeout=60)
            assert p.returncode != 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=60)

    # 3) restart the gang with --auto-resume: both processes restore
    # process 0's snapshot from the shared directory and continue;
    # the FULL per-epoch integer trajectory after the restore point
    # must equal the straight run's
    outs = _finish_gang(_spawn_gang(killed_dir, _free_port(),
                                    ["--auto-resume"]))
    for rc, text in outs:
        assert rc == 0, text[-3000:]
    combined = outs[0][1]
    assert "auto-resume: restoring" in combined
    res_traj = _epoch_trajectory(combined)
    assert res_traj, combined[-3000:]
    for e, c, n, t in res_traj:
        assert ref_traj.get((e, c)) == (n, t), (
            "epoch %d %s: resumed (%d, %d) != straight %s"
            % (e, c, n, t, ref_traj.get((e, c))))
