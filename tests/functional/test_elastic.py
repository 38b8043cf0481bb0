"""Job-level elastic recovery.

The reference tolerates slave loss per unit (nn_units.py:210-211,
nn_rollback.py:87-97 re-runs pending work); synchronous SPMD loses that,
so elasticity is re-provided at the JOB level (SURVEY.md §2.8): snapshots
publish atomically, and ``--auto-resume`` restores the newest matching
snapshot and continues — loader position, PRNG streams and optimizer
state included, so the post-recovery trajectory EQUALS the uninterrupted
one (the bit-exact resume tests prove the mechanism; this proves the
operational loop around a real SIGKILL).
"""

import os
import signal
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cli(snapdir, extra=()):
    return [sys.executable, "-m", "znicz_tpu", "mnist",
            "--config", "mnistr.loader.synthetic_train=2000",
            "--config", "mnistr.loader.synthetic_valid=400",
            "--config", "mnistr.loader.minibatch_size=20",
            "--config", "mnistr.decision.max_epochs=5",
            "--config", "mnistr.decision.fail_iterations=50",
            "--config", "mnistr.snapshotter.directory=%s" % snapdir,
            "--config", "mnistr.snapshotter.compression=",
            ] + list(extra)


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return env


def _best_line(stdout):
    for line in stdout.splitlines():
        if line.startswith("best val/train err%"):
            return line
    raise AssertionError("no best-err line in output:\n" + stdout[-2000:])


_EPOCH_RE = __import__("re").compile(
    r"Epoch (\d+) class (\w+) n_err (\d+) of (\d+)")


def _epoch_trajectory(stdout):
    """[(epoch, class, n_err, total), ...] from the decision's log —
    the full integer trajectory, not just the final best line."""
    return [tuple(int(g) if g.isdigit() else g for g in m.groups())
            for m in _EPOCH_RE.finditer(stdout)]


def test_sigkill_mid_training_then_auto_resume_matches_straight(tmp_path):
    straight_dir = str(tmp_path / "straight")
    killed_dir = str(tmp_path / "killed")
    os.makedirs(straight_dir)
    os.makedirs(killed_dir)

    # 1) straight-through reference run
    ref = subprocess.run(_cli(straight_dir), env=_env(), cwd=REPO,
                         capture_output=True, text=True, timeout=600)
    assert ref.returncode == 0, ref.stderr[-2000:]
    ref_line = _best_line(ref.stdout)

    # 2) identical run, SIGKILLed after the first snapshot lands
    proc = subprocess.Popen(_cli(killed_dir), env=_env(), cwd=REPO,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    deadline = time.time() + 300
    snap_seen = False
    while time.time() < deadline and proc.poll() is None:
        if any(f.endswith(".pickle")
               for f in os.listdir(killed_dir)):
            snap_seen = True
            break
        time.sleep(0.02)
    assert snap_seen, "no snapshot appeared before the deadline"
    assert proc.poll() is None, \
        "run finished before the kill — grow the dataset"
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=60)
    assert proc.returncode != 0

    # 3) a corrupt newest file must not derail recovery
    junk = os.path.join(killed_dir, "mnist_zzz.9999.pickle")
    with open(junk, "wb") as f:
        f.write(b"truncated-garbage")
    now = time.time() + 10
    os.utime(junk, (now, now))

    # 4) restart with --auto-resume: picks the newest VALID snapshot,
    # fast-forwards, trains to max_epochs — same final answer as the
    # uninterrupted run
    res = subprocess.run(_cli(killed_dir, ["--auto-resume"]), env=_env(),
                         cwd=REPO, capture_output=True, text=True,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    out = res.stdout + res.stderr
    assert "auto-resume: restoring" in out
    assert "skipping unreadable snapshot" in out
    assert _best_line(res.stdout) == ref_line
    # the FULL per-epoch integer trajectory after the restore point must
    # equal the straight run's — a resume that diverged mid-run and
    # re-converged to the same best would pass the best-line check but
    # fail here
    ref_traj = {(e, c): (n, t)
                for e, c, n, t in _epoch_trajectory(
                    ref.stdout + ref.stderr)}
    res_traj = _epoch_trajectory(out)
    assert res_traj, "resumed run logged no epoch lines"
    for e, c, n, t in res_traj:
        assert ref_traj.get((e, c)) == (n, t), (
            "epoch %d %s: resumed (%d, %d) != straight %s"
            % (e, c, n, t, ref_traj.get((e, c))))


def test_auto_resume_without_snapshots_starts_fresh(tmp_path):
    """--auto-resume on a clean directory is a plain cold start."""
    snapdir = str(tmp_path / "fresh")
    os.makedirs(snapdir)
    res = subprocess.run(
        _cli(snapdir, ["--auto-resume",
                       "--config", "mnistr.loader.synthetic_train=200",
                       "--config", "mnistr.loader.synthetic_valid=40",
                       "--config", "mnistr.decision.max_epochs=2"]),
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    _best_line(res.stdout)
