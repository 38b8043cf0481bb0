"""CLI / launcher contract (reference run(load, main), veles CLI role)."""

import os
import subprocess
import sys

import numpy

from znicz_tpu.core.config import root
from znicz_tpu.launcher import (Launcher, list_samples, run_workflow,
                                resolve_workflow_module)
from znicz_tpu.__main__ import apply_override
import znicz_tpu.samples.wine  # noqa: F401 (installs root.wine defaults)

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def test_list_samples():
    names = list_samples()
    for expected in ("wine", "mnist", "cifar", "kanji", "lines",
                     "yale_faces", "demo_kohonen", "mnist_rbm",
                     "approximator"):
        assert expected in names


def test_resolve_by_bare_name_and_dotted():
    m1 = resolve_workflow_module("wine")
    m2 = resolve_workflow_module("znicz_tpu.samples.wine")
    assert m1 is m2
    assert hasattr(m1, "run")


def test_run_workflow_wine_via_contract():
    old = root.wine.decision.max_epochs
    root.wine.decision.max_epochs = 15
    try:
        wf = run_workflow("wine")
    finally:
        root.wine.decision.max_epochs = old
    assert wf is not None
    assert wf.decision.epoch_ended


def test_dry_run_builds_but_does_not_train():
    wf = run_workflow("wine", dry_run=True)
    assert wf is not None
    assert not wf.decision.complete


def test_serve_subcommand_dispatches():
    """'python -m znicz_tpu serve' routes to the serving CLI (its own
    parser), and newest_snapshot picks the latest prefix match."""
    import time
    import pytest
    from znicz_tpu.__main__ import main
    from znicz_tpu.launcher import newest_snapshot
    with pytest.raises(SystemExit) as e:
        main(["serve", "--help"])
    assert e.value.code == 0
    assert newest_snapshot("/nonexistent", "x") is None
    d = root.common.dirs.snapshots  # conftest points this at tmp
    os.makedirs(d, exist_ok=True)
    for i, name in enumerate(("p_old.1.pickle", "p_new.2.pickle",
                              "p_part.3.pickle.part", "q_no.4.pickle")):
        with open(os.path.join(d, name), "wb") as f:
            f.write(b"x")
        os.utime(os.path.join(d, name), (time.time() + i,
                                         time.time() + i))
    assert newest_snapshot(d, "p").endswith("p_new.2.pickle")


def test_optimize_rejects_max_restarts(tmp_path):
    """--max-restarts supervision does not cover the genetics sweep —
    the combination errors loudly instead of silently dropping the
    flag."""
    import pytest
    from znicz_tpu.__main__ import main
    wf = tmp_path / "wf_noop.py"
    wf.write_text("def run(load, main):\n    pass\n")
    with pytest.raises(SystemExit) as e:
        main([str(wf), "--optimize", "2", "--max-restarts", "1"])
    assert e.value.code == 2


def test_launcher_roles():
    l = Launcher()
    assert l.is_standalone and not l.is_master and not l.is_slave


def test_apply_override_literal_and_string():
    root.test_cli_ns.update({"a": {"b": 1}, "s": "x"})
    apply_override(root, "test_cli_ns.a.b=42")
    assert root.test_cli_ns.a.b == 42
    apply_override(root, "test_cli_ns.s=hello")
    assert root.test_cli_ns.s == "hello"
    apply_override(root, "test_cli_ns.lst=[1, 2]")
    assert root.test_cli_ns.lst == [1, 2]


def test_snapshot_resume_via_launcher(tmp_path):
    """Train wine briefly with snapshots on, then resume via --snapshot."""
    import glob
    import os
    from znicz_tpu.core import prng
    prng.get().seed(1234)
    saved_epochs = root.wine.decision.max_epochs
    saved_snap = dict(root.wine.snapshotter.as_dict())
    root.wine.decision.max_epochs = 3
    root.wine.snapshotter.update({
        "directory": str(tmp_path), "interval": 1, "time_interval": 0,
        "compression": ""})
    try:
        wf = run_workflow("wine")
        files = sorted(glob.glob(os.path.join(str(tmp_path), "*.pickle")),
                       key=os.path.getmtime)
        assert files
        w_trained = numpy.array(wf.forwards[0].weights.mem)

        prng.get().seed(1234)
        root.wine.decision.max_epochs = 4
        wf2 = run_workflow("wine", snapshot=files[-1], dry_run=True)
        w_resumed = numpy.array(wf2.forwards[0].weights.mem)
        # dry_run: restored but not retrained -> weights match the snapshot
        assert numpy.abs(w_resumed - w_trained).max() < 1e-6
    finally:
        root.wine.decision.max_epochs = saved_epochs
        root.wine.snapshotter.update(saved_snap)


def test_cli_process_end_to_end(tmp_path):
    """The real `python -m znicz_tpu` process: run wine for 2 epochs."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO_ROOT, HOME=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "znicz_tpu", "wine",
         "--config", "wine.decision.max_epochs=2",
         "--config", "wine.snapshotter.directory=%s" % tmp_path],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "best val/train err%" in out.stdout
    # the override must actually take effect (2 epochs, not the
    # import-time default 100)
    assert "Epoch 2" in out.stderr or "Epoch 2" in out.stdout
    assert "Epoch 5" not in out.stderr and "Epoch 5" not in out.stdout


def test_dump_graph(tmp_path):
    """--dump-graph writes a DOT file of the control graph."""
    out = subprocess.run(
        [sys.executable, "-m", "znicz_tpu", "wine",
         "--dump-graph", str(tmp_path / "g.dot")],
        cwd=REPO_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT,
                 HOME=str(tmp_path)),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    dot = (tmp_path / "g.dot").read_text()
    assert dot.startswith("digraph")
    assert "loader" in dot and "decision" in dot
    assert "->" in dot


def test_cli_optimize_runs_ga(tmp_path):
    """--optimize evolves Range config values through real training runs
    (the reference GA tier driven from the CLI)."""
    script = tmp_path / "wine_ga.py"
    script.write_text("""
from znicz_tpu.core.config import root
from znicz_tpu.core.genetics import Range
import znicz_tpu.samples.wine  # installs defaults + WineWorkflow

root.wine.decision.max_epochs = 3
root.wine.learning_rate = Range(0.3, 0.05, 0.6)
from znicz_tpu.samples.wine import run  # noqa: F401,E402
""")
    out = subprocess.run(
        [sys.executable, "-m", "znicz_tpu", str(script),
         "--optimize", "2x3"],
        cwd=REPO_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT,
                 HOME=str(tmp_path)),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "best fitness" in out.stdout
    assert "learning_rate" in out.stdout


def test_cli_optimize_validation(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT,
               HOME=str(tmp_path))
    for args, needle in (
            (["wine", "--optimize", "abc"], "GENSxPOP"),
            (["wine", "--optimize", "0x8"], "at least 1"),
            (["wine", "--optimize", "2x3", "--dry-run"],
             "cannot be combined")):
        out = subprocess.run(
            [sys.executable, "-m", "znicz_tpu"] + args,
            cwd=REPO_ROOT, env=env, capture_output=True, text=True,
            timeout=120)
        assert out.returncode != 0
        assert needle in out.stderr, (args, out.stderr[-500:])


def test_list_includes_research_tier_and_manifests():
    from znicz_tpu.samples import MANIFESTS
    names = list_samples()
    assert "research.alexnet" in names
    assert "research.stl10" in names
    # every manifest entry names a listable sample
    for name in MANIFESTS:
        assert name in names, name


def test_resolver_surfaces_inner_import_errors(tmp_path):
    """A fully-qualified module whose own imports fail must surface the
    REAL ImportError, not retry under the samples namespace (review
    regression)."""
    import pytest as _pytest
    bad = tmp_path / "badmod.py"
    bad.write_text("from znicz_tpu import does_not_exist_symbol\n")
    import sys as _sys
    _sys.path.insert(0, str(tmp_path))
    try:
        with _pytest.raises(ImportError, match="does_not_exist_symbol"):
            resolve_workflow_module("badmod")
    finally:
        _sys.path.remove(str(tmp_path))
    # dotted research names still resolve via the fallback
    m = resolve_workflow_module("research.wine_relu")
    assert m.__name__.endswith("research.wine_relu")


def test_every_manifest_sample_dry_runs():
    """Zoo integrity: every sample in MANIFESTS builds + initializes
    through the launcher contract (dry run — no training).  Catches
    registration/config regressions across the whole zoo in one sweep."""
    from znicz_tpu.samples import MANIFESTS
    # pure-jax demo trains inside run() itself; everything else dry-runs
    skip = {"research.long_context"}
    for name in sorted(MANIFESTS):
        if name in skip:
            continue
        wf = run_workflow(name, dry_run=True)
        assert wf is not None, name
        assert wf.initialized, name


def test_fused_snapshot_topology_mismatch_rejected(tmp_path):
    """A fused snapshot of a DIFFERENT topology (fewer layers, leading
    layer shapes equal) must be rejected by the compatibility check —
    plain zip would truncate and accept it, then load_state_dict would
    wholesale-replace params with a wrong-length list.  Missing
    per-layer param keys are rejected too."""
    import copy
    from znicz_tpu.launcher import Launcher

    root.mnistr.loader.update({"synthetic_train": 60,
                               "synthetic_valid": 20,
                               "minibatch_size": 20})
    root.mnistr.snapshotter.update({"directory": str(tmp_path),
                                    "compression": ""})
    wf = run_workflow("mnist", dry_run=True, fused={})
    launcher = Launcher(dry_run=True, fused={})
    trainer = wf.fused_trainer
    good = {"workflow": type(wf).__name__,
            "units": {trainer.name: {
                "fused_state": copy.deepcopy(trainer.fused_state)}}}
    assert launcher._snapshot_incompatible(good, wf) is None

    truncated = copy.deepcopy(good)
    sd = truncated["units"][trainer.name]["fused_state"]
    sd["params"] = sd["params"][:-1]
    reason = launcher._snapshot_incompatible(truncated, wf)
    assert reason and "layer count" in reason, reason

    missing_key = copy.deepcopy(good)
    sd = missing_key["units"][trainer.name]["fused_state"]
    for p in sd["params"]:
        if "b" in p:
            del p["b"]
            break
    reason = launcher._snapshot_incompatible(missing_key, wf)
    assert reason and "param keys" in reason, reason


def test_cli_optimize_generic_vmapped(tmp_path):
    """--optimize takes the GENERIC vmapped population path for ANY
    registered sample whose Range sites map onto fused hyper slots —
    no sample-file population_evaluator needed.  yale_faces gains a
    runtime Range site; the CLI must report the generic fused GA
    engaging."""
    script = tmp_path / "yale_ga.py"
    script.write_text("""
from znicz_tpu.core.config import root
from znicz_tpu.core.genetics import Range
import znicz_tpu.samples.yale_faces  # installs defaults + workflow

root.yalefaces.decision.max_epochs = 2
root.yalefaces.loader.minibatch_size = 20
root.yalefaces.snapshotter.directory = "/tmp"
root.yalefaces.learning_rate = Range(0.05, 0.01, 0.1)
from znicz_tpu.samples.yale_faces import run  # noqa: F401,E402
""")
    out = subprocess.run(
        [sys.executable, "-m", "znicz_tpu", str(script),
         "--optimize", "2x3"],
        cwd=REPO_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT,
                 HOME=str(tmp_path)),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "fused GA: vmapping each generation over root.yalefaces" \
        in out.stdout, out.stdout[-2000:]
    assert "best fitness" in out.stdout


def test_cli_optimize_serial_fallback_trains_fused(tmp_path):
    """When the fused population path cannot engage (here: an MSE head
    has no softmax fitness), --optimize prints the reason, falls back
    to serial evaluations, and those serial runs may train on the
    fused path (--fused now combines with --optimize)."""
    script = tmp_path / "approx_ga.py"
    script.write_text("""
from znicz_tpu.core.config import root
from znicz_tpu.core.genetics import Range
import znicz_tpu.samples.approximator

root.approximator.decision.max_epochs = 2
root.approximator.snapshotter.directory = "/tmp"
root.approximator.learning_rate = Range(0.02, 0.005, 0.05)
from znicz_tpu.samples.approximator import run  # noqa: F401,E402
""")
    out = subprocess.run(
        [sys.executable, "-m", "znicz_tpu", str(script),
         "--optimize", "1x2", "--fused"],
        cwd=REPO_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT,
                 HOME=str(tmp_path)),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    combined = out.stdout + out.stderr
    assert "fused GA unavailable" in combined, combined[-2000:]
    assert "evaluating serially" in combined
    assert "best fitness" in out.stdout
