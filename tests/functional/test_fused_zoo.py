"""Fused-mode zoo sweep: every fused-compatible StandardWorkflow sample
builds, initializes and (for representative topologies) trains through
``--fused`` — the CLI flag and the Launcher plumbing included."""

import pytest

from znicz_tpu.core.config import root
from znicz_tpu.core import prng
from znicz_tpu.launcher import run_workflow
from znicz_tpu.loader.base import VALID

#: StandardWorkflow-based samples whose layer stacks the fused path
#: supports (FC/conv/pool/LRN/activation/dropout + softmax or MSE head)
FUSED_ZOO = ("mnist", "cifar", "lines", "yale_faces", "kanji",
             "approximator")


@pytest.fixture(autouse=True)
def _datasets_tmp(tmp_path, monkeypatch):
    monkeypatch.setattr(root.common.dirs, "datasets", str(tmp_path))
    prng.get(1).seed(1024)
    prng.get(2).seed(1025)


def test_fused_zoo_dry_runs():
    """--fused builds a fused trainer (with a compiled-net handle) for
    every compatible sample; the sweep catches spec-coverage
    regressions across the zoo in one pass."""
    for name in FUSED_ZOO:
        wf = run_workflow(name, dry_run=True, fused=True)
        assert wf.fused_trainer is not None, name
        assert wf.fused_trainer.net is not None, name
        assert wf.gds == [], name


def test_fused_lines_cli_flag_trains(tmp_path):
    """The --fused CLI flag end to end on a conv sample (mcdnnic
    topology, file-based loader)."""
    from znicz_tpu import __main__ as cli
    rc = cli.main([
        "lines", "--fused",
        "--config", "lines.decision.max_epochs=2",
        "--config", "lines.decision.fail_iterations=10",
    ])
    assert rc == 0


def test_fused_kanji_mse_trains(tmp_path):
    """Kanji (MSE head + class_targets nearest-class metric) trains in
    fused mode and reports the same metric surface as the unit graph."""
    from znicz_tpu.samples import kanji
    wf = kanji.run_sample(
        loader_config={
            "minibatch_size": 30,
            "train_paths": [str(tmp_path / "kanji" / "train")],
            "target_paths": [str(tmp_path / "kanji" / "target")]},
        decision_config={"max_epochs": 4, "fail_iterations": 100},
        fused=True)
    dec = wf.decision
    assert wf.fused_trainer is not None
    assert wf.loader.epoch_number == 4
    assert dec.epoch_metrics[VALID] is not None
    assert dec.best_metrics[VALID][0] < 1.0
    assert dec.epoch_n_err[VALID] is not None  # class_targets metric


def test_fused_flag_on_hand_wired_workflow_is_an_error():
    """wine is hand-built (no StandardWorkflow) — --fused must refuse
    it, not run the unit graph under a fused label (ISSUE 21: no
    fallback that hides what ran)."""
    import pytest
    with pytest.raises(SystemExit, match="does not build a fused trainer"):
        run_workflow("wine", fused=True)


def test_fused_cli_kv_spec_parses_to_config(tmp_path):
    """--fused mesh=2,pool_impl=gather reaches the trainer as a config
    dict (the K=V CLI surface)."""
    from znicz_tpu import __main__ as cli
    rc = cli.main([
        "approximator", "--fused", "mesh=2,pool_impl=gather",
        "--config", "approximator.decision.max_epochs=1",
        "--config", "approximator.loader.minibatch_size=20",
    ])
    assert rc == 0
