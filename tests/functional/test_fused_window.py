"""Scan windows in the control plane.

The fused trainer batches K TRAIN minibatches per compiled dispatch
(FusedNet.run_window — one ``lax.scan`` call), while the unit graph keeps
its epoch-level roles.  These tests pin the window path against the
per-minibatch path (the executable spec):

* window=8 trajectory EQUALS window=1 in float64 — params, per-epoch
  error integers, and the max_err_output_sum float the decision tracks;
* an LR-schedule boundary INSIDE a window applies policy(k) to step k
  (the adjuster ticks per collected minibatch, and the per-step hyper
  pytree rides the scan);
* segment tails (window stops at last_minibatch; padded tail minibatch
  masked in-scan exactly like the evaluator would);
* the device-resident dataset path (indices-only host->device traffic)
  equals the host-stacked path;
* CIFAR-caffe on the 8-device mesh: window=8 == window=1.
"""

import numpy
import pytest

pytestmark = pytest.mark.slow

from znicz_tpu.core.config import root
from znicz_tpu.core import prng
from znicz_tpu.core.backends import JaxDevice


@pytest.fixture()
def float64_engine():
    prev_type = root.common.engine.precision_type
    root.common.engine.precision_type = "double"
    root.common.engine.precision_dtype = numpy.float64
    yield
    root.common.engine.precision_type = prev_type
    root.common.engine.__dict__.pop("precision_dtype", None)


def _seed():
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)


def _params(wf):
    return {i: p for i, p in enumerate(wf.fused_trainer.host_params())
            if p}


def _assert_same_trajectory(wf_a, wf_b, tol=1e-12):
    assert list(wf_a.decision.epoch_n_err) == list(wf_b.decision.epoch_n_err)
    for ca, cb in zip(wf_a.decision.confusion_matrixes,
                      wf_b.decision.confusion_matrixes):
        if ca is None or cb is None:
            assert ca is None and cb is None
            continue
        numpy.testing.assert_array_equal(ca, cb)
    for a, b in zip(wf_a.decision.max_err_y_sums,
                    wf_b.decision.max_err_y_sums):
        assert abs(a - b) < 1e-12, (wf_a.decision.max_err_y_sums,
                                    wf_b.decision.max_err_y_sums)
    pa, pb = _params(wf_a), _params(wf_b)
    assert set(pa) == set(pb)
    for i in pa:
        for k in pa[i]:
            diff = numpy.abs(pa[i][k] - pb[i][k]).max()
            assert diff < tol, "layer %d %s diff %g" % (i, k, diff)


def _mnist(tmp_path, fused_cfg, max_epochs=2, train=130, valid=60, mb=40):
    """Train sizes chosen so a segment is NOT a multiple of the window
    (130/40 -> 4 minibatches incl. a 10-sample padded tail): windows hit
    both the segment-boundary stop and the tail mask."""
    from znicz_tpu.samples import mnist
    _seed()
    wf = mnist.build(
        layers=root.mnistr_conv.layers,
        loader_config={"synthetic_train": train, "synthetic_valid": valid,
                       "minibatch_size": mb},
        decision_config={"max_epochs": max_epochs, "fail_iterations": 50},
        snapshotter_config={"prefix": "fw", "interval": 100,
                            "time_interval": 1e9, "compression": "",
                            "directory": str(tmp_path)},
        fused=dict(fused_cfg))
    wf.initialize(device=JaxDevice())
    wf.run()
    return wf


def test_window8_equals_window1(tmp_path, float64_engine):
    wf_w = _mnist(tmp_path, {"pool_impl": "gather", "window": 8})
    wf_1 = _mnist(tmp_path, {"pool_impl": "gather", "window": 1})
    assert wf_w.fused_trainer.window == 8
    assert wf_w.fused_trainer._use_device_data
    _assert_same_trajectory(wf_w, wf_1)


def test_window_host_path_equals_device_path(tmp_path, float64_engine):
    wf_d = _mnist(tmp_path, {"pool_impl": "gather", "window": 4})
    wf_h = _mnist(tmp_path, {"pool_impl": "gather", "window": 4,
                             "device_data": False})
    assert wf_d.fused_trainer._use_device_data
    assert not wf_h.fused_trainer._use_device_data
    _assert_same_trajectory(wf_d, wf_h)


@pytest.mark.parametrize("mesh", [None, 4], ids=["one_device", "mesh4"])
def test_window_lr_schedule_boundary_mid_window(tmp_path, float64_engine,
                                                mesh):
    """arbitrary_step boundary at train step 3 with window=8: the drop
    lands INSIDE the first window.  Equality with the per-minibatch run
    proves policy(k) reaches exactly step k, on one device and where the
    net keeps its placed hypers replicated on a data mesh (the tier-1
    twin: test_placed_hypers.py)."""
    from znicz_tpu.samples import cifar

    schedule = {"do": True, "lr_policy_name": "arbitrary_step",
                "bias_lr_policy_name": "arbitrary_step",
                "lr_parameters": {
                    "lrs_with_lengths": [(1, 3), (0.1, 100000)]},
                "bias_lr_parameters": {
                    "lrs_with_lengths": [(1, 3), (0.1, 100000)]}}

    def run(window):
        _seed()
        fused_cfg = {"pool_impl": "gather", "window": window}
        if mesh is not None:
            fused_cfg["mesh"] = mesh
        wf = cifar.build(
            loader_config={"synthetic_train": 200, "synthetic_valid": 80,
                           "minibatch_size": 40},
            decision_config={"max_epochs": 2, "fail_iterations": 100},
            snapshotter_config={"directory": str(tmp_path),
                                "compression": ""},
            lr_adjuster_config=dict(schedule),
            fused=fused_cfg)
        wf.initialize(device=JaxDevice())
        wf.run()
        return wf

    wf_w = run(8)
    wf_1 = run(1)
    # schedule ticked once per MINIBATCH, not per window
    assert wf_w.lr_adjuster._minibatches_count == \
        wf_1.lr_adjuster._minibatches_count
    _assert_same_trajectory(wf_w, wf_1)


def test_cifar_caffe_mesh_window8_equals_window1(tmp_path, float64_engine):
    """Fused CIFAR-caffe with window=8 on the 8-device (data x model)
    mesh, trajectory equal to window=1."""
    from znicz_tpu.samples import cifar

    def run(window):
        _seed()
        wf = cifar.build(
            loader_config={"synthetic_train": 200, "synthetic_valid": 80,
                           "minibatch_size": 40},
            decision_config={"max_epochs": 2, "fail_iterations": 100},
            snapshotter_config={"directory": str(tmp_path),
                                "compression": ""},
            fused={"mesh": 8, "model_parallel": 2,
                   "pool_impl": "gather", "window": window})
        wf.initialize(device=JaxDevice())
        wf.run()
        return wf

    _assert_same_trajectory(run(8), run(1))


def test_window_stats_replace_evaluator_compute(tmp_path, float64_engine):
    """The evaluator consumes the trainer's in-scan window stats on TRAIN
    windows (output holds only the last minibatch) and still computes
    VALID stats itself from the compiled forward's output."""
    wf = _mnist(tmp_path, {"pool_impl": "gather", "window": 8},
                max_epochs=1)
    ev = wf.evaluator
    assert ev.stats_source is wf.fused_trainer
    # after the run the trainer's last dispatch was a VALID minibatch ->
    # window_stats cleared; the decision still recorded TRAIN epoch stats
    assert wf.fused_trainer.window_stats is None
    assert wf.decision.epoch_n_err[2] is not None  # TRAIN
    assert wf.decision.epoch_n_err[1] is not None  # VALID


def _approximator(tmp_path, fused_cfg, max_epochs=3):
    from znicz_tpu.samples import approximator
    _seed()
    wf = approximator.build(
        loader_config={"minibatch_size": 64},
        decision_config={"max_epochs": max_epochs,
                         "fail_iterations": 100},
        snapshotter_config={"prefix": "fwm", "interval": 100,
                            "time_interval": 1e9, "compression": "",
                            "directory": str(tmp_path)},
        fused=dict(fused_cfg))
    wf.initialize(device=JaxDevice())
    wf.run()
    return wf


def _assert_same_mse_trajectory(wf_a, wf_b, tol=1e-12):
    ma, mb = wf_a.decision.epoch_metrics, wf_b.decision.epoch_metrics
    for ca, cb in zip(ma, mb):
        if ca is None or cb is None:
            assert ca is None and cb is None
            continue
        for a, b in zip(ca, cb):
            assert abs(a - b) < tol, (ma, mb)
    pa, pb = _params(wf_a), _params(wf_b)
    assert set(pa) == set(pb)
    for i in pa:
        for k in pa[i]:
            diff = numpy.abs(pa[i][k] - pb[i][k]).max()
            assert diff < tol, "layer %d %s diff %g" % (i, k, diff)


@pytest.mark.parametrize("valid", [200, 0], ids=["valid", "no_valid"])
def test_mse_window8_equals_window1(tmp_path, float64_engine, monkeypatch,
                                    valid):
    """The windowed MSE fast path: float64 window=8 (sliced device
    data, in-scan [sum,max,min] metrics) == window=1 (per-minibatch
    step_mse + host evaluator) — epoch metrics and parameters, across
    epochs with reshuffles and a padded tail minibatch (600 train / 64
    -> 10 minibatches, 24-sample tail).  With NO validation split TRAIN
    is the epoch's last served segment and the loader reshuffles IN
    PLACE while serving the epoch-final minibatch — i.e. mid
    window-collection: the sliced path must train that window on the
    order its starts were collected against (rematerializing at flush
    time trained every epoch's tail window on next-epoch rows)."""
    from znicz_tpu.samples import approximator
    monkeypatch.setattr(approximator.ApproximatorLoader, "SYNTH_VALID",
                        valid)
    wf_w = _approximator(tmp_path, {"window": 8})
    wf_1 = _approximator(tmp_path, {"window": 1})
    assert wf_w.loader.class_lengths[1] == valid
    assert wf_w.fused_trainer.window == 8
    assert wf_w.fused_trainer._use_device_data
    assert wf_w.fused_trainer._use_sliced
    _assert_same_mse_trajectory(wf_w, wf_1)


def test_mse_window_host_stacked_equals_sliced(tmp_path, float64_engine):
    """The host-stacked MSE window (non-qualifying loaders' fallback)
    equals the sliced device path exactly."""
    wf_h = _approximator(tmp_path, {"window": 4, "device_data": False})
    wf_s = _approximator(tmp_path, {"window": 4})
    assert not wf_h.fused_trainer._use_device_data
    assert wf_s.fused_trainer._use_sliced
    _assert_same_mse_trajectory(wf_h, wf_s)


def test_mse_window_class_targets_equals_window1(tmp_path,
                                                 float64_engine):
    """Windowed MSE with CLASS TARGETS (kanji-style): the in-scan
    nearest-class-target n_err (fused._get_window_fn_mse) must equal
    the per-minibatch evaluator's host loop integer-for-integer, along
    with metrics and params — float64, window=4 vs window=1 on the
    host-stacked path (image loaders do not qualify for device data)."""
    from znicz_tpu.samples import kanji

    def run(window):
        _seed()
        wf = kanji.build(
            loader_config={
                "minibatch_size": 30,
                "train_paths": [str(tmp_path / ("kj%d" % window) / "train")],
                "target_paths": [str(tmp_path / ("kj%d" % window) /
                                     "target")]},
            decision_config={"max_epochs": 2, "fail_iterations": 100},
            snapshotter_config={"prefix": "kw%d" % window,
                                "interval": 100, "time_interval": 1e9,
                                "compression": "",
                                "directory": str(tmp_path)},
            fused={"window": window})
        wf.initialize(device=JaxDevice())
        wf.run()
        return wf

    wf_w = run(4)
    wf_1 = run(1)
    assert wf_w.fused_trainer.window == 4
    assert not wf_w.fused_trainer._use_device_data  # host-stacked path
    assert wf_w.fused_trainer.net.class_targets is not None
    _assert_same_mse_trajectory(wf_w, wf_1)
    assert list(wf_w.decision.epoch_n_err) == \
        list(wf_1.decision.epoch_n_err)
    assert wf_w.decision.epoch_n_err[2] is not None
