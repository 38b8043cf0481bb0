"""Fused execution mode — the SPMD hot loop joined to the control plane.

The fused jitted train step and the
StandardWorkflow epoch control plane must be ONE training path.  These
tests prove the join:

* ``fused=True`` builds the canonical graph with forwards+gds collapsed
  into one compiled unit, and the whole trajectory (per-epoch integer
  error counts) EQUALS the unit-graph path's in float64 — the unit path
  is the executable spec, so any fused-side numeric or bookkeeping drift
  fails loudly.
* VALID epochs run through the compiled forward (the n_err equality
  covers them).
* LR schedules apply per iteration as traced arguments (no recompile) —
  the CIFAR-caffe config's arbitrary_step policy runs in both modes and
  trajectories still match.
* snapshot/resume is bit-exact: params + optimizer state + dropout key +
  loader position all restore (the fused twin of
  test_golden.test_mnist_mlp_resume_retrain_is_exact).
* the whole thing compiles and executes sharded over the 8-device
  virtual mesh (data x model), including VALID-epoch inference.
"""

import os

import numpy
import pytest

pytestmark = pytest.mark.slow

from znicz_tpu.core.config import root
from znicz_tpu.core import prng
from znicz_tpu.core.backends import JaxDevice
from znicz_tpu.core.snapshotter import SnapshotterToFile
from znicz_tpu.units.nn_units import load_snapshot_into_workflow
from znicz_tpu.loader.base import VALID, TRAIN

MNIST_LOADER = {"synthetic_train": 120, "synthetic_valid": 60,
                "minibatch_size": 30}
CIFAR_LOADER = {"synthetic_train": 200, "synthetic_valid": 80,
                "minibatch_size": 40}


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


def _mnist_conv(tmp_path, max_epochs, prefix="fusedwf", fused=None):
    from znicz_tpu.samples import mnist
    kwargs = {} if fused is None else {"fused": fused}
    wf = mnist.build(
        layers=root.mnistr_conv.layers,
        loader_config=dict(MNIST_LOADER),
        decision_config={"max_epochs": max_epochs, "fail_iterations": 50},
        snapshotter_config={"prefix": prefix, "interval": 1,
                            "time_interval": 0, "compression": "",
                            "directory": str(tmp_path)},
        **kwargs)
    wf.initialize(device=JaxDevice())
    return wf


def _host_params_by_layer(wf):
    """{layer index: {"w","b"}} host params from either execution mode."""
    if wf.fused_trainer is not None:
        return {i: p for i, p in enumerate(wf.fused_trainer.host_params())
                if p}
    out = {}
    for i, f in enumerate(wf.forwards):
        if getattr(f, "weights", None) is not None and f.weights:
            out[i] = {"w": numpy.array(f.weights.mem)}
            if f.bias:
                out[i]["b"] = numpy.array(f.bias.mem)
    return out


def test_fused_mode_matches_unit_graph_trajectory(tmp_path, float64_engine):
    """Same seeds => the fused MNIST conv workflow reproduces the
    unit-graph per-epoch error integers, and the final weights agree to
    float64 association noise."""
    _seed()
    wf_f = _mnist_conv(tmp_path, max_epochs=2, fused={"pool_impl": "gather"})
    wf_f.run()
    _seed()
    wf_u = _mnist_conv(tmp_path, max_epochs=2)
    wf_u.run()

    assert wf_f.loader.epoch_number == 2
    assert list(wf_f.decision.epoch_n_err) == list(wf_u.decision.epoch_n_err)
    assert wf_f.decision.epoch_n_err[VALID] is not None

    pf, pu = _host_params_by_layer(wf_f), _host_params_by_layer(wf_u)
    assert set(pf) == set(pu)
    for i in pf:
        for k in pf[i]:
            diff = numpy.abs(pf[i][k] - pu[i][k]).max()
            assert diff < 1e-12, "layer %d %s diff %g" % (i, k, diff)

    # the graph really is the control plane: one worker unit, no gds
    assert wf_f.gds == []
    assert wf_f.forwards == [wf_f.fused_trainer]
    assert wf_f.evaluator is not None and wf_f.decision is not None


def test_fused_resume_is_bit_exact(tmp_path, float64_engine):
    """Interrupted-at-epoch-2-and-resumed == trained-straight-through,
    on the FUSED path: snapshot carries params, optimizer state, dropout
    key, live hyperparameters, loader position and PRNG streams."""
    _seed()
    wf_a = _mnist_conv(tmp_path, 4, prefix="straight",
                       fused={"pool_impl": "gather"})
    wf_a.run()
    errs_a = list(wf_a.decision.epoch_n_err)
    params_a = _host_params_by_layer(wf_a)

    _seed()
    wf_b = _mnist_conv(tmp_path, 2, prefix="interrupted",
                       fused={"pool_impl": "gather"})
    wf_b.run()
    snap = wf_b.snapshotter.destination
    assert snap and os.path.exists(snap)

    _seed()
    wf_c = _mnist_conv(tmp_path, 4, prefix="resumed",
                       fused={"pool_impl": "gather"})
    load_snapshot_into_workflow(SnapshotterToFile.import_(snap), wf_c)
    assert wf_c.loader.epoch_number == 2
    wf_c.run()

    assert wf_c.loader.epoch_number == 4
    assert list(wf_c.decision.epoch_n_err) == errs_a
    params_c = _host_params_by_layer(wf_c)
    for i in params_a:
        for k in params_a[i]:
            diff = numpy.abs(params_a[i][k] - params_c[i][k]).max()
            assert diff == 0.0, \
                "layer %d %s resumed diff %g" % (i, k, diff)


def test_fused_cifar_caffe_on_mesh_matches_unit_graph(tmp_path,
                                                      float64_engine):
    """The flagship: CIFAR-caffe (conv + max/avg pool + strict relu +
    LRN + arbitrary_step LR schedule + ortho + momentum) trains through
    StandardWorkflow in fused mode on the 8-device (data x model) mesh —
    and the whole trajectory matches the unit-graph mode exactly."""
    from znicz_tpu.samples import cifar

    # LR schedule with a boundary INSIDE the run (10 train steps,
    # 10x drop after step 3): the fused adjuster must apply policy(k)
    # to update k exactly like the unit graph — an off-by-one shows up
    # as trajectory divergence from step 4 on
    schedule = {"do": True, "lr_policy_name": "arbitrary_step",
                "bias_lr_policy_name": "arbitrary_step",
                "lr_parameters": {
                    "lrs_with_lengths": [(1, 3), (0.1, 100000)]},
                "bias_lr_parameters": {
                    "lrs_with_lengths": [(1, 3), (0.1, 100000)]}}

    def run(fused_cfg):
        _seed()
        kwargs = {"fused": fused_cfg} if fused_cfg is not None else {}
        wf = cifar.build(
            loader_config=dict(CIFAR_LOADER),
            decision_config={"max_epochs": 2, "fail_iterations": 100},
            snapshotter_config={"directory": str(tmp_path),
                                "compression": ""},
            lr_adjuster_config=dict(schedule),
            **kwargs)
        wf.initialize(device=JaxDevice())
        wf.run()
        return wf

    wf_f = run({"mesh": 8, "model_parallel": 2,
                "pool_impl": "gather"})
    wf_u = run(None)
    assert list(wf_f.decision.epoch_n_err) == list(wf_u.decision.epoch_n_err)
    assert wf_f.decision.epoch_n_err[TRAIN] is not None
    # LR schedule engaged through proxies (traced — same compiled step)
    assert wf_f.lr_adjuster._minibatches_count > 0
    for proxy in wf_f.fused_trainer.gd_proxies:
        assert proxy.learning_rate > 0
    pf, pu = _host_params_by_layer(wf_f), _host_params_by_layer(wf_u)
    for i in pf:
        diff = numpy.abs(pf[i]["w"] - pu[i]["w"]).max()
        assert diff < 1e-12, "layer %d dw %g" % (i, diff)


def test_fused_extract_forward_workflow(tmp_path, float64_engine):
    """Inference extraction from a fused workflow: params map onto a
    forward-only unit graph through the broadcast protocol and predict
    the same classes the fused forward does."""
    _seed()
    wf = _mnist_conv(tmp_path, 1, fused={"pool_impl": "gather"})
    wf.run()

    from znicz_tpu.loader.loader_mnist import MnistLoader
    fwd_wf = wf.extract_forward_workflow(
        loader_factory=lambda w: MnistLoader(
            w, name="loader", **dict(MNIST_LOADER)))
    fwd_wf.initialize(device=JaxDevice())
    fwd_wf.run()
    out_unit = numpy.array(fwd_wf.forwards[-1].output.mem)

    x = numpy.array(fwd_wf.loader.minibatch_data.mem)
    out_fused = numpy.asarray(wf.fused_trainer.net.predict(x))
    assert out_unit.shape == out_fused.shape
    assert numpy.argmax(out_unit, 1).tolist() == \
        numpy.argmax(out_fused, 1).tolist()


def test_fused_mse_workflow_matches_unit_graph(tmp_path, float64_engine):
    """MSE-head topologies train fused through StandardWorkflow:
    the Approximator regression sample in fused
    mode reproduces the unit-graph epoch metrics and weights."""
    from znicz_tpu.samples import approximator

    def run(fused_cfg):
        _seed()
        kwargs = {"fused": fused_cfg} if fused_cfg else {}
        wf = approximator.build(
            loader_config={"synthetic_train": 60, "synthetic_valid": 30,
                           "minibatch_size": 30},
            decision_config={"max_epochs": 2, "fail_iterations": 20},
            snapshotter_config={"directory": str(tmp_path),
                                "compression": ""},
            **kwargs)
        wf.initialize(device=JaxDevice())
        wf.run()
        return wf

    wf_f = run({"mesh": 2})  # minibatch 30 shards over 2 data devices
    wf_u = run(None)
    for mf, mu in zip(wf_f.decision.epoch_metrics,
                      wf_u.decision.epoch_metrics):
        if mf is None:
            assert mu is None
            continue
        for a, b in zip(mf, mu):
            assert abs(a - b) < 1e-9, (mf, mu)
    pf, pu = _host_params_by_layer(wf_f), _host_params_by_layer(wf_u)
    for i in pf:
        diff = numpy.abs(pf[i]["w"] - pu[i]["w"]).max()
        assert diff < 1e-12, "layer %d dw %g" % (i, diff)


def test_fused_rollback_restores_state(tmp_path, float64_engine):
    """FusedNNRollback: LR decay + state restore after consecutive
    non-improvements; LR bump + state stash on improvement."""
    _seed()
    # 2 epochs: the epoch-1 end fires rollback while training is still
    # incomplete (a 1-epoch run completes before rollback ever runs)
    wf = _mnist_conv(tmp_path, 2, fused={"pool_impl": "gather"})
    rollback = wf.link_rollback(wf.snapshotter, minus_steps=2)
    wf.repeater.unlink_from(wf.snapshotter)
    wf.repeater.link_from(rollback)
    wf.run()

    trainer = wf.fused_trainer
    base_lr = trainer.gd_proxies[0].learning_rate
    # improvement epoch happened -> history stored, LR bumped
    assert rollback._history
    assert base_lr > 0
    stored = rollback._history[0]["params"]

    # force two non-improvement runs -> rollback fires
    wf.decision.improved <<= False
    rollback._first_run = False
    rollback.run()
    rollback.run()
    assert trainer.gd_proxies[0].learning_rate < base_lr
    restored = trainer.host_params()
    for p_s, p_r in zip(stored, restored):
        for k in p_s:
            assert numpy.array_equal(p_s[k], p_r[k])


def test_fused_zero_filter_matches_unit_graph(tmp_path, float64_engine):
    """Grouped-conv masking (zero_filter) in fused mode: the AlexNet
    grouping pattern trains identically to the unit graph — the mask
    re-zeroes before every update, so weight decay/ortho see masked
    weights on both paths."""
    from znicz_tpu.standard_workflow import StandardWorkflow
    import znicz_tpu.loader.loader_mnist  # noqa: F401

    layers = [
        {"name": "c1", "type": "conv_tanh",
         "->": {"n_kernels": 4, "kx": 3, "ky": 3},
         "<-": {"learning_rate": 0.1, "weights_decay": 0.001,
                "gradient_moment": 0.9}},
        {"name": "mp", "type": "max_pooling", "->": {"kx": 2, "ky": 2}},
        {"name": "zf", "type": "zero_filter", "grouping": 2},
        {"name": "c2", "type": "conv_tanh",
         "->": {"n_kernels": 6, "kx": 3, "ky": 3},
         "<-": {"learning_rate": 0.1, "weights_decay": 0.001,
                "gradient_moment": 0.9}},
        {"name": "sm", "type": "softmax",
         "->": {"output_sample_shape": 10},
         "<-": {"learning_rate": 0.1}},
    ]

    def run(fused):
        _seed()
        kwargs = {"fused": {"pool_impl": "gather"}} if fused else {}
        wf = StandardWorkflow(
            None, layers=layers, loader_name="mnist_loader",
            loader_config={"synthetic_train": 60, "synthetic_valid": 30,
                           "minibatch_size": 30},
            decision_config={"max_epochs": 2, "fail_iterations": 20},
            snapshotter_config={"directory": str(tmp_path),
                                "interval": 100, "time_interval": 1e9},
            **kwargs)
        wf.initialize(device=JaxDevice())
        wf.run()
        return wf

    wf_f = run(True)
    wf_u = run(False)
    assert list(wf_f.decision.epoch_n_err) == list(wf_u.decision.epoch_n_err)

    # the grouped conv's USED weights agree; compare them MASKED (the
    # unit path lets masked positions drift between passes, the fused
    # path keeps them at zero — both use zero)
    spec_params = wf_f.fused_trainer.host_params()
    c2_spec = wf_f.fused_trainer.net.specs[3]
    mask = c2_spec.weight_mask
    w_f = spec_params[3]["w"] * mask
    c2_unit = wf_u.forwards[3]
    w_u = numpy.array(c2_unit.weights.mem) * mask
    assert numpy.abs(w_f - w_u).max() < 1e-12
    # fused stored masked positions are exactly zero
    assert numpy.abs(spec_params[3]["w"] * (1 - mask)).max() == 0.0


def test_fused_alexnet_builds_and_trains(tmp_path):
    """The 21-layer AlexNet topology (grouped convs, LRN, dropout)
    trains on the fused path over the 8-device mesh."""
    from znicz_tpu.samples.research import alexnet
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    wf = alexnet.build(
        loader_config={"n_train": 16, "n_valid": 8, "minibatch_size": 8},
        decision_config={"max_epochs": 1, "fail_iterations": 5},
        snapshotter_config={"interval": 1000, "time_interval": 1e9,
                            "directory": str(tmp_path)},
        fused={"mesh": 8, "model_parallel": 2})
    wf.initialize(device=JaxDevice())
    wf.run()
    assert wf.fused_trainer is not None
    assert wf.loader.epoch_number == 1
    assert wf.decision.epoch_n_err[VALID] is not None
    # the grouped layers carry masks in their specs
    masked = [s for s in wf.fused_trainer.net.specs
              if getattr(s, "weight_mask", None) is not None]
    assert len(masked) == 4


def test_fused_weights_plotters_render(tmp_path, float64_engine):
    """The plotter tier keeps its role in fused mode: Weights2D and
    MultiHistogram read the trainer's device-backed weight views."""
    _seed()
    # 2 epochs: epoch-1's end fires the plotters while training is
    # still incomplete (the final iteration stops at the end point)
    wf = _mnist_conv(tmp_path, 2, fused={"pool_impl": "gather"})
    last = wf.link_weights_plotter(wf.snapshotter)
    last = wf.link_multi_hist_plotter(last)
    wf.repeater.unlink_from(wf.snapshotter)
    wf.repeater.link_from(last)
    wf.run()

    assert len(wf.weights_plotter) == 4   # conv, conv, fc, softmax
    for p in wf.weights_plotter:
        assert p.input is not None and p.input
    # the views track the TRAINED params
    for i, view in wf.fused_trainer.weight_views:
        trained = wf.fused_trainer.host_params()[i]["w"]
        numpy.testing.assert_array_equal(
            numpy.asarray(view.mem), trained)
    assert len(wf.multi_hist_plotter) == 4
    for p in wf.multi_hist_plotter:
        assert p.histograms, "histogram plotter never fired"
