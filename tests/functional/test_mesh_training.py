"""Mesh-sharded asynchronous fused training pins (ISSUE 6).

All four fused window dispatch modes and the PR 5 asynchronous control
plane run data-parallel over a ``jax.sharding`` mesh (the conftest
forces 8 virtual CPU host devices): window inputs shard ``P(None,
"data", ...)``, the epoch accumulators stay device-resident SHARDED
partials (leading shard axis, ``P("data", ...)``), and the one stats
all-reduce per segment is folded into the segment-final window
executable.  These tests pin:

* sharded async aggregates == single-device sync aggregates: integer
  n_err/confusion EXACT; max_err_sum and the MSE max/min within
  MESH_MAX_ULPS float32 units in the last place (the max itself is
  reduction-order independent, but each sample's float32 sum under it
  is made by a differently partitioned program); the MSE SUM metric is
  the ONE documented f32 reassociation (per-shard sums then one
  cross-shard sum) and holds to MESH_MSE_RTOL; parameters agree to
  MESH_PARAM_TOL (the gradient psum reassociates the same batch sum);
* mesh async == mesh sync BIT-identical for the integer/max aggregates
  (both fold the same per-shard partials, only the place of the final
  reduce differs);
* zero mid-epoch d2h under the mesh: telemetry ``d2h_calls`` per epoch
  == segments, ``trainer.readbacks`` == segments — the PR 5 invariant
  survives sharding;
* a batch not divisible by the data shards raises the existing loud
  error, and ``mesh=None`` keeps the PR 5 accumulator layout
  (no leading shard axis, no ``final`` executable variants).

Fast lane (tier-1): wine-sized FC topologies, f32.
"""

import numpy
import pytest

import jax

from znicz_tpu.core.config import root
from znicz_tpu.core import prng, telemetry
from znicz_tpu.core.backends import JaxDevice
from znicz_tpu.parallel import fused, make_mesh
from znicz_tpu.standard_workflow import StandardWorkflow

#: f32 tolerance pins for the documented reduction-order deviations
#: under a data mesh (docs/distributed.md "Numerical pins")
MESH_MSE_RTOL = 1e-6
MESH_PARAM_TOL = 1e-5
#: a maximum (or minimum) over samples is exact whatever the order, but
#: the float32 per-sample sums it ranges over come from two programs
#: that XLA partitions and fuses differently (one device against a
#: data=4 mesh), and their parameters already differ by the gradient
#: psum's reassociation: the winner reads one unit in the last place
#: apart on this CPU backend (0.43518469 against 0.43518466), so mesh
#: against ONE DEVICE allows a few; mesh against mesh stays exact
MESH_MAX_ULPS = 4

FC_LAYERS = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
     "<-": {"learning_rate": 0.1}},
    {"type": "softmax", "->": {"output_sample_shape": 3},
     "<-": {"learning_rate": 0.1}},
]


def _seed():
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)


def _wine(tmp_path, fused_cfg, max_epochs=3, prefix="mesh"):
    import znicz_tpu.loader.loader_wine  # noqa: F401 (registry)
    _seed()
    wf = StandardWorkflow(
        None, layers=[dict(l) for l in FC_LAYERS],
        loader_name="wine_loader",
        # wine: 178 samples / mb 10 -> 18 minibatches; batch 10 is not
        # divisible by 4 shards, so mesh runs use mb 16 (see callers)
        loader_config={"minibatch_size": fused_cfg.pop("_mb", 16)},
        decision_config={"max_epochs": max_epochs,
                         "fail_iterations": 100},
        snapshotter_config={"prefix": prefix, "interval": 10 ** 9,
                            "time_interval": 1e9, "compression": "",
                            "directory": str(tmp_path)},
        fused=dict(fused_cfg))
    wf.initialize(device=JaxDevice())
    wf.run()
    return wf


def _aggregates(wf):
    return (list(wf.decision.epoch_n_err),
            [None if c is None else numpy.asarray(c)
             for c in wf.decision.confusion_matrixes],
            list(wf.decision.max_err_y_sums))


def _assert_aggregates_equal(wf_a, wf_b, max_ulps=0):
    ne_a, cm_a, mx_a = _aggregates(wf_a)
    ne_b, cm_b, mx_b = _aggregates(wf_b)
    assert ne_a == ne_b
    for ca, cb in zip(cm_a, cm_b):
        if ca is None or cb is None:
            assert ca is None and cb is None
            continue
        numpy.testing.assert_array_equal(ca, cb)
    # max_err_sum is a MAX — reduction-order independent, exact across
    # the shard fold of one program (``max_ulps``: MESH_MAX_ULPS)
    numpy.testing.assert_array_max_ulp(
        numpy.float32(mx_a), numpy.float32(mx_b), maxulp=max_ulps)


def _assert_params_close(wf_a, wf_b, tol=MESH_PARAM_TOL):
    pa = wf_a.fused_trainer.host_params()
    pb = wf_b.fused_trainer.host_params()
    for i, (la, lb) in enumerate(zip(pa, pb)):
        assert set(la) == set(lb)
        for k in la:
            diff = numpy.abs(la[k] - lb[k]).max()
            assert diff < tol, "layer %d %s diff %g" % (i, k, diff)


def test_mesh_async_equals_single_device(tmp_path):
    """4-way data mesh, async windows vs. unsharded async windows:
    integer epoch aggregates EXACT, the max_err_sum float within
    MESH_MAX_ULPS; params within the documented gradient-psum
    reassociation tolerance."""
    wf_m = _wine(tmp_path, {"window": 4, "mesh": 4, "_mb": 16},
                 prefix="m4")
    wf_1 = _wine(tmp_path, {"window": 4, "_mb": 16}, prefix="m1")
    assert wf_m.fused_trainer.net.data_shards == 4
    assert wf_1.fused_trainer.net.data_shards == 1
    assert wf_m.fused_trainer._use_device_data
    _assert_aggregates_equal(wf_m, wf_1, max_ulps=MESH_MAX_ULPS)
    _assert_params_close(wf_m, wf_1)


def test_mesh_async_equals_mesh_sync(tmp_path):
    """On the SAME mesh, async (sharded accumulators + one folded
    all-reduce per segment) == sync (per-window host-reduced partials)
    bit-for-bit on every integer/max aggregate AND the parameters —
    both modes run the same sharded step executables."""
    wf_a = _wine(tmp_path, {"window": 4, "mesh": 4, "_mb": 16},
                 prefix="ma")
    wf_s = _wine(tmp_path, {"window": 4, "mesh": 4, "_mb": 16,
                            "async_windows": False}, prefix="ms")
    assert wf_a.fused_trainer.async_windows
    assert not wf_s.fused_trainer.async_windows
    _assert_aggregates_equal(wf_a, wf_s)
    pa = wf_a.fused_trainer.host_params()
    pb = wf_s.fused_trainer.host_params()
    for la, lb in zip(pa, pb):
        for k in la:
            numpy.testing.assert_array_equal(la[k], lb[k])


def test_mesh_host_stacked_equals_device_path(tmp_path):
    """The shard-major staging layout (host-stacked collection feeding
    per-shard contiguous device_put blocks) trains the same trajectory
    as the device-resident indexed path on the same mesh."""
    wf_h = _wine(tmp_path, {"window": 4, "mesh": 4, "_mb": 16,
                            "device_data": False}, prefix="mh")
    wf_d = _wine(tmp_path, {"window": 4, "mesh": 4, "_mb": 16},
                 prefix="md")
    assert not wf_h.fused_trainer._use_device_data
    assert wf_d.fused_trainer._use_device_data
    _assert_aggregates_equal(wf_h, wf_d)
    pa = wf_h.fused_trainer.host_params()
    pb = wf_d.fused_trainer.host_params()
    for la, lb in zip(pa, pb):
        for k in la:
            numpy.testing.assert_array_equal(la[k], lb[k])


def test_mesh_zero_mid_epoch_d2h(tmp_path):
    """The PR 5 invariant under the mesh: exactly ONE batched d2h per
    segment (telemetry call meter) and ``trainer.readbacks`` ==
    segments — the sharded accumulators never leak mid-epoch
    transfers."""
    root.common.telemetry.enabled = True
    telemetry.reset()
    try:
        import znicz_tpu.loader.loader_wine  # noqa: F401
        _seed()
        wf = StandardWorkflow(
            None, layers=[dict(l) for l in FC_LAYERS],
            loader_name="wine_loader",
            loader_config={"minibatch_size": 16},
            decision_config={"max_epochs": 3, "fail_iterations": 100},
            snapshotter_config={"prefix": "mz", "interval": 10 ** 9,
                                "time_interval": 1e9, "compression": "",
                                "directory": str(tmp_path)},
            fused={"window": 4, "mesh": 4})
        wf.initialize(device=JaxDevice())
        at_epoch = []
        orig_hook = wf.decision.on_training_finished

        def hook():
            at_epoch.append((
                telemetry.counter("transfer.d2h_calls").value,
                telemetry.counter("trainer.readbacks").value))
            orig_hook()

        wf.decision.on_training_finished = hook
        wf.run()
        summary = telemetry.summary()
    finally:
        root.common.telemetry.enabled = False
    assert len(at_epoch) == 3
    d2h_calls, readbacks = zip(*at_epoch)
    # wine has no VALID split here -> 1 TRAIN segment per epoch
    assert readbacks == (1, 2, 3), readbacks
    assert d2h_calls == (1, 2, 3), d2h_calls
    # mesh extents surface in the telemetry summary
    assert summary["data_shards"] == 4
    assert summary["model_shards"] == 1


def _approximator(tmp_path, fused_cfg, prefix, max_epochs=2):
    from znicz_tpu.samples import approximator
    _seed()
    wf = approximator.build(
        loader_config={"minibatch_size": 64},
        decision_config={"max_epochs": max_epochs,
                         "fail_iterations": 100},
        snapshotter_config={"prefix": prefix, "interval": 10 ** 9,
                            "time_interval": 1e9, "compression": "",
                            "directory": str(tmp_path)},
        fused=dict(fused_cfg))
    wf.initialize(device=JaxDevice())
    wf.run()
    return wf


def test_mesh_mse_async_equals_single_device(tmp_path):
    """MSE objective (approximator, sliced device path) on the mesh:
    n_err exact, max/min metrics within MESH_MAX_ULPS, the SUM metric
    within the documented MESH_MSE_RTOL reassociation pin."""
    wf_m = _approximator(tmp_path, {"window": 4, "mesh": 4}, "mm4")
    wf_1 = _approximator(tmp_path, {"window": 4}, "mm1")
    assert wf_m.fused_trainer.net.data_shards == 4
    assert wf_m.fused_trainer._use_sliced
    for ma, mb in zip(wf_m.decision.epoch_metrics,
                      wf_1.decision.epoch_metrics):
        if ma is None or mb is None:
            assert ma is None and mb is None
            continue
        # [sum, max, min]: the sum reassociates across shards
        assert abs(ma[0] - mb[0]) <= MESH_MSE_RTOL * abs(mb[0]), (ma, mb)
        numpy.testing.assert_array_max_ulp(
            numpy.float32(ma[1:3]), numpy.float32(mb[1:3]),
            maxulp=MESH_MAX_ULPS)
    _assert_params_close(wf_m, wf_1)


def test_mesh_mse_host_stacked_matches_sliced(tmp_path):
    """MSE host-stacked windows (shard-major staging, run_window_mse)
    on the mesh equal the sliced device path bitwise — both feed the
    same sharded executED rows."""
    wf_h = _approximator(tmp_path, {"window": 4, "mesh": 4,
                                    "device_data": False}, "mmh")
    wf_s = _approximator(tmp_path, {"window": 4, "mesh": 4}, "mms")
    assert not wf_h.fused_trainer._use_device_data
    assert wf_s.fused_trainer._use_sliced
    for ma, mb in zip(wf_h.decision.epoch_metrics,
                      wf_s.decision.epoch_metrics):
        if ma is None or mb is None:
            assert ma is None and mb is None
            continue
        assert tuple(ma) == tuple(mb)
    pa = wf_h.fused_trainer.host_params()
    pb = wf_s.fused_trainer.host_params()
    for la, lb in zip(pa, pb):
        for k in la:
            numpy.testing.assert_array_equal(la[k], lb[k])


@pytest.mark.parametrize("valid", [200, 0], ids=["valid", "no_valid"])
def test_mesh_mse_window8_equals_window1(tmp_path, monkeypatch, valid):
    """The sliced MSE window on the data=4 mesh against the
    per-minibatch step on the same mesh, three epochs with reshuffles
    and a padded tail minibatch.  With NO validation split TRAIN is the
    epoch's last served segment and the loader reshuffles IN PLACE
    while the epoch's last window is collected: the window must train
    on the order its starts were collected against (the float64 twin on
    one device: test_fused_window.py::test_mse_window8_equals_window1).
    A window that read next-epoch rows moves the parameters by orders
    of magnitude more than MESH_PARAM_TOL."""
    from znicz_tpu.samples import approximator
    monkeypatch.setattr(approximator.ApproximatorLoader, "SYNTH_VALID",
                        valid)
    wf_w = _approximator(tmp_path, {"window": 8, "mesh": 4}, "mw8",
                         max_epochs=3)
    wf_1 = _approximator(tmp_path, {"window": 1, "mesh": 4}, "mw1",
                         max_epochs=3)
    assert wf_w.loader.class_lengths[1] == valid
    assert wf_w.fused_trainer._use_sliced
    assert not wf_1.fused_trainer._use_device_data
    for ma, mb in zip(wf_w.decision.epoch_metrics,
                      wf_1.decision.epoch_metrics):
        if ma is None or mb is None:
            assert ma is None and mb is None
            continue
        assert abs(ma[0] - mb[0]) <= MESH_MSE_RTOL * abs(mb[0]), (ma, mb)
    _assert_params_close(wf_w, wf_1)


def test_mesh_batch_not_divisible_raises():
    """The existing loud error: a window batch that does not divide by
    the data shards is rejected before any dispatch."""
    _seed()
    mesh = make_mesh(4, model_parallel=1)
    net = fused.FusedNet(FC_LAYERS, 5, mesh=mesh,
                         rand=prng.RandomGenerator().seed(7))
    xs = numpy.zeros((2, 10, 5), numpy.float32)   # 10 % 4 != 0
    ls = numpy.zeros((2, 10), numpy.int32)
    hy = jax.tree.map(lambda *l: numpy.asarray(l, numpy.float32),
                      *[net.hypers] * 2)
    with pytest.raises(ValueError, match="not divisible"):
        net.run_window(xs, ls, [10, 10], hy)
    # the shard-major staging ring enforces the same contract
    from znicz_tpu.units.fused_trainer import _StagingRing
    ring = _StagingRing(2)
    with pytest.raises(ValueError, match="not divisible"):
        ring.get("x", (2, 10, 5), numpy.float32, shards=4)


def test_mesh_none_keeps_pr5_layout(tmp_path):
    """Without a mesh the accumulator layout, window-fn cache keys and
    stats shapes stay exactly the PR 5 ones: no leading shard axis, no
    ``final`` executable variants (final=... normalizes to one cached
    entry), scalar max_err_sum."""
    wf = _wine(tmp_path, {"window": 4, "_mb": 16}, max_epochs=1,
               prefix="mnone")
    net = wf.fused_trainer.net
    assert net.data_shards == 1
    # every cached softmax window key carries final=False (the final
    # flag is meaningless without data shards — one executable per
    # (K, mode) geometry)
    for key in net._window_fns:
        assert key[-1] is False, key
    acc = net._window_acc()
    assert acc["n_err"].shape == (2,)
    assert acc["max_err_sum"].shape == ()
    net.reset_window_acc()
