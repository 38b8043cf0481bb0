"""Validation from the resident data set (ISSUE 26).

On the device-resident window path a VALID/TEST minibatch is taken as a
train window's is: its ``(batch,)`` indices cross to the device and the
rows are gathered there (``FusedNet.predict_indexed``); the loader fills
labels and targets at once and puts the row copy into ``minibatch_data``
off until a unit reads the buffer (``Array.defer``).  These tests pin:

* the indexed forward against the forward of the same rows sent from
  the host, softmax and MSE, one device and a 4-device data mesh;
* a fused run with a validation split against the same run with the
  buffer read on every VALID minibatch (which forces the copy and sends
  the trainer down the host-rows path it had before), with the two
  counters ``loader.fill_deferred`` / ``loader.fill_forced``;
* readers of ``minibatch_data`` (a saver in the graph, the ``Array``
  object held directly) seeing the rows an eager fill gives;
* the paths that never defer: streaming, ``window=1``, a custom fill.

Tier-1: small topologies in float32; both sides of every comparison run
the same compiled forward on the same bits, so equality is exact.
"""

import numpy
import pytest

import jax

from znicz_tpu.core import prng, telemetry
from znicz_tpu.core.backends import JaxDevice
from znicz_tpu.core.config import root
from znicz_tpu.loader.base import TRAIN, VALID
from znicz_tpu.loader.loader_mnist import MnistLoader
from znicz_tpu.parallel import fused, make_mesh
from znicz_tpu.standard_workflow import StandardWorkflow

CONV_LAYERS = [
    {"type": "conv_relu", "->": {"n_kernels": 4, "kx": 5, "ky": 5},
     "<-": {"learning_rate": 0.03}},
    {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
    {"type": "softmax", "->": {"output_sample_shape": 10},
     "<-": {"learning_rate": 0.03}},
]

MSE_LAYERS = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 6},
     "<-": {"learning_rate": 0.05}},
    {"type": "all2all", "->": {"output_sample_shape": 3},
     "<-": {"learning_rate": 0.05}},
]


class CustomFillLoader(MnistLoader):
    """A per-minibatch transform: the trainer may not take its rows from
    the resident set, so it is never asked to defer."""

    MAPPING = "mnist_custom_fill_test"

    def fill_minibatch(self):
        super(CustomFillLoader, self).fill_minibatch()
        self.minibatch_data.mem[:self.minibatch_size] *= 0.5


@pytest.fixture(autouse=True)
def _prng_streams_restored():
    """These tests seed the process-global streams; whatever runs after
    them in the same worker finds the streams as they were."""
    prng.get(1), prng.get(2)
    before = prng.states()
    yield
    prng.restore(before)


# -- (a) the indexed forward ------------------------------------------------

@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"],
                         ids=["f32", "bf16_resident_set"])
@pytest.mark.parametrize("objective", ["softmax", "mse"])
@pytest.mark.parametrize("mesh_size", [None, 4], ids=["one_device", "mesh4"])
def test_predict_indexed_equals_predict_of_host_rows(objective, mesh_size,
                                                     compute_dtype):
    rng = numpy.random.RandomState(7)
    data = rng.normal(size=(40, 6, 6, 1)).astype(numpy.float32)
    labels = rng.randint(0, 10, size=40)
    if objective == "softmax":
        layers = CONV_LAYERS
    else:
        layers = MSE_LAYERS
        data = data.reshape(40, 36)
    mesh = None if mesh_size is None else make_mesh(mesh_size)
    net = fused.FusedNet(
        [dict(l) for l in layers], input_sample_shape=data.shape[1:],
        mesh=mesh, rand=prng.RandomGenerator().seed(3),
        objective=objective,
        compute_dtype=compute_dtype and jax.numpy.dtype(compute_dtype))
    targets = None if objective == "softmax" else \
        rng.normal(size=(40, 3)).astype(numpy.float32)
    net.set_dataset(data, labels, targets=targets)
    # the set is kept in the compute dtype: the forward casts the host
    # rows to it anyway, and the gather commutes with the cast
    assert net._data_d.dtype == (compute_dtype or numpy.float32)
    # a partial minibatch: 11 real rows of 16, the tail padded with -1
    idx = numpy.full(16, -1, numpy.int32)
    idx[:11] = rng.permutation(40)[:11]
    rows = data[numpy.maximum(idx, 0)]
    if objective == "softmax":
        out, am = net.host_fetch(net.predict_indexed(idx, with_idx=True))
        ref, ref_am = net.host_fetch(net.predict_with_idx(rows))
        numpy.testing.assert_array_equal(am[:11], ref_am[:11])
        assert am.dtype == numpy.int32
    else:
        out = net.host_fetch(net.predict_indexed(idx))
        ref = net.host_fetch(net.predict(rows))
    numpy.testing.assert_array_equal(out[:11], ref[:11])
    # a padded slot reads row 0 (the window's own rule for -1)
    row0 = net.host_fetch(net.predict(numpy.repeat(data[:1], 16, axis=0)))
    numpy.testing.assert_array_equal(out[11:], row0[11:])
    if mesh is not None:
        placed = net._place_valid_indices(idx)
        assert len(placed.sharding.device_set) == 4
        assert placed.sharding.spec == jax.sharding.PartitionSpec("data")


# -- workflows ---------------------------------------------------------------

def _seed():
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)


def _build(tmp_path, fused_cfg, valid=50, loader_name="mnist_loader"):
    _seed()
    wf = StandardWorkflow(
        None, layers=[dict(l) for l in CONV_LAYERS],
        loader_name=loader_name,
        loader_config={"synthetic_train": 160, "synthetic_valid": valid,
                       "synthetic": True, "minibatch_size": 20,
                       "normalization_type": "none"},
        decision_config={"max_epochs": 2, "fail_iterations": 100},
        snapshotter_config={"prefix": "vr", "interval": 10 ** 9,
                            "time_interval": 1e9, "compression": "",
                            "directory": str(tmp_path)},
        fused=dict(fused_cfg))
    return wf


def _read_on_valid(wf, seen=None):
    """Read the loader's buffer right after every VALID minibatch is
    served, before the trainer runs: an eager reader."""
    loader = wf.loader
    orig = loader.run

    def run():
        orig()
        if loader.minibatch_class == VALID:
            rows = loader.minibatch_data.mem
            if seen is not None:
                n = loader.minibatch_size
                seen.append((loader.minibatch_indices.mem[:n].copy(),
                             rows[:n].copy()))
    loader.run = run


def _traced(fn):
    root.common.telemetry.enabled = True
    telemetry.reset()
    try:
        wf = fn()
        counters = {
            name: telemetry.counter("loader." + name).value
            for name in ("fill_deferred", "fill_forced", "minibatches")}
        fills = [s[5] for s in telemetry.spans() if s[0] == "loader.fill"]
    finally:
        root.common.telemetry.enabled = False
    return wf, counters, fills


def _run(wf):
    wf.initialize(device=JaxDevice())
    wf.run()
    return wf


def _assert_same_decisions(wf_a, wf_b):
    da, db = wf_a.decision, wf_b.decision
    assert list(da.epoch_n_err) == list(db.epoch_n_err)
    assert da.epoch_n_evaluated_samples == db.epoch_n_evaluated_samples
    for ca, cb in zip(da.confusion_matrixes, db.confusion_matrixes):
        if ca is None or cb is None:
            assert ca is None and cb is None
            continue
        numpy.testing.assert_array_equal(ca, cb)
    assert list(da.max_err_y_sums) == list(db.max_err_y_sums)
    for la, lb in zip(wf_a.fused_trainer.host_params(),
                      wf_b.fused_trainer.host_params()):
        for k in la:
            numpy.testing.assert_array_equal(la[k], lb[k])


# (b) and (e): 40 validation rows are two whole minibatches of 20, 50 leave
# a partial last one (10 rows, padded to 20 and masked by minibatch_size)
@pytest.mark.parametrize("valid", [40, 50], ids=["whole", "partial_tail"])
@pytest.mark.parametrize("fused_cfg", [{"window": 4},
                                       {"window": 4, "mesh": 4}],
                         ids=["one_device", "mesh4"])
def test_epoch_equals_host_rows_path(tmp_path, monkeypatch, valid,
                                     fused_cfg):
    n_valid_mb = -(-valid // 20)
    wf, counters, fills = _traced(
        lambda: _run(_build(tmp_path, fused_cfg, valid)))
    assert wf.fused_trainer._use_device_data and wf.loader.skip_fill
    assert counters["fill_deferred"] == 2 * n_valid_mb
    assert counters["fill_forced"] == 0
    # the fill span and its fault site are still there, labels filled
    assert len(fills) == 2 * n_valid_mb
    assert all(attrs["clazz"] == "validation" for attrs in fills)

    calls = []
    orig = fused.FusedNet.predict_with_idx
    monkeypatch.setattr(
        fused.FusedNet, "predict_with_idx",
        lambda self, x: calls.append(1) or orig(self, x))

    def forced():
        wf = _build(tmp_path, fused_cfg, valid)
        _read_on_valid(wf)
        return _run(wf)

    wf_old, counters_old, _ = _traced(forced)
    # every copy was forced, and the trainer then fed the host rows
    assert counters_old["fill_forced"] == counters_old["fill_deferred"] \
        == 2 * n_valid_mb
    assert len(calls) == 2 * n_valid_mb
    _assert_same_decisions(wf, wf_old)
    assert wf.decision.epoch_n_evaluated_samples[VALID] == valid
    assert wf.decision.epoch_n_evaluated_samples[TRAIN] == 160


def test_mse_epoch_equals_host_rows_path(tmp_path):
    """The sliced MSE path: targets fill on the host at once (the host
    evaluator reads them), rows come from the device."""
    from znicz_tpu.samples import approximator

    def run(read):
        _seed()
        wf = approximator.build(
            loader_config={"minibatch_size": 64},
            decision_config={"max_epochs": 2, "fail_iterations": 100},
            snapshotter_config={"prefix": "vm", "interval": 10 ** 9,
                                "time_interval": 1e9, "compression": "",
                                "directory": str(tmp_path)},
            fused={"window": 4})
        if read:
            _read_on_valid(wf)
        return _run(wf)

    wf, counters, _ = _traced(lambda: run(False))
    wf_old, counters_old, _ = _traced(lambda: run(True))
    assert wf.fused_trainer._use_sliced and wf.loader.skip_fill
    assert wf.loader.class_lengths[VALID] > 0
    assert counters["fill_deferred"] > 0 and counters["fill_forced"] == 0
    assert counters_old["fill_forced"] == counters_old["fill_deferred"] \
        == counters["fill_deferred"]
    for ma, mb in zip(wf.decision.epoch_metrics,
                      wf_old.decision.epoch_metrics):
        assert (ma is None and mb is None) or tuple(ma) == tuple(mb)
    for la, lb in zip(wf.fused_trainer.host_params(),
                      wf_old.fused_trainer.host_params()):
        for k in la:
            numpy.testing.assert_array_equal(la[k], lb[k])


# -- (c) readers of minibatch_data -------------------------------------------

def _eager_rows(loader, idx):
    return numpy.asarray(loader.original_data.mem)[idx]


def test_saver_in_the_graph_sees_the_eager_rows(tmp_path):
    from znicz_tpu.loader.saver import read_minibatch_stream

    def run():
        wf = _build(tmp_path, {"window": 4})
        wf.link_data_saver(wf.loader, file_name=str(tmp_path / "s.sav"))
        return _run(wf)

    wf, counters, _ = _traced(run)
    _, records = read_minibatch_stream(str(tmp_path / "s.sav"))
    got = [r for r in records if r["minibatch_class"] == VALID]
    # validation rows are served in order: 20, 20, 10 of rows 0..49
    assert [r["minibatch_size"] for r in got] == [20, 20, 10] * 2
    start, _ = wf.loader.class_index_range(VALID)
    for i, rec in enumerate(got):
        off = start + 20 * (i % 3)
        idx = numpy.arange(off, off + rec["minibatch_size"])
        numpy.testing.assert_array_equal(
            rec["data"], _eager_rows(wf.loader, idx))
    assert counters["fill_deferred"] == counters["fill_forced"] == 6


def test_array_object_held_directly_sees_the_eager_rows(tmp_path):
    """What ``link_immediate_plotter`` does: keep the ``Array`` itself
    and read it later (here at each epoch's end, after the trainer has
    taken the last validation minibatch by its indices)."""
    def run():
        wf = _build(tmp_path, {"window": 4})
        held = wf.loader.minibatch_data
        seen = []
        orig = wf.decision.stop_condition

        def at_epoch_end():
            assert held.pending
            n = wf.loader.minibatch_size
            idx = wf.loader.minibatch_indices.mem[:n].copy()
            seen.append((idx, held.mem[:n].copy(), held.pending))
            return orig()

        wf.decision.stop_condition = at_epoch_end
        return _run(wf), seen

    (wf, seen), counters, _ = _traced(run)
    assert len(seen) == 2
    for idx, rows, pending_after in seen:
        assert len(idx) == 10 and not pending_after
        numpy.testing.assert_array_equal(rows, _eager_rows(wf.loader, idx))
    assert counters["fill_deferred"] == 6 and counters["fill_forced"] == 2


def test_eager_reader_sees_the_eager_rows(tmp_path):
    seen = []

    def run():
        wf = _build(tmp_path, {"window": 4})
        _read_on_valid(wf, seen)
        return _run(wf)

    wf, counters, _ = _traced(run)
    assert len(seen) == 6
    for idx, rows in seen:
        numpy.testing.assert_array_equal(rows, _eager_rows(wf.loader, idx))
    # device reads force the copy as host reads do
    wf.loader.skip_fill = True
    wf.loader.minibatch_size = 20
    wf.loader.minibatch_indices.mem[:] = numpy.arange(20)
    wf.loader.fill_minibatch()
    assert wf.loader.minibatch_data.pending
    numpy.testing.assert_array_equal(
        numpy.asarray(wf.loader.minibatch_data.dev),
        _eager_rows(wf.loader, numpy.arange(20)))
    assert not wf.loader.minibatch_data.pending


# -- (d) the paths that never defer ------------------------------------------

@pytest.mark.parametrize("fused_cfg,loader_name", [
    ({"window": 4, "device_data": False}, "mnist_loader"),
    ({"window": 1}, "mnist_loader"),
    ({"window": 4}, CustomFillLoader.MAPPING),
], ids=["streaming", "window1", "custom_fill"])
def test_never_defers(tmp_path, monkeypatch, fused_cfg, loader_name):
    indexed = []
    orig_pi = fused.FusedNet.predict_indexed
    monkeypatch.setattr(
        fused.FusedNet, "predict_indexed",
        lambda self, *a, **kw: indexed.append(1) or orig_pi(self, *a, **kw))

    def run():
        wf = _build(tmp_path, fused_cfg, loader_name=loader_name)
        pending = []
        orig = wf.loader.run

        def loader_run():
            orig()
            pending.append(wf.loader.minibatch_data.pending)
        wf.loader.run = loader_run
        return _run(wf), pending

    (wf, pending), counters, fills = _traced(run)
    assert not wf.loader.skip_fill
    assert not wf.fused_trainer._use_device_data
    assert pending and not any(pending) and not indexed
    assert counters["fill_deferred"] == counters["fill_forced"] == 0
    # every minibatch of both epochs was filled at once: 8 TRAIN + 3 VALID
    assert len(fills) == counters["minibatches"] == 22
