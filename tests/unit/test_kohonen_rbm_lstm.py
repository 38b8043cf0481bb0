"""Kohonen SOM, RBM (CD-k), and the LSTM cell sub-workflow."""

import numpy
import pytest

from znicz_tpu.core.backends import NumpyDevice, JaxDevice
from znicz_tpu.core.workflow import DummyWorkflow
from znicz_tpu.core.memory import Array
from znicz_tpu.core import prng
from znicz_tpu.ops import kohonen as koh_ops
from znicz_tpu.units import kohonen as koh_units
from znicz_tpu.units import rbm_units, lstm


def _blobs(n=60, seed=0):
    """Three well-separated 2D clusters."""
    r = numpy.random.RandomState(seed)
    centers = numpy.array([[2.0, 2.0], [-2.0, 2.0], [0.0, -2.0]])
    labels = r.randint(0, 3, n)
    x = centers[labels] + r.normal(0, 0.2, (n, 2))
    return x, labels


def test_kohonen_ops_jax_matches_numpy():
    x, _ = _blobs()
    r = numpy.random.RandomState(1)
    w = r.uniform(-0.05, 0.05, (9, 2))
    coords = koh_ops.make_coords(9)
    wn, hn, an = koh_ops.train_step_numpy(x, w.copy(), coords, 2.84, 0.1)
    wj, hj, aj = koh_ops.train_step_jax(x, w.copy(), coords, 2.84, 0.1)
    assert (an == numpy.asarray(aj)).all()
    assert (hn == numpy.asarray(hj)).all()
    assert numpy.abs(wn - numpy.asarray(wj)).max() < 1e-10
    assert (koh_ops.winners_numpy(x, w) ==
            numpy.asarray(koh_ops.winners_jax(x, w))).all()


@pytest.mark.parametrize("device_cls", [NumpyDevice, JaxDevice])
def test_kohonen_trainer_organizes(device_cls):
    # the map's first weights come from the process's generator: seeded
    # here, so that the test reads the same whatever ran before it in its
    # worker (after ``test_looped_lm``'s sample run it read 40 of 60)
    prng.get().seed(1234)
    device = device_cls()
    x, labels = _blobs()
    wf = DummyWorkflow()
    trainer = koh_units.KohonenTrainer(wf, shape=(3, 3))
    trainer.input = Array(x.copy())
    trainer.link_from(wf.start_point)
    trainer.initialize(device=device)
    for _ in range(30):
        trainer.run()
    fwd = koh_units.KohonenForward(wf)
    fwd.input = Array(x.copy())
    fwd.link_attrs(trainer, "weights")
    fwd.initialize(device=device)
    fwd.run()
    winners = numpy.asarray(fwd.output.mem)
    # samples in the same cluster map to the same neuron, clusters differ
    purity = 0
    for c in range(3):
        vals, counts = numpy.unique(winners[labels == c],
                                    return_counts=True)
        purity += counts.max()
    assert purity / len(x) > 0.9


def test_kohonen_validator():
    wf = DummyWorkflow()
    v = koh_units.KohonenValidator(wf)
    v.shape = (2, 2)
    v.samples_by_label = {"a": {0, 1, 2}, "b": {3, 4, 5}}
    # winners: samples 0-2 -> neuron 1, samples 3-5 -> neuron 2
    v.input = Array(numpy.array([1, 1, 1, 2, 2, 2], dtype=numpy.int32))
    v.minibatch_indices = Array(numpy.arange(6, dtype=numpy.int32))
    v.minibatch_size = 6
    v.initialize()
    v.run()
    assert v.fitness == 1.0
    assert v.result["a"] == {1}
    assert v.result["b"] == {2}


def test_rbm_gradient_workflow_runs_cd1():
    wf = DummyWorkflow()
    r = numpy.random.RandomState(3)
    v_size, h_size, batch = 12, 6, 8
    grad = rbm_units.GradientRBM(wf, stddev=0.1, cd_k=1,
                                 v_size=v_size, h_size=h_size,
                                 rand_h=prng.RandomGenerator().seed(1),
                                 rand_v=prng.RandomGenerator().seed(2))
    h0 = r.uniform(0, 1, (batch, h_size))
    grad.input = Array(h0.copy())
    grad.weights = Array(r.uniform(-0.1, 0.1, (h_size, v_size)))
    grad.hbias = Array(numpy.zeros((1, h_size)))
    grad.vbias = Array(numpy.zeros((1, v_size)))
    grad.batch_size = batch
    grad.initialize(device=NumpyDevice())
    grad.run()
    assert grad.v1.shape == (batch, v_size)
    assert grad.h1.shape == (batch, h_size)
    h1 = numpy.asarray(grad.h1.mem)
    assert ((h1 >= 0) & (h1 <= 1)).all()


def test_rbm_cd_units_pipeline():
    """BatchWeights -> GradientsCalculator -> WeightsUpdater math."""
    wf = DummyWorkflow()
    r = numpy.random.RandomState(4)
    batch, v_size, h_size = 5, 4, 3
    v0 = r.uniform(0, 1, (batch, v_size))
    h0 = r.uniform(0, 1, (batch, h_size))
    v1 = r.uniform(0, 1, (batch, v_size))
    h1 = r.uniform(0, 1, (batch, h_size))

    bw0 = rbm_units.BatchWeights(wf)
    bw0.v, bw0.h, bw0.batch_size = Array(v0), Array(h0), batch
    bw0.initialize(device=NumpyDevice())
    bw0.run()
    assert numpy.allclose(bw0.weights_batch.mem, v0.T @ h0 / batch)

    bw1 = rbm_units.BatchWeights2(wf)
    bw1.v, bw1.h, bw1.batch_size = Array(v1), Array(h1), batch
    bw1.initialize(device=NumpyDevice())
    bw1.run()

    gc = rbm_units.GradientsCalculator(wf)
    gc.hbias0, gc.vbias0, gc.weights0 = (bw0.hbias_batch, bw0.vbias_batch,
                                         bw0.weights_batch)
    gc.hbias1, gc.vbias1, gc.weights1 = (bw1.hbias_batch, bw1.vbias_batch,
                                         bw1.weights_batch)
    gc.initialize(device=NumpyDevice())
    gc.run()
    assert numpy.allclose(gc.weights_grad.mem,
                          (v0.T @ h0 - v1.T @ h1) / batch)

    wu = rbm_units.WeightsUpdater(wf, learning_rate=0.5)
    weights = Array(numpy.zeros((h_size, v_size)))
    hbias = Array(numpy.zeros((1, h_size)))
    vbias = Array(numpy.zeros((1, v_size)))
    wu.weights, wu.hbias, wu.vbias = weights, hbias, vbias
    wu.hbias_grad, wu.vbias_grad, wu.weights_grad = (
        gc.hbias_grad, gc.vbias_grad, gc.weights_grad)
    wu.initialize()
    wu.run()
    assert numpy.allclose(weights.mem, 0.5 * gc.weights_grad.mem.T)


@pytest.mark.parametrize("device_cls", [NumpyDevice, JaxDevice])
def test_lstm_cell_forward_backward(device_cls):
    device = device_cls()
    r = numpy.random.RandomState(5)
    batch, in_size, hidden = 4, 6, 5
    wf = DummyWorkflow()
    cell = lstm.LSTM(wf, output_sample_shape=(hidden,),
                     weights_stddev=0.1, bias_stddev=0.1)
    cell.input = Array(r.uniform(-1, 1, (batch, in_size)))
    cell.prev_output = Array(numpy.zeros((batch, hidden)))
    cell.prev_memory = Array(numpy.zeros((batch, hidden)))
    cell.initialize(device=device)
    cell.run()
    assert cell.output.shape == (batch, hidden)
    assert cell.memory.shape == (batch, hidden)
    out1 = numpy.array(numpy.asarray(cell.output.mem))

    gd_cell = lstm.GDLSTM(wf, cell, learning_rate=0.1)
    gd_cell.err_output = Array(r.uniform(-0.1, 0.1, (batch, hidden)))
    gd_cell.err_memory = Array(numpy.zeros((batch, hidden)))
    gd_cell.initialize(device=device)
    gd_cell.run()
    assert gd_cell.err_input.shape == (batch, in_size)
    assert gd_cell.err_prev_output.shape == (batch, hidden)
    assert gd_cell.err_prev_memory.shape == (batch, hidden)

    # weights were updated -> output changes
    cell.run()
    out2 = numpy.asarray(cell.output.mem)
    assert numpy.abs(out2 - out1).max() > 0


def test_lstm_registered():
    from znicz_tpu.units.nn_units import mapping
    assert mapping["LSTM"].has_forward
    assert next(mapping["LSTM"].backwards) is lstm.GDLSTM


def test_kohonen_train_step_data_parallel_matches_single_device():
    """SPMD Kohonen (SURVEY §2.8): the batch-sharded SOM step over the
    8-device mesh reproduces the single-device step — GSPMD's inserted
    all-reduce replaces the reference's master-slave aggregation."""
    from znicz_tpu.ops import kohonen as koh_ops
    from znicz_tpu.parallel import make_mesh

    r = numpy.random.RandomState(7)
    x = r.uniform(-1, 1, (32, 6))
    w = r.uniform(-0.05, 0.05, (9, 6))
    coords = koh_ops.make_coords(9)
    new_w, hist, argmins = koh_ops.train_step_jax(
        x, w, coords, 1.4, 0.05)
    mesh = make_mesh(8)
    new_w2, hist2, argmins2 = koh_ops.train_step_sharded(
        mesh, x, w, coords, 1.4, 0.05)
    assert numpy.abs(numpy.asarray(new_w) -
                     numpy.asarray(new_w2)).max() < 1e-12
    assert numpy.array_equal(numpy.asarray(hist), numpy.asarray(hist2))
    assert numpy.array_equal(numpy.asarray(argmins),
                             numpy.asarray(argmins2))
