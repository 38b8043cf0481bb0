"""lax.scan LSTM driver vs the unit-graph per-timestep unroll:
same outputs to 1e-6 (float64 gives ~1e-12), one
compile for T timesteps, differentiable end to end."""

import numpy

import jax
import jax.numpy as jnp

from znicz_tpu.core.backends import NumpyDevice
from znicz_tpu.core.memory import Array
from znicz_tpu.core.workflow import DummyWorkflow
from znicz_tpu.units import lstm
from znicz_tpu.ops import recurrent


def _unit_unroll(cell, xs):
    """Drive the cell sub-workflow one timestep at a time, threading
    prev_output/prev_memory by hand (the reference's external unroll)."""
    batch, hidden = xs.shape[1], cell.output_sample_shape[0]
    h = numpy.zeros((batch, hidden))
    c = numpy.zeros((batch, hidden))
    ys = []
    for t in range(len(xs)):
        cell.input.map_invalidate()
        cell.input.mem[...] = xs[t]
        cell.prev_output.map_invalidate()
        cell.prev_output.mem[...] = h
        cell.prev_memory.map_invalidate()
        cell.prev_memory.mem[...] = c
        cell.run()
        h = numpy.array(cell.output.mem)
        c = numpy.array(cell.memory.mem)
        ys.append(h)
    return numpy.stack(ys), h, c


def test_lstm_scan_matches_unit_unroll():
    r = numpy.random.RandomState(3)
    T, batch, in_size, hidden = 7, 4, 6, 5
    xs = r.uniform(-1, 1, (T, batch, in_size))

    wf = DummyWorkflow()
    cell = lstm.LSTM(wf, output_sample_shape=(hidden,),
                     weights_stddev=0.1, bias_stddev=0.1)
    cell.input = Array(xs[0].copy())
    cell.prev_output = Array(numpy.zeros((batch, hidden)))
    cell.prev_memory = Array(numpy.zeros((batch, hidden)))
    cell.initialize(device=NumpyDevice())

    ys_unit, h_unit, c_unit = _unit_unroll(cell, xs)

    params = recurrent.params_from_cell(cell)
    ys, h, c = recurrent.lstm_scan_jax(
        params, jnp.asarray(xs),
        jnp.zeros((batch, hidden)), jnp.zeros((batch, hidden)))
    assert numpy.abs(numpy.asarray(ys) - ys_unit).max() < 1e-6
    assert numpy.abs(numpy.asarray(h) - h_unit).max() < 1e-6
    assert numpy.abs(numpy.asarray(c) - c_unit).max() < 1e-6


def test_lstm_scan_compiles_once_and_is_differentiable():
    r = numpy.random.RandomState(4)
    T, batch, in_size, hidden = 5, 2, 3, 4
    xs = jnp.asarray(r.uniform(-1, 1, (T, batch, in_size)))
    params = {
        name: {"w": jnp.asarray(
            r.uniform(-0.1, 0.1, (hidden, in_size + hidden))),
            "b": jnp.asarray(r.uniform(-0.1, 0.1, hidden))}
        for name in recurrent.GATES}
    h0 = jnp.zeros((batch, hidden))
    c0 = jnp.zeros((batch, hidden))

    traces = []

    def loss(p):
        traces.append(1)
        ys, _, _ = recurrent.lstm_scan_jax.__wrapped__(p, xs, h0, c0)
        return (ys ** 2).sum()

    g = jax.jit(jax.grad(loss))
    g1 = g(params)
    g(params)  # second call: cached — the body traced once per compile
    assert len(traces) == 1
    for name in recurrent.GATES:
        assert numpy.isfinite(numpy.asarray(g1[name]["w"])).all()
        assert float(numpy.abs(numpy.asarray(g1[name]["w"])).max()) > 0
