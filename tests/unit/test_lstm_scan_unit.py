"""The trainable scan-LSTM unit pair.

* registration: lstm_scan forward/backward resolve through the
  MatchingObject registry like every layer type;
* gradient exactness: with lr=1 / no decay / no momentum the applied
  update IS -grad; checked against numeric differentiation of the same
  loss in float64 (the reference's own oracle for every GD unit,
  tests/unit/gd_numdiff.py) — this covers full BPTT through T
  timesteps, which the per-timestep unit graph cannot express;
* T=1 training parity: for one-step sequences the scan is exactly the
  cell, and two epochs of scan-unit training match two epochs of the
  cell + GDLSTM unit pair on every gate parameter.
"""

import numpy

from znicz_tpu.core.backends import JaxDevice
from znicz_tpu.core.memory import Array
from znicz_tpu.core.workflow import DummyWorkflow
from znicz_tpu.units import lstm, lstm_scan
from znicz_tpu.ops.recurrent import GATES


def test_lstm_scan_registered():
    from znicz_tpu.units.nn_units import mapping
    assert mapping["lstm_scan"].forward is lstm_scan.LSTMScan
    assert next(mapping["lstm_scan"].backwards) is lstm_scan.GDLSTMScan


def _build_pair(batch, t, feats, hidden, **gd_kwargs):
    wf = DummyWorkflow()
    fwd = lstm_scan.LSTMScan(wf, output_sample_shape=(hidden,),
                             weights_stddev=0.2, bias_stddev=0.2)
    fwd.input = Array(numpy.zeros((batch, t, feats)))
    fwd.initialize(device=JaxDevice())
    gd = lstm_scan.GDLSTMScan(wf, **gd_kwargs)
    gd.bind_forward(fwd)
    gd.input = fwd.input
    gd.err_output = Array(numpy.zeros((batch, hidden)))
    gd.initialize(device=JaxDevice())
    return fwd, gd


def test_bptt_gradient_matches_numdiff():
    """loss = 0.5 * sum((h_T - target)^2), err_output = h_T - target;
    lr=1, wd=0, moment=0 makes the applied update exactly -grad."""
    r = numpy.random.RandomState(11)
    batch, t, feats, hidden = 3, 4, 5, 4
    fwd, gd = _build_pair(batch, t, feats, hidden,
                          learning_rate=1.0, learning_rate_bias=1.0,
                          weights_decay=0.0, weights_decay_bias=0.0,
                          gradient_moment=0.0, gradient_moment_bias=0.0)
    xs = r.uniform(-1, 1, (batch, t, feats))
    target = r.uniform(-1, 1, (batch, hidden))
    fwd.input.map_invalidate()
    fwd.input.mem[...] = xs

    def loss():
        fwd.run()
        h = numpy.asarray(fwd.output.mem)
        return 0.5 * ((h - target) ** 2).sum()

    before = {n: {"w": numpy.array(fwd.gate_arrays[n]["w"].mem),
                  "b": numpy.array(fwd.gate_arrays[n]["b"].mem)}
              for n in GATES}

    def restore():
        for n2 in GATES:
            for k in ("w", "b"):
                fwd.gate_arrays[n2][k].map_invalidate()
                fwd.gate_arrays[n2][k].mem[...] = before[n2][k]

    loss()
    gd.err_output.map_invalidate()
    gd.err_output.mem[...] = numpy.asarray(fwd.output.mem) - target
    gd.run()
    analytic = {n: before[n]["w"] -
                numpy.asarray(fwd.gate_arrays[n]["w"].mem)
                for n in GATES}

    eps = 1e-6
    for name in GATES:
        arr = fwd.gate_arrays[name]["w"]
        for (i, j) in [(0, 0), (1, 2), (hidden - 1, feats + hidden - 1)]:
            restore()
            arr.map_invalidate()
            arr.mem[i, j] += eps
            lp = loss()
            arr.map_invalidate()
            arr.mem[i, j] -= 2 * eps
            lm = loss()
            num = (lp - lm) / (2 * eps)
            ana = analytic[name][i, j]
            assert abs(num - ana) < 1e-5, (name, i, j, num, ana)


def test_t1_training_parity_with_cell_unit_pair():
    """Two epochs of T=1 training: scan unit == cell + GDLSTM on every
    gate parameter (float64, 1e-9)."""
    r = numpy.random.RandomState(7)
    batch, feats, hidden = 4, 6, 5
    n_minibatches, epochs = 3, 2
    hy = dict(learning_rate=0.1, learning_rate_bias=0.1,
              weights_decay=0.0, weights_decay_bias=0.0,
              gradient_moment=0.9, gradient_moment_bias=0.9)

    xs_all = r.uniform(-1, 1, (n_minibatches, batch, feats))
    targets = r.uniform(-1, 1, (n_minibatches, batch, hidden))

    # -- cell + GDLSTM (the per-timestep unit pair) -------------------------
    wf = DummyWorkflow()
    cell = lstm.LSTM(wf, output_sample_shape=(hidden,),
                     weights_stddev=0.2, bias_stddev=0.2)
    cell.input = Array(xs_all[0].copy())
    cell.prev_output = Array(numpy.zeros((batch, hidden)))
    cell.prev_memory = Array(numpy.zeros((batch, hidden)))
    cell.initialize(device=JaxDevice())
    gd_cell = lstm.GDLSTM(wf, cell, **hy)
    gd_cell.err_output = Array(numpy.zeros((batch, hidden)))
    gd_cell.err_memory = Array(numpy.zeros((batch, hidden)))
    gd_cell.initialize(device=JaxDevice())

    # -- scan pair seeded with the SAME initial gate parameters -------------
    fwd, gd = _build_pair(batch, 1, feats, hidden, **hy)
    init = {}
    for name in GATES:
        unit = getattr(cell, name)
        init[name] = {"w": numpy.array(unit.weights.mem),
                      "b": numpy.array(unit.bias.mem)}
    fwd.gate_state = init

    for _ in range(epochs):
        for k in range(n_minibatches):
            # unit pair
            cell.input.map_invalidate()
            cell.input.mem[...] = xs_all[k]
            cell.prev_output.map_invalidate()
            cell.prev_output.mem[...] = 0
            cell.prev_memory.map_invalidate()
            cell.prev_memory.mem[...] = 0
            cell.run()
            gd_cell.err_output.map_invalidate()
            gd_cell.err_output.mem[...] = (
                numpy.asarray(cell.output.mem) - targets[k])
            gd_cell.err_memory.map_invalidate()
            gd_cell.err_memory.mem[...] = 0
            gd_cell.run()
            # scan pair
            fwd.input.map_invalidate()
            fwd.input.mem[...] = xs_all[k][:, None, :]
            fwd.run()
            gd.err_output.map_invalidate()
            gd.err_output.mem[...] = (
                numpy.asarray(fwd.output.mem) - targets[k])
            gd.run()

    scan_state = fwd.gate_state
    for name in GATES:
        unit = getattr(cell, name)
        unit.weights.map_read()
        unit.bias.map_read()
        dw = numpy.abs(numpy.asarray(unit.weights.mem) -
                       scan_state[name]["w"]).max()
        db = numpy.abs(numpy.asarray(unit.bias.mem) -
                       scan_state[name]["b"]).max()
        assert dw < 1e-9, (name, dw)
        assert db < 1e-9, (name, db)


def test_sequence_sample_trains_below_chance():
    """The sequence sample (scan-LSTM + softmax through StandardWorkflow)
    learns delayed recall: validation error falls far below the 75%
    chance floor within a few epochs, proving loss decrease end to end."""
    from znicz_tpu.core import prng
    from znicz_tpu.samples import sequence

    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    wf = sequence.build(
        decision_config={"max_epochs": 15, "fail_iterations": 30},
        snapshotter_config={"interval": 100, "time_interval": 1e9},
        loader_config={"n_train": 300, "n_valid": 100,
                       "minibatch_size": 50})
    wf.initialize(device=JaxDevice())
    wf.run()
    best = wf.decision.best_n_err_pt[1]
    assert best is not None and best < 20.0, best
    # the backward pair really is the scan unit
    assert isinstance(wf.gds[0], lstm_scan.GDLSTMScan)
    assert wf.gds[0].forward_unit is wf.forwards[0]
