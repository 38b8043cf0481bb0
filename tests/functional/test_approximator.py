"""Approximator functional test — the MSE pipeline end to end.

minibatch_targets flow through the
loader -> evaluator_mse -> decision_mse chain built entirely by
StandardWorkflow, training until the decision stops on metrics
(reference tests/research/Approximator + evaluator.py:334-556).
"""

import numpy

from znicz_tpu.core import prng
from znicz_tpu.core.backends import NumpyDevice, JaxDevice
from znicz_tpu.loader.base import TRAIN, VALID


def _run(device, max_epochs=20, **kwargs):
    from znicz_tpu.samples import approximator
    prng.get(1).seed(1024)
    prng.get(2).seed(1025)
    decision_config = {"fail_iterations": 100, "max_epochs": max_epochs}
    decision_config.update(kwargs.pop("decision_config", {}))
    wf = approximator.build(decision_config=decision_config, **kwargs)
    wf.initialize(device=device)
    wf.run()
    return wf


def test_approximator_trains_and_stops_on_metrics():
    wf = _run(NumpyDevice(), max_epochs=15)
    dec = wf.decision
    assert bool(dec.complete)
    assert wf.loader.epoch_number == 15
    # the MSE path populated per-class epoch metrics and they improved
    assert dec.epoch_metrics[TRAIN] is not None
    assert dec.epoch_metrics[VALID] is not None
    assert dec.best_metrics[VALID][0] < 0.2, \
        "validation avg RMSE should drop well below the untrained ~0.30 " \
        "(got %r)" % (dec.best_metrics[VALID],)
    # evaluator target wiring: the output layer auto-sized to the targets
    assert wf.forwards[-1].output.shape[1:] == \
        wf.loader.minibatch_targets.shape[1:]
    # snapshot suffix carries the MSE values (reference decision.py:540-548)
    assert "validation_" in dec.snapshot_suffix


def test_approximator_jax_matches_numpy_start():
    """Early-epoch metrics agree across backends (same seeds; float32
    training drift compounds per epoch, so the tolerance is modest —
    per-op backend equivalence is asserted at 1e-4 in tests/unit)."""
    wf_np = _run(NumpyDevice(), max_epochs=2)
    wf_jx = _run(JaxDevice(), max_epochs=2)
    m_np = wf_np.decision.epoch_metrics[VALID]
    m_jx = wf_jx.decision.epoch_metrics[VALID]
    assert numpy.allclose(m_np, m_jx, rtol=5e-2, atol=5e-3), \
        (m_np, m_jx)


def test_mse_decision_stops_early_without_improvement():
    """fail_iterations fires when validation MSE stalls."""
    wf = _run(NumpyDevice(), max_epochs=50,
              decision_config={"fail_iterations": 3, "max_epochs": 50,
                               "snapshot_interval": 0},
              layers=[
                  {"type": "all2all_tanh",
                   "->": {"output_sample_shape": 2,
                          "weights_stddev": 0.05, "bias_stddev": 0.05},
                   # zero LR: nothing can improve after epoch 1
                   "<-": {"learning_rate": 0.0, "weights_decay": 0.0}},
                  {"type": "all2all_tanh",
                   "->": {"weights_stddev": 0.05, "bias_stddev": 0.05},
                   "<-": {"learning_rate": 0.0, "weights_decay": 0.0}}])
    assert bool(wf.decision.complete)
    assert wf.loader.epoch_number < 50, "should stop on fail_iterations"
