"""Production pooling path pins.

Every fused golden/parity test forces ``pool_impl="gather"`` (exact tie
parity with the unit path); the DEFAULT ``reduce_window`` lowering —
what real TPU runs use — needs its own trajectory pin.  Exact parity on
tied windows is impossible by design (XLA's select-and-scatter routes
ties implementation-defined, fused.py PoolSpec docstring), so the pin
uses UNTIED data: continuous uniform noise has no equal values inside a
pooling window, select-and-scatter's winner is unique, and the
reduce_window trajectory must EQUAL the gather trajectory integer for
integer — plus pinned golden integers so a numerics change that shifts
BOTH paths still fails.  A changed select-and-scatter VJP or
tie-routing behavior breaks this suite (reference exact-integer pin
pattern: test_mnist_all2all.py:112-135).
"""

import numpy
import pytest

pytestmark = pytest.mark.slow

from znicz_tpu.core.config import root
from znicz_tpu.core import prng
from znicz_tpu.core.backends import JaxDevice
from znicz_tpu.loader.base import FullBatchLoader, TEST, VALID, TRAIN


class UntiedLoader(FullBatchLoader):
    """Continuous uniform data — tie probability inside any pooling
    window is zero, so max pooling has a unique winner everywhere."""

    MAPPING = "untied_synthetic"

    def load_data(self):
        n_valid, n_train = 60, 130
        self.class_lengths[TEST] = 0
        self.class_lengths[VALID] = n_valid
        self.class_lengths[TRAIN] = n_train
        r = numpy.random.RandomState(424242)
        data = r.uniform(-1.0, 1.0, (n_valid + n_train, 28, 28))
        self.original_data.reset(data.astype(numpy.float64))
        self._original_labels[:] = r.randint(
            0, 10, n_valid + n_train).tolist()


#: golden per-epoch error integers for the DEFAULT (reduce_window)
#: production pooling path — float64, seeds 1234/5678, 2 epochs of the
#: MNIST conv topology on the untied dataset above.  Regenerate ONLY
#: for an intentional numerics change:
#:   pytest tests/functional/test_pool_production_pin.py -s  (prints)
GOLDEN_N_ERR = {VALID: 53, TRAIN: 118}


@pytest.fixture()
def float64_engine():
    prev_type = root.common.engine.precision_type
    root.common.engine.precision_type = "double"
    root.common.engine.precision_dtype = numpy.float64
    yield
    root.common.engine.precision_type = prev_type
    root.common.engine.__dict__.pop("precision_dtype", None)


def _train(tmp_path, fused_cfg):
    from znicz_tpu.samples.mnist import MnistWorkflow
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    wf = MnistWorkflow(
        layers=root.mnistr_conv.layers,
        loader_name="untied_synthetic",
        loader_config={"minibatch_size": 40},
        decision_config={"max_epochs": 2, "fail_iterations": 50},
        snapshotter_config={"prefix": "pin", "interval": 100,
                            "time_interval": 1e9, "compression": "",
                            "directory": str(tmp_path)},
        fused=dict(fused_cfg))
    wf.initialize(device=JaxDevice())
    wf.run()
    return wf


def test_production_pool_trajectory_pinned(tmp_path, float64_engine):
    """Both max-pool lowerings must agree exactly on untied data —
    the default reduce_window select-and-scatter VJP (what every
    benchmark cell runs) and the gather/scatter-add path (the tests'
    reference) — and the absolute integers are pinned (catches a
    numerics change that shifts both lowerings together)."""
    wf_def = _train(tmp_path, {})             # default: reduce_window
    wf_g = _train(tmp_path, {"pool_impl": "gather"})

    for wf, impl in ((wf_def, "reduce_window"), (wf_g, "gather")):
        for spec in wf.fused_trainer.net.specs:
            if spec.kind == "pool":
                assert spec.impl == impl

    assert list(wf_def.decision.epoch_n_err) == \
        list(wf_g.decision.epoch_n_err)
    p_a = wf_def.fused_trainer.host_params()
    p_b = wf_g.fused_trainer.host_params()
    for a, b in zip(p_a, p_b):
        for k in a:
            diff = numpy.abs(a[k] - b[k]).max()
            assert diff < 1e-12, diff

    print("production pool n_err:", wf_def.decision.epoch_n_err)
    assert wf_def.decision.epoch_n_err[VALID] == GOLDEN_N_ERR[VALID]
    assert wf_def.decision.epoch_n_err[TRAIN] == GOLDEN_N_ERR[TRAIN]


#: AlexNet 1-epoch pins on the default pooling path (tiny synthetic
#: set, seeds 1234/5678).  Tie routing inside flat activation regions
#: is implementation-defined by design, so the float metric carries a
#: tolerance BAND rather than exact bits; a select-and-scatter behavior
#: change that alters training lands outside it.
ALEXNET_TRAIN_N_ERR = 16       # of 16 (1000-way head, 1 tiny epoch)
ALEXNET_MAX_ERR_Y_SUM = 0.25   # |err| row sum cap = 2/batch (mean mode)
ALEXNET_BAND_REL = 0.10


def test_alexnet_default_pool_band(tmp_path):
    from znicz_tpu.samples.research import alexnet
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    wf = alexnet.build(
        loader_config={"n_train": 16, "n_valid": 8, "minibatch_size": 8},
        decision_config={"max_epochs": 1, "fail_iterations": 5},
        snapshotter_config={"interval": 1000, "time_interval": 1e9,
                            "directory": str(tmp_path)},
        fused={})
    wf.initialize(device=JaxDevice())
    wf.run()
    n_err = wf.decision.epoch_n_err[TRAIN]
    mx = wf.decision.max_err_y_sums[TRAIN]
    print("alexnet train n_err:", wf.decision.epoch_n_err,
          "max_err_y_sum:", mx)
    assert n_err == ALEXNET_TRAIN_N_ERR
    if ALEXNET_MAX_ERR_Y_SUM is not None:
        assert abs(mx - ALEXNET_MAX_ERR_Y_SUM) <= \
            ALEXNET_BAND_REL * ALEXNET_MAX_ERR_Y_SUM, mx
