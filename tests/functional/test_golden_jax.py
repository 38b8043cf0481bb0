"""Production-path golden determinism.

test_golden.py pins exact integers on the float64 NUMPY path; these pin
the PRODUCTION paths: (a) the jax unit-graph MNIST MLP, (b) the fused
MNIST conv on the 8-device virtual mesh.  The full per-epoch n_err
sequence is recorded (not just the final epoch), so any numerics,
sharding, GSPMD or scheduling drift that shifts a single error count
anywhere in the trajectory fails loudly.

The values were generated by this same code (float64, CPU backend,
highest matmul precision, seeds 1234/5678) — the point is drift
DETECTION; regenerate deliberately when jax/XLA versions move.
"""

import numpy
import pytest

from znicz_tpu.core.config import root
from znicz_tpu.core import prng
from znicz_tpu.core.backends import JaxDevice
from znicz_tpu.loader.base import VALID, TRAIN


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


def _track_epochs(wf):
    """Record (class, n_err) at every segment end — the full trajectory."""
    seq = []
    decision = wf.decision
    orig = decision.on_last_minibatch

    def wrapped():
        orig()
        clazz = decision.minibatch_class
        seq.append((int(clazz), int(decision.epoch_n_err[clazz])))

    decision.on_last_minibatch = wrapped
    return seq


#: jax unit-graph MNIST MLP: (class, n_err) at each segment end over 3
#: epochs (TRAIN=2 then VALID=1 per epoch; synthetic 600/200, mb 60)
GOLDEN_MLP_SEQUENCE = [
    (2, 393), (1, 86), (2, 105), (1, 12), (2, 18), (1, 4)]
#: equals test_golden.GOLDEN_MNIST_W0_ABSSUM — the jax path lands on the
#: numpy path's float64 checksum, a cross-backend confirmation
GOLDEN_MLP_W0_ABSSUM = 1965.9344969151437


def test_jax_unit_graph_mnist_mlp_golden(tmp_path, float64_engine):
    from znicz_tpu.samples import mnist
    _seed()
    wf = mnist.build(
        loader_config={"synthetic_train": 600, "synthetic_valid": 200,
                       "minibatch_size": 60},
        decision_config={"max_epochs": 3, "fail_iterations": 50},
        snapshotter_config={"prefix": "gjax", "interval": 100,
                            "time_interval": 1e9,
                            "directory": str(tmp_path)})
    wf.initialize(device=JaxDevice())
    seq = _track_epochs(wf)
    wf.run()
    assert wf.loader.epoch_number == 3
    assert seq == GOLDEN_MLP_SEQUENCE, seq
    w0 = float(numpy.abs(numpy.asarray(wf.forwards[0].weights.mem)).sum())
    assert abs(w0 - GOLDEN_MLP_W0_ABSSUM) < 1e-9, w0


#: fused MNIST conv on the 8-device (data=4, model=2) mesh: (class,
#: n_err) at each segment end over 2 epochs (synthetic 120/80, mb 40)
GOLDEN_FUSED_CONV_SEQUENCE = [
    (2, 110), (1, 73), (2, 113), (1, 74)]
GOLDEN_FUSED_CONV_W0_ABSSUM = 76.91625527733359


def test_fused_mesh_mnist_conv_golden(tmp_path, float64_engine):
    from znicz_tpu.samples import mnist
    _seed()
    wf = mnist.build(
        layers=root.mnistr_conv.layers,
        loader_config={"synthetic_train": 120, "synthetic_valid": 80,
                       "minibatch_size": 40},
        decision_config={"max_epochs": 2, "fail_iterations": 50},
        snapshotter_config={"prefix": "gfused", "interval": 100,
                            "time_interval": 1e9,
                            "directory": str(tmp_path)},
        fused={"mesh": 8, "model_parallel": 2, "pool_impl": "gather"})
    wf.initialize(device=JaxDevice())
    seq = _track_epochs(wf)
    wf.run()
    assert wf.loader.epoch_number == 2
    assert seq == GOLDEN_FUSED_CONV_SEQUENCE, seq
    w0 = float(numpy.abs(
        wf.fused_trainer.host_params()[0]["w"]).sum())
    assert abs(w0 - GOLDEN_FUSED_CONV_W0_ABSSUM) < 1e-9, w0
    assert wf.decision.epoch_n_err[VALID] == GOLDEN_FUSED_CONV_SEQUENCE[-1][1]
    assert wf.decision.epoch_n_err[TRAIN] == GOLDEN_FUSED_CONV_SEQUENCE[-2][1]
