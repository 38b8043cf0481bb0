"""CIFAR functional test — the caffe-style conv topology actually trains.

samples/cifar.py (conv + maxpool +
strict-relu + LRN + avgpool + arbitrary_step LR schedule, the 17.21%-val
reference config) had no test.  Trains the real workflow for several epochs
on the deterministic synthetic set and asserts the error decreases and the
lr_adjuster graph surgery holds together.
"""


from znicz_tpu.core.backends import JaxDevice
from znicz_tpu.core import prng
from znicz_tpu.loader.base import TRAIN, VALID

LOADER_CFG = {"synthetic_train": 200, "synthetic_valid": 80,
              "minibatch_size": 40}


def _run(max_epochs):
    from znicz_tpu.samples import cifar
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    wf = cifar.build(
        loader_config=dict(LOADER_CFG),
        decision_config={"max_epochs": max_epochs, "fail_iterations": 100})
    wf.initialize(device=JaxDevice())
    wf.run()
    return wf


def test_cifar_caffe_topology_trains():
    wf1 = _run(max_epochs=1)
    first_train = wf1.decision.epoch_n_err[TRAIN]
    first_valid = wf1.decision.epoch_n_err[VALID]

    wf = _run(max_epochs=4)
    assert wf.loader.epoch_number == 4
    # same seeds => epoch 1 identical; epochs 2-4 must improve on it
    assert wf.decision.epoch_n_err[TRAIN] < first_train, \
        "training error should decrease (epoch1 %d -> epoch4 %d)" % (
            first_train, wf.decision.epoch_n_err[TRAIN])
    assert wf.decision.best_n_err_pt[VALID] <= \
        100.0 * first_valid / LOADER_CFG["synthetic_valid"]

    # the lr_adjuster re-link surgery: adjuster feeds the gd chain
    assert wf.lr_adjuster in wf.gds[-1].links_from
    assert wf.snapshotter not in wf.gds[-1].links_from
    # arbitrary_step schedule engaged on every gd unit
    for gd in wf.gds:
        assert gd.learning_rate > 0

    # graph shape sanity: conv stack geometry (32x32 pad2 5x5 convs)
    shapes = [tuple(f.output.shape) for f in wf.forwards]
    mb = LOADER_CFG["minibatch_size"]
    assert shapes[0] == (mb, 32, 32, 32)     # conv1
    assert shapes[-1] == (mb, 10)            # softmax head


def test_cifar_mlp_variant():
    """cifar_config MLP: all2all + sincos stack (baseline 45.80%)."""
    from znicz_tpu.samples import cifar
    wf = cifar.build_variant(
        "mlp",
        loader_config={"synthetic_train": 60, "synthetic_valid": 30,
                       "minibatch_size": 30},
        decision_config={"max_epochs": 3, "fail_iterations": 10})
    wf.initialize()
    wf.run()
    types = [type(f).__name__ for f in wf.forwards]
    assert types.count("ForwardSinCos") == 2
    assert wf.decision.epoch_number >= 3


def test_cifar_nin_variant():
    """cifar_nin_config: 5x5 + 1x1 mlpconv stages, global avg pool
    (baseline 9.09%)."""
    from znicz_tpu.samples import cifar
    wf = cifar.build_variant(
        "nin",
        loader_config={"synthetic_train": 30, "synthetic_valid": 10,
                       "minibatch_size": 10},
        decision_config={"max_epochs": 1, "fail_iterations": 5})
    wf.initialize()
    # 9 convs incl. the 1x1 stages; final avg pool is global (8x8)
    convs = [f for f in wf.forwards if type(f).__name__ == "Conv"]
    assert len(convs) == 9
    assert sum(1 for c in convs if c.kx == 1) == 6
    wf.run()
    assert wf.decision.epoch_number >= 1


def test_mnist_caffe_variant():
    """mnist_caffe_config LeNet (baseline 0.80%): trains and the error
    decreases."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root
    from znicz_tpu.samples import mnist
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    wf = mnist.build(
        layers=root.mnistr_caffe.layers,
        loader_config={"synthetic_train": 120, "synthetic_valid": 60,
                       "minibatch_size": 30},
        decision_config={"max_epochs": 8, "fail_iterations": 20})
    wf.initialize()
    wf.run()
    assert wf.decision.best_n_err_pt[1] < 80.0  # improving from ~90%


def test_run_profiled_writes_trace(tmp_path):
    """Workflow.run_profiled captures an XLA trace (SURVEY.md 5.1)."""
    import os
    from znicz_tpu.core.config import root
    from znicz_tpu.samples import wine
    saved = root.wine.decision.max_epochs
    root.wine.decision.max_epochs = 2
    try:
        wf = wine.WineWorkflow()
        wf.initialize()
        wf.run_profiled(str(tmp_path / "trace"))
    finally:
        root.wine.decision.max_epochs = saved
    found = []
    for dirpath, _, files in os.walk(str(tmp_path / "trace")):
        found.extend(files)
    assert found, "no profiler artifacts written"
