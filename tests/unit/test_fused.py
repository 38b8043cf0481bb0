"""Fused SPMD train step: parity with the unit-graph path + mesh execution.

The unit-at-a-time numpy path is the executable spec (reference pattern,
tests/unit/test_all2all.py:95-152).  The fused jitted step must produce the
same updated weights after one minibatch in float64, and must compile and
run sharded over a (data, model) mesh of 8 virtual devices.
"""

import numpy
import pytest

from znicz_tpu.core.backends import NumpyDevice
from znicz_tpu.core.workflow import DummyWorkflow
from znicz_tpu.core import prng
from znicz_tpu.units import all2all, gd, evaluator
from znicz_tpu.parallel import FusedMLP, make_mesh

LAYERS = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 8,
                                    "weights_stddev": 0.05,
                                    "bias_stddev": 0.05},
     "<-": {"learning_rate": 0.3, "weights_decay": 0.0}},
    {"type": "softmax", "->": {"output_sample_shape": 4,
                               "weights_stddev": 0.05,
                               "bias_stddev": 0.05},
     "<-": {"learning_rate": 0.3, "weights_decay": 0.0}},
]


def _batch(n=16, f=13, c=4, seed=3):
    """Linearly separable synthetic data (labels = argmax of a fixed
    random linear map) so small nets can actually fit it."""
    r = numpy.random.RandomState(seed)
    x = r.uniform(-1, 1, (n, f))
    proj = r.uniform(-1, 1, (f, c))
    labels = numpy.argmax(x @ proj, axis=1).astype(numpy.int32)
    return x, labels


def _unit_graph_one_step(x, labels):
    """Hand-built 2-layer MLP trained one minibatch on the numpy path."""
    wf = DummyWorkflow()
    rand = prng.RandomGenerator().seed(1234)
    device = NumpyDevice()

    f0 = all2all.All2AllTanh(wf, output_sample_shape=(8,),
                             weights_stddev=0.05, bias_stddev=0.05)
    f0.rand = rand
    f0.input = type(f0.output)(x.copy())
    f0.link_from(wf.start_point)
    f1 = all2all.All2AllSoftmax(wf, output_sample_shape=(4,),
                                weights_stddev=0.05, bias_stddev=0.05)
    f1.rand = rand
    f1.link_from(f0)
    f1.link_attrs(f0, ("input", "output"))

    ev = evaluator.EvaluatorSoftmax(wf)
    ev.link_from(f1)
    ev.link_attrs(f1, "output", "max_idx")
    ev.labels = type(f0.output)(labels.copy())
    ev.batch_size = len(x)

    g1 = gd.GDSoftmax(wf, learning_rate=0.3, weights_decay=0.0)
    g1.link_from(ev)
    g1.link_attrs(ev, "err_output")
    g1.link_attrs(f1, "output", "input", "weights", "bias")
    g1.batch_size = len(x)
    g0 = gd.GDTanh(wf, learning_rate=0.3, weights_decay=0.0,
                   need_err_input=False)
    g0.link_from(g1)
    g0.link_attrs(g1, ("err_output", "err_input"))
    g0.link_attrs(f0, "output", "input", "weights", "bias")
    g0.batch_size = len(x)

    for u in (f0, f1, ev, g1, g0):
        u.initialize(device=device)
    for u in (f0, f1, ev, g1, g0):
        u.run()
    return f0, f1


def test_fused_matches_unit_graph_float64():
    x, labels = _batch()
    x = x.astype(numpy.float64)
    f0, f1 = _unit_graph_one_step(x, labels)

    trainer = FusedMLP(LAYERS, input_sample_size=13,
                       rand=prng.RandomGenerator().seed(1234),
                       dtype=numpy.float64)
    trainer.step(x, labels)
    params = trainer.host_params()

    for i, fwd in enumerate((f0, f1)):
        dw = numpy.abs(params[i]["w"] - fwd.weights.mem).max()
        db = numpy.abs(params[i]["b"] - fwd.bias.mem).max()
        assert dw < 1e-10, "layer %d weights diff %g" % (i, dw)
        assert db < 1e-10, "layer %d bias diff %g" % (i, db)


def test_fused_init_matches_unit_init():
    """Same seed => identical initial weights (same draw order)."""
    x, labels = _batch()
    wf = DummyWorkflow()
    rand = prng.RandomGenerator().seed(7)
    f0 = all2all.All2AllTanh(wf, output_sample_shape=(8,))
    f0.rand = rand
    f0.input = type(f0.output)(x.copy())
    f0.link_from(wf.start_point)
    f0.initialize(device=NumpyDevice())

    from znicz_tpu.parallel import fused
    specs = fused.build_fc_specs(
        [{"type": "all2all_tanh", "->": {"output_sample_shape": 8}}], 13)
    params = fused.init_params(specs, prng.RandomGenerator().seed(7),
                               dtype=numpy.float64)
    assert numpy.abs(params[0]["w"] - f0.weights.mem).max() == 0
    assert numpy.abs(params[0]["b"] - f0.bias.mem).max() == 0


@pytest.mark.parametrize("model_parallel", [1, 2])
def test_fused_on_mesh(model_parallel):
    """Compiles + executes sharded over the 8-device CPU mesh; converges."""
    mesh = make_mesh(8, model_parallel=model_parallel)
    x, labels = _batch(n=64)
    trainer = FusedMLP(LAYERS, input_sample_size=13,
                       rand=prng.RandomGenerator().seed(42), mesh=mesh)
    first = None
    for i in range(120):
        m = trainer.step(x, labels)
        if first is None:
            first = float(m["loss"])
    assert float(m["loss"]) < first
    assert int(m["n_err"]) == 0, "should memorize 64 samples"


def test_fused_momentum_and_solvers_run():
    x, labels = _batch()
    layers = [dict(LAYERS[0]), dict(LAYERS[1])]
    layers[0]["<-"] = {"learning_rate": 0.1, "gradient_moment": 0.9,
                       "solvers": ("adagrad",)}
    trainer = FusedMLP(layers, input_sample_size=13,
                       rand=prng.RandomGenerator().seed(5))
    for _ in range(3):
        m = trainer.step(x, labels)
    assert numpy.isfinite(float(m["loss"]))


def test_run_steps_matches_stepwise():
    """The lax.scan multi-step driver produces the same parameters as the
    same minibatches fed through step() one at a time."""
    import numpy
    from znicz_tpu.core import prng
    from znicz_tpu.parallel import FusedNet

    layers = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
         "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 4},
         "<-": {"learning_rate": 0.1}},
    ]
    r = numpy.random.RandomState(3)
    xs = r.uniform(-1, 1, (4, 8, 10)).astype(numpy.float64)
    ls = r.randint(0, 4, (4, 8)).astype(numpy.int32)

    a = FusedNet(layers, 10, rand=prng.RandomGenerator().seed(7),
                 dtype=numpy.float64)
    b = FusedNet(layers, 10, rand=prng.RandomGenerator().seed(7),
                 dtype=numpy.float64)
    ms = a.run_steps(xs, ls)
    for i in range(4):
        m = b.step(xs[i], ls[i])
    pa, pb = a.host_params(), b.host_params()
    for la, lb in zip(pa, pb):
        for k in la:
            assert numpy.abs(la[k] - lb[k]).max() < 1e-12
    assert numpy.abs(float(ms["loss"][-1]) - float(m["loss"])) < 1e-12


def test_run_steps_on_mesh_no_recompile():
    """run_steps over the 8-device mesh: out-shardings are pinned, so the
    second call must hit the compile cache (no GSPMD spec drift)."""
    mesh = make_mesh(8, model_parallel=2)
    import numpy
    r = numpy.random.RandomState(1)
    xs = r.uniform(-1, 1, (3, 16, 13)).astype(numpy.float32)
    ls = r.randint(0, 3, (3, 16)).astype(numpy.int32)
    trainer = FusedMLP(LAYERS, input_sample_size=13,
                       rand=prng.RandomGenerator().seed(42), mesh=mesh)
    m = trainer.run_steps(xs, ls)
    n0 = trainer._scan_step._cache_size()
    m = trainer.run_steps(xs, ls)
    assert trainer._scan_step._cache_size() == n0, "recompiled"
    assert numpy.isfinite(float(m["loss"][-1]))
    # step() after run_steps must also reuse its own cache entry
    m1 = trainer.step(xs[0], ls[0])
    assert numpy.isfinite(float(m1["loss"]))
    # divisibility guard
    import pytest as _pytest
    bad_x = r.uniform(-1, 1, (2, 15, 13)).astype(numpy.float32)
    bad_l = r.randint(0, 3, (2, 15)).astype(numpy.int32)
    with _pytest.raises(ValueError):
        trainer.run_steps(bad_x, bad_l)


@pytest.mark.parametrize("option, value", [
    ("device_perm", True), ("pool_impl", "reshape"),
    ("pool_impl", "offsets")])
def test_removed_option_raises(option, value):
    """An option that is gone is refused by name, not ignored: the
    code chooses the resident window's form and the pool lowering."""
    from znicz_tpu.parallel import FusedNet
    from znicz_tpu.units.fused_trainer import FusedForwardBackward
    with pytest.raises(ValueError, match=option):
        if option == "device_perm":
            FusedForwardBackward(DummyWorkflow(), layers=LAYERS,
                                 device_perm=value)
        else:
            FusedNet(LAYERS, 13, pool_impl=value)
