"""Fused conv-net SPMD path: parity with the unit-graph path + mesh run.

Same contract as test_fused.py but for the conv family: the unit-at-a-time
numpy path (Conv/MaxPooling/All2All units + their GD pairs) is the
executable spec; the fused jitted step must produce the same updated
weights after one minibatch in float64, and must compile and run sharded
over the 8-device virtual CPU mesh.
"""

import numpy

from znicz_tpu.core.backends import NumpyDevice
from znicz_tpu.core.workflow import DummyWorkflow
from znicz_tpu.core import prng
from znicz_tpu.core.memory import Array
from znicz_tpu.units import all2all, conv, gd, gd_conv, gd_pooling
from znicz_tpu.units import pooling, evaluator
from znicz_tpu.parallel import FusedNet, make_mesh, flops_per_image
from znicz_tpu.parallel import fused

LAYERS = [
    {"type": "conv_tanh",
     "->": {"n_kernels": 4, "kx": 3, "ky": 3, "sliding": (1, 1),
            "weights_stddev": 0.05, "bias_stddev": 0.05},
     "<-": {"learning_rate": 0.1, "weights_decay": 0.0}},
    {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
    {"type": "all2all_tanh",
     "->": {"output_sample_shape": 8,
            "weights_stddev": 0.05, "bias_stddev": 0.05},
     "<-": {"learning_rate": 0.1, "weights_decay": 0.0}},
    {"type": "softmax",
     "->": {"output_sample_shape": 4,
            "weights_stddev": 0.05, "bias_stddev": 0.05},
     "<-": {"learning_rate": 0.1, "weights_decay": 0.0}},
]


def _batch(n=4, seed=3):
    r = numpy.random.RandomState(seed)
    x = r.uniform(-1, 1, (n, 8, 8, 1))
    labels = r.randint(0, 4, n).astype(numpy.int32)
    return x, labels


def _unit_graph_one_step(x, labels):
    """Conv -> maxpool -> FC -> softmax trained one minibatch on the
    numpy path (the graph StandardWorkflow.link_gds builds, by hand)."""
    wf = DummyWorkflow()
    rand = prng.RandomGenerator().seed(1234)
    device = NumpyDevice()
    b = len(x)

    f0 = conv.ConvTanh(wf, n_kernels=4, kx=3, ky=3, sliding=(1, 1),
                       weights_stddev=0.05, bias_stddev=0.05)
    f0.rand = rand
    f0.input = Array(x.copy())
    f0.link_from(wf.start_point)
    f1 = pooling.MaxPooling(wf, kx=2, ky=2)
    f1.link_from(f0)
    f1.link_attrs(f0, ("input", "output"))
    f2 = all2all.All2AllTanh(wf, output_sample_shape=(8,),
                             weights_stddev=0.05, bias_stddev=0.05)
    f2.rand = rand
    f2.link_from(f1)
    f2.link_attrs(f1, ("input", "output"))
    f3 = all2all.All2AllSoftmax(wf, output_sample_shape=(4,),
                                weights_stddev=0.05, bias_stddev=0.05)
    f3.rand = rand
    f3.link_from(f2)
    f3.link_attrs(f2, ("input", "output"))

    ev = evaluator.EvaluatorSoftmax(wf)
    ev.link_from(f3)
    ev.link_attrs(f3, "output", "max_idx")
    ev.labels = Array(labels.copy())
    ev.batch_size = b

    g3 = gd.GDSoftmax(wf, learning_rate=0.1, weights_decay=0.0)
    g3.link_from(ev)
    g3.link_attrs(ev, "err_output")
    g3.link_attrs(f3, "output", "input", "weights", "bias")
    g3.batch_size = b
    g2 = gd.GDTanh(wf, learning_rate=0.1, weights_decay=0.0)
    g2.link_from(g3)
    g2.link_attrs(g3, ("err_output", "err_input"))
    g2.link_attrs(f2, "output", "input", "weights", "bias")
    g2.batch_size = b
    gp = gd_pooling.GDMaxPooling(wf, kx=2, ky=2, sliding=(2, 2))
    gp.link_from(g2)
    gp.link_attrs(g2, ("err_output", "err_input"))
    gp.link_attrs(f1, "input", "input_offset", "output")
    g0 = gd_conv.GDTanhConv(wf, learning_rate=0.1, weights_decay=0.0,
                            need_err_input=False)
    g0.link_from(gp)
    g0.link_attrs(gp, ("err_output", "err_input"))
    g0.link_attrs(f0, "output", "input", "weights", "bias",
                  "n_kernels", "kx", "ky", "padding", "sliding")
    g0.batch_size = b

    units = (f0, f1, f2, f3, ev, g3, g2, gp, g0)
    for u in units:
        u.initialize(device=device)
    for u in units:
        u.run()
    return f0, f2, f3


def test_fused_conv_matches_unit_graph_float64():
    x, labels = _batch()
    x = x.astype(numpy.float64)
    f0, f2, f3 = _unit_graph_one_step(x, labels)

    trainer = FusedNet(LAYERS, input_sample_shape=(8, 8, 1),
                       rand=prng.RandomGenerator().seed(1234),
                       dtype=numpy.float64)
    trainer.step(x, labels)
    params = trainer.host_params()

    trained = {0: f0, 2: f2, 3: f3}
    for i, fwd in trained.items():
        dw = numpy.abs(params[i]["w"] - fwd.weights.mem).max()
        db = numpy.abs(params[i]["b"] - fwd.bias.mem).max()
        assert dw < 1e-10, "layer %d weights diff %g" % (i, dw)
        assert db < 1e-10, "layer %d bias diff %g" % (i, db)
    assert params[1] == {}  # pooling holds no params


def test_fused_conv_init_matches_unit_init():
    """Same seed => identical initial conv weights (same draw order,
    same magnitude heuristic when stddev is unset)."""
    wf = DummyWorkflow()
    rand = prng.RandomGenerator().seed(7)
    x = numpy.zeros((2, 8, 8, 1))
    f0 = conv.Conv(wf, n_kernels=4, kx=3, ky=3)
    f0.rand = rand
    f0.input = Array(x.copy())
    f0.link_from(wf.start_point)
    f0.initialize(device=NumpyDevice())

    specs = fused.build_specs(
        [{"type": "conv", "->": {"n_kernels": 4, "kx": 3, "ky": 3}}],
        (8, 8, 1))
    params = fused.init_params(specs, prng.RandomGenerator().seed(7),
                               dtype=numpy.float64)
    assert numpy.abs(params[0]["w"] - f0.weights.mem).max() == 0
    assert numpy.abs(params[0]["b"] - f0.bias.mem).max() == 0


def test_fused_conv_on_mesh_converges():
    """Conv net compiles + executes data-parallel over the 8-device CPU
    mesh and memorizes a small synthetic set."""
    mesh = make_mesh(8, model_parallel=2)
    r = numpy.random.RandomState(0)
    x = r.uniform(-1, 1, (64, 8, 8, 1)).astype(numpy.float32)
    labels = (x.mean(axis=(1, 2, 3)) > 0).astype(numpy.int32) * 2
    layers = [dict(l) for l in LAYERS]
    for l in layers:
        if "<-" in l:
            l["<-"] = {"learning_rate": 0.5, "weights_decay": 0.0}
    trainer = FusedNet(layers, input_sample_shape=(8, 8, 1), mesh=mesh,
                       rand=prng.RandomGenerator().seed(42))
    # every step's loss is read before the next is dispatched: 200
    # steps in flight at once can starve the CPU backend's collectives
    # of threads on a loaded host (eight virtual devices rendezvous in
    # every all-reduce; the process then aborts after a minute), which
    # is how this test failed only in the six-worker run
    first = None
    for _ in range(200):
        m = trainer.step(x, labels)
        loss = float(m["loss"])
        if first is None:
            first = loss
    assert loss < first
    assert int(m["n_err"]) == 0, "should memorize 64 samples"


def test_fused_cifar_caffe_topology_builds_and_steps():
    """The CIFAR caffe-style topology (conv/pool/activation/LRN mix,
    samples/cifar.py) compiles on the fused path end to end."""
    from znicz_tpu.samples import cifar
    from znicz_tpu.core.config import root
    layers = [dict(l) for l in root.cifar.layers]
    r = numpy.random.RandomState(1)
    x = r.uniform(-1, 1, (16, 32, 32, 3)).astype(numpy.float32)
    labels = r.randint(0, 10, 16).astype(numpy.int32)
    trainer = FusedNet(layers, input_sample_shape=(32, 32, 3),
                       rand=prng.RandomGenerator().seed(9))
    m1 = trainer.step(x, labels)
    m2 = trainer.step(x, labels)
    assert numpy.isfinite(float(m1["loss"]))
    assert numpy.isfinite(float(m2["loss"]))
    assert cifar  # imported for config registration


def test_fused_dropout_trains_and_inference_is_deterministic():
    layers = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 16}},
        {"type": "dropout", "dropout_ratio": 0.3},
        {"type": "softmax", "->": {"output_sample_shape": 4}},
    ]
    r = numpy.random.RandomState(2)
    x = r.uniform(-1, 1, (8, 12)).astype(numpy.float32)
    labels = r.randint(0, 4, 8).astype(numpy.int32)
    trainer = FusedNet(layers, input_sample_shape=12,
                       rand=prng.RandomGenerator().seed(3))
    m1 = trainer.step(x, labels)
    m2 = trainer.step(x, labels)
    assert numpy.isfinite(float(m1["loss"]))
    assert numpy.isfinite(float(m2["loss"]))
    y1 = numpy.asarray(trainer.predict(x))
    y2 = numpy.asarray(trainer.predict(x))
    assert numpy.array_equal(y1, y2), "inference must not apply dropout"


def test_flops_per_image_counts_conv_and_fc():
    specs = fused.build_specs(LAYERS, (8, 8, 1))
    # conv: 2*6*6*4*(3*3*1); fc: 2*36*8 + 2*8*4 (pool contributes 0)
    expect = 2 * 6 * 6 * 4 * 9 + 2 * 36 * 8 + 2 * 8 * 4
    assert flops_per_image(specs) == expect


def test_fused_cifar_caffe_on_mesh_trains():
    """The FULL CIFAR-caffe topology (conv/max+avg pool/strict-relu/LRN)
    trains data-parallel over the 8-device mesh — the reference's
    flagship conv model under SPMD."""
    from znicz_tpu.parallel import make_mesh, multihost
    from znicz_tpu.samples import cifar
    from znicz_tpu.core.config import root
    assert cifar  # config registration
    mesh = make_mesh(8, model_parallel=2)
    layers = [dict(l) for l in root.cifar.layers]
    r = numpy.random.RandomState(2)
    # separable per-class prototypes so a few steps measurably learn
    protos = r.uniform(-1, 1, (4, 32, 32, 3))
    labels = r.randint(0, 4, 32).astype(numpy.int32)
    x = (protos[labels] +
         0.1 * r.standard_normal((32, 32, 32, 3))).astype(numpy.float32)
    trainer = FusedNet(layers, input_sample_shape=(32, 32, 3), mesh=mesh,
                       rand=prng.RandomGenerator().seed(7))
    xg, lg = multihost.global_batch(mesh, x, labels)
    first = None
    for _ in range(12):
        m = trainer.step(xg, lg)
        if first is None:
            first = float(m["loss"])
    assert numpy.isfinite(float(m["loss"]))
    assert float(m["loss"]) < first, "did not learn under SPMD"
