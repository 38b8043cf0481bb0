"""Numeric differentiation of the conv / pooling / deconv backwards and a
whole conv workflow (float64 only).

The conv-family backward math was
verified only against its own numpy twins (shared-bug blind spot).  Here
every analytic gradient is checked against a five-point finite-difference
gradient of an independently composed numpy loss, |analytic - numeric| <
1e-5 — the reference harness breadth (tests/unit/test_gd_conv.py,
test_gd_workflow.py:61-246, gd_numdiff.py:43-156).
"""

import numpy
import pytest

from znicz_tpu.core.backends import NumpyDevice, JaxDevice
from znicz_tpu.core.workflow import DummyWorkflow
from znicz_tpu.core.memory import Array
from znicz_tpu.core import prng
from znicz_tpu.units import all2all, conv, gd, gd_conv, gd_pooling
from znicz_tpu.units import pooling, evaluator
from znicz_tpu.ops import conv as conv_ops
from znicz_tpu.ops import pooling as pool_ops
from znicz_tpu.ops import dense, activations

from tests.unit.test_gd_numdiff import numdiff  # shared 5-point stencil

#: conv geometry under test: asymmetric padding + non-unit sliding
PAD = (1, 2, 1, 0)   # L T R B
SLIDE = (2, 2)


def test_conv_backward_numdiff_padding_sliding():
    """Conv backward (tanh activation, padded, strided) vs numdiff."""
    r = numpy.random.RandomState(3)
    x = r.uniform(-1, 1, (2, 6, 7, 2))
    w = r.uniform(-0.5, 0.5, (3, 3 * 3 * 2))   # 3 kernels of 3x3x2
    b = r.uniform(-0.5, 0.5, 3)
    ny, nx = conv_ops.output_spatial(6, 7, 3, 3, PAD, SLIDE)
    proj = r.uniform(-1, 1, (2, ny, nx, 3))    # fixed loss projection

    def loss():
        y = conv_ops.forward_numpy(x, w, b, 3, 3, PAD, SLIDE,
                                   activation="tanh")
        return (y * proj).sum()

    y_act = conv_ops.forward_numpy(x, w, b, 3, 3, PAD, SLIDE,
                                   activation="tanh")
    err_output = proj * activations.derivative_numpy("tanh", y_act)
    err_in, gw, gb = conv_ops.backward_numpy(
        x, err_output, w, 3, 3, PAD, SLIDE)

    assert numpy.abs(gw - numdiff(loss, w)).max() < 1e-5
    assert numpy.abs(gb - numdiff(loss, b)).max() < 1e-5
    assert numpy.abs(err_in - numdiff(loss, x)).max() < 1e-5


def test_deconv_backward_numdiff():
    """Deconv (transposed conv) backward vs numdiff."""
    r = numpy.random.RandomState(4)
    out_shape = (2, 6, 6, 2)
    ny, nx = conv_ops.output_spatial(6, 6, 3, 3, (0, 0, 0, 0), (1, 1))
    x = r.uniform(-1, 1, (2, ny, nx, 3))       # deconv input (B, ny, nx, K)
    w = r.uniform(-0.5, 0.5, (3, 3 * 3 * 2))
    proj = r.uniform(-1, 1, out_shape)

    def loss():
        y = conv_ops.deconv_forward_numpy(x, w, 3, 3, (0, 0, 0, 0), (1, 1),
                                          out_shape)
        return (y * proj).sum()

    err_in, gw = conv_ops.deconv_backward_numpy(
        x, proj, w, 3, 3, (0, 0, 0, 0), (1, 1))
    assert numpy.abs(gw - numdiff(loss, w)).max() < 1e-5
    assert numpy.abs(err_in - numdiff(loss, x)).max() < 1e-5


@pytest.mark.parametrize("mode", ["max", "maxabs", "avg"])
def test_pooling_backward_numdiff(mode):
    """Pooling err_input (winner scatter / window spread) vs numdiff,
    including ceil-mode truncated windows (5x5 input, 2x2/2 pooling)."""
    r = numpy.random.RandomState(5)
    x = r.uniform(-1, 1, (2, 5, 5, 2))
    ny, nx = pool_ops.output_spatial(5, 5, 2, 2, (2, 2))
    proj = r.uniform(-1, 1, (2, ny, nx, 2))

    if mode == "avg":
        def loss():
            return (pool_ops.avg_pooling_numpy(x, 2, 2, (2, 2)) *
                    proj).sum()
        err_in = pool_ops.avg_pooling_backward_numpy(
            proj, 2, 2, (2, 2), x.shape)
    else:
        use_abs = mode == "maxabs"

        def loss():
            out, _ = pool_ops.max_pooling_numpy(x, 2, 2, (2, 2),
                                                use_abs=use_abs)
            return (out * proj).sum()
        _, offs = pool_ops.max_pooling_numpy(x, 2, 2, (2, 2),
                                             use_abs=use_abs)
        err_in = pool_ops.max_pooling_backward_numpy(proj, offs, x.shape)

    assert numpy.abs(err_in - numdiff(loss, x)).max() < 1e-5


@pytest.mark.parametrize("device_cls", [NumpyDevice, JaxDevice])
def test_conv_workflow_gradients_match_numdiff(device_cls):
    """Whole conv+pool+FC+softmax unit chain: every layer's analytic
    gradient matches numdiff of an independently composed numpy loss
    (reference test_gd_workflow.py:61-246)."""
    device = device_cls()
    r = numpy.random.RandomState(7)
    x = r.uniform(-1, 1, (3, 8, 8, 1))
    labels = r.randint(0, 3, 3).astype(numpy.int32)
    b_size = len(x)

    wf = DummyWorkflow()
    rand = prng.RandomGenerator().seed(321)
    f0 = conv.ConvTanh(wf, n_kernels=2, kx=3, ky=3, sliding=(1, 1),
                       weights_stddev=0.3, bias_stddev=0.3)
    f0.rand = rand
    f0.input = Array(x.copy())
    f0.link_from(wf.start_point)
    f1 = pooling.MaxPooling(wf, kx=2, ky=2)
    f1.link_from(f0)
    f1.link_attrs(f0, ("input", "output"))
    f2 = all2all.All2AllTanh(wf, output_sample_shape=(5,),
                             weights_stddev=0.3, bias_stddev=0.3)
    f2.rand = rand
    f2.link_from(f1)
    f2.link_attrs(f1, ("input", "output"))
    f3 = all2all.All2AllSoftmax(wf, output_sample_shape=(3,),
                                weights_stddev=0.3, bias_stddev=0.3)
    f3.rand = rand
    f3.link_from(f2)
    f3.link_attrs(f2, ("input", "output"))

    ev = evaluator.EvaluatorSoftmax(wf)
    ev.link_from(f3)
    ev.link_attrs(f3, "output", "max_idx")
    ev.labels = Array(labels.copy())
    ev.batch_size = b_size

    g3 = gd.GDSoftmax(wf, apply_gradient=False)
    g3.link_from(ev)
    g3.link_attrs(ev, "err_output")
    g3.link_attrs(f3, "output", "input", "weights", "bias")
    g3.batch_size = b_size
    g2 = gd.GDTanh(wf, apply_gradient=False)
    g2.link_from(g3)
    g2.link_attrs(g3, ("err_output", "err_input"))
    g2.link_attrs(f2, "output", "input", "weights", "bias")
    g2.batch_size = b_size
    gp = gd_pooling.GDMaxPooling(wf, kx=2, ky=2, sliding=(2, 2))
    gp.link_from(g2)
    gp.link_attrs(g2, ("err_output", "err_input"))
    gp.link_attrs(f1, "input", "input_offset", "output")
    g0 = gd_conv.GDTanhConv(wf, apply_gradient=False,
                            need_err_input=False)
    g0.link_from(gp)
    g0.link_attrs(gp, ("err_output", "err_input"))
    g0.link_attrs(f0, "output", "input", "weights", "bias",
                  "n_kernels", "kx", "ky", "padding", "sliding")
    g0.batch_size = b_size

    units = (f0, f1, f2, f3, ev, g3, g2, gp, g0)
    for u in units:
        u.initialize(device=device)
    for u in units:
        u.run()

    w0 = f0.weights.map_write().mem
    b0 = f0.bias.map_write().mem
    w1 = f2.weights.map_write().mem
    b1 = f2.bias.map_write().mem
    w2 = f3.weights.map_write().mem
    b2 = f3.bias.map_write().mem

    def loss():
        h = conv_ops.forward_numpy(x, w0, b0, 3, 3, (0, 0, 0, 0), (1, 1),
                                   activation="tanh")
        p, _ = pool_ops.max_pooling_numpy(h, 2, 2, (2, 2))
        f = dense.forward_numpy(p.reshape(b_size, -1), w1, b1,
                                activation="tanh")
        y = dense.forward_numpy(f, w2, b2, activation="linear")
        sm, _ = dense.softmax_numpy(y)
        return -numpy.log(
            sm[numpy.arange(b_size), labels]).sum() / b_size

    checks = ((g0, w0, b0, "conv"), (g2, w1, b1, "fc"),
              (g3, w2, b2, "softmax"))
    for unit, w, b, tag in checks:
        unit.gradient_weights.map_read()
        unit.gradient_bias.map_read()
        dw = numpy.abs(unit.gradient_weights.mem - numdiff(loss, w)).max()
        db = numpy.abs(unit.gradient_bias.mem - numdiff(loss, b)).max()
        assert dw < 1e-5, "%s weights: %g" % (tag, dw)
        assert db < 1e-5, "%s bias: %g" % (tag, db)
