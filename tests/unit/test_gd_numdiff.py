"""Numeric differentiation of analytic gradients (float64 only).

The reference's strongest correctness harness (tests/unit/gd_numdiff.py:
43-156): perturb every weight/bias/input element with a five-point stencil,
compute d(loss)/d(theta) by finite differences, assert
|analytic - numeric| < 1e-5.  Here the loss is softmax cross-entropy
(mean over batch), matching EvaluatorSoftmax's err_output.
"""

import numpy
import pytest

from znicz_tpu.core.backends import NumpyDevice, JaxDevice
from znicz_tpu.core.workflow import DummyWorkflow
from znicz_tpu.core.memory import Array
from znicz_tpu.core import prng
from znicz_tpu.units import all2all, gd
from znicz_tpu.ops import dense

H = 1e-5
POINTS = (2 * H, H, -H, -2 * H)
COEFFS = numpy.array([-1.0, 8.0, -8.0, 1.0]) / (12.0 * H)


def ce_loss(x, params, labels):
    """Forward the 2-layer net in float64 numpy and return mean CE."""
    (w1, b1), (w2, b2) = params
    h = dense.forward_numpy(x, w1, b1, activation="tanh")
    y = dense.forward_numpy(h, w2, b2, activation="linear")
    sm, _ = dense.softmax_numpy(y)
    n = x.shape[0]
    return -numpy.log(sm[numpy.arange(n), labels]).sum() / n


def numdiff(f, arr):
    """Five-point numeric gradient of scalar f w.r.t. every arr element."""
    g = numpy.zeros_like(arr)
    flat = arr.reshape(-1)
    gf = g.reshape(-1)
    for i in range(flat.size):
        orig = flat[i]
        vals = []
        for d in POINTS:
            flat[i] = orig + d
            vals.append(f())
        flat[i] = orig
        gf[i] = (numpy.array(vals) * COEFFS).sum()
    return g


def build_net(device):
    rng = numpy.random.RandomState(11)
    x = rng.uniform(-1, 1, (4, 5))
    labels = rng.randint(0, 3, 4).astype(numpy.int32)

    wf = DummyWorkflow()
    f1 = all2all.All2AllTanh(wf, output_sample_shape=(6,),
                             weights_stddev=0.3, bias_stddev=0.3)
    f1.rand = prng.RandomGenerator().seed(5)
    f1.input = Array(x.copy())
    f2 = all2all.All2AllSoftmax(wf, output_sample_shape=(3,),
                                weights_stddev=0.3, bias_stddev=0.3)
    f2.rand = prng.RandomGenerator().seed(6)
    f2.link_attrs(f1, ("input", "output"))
    for f in (f1, f2):
        f.link_from(wf.start_point)
        f.initialize(device=device)
    return wf, x, labels, f1, f2


@pytest.mark.parametrize("device_cls", [NumpyDevice, JaxDevice])
def test_gradients_match_numdiff(device_cls):
    device = device_cls()
    wf, x, labels, f1, f2 = build_net(device)
    f1.run()
    f2.run()

    # evaluator math: err_output = (softmax - onehot)/batch
    n = x.shape[0]
    sm = f2.output.mem
    err = sm.copy()
    err[numpy.arange(n), labels] -= 1.0
    err /= n

    g2 = gd.GDSoftmax(wf, apply_gradient=False)
    g2.err_output = Array(err.copy())
    g2.link_attrs(f2, "output", "input", "weights", "bias")
    g2.initialize(device=device)
    g2.run()

    g1 = gd.GDTanh(wf, apply_gradient=False, need_err_input=False)
    g1.link_attrs(g2, ("err_output", "err_input"))
    g1.link_attrs(f1, "output", "input", "weights", "bias")
    g1.initialize(device=device)
    g1.run()

    params = [(f1.weights.map_write().mem, f1.bias.map_write().mem),
              (f2.weights.map_write().mem, f2.bias.map_write().mem)]
    loss = lambda: ce_loss(x, params, labels)  # noqa: E731

    for unit, (w, b), tag in ((g2, params[1], "layer2"),
                              (g1, params[0], "layer1")):
        gw_num = numdiff(loss, w)
        gb_num = numdiff(loss, b)
        gw_ana = unit.gradient_weights.mem
        gb_ana = unit.gradient_bias.mem
        assert numpy.abs(gw_ana - gw_num).max() < 1e-5, tag
        assert numpy.abs(gb_ana - gb_num).max() < 1e-5, tag

    assert g2.err_input.mem.shape == f1.output.shape


# -- AdamW (ops/gd_math.adamw): the jax twin, the numpy twin, a numerical
# derivative ----------------------------------------------------------------

ADAMW_HYPER = dict(lr=3e-3, wd=0.1, adam_beta1=0.9, adam_beta2=0.95,
                   adam_eps=1e-8)
ADAMW_FLAGS = dict(solvers=frozenset(["adamw"]), apply=True)


def _adamw_problem():
    from znicz_tpu.ops import gd_math
    rng = numpy.random.RandomState(3)
    w = rng.normal(size=(4, 3))
    a = rng.normal(size=(3, 3))
    a = a @ a.T + numpy.eye(3)          # loss = 0.5 sum_i w_i A w_i^T
    return gd_math, w, a


def test_adamw_jax_twin_equals_numpy_twin_over_steps():
    import jax.numpy as jnp
    gd_math, w, a = _adamw_problem()
    wn, sn = w.copy(), gd_math.init_state(w, ADAMW_FLAGS)
    wj, sj = jnp.asarray(w), gd_math.init_state(jnp.asarray(w), ADAMW_FLAGS,
                                                like=jnp)
    assert set(sn) == {"m", "v", "t"}
    for _ in range(5):
        wn, sn = gd_math.update_numpy(wn, wn @ a, sn, ADAMW_HYPER,
                                      ADAMW_FLAGS)
        wj, sj = gd_math.update_jax(wj, wj @ jnp.asarray(a), sj,
                                    ADAMW_HYPER, ADAMW_FLAGS)
    numpy.testing.assert_allclose(numpy.asarray(wj), wn, rtol=1e-12)
    for leaf in ("m", "v", "t"):
        numpy.testing.assert_allclose(numpy.asarray(sj[leaf]), sn[leaf],
                                      rtol=1e-12)
    assert float(sn["t"]) == 5.0


def test_adamw_step_from_a_numerical_derivative():
    """Fed the five-point derivative of the loss, the first step is the
    closed form ``-lr (g / (|g| + eps) + wd w)`` (bias correction makes the
    first moment the gradient itself), and later steps follow the analytic
    gradient's to 1e-8."""
    gd_math, w, a = _adamw_problem()

    def loss():
        return 0.5 * (w @ a * w).sum()

    g_num = numdiff(loss, w)
    numpy.testing.assert_allclose(g_num, w @ a, atol=1e-8)
    state = gd_math.init_state(w, ADAMW_FLAGS)
    w1, s1, applied = gd_math.update(numpy, w, g_num, state, ADAMW_HYPER,
                                     ADAMW_FLAGS)
    want = -ADAMW_HYPER["lr"] * (g_num / (numpy.abs(g_num) + 1e-8)
                                 + ADAMW_HYPER["wd"] * w)
    numpy.testing.assert_allclose(applied, want, rtol=1e-9)
    numpy.testing.assert_allclose(w1, w + want, rtol=1e-12)
    w2n, _ = gd_math.update_numpy(w1, w1 @ a, s1, ADAMW_HYPER, ADAMW_FLAGS)
    wa, sa = gd_math.update_numpy(w, w @ a, state, ADAMW_HYPER, ADAMW_FLAGS)
    w2a, _ = gd_math.update_numpy(wa, wa @ a, sa, ADAMW_HYPER, ADAMW_FLAGS)
    numpy.testing.assert_allclose(w2n, w2a, atol=1e-8)


def test_adamw_decay_is_decoupled_and_leaves_other_solvers_alone():
    gd_math, w, a = _adamw_problem()
    state = gd_math.init_state(w, ADAMW_FLAGS)
    zero_g = numpy.zeros_like(w)
    w1, _ = gd_math.update_numpy(w, zero_g, state, ADAMW_HYPER, ADAMW_FLAGS)
    # no gradient: pure decay, not routed through the moments
    numpy.testing.assert_allclose(
        w1, w * (1 - ADAMW_HYPER["lr"] * ADAMW_HYPER["wd"]), rtol=1e-12)
    plain = gd_math.init_state(w, dict(solvers=frozenset()))
    assert set(plain) == {"vel"}
