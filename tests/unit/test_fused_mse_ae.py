"""Fused MSE objective + deconv/depooling specs.

The unit-at-a-time graph is the executable spec: the fused jitted MSE
step must reproduce its updated weights in float64 — including the AE
stage pattern (conv -> maxabs pool -> depooling -> weight-SHARED deconv
trained against the input), where reference parity requires

* the shared weights to receive gradient ONLY through the deconv
  application (GDDeconv is the sole gradient unit, mnist_ae.py:126-136),
* the deconv to run in the tied conv's geometry (link_conv_attrs copies
  padding et al.), and
* the ``hits`` normalization of unsafe padding to stay OUT of the
  backward (gd_deconv backpropagates the undivided scatter).
"""

import numpy

import jax.numpy as jnp

from znicz_tpu.core.backends import NumpyDevice
from znicz_tpu.core.workflow import DummyWorkflow
from znicz_tpu.core import prng
from znicz_tpu.core.memory import Array
from znicz_tpu.units import all2all, conv as conv_units, deconv as \
    deconv_units, evaluator, gd, gd_pooling, pooling
from znicz_tpu.parallel import FusedNet, make_mesh
from znicz_tpu.parallel import fused

AE_LAYERS = [
    {"name": "c", "type": "conv",
     "->": {"n_kernels": 3, "kx": 5, "ky": 5, "include_bias": False,
            "weights_stddev": 0.1},
     "<-": {"learning_rate": 0.05, "weights_decay": 0.0,
            "gradient_moment": 0.9}},
    {"name": "p", "type": "maxabs_pooling",
     "->": {"kx": 3, "ky": 3, "sliding": (2, 2)}},
    {"name": "d", "type": "depooling", "->": {"tied_to": "p"}},
    {"name": "dc", "type": "deconv",
     "->": {"tied_to": "c", "unsafe_padding": True}},
]


def _ae_unit_graph(x, steps=3):
    """conv -> maxabs pool -> depool -> tied deconv -> MSE(input), only
    GDDeconv trains — the MnistAE stage graph (mnist_ae.py:64-190)."""
    B = len(x)
    wf = DummyWorkflow()
    rand = prng.RandomGenerator().seed(99)
    dev = NumpyDevice()
    cv = conv_units.Conv(wf, n_kernels=3, kx=5, ky=5, include_bias=False,
                         weights_stddev=0.1)
    cv.rand = rand
    cv.input = Array(x.copy())
    cv.link_from(wf.start_point)
    pl = pooling.MaxAbsPooling(wf, kx=3, ky=3, sliding=(2, 2))
    pl.link_from(cv)
    pl.link_attrs(cv, ("input", "output"))
    dp = gd_pooling.GDMaxAbsPooling(wf, kx=3, ky=3, sliding=(2, 2))
    dp.link_from(pl)
    dp.link_attrs(pl, "input", "input_offset", ("err_output", "output"))
    dc = deconv_units.Deconv(wf, unsafe_padding=True)
    dc.link_from(dp)
    dc.link_attrs(cv, "weights")
    dc.link_conv_attrs(cv)
    dc.link_attrs(dp, ("input", "err_input"))
    dc.link_attrs(cv, ("output_shape_source", "input"))
    ev = evaluator.EvaluatorMSE(wf)
    ev.link_from(dc)
    ev.link_attrs(dc, "output")
    ev.target = Array(x.copy())
    ev.batch_size = B
    gdd = deconv_units.GDDeconv(
        wf, learning_rate=0.05, weights_decay=0.0, gradient_moment=0.9,
        need_err_input=False)
    gdd.link_from(ev)
    gdd.link_attrs(ev, "err_output")
    gdd.link_attrs(dc, "weights", "input", "n_kernels", "kx", "ky",
                   "padding", "sliding")
    gdd.batch_size = B
    units = (cv, pl, dp, dc, ev, gdd)
    for u in units:
        u.initialize(device=dev)
    for _ in range(steps):
        for u in units:
            u.run()
    return cv, dc


def test_fused_ae_matches_unit_graph_float64():
    r = numpy.random.RandomState(5)
    x = r.uniform(-1, 1, (4, 12, 12, 1)).astype(numpy.float64)
    cv, dc_unit = _ae_unit_graph(x, steps=3)

    net = FusedNet(AE_LAYERS, (12, 12, 1),
                   rand=prng.RandomGenerator().seed(99),
                   dtype=numpy.float64, objective="mse")
    # deconv runs in the tied conv's geometry
    assert net.specs[3].padding == tuple(dc_unit.padding)
    for _ in range(3):
        m = net.step_mse(x, x, len(x))
    assert numpy.isfinite(float(m["loss"]))
    dw = numpy.abs(net.host_params()[0]["w"] - cv.weights.mem).max()
    assert dw < 1e-12, dw
    # deconv shares the conv's param slot — no separate weights
    assert net.host_params()[3] == {}


def test_fused_ae_output_matches_unit_forward():
    """The fused AE forward (same init PRNG draws) reproduces the unit
    graph's reconstruction exactly — the deconv output after one pass
    (unit weights update AFTER the forward, so dc.output reflects the
    initial weights both sides)."""
    r = numpy.random.RandomState(7)
    x = r.uniform(-1, 1, (2, 12, 12, 1)).astype(numpy.float64)
    cv, dc_unit = _ae_unit_graph(x, steps=1)
    y_unit = numpy.array(dc_unit.output.mem)

    net = FusedNet(AE_LAYERS, (12, 12, 1),
                   rand=prng.RandomGenerator().seed(99),
                   dtype=numpy.float64, objective="mse")
    y = numpy.asarray(fused.forward(net.params, jnp.asarray(x),
                                    tuple(net.specs)))
    assert y.shape == x.shape
    assert numpy.abs(y - y_unit).max() < 1e-12


def test_fused_ae_trains_on_mesh():
    """The AE stage trains data-parallel over the 8-device mesh."""
    mesh = make_mesh(8, model_parallel=2)
    r = numpy.random.RandomState(3)
    x = r.uniform(-1, 1, (16, 12, 12, 1)).astype(numpy.float32)
    net = FusedNet(AE_LAYERS, (12, 12, 1),
                   rand=prng.RandomGenerator().seed(4), mesh=mesh,
                   objective="mse")
    first = None
    for _ in range(20):
        m = net.step_mse(x, x, len(x))
        if first is None:
            first = float(m["loss"])
    assert float(m["loss"]) < first, "AE did not learn under SPMD"


def test_fused_mse_fc_matches_unit_graph():
    """Plain MSE regression head (Approximator/Kanji family): fused
    step_mse == All2AllTanh+All2All + EvaluatorMSE + gds in float64."""
    r = numpy.random.RandomState(11)
    x = r.uniform(-1, 1, (6, 10)).astype(numpy.float64)
    t = r.uniform(-1, 1, (6, 3)).astype(numpy.float64)
    B = len(x)

    wf = DummyWorkflow()
    rand = prng.RandomGenerator().seed(21)
    dev = NumpyDevice()
    f0 = all2all.All2AllTanh(wf, output_sample_shape=(7,),
                             weights_stddev=0.1, bias_stddev=0.1)
    f0.rand = rand
    f0.input = Array(x.copy())
    f0.link_from(wf.start_point)
    f1 = all2all.All2All(wf, output_sample_shape=(3,),
                         weights_stddev=0.1, bias_stddev=0.1)
    f1.rand = rand
    f1.link_from(f0)
    f1.link_attrs(f0, ("input", "output"))
    ev = evaluator.EvaluatorMSE(wf)
    ev.link_from(f1)
    ev.link_attrs(f1, "output")
    ev.target = Array(t.copy())
    ev.batch_size = B
    g1 = gd.GradientDescent(wf, learning_rate=0.1, weights_decay=0.0)
    g1.link_from(ev)
    g1.link_attrs(ev, "err_output")
    g1.link_attrs(f1, "output", "input", "weights", "bias")
    g1.batch_size = B
    g0 = gd.GDTanh(wf, learning_rate=0.1, weights_decay=0.0,
                   need_err_input=False)
    g0.link_from(g1)
    g0.link_attrs(g1, ("err_output", "err_input"))
    g0.link_attrs(f0, "output", "input", "weights", "bias")
    g0.batch_size = B
    units = (f0, f1, ev, g1, g0)
    for u in units:
        u.initialize(device=dev)
    for _ in range(2):
        for u in units:
            u.run()

    layers = [
        {"type": "all2all_tanh",
         "->": {"output_sample_shape": 7, "weights_stddev": 0.1,
                "bias_stddev": 0.1},
         "<-": {"learning_rate": 0.1, "weights_decay": 0.0}},
        {"type": "all2all",
         "->": {"output_sample_shape": 3, "weights_stddev": 0.1,
                "bias_stddev": 0.1},
         "<-": {"learning_rate": 0.1, "weights_decay": 0.0}},
    ]
    net = FusedNet(layers, 10, rand=prng.RandomGenerator().seed(21),
                   dtype=numpy.float64, objective="mse")
    for _ in range(2):
        net.step_mse(x, t, B)
    params = net.host_params()
    for i, f in enumerate((f0, f1)):
        dw = numpy.abs(params[i]["w"] - f.weights.mem).max()
        db = numpy.abs(params[i]["b"] - f.bias.mem).max()
        assert dw < 1e-12 and db < 1e-12, (i, dw, db)


def test_fused_mse_rejects_softmax_head():
    layers = [{"type": "softmax", "->": {"output_sample_shape": 3}}]
    try:
        FusedNet(layers, 5, objective="mse")
    except ValueError as e:
        assert "softmax" in str(e)
    else:
        raise AssertionError("mse objective accepted a softmax head")


# -- compiled stochastic pooling -----------------------

STOCH_AE_LAYERS = [
    {"name": "c", "type": "conv",
     "->": {"n_kernels": 3, "kx": 5, "ky": 5, "include_bias": False,
            "weights_stddev": 0.1},
     "<-": {"learning_rate": 0.02, "weights_decay": 0.0,
            "gradient_moment": 0.9}},
    {"name": "p", "type": "stochastic_abs_pooling",
     "->": {"kx": 3, "ky": 3, "sliding": (2, 2)}},
    {"name": "d", "type": "depooling", "->": {"tied_to": "p"}},
    {"name": "dc", "type": "deconv",
     "->": {"tied_to": "c", "unsafe_padding": True}},
]


def test_fused_stochastic_ae_stage_trains_compiled():
    """The ImagenetAE stage pattern with a STOCHASTIC pooling stays on
    the fast path: winners sampled from the jax PRNG key, depooling
    scatters to the recorded offsets, only the tied deconv trains —
    and the reconstruction loss decreases."""
    r = numpy.random.RandomState(3)
    x = r.uniform(-1, 1, (6, 12, 12, 1)).astype(numpy.float64)
    net = FusedNet(STOCH_AE_LAYERS, (12, 12, 1),
                   rand=prng.RandomGenerator().seed(99),
                   dtype=numpy.float64, objective="mse", dropout_seed=5)
    assert net._has_stochastic and net._needs_key
    losses = []
    for _ in range(12):
        m = net.step_mse(x, x)
        losses.append(float(m["loss"]))
    assert numpy.isfinite(losses).all()
    assert min(losses[6:]) < losses[0], losses


def test_fused_stochastic_pool_depool_trains():
    """The one-unit pool+depool variant (reference
    stochastic_pooling_depooling kernel) keeps the input shape and
    trains compiled."""
    layers = [
        {"name": "c", "type": "conv_tanh",
         "->": {"n_kernels": 2, "kx": 3, "ky": 3, "weights_stddev": 0.1},
         "<-": {"learning_rate": 0.05}},
        {"name": "pd", "type": "stochastic_pool_depool",
         "->": {"kx": 2, "ky": 2}},
        {"name": "sm", "type": "softmax",
         "->": {"output_sample_shape": 4}, "<-": {"learning_rate": 0.05}},
    ]
    r = numpy.random.RandomState(5)
    x = r.uniform(-1, 1, (8, 8, 8, 1)).astype(numpy.float32)
    labels = r.randint(0, 4, 8).astype(numpy.int32)
    net = FusedNet(layers, (8, 8, 1),
                   rand=prng.RandomGenerator().seed(7), dropout_seed=3)
    # pool+depool keeps the spatial shape
    assert net.specs[1].out_shape == net.specs[1].in_shape
    losses = [float(net.step(x, labels)["loss"]) for _ in range(15)]
    assert numpy.isfinite(losses).all()
    assert min(losses[5:]) < losses[0], losses
    # inference also samples (reference draws on every run) and the key
    # chain advances — two predicts generally differ, deterministically
    # from the snapshot-able key
    k_before = numpy.asarray(net._key)
    p1 = numpy.asarray(net.predict(x))
    assert not numpy.array_equal(numpy.asarray(net._key), k_before)
    assert numpy.isfinite(p1).all()


def test_fused_stochastic_distribution_matches_unit_op():
    """Distribution parity: over many draws the fused (jax-PRNG) winner
    frequencies match the value-proportional law the unit path's host
    stream produces (exact stream parity waived, like dropout)."""
    import jax
    from znicz_tpu.ops import pooling as pool_ops

    # one 2x2 window, values 1,2,3,4 (+abs): P(win) = v/10
    x = numpy.array([[[[1.0], [2.0]], [[3.0], [4.0]]]])
    layers = [{"name": "p", "type": "stochastic_pooling",
               "->": {"kx": 2, "ky": 2}}]
    specs = fused.build_specs(layers, (2, 2, 1))
    counts = numpy.zeros(4)
    key = jax.random.PRNGKey(0)
    draws = 3000
    fwd = jax.jit(lambda k: fused.forward(
        [{}], jnp.asarray(x), tuple(specs), key=k))
    keys = jax.random.split(key, draws)
    vals = numpy.asarray(jax.vmap(fwd)(keys)).reshape(draws)
    for v in vals:
        counts[int(round(v)) - 1] += 1
    freqs = counts / draws
    expect = numpy.array([0.1, 0.2, 0.3, 0.4])
    assert numpy.abs(freqs - expect).max() < 0.04, freqs

    # and the same law from the unit op fed a host uint16 stream
    r = numpy.random.RandomState(0)
    u16 = r.randint(0, 65536, draws).astype(numpy.uint16)
    counts_u = numpy.zeros(4)
    for i in range(draws):
        val, _ = pool_ops.stochastic_pooling_numpy(
            x, u16[i:i + 1], 2, 2, (2, 2))
        counts_u[int(round(float(val.ravel()[0]))) - 1] += 1
    assert numpy.abs(counts_u / draws - expect).max() < 0.04


def test_fused_ae_windowed_equals_per_step_float64():
    """The windowed MSE scan (run_window_mse — K steps, one compiled
    dispatch, in-scan metrics) reproduces K per-minibatch step_mse
    calls exactly on the AE stage, params AND evaluator metrics
    (mse_jax semantics)."""
    import jax
    from znicz_tpu.ops import evaluator as ev_ops

    r = numpy.random.RandomState(5)
    K, B = 4, 4
    xs = r.uniform(-1, 1, (K, B, 12, 12, 1)).astype(numpy.float64)

    def make_net():
        return FusedNet(AE_LAYERS, (12, 12, 1),
                        rand=prng.RandomGenerator().seed(99),
                        dtype=numpy.float64, objective="mse")

    net_1 = make_net()
    md_acc = numpy.zeros(3)
    md_acc[2] = numpy.inf
    for k in range(K):
        m = net_1.step_mse(xs[k], xs[k], B)
        _, md, mse_per = ev_ops.mse_jax(
            jnp.asarray(numpy.asarray(m["output"])), jnp.asarray(
                xs[k].reshape(B, -1)), B, mean=True, root=True)
        md = numpy.asarray(md)
        md_acc[0] += md[0]
        md_acc[1] = max(md_acc[1], md[1])
        md_acc[2] = min(md_acc[2], md[2])

    net_w = make_net()
    hy = jax.tree.map(
        lambda *leaves: numpy.asarray(leaves, numpy.float64),
        *[net_w.hypers] * K)
    lbl_s = numpy.full((K, B), -1, numpy.int32)
    stats = net_w.run_window_mse(xs, xs, lbl_s, [B] * K, hy)

    pa, pb = net_1.host_params(), net_w.host_params()
    for a, b in zip(pa, pb):
        for key in a:
            diff = numpy.abs(a[key] - b[key]).max()
            assert diff < 1e-12, (key, diff)
    md_w = numpy.asarray(stats["metrics"])
    assert numpy.abs(md_w - md_acc).max() < 1e-12, (md_w, md_acc)
    assert numpy.asarray(stats["mse_per"]).shape == (B,)
