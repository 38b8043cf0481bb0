"""Pooling: jax-vs-numpy cross-validation incl. ceil-mode overhang windows,
offset parity, and backward scatter checks (reference tests/unit/
test_pooling.py pattern)."""

import numpy
import pytest

from znicz_tpu.core.backends import NumpyDevice, JaxDevice
from znicz_tpu.core.workflow import DummyWorkflow
from znicz_tpu.core.memory import Array
from znicz_tpu.core import prng
from znicz_tpu.ops import pooling as pool_ops
from znicz_tpu.units import pooling as pool_units
from znicz_tpu.units import gd_pooling

def _pallas_interpreted(x, ky, kx, sliding, use_abs=False):
    """The Pallas max-pool kernel run off the chip: the TEST picks the
    interpreter (the kernel itself is always built for the TPU's
    compiler; ops/pooling.py routes non-TPU backends to the gather
    lowering)."""
    from jax.experimental.pallas import tpu as pltpu
    from znicz_tpu.ops.pallas_pooling import max_pooling_offsets_pallas
    with pltpu.force_tpu_interpret_mode():
        return max_pooling_offsets_pallas(x, ky, kx, tuple(sliding),
                                          use_abs)


GEOMS = [
    # (sy, sx, c, ky, kx, sliding) — second has overhanging windows
    (6, 6, 3, 2, 2, (2, 2)),
    (5, 7, 2, 3, 2, (2, 3)),
    (4, 4, 1, 3, 3, (3, 3)),
]


@pytest.mark.parametrize("geom", GEOMS)
@pytest.mark.parametrize("use_abs", [False, True])
def test_max_pooling_jax_matches_numpy(geom, use_abs):
    sy, sx, c, ky, kx, sliding = geom
    r = numpy.random.RandomState(1)
    x = r.uniform(-1, 1, (3, sy, sx, c)).astype(numpy.float32)
    on, offn = pool_ops.max_pooling_numpy(x, ky, kx, sliding, use_abs)
    oj, offj = pool_ops.max_pooling_jax(x, ky, kx, sliding, use_abs)
    assert numpy.abs(on - numpy.asarray(oj)).max() == 0
    assert (offn == numpy.asarray(offj)).all()


@pytest.mark.parametrize("geom", GEOMS)
def test_avg_pooling_jax_matches_numpy(geom):
    sy, sx, c, ky, kx, sliding = geom
    r = numpy.random.RandomState(2)
    x = r.uniform(-1, 1, (3, sy, sx, c)).astype(numpy.float64)
    on = pool_ops.avg_pooling_numpy(x, ky, kx, sliding)
    oj = pool_ops.avg_pooling_jax(x, ky, kx, sliding)
    assert numpy.abs(on - numpy.asarray(oj)).max() < 1e-12


@pytest.mark.parametrize("geom", GEOMS)
@pytest.mark.parametrize("use_abs", [False, True])
def test_stochastic_pooling_jax_matches_numpy(geom, use_abs):
    sy, sx, c, ky, kx, sliding = geom
    r = numpy.random.RandomState(3)
    x = r.uniform(-1, 1, (2, sy, sx, c)).astype(numpy.float64)
    ny, nx = pool_ops.output_spatial(sy, sx, ky, kx, sliding)
    rand = r.randint(0, 1 << 16, 2 * ny * nx * c).astype(numpy.uint16)
    on, offn = pool_ops.stochastic_pooling_numpy(x, rand, ky, kx, sliding,
                                                 use_abs)
    oj, offj = pool_ops.stochastic_pooling_jax(x, rand, ky, kx, sliding,
                                               use_abs)
    assert (offn == numpy.asarray(offj)).all()
    assert numpy.abs(on - numpy.asarray(oj)).max() == 0


@pytest.mark.parametrize("geom", GEOMS)
def test_max_backward_scatter(geom):
    sy, sx, c, ky, kx, sliding = geom
    r = numpy.random.RandomState(4)
    x = r.uniform(-1, 1, (2, sy, sx, c)).astype(numpy.float64)
    _, offs = pool_ops.max_pooling_numpy(x, ky, kx, sliding)
    err = r.uniform(-1, 1, offs.shape).astype(numpy.float64)
    en = pool_ops.max_pooling_backward_numpy(err, offs, x.shape)
    ej = pool_ops.max_pooling_backward_jax(err, offs, x.size, x.shape)
    assert numpy.abs(en - numpy.asarray(ej)).max() < 1e-12
    assert abs(en.sum() - err.sum()) < 1e-9  # scatter conserves mass


@pytest.mark.parametrize("geom", GEOMS)
def test_avg_backward_matches_vjp_and_numpy(geom):
    sy, sx, c, ky, kx, sliding = geom
    r = numpy.random.RandomState(5)
    x = r.uniform(-1, 1, (2, sy, sx, c)).astype(numpy.float64)
    out = pool_ops.avg_pooling_numpy(x, ky, kx, sliding)
    err = r.uniform(-1, 1, out.shape).astype(numpy.float64)
    en = pool_ops.avg_pooling_backward_numpy(err, ky, kx, sliding, x.shape)
    ej = pool_ops.avg_pooling_backward_jax(err, ky, kx, sliding, x.shape)
    assert numpy.abs(en - numpy.asarray(ej)).max() < 1e-12


@pytest.mark.parametrize("device_cls", [NumpyDevice, JaxDevice])
def test_pooling_units_graph(device_cls):
    """MaxPooling + GDMaxPooling and AvgPooling + GDAvgPooling units."""
    device = device_cls()
    r = numpy.random.RandomState(6)
    x = r.uniform(-1, 1, (2, 5, 5, 2)).astype(numpy.float64)

    wf = DummyWorkflow()
    fwd = pool_units.MaxPooling(wf, kx=2, ky=2)
    fwd.input = Array(x.copy())
    fwd.link_from(wf.start_point)
    fwd.initialize(device=device)
    fwd.run()
    assert fwd.output.shape == (2, 3, 3, 2)

    err = r.uniform(-1, 1, fwd.output.shape).astype(numpy.float64)
    bwd = gd_pooling.GDMaxPooling(wf)
    bwd.err_output = Array(err.copy())
    bwd.link_attrs(fwd, "input", "input_offset", "kx", "ky", "sliding")
    bwd.initialize(device=device)
    bwd.run()
    assert bwd.err_input.shape == x.shape
    assert abs(numpy.asarray(bwd.err_input.mem).sum() - err.sum()) < 1e-9

    fwd2 = pool_units.AvgPooling(wf, kx=3, ky=3, sliding=(2, 2))
    fwd2.input = Array(x.copy())
    fwd2.link_from(wf.start_point)
    fwd2.initialize(device=device)
    fwd2.run()
    bwd2 = gd_pooling.GDAvgPooling(wf)
    err2 = r.uniform(-1, 1, fwd2.output.shape).astype(numpy.float64)
    bwd2.err_output = Array(err2.copy())
    bwd2.link_attrs(fwd2, "input", "kx", "ky", "sliding")
    bwd2.initialize(device=device)
    bwd2.run()
    assert bwd2.err_input.shape == x.shape


def test_stochastic_units_same_seed_same_result():
    outs = {}
    for device in (NumpyDevice(), JaxDevice()):
        r = numpy.random.RandomState(7)
        x = r.uniform(-1, 1, (2, 4, 4, 2)).astype(numpy.float64)
        wf = DummyWorkflow()
        fwd = pool_units.StochasticPooling(
            wf, kx=2, ky=2, uniform=prng.RandomGenerator().seed(21))
        fwd.input = Array(x.copy())
        fwd.link_from(wf.start_point)
        fwd.initialize(device=device)
        fwd.run()
        outs[device.backend_name] = (numpy.array(fwd.output.mem),
                                     numpy.array(fwd.input_offset.mem))
    assert (outs["numpy"][1] == outs["jax"][1]).all()
    assert numpy.abs(outs["numpy"][0] - outs["jax"][0]).max() == 0


@pytest.mark.parametrize("geom", GEOMS)
@pytest.mark.parametrize("mode", ["max", "maxabs", "avg"])
def test_pooling_fwd_reduce_window_matches_numpy(geom, mode):
    """The offset-free reduce_window formulation (fused path) reproduces
    the numpy twins, including ceil-mode overhang."""
    import jax
    import jax.numpy as jnp

    sy, sx, c, ky, kx, sliding = geom
    r = numpy.random.RandomState(7)
    x = r.uniform(-1, 1, (3, sy, sx, c)).astype(numpy.float64)
    oj = pool_ops.pooling_fwd_jax(x, ky, kx, sliding, mode=mode)
    if mode == "avg":
        on = pool_ops.avg_pooling_numpy(x, ky, kx, sliding)
        assert numpy.abs(on - numpy.asarray(oj)).max() < 1e-12
    else:
        on, _ = pool_ops.max_pooling_numpy(x, ky, kx, sliding,
                                           use_abs=(mode == "maxabs"))
        assert numpy.abs(on - numpy.asarray(oj)).max() == 0
    # differentiable (the fused path takes jax.grad through it)
    g = jax.grad(lambda x: jnp.sum(
        pool_ops.pooling_fwd_jax(x, ky, kx, sliding, mode=mode) ** 2))(x)
    assert numpy.isfinite(numpy.asarray(g)).all()


@pytest.mark.parametrize("geom", GEOMS + [(24, 24, 64, 2, 2, (2, 2))])
@pytest.mark.parametrize("use_abs", [False, True])
def test_pallas_pooling_kernel_bit_parity(geom, use_abs):
    """The fused Pallas max-pool kernel (ops/pallas_pooling.py) is
    bit-exact against the numpy twin — values AND winner offsets,
    including overhanging ceil-mode windows and tie-breaking."""
    sy, sx, c, ky, kx, sliding = geom
    r = numpy.random.RandomState(11)
    x = r.uniform(-1, 1, (3, sy, sx, c)).astype(numpy.float32)
    # force exact ties inside windows to pin the first-winner rule
    x[:, 0, :2, :] = 0.5
    on, offn = pool_ops.max_pooling_numpy(x, ky, kx, sliding, use_abs)
    op, offp = _pallas_interpreted(x, ky, kx, sliding, use_abs)
    assert numpy.abs(on - numpy.asarray(op)).max() == 0
    assert (offn == numpy.asarray(offp)).all()


def test_max_pooling_jax_gather_fallback_parity():
    """The non-float (gather) path stays bit-exact too."""
    r = numpy.random.RandomState(12)
    x = r.randint(-9, 9, (2, 6, 6, 3)).astype(numpy.int32)
    on, offn = pool_ops.max_pooling_numpy(x, 2, 2, (2, 2))
    oj, offj = pool_ops.max_pooling_jax(x, 2, 2, (2, 2))
    assert (on == numpy.asarray(oj)).all()
    assert (offn == numpy.asarray(offj)).all()


def test_pallas_pooling_review_regressions():
    """supported() works on tracers and bounds VMEM; sentinel-valued
    inputs (-inf / finfo.min) still pick the right winner; maxabs
    pooling stays differentiable through the fused forward."""
    import jax
    import jax.numpy as jnp
    from znicz_tpu.ops import pallas_pooling

    # 1. tracer-safe dtype check (no numpy.asarray on tracers)
    @jax.jit
    def pooled(x):
        assert pallas_pooling.supported(x, 2, 2, (2, 2), False)
        return pool_ops.max_pooling_jax(x, 2, 2, (2, 2))[0]
    r = numpy.random.RandomState(5)
    x = r.uniform(-1, 1, (2, 6, 6, 3)).astype(numpy.float32)
    assert pooled(x).shape == (2, 3, 3, 3)

    # 2. VMEM bound: oversized maps fall back to the gather path
    big = numpy.zeros((1, 2048, 2048, 1), numpy.float32)
    assert not pallas_pooling.supported(big, 2, 2, (2, 2), False)

    # 3. -inf / finfo.min values must win over the init sentinel
    xm = numpy.full((1, 2, 2, 1), -numpy.inf, numpy.float32)
    xm[0, 1, 1, 0] = numpy.float32(numpy.finfo(numpy.float32).min)
    on, offn = pool_ops.max_pooling_numpy(xm, 2, 2, (2, 2))
    for op, offp in (_pallas_interpreted(xm, 2, 2, (2, 2)),
                     pool_ops.max_pooling_jax(xm, 2, 2, (2, 2))):
        assert numpy.array_equal(on, numpy.asarray(op))
        assert numpy.array_equal(offn, numpy.asarray(offp))

    # 4. fused maxabs differentiates (gather path)
    from znicz_tpu.parallel import fused
    g = jax.grad(lambda x: jnp.sum(
        pool_ops.max_pooling_gather_jax(x, 2, 2, (2, 2),
                                         use_abs=True)[0]))(
        jnp.asarray(x, jnp.float32))
    assert numpy.isfinite(numpy.asarray(g)).all()
    specs = fused.build_specs(
        [{"type": "conv_tanh", "->": {"n_kernels": 2, "kx": 3, "ky": 3}},
         {"type": "maxabs_pooling", "->": {"kx": 2, "ky": 2}},
         {"type": "all2all_tanh", "->": {"output_sample_shape": 4}},
         {"type": "softmax", "->": {"output_sample_shape": 2}}],
        (6, 6, 1))
    params = fused.init_params(specs)
    grads = jax.grad(lambda p: fused._loss_and_stats(
        p, jnp.zeros((2, 6, 6, 1), jnp.float32),
        jnp.zeros(2, jnp.int32), tuple(specs))[0])(params)
    assert all(numpy.isfinite(numpy.asarray(v)).all()
               for d in grads for v in d.values())


def test_pallas_kernel_review_regressions():
    """Review findings, pinned: (a) the kernel computes in f32,
    so float64 must NOT route through it (values would round and
    winners could flip); (b) a real -inf cell inside a ceil-mode
    overhang window must beat the padding sentinel (the winner offset
    must stay in-bounds)."""
    import jax.numpy as jnp
    from znicz_tpu.ops import pallas_pooling, pooling as pool_ops

    # (a) f64 rejected by the gate; max_pooling_jax still exact via the
    # window-view path
    x64 = numpy.zeros((1, 2, 2, 1))
    x64[0, 0, 0, 0] = 1.0
    x64[0, 1, 1, 0] = 1.0 + 1e-12
    assert not pallas_pooling.supported(jnp.asarray(x64), 2, 2, (2, 2),
                                        False)
    val, off = pool_ops.max_pooling_jax(jnp.asarray(x64), 2, 2, (2, 2))
    ref_val, ref_off = pool_ops.max_pooling_numpy(x64, 2, 2, (2, 2))
    assert float(val.ravel()[0]) == float(ref_val.ravel()[0])
    assert int(off.ravel()[0]) == int(ref_off.ravel()[0])

    # (b) -inf in the overhang window: winner = the real -inf cell, not
    # the sentinel padding (offset must be in-bounds)
    x = numpy.zeros((1, 3, 3, 1), numpy.float32)
    x[0, 2, 2, 0] = -numpy.inf
    x[0, :2, :2, 0] = 5.0  # window (0,0) is benign
    ref_val, ref_off = pool_ops.max_pooling_numpy(x, 2, 2, (2, 2))
    for val, off in (_pallas_interpreted(jnp.asarray(x), 2, 2, (2, 2)),
                     pool_ops.max_pooling_jax(jnp.asarray(x), 2, 2,
                                              (2, 2))):
        numpy.testing.assert_array_equal(numpy.asarray(val), ref_val)
        numpy.testing.assert_array_equal(numpy.asarray(off), ref_off)
        assert int(numpy.asarray(off).max()) < x.size
