"""Round-2 parity holes: stochastic pool-depool units, Gabor filling,
Kohonen map plotters, per-unit wall-time stats."""

import numpy
import pytest

from znicz_tpu.core.backends import NumpyDevice, JaxDevice
from znicz_tpu.core.memory import Array
from znicz_tpu.core import prng
from znicz_tpu.core.workflow import DummyWorkflow
from znicz_tpu.ops import pooling as pool_ops
from znicz_tpu.units import pooling as pool_units
from znicz_tpu.units.conv import fill_gabor_filters, gabor_kernel
from znicz_tpu.units import nn_plotting_units as nnp


# -- stochastic pooling-depooling -------------------------------------------

@pytest.mark.parametrize("use_abs", [False, True])
def test_pool_depool_jax_matches_numpy(use_abs):
    r = numpy.random.RandomState(3)
    x = r.uniform(-1, 1, (2, 6, 6, 3)).astype(numpy.float32)
    rand = r.randint(0, 1 << 16, 2 * 3 * 3 * 3).astype(numpy.uint16)
    yn, on = pool_ops.stochastic_pool_depool_numpy(x, rand, 2, 2, use_abs)
    yj, oj = pool_ops.stochastic_pool_depool_jax(x, rand, 2, 2, use_abs)
    assert yn.shape == x.shape
    assert numpy.abs(yn - numpy.asarray(yj)).max() == 0
    assert (on == numpy.asarray(oj)).all()
    # exactly one survivor per window, keeping its original value
    nz = yn != 0
    assert nz.sum() <= 2 * 3 * 3 * 3
    assert (yn[nz] == x[nz]).all()


def test_pool_depool_zero_sum_window_uniform():
    """All-negative windows (sum of max(x,0) == 0) sample uniformly via the
    kernel's pos_add walk."""
    x = -numpy.ones((1, 4, 4, 1), numpy.float32)
    rand = numpy.array([0, 30000, 50000, 65535], numpy.uint16)
    yn, on = pool_ops.stochastic_pool_depool_numpy(x, rand, 2, 2, False)
    yj, oj = pool_ops.stochastic_pool_depool_jax(x, rand, 2, 2, False)
    assert numpy.abs(yn - numpy.asarray(yj)).max() == 0
    assert (on == numpy.asarray(oj)).all()
    assert (yn != 0).sum() == 4   # one survivor in each of the 4 windows


@pytest.mark.parametrize("device_cls", [NumpyDevice, JaxDevice])
def test_pool_depool_unit(device_cls):
    w = DummyWorkflow()
    unit = pool_units.StochasticPoolingDepooling(
        w, kx=2, ky=2, uniform=prng.RandomGenerator().seed(11))
    r = numpy.random.RandomState(5)
    x = r.uniform(-1, 1, (3, 6, 6, 2)).astype(numpy.float32)
    unit.input = Array(x.copy())
    unit.initialize(device_cls())
    unit.run()
    unit.output.map_read()
    assert unit.output.shape == x.shape
    assert unit.input_offset.shape == (3, 3, 3, 2)
    nz = unit.output.mem != 0
    assert (unit.output.mem[nz] == x[nz]).all()


def test_pool_depool_registry_and_sliding_guard():
    from znicz_tpu.units.nn_units import mapping
    assert mapping["stochastic_pool_depool"].forward is \
        pool_units.StochasticPoolingDepooling
    assert mapping["stochastic_abs_pool_depool"].forward is \
        pool_units.StochasticAbsPoolingDepooling
    w = DummyWorkflow()
    unit = pool_units.StochasticPoolingDepooling(
        w, kx=2, ky=2, sliding=(1, 1))
    unit.input = Array(numpy.zeros((1, 4, 4, 1), numpy.float32))
    with pytest.raises(ValueError):
        unit.initialize(NumpyDevice())


# -- Gabor filling ----------------------------------------------------------

def test_gabor_filling():
    r = prng.RandomGenerator().seed(2)
    w = numpy.zeros((8, 5 * 5 * 2), numpy.float32)
    fill_gabor_filters(w, 5, 5, 2, 0.05, r)
    # all kernels filled, channels identical, values bounded by 255*stddev
    assert (numpy.abs(w).sum(axis=1) > 0).all()
    k0 = w[0].reshape(5, 5, 2)
    assert numpy.abs(k0[..., 0] - k0[..., 1]).max() == 0
    assert w.max() <= 255.0 * 0.05 + 1e-6 and w.min() >= 0.0
    # distinct filters
    assert numpy.abs(w[0] - w[1]).max() > 0

    # >96 kernels fall back to white noise
    w2 = numpy.zeros((100, 25), numpy.float32)
    fill_gabor_filters(w2, 5, 5, 1, 0.05, prng.RandomGenerator().seed(3))
    assert (numpy.abs(w2[96:]).sum(axis=1) > 0).all()
    assert w2[96:].min() < 0  # noise is signed; gabor rows are not

    # symmetry sanity of the kernel formula: theta=0, psi=0 is even in x
    k = gabor_kernel(5, 5, sigma=1.0, theta=0.0, lambd=4.0, gamma=1.0,
                     psi=0.0)
    assert numpy.abs(k - k[:, ::-1]).max() < 1e-12


def test_conv_gabor_weights_filling():
    from znicz_tpu.units.conv import Conv
    w = DummyWorkflow()
    unit = Conv(w, n_kernels=4, kx=3, ky=3, weights_filling="gabor",
                rand=prng.RandomGenerator().seed(1))
    unit.input = Array(numpy.zeros((2, 8, 8, 1), numpy.float32))
    unit.initialize(NumpyDevice())
    assert (numpy.abs(unit.weights.mem).sum(axis=1) > 0).all()


# -- Kohonen plotters --------------------------------------------------------

def _grid_plotter(cls, **kw):
    w = DummyWorkflow()
    p = cls(w, **kw)
    p.shape = (4, 3)
    return p


def test_kohonen_hits_plotter():
    p = _grid_plotter(nnp.KohonenHits)
    p.input = numpy.arange(12)
    p.fill()
    assert p.sizes.max() == 1.0 and p.sizes[0] == 0.0
    cx, cy = p.hex_centers()
    assert cx.size == 12
    assert cx[4] == 0.5  # odd row shifted


def test_kohonen_input_maps_plotter():
    p = _grid_plotter(nnp.KohonenInputMaps)
    r = numpy.random.RandomState(0)
    p.input = r.uniform(-1, 1, (12, 5))
    p.fill()
    assert len(p.maps) == 5
    for m in p.maps:
        assert m.min() == 0.0 and m.max() == 1.0


def test_kohonen_neighbor_map_plotter():
    p = _grid_plotter(nnp.KohonenNeighborMap)
    r = numpy.random.RandomState(1)
    w = r.uniform(-1, 1, (12, 5))
    p.input = w
    p.fill()
    # reference link count: (w-1)*h + up to (2w-1)*(h-1)
    assert len(p.links) == len(p.link_values)
    assert len(p.links) == (4 - 1) * 3 + (2 * 4 - 1) * (3 - 1)
    # first link is (0,0)-(1,0): plain L2 distance
    assert abs(p.link_values[0] -
               numpy.linalg.norm(w[0] - w[1])) < 1e-12


def test_kohonen_validation_results_plotter():
    p = _grid_plotter(nnp.KohonenValidationResults)
    p.input = numpy.arange(12)
    p.result = {0: {0, 1}, 1: {5}}
    p.fitness = 0.5
    p.fitness_by_label = {0: 0.4, 1: 0.6}
    p.fitness_by_neuron = {0: 0.3, 1: 0.2, 5: 0.9}
    p.fill()
    assert p.neuron_labels[0] == 0 and p.neuron_labels[5] == 1
    assert p.neuron_labels[7] == -1
    assert p.neuron_fitness[5] == 0.9


def test_kohonen_plotters_render(tmp_path):
    """redraw() writes a png for each plotter (Agg backend)."""
    from znicz_tpu.core.config import root
    old = root.common.dirs.cache
    root.common.dirs.cache = str(tmp_path)
    try:
        r = numpy.random.RandomState(2)
        for cls, setup in (
                (nnp.KohonenHits, dict(input=numpy.arange(12))),
                (nnp.KohonenInputMaps,
                 dict(input=r.uniform(-1, 1, (12, 3)))),
                (nnp.KohonenNeighborMap,
                 dict(input=r.uniform(-1, 1, (12, 3)))),
                (nnp.KohonenValidationResults,
                 dict(input=numpy.arange(12), result={0: {0}, 1: {5}},
                      fitness=0.5, fitness_by_label={0: 0.4, 1: 0.6},
                      fitness_by_neuron={0: 0.3, 5: 0.9})),
        ):
            p = _grid_plotter(cls)
            for k, v in setup.items():
                setattr(p, k, v)
            p.fill()
            p.redraw()
            assert p._fig_path is not None
            import os
            assert os.path.exists(p._fig_path)
    finally:
        root.common.dirs.cache = old


# -- per-unit timing stats ---------------------------------------------------

def test_unit_timing_stats():
    from znicz_tpu.core.units import Unit
    from znicz_tpu.core.workflow import Workflow

    class Sleepy(Unit):
        def run(self):
            pass

    w = Workflow()
    u = Sleepy(w, name="sleepy")
    u.link_from(w.start_point)
    w.end_point.link_from(u)
    w.initialize()
    w.run()
    assert u.run_count_ == 1
    assert u.run_time_ >= 0.0
    rows = w.unit_timings()
    assert any(r[0] is u for r in rows)
    w.log_unit_timings()  # must not raise
