"""The kept placed copy of a window's hyperparameters (ISSUE 29).

``FusedNet._place_window_scalars`` keeps, one entry a window length, the
stacked host hyper pytree it placed last beside its placed form, and hands
the placed form back while that very object is handed in again: the
trainer's cached stacked form, as long as no schedule moves a rate.  These
tests pin, on the conftest's virtual devices:

* the same object over six windows is placed once and reused five times
  (``trainer.hypers_placed`` / ``trainer.hypers_reused``), and trains bit
  for bit as a net that is handed a fresh copy, and so places, every
  window (the behaviour before the copy was kept);
* a rate a schedule moves (``GDProxy.serial``) is a miss, and the step
  takes the new value: the windowed trajectory on a mesh equals the
  per-minibatch one, with the boundary inside a window and between two;
* one entry a window length, whatever is handed in;
* without a mesh the jitted call is handed ``jax.Array`` leaves, the same
  ones from the second window on;
* a trainer that has run windows snapshots through pickle and resumes bit
  for bit: no device buffer is in its state.

Fast lane (tier-1): wine-sized FC topologies.
"""

import os
import pickle

import numpy
import pytest

import jax

from znicz_tpu.core.config import root
from znicz_tpu.core import prng, telemetry
from znicz_tpu.core.backends import JaxDevice
from znicz_tpu.core.snapshotter import SnapshotterToFile
from znicz_tpu.parallel import fused, make_mesh
from znicz_tpu.standard_workflow import StandardWorkflow
from znicz_tpu.units.nn_units import load_snapshot_into_workflow

FC_LAYERS = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
     "<-": {"learning_rate": 0.1}},
    {"type": "softmax", "->": {"output_sample_shape": 3},
     "<-": {"learning_rate": 0.1}},
]

MESHES = pytest.mark.parametrize("mesh", [None, 4],
                                 ids=["one_device", "mesh4"])


@pytest.fixture()
def counters():
    """Telemetry on and zeroed; yields a reader of the two counters as
    ``(placed, reused)``."""
    root.common.telemetry.enabled = True
    telemetry.reset()
    yield lambda: (telemetry.counter("trainer.hypers_placed").value,
                   telemetry.counter("trainer.hypers_reused").value)
    root.common.telemetry.enabled = False


@pytest.fixture()
def float64_engine():
    prev_type = root.common.engine.precision_type
    root.common.engine.precision_type = "double"
    root.common.engine.precision_dtype = numpy.float64
    yield
    root.common.engine.precision_type = prev_type
    root.common.engine.__dict__.pop("precision_dtype", None)


def _net(mesh):
    return fused.FusedNet(
        FC_LAYERS, 5, rand=prng.RandomGenerator().seed(7),
        mesh=None if mesh is None else make_mesh(mesh, model_parallel=1))


def _stacked(net, n, lr=None):
    """A fresh stacked host hyper pytree of ``n`` steps, as the trainer
    stacks one."""
    hypers = net.hypers
    if lr is not None:
        hypers = jax.tree.map(lambda _: lr, hypers)
    return jax.tree.map(lambda *l: numpy.asarray(l, net.dtype),
                        *[hypers] * n)


def _window(n, seed):
    rng = numpy.random.RandomState(seed)
    return (rng.rand(n, 8, 5).astype(numpy.float32),
            rng.randint(0, 3, (n, 8)).astype(numpy.int32))


# (1) the same object: placed once, and the training it feeds is the
# training of a net that places every window
@MESHES
def test_same_object_is_placed_once(counters, mesh):
    net_kept, net_fresh = _net(mesh), _net(mesh)
    hy = _stacked(net_kept, 2)
    for w in range(6):
        xs, ls = _window(2, w)
        net_kept.run_window(xs, ls, [8, 8], hy, final=(w == 5))
    assert counters() == (1, 5)
    for w in range(6):
        xs, ls = _window(2, w)
        net_fresh.run_window(xs, ls, [8, 8],
                             jax.tree.map(numpy.copy, hy), final=(w == 5))
    assert counters() == (7, 5)
    for kept, fresh in zip(net_kept.host_params(), net_fresh.host_params()):
        for k in kept:
            numpy.testing.assert_array_equal(kept[k], fresh[k])
    # a window that reused crossed only its batch sizes
    places = [s[5]["bytes"] for s in telemetry.spans()
              if s[0] == "trainer.place" and s[5]["bytes"] < 100]
    assert places.count(8) == 5


# (3) one entry a window length, and it is the object handed in last
@MESHES
def test_one_entry_a_window_length(counters, mesh):
    net = _net(mesh)
    for i in range(50):
        n = 2 if i % 2 == 0 else 1
        hy = _stacked(net, n, lr=0.1 / (1 + i))
        xs, ls = _window(n, i)
        net.run_window(xs, ls, [8] * n, hy)
        assert net._placed_hypers[n][0] is hy
    assert sorted(net._placed_hypers) == [1, 2]
    assert counters() == (50, 0)
    # the kept copies are replicated on the mesh, and hold what was placed
    for n, (host, placed) in net._placed_hypers.items():
        for h, p in zip(jax.tree.leaves(host), jax.tree.leaves(placed)):
            assert p.shape == (n,) and p.sharding.is_fully_replicated
            assert len(p.sharding.device_set) == (mesh or 1)
            numpy.testing.assert_array_equal(numpy.asarray(p), h)


# (4) without a mesh the compiled call takes device leaves, not numpy ones
def test_one_device_call_is_handed_device_leaves():
    net = _net(None)
    handed = []
    orig = net._dispatch_window

    def spy(kind, fn, inputs, *rest):
        handed.append(jax.tree.leaves(inputs[-1]))
        return orig(kind, fn, inputs, *rest)

    net._dispatch_window = spy
    hy = _stacked(net, 2)
    for w in range(3):
        xs, ls = _window(2, w)
        net.run_window(xs, ls, [8, 8], hy)
    assert all(isinstance(leaf, jax.Array)
               for leaves in handed for leaf in leaves)
    assert handed[0] and all(
        a is b for later in handed[1:] for a, b in zip(handed[0], later))
    # a caller's own placed pytree goes through as it is, and is not kept
    own = jax.device_put(_stacked(net, 2))
    xs, ls = _window(2, 9)
    net.run_window(xs, ls, [8, 8], own)
    assert all(a is b for a, b in zip(jax.tree.leaves(own), handed[-1]))
    assert net._placed_hypers[2][0] is hy


# -- through the workflow ----------------------------------------------------

def _seed():
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)


def _wine(tmp_path, fused_cfg, max_epochs, prefix, schedule_until=None):
    """Wine, 178 TRAIN rows at minibatch 16: twelve minibatches an epoch
    (the last one 2 rows, padded), three windows of four."""
    import znicz_tpu.loader.loader_wine  # noqa: F401 (registry)
    _seed()
    wf = StandardWorkflow(
        None, layers=[dict(l) for l in FC_LAYERS],
        loader_name="wine_loader", loader_config={"minibatch_size": 16},
        decision_config={"max_epochs": max_epochs, "fail_iterations": 100},
        snapshotter_config={"prefix": prefix, "interval": 1,
                            "time_interval": 0, "compression": "",
                            "directory": str(tmp_path)},
        fused=dict(fused_cfg))
    if schedule_until is not None:
        steps = [(1, schedule_until), (0.1, 100000)]
        wf.link_lr_adjuster(
            lr_policy_name="arbitrary_step",
            bias_lr_policy_name="arbitrary_step",
            lr_parameters={"lrs_with_lengths": steps},
            bias_lr_parameters={"lrs_with_lengths": steps})
    wf.initialize(device=JaxDevice())
    return wf


# (2) a rate the schedule moves is a miss, and the step takes the new value
@pytest.mark.parametrize("until, placed", [(3, 2), (4, 2), (100000, 1)],
                         ids=["inside_window", "between_windows", "never"])
def test_moved_rate_is_a_miss_on_the_mesh(tmp_path, float64_engine,
                                          counters, until, placed):
    """The 10x drop after train step ``until``, window 4 on a data=4 mesh,
    against the per-minibatch run on the same mesh (python-float
    hyperparameters, nothing placed).  Inside the first window the trainer
    stacks that window afresh; between the first two windows the proxies'
    serial moves and the cached stacked form is rebuilt; both are one more
    placement and every later window reuses."""
    wf_w = _wine(tmp_path, {"window": 4, "mesh": 4}, 2, "w4", until)
    wf_w.run()
    assert counters() == (placed, 6 - placed)
    wf_1 = _wine(tmp_path, {"window": 1, "mesh": 4}, 2, "w1", until)
    wf_1.run()
    assert counters() == (placed, 6 - placed)
    assert wf_w.lr_adjuster._minibatches_count == \
        wf_1.lr_adjuster._minibatches_count == 24
    rate = 0.1 * (1 if until > 24 else 0.1)
    for proxy in wf_w.fused_trainer.gd_proxies:
        assert proxy.learning_rate == pytest.approx(rate)
    assert list(wf_w.decision.epoch_n_err) == list(wf_1.decision.epoch_n_err)
    for la, lb in zip(wf_w.fused_trainer.host_params(),
                      wf_1.fused_trainer.host_params()):
        for k in la:
            assert numpy.abs(la[k] - lb[k]).max() < 1e-12


def _device_leaves(tree):
    return [leaf for leaf in jax.tree.leaves(tree)
            if isinstance(leaf, jax.Array)]


# (5) the kept copies are no part of a snapshot
def test_run_trainer_snapshots_and_resumes(tmp_path):
    wf_a = _wine(tmp_path, {"window": 4, "mesh": 4}, 3, "straight")
    wf_a.run()

    wf_b = _wine(tmp_path, {"window": 4, "mesh": 4}, 2, "interrupted")
    wf_b.run()
    assert wf_b.fused_trainer.net._placed_hypers
    state = wf_b.fused_trainer.fused_state
    assert not _device_leaves(state)
    assert not _device_leaves(pickle.loads(pickle.dumps(state)))
    snap = wf_b.snapshotter.destination
    assert snap and os.path.exists(snap)

    wf_c = _wine(tmp_path, {"window": 4, "mesh": 4}, 3, "resumed")
    load_snapshot_into_workflow(SnapshotterToFile.import_(snap), wf_c)
    assert wf_c.loader.epoch_number == 2
    wf_c.run()
    assert list(wf_c.decision.epoch_n_err) == list(wf_a.decision.epoch_n_err)
    for la, lc in zip(wf_a.fused_trainer.host_params(),
                      wf_c.fused_trainer.host_params()):
        for k in la:
            numpy.testing.assert_array_equal(la[k], lc[k])
