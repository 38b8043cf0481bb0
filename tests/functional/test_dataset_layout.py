"""The resident data set is stored in the layout the row gather reads
(ISSUE 31).

``FusedNet.set_dataset`` asks the compiler for the layout it gives the
operand of ``_gather_rows`` (``fused.gather_format``) and, where that is
not the layout the runtime placed the set in, relays the set once.  On a
TPU that takes a copy of the whole set out of every program that gathers
from it (``tests/unit/test_tpu_compile.py`` holds the compiles); here, on
the CPU, these tests pin:

* the CPU's answer is the layout the set already has: it is placed as
  before, the ``trainer.set_dataset`` span says ``layout="default"`` and
  ``trainer.dataset_relayouts`` stays 0;
* with the compiler's answer replaced by the one a TPU gives for images
  with 3 channels last (rows major-most, channels before pixels) the set
  is relaid once, the window program and the validation forward take it
  in that layout and are compiled once, and training, prediction and
  MSE's epoch permutation read the same rows as from a set left alone:
  every number equal;
* a second process over the first one's persistent compilation cache
  does the same (the relayout itself is never loaded from there).

Tier-1: small topologies in float32 on the conftest's virtual devices.
"""

import json
import os
import subprocess
import sys

import numpy
import pytest

import jax
from jax.experimental.layout import Format, Layout

from znicz_tpu.core import prng, telemetry
from znicz_tpu.core.config import root
from znicz_tpu.parallel import fused, make_mesh

CONV_LAYERS = [
    {"type": "conv_relu", "->": {"n_kernels": 4, "kx": 3, "ky": 3},
     "<-": {"learning_rate": 0.03}},
    {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
    {"type": "softmax", "->": {"output_sample_shape": 10},
     "<-": {"learning_rate": 0.03}},
]

MSE_LAYERS = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 6},
     "<-": {"learning_rate": 0.05}},
    {"type": "all2all", "->": {"output_sample_shape": 3},
     "<-": {"learning_rate": 0.05}},
]

#: what the TPU compiler answers for bf16[rows, y, x, 3]
ROWS_MAJOR = (0, 3, 1, 2)

MESHES = pytest.mark.parametrize("mesh", [None, 4],
                                 ids=["one_device", "mesh4"])


@pytest.fixture()
def relayouts():
    """Telemetry on and zeroed; yields a reader of the counter and of the
    ``layout`` attribute of every ``trainer.set_dataset`` span."""
    root.common.telemetry.enabled = True
    telemetry.reset()
    yield lambda: (telemetry.counter("trainer.dataset_relayouts").value,
                   [s[5]["layout"] for s in telemetry.spans()
                    if s[0] == "trainer.set_dataset"])
    root.common.telemetry.enabled = False


def _answer_as_a_tpu(monkeypatch):
    """From here on ``gather_format`` answers for a 4-d set as the TPU
    compiler does."""
    asked = fused.gather_format

    def answer(shape, dtype, sharding, minibatch):
        fmt, default = asked(shape, dtype, sharding, minibatch)
        if len(shape) == 4:
            fmt = Format(Layout(major_to_minor=ROWS_MAJOR), fmt.sharding)
        return fmt, default

    monkeypatch.setattr(fused, "gather_format", answer)


def _images(n=40):
    rng = numpy.random.RandomState(7)
    return (rng.normal(size=(n, 6, 6, 3)).astype(numpy.float32),
            rng.randint(0, 10, size=n))


def _net(mesh, layers=CONV_LAYERS, sample_shape=(6, 6, 3), **kwargs):
    return fused.FusedNet(
        [dict(l) for l in layers], sample_shape,
        mesh=None if mesh is None else make_mesh(mesh, model_parallel=1),
        rand=prng.RandomGenerator().seed(3), **kwargs)


def _stacked(net, n):
    return jax.tree.map(lambda *l: numpy.asarray(l, net.dtype),
                        *[net.hypers] * n)


def _train_and_predict(net, data, labels):
    """Three indexed windows of two steps (the last minibatch partial),
    then the indexed validation forward: parameters and outputs."""
    net.set_dataset(data, labels, minibatch=8)
    rng = numpy.random.RandomState(11)
    hy = _stacked(net, 2)
    for w in range(3):
        idx = rng.permutation(len(data))[:16].reshape(2, 8).astype(
            numpy.int32)
        sizes = [8, 8]
        if w == 2:
            idx[1, 5:], sizes = -1, [8, 5]
        net.run_window_indexed(idx, sizes, hy, final=(w == 2))
    idx = numpy.full(8, -1, numpy.int32)
    idx[:6] = rng.permutation(len(data))[:6]
    out, am = net.host_fetch(net.predict_indexed(idx, with_idx=True))
    return net.host_params(), out, am


@MESHES
def test_the_cpu_places_the_set_as_before(relayouts, mesh):
    net = _net(mesh)
    data, labels = _images()
    net.set_dataset(data, labels, minibatch=8)
    assert relayouts() == (0, ["default"])
    assert net._data_d.format.layout == net._data_format.layout
    assert net._data_d.format.layout.major_to_minor == (0, 1, 2, 3)
    numpy.testing.assert_array_equal(numpy.asarray(net._data_d), data)


@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"],
                         ids=["f32", "bf16_resident_set"])
@MESHES
def test_a_relaid_set_trains_and_predicts_the_same(relayouts, mesh,
                                                   compute_dtype,
                                                   monkeypatch):
    data, labels = _images()
    kwargs = {"compute_dtype": compute_dtype and jax.numpy.dtype(
        compute_dtype)}
    plain = _train_and_predict(_net(mesh, **kwargs), data, labels)
    assert relayouts() == (0, ["default"])
    _answer_as_a_tpu(monkeypatch)
    # 40 rows are stored as 3 blocks of 14: the last one starts at row 26
    monkeypatch.setattr(fused, "_STORE_BLOCKS", 3)
    net = _net(mesh, **kwargs)
    relaid = _train_and_predict(net, data, labels)
    data = data.astype(compute_dtype or data.dtype)
    assert net._data_d.dtype == data.dtype
    assert relayouts() == (1, ["default", ROWS_MAJOR])
    # the only reference to the stored array, in the layout asked for,
    # replicated over the mesh; the same values
    assert net._data_d.format.layout.major_to_minor == ROWS_MAJOR
    assert net._data_format.layout.major_to_minor == ROWS_MAJOR
    assert len(net._data_d.sharding.device_set) == (mesh or 1)
    assert net._data_d.sharding.is_fully_replicated
    numpy.testing.assert_array_equal(numpy.asarray(net._data_d), data)
    # both programs were compiled for the set as it is stored
    window = net._get_window_fn(2, "indexed")
    hy = jax.device_put(_stacked(net, 2))
    taken = window.lower(
        net.params, net.state, net._key, net._data_d, net._labels_d,
        net._place_window(numpy.zeros((2, 8), numpy.int32), 0), None,
        jax.numpy.array([8, 8], jax.numpy.int32), hy,
        net._window_acc()).compile().input_formats[0][3]
    assert taken.layout.major_to_minor == ROWS_MAJOR
    taken = net._fwd_idx_at.lower(
        net.params, net._data_d,
        net._place_valid_indices(numpy.zeros(8, numpy.int32)),
        None).compile().input_formats[0][1]
    assert taken.layout.major_to_minor == ROWS_MAJOR
    # and compiled once: what a window hands back goes in as it came out
    assert window._cache_size() == 1
    assert net._fwd_idx_at._cache_size() == 1
    for a, b in zip(plain[0], relaid[0]):
        for k in a:
            numpy.testing.assert_array_equal(a[k], b[k])
    numpy.testing.assert_array_equal(plain[1], relaid[1])
    numpy.testing.assert_array_equal(plain[2], relaid[2])


def test_the_epoch_permutation_reads_a_relaid_set(relayouts, monkeypatch):
    """MSE's sliced window: ``set_epoch_perm`` gathers the epoch's order
    from the same stored array; what it hands on keeps the default."""
    rng = numpy.random.RandomState(5)
    data = rng.normal(size=(24, 2, 3, 3)).astype(numpy.float32)
    targets = rng.normal(size=(24, 3)).astype(numpy.float32)
    _answer_as_a_tpu(monkeypatch)
    net = _net(None, MSE_LAYERS, (2, 3, 3), objective="mse")
    net.set_dataset(data, None, targets=targets, minibatch=8)
    assert relayouts() == (1, [ROWS_MAJOR])
    perm = rng.permutation(24)
    net.set_epoch_perm(perm, pad=8)
    assert net._data_p.format.layout.major_to_minor == (0, 1, 2, 3)
    numpy.testing.assert_array_equal(numpy.asarray(net._data_p)[:24],
                                     data[perm])
    numpy.testing.assert_array_equal(numpy.asarray(net._data_p)[24:], 0)
    net.run_window_mse_sliced([0, 8], 8, [8, 8], _stacked(net, 2),
                              final=True)
    assert all(numpy.isfinite(v).all()
               for p in net.host_params() for v in p.values())


#: a job's process with the persistent compilation cache on: a net over a
#: relaid set (the TPU's answer stands in for the CPU's) trains two
#: windows; prints a digest of the parameters, the layout the stored set
#: says it has, and how many of its programs came from the cache
_PROCESS = r"""
import hashlib, json, sys
import numpy
import jax
from jax.experimental.layout import Format, Layout
from znicz_tpu.core import compile_cache, prng, telemetry
from znicz_tpu.parallel import fused

telemetry.enable()
compile_cache.enable(sys.argv[1])
watch = compile_cache.watch()
asked = fused.gather_format

def answer(*of):
    fmt, default = asked(*of)
    return Format(Layout(major_to_minor=(0, 3, 1, 2)), fmt.sharding), default

fused.gather_format = answer
net = fused.FusedNet(
    [{"type": "conv_relu", "->": {"n_kernels": 4, "kx": 3, "ky": 3},
      "<-": {"learning_rate": 0.03}},
     {"type": "softmax", "->": {"output_sample_shape": 10},
      "<-": {"learning_rate": 0.03}}],
    (6, 6, 3), rand=prng.RandomGenerator().seed(3))
rng = numpy.random.RandomState(7)
net.set_dataset(rng.normal(size=(40, 6, 6, 3)).astype(numpy.float32),
                rng.randint(0, 10, size=40), minibatch=8)
hy = jax.tree.map(lambda *l: numpy.asarray(l, net.dtype), *[net.hypers] * 2)
for w in range(2):
    idx = rng.permutation(40)[:16].reshape(2, 8).astype(numpy.int32)
    net.run_window_indexed(idx, [8, 8], hy, final=(w == 1))
digest = hashlib.sha256()
for p in net.host_params():
    for k in sorted(p):
        digest.update(numpy.ascontiguousarray(p[k]).tobytes())
print("PROCESS " + json.dumps({
    "digest": digest.hexdigest(),
    "layout": net._data_d.format.layout.major_to_minor,
    "relayouts": telemetry.counter("trainer.dataset_relayouts").value,
    "cache_hits": watch.delta()["persistent_cache_hits"]}))
"""


def test_a_warm_process_reads_the_relaid_set_as_a_cold_one(tmp_path):
    """A second process over the first one's compilation cache.  jax
    0.9.0 hands back the outputs of an executable it loaded from that
    cache labelled with the default layout, whatever layout their bytes
    have; a relayout loaded from there made every later program read the
    set wrongly (found on the chip: PERF.md section 6, PR 31), so the
    relayout is never written there."""
    script = tmp_path / "process.py"
    script.write_text(_PROCESS)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    def process():
        proc = subprocess.run(
            [sys.executable, str(script), str(tmp_path / "xla_cache")],
            capture_output=True, text=True, timeout=600, cwd=repo,
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo))
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("PROCESS ")]
        assert proc.returncode == 0 and lines, proc.stderr[-2000:]
        return json.loads(lines[-1][len("PROCESS "):])

    cold, warm = process(), process()
    assert cold["cache_hits"] == 0 and warm["cache_hits"] > 0
    assert cold["relayouts"] == warm["relayouts"] == 1
    assert cold["layout"] == warm["layout"] == list(ROWS_MAJOR)
    assert warm["digest"] == cold["digest"]
