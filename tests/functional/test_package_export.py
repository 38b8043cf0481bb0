"""Package export + C++ inference runtime (libZnicz parity).

A trained workflow exports to the
package zip and a non-Python runtime executes it — outputs match the
Python forward to 1e-5 (reference libZnicz/tests/functional_mnist.cc,
test_package_export.py).
"""

import ctypes
import os
import subprocess

import numpy
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.backends import NumpyDevice
from znicz_tpu.export import export_package, load_package, \
    run_package_numpy
from znicz_tpu.samples import mnist

CPP_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       os.pardir, os.pardir, "cpp")


def _build_cpp():
    """Build (cached) the C++ runtime; skip tests when no toolchain."""
    try:
        res = subprocess.run(["make", "-j4"], cwd=CPP_DIR, check=False,
                             capture_output=True, text=True, timeout=300)
    except OSError as e:  # make itself missing
        pytest.skip("C++ toolchain unavailable: %s" % e)
    assert res.returncode == 0, \
        "C++ build failed (a compile error is a test failure, not a " \
        "skip):\n%s" % res.stderr
    return os.path.join(CPP_DIR, "build")


def _trained_mlp(tmp_path):
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    wf = mnist.build(
        loader_config={"synthetic_train": 300, "synthetic_valid": 100,
                       "minibatch_size": 50},
        decision_config={"max_epochs": 2, "fail_iterations": 10},
        snapshotter_config={"prefix": "pkg", "interval": 1,
                            "time_interval": 0, "compression": "",
                            "directory": str(tmp_path)})
    wf.initialize(device=NumpyDevice())
    wf.run()
    return wf


def _python_forward(wf, x):
    """Run the trained workflow's own forward stack on a fresh batch."""
    wf.forwards[0].input.reset(x.astype(
        wf.forwards[0].weights.mem.dtype))
    for fwd in wf.forwards:
        fwd.run()
    out = wf.forwards[-1].output
    out.map_read()
    return numpy.array(out.mem)


def test_package_roundtrip_and_numpy_runner(tmp_path):
    wf = _trained_mlp(tmp_path)
    pkg = str(tmp_path / "mnist.zip")
    export_package(wf, pkg)

    manifest, arrays = load_package(pkg)
    assert [l["type"] for l in manifest["layers"]] == \
        ["all2all_tanh", "softmax"]
    assert arrays["layer0_weights.npy"].shape == (100, 784)

    x = numpy.random.RandomState(0).uniform(
        -1, 1, (50, 784)).astype(numpy.float32)
    y_py = _python_forward(wf, x)
    y_pkg = run_package_numpy(pkg, x)
    assert numpy.abs(y_py - y_pkg).max() < 1e-5


def test_cpp_cli_matches_python(tmp_path):
    build = _build_cpp()
    wf = _trained_mlp(tmp_path)
    pkg = str(tmp_path / "mnist.zip")
    export_package(wf, pkg)

    x = numpy.random.RandomState(1).uniform(
        -1, 1, (50, 784)).astype(numpy.float32)
    in_npy = str(tmp_path / "in.npy")
    out_npy = str(tmp_path / "out.npy")
    numpy.save(in_npy, x)
    res = subprocess.run(
        [os.path.join(build, "znicz_infer"), pkg, in_npy, out_npy],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr

    y_cpp = numpy.load(out_npy)
    y_py = _python_forward(wf, x)
    assert y_cpp.shape == y_py.shape
    assert numpy.abs(y_cpp - y_py).max() < 1e-5
    # classifications agree exactly
    assert numpy.array_equal(y_cpp.argmax(1), y_py.argmax(1))


def test_cpp_ctypes_binding(tmp_path):
    build = _build_cpp()
    wf = _trained_mlp(tmp_path)
    pkg = str(tmp_path / "mnist.zip")
    export_package(wf, pkg)

    lib = ctypes.CDLL(os.path.join(build, "libznicz_infer.so"))
    lib.znicz_load.restype = ctypes.c_void_p
    lib.znicz_load.argtypes = [ctypes.c_char_p]
    lib.znicz_infer.restype = ctypes.c_int
    lib.znicz_infer.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_float), ctypes.c_int,
        ctypes.c_int, ctypes.POINTER(ctypes.c_float), ctypes.c_int]
    lib.znicz_last_error.restype = ctypes.c_char_p

    handle = lib.znicz_load(pkg.encode())
    assert handle, lib.znicz_last_error().decode()

    x = numpy.random.RandomState(2).uniform(
        -1, 1, (50, 784)).astype(numpy.float32)
    out = numpy.zeros((50, 10), dtype=numpy.float32)
    n = lib.znicz_infer(
        ctypes.c_void_p(handle),
        x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), 50, 784,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), out.size)
    assert n == 10, lib.znicz_last_error().decode()

    y_py = _python_forward(wf, x)
    assert numpy.abs(out - y_py).max() < 1e-5
    lib.znicz_free(ctypes.c_void_p(handle))


def test_cpp_unit_tests_pass():
    build = _build_cpp()
    res = subprocess.run([os.path.join(build, "test_units")],
                         capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr


def test_train_extract_serve_pipeline(tmp_path):
    """Full serving path: train -> extract_forward_workflow with an
    InteractiveLoader -> feed live samples -> predictions match the
    training workflow's forward output."""
    import numpy
    from znicz_tpu.core import prng
    from znicz_tpu.loader.interactive import InteractiveLoader
    import znicz_tpu.loader.loader_wine  # noqa: F401
    from znicz_tpu.standard_workflow import StandardWorkflow

    wf = StandardWorkflow(
        None,
        layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 12},
             "<-": {"learning_rate": 0.3}},
            {"type": "softmax", "->": {"output_sample_shape": 3},
             "<-": {"learning_rate": 0.3}},
        ],
        loader_name="wine_loader",
        loader_config={"minibatch_size": 10},
        decision_config={"max_epochs": 5, "fail_iterations": 20},
        snapshotter_config={"prefix": "serve", "interval": 100,
                            "time_interval": 1e9,
                            "directory": str(tmp_path)})
    wf.initialize()
    wf.run()

    served = []

    def loader_factory(fwd_wf, **kwargs):
        ldr = InteractiveLoader(fwd_wf, sample_shape=(13,),
                                minibatch_size=4)
        served.append(ldr)
        return ldr

    fwd_wf = wf.extract_forward_workflow(loader_factory=loader_factory)
    fwd_wf.initialize()
    ldr = served[0]
    r = numpy.random.RandomState(0)
    samples = r.uniform(-1, 1, (6, 13)).astype(numpy.float32)
    for s in samples:
        ldr.feed(s)
    ldr.finish()
    fwd_wf.run()

    # weights really were copied: match a direct numpy forward with the
    # TRAINER's weights
    w0 = numpy.array(wf.forwards[0].weights.mem)
    b0 = numpy.array(wf.forwards[0].bias.mem)
    w1 = numpy.array(wf.forwards[1].weights.mem)
    b1 = numpy.array(wf.forwards[1].bias.mem)
    h = 1.7159 * numpy.tanh(0.6666 * (samples @ w0.T + b0))
    logits = h @ w1.T + b1
    e = numpy.exp(logits - logits.max(axis=1, keepdims=True))
    want = e / e.sum(axis=1, keepdims=True)
    fwd_wf.forwards[-1].output.map_read()
    got = numpy.array(fwd_wf.forwards[-1].output.mem[:ldr.minibatch_size])
    # the serving loader batches by 4: the LAST minibatch holds samples
    # 4..5
    assert numpy.abs(got[:2] - want[4:6]).max() < 1e-5


def test_serving_workflow_is_reusable(tmp_path):
    """A second feed()+run() session serves NEW predictions (review
    regression: gates must re-arm, not latch)."""
    import numpy
    from znicz_tpu.loader.interactive import InteractiveLoader
    import znicz_tpu.loader.loader_wine  # noqa: F401
    from znicz_tpu.standard_workflow import StandardWorkflow

    wf = StandardWorkflow(
        None,
        layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
             "<-": {"learning_rate": 0.3}},
            {"type": "softmax", "->": {"output_sample_shape": 3},
             "<-": {"learning_rate": 0.3}},
        ],
        loader_name="wine_loader",
        loader_config={"minibatch_size": 10},
        decision_config={"max_epochs": 2, "fail_iterations": 20},
        snapshotter_config={"prefix": "reuse", "interval": 100,
                            "time_interval": 1e9,
                            "directory": str(tmp_path)})
    wf.initialize()
    wf.run()

    holder = []

    def loader_factory(fwd_wf, **kwargs):
        ldr = InteractiveLoader(fwd_wf, sample_shape=(13,),
                                minibatch_size=4)
        holder.append(ldr)
        return ldr

    fwd_wf = wf.extract_forward_workflow(loader_factory=loader_factory)
    fwd_wf.initialize()
    ldr = holder[0]
    r = numpy.random.RandomState(1)

    def serve(batch):
        for s in batch:
            ldr.feed(s)
        ldr.finish()
        fwd_wf.run()
        fwd_wf.forwards[-1].output.map_read()
        return numpy.array(
            fwd_wf.forwards[-1].output.mem[:int(ldr.minibatch_size)])

    a = serve(r.uniform(-1, 1, (2, 13)).astype(numpy.float32))
    b = serve(r.uniform(-1, 1, (2, 13)).astype(numpy.float32))
    assert a.shape == (2, 3) and b.shape == (2, 3)
    assert numpy.abs(a - b).max() > 1e-9  # fresh outputs, not stale
    assert len(ldr._queue) == 0


def _trained_conv(tmp_path):
    from znicz_tpu.core.config import root
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    wf = mnist.build(
        layers=root.mnistr_caffe.layers,
        loader_config={"synthetic_train": 60, "synthetic_valid": 30,
                       "minibatch_size": 30},
        decision_config={"max_epochs": 1, "fail_iterations": 5},
        snapshotter_config={"prefix": "pkgc", "interval": 100,
                            "time_interval": 1e9,
                            "directory": str(tmp_path)})
    wf.initialize(device=NumpyDevice())
    wf.run()
    return wf


def test_conv_package_numpy_runner(tmp_path):
    """The spatial tier (conv/pool) exports and replays through the
    numpy package runner, matching the live unit graph."""
    wf = _trained_conv(tmp_path)
    pkg = str(tmp_path / "conv.zip")
    export_package(wf, pkg)
    x = numpy.random.RandomState(0).uniform(
        -1, 1, (30, 28, 28, 1)).astype(numpy.float32)
    y_pkg = run_package_numpy(pkg, x)
    y_py = _python_forward(wf, x)
    assert y_pkg.shape == (30, 10)
    assert numpy.abs(y_pkg - y_py).max() < 1e-5


def test_cpp_conv_cli_matches_python(tmp_path):
    """The C++ runtime executes the CONV flagship package end to end:
    conv 20C5 -> MP2 -> conv 50C5 -> MP2 -> fc_relu -> softmax."""
    build = _build_cpp()
    wf = _trained_conv(tmp_path)
    pkg = str(tmp_path / "conv.zip")
    export_package(wf, pkg)

    x = numpy.random.RandomState(1).uniform(
        -1, 1, (10, 28, 28, 1)).astype(numpy.float32)
    in_npy = str(tmp_path / "in.npy")
    out_npy = str(tmp_path / "out.npy")
    numpy.save(in_npy, x)
    res = subprocess.run(
        [os.path.join(build, "znicz_infer"), pkg, in_npy, out_npy],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr

    y_cpp = numpy.load(out_npy)
    y_py = run_package_numpy(pkg, x)
    assert y_cpp.shape == (10, 10)
    assert numpy.abs(y_cpp - y_py).max() < 1e-4
    assert numpy.array_equal(y_cpp.argmax(1), y_py.argmax(1))


def test_cpp_cifar_topology(tmp_path):
    """C++ runs a CIFAR-caffe-style package: conv/pool/str/LRN stack
    with avg pooling and overhanging (ceil-mode) windows."""
    build = _build_cpp()
    import znicz_tpu.loader.loader_cifar  # noqa: F401
    from znicz_tpu.samples import cifar
    prng.get(1).seed(42)
    prng.get(2).seed(43)
    wf = cifar.build(
        loader_config={"synthetic_train": 60, "synthetic_valid": 30,
                       "minibatch_size": 30},
        decision_config={"max_epochs": 1, "fail_iterations": 5},
        snapshotter_config={"interval": 100, "time_interval": 1e9,
                            "directory": str(tmp_path)})
    wf.initialize(device=NumpyDevice())
    wf.run()
    pkg = str(tmp_path / "cifar.zip")
    export_package(wf, pkg)

    x = numpy.random.RandomState(2).uniform(
        -1, 1, (4, 32, 32, 3)).astype(numpy.float32)
    in_npy = str(tmp_path / "in.npy")
    out_npy = str(tmp_path / "out.npy")
    numpy.save(in_npy, x)
    res = subprocess.run(
        [os.path.join(build, "znicz_infer"), pkg, in_npy, out_npy],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    y_cpp = numpy.load(out_npy)
    y_py = run_package_numpy(pkg, x)
    assert numpy.abs(y_cpp - y_py).max() < 1e-4


def test_cpp_ctypes_nhwc_binding(tmp_path):
    """The spatial C ABI (znicz_infer_nhwc) serves a conv package from
    Python via ctypes (review regression: the rank-2 ABI cannot)."""
    build = _build_cpp()
    wf = _trained_conv(tmp_path)
    pkg = str(tmp_path / "conv.zip")
    export_package(wf, pkg)

    lib = ctypes.CDLL(os.path.join(build, "libznicz_infer.so"))
    lib.znicz_load.restype = ctypes.c_void_p
    lib.znicz_load.argtypes = [ctypes.c_char_p]
    lib.znicz_infer_nhwc.restype = ctypes.c_int
    lib.znicz_infer_nhwc.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_float), ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_float), ctypes.c_int]
    lib.znicz_last_error.restype = ctypes.c_char_p

    handle = lib.znicz_load(pkg.encode())
    assert handle, lib.znicz_last_error().decode()
    x = numpy.random.RandomState(3).uniform(
        -1, 1, (6, 28, 28, 1)).astype(numpy.float32)
    out = numpy.zeros((6, 10), dtype=numpy.float32)
    n = lib.znicz_infer_nhwc(
        ctypes.c_void_p(handle),
        x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), 6, 28, 28, 1,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), out.size)
    assert n == 10, lib.znicz_last_error().decode()
    y_py = run_package_numpy(pkg, x)
    assert numpy.abs(out - y_py).max() < 1e-4
    lib.znicz_free(ctypes.c_void_p(handle))


def test_mul_activation_exports_and_replays(tmp_path):
    """activation_mul's (auto-set) factor travels through the package:
    numpy runner and the C++ runtime both honor it."""
    import znicz_tpu.loader.loader_wine  # noqa: F401
    from znicz_tpu.standard_workflow import StandardWorkflow
    wf = StandardWorkflow(
        None,
        layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
             "<-": {"learning_rate": 0.3}},
            {"type": "activation_mul", "factor": 0.5},
            {"type": "softmax", "->": {"output_sample_shape": 3},
             "<-": {"learning_rate": 0.3}},
        ],
        loader_name="wine_loader",
        loader_config={"minibatch_size": 10},
        decision_config={"max_epochs": 1, "fail_iterations": 5},
        snapshotter_config={"prefix": "mul", "interval": 100,
                            "time_interval": 1e9,
                            "directory": str(tmp_path)})
    wf.initialize(device=NumpyDevice())
    wf.run()
    pkg = str(tmp_path / "mul.zip")
    export_package(wf, pkg)
    manifest, _ = load_package(pkg)
    entry = [l for l in manifest["layers"]
             if l["type"] == "activation_mul"][0]
    assert float(entry["factor"]) == 0.5

    x = numpy.random.RandomState(0).uniform(
        -1, 1, (10, 13)).astype(numpy.float32)
    y_pkg = run_package_numpy(pkg, x)
    y_py = _python_forward(wf, x)
    assert numpy.abs(y_pkg - y_py).max() < 1e-5

    build = _build_cpp()
    in_npy, out_npy = str(tmp_path / "i.npy"), str(tmp_path / "o.npy")
    numpy.save(in_npy, x)
    res = subprocess.run(
        [os.path.join(build, "znicz_infer"), pkg, in_npy, out_npy],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr
    assert numpy.abs(numpy.load(out_npy) - y_pkg).max() < 1e-5


def test_zero_filter_export_roundtrips_losslessly(tmp_path):
    """The grouping mask folds into the next layer's weights AND
    survives in the manifest (mask + grouping recoverable —
    import_package loses nothing), while manifest.txt stays clean for
    the C++ parser."""
    import zipfile
    import znicz_tpu.loader.loader_wine  # noqa: F401
    from znicz_tpu.export import import_package
    from znicz_tpu.standard_workflow import StandardWorkflow

    wf = StandardWorkflow(
        None,
        layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
             "<-": {"learning_rate": 0.3}},
            {"name": "zf", "type": "zero_filter", "grouping": 2},
            {"type": "softmax", "->": {"output_sample_shape": 4},
             "<-": {"learning_rate": 0.3}},
        ],
        loader_name="wine_loader",
        loader_config={"minibatch_size": 10},
        decision_config={"max_epochs": 1, "fail_iterations": 5},
        snapshotter_config={"prefix": "zf", "interval": 100,
                            "time_interval": 1e9,
                            "directory": str(tmp_path)})
    wf.initialize(device=NumpyDevice())
    wf.run()
    pkg = str(tmp_path / "zf.zip")
    export_package(wf, pkg)

    manifest, arrays = import_package(pkg)  # strict loader accepts it
    assert [e["type"] for e in manifest["layers"]] == \
        ["all2all_tanh", "softmax"]
    entry = manifest["layers"][1]
    assert entry["zero_filter_grouping"] == 2
    mask = arrays[entry["arrays"]["zero_filter_mask"]]
    w = arrays[entry["arrays"]["weights"]]
    assert mask.shape == w.shape
    # the exported weights ARE the masked weights — folding again is a
    # no-op (the lossless-fold invariant)
    assert numpy.array_equal(w, w * mask)
    assert (mask == 0).any() and (mask == 1).any()
    # the C++ flat manifest never sees the provenance attrs
    with zipfile.ZipFile(pkg) as zf:
        txt = zf.read("manifest.txt").decode()
    assert "zero_filter" not in txt
    # the numpy runner serves the masked stack
    x = numpy.random.RandomState(0).uniform(
        -1, 1, (10, 13)).astype(numpy.float32)
    y_pkg = run_package_numpy(pkg, x)
    y_py = _python_forward(wf, x)
    assert numpy.abs(y_pkg - y_py).max() < 1e-5


def test_mul_export_refuses_unset_factor(tmp_path):
    """Exporting an activation_mul whose factor was never set must fail
    loudly (review regression: runners would otherwise diverge)."""
    import pytest as _pytest
    from znicz_tpu.core.workflow import DummyWorkflow
    from znicz_tpu.units.activation import ForwardMul
    from znicz_tpu.units.all2all import All2AllTanh
    from znicz_tpu.core.memory import Array
    from znicz_tpu.core import prng as _prng

    wf = DummyWorkflow()
    fwd = All2AllTanh(wf, output_sample_shape=4, weights_stddev=0.05,
                      bias_stddev=0.05,
                      rand=_prng.RandomGenerator().seed(3))
    fwd.input = Array(numpy.zeros((2, 5), numpy.float32))
    fwd.initialize(NumpyDevice())
    mul = ForwardMul(wf)  # factor unset, never ran
    mul.input = fwd.output
    mul.initialize(NumpyDevice())
    wf.forwards = [fwd, mul]
    with _pytest.raises(ValueError, match="factor is unset"):
        export_package(wf, str(tmp_path / "bad.zip"))


def test_fused_train_export_cpp_serve(tmp_path):
    """The fused path closes the deployment loop: train on the compiled
    SPMD step, extract the forward workflow (params injected through the
    broadcast protocol), export the package, and serve it from the C++
    runtime with outputs matching the fused net's own predict."""
    from znicz_tpu.core.backends import JaxDevice
    from znicz_tpu.core.config import root

    build = _build_cpp()
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    wf = mnist.build(
        layers=root.mnistr_conv.layers,
        loader_config={"synthetic_train": 120, "synthetic_valid": 60,
                       "minibatch_size": 30},
        decision_config={"max_epochs": 1, "fail_iterations": 10},
        snapshotter_config={"prefix": "fpkg", "interval": 100,
                            "time_interval": 1e9,
                            "directory": str(tmp_path)},
        fused=True)
    wf.initialize(device=JaxDevice())
    wf.run()

    fwd_wf = wf.extract_forward_workflow()
    pkg = str(tmp_path / "fused_conv.zip")
    export_package(fwd_wf, pkg)

    x = numpy.random.RandomState(3).uniform(
        -1, 1, (10, 28, 28, 1)).astype(numpy.float32)
    y_fused = numpy.asarray(wf.fused_trainer.net.predict(x))

    in_npy = str(tmp_path / "fin.npy")
    out_npy = str(tmp_path / "fout.npy")
    numpy.save(in_npy, x)  # 4-D keeps the (h, w, c) spatial shape
    res = subprocess.run(
        [os.path.join(build, "znicz_infer"), pkg, in_npy, out_npy],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    out = numpy.load(out_npy)

    assert out.shape == (10, 10)
    assert numpy.abs(out - y_fused).max() < 1e-4
    assert numpy.argmax(out, 1).tolist() == \
        numpy.argmax(y_fused, 1).tolist()
