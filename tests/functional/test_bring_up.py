"""The repairs that bring the train -> snapshot -> serve path up on the
chip (ISSUE 21), pinned on the CPU: the fused snapshot -> engine seam, no
fallback that hides the device, a compile cache placed from outside,
directories that follow the checkout, and a ``chip_smoke.py`` that cannot
pass without an accelerator."""

import os
import shutil
import subprocess
import sys

import numpy
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.backends import JaxDevice
from znicz_tpu.core.config import root

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _fused_cifar(tmp_path):
    from znicz_tpu.samples import cifar
    return cifar.build(
        loader_config={"minibatch_size": 20, "synthetic": True,
                       "synthetic_train": 40, "synthetic_valid": 20},
        decision_config={"max_epochs": 1},
        snapshotter_config={"directory": str(tmp_path)},
        fused={"window": 2}), (32, 32, 3)


def _fused_mnist_mlp(tmp_path):
    from znicz_tpu.samples import mnist
    return mnist.build(
        loader_config={"minibatch_size": 20, "synthetic_train": 40,
                       "synthetic_valid": 20},
        decision_config={"max_epochs": 1},
        snapshotter_config={"directory": str(tmp_path)},
        fused={"window": 2}), (28, 28)


@pytest.mark.parametrize("build", [_fused_cifar, _fused_mnist_mlp],
                         ids=["cifar_caffe", "mnist_mlp"])
def test_fused_snapshot_serves_like_fused_predict(tmp_path, build):
    """``--fused`` training -> snapshot -> ``serve --latest``: the
    snapshot carries the serving topology, and the engine built from it
    answers what ``FusedNet.predict`` answers."""
    from znicz_tpu.core.snapshotter import SnapshotterToFile
    from znicz_tpu.launcher import newest_snapshot
    from znicz_tpu.serving.engine import InferenceEngine
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    wf, sample_shape = build(tmp_path)
    wf.initialize(device=JaxDevice())
    wf.run()
    path = newest_snapshot(str(tmp_path), wf.snapshotter.prefix)
    topology = SnapshotterToFile.import_(path)["topology"]
    assert len(topology["layers"]) == len(wf.fused_trainer.net.specs)
    assert tuple(topology["input_sample_shape"]) == sample_shape

    engine = InferenceEngine(path, max_batch=8)
    x = numpy.random.RandomState(3).uniform(
        -1, 1, (5,) + sample_shape).astype(numpy.float32)
    served = numpy.asarray(engine.predict(x))
    fused = numpy.asarray(wf.fused_trainer.net.predict(x))
    assert served.shape == fused.shape == (5, 10)
    assert numpy.abs(served - fused).max() < 1e-5


def test_snapshot_of_undescribable_forward_stack_fails(tmp_path):
    """A workflow that HAS a forward stack must describe it: the
    snapshotter no longer writes a snapshot that ``serve`` cannot load
    and a warning nobody reads."""
    from znicz_tpu.core.snapshotter import SnapshotterToFile
    from znicz_tpu.core.units import Unit
    from znicz_tpu.core.workflow import Workflow
    wf = Workflow(None)
    wf.forwards = [Unit(wf, name="no_type_string")]
    snap = SnapshotterToFile(wf, directory=str(tmp_path), prefix="x")
    with pytest.raises(ValueError, match="MAPPING"):
        snap.export()
    wf.forwards = []
    assert "topology" not in SnapshotterToFile.import_(snap.export())


def test_get_device_raises_when_backend_cannot_start(monkeypatch):
    import jax
    from znicz_tpu.core import backends

    def no_backend(*args, **kwargs):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(backends, "_default_device", None)
    monkeypatch.setattr(jax, "devices", no_backend)
    for backend in ("auto", "jax"):
        with pytest.raises(RuntimeError, match="initialize backend"):
            backends.get_device(backend)
    with pytest.raises(RuntimeError, match="initialize backend"):
        backends.describe()


def test_compile_cache_follows_env_when_set(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR decides: it enables the cache, wins
    over config and ``enable(dir)``, and jax's own setting (which jax
    took from the variable at import) is never overridden."""
    import jax
    from znicz_tpu.core import compile_cache
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    monkeypatch.setattr(root.common.compile_cache, "dir",
                        str(tmp_path / "from_config"))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configured_dir() == placed
    assert not root.common.compile_cache.get("enabled", False)
    assert compile_cache.maybe_enable() == placed
    assert compile_cache.enable(str(tmp_path / "from_flag")) == placed
    assert compile_cache.active_dir() == placed
    assert os.path.isdir(placed)
    assert not (tmp_path / "from_flag").exists()
    assert jax.config.jax_compilation_cache_dir == before
    compile_cache.disable()
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_under_the_checkout(monkeypatch):
    from znicz_tpu.core import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.configured_dir() == os.path.join(
        REPO, ".cache", "xla_cache")
    assert compile_cache.maybe_enable() is None  # still off by default


def test_fleet_replicas_get_no_compile_cache_flag_under_env(monkeypatch):
    """``serve --fleet`` pins the shared directory into its replicas'
    argv — unless the variable already places it for every process."""
    from znicz_tpu.serving import router, server
    seen = {}

    class Started(Exception):
        pass

    class FakeRouter(object):
        def __init__(self, replica_argv, **kwargs):
            seen["argv"] = list(replica_argv)
            raise Started()

    monkeypatch.setattr(router, "FleetRouter", FakeRouter)
    for placed, expect_flag in ((None, True), ("/somewhere/cache", False)):
        if placed:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        with pytest.raises(Started):
            server.main(["model.zip", "--fleet", "1"])
        assert ("--compile-cache" in seen["argv"]) is expect_flag


def test_default_dirs_follow_the_checkout(tmp_path):
    """A copy of the tree keeps its data, snapshots and caches to itself
    (the chip machine runs a copy)."""
    assert root.common.dirs.datasets == os.path.join(REPO, ".data")
    assert root.common.dirs.cache == os.path.join(REPO, ".cache")
    os.makedirs(str(tmp_path / "znicz_tpu" / "core"))
    for rel in ("__init__.py", os.path.join("core", "__init__.py"),
                os.path.join("core", "config.py")):
        shutil.copy(os.path.join(REPO, "znicz_tpu", rel),
                    str(tmp_path / "znicz_tpu" / rel))
    out = subprocess.run(
        [sys.executable, "-c",
         "from znicz_tpu import root; d = root.common.dirs; "
         "print(d.datasets, d.snapshots, d.cache)"],
        cwd=str(tmp_path), env=dict(os.environ, PYTHONPATH=str(tmp_path)),
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [
        str(tmp_path / name) for name in (".data", ".snapshots", ".cache")]


def test_fused_flag_without_fused_trainer_exits_nonzero(tmp_path):
    """``--fused`` on a hand-wired workflow is an error, not a quiet
    unit-graph run."""
    from znicz_tpu.__main__ import main
    module = tmp_path / "hand_wired.py"
    module.write_text(
        "from znicz_tpu.core.workflow import Workflow\n"
        "def run(load, main):\n"
        "    load(lambda **kwargs: Workflow(None))\n"
        "    main()\n")
    with pytest.raises(SystemExit) as exc:
        main([str(module), "--fused"])
    assert exc.value.code not in (0, None)
    assert "does not build a fused trainer" in str(exc.value.code)
    assert main([str(module)]) == 0


def _run_chip_smoke(directory, pythonpath):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=str(directory), env=env,
        capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("with_repo", [True, False],
                         ids=["no_accelerator", "nothing_but_the_script"])
def test_chip_smoke_cannot_pass_off_the_chip(tmp_path, with_repo):
    """Without an accelerator — and alone in a directory — the smoke
    exits non-zero before doing work and prints no result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), str(tmp_path))
    done = _run_chip_smoke(tmp_path, REPO if with_repo else None)
    assert done.returncode != 0, done.stdout
    assert '"ok"' not in done.stdout
    expect = "needs a TPU" if with_repo else "No module named"
    assert expect in done.stdout, done.stdout + done.stderr
