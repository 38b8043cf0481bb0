"""BENCHMARK.json resolves to files by name; peaks are keyed by kind."""
import importlib
import json
import os
import re

import pytest

from benchmarks import families
from benchmarks import run as run_mod

ROOT = run_mod.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves_to_its_files(manifest):
    for cell in manifest["workloads"]:
        c, cfg, mix, limits, _ = run_mod.resolve(cell["name"])
        assert cfg["name"] == cell["config"]
        assert cfg["layers"] and cfg["source"] and "assumed" in cfg
        assert "reduced" in cfg
        for key in ("minibatch", "n_train", "n_valid", "window",
                    "warm_epochs", "check_windows", "tiny"):
            assert key in mix
        assert mix["n_train"] % mix["minibatch"] == 0
        for key in families.load(cfg).GRADED:
            assert limits[key] > 0
        families.reference(cfg)


def test_every_metric_has_a_reader(manifest):
    for m in manifest["per_layer"]:
        mod = importlib.import_module("benchmarks.layer_metrics." + m["name"])
        assert callable(mod.read)
        assert m["moves"] in {e["name"] for e in manifest["end_to_end"]}


def test_names_and_shape_of_the_manifest(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[section]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    assert any(e["name"] == "setup_s" for e in manifest["end_to_end"])
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    for e in manifest["end_to_end"]:
        assert 0 < e["bound"] <= 0.1


def test_reduced_names_no_width(manifest):
    for c in manifest["configs"]:
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank"))
            assert "hidden" not in key and "n_kernels" not in key


def test_unknown_device_kind_is_an_error(monkeypatch):
    class Dev(object):
        platform = "tpu"
        device_kind = "TPU v99 imaginary"

    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(SystemExit) as err:
        run_mod.device_check({"chips": 1})
    assert "peaks.json" in str(err.value)


def test_no_accelerator_is_an_error(monkeypatch):
    class Dev(object):
        platform = "cpu"
        device_kind = "cpu"

    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(SystemExit):
        run_mod.device_check({"chips": 1})


def test_unknown_workload_is_an_error():
    with pytest.raises(SystemExit):
        run_mod.resolve("no.such_cell")
