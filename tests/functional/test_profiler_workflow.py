"""Functional performance-introspection tests through REAL training
loops (ISSUE 4 acceptance): a fused run populates both pillars
(cost registry with the analytic cross-check, balanced device-memory
ledger), ``GET /debug/profile``
returns a directory containing a loadable trace, and a run with the
profiler disabled never touches profiler state (zero extra compiles,
zero device syncs — the hook sites are guard-only).  Micro-behavior is
covered by ``tests/unit/test_profiler.py``; the CI smoke
(``tools/profiler_smoke.py``) exercises the unit-graph wine path.
"""

import glob
import gzip
import json
import os
import urllib.error
import urllib.request

import pytest

from znicz_tpu.core.config import root
from znicz_tpu.core import profiler, prng, telemetry
from znicz_tpu.core.backends import JaxDevice
from znicz_tpu.core.status_server import StatusServer


@pytest.fixture(autouse=True)
def _fresh(tmp_path):
    profiler.reset()
    telemetry.reset()
    yield
    profiler.reset()
    telemetry.reset()
    root.common.profiler.capture_dir = None


def _mlp(tmp_path, max_epochs=2, fused=True):
    from znicz_tpu.samples import mnist
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    wf = mnist.build(
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 16}},
                {"type": "softmax", "->": {"output_sample_shape": 10}}],
        loader_config={"synthetic_train": 60, "synthetic_valid": 30,
                       "minibatch_size": 30},
        decision_config={"max_epochs": max_epochs,
                         "fail_iterations": 50},
        snapshotter_config={"prefix": "prof", "interval": 10 ** 9,
                            "time_interval": 1e9, "compression": "",
                            "directory": str(tmp_path)},
        fused=fused)
    wf.initialize(device=JaxDevice())
    return wf


def test_fused_run_populates_both_pillars(tmp_path):
    telemetry.enable()
    telemetry.reset()
    profiler.enable()
    wf = _mlp(tmp_path)
    wf.run()
    # pillar 1: the window executable registered with measured FLOPs
    # and the analytic cross-check
    registry = profiler.cost_registry()
    names = [e["name"] for e in registry]
    windows = [e for e in registry
               if e["name"].startswith("fused.window")]
    assert windows, names
    win = windows[0]
    assert win["flops"] > 0 and win["bytes_accessed"] > 0
    ratio = win["flops_ratio_measured_vs_analytic"]
    assert ratio is not None and 0.3 < ratio < 2.5, win
    # the VALID segment runs the compiled inference forward
    assert any(n.startswith("fused.predict") for n in names), names
    # pillar 2: every accounted device byte is attributed and balanced
    led = profiler.ledger_summary()
    assert led["allocs"] > 0 and led["balanced"], led
    assert led["high_water_bytes"] >= led["live_bytes"]
    # exported through the telemetry registry (/metrics machinery)
    snap = telemetry.snapshot()
    assert snap["gauges"].get("profiler.executables", 0) >= 1
    assert snap["gauges"].get("profiler.ledger_high_water_bytes", 0) > 0


def test_debug_profile_returns_loadable_trace(tmp_path):
    # on-demand capture is the opt-in: works with the profiler flag OFF
    profiler.disable()
    root.common.profiler.capture_dir = str(tmp_path / "profiles")
    server = StatusServer(None, port=0).start()
    try:
        url = ("http://127.0.0.1:%d/debug/profile?seconds=0.2"
               % server.port)
        with urllib.request.urlopen(url, timeout=60) as r:
            assert r.status == 200
            doc = json.loads(r.read())
        trace_dir = doc["trace_dir"]
        assert os.path.isdir(trace_dir)
        assert doc["files"]
        # the capture contains a loadable device trace: the xplane
        # protos plus the chrome-trace sidecar (valid gzipped JSON)
        xplanes = glob.glob(os.path.join(trace_dir, "**",
                                         "*.xplane.pb"),
                            recursive=True)
        assert xplanes and os.path.getsize(xplanes[0]) > 0
        sidecars = glob.glob(os.path.join(trace_dir, "**",
                                          "*.json.gz"), recursive=True)
        for sidecar in sidecars:
            with gzip.open(sidecar) as f:
                json.load(f)
        # a concurrent capture is refused, not queued
        assert profiler._capture_lock.acquire(blocking=False)
        try:
            with pytest.raises(urllib.error.HTTPError) as excinfo:
                urllib.request.urlopen(url, timeout=30)
            assert excinfo.value.code == 409
        finally:
            profiler._capture_lock.release()
        # malformed seconds answers 400, not a stack trace
        with pytest.raises(urllib.error.HTTPError) as excinfo:
            urllib.request.urlopen(
                "http://127.0.0.1:%d/debug/profile?seconds=x"
                % server.port, timeout=10)
        assert excinfo.value.code == 400
    finally:
        server.stop()


def test_disabled_profiler_run_touches_nothing(tmp_path, monkeypatch):
    """The workflow-level disabled pin: a full fused training run with
    the profiler off never builds profiler state — the hook sites
    (loader, trainer window, memory.Array, GD units, workflow) are
    guard-only, so the disabled path adds zero compiles and zero
    device syncs by construction."""
    profiler.disable()

    def boom(*args, **kwargs):
        raise AssertionError("profiler state touched while disabled")

    monkeypatch.setattr(profiler, "_prof", boom)
    wf = _mlp(tmp_path)
    wf.run()
    assert profiler._state is None
