"""CI smoke: the performance-introspection layer end to end — a tiny
fused wine run with the profiler armed, asserting the acceptance
contract of ``core/profiler.py``:

* the **cost registry** is non-empty and the fused window executable
  carries XLA-measured FLOPs/bytes plus the analytic cross-check
  ratio,
* the **device-memory ledger** is balanced (live bytes == per-name
  attribution sum) with a high-water mark and alloc/free counts,
* ``GET /debug/profile?seconds=N`` on the status server returns a
  directory containing a loadable ``jax.profiler`` trace,
* the exported report renders through
  ``tools/profile_summary.py --roofline`` / ``--ledger``.

Run by ``tools/ci.sh`` (fast lane).  Exit code 0 = pass.
"""

import glob
import gzip
import json
import os
import sys
import tempfile
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from znicz_tpu.core.config import root  # noqa: E402
from znicz_tpu.core import profiler, prng, telemetry  # noqa: E402
from znicz_tpu.core.backends import JaxDevice  # noqa: E402
from znicz_tpu.core.status_server import StatusServer  # noqa: E402


def main():
    tmp = tempfile.mkdtemp(prefix="profiler_smoke_")
    root.common.dirs.snapshots = os.path.join(tmp, "snapshots")
    root.common.profiler.capture_dir = os.path.join(tmp, "profiles")
    telemetry.enable()
    telemetry.reset()
    profiler.reset()
    profiler.enable()

    import znicz_tpu.loader.loader_wine  # noqa: F401
    from znicz_tpu.standard_workflow import StandardWorkflow
    prng.get(1).seed(2048)
    prng.get(2).seed(2049)
    wf = StandardWorkflow(
        None,
        layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
             "<-": {"learning_rate": 0.1}},
            {"type": "softmax", "->": {"output_sample_shape": 3},
             "<-": {"learning_rate": 0.1}},
        ],
        loader_name="wine_loader",
        loader_config={"minibatch_size": 10},
        decision_config={"max_epochs": 3, "fail_iterations": 20},
        snapshotter_config={"prefix": "psmoke", "interval": 10 ** 9,
                            "time_interval": 1e9, "compression": ""},
        fused={"window": 4})
    wf.initialize(device=JaxDevice())
    wf.run()

    # -- pillar 1: the cost registry -------------------------------------
    registry = profiler.cost_registry()
    assert registry, "cost registry is empty"
    windows = [e for e in registry
               if e["name"].startswith("fused.window")]
    assert windows, "no fused window executable registered: %s" \
        % [e["name"] for e in registry]
    win = windows[0]
    assert win.get("flops", 0) > 0, win
    assert win.get("bytes_accessed", 0) > 0, win
    ratio = win.get("flops_ratio_measured_vs_analytic")
    assert ratio is not None and 0.3 < ratio < 2.0, win
    report = profiler.cost_report()
    assert report["compared"] >= 1

    # -- pillar 2: the device-memory ledger ------------------------------
    ledger = profiler.ledger_summary()
    assert ledger["allocs"] > 0, ledger
    assert ledger["balanced"], ledger
    assert ledger["high_water_bytes"] >= ledger["live_bytes"], ledger

    # -- /debug/profile returns a loadable trace -------------------------
    server = StatusServer(wf, port=0).start()
    try:
        url = ("http://127.0.0.1:%d/debug/profile?seconds=0.3"
               % server.port)
        with urllib.request.urlopen(url, timeout=60) as r:
            doc = json.loads(r.read())
        trace_dir = doc["trace_dir"]
        assert os.path.isdir(trace_dir), doc
        xplanes = glob.glob(os.path.join(trace_dir, "**",
                                         "*.xplane.pb"),
                            recursive=True)
        assert xplanes, "no xplane files under %s" % trace_dir
        gz = glob.glob(os.path.join(trace_dir, "**", "*.json.gz"),
                       recursive=True)
        if gz:  # the chrome-trace sidecar, when the backend writes one
            with gzip.open(gz[0]) as f:
                json.load(f)
        # the introspection report endpoint mirrors the snapshot
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/debug/profiler" % server.port,
                timeout=10) as r:
            snap = json.loads(r.read())
        assert snap["cost_registry"] and snap["ledger"]["balanced"]
    finally:
        server.stop()

    # -- the report renders through profile_summary ----------------------
    report_path = profiler.export_report(
        os.path.join(tmp, "profiler_report.json"))
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import profile_summary
    roof = profile_summary.summarize_roofline(report_path)
    assert "fused.window" in roof
    led = profile_summary.summarize_ledger(report_path)
    assert "balanced=True" in led

    print("profiler smoke OK: %d executables (window ratio %.3f), "
          "ledger live %d B / hwm %d B"
          % (len(registry), ratio, ledger["live_bytes"],
             ledger["high_water_bytes"]))


if __name__ == "__main__":
    main()
