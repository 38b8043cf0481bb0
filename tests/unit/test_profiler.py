"""Performance-introspection unit tests (core/profiler.py):

* the DISABLED path does no work at all — no state allocation, no jax
  calls, zero extra compiles, zero device syncs (the discipline
  health.py established, same pin style as test_health.py),
* cost registry: register / lookup / dedup, the analytic cross-check
  and agreement band, scan-body scaling, and the zero-extra-compiles
  property of registration,
* device-memory ledger: balance + per-name attribution + high-water
  mark, a snapshot/reload cycle, epoch-boundary leak detection.
"""

import numpy
import pytest

from znicz_tpu.core import profiler, telemetry
from znicz_tpu.core.memory import Array


@pytest.fixture(autouse=True)
def _fresh():
    profiler.reset()
    telemetry.reset()
    yield
    profiler.reset()
    telemetry.reset()


def _boom(*args, **kwargs):
    raise AssertionError("profiler state touched while disabled")


# -- the disabled fast path --------------------------------------------------

def test_disabled_path_does_no_work(monkeypatch):
    profiler.disable()
    telemetry.enable()
    telemetry.reset()
    # any attempt to build the profiler state would blow up
    monkeypatch.setattr(profiler, "_prof", _boom)
    assert profiler.register_jit_cost("x", None, ()) is None
    assert profiler.epoch_check(3) is None
    assert profiler.ledger_swap("a", 0, 128) is None
    # the memory.Array device lifecycle never reaches the ledger
    monkeypatch.setattr(profiler, "ledger_swap", _boom)
    a = Array(numpy.zeros(4, numpy.float32), name="a")
    a.dev
    a.set_dev(a.dev)
    a.reset()
    # no state was allocated, no compiles happened, no profiler series
    assert profiler._state is None
    snap = telemetry.snapshot()
    assert snap["counters"].get("jax.backend_compiles", 0) == 0
    assert not any(k.startswith("profiler.")
                   for k in list(snap["gauges"]) + list(snap["counters"]))
    assert profiler.cost_registry() == []


def test_disabled_summaries_are_safe():
    profiler.disable()
    led = profiler.ledger_summary()
    assert led["live_bytes"] == 0 and led["balanced"]
    snap = profiler.snapshot()
    assert snap["enabled"] is False and snap["cost_registry"] == []


# -- pillar 1: the executable cost registry ----------------------------------

def _matmul_jit():
    import jax
    m, n, k = 64, 128, 32
    f = jax.jit(lambda a, b: a @ b)
    a = numpy.zeros((m, n), numpy.float32)
    b = numpy.zeros((n, k), numpy.float32)
    return f, a, b, 2.0 * m * n * k


def test_cost_registry_register_lookup_crosscheck():
    profiler.enable()
    f, a, b, analytic = _matmul_jit()
    e = profiler.register_jit_cost("unit.matmul", f, (a, b),
                                   analytic_flops=analytic)
    # XLA counts a dense matmul at exactly 2*m*n*k flops
    assert e["flops"] == analytic
    assert e["bytes_accessed"] > 0
    assert e["operational_intensity"] == \
        e["flops"] / e["bytes_accessed"]
    assert e["flops_ratio_measured_vs_analytic"] == 1.0
    assert e["agreement"] is True
    # lookup + dedup: the same name returns the SAME entry without
    # re-lowering (fn is not even touched)
    assert profiler.cost_entry("unit.matmul") is e
    assert profiler.register_jit_cost("unit.matmul", None, ()) is e
    assert [x["name"] for x in profiler.cost_registry()] == \
        ["unit.matmul"]
    rep = profiler.cost_report()
    assert rep["compared"] == 1 and rep["agree"] is True


def test_cost_registration_adds_zero_backend_compiles():
    profiler.enable()
    telemetry.enable()
    telemetry.reset()
    f, a, b, analytic = _matmul_jit()
    profiler.register_jit_cost("unit.matmul2", f, (a, b),
                               analytic_flops=analytic)
    # lowering for cost analysis is NOT a backend compile...
    assert telemetry.counter("jax.backend_compiles").value == 0
    # ...and the dispatch that follows reuses the trace: one compile
    f(a, b)
    assert telemetry.counter("jax.backend_compiles").value == 1


def test_cost_scan_scaling():
    profiler.enable()
    f, a, b, analytic = _matmul_jit()
    e = profiler.register_jit_cost("unit.scan", f, (a, b),
                                   analytic_flops=4 * analytic,
                                   scan_steps=4)
    assert e["flops"] == 4 * analytic
    assert e["scan_scaled"] is True and e["scan_steps"] == 4
    assert e["agreement"] is True


def test_cost_disagreement_outside_band():
    profiler.enable()
    f, a, b, analytic = _matmul_jit()
    e = profiler.register_jit_cost("unit.off", f, (a, b),
                                   analytic_flops=analytic * 10)
    assert e["agreement"] is False
    assert profiler.cost_report()["agree"] is False


def test_fused_net_step_registers_cost_within_tolerance():
    profiler.enable()
    from znicz_tpu.parallel import fused
    net = fused.FusedNet(
        [{"type": "all2all_tanh", "->": {"output_sample_shape": 256}},
         {"type": "softmax", "->": {"output_sample_shape": 10}}], 784)
    x = numpy.zeros((32, 784), numpy.float32)
    labels = numpy.zeros((32,), numpy.int32)
    net.step(x, labels)
    e = profiler.cost_entry("fused.step")
    assert e is not None and e["flops"] > 0
    # measured vs the 3x-forward analytic estimate: the backward of
    # the FIRST layer needs no err_input, so measured sits below 1.0
    assert 0.4 < e["flops_ratio_measured_vs_analytic"] < 1.6
    assert e["meta"]["batch"] == 32


# -- pillar 2: the device-memory ledger --------------------------------------

def test_ledger_balance_attribution_high_water():
    profiler.enable()
    import jax.numpy as jnp
    a = Array(numpy.zeros((100,), numpy.float32), name="acts")
    w = Array(numpy.zeros((50,), numpy.float32), name="weights")
    a.unmap()
    w.unmap()
    led = profiler.ledger_summary()
    assert led["live_bytes"] == 600 == led["high_water_bytes"]
    assert led["by_name"] == {"acts": 400, "weights": 200}
    assert led["balanced"] and led["allocs"] == 2
    # a device 'write' REPLACES the buffer: swap, never double count
    a.set_dev(jnp.zeros((200,), jnp.float32))
    led = profiler.ledger_summary()
    assert led["by_name"]["acts"] == 800
    assert led["live_bytes"] == 1000 == led["high_water_bytes"]
    assert led["frees"] == 1
    a.reset()
    led = profiler.ledger_summary()
    assert led["live_bytes"] == 200
    assert led["high_water_bytes"] == 1000  # the mark survives frees
    w.reset()
    led = profiler.ledger_summary()
    assert led["live_bytes"] == 0 and led["balanced"]


def test_ledger_across_snapshot_reload_cycle():
    profiler.enable()
    arrays = {name: Array(numpy.full((64,), i, numpy.float32),
                          name=name)
              for i, name in enumerate(("w0", "w1"))}
    for arr in arrays.values():
        arr.unmap()
    led0 = profiler.ledger_summary()
    assert led0["live_bytes"] == 512 and led0["balanced"]
    # snapshot: the snapshotter collects host copies (.mem) — no
    # device change
    state = {n: numpy.array(arr.mem) for n, arr in arrays.items()}
    assert profiler.ledger_summary()["live_bytes"] == 512
    # teardown: device buffers dropped, every byte comes back
    for arr in arrays.values():
        arr.reset()
    assert profiler.ledger_summary()["live_bytes"] == 0
    # reload: restore the snapshot and re-upload
    restored = {n: Array(v, name=n) for n, v in state.items()}
    for arr in restored.values():
        arr.unmap()
    led1 = profiler.ledger_summary()
    assert led1["live_bytes"] == 512 and led1["balanced"]
    assert led1["by_name"] == led0["by_name"]
    # the high-water mark spans the whole cycle
    assert led1["high_water_bytes"] == 512
    assert (numpy.asarray(restored["w1"].mem) == 1.0).all()


def test_ledger_leak_detection():
    profiler.enable(leak_epochs=2, leak_min_bytes=1024)
    telemetry.enable()
    telemetry.reset()
    profiler.ledger_swap("grow0", 0, 2048)
    assert profiler.epoch_check(1) is None  # baseline sample
    profiler.ledger_swap("grow1", 0, 2048)
    assert profiler.epoch_check(2) is None  # first growth
    profiler.ledger_swap("grow2", 0, 2048)
    suspect = profiler.epoch_check(3)       # second consecutive growth
    assert suspect is not None
    assert suspect["grown_bytes"] == 4096 and suspect["epoch"] == 3
    assert telemetry.counter("profiler.leak_suspects").value == 1
    kinds = [ev["kind"] for ev in telemetry.journal_events()]
    assert "profiler.leak_suspect" in kinds
    # a flat epoch breaks the consecutive-growth streak
    assert profiler.epoch_check(4) is None


def test_ledger_unmatched_free_breaks_balance():
    profiler.enable()
    profiler.ledger_swap("seen", 0, 256)
    assert profiler.ledger_summary()["balanced"] is True
    # a free of bytes the ledger never saw allocated (profiler armed
    # mid-run / reset with live buffers): flagged untrustworthy
    # instead of silently reporting a clean balance
    profiler.ledger_swap("ghost", 4096, 0)
    led = profiler.ledger_summary()
    assert led["balanced"] is False and led["clamped_frees"] == 1
    assert led["live_bytes"] == 256  # lower bound, never negative


def test_ledger_no_leak_on_steady_state():
    profiler.enable(leak_epochs=2, leak_min_bytes=1)
    profiler.ledger_swap("buf", 0, 4096)
    for epoch in range(1, 6):  # stable footprint across epochs
        assert profiler.epoch_check(epoch) is None


# -- report plumbing ---------------------------------------------------------

def test_export_report_and_summary_modes(tmp_path):
    profiler.enable()
    f, a, b, analytic = _matmul_jit()
    profiler.register_jit_cost("unit.matmul", f, (a, b),
                               analytic_flops=analytic)
    profiler.ledger_swap("w", 0, 1024)
    path = profiler.export_report(str(tmp_path / "report.json"))
    import importlib
    import sys
    sys.path.insert(0, "tools")
    try:
        profile_summary = importlib.import_module("profile_summary")
    finally:
        sys.path.pop(0)
    roof = profile_summary.summarize_roofline(path)
    assert "unit.matmul" in roof and "1.000" in roof
    led = profile_summary.summarize_ledger(path)
    assert "balanced=True" in led and "`w`" in led
