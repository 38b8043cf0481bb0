"""Test harness config.

Tests run on the CPU host platform with 8 virtual devices so multi-chip
sharding paths compile and execute without TPU hardware (SURVEY.md §4.4 —
single-process multi-device simulation).

The platform is pinned with ``jax.config.update`` as well as by the
tier-1 command's ``JAX_PLATFORMS=cpu``, so a bare ``pytest`` on a machine
with an accelerator still runs here on the CPU; no backend has been
initialized yet when this file runs.  The chip is reached only through
``chip_smoke.py`` (and later the benchmark), never from the tests;
``tests/unit/test_tpu_compile.py`` compiles for a described v5e without
one.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
# float32 matmuls must match numpy to <1e-4 (reference test contract,
# tests/unit/test_all2all.py:95-152).  TPU-style bf16 passes are a bench-time
# choice, not a test-time one.
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: float64 dual-trajectory / mesh / multi-epoch tests — the "
        "full lane (tools/ci.sh full); the fast lane (tools/ci.sh) "
        "deselects them with -m 'not slow'")


@pytest.fixture(autouse=True)
def _snapshots_to_tmp(tmp_path, monkeypatch):
    """Keep generated snapshot pickles out of the repo tree."""
    from znicz_tpu.core.config import root
    monkeypatch.setattr(root.common.dirs, "snapshots", str(tmp_path))


@pytest.fixture(autouse=True)
def _engine_flags_isolated():
    """One test must not leak engine-mode flags into the rest of the
    suite: blocking-sync timing (``root.common.timings.sync_each_run``,
    formerly the mutable class global ``Unit.sync_timings``), the
    telemetry gate and the health-monitor gate/policy are snapshotted
    and restored around every test."""
    from znicz_tpu.core.config import root
    sync = root.common.timings.get("sync_each_run", False)
    tel = root.common.telemetry.get("enabled", False)
    hen = root.common.health.get("enabled", False)
    hpolicy = root.common.health.get("policy", "warn")
    hinterval = root.common.health.get("interval", 1)
    pen = root.common.profiler.get("enabled", False)
    fen = root.common.faults.get("enabled", False)
    cen = root.common.compile_cache.get("enabled", False)
    # the serving SLO plane's gates (ISSUE 14): the time-series
    # sampler, server-side SLO tracking and request-trace sampling
    tsen = root.common.telemetry.timeseries.get("enabled", False)
    slo_en = root.common.serving.get("slo_enabled", False)
    trace_n = root.common.serving.get("trace_sample_n", 0)
    # the durable blackbox (ISSUE 19): gate + dir/role knobs
    bben = root.common.telemetry.blackbox.get("enabled", False)
    bbdir = root.common.telemetry.blackbox.get("dir", None)
    bbrole = root.common.telemetry.blackbox.get("role", None)
    yield
    root.common.timings.sync_each_run = sync
    root.common.telemetry.enabled = tel
    root.common.health.enabled = hen
    root.common.health.policy = hpolicy
    root.common.health.interval = hinterval
    root.common.profiler.enabled = pen
    # fault-injection isolation: the gate, any armed rules (registry
    # AND config-declared) and the site counters all reset per test
    root.common.faults.enabled = fen
    from znicz_tpu.core.config import Config
    object.__setattr__(root.common.faults, "rules",
                       Config("root.common.faults.rules"))
    from znicz_tpu.core import faults
    faults.reset()
    # persistent-compile-cache isolation: a test that wired the cache
    # must not leave later tests' jit compiles writing to its tempdir
    root.common.compile_cache.enabled = cen
    from znicz_tpu.core import compile_cache
    if compile_cache.enabled():
        compile_cache.disable()
    root.common.telemetry.timeseries.enabled = tsen
    root.common.serving.slo_enabled = slo_en
    root.common.serving.trace_sample_n = trace_n
    # durable-blackbox isolation: close any armed writer and uninstall
    # the plane sinks, then restore the knobs (a test that armed the
    # blackbox must not leave later tests writing segments)
    root.common.telemetry.blackbox.enabled = bben
    root.common.telemetry.blackbox.dir = bbdir
    root.common.telemetry.blackbox.role = bbrole
    import sys
    blackbox = sys.modules.get("znicz_tpu.core.blackbox")
    if blackbox is not None and blackbox.armed():
        blackbox.reset()


#: test modules whose CONCURRENT serving traffic runs under the armed
#: lock-order sanitizer (ISSUE 13) — registry storms, continuous-
#: batcher floods, breaker half-open races.  The teardown asserts the
#: run recorded zero lock-order cycles and zero blocking-under-lock,
#: then restores the gate.
_LOCKSMITH_ARMED_MODULES = (
    "test_model_registry",
    "test_continuous_batcher",
    "test_serving_resilience",
)


@pytest.fixture(autouse=True)
def _lock_order_sanitizer(request):
    name = request.module.__name__.rsplit(".", 1)[-1]
    if name not in _LOCKSMITH_ARMED_MODULES:
        yield
        return
    from znicz_tpu.analysis import locksmith
    locksmith.reset()
    locksmith.arm()
    try:
        yield
    finally:
        locksmith.disarm()
    try:
        # raises LockOrderViolation (with both stacks per violation)
        # if the test's threads ever acquired locks in a cyclic order
        # or blocked while holding one
        locksmith.assert_clean()
    finally:
        locksmith.reset()

