"""Tail-latency engineering — the measurement half of the batch-1
fast path (ISSUE 12).

The serving tier's p99 was an *observed* number: per-bucket histograms
existed, loadgen reported approximate percentiles, and nothing stopped
a PR from regressing the tail on the paths real traffic hits — a cold
bucket's first request, a request that pays an evict→restore, a
breaker's half-open probe.  This module makes the tail an *engineered*
number, in three pieces:

* **Exact quantiles** (:func:`exact_percentile` /
  :func:`quantile_summary`): one deterministic formula over RETAINED
  samples — sorted order statistics with linear interpolation (the
  ``numpy.percentile`` "linear" definition, implemented once here so
  ``tools/loadgen.py`` and the unit tests can never drift apart).
  No bucketed approximation: p999 of 1000 retained samples is the
  interpolation of the two largest, not a histogram bucket edge.

* **Per-scenario series** (:func:`record_scenario`): every adversarial
  scenario's request latencies land in their own telemetry histogram
  ``serving.tail_seconds.scenario_<name>`` (plus a ``model_<name>``
  label for named engines) so /metrics and the flight recorder can
  tell a steady-state regression from a cold-path one.

* **Scenario runners** (:func:`run_steady`, :func:`run_cold_bucket`,
  :func:`run_evict_restore`, :func:`run_breaker_probe`): the
  adversarial mixes themselves, run by the functional tests (which
  pin that the scenarios produce CORRECT answers; their latencies on
  the chip: not measured, no serving cell yet, PERF.md section 7).

Latencies are measured around :meth:`InferenceEngine.predict` — the
dispatch path a request actually pays (pad, breaker admission, jitted
forward, slice) — not around the bare executable.
"""

import math
import time

import numpy

from znicz_tpu.core.config import root

#: the tail quantiles every report carries, in reporting order
QUANTILES = (50.0, 95.0, 99.0, 99.9)

#: the adversarial scenario vocabulary (the ``scenario_<name>`` label
#: set of the ``serving.tail_seconds`` series — bounded by design)
SCENARIOS = ("steady", "cold_bucket", "evict_restore", "breaker_probe")

#: the per-scenario histogram family
SERIES = "serving.tail_seconds"


# -- exact quantiles --------------------------------------------------------

def exact_percentile(samples, q):
    """Exact quantile of RETAINED samples: sort, then linearly
    interpolate between the two order statistics enclosing rank
    ``q/100 * (n-1)`` (the ``numpy.percentile`` "linear" method,
    restated here as the one formula the whole latency stack shares).

    Deterministic edge cases, pinned by unit test: an empty sequence
    returns None; ``n == 1`` returns that sample for every q; q <= 0 /
    q >= 100 return the min / max; ties interpolate to the tied value.
    """
    data = sorted(float(v) for v in samples)
    if not data:
        return None
    if q <= 0.0:
        return data[0]
    if q >= 100.0:
        return data[-1]
    rank = (q / 100.0) * (len(data) - 1)
    lo = int(math.floor(rank))
    hi = min(lo + 1, len(data) - 1)
    frac = rank - lo
    return data[lo] * (1.0 - frac) + data[hi] * frac


def quantile_summary(samples_s):
    """The standard tail block over latencies in SECONDS: count, mean
    and the :data:`QUANTILES` in milliseconds (p50/p95/p99/p999), plus
    min/max.  ``None``-valued quantile keys when there are no samples
    — a consumer must see the hole, not a zero."""
    # sort ONCE: exact_percentile re-sorts its input, but Timsort on
    # an already-sorted list is O(n), so pre-sorting here keeps the
    # 4-quantile block at one O(n log n) instead of four
    samples_s = sorted(float(s) for s in samples_s)
    out = {"count": len(samples_s)}
    for q in QUANTILES:
        key = "p%s_ms" % ("%g" % q).replace(".", "")
        v = exact_percentile(samples_s, q)
        out[key] = round(v * 1e3, 4) if v is not None else None
    if samples_s:
        out["mean_ms"] = round(1e3 * sum(samples_s) / len(samples_s), 4)
        out["min_ms"] = round(1e3 * samples_s[0], 4)
        out["max_ms"] = round(1e3 * samples_s[-1], 4)
    else:
        out["mean_ms"] = out["min_ms"] = out["max_ms"] = None
    return out


# -- per-scenario series ----------------------------------------------------

def record_scenario(scenario, seconds, model=None):
    """One scenario latency observation into the per-scenario
    histogram series (no-op while telemetry is disabled).  Unknown
    scenario names fail loudly — the label set is the bounded
    :data:`SCENARIOS` vocabulary, never free-form."""
    if scenario not in SCENARIOS:
        raise ValueError("unknown tail-latency scenario %r (known: %s)"
                         % (scenario, "/".join(SCENARIOS)))
    from znicz_tpu.core import telemetry
    if not telemetry.enabled():
        return
    labels = {"scenario": scenario}
    if model:
        labels["model"] = model
    # label set bounded by the SCENARIOS check above + model names
    telemetry.histogram(
        telemetry.labeled(  # graftlint: disable=telemetry-cardinality
            SERIES, **labels)).observe(float(seconds))


def timed_predict(engine, x, scenario):
    """One engine dispatch with its wall latency recorded into the
    scenario's series; returns ``(reply, seconds)``."""
    t0 = time.perf_counter()
    y = engine.predict(x)
    dt = time.perf_counter() - t0
    record_scenario(scenario, dt, model=engine.name)
    return y, dt


# -- scenario runners -------------------------------------------------------

def run_steady(engine, x, n=200):
    """Steady state: ``n`` warmed dispatches of ``x`` (batch-1 in the
    bench's use).  Returns ``(samples_s, elapsed_s)`` — the retained
    per-request latencies and the wall time of the whole loop (the
    honest req/s denominator)."""
    engine.predict(x)  # ensure the bucket is warm before timing
    samples = []
    t0 = time.perf_counter()
    for _ in range(int(n)):
        _, dt = timed_predict(engine, x, "steady")
        samples.append(dt)
    return samples, time.perf_counter() - t0


def run_cold_bucket(make_engine, sample_shape, dtype=numpy.float32,
                    trials=2):
    """Cold-bucket first hit ON THE REQUEST PATH: a fresh un-warmed
    engine per trial (``make_engine()`` must build with
    ``warmup=False``), then the FIRST request of every bucket pays its
    trace+compile (a persistent-cache load when ``core/compile_cache``
    is wired).  Returns the first-hit latencies across all buckets and
    trials — the worst a request can hit on a replica that skipped (or
    lost) its warmup."""
    samples = []
    for _ in range(int(trials)):
        engine = make_engine()
        for bucket in engine.buckets:
            x = numpy.zeros((int(bucket),) + tuple(sample_shape),
                            dtype=dtype)
            _, dt = timed_predict(engine, x, "cold_bucket")
            samples.append(dt)
    return samples


def run_evict_restore(engine, x, n=3):
    """Evict→restore on the request path: each trial evicts the
    model's device state (params + executables + warm set — what the
    registry's LRU budget does to a cold model) and times the next
    request, which pays the lazy restore: host→device re-upload,
    forward rebuild and the re-warm sweep, then its own dispatch.
    Returns ``(samples_s, replies)`` so callers can pin that the
    restored answers are CORRECT, not just timely."""
    samples, replies = [], []
    for _ in range(int(n)):
        engine.evict()
        y, dt = timed_predict(engine, x, "evict_restore")
        samples.append(dt)
        replies.append(y)
    return samples, replies


def run_breaker_probe(engine, x, trials=2, settle_s=5.0):
    """Breaker half-open probe latency: open the request bucket's
    circuit breaker with injected ``serving.forward`` faults (the
    deterministic ``core/faults`` registry — retries disabled for the
    duration so each injected failure counts immediately), wait out
    the cooldown, then time the half-open PROBE request — the first
    real traffic through a recovering bucket.  Returns ``(samples_s,
    replies)``; each probe's reply must be correct (the fault is
    cleared before the probe fires) and each probe closes the breaker
    again.

    Config touched (breaker threshold/cooldown are LIVE reads, PR 7)
    is restored on exit; the faults registry is reset.  Only the
    breaker's own open-rejection is retried during the wait — any
    other engine failure propagates with its real traceback."""
    from znicz_tpu.core import faults
    from znicz_tpu.serving.breaker import CircuitOpenError

    cfg = root.common.serving
    saved = {
        "faults_enabled": bool(root.common.faults.get("enabled",
                                                      False)),
        "retry_attempts": root.common.retry.get("attempts", 3),
        "threshold": cfg.get("breaker_threshold", 5),
        "cooldown_ms": cfg.get("breaker_cooldown_ms", 1000.0),
    }
    threshold, cooldown_ms = 2, 50.0
    samples, replies = [], []
    try:
        root.common.retry.attempts = 0
        cfg.breaker_threshold = threshold
        cfg.breaker_cooldown_ms = cooldown_ms
        engine.predict(x)  # warm + instantiate the bucket's breaker
        for _ in range(int(trials)):
            root.common.faults.enabled = True
            faults.install("serving.forward", kind="io", every=1,
                           times=threshold)
            for _ in range(threshold):
                try:
                    engine.predict(x)
                except OSError:
                    pass  # the injected fault, counted by the breaker
            faults.clear("serving.forward")
            root.common.faults.enabled = saved["faults_enabled"]
            # the bucket is open now; wait out the cooldown so the
            # next request is admitted as the half-open probe
            deadline = time.monotonic() + settle_s
            while time.monotonic() < deadline:
                time.sleep(cooldown_ms / 1e3)
                try:
                    y, dt = timed_predict(engine, x, "breaker_probe")
                except CircuitOpenError:
                    continue  # still cooling down — wait it out
                samples.append(dt)
                replies.append(y)
                break
            else:
                raise RuntimeError(
                    "breaker never admitted the half-open probe "
                    "within %.1fs" % settle_s)
    finally:
        faults.clear("serving.forward")
        faults.reset()
        root.common.faults.enabled = saved["faults_enabled"]
        root.common.retry.attempts = saved["retry_attempts"]
        cfg.breaker_threshold = saved["threshold"]
        cfg.breaker_cooldown_ms = saved["cooldown_ms"]
    return samples, replies
