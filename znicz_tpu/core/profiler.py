"""Performance introspection — XLA cost accounting and the
device-memory ledger.

PR 1 made the training stack observable (telemetry), PR 3 made it
watched (health).  This module makes it *explainable*: it answers two
questions every perf PR needs answered before it starts —

* **What did XLA actually compile?**  An **executable cost registry**:
  every jitted entry point (the fused train step and scan windows, the
  GD-unit update kernels, the serving forward buckets) registers its
  lowered ``cost_analysis()`` FLOPs and bytes-accessed via
  :func:`register_jit_cost`.  That gives *measured* MFU and the
  roofline operational intensity (FLOPs / byte — Williams et al.,
  "Roofline: An Insightful Visual Performance Model") per executable,
  cross-checked against the analytic ``flops_per_image`` estimate
  (the PaLM-style MFU accounting).  Registration
  lowers the ALREADY-TRACED function before its first dispatch, so it
  adds zero backend compiles (the dispatch reuses the trace cache).
* **Where did the memory go?**  A **device-memory ledger**:
  ``core/memory.py:Array`` device buffers are byte-accounted on every
  upload / ``set_dev`` / ``reset`` with per-Array-name attribution, a
  high-water-mark gauge, optional ``device.memory_stats()`` sampling
  (TPU; returns None on backends without it), and an epoch-boundary
  leak check that flags ``leak_epochs`` consecutive epochs of ledger
  growth.  The ledger counts *logical* per-Array references — two
  Arrays adopting views of one buffer both account it — which is the
  right invariant for leak detection (a reference that never goes away
  is the leak, aliased or not).

Where a step's time goes is the spans' to say (``core/telemetry.py``:
``telemetry.self_times``), without a device sync.  On-demand
``jax.profiler`` capture: ``GET /debug/profile?seconds=N`` on the
status and serving servers (:func:`capture_trace`) and a ``python -m
znicz_tpu profile`` CLI (:func:`cli_main`).

Disabled-by-default discipline (the contract ``health.py``
established, pinned by ``tests/unit/test_profiler.py``): every hook
site guards with ``if profiler.enabled():`` and every public hook
re-guards internally — with the flag off there are ZERO extra
compiles, ZERO device syncs, zero allocation; no profiler state is
even created.  Everything is exported through the existing machinery:
``profiler.*`` counters/gauges/histograms in the telemetry registry
(``/metrics``), ``profiler.*`` flight-recorder journal events, and the
``--roofline`` / ``--ledger`` modes of ``tools/profile_summary.py``.
"""

import collections
import glob
import json
import logging
import os
import time

from znicz_tpu.core.config import root
from znicz_tpu.core import telemetry
from znicz_tpu.analysis import locksmith

logger = logging.getLogger("profiler")

_cfg = root.common.profiler

def enabled():
    """The one gate every hook site tests.  Reads the live config so
    flipping ``root.common.profiler.enabled`` mid-run takes effect on
    the next step."""
    return bool(_cfg.get("enabled", False))


def enable(**overrides):
    """Arm the profiler (optionally overriding config knobs)."""
    for k, v in overrides.items():
        setattr(root.common.profiler, k, v)
    root.common.profiler.enabled = True
    return True


def disable():
    root.common.profiler.enabled = False
    return False


# ---------------------------------------------------------------------------
# Process-global state (created on first ENABLED use only — the
# disabled path must not allocate)
# ---------------------------------------------------------------------------

class DeviceLedger(object):
    """Byte-accounting of live device buffers, attributed by Array
    name.  ``swap(name, old, new)`` is the one mutation: it frees
    ``old`` bytes and allocates ``new`` (either may be 0), matching the
    replace-don't-mutate lifecycle of ``memory.Array._dev``."""

    def __init__(self):
        self.by_name = collections.defaultdict(int)
        self.live_bytes = 0
        self.high_water_bytes = 0
        self.allocs = 0
        self.frees = 0
        #: frees of bytes the ledger never saw allocated (clamped to
        #: keep counts non-negative) — any such event means the window
        #: of observation missed allocations (profiler armed mid-run,
        #: or reset() while buffers were live) and the live totals are
        #: LOWER BOUNDS, not exact
        self.clamped_frees = 0
        self._lock = locksmith.lock("profiler.ledger")

    def swap(self, name, old_nbytes, new_nbytes):
        name = name or "<unnamed>"
        with self._lock:
            if old_nbytes:
                self.frees += 1
                # clamp: arming the profiler mid-run may free buffers
                # it never saw allocated (best-effort accounting)
                drop = min(int(old_nbytes), self.by_name[name])
                if drop < int(old_nbytes):
                    self.clamped_frees += 1
                self.by_name[name] -= drop
                self.live_bytes -= drop
            if new_nbytes:
                self.allocs += 1
                self.by_name[name] += int(new_nbytes)
                self.live_bytes += int(new_nbytes)
                if self.live_bytes > self.high_water_bytes:
                    self.high_water_bytes = self.live_bytes

    def summary(self, top=16):
        with self._lock:
            names = {k: v for k, v in self.by_name.items() if v}
            live, hwm = self.live_bytes, self.high_water_bytes
            allocs, frees = self.allocs, self.frees
            clamped = self.clamped_frees
        ranked = sorted(names.items(), key=lambda kv: -kv[1])
        return {
            "live_bytes": live,
            "high_water_bytes": hwm,
            "allocs": allocs,
            "frees": frees,
            # the trust invariant: every observed free was matched by
            # an observed allocation.  False means the ledger missed
            # part of the buffer lifecycle (armed mid-run / reset with
            # live buffers) and the totals are lower bounds.
            "balanced": clamped == 0,
            "clamped_frees": clamped,
            "by_name": dict(ranked[:top]),
            "tracked_names": len(names),
        }


class _ProfilerState(object):
    """Everything the armed profiler accumulates."""

    def __init__(self):
        self.cost = {}                    # name -> cost-registry entry
        self.ledger = DeviceLedger()
        #: (epoch, ledger live bytes) at each epoch boundary
        self.epoch_bytes = []
        self.leak_suspects = 0
        self.lock = locksmith.lock("profiler.state")


_state = None
_state_lock = locksmith.lock("profiler.module")


def _prof():
    """The process-global profiler state (created on first use)."""
    global _state
    if _state is None:
        with _state_lock:
            if _state is None:
                _state = _ProfilerState()
    return _state


def reset():
    """Fresh profiler state (tests)."""
    global _state
    with _state_lock:
        _state = None


# ---------------------------------------------------------------------------
# Pillar 1: the executable cost registry
# ---------------------------------------------------------------------------

def _cost_dict(lowered):
    """Normalize ``Lowered.cost_analysis()`` output across jax
    versions (dict, or a per-device list of dicts)."""
    ca = lowered.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca or {}


def register_jit_cost(name, fn, args, kwargs=None, analytic_flops=None,
                      scan_steps=1, **meta):
    """Register one jitted entry point's lowered cost analysis.

    Call BEFORE the first dispatch with the exact dispatch arguments:
    ``fn.lower(*args)`` traces abstractly (shapes only — donated or
    huge buffers are fine) and the dispatch that follows reuses the
    trace cache, so registration costs one extra trace and ZERO extra
    backend compiles.  Duplicate names return the existing entry
    without re-lowering, so per-dispatch call sites stay cheap.

    ``analytic_flops`` is the closed-form estimate to cross-check
    against (e.g. ``3 * flops_per_image * batch * steps`` for a train
    window); the entry records the measured/analytic ratio and whether
    it falls inside the ``cost_rtol`` agreement band.  Extra ``meta``
    kwargs (steps, batch, ...) ride on the entry for report math.

    ``scan_steps``: HLO cost analysis counts a ``lax.scan``/while-loop
    BODY once (the trip count is not static at the HLO level), so for
    an executable whose hot path is a K-step scan the caller passes
    ``scan_steps=K`` and the measured numbers are scaled by it (the
    entry is flagged ``scan_scaled``).
    """
    if not enabled():
        return None
    p = _prof()
    with p.lock:
        entry = p.cost.get(name)
    if entry is not None:
        return entry
    entry = {"name": name, "flops": None, "bytes_accessed": None,
             "operational_intensity": None}
    scan_steps = max(int(scan_steps), 1)
    try:
        lowered = fn.lower(*args, **(kwargs or {}))
        ca = _cost_dict(lowered)
        flops = float(ca.get("flops", 0.0) or 0.0) * scan_steps
        nbytes = float(ca.get("bytes accessed", 0.0) or 0.0) * scan_steps
        entry["flops"] = flops
        entry["bytes_accessed"] = nbytes
        if nbytes:
            entry["operational_intensity"] = flops / nbytes
        if "transcendentals" in ca:
            entry["transcendentals"] = \
                float(ca["transcendentals"]) * scan_steps
        if scan_steps > 1:
            entry["scan_scaled"] = True
            entry["scan_steps"] = scan_steps
    except Exception as e:  # noqa: BLE001 - introspection must not kill a run
        entry["error"] = repr(e)
        logger.warning("cost_analysis failed for %s: %r", name, e)
    if analytic_flops:
        entry["analytic_flops"] = float(analytic_flops)
        if entry["flops"]:
            ratio = entry["flops"] / float(analytic_flops)
            rtol = float(_cfg.get("cost_rtol", 0.5))
            entry["flops_ratio_measured_vs_analytic"] = ratio
            entry["agreement"] = bool(1.0 - rtol <= ratio <= 1.0 + rtol)
    if meta:
        entry["meta"] = meta
    with p.lock:
        # first registration wins (a racing duplicate lowered the same
        # program; keep one entry so dedup stays O(1) per dispatch)
        entry = p.cost.setdefault(name, entry)
        count = len(p.cost)
    telemetry.gauge("profiler.executables").set(count)
    telemetry.record_event(
        "profiler.cost_registered", name=name, flops=entry.get("flops"),
        bytes_accessed=entry.get("bytes_accessed"),
        analytic_flops=entry.get("analytic_flops"))
    return entry


def cost_entry(name):
    """The registered entry for ``name`` (None when absent/disabled)."""
    if _state is None:
        return None
    with _state.lock:
        return _state.cost.get(name)


def cost_registry():
    """All registered entries, registration order (empty when the
    profiler never armed)."""
    if _state is None:
        return []
    with _state.lock:
        return list(_state.cost.values())


def cost_entries_by_meta(**match):
    """Registered entries whose ``meta`` carries every given
    key=value — e.g. ``cost_entries_by_meta(dtype="int8")`` selects
    the int8 serving-forward executables."""
    return [e for e in cost_registry()
            if all((e.get("meta") or {}).get(k) == v
                   for k, v in match.items())]


def cost_report():
    """The cross-check view: every entry that carries an analytic
    estimate plus an overall ``agree`` verdict (True only when every
    comparable entry sits inside the ``cost_rtol`` band)."""
    entries = cost_registry()
    compared = [e for e in entries if e.get("analytic_flops")
                and e.get("flops")]
    return {
        "executables": entries,
        "compared": len(compared),
        "agree": all(e.get("agreement", False) for e in compared)
        if compared else None,
        "cost_rtol": float(_cfg.get("cost_rtol", 0.5)),
    }


# ---------------------------------------------------------------------------
# Pillar 2: the device-memory ledger
# ---------------------------------------------------------------------------

def ledger_swap(name, old_nbytes, new_nbytes):
    """``memory.Array`` hook: the Array named ``name`` replaced a
    device buffer of ``old_nbytes`` with one of ``new_nbytes`` (either
    0).  Call sites guard with :func:`enabled`; this re-guards so a
    stray call is still free."""
    if not enabled():
        return None
    p = _prof()
    p.ledger.swap(name, old_nbytes, new_nbytes)
    telemetry.gauge("profiler.ledger_bytes").set(p.ledger.live_bytes)
    telemetry.gauge("profiler.ledger_high_water_bytes").set(
        p.ledger.high_water_bytes)
    return True


def ledger_summary(top=16):
    """Ledger totals + per-name attribution (zeros when never armed)."""
    if _state is None:
        return DeviceLedger().summary(top)
    return _state.ledger.summary(top)


def epoch_check(epoch):
    """Epoch-boundary leak check (called by ``Loader.run`` when an
    epoch wraps): record the ledger's live bytes and flag a leak
    suspect after ``leak_epochs`` CONSECUTIVE epochs of growth
    totalling more than ``leak_min_bytes``.  Returns the suspect dict
    when one fired, else None."""
    if not enabled():
        return None
    p = _prof()
    with p.lock:
        p.epoch_bytes.append((int(epoch), p.ledger.live_bytes))
        window = int(_cfg.get("leak_epochs", 3))
        tail = p.epoch_bytes[-(window + 1):]
        if len(tail) < window + 1:
            return None
        deltas = [b - a for (_, a), (_, b) in zip(tail, tail[1:])]
        growth = tail[-1][1] - tail[0][1]
        if not (all(d > 0 for d in deltas)
                and growth >= int(_cfg.get("leak_min_bytes", 1 << 20))):
            return None
        p.leak_suspects += 1
    suspect = {"epoch": int(epoch), "grown_bytes": int(growth),
               "epochs": window, "live_bytes": tail[-1][1]}
    telemetry.counter("profiler.leak_suspects").inc()
    telemetry.instant("profiler.leak_suspect", **suspect)
    telemetry.record_event("profiler.leak_suspect", **suspect)
    logger.warning("device-memory leak suspect: ledger grew %d bytes "
                   "over %d consecutive epochs (live %d)",
                   growth, window, tail[-1][1])
    return suspect


def sample_device_memory():
    """``device.memory_stats()`` where the backend provides it (TPU:
    bytes_in_use / peak_bytes_in_use; CPU returns None).  Gauges
    ``profiler.device<N>_bytes_in_use`` per device and returns the
    per-device dict — None entries mean the backend has no counter."""
    try:
        import jax
        devices = jax.local_devices()
    except Exception:  # pragma: no cover - jax is a baked-in dep
        return None
    out = {}
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # backend without the API
            stats = None
        out[str(d.id)] = stats
        if stats and "bytes_in_use" in stats:
            telemetry.gauge(telemetry.labeled(
                "profiler.device_bytes_in_use", device=d.id)).set(
                int(stats["bytes_in_use"]))
    return out


# ---------------------------------------------------------------------------
# On-demand jax.profiler capture (/debug/profile + the CLI)
# ---------------------------------------------------------------------------

_capture_lock = locksmith.lock("profiler.capture")
_heartbeat = None


def capture_trace(seconds=3.0, directory=None):
    """Capture a ``jax.profiler`` trace for ``seconds`` and return
    ``{"trace_dir", "seconds", "files"}``.  On-demand — works whether
    or not the profiler flag is armed (the request itself is the
    opt-in).  One capture at a time; a concurrent request raises
    ``RuntimeError`` (the HTTP endpoint maps it to 409).  A tiny
    jitted heartbeat is executed inside the window so the trace always
    contains at least one device event."""
    global _heartbeat
    seconds = max(0.05, min(
        float(seconds), float(_cfg.get("capture_seconds_cap", 60.0))))
    base = (directory or _cfg.get("capture_dir", None)
            or os.path.join(root.common.dirs.cache, "profiles"))
    stamp = time.strftime("%Y%m%d_%H%M%S")
    path = os.path.join(base, "capture_%s_pid%d" % (stamp, os.getpid()))
    n = 0
    while os.path.exists(path):
        n += 1
        path = os.path.join(base, "capture_%s_pid%d_%d"
                            % (stamp, os.getpid(), n))
    if not _capture_lock.acquire(blocking=False):
        raise RuntimeError("a profiler capture is already running")
    try:
        import jax
        import jax.numpy as jnp
        os.makedirs(path, exist_ok=True)
        if _heartbeat is None:
            _heartbeat = jax.jit(lambda a: a + 1.0)
        jax.profiler.start_trace(path)
        try:
            deadline = time.perf_counter() + seconds
            while True:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                time.sleep(min(0.05, remaining))
            jax.block_until_ready(_heartbeat(jnp.zeros(())))
        finally:
            jax.profiler.stop_trace()
    finally:
        _capture_lock.release()
    files = sorted(
        os.path.relpath(f, path)
        for f in glob.glob(os.path.join(path, "**", "*"), recursive=True)
        if os.path.isfile(f))
    telemetry.record_event("profiler.capture", trace_dir=path,
                           seconds=seconds, files=len(files))
    logger.info("profiler capture (%.2fs) -> %s (%d files)",
                seconds, path, len(files))
    return {"trace_dir": path, "seconds": seconds, "files": files}


# ---------------------------------------------------------------------------
# Reports
# ---------------------------------------------------------------------------

def snapshot():
    """JSON-able view of both pillars (what ``export_report``
    writes and ``GET /debug/profiler`` serves)."""
    return {
        "enabled": enabled(),
        "cost_registry": cost_registry(),
        "ledger": ledger_summary(),
        "device_memory": sample_device_memory(),
        "leak_suspects": (_state.leak_suspects
                          if _state is not None else 0),
    }


def export_report(path):
    """Write :func:`snapshot` as JSON (the file
    ``tools/profile_summary.py --roofline / --ledger`` renders)."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(snapshot(), f, indent=2, default=str)
    return path


# ---------------------------------------------------------------------------
# CLI: python -m znicz_tpu profile
# ---------------------------------------------------------------------------

def cli_main(argv=None):
    """``python -m znicz_tpu profile TARGET``.

    * TARGET is a URL (``http://host:port``) — hit the running
      server's ``GET /debug/profile?seconds=N`` and print the reply.
    * TARGET is a workflow spec (sample name / module / .py file) —
      run it with the profiler and telemetry armed under
      ``jax.profiler.trace``, then write ``profiler_report.json`` next
      to the device trace and print the two pillars' summary.
    """
    import argparse
    parser = argparse.ArgumentParser(
        prog="python -m znicz_tpu profile",
        description="Capture a device trace from a running server "
                    "(URL target) or run a workflow under the full "
                    "introspection stack (workflow target).")
    parser.add_argument("target",
                        help="http://host:port of a running status/"
                             "serving server, OR a workflow spec "
                             "(sample name, dotted module, .py file)")
    parser.add_argument("--seconds", type=float, default=3.0,
                        help="capture window for the URL mode "
                             "(default 3)")
    parser.add_argument("--out", default=None,
                        help="output directory for the workflow mode "
                             "(default <cache>/profiles/cli_<stamp>)")
    args = parser.parse_args(argv)

    if args.target.startswith(("http://", "https://")):
        import urllib.request
        url = (args.target.rstrip("/")
               + "/debug/profile?seconds=%g" % args.seconds)
        with urllib.request.urlopen(url,
                                    timeout=args.seconds + 60) as r:
            doc = json.loads(r.read())
        print(json.dumps(doc, indent=2))  # noqa: T201 - CLI output
        return 0

    telemetry.enable()
    enable()
    out = args.out or os.path.join(
        root.common.dirs.cache, "profiles",
        "cli_%s" % time.strftime("%Y%m%d_%H%M%S"))
    os.makedirs(out, exist_ok=True)
    from znicz_tpu.launcher import run_workflow
    import jax
    with jax.profiler.trace(out):
        run_workflow(args.target)
        import jax.numpy as jnp
        jax.block_until_ready(jnp.zeros(()) + 0)  # drain before close
    report = export_report(os.path.join(out, "profiler_report.json"))
    print("device trace -> %s" % out)  # noqa: T201 - CLI output
    print("profiler report -> %s" % report)  # noqa: T201
    print("executables registered: %d"  # noqa: T201
          % len(cost_registry()))
    led = ledger_summary()
    print("ledger: live %d B, high water %d B, balanced=%s"  # noqa: T201
          % (led["live_bytes"], led["high_water_bytes"],
             led["balanced"]))
    print("summarize: python tools/profile_summary.py %s"  # noqa: T201
          % out)
    return 0
