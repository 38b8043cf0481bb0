"""Unified telemetry — span tracing, metrics registry, JAX-aware counters.

The reference Veles core shipped live observability as a first-class
tier (SURVEY.md §5.5: web status + plot streaming); znicz_tpu's tier-2
equivalent is this module, shared by the trainer, the loaders, the
snapshotter, the benchmark (``benchmarks/``) and the status server.
Three pillars:

* **Span tracer** — nestable ``with telemetry.span("name", **attrs):``
  blocks record complete events into a bounded ring buffer, stamped in
  integer nanoseconds of ``time.perf_counter_ns()`` with an id, the
  parent's id (a per-thread stack kept only while telemetry is on) and
  the identifiers a unit of work shares (``window``), and enter a
  ``jax.profiler.TraceAnnotation`` of the same name for an operator's
  own capture.  :func:`spans` / :func:`self_times` read the ring;
  :func:`export_trace` writes Chrome-trace/Perfetto JSON
  (``traceEvents`` schema — load it at https://ui.perfetto.dev).
* **Metrics registry** — process-global :func:`counter` /
  :func:`gauge` / :func:`histogram` series.  :func:`prometheus_text`
  renders the Prometheus text exposition (served at ``/metrics`` by
  :class:`znicz_tpu.core.status_server.StatusServer`);
  :func:`snapshot` returns the JSON view merged into Publisher
  reports.
* **Flight recorder** — a bounded structured-event journal
  (:func:`record_event` / :func:`journal_events` /
  :func:`export_journal`): config at start, epoch milestones,
  snapshot/reload events, health violations, slow serving requests.
  On an unhandled exception or SIGTERM (:func:`install_crash_handler`)
  — or explicitly via :func:`write_crash_report` — the last-N events,
  a metrics snapshot and the traceback land in a crash-report
  directory.  Records when telemetry OR the health monitor
  (:mod:`znicz_tpu.core.health`) is enabled.
* **JAX-aware counters** — two ``jax.monitoring`` listeners.  jax's
  time span of each jaxpr trace, lowering and backend compile becomes
  a span of the ring (``jax.trace`` / ``jax.lower`` / ``jax.compile``,
  attr ``fun``, a compile's ``cache`` = ``hit`` / ``miss`` / ``off``;
  its wall-clock seconds laid on ``perf_counter_ns`` by
  :func:`clock_anchor`) under the span open on the calling thread, and
  is counted: backend compiles (`jax.backend_compiles` +
  `jax.compile_seconds`), jaxpr traces (`jax.traces` +
  `jax.trace_seconds` — a re-trace on every dispatch means the jit
  cache is MISSING; steady counters with growing step counts mean
  cache hits).  The event listener counts persistent-compilation-cache
  hits/misses.
  Host↔device traffic is metered where it actually happens —
  ``memory.Array`` map_read/dev and the fused trainer's batched
  ``host_fetch`` (`transfer.d2h_bytes` / `transfer.h2d_bytes`, one
  `transfer.*_calls` bump per round trip).  The asynchronous control
  plane additionally counts its per-segment aggregate readbacks
  (`trainer.readbacks` — == segments when fully async; surfaced as
  ``summary()["readbacks"]`` and ``BENCHMARK.json``'s
  `readbacks_per_epoch`)
  and gauges the window pipeline (`trainer.inflight_windows`).

Disabled-by-default fast path: everything is gated on
``root.common.telemetry.enabled``.  When off, :func:`span` returns one
shared no-op context manager and :func:`counter`/:func:`gauge`/
:func:`histogram` return one shared null metric — no events, no
registry entries, no allocation.  Hot call sites additionally guard
with ``if telemetry.enabled():`` so the disabled cost is a single
predicate.

Multi-host: every process keeps its own registry;
:func:`merged_snapshot` reduces all hosts' counters into one view
through :func:`znicz_tpu.parallel.multihost.aggregate_telemetry`.
"""

import collections
import itertools
import json
import logging
import os
import threading
import time

from znicz_tpu.core.config import root
from znicz_tpu.analysis import locksmith

logger = logging.getLogger("telemetry")

#: the config node (object identity is stable: config.py creates it at
#: import and Config merges dict assignments into the existing node)
_cfg = root.common.telemetry

#: the Chrome export's time origin (module import), so that its ts/dur
#: microseconds stay small; the ring itself holds absolute
#: ``time.perf_counter_ns()`` stamps, the clock a reader outside this
#: module can lay under a device trace
_T0_NS = time.perf_counter_ns()
_T0 = _T0_NS * 1e-9

_lock = locksmith.lock("telemetry.registry")


def enabled():
    """The one gate every hook checks.  Reads the live config value so
    flipping ``root.common.telemetry.enabled`` mid-run takes effect
    immediately (the status server can watch a run that enables
    tracing for one epoch).  The first enabled check also installs the
    jax.monitoring listeners — deferring the (heavy) jax import out of
    module import keeps telemetry-importing tools jax-free until
    telemetry is actually turned on."""
    if _cfg.get("enabled", False):
        if not _jax_hooked:
            install_jax_hooks()
        return True
    return False


def enable():
    root.common.telemetry.enabled = True
    install_jax_hooks()
    clock_anchor()
    return True


#: the last (``time_ns``, ``perf_counter_ns``) pair :func:`clock_anchor`
#: took; None until telemetry is enabled
_anchor = None


def clock_anchor():
    """(``time.time_ns()``, ``time.perf_counter_ns()``) read back to back:
    of three tries the one whose two ``perf_counter_ns`` reads around the
    wall clock's lie closest, with their midpoint.  It lays the ring's
    stamps on any wall clock: jax's compile spans (given in wall seconds)
    here, and a profiler session's ``profile_start_time`` in the
    benchmark (``benchmarks/lib/program_clock.py``).  Taken at
    :func:`enable` and again at each call."""
    global _anchor
    best = None
    for _ in range(3):
        p0 = time.perf_counter_ns()
        wall = time.time_ns()
        p1 = time.perf_counter_ns()
        if best is None or p1 - p0 < best[0]:
            best = (p1 - p0, wall, (p0 + p1) // 2)
    _anchor = best[1:]
    return _anchor


def disable():
    root.common.telemetry.enabled = False
    return False


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------

class _NullSpan(object):
    """Shared no-op context manager — the disabled-mode span."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NULL_SPAN = _NullSpan()


class _Ring(object):
    """Bounded event buffer (oldest events drop first).  Capacity is
    read lazily from ``root.common.telemetry.<cap_key>`` so tests can
    shrink a ring before its first append."""

    def __init__(self, cap_key="trace_capacity", default=65536):
        self._cap_key = cap_key
        self._default = default
        self._events = None
        self.dropped = 0

    def _buf(self):
        if self._events is None:
            cap = int(_cfg.get(self._cap_key, self._default))
            self._events = collections.deque(maxlen=cap)
        return self._events

    def append(self, ev):
        buf = self._buf()
        if len(buf) == buf.maxlen:
            self.dropped += 1
        buf.append(ev)

    def clear(self):
        self._events = None
        self.dropped = 0

    def __len__(self):
        return 0 if self._events is None else len(self._events)

    def events(self):
        return [] if self._events is None else list(self._events)


_ring = _Ring()

#: flight-recorder journal — structured milestone events (config at
#: start, epochs, snapshots, reloads, health violations, slow serving
#: requests), dumped as JSONL by export_journal/write_crash_report
_journal = _Ring("journal_capacity", 4096)


#: open spans of each thread, innermost last; the attribute exists on
#: a thread only once a span was entered there with telemetry on
_open = threading.local()
_span_ids = itertools.count(1)

#: attrs a span hands down to the spans opened inside it: what the
#: spans of one unit of work share (``window``: the trainer's running
#: count of dispatched windows and validation minibatches)
INHERITED_ATTRS = ("window",)

#: jax.profiler, imported when the first span is entered (False where
#: jax cannot be imported: config-only tools)
_jax_profiler = None


def _annotation(name, step_num):
    """The ``jax.profiler`` annotation of a span: an operator's own
    capture (``/debug/profile``, ``python -m znicz_tpu profile``), whose
    host tracer is on, shows the program's span names on the trace's own
    clock; a ``step_num`` makes it a step marker."""
    global _jax_profiler
    if _jax_profiler is None:
        try:
            import jax.profiler as jax_profiler
        except Exception:  # noqa: BLE001 - a jax-free interpreter
            jax_profiler = False
        _jax_profiler = jax_profiler
    if not _jax_profiler:
        return None
    if step_num is None:
        return _jax_profiler.TraceAnnotation(name)
    return _jax_profiler.StepTraceAnnotation(name, step_num=step_num)


class _Span(object):
    """A live span: records one complete ("X") event on exit.
    Exceptions propagate; the span still closes (the trace shows where
    the run died)."""

    __slots__ = ("name", "args", "t0", "id", "parent", "_step_num",
                 "_ann")

    def __init__(self, name, args, step_num=None):
        self.name = name
        self.args = args or None
        self.t0 = None
        self.id = self.parent = 0
        self._step_num = step_num
        self._ann = None

    def set(self, **attrs):
        """Attributes known only once the work is under way (a fetch's
        bytes, a window's step count)."""
        if self.args is None:
            self.args = attrs
        else:
            self.args.update(attrs)
        return self

    def __enter__(self):
        stack = _open.__dict__.setdefault("stack", [])
        self.id = next(_span_ids)
        if stack:
            parent = stack[-1]
            self.parent = parent.id
            if parent.args:
                for key in INHERITED_ATTRS:
                    if key in parent.args and \
                            (self.args is None or key not in self.args):
                        self.set(**{key: parent.args[key]})
        stack.append(self)
        self._ann = _annotation(self.name, self._step_num)
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = _open.__dict__.get("stack")
        if stack:
            if stack[-1] is self:
                stack.pop()
            elif self in stack:     # a reset() or a toggle in between
                stack.remove(self)
        _ring.append(("X", self.name, self.t0, t1 - self.t0,
                      threading.get_ident(), self.args, self.id,
                      self.parent))
        return False


def span(name, step_num=None, **attrs):
    """``with telemetry.span("loader.fill", size=n):`` — a nestable
    traced region.  Returns the shared no-op when telemetry is off: no
    stack, no annotation, no allocation.  ``step_num`` marks the span as
    one step of the job for ``jax.profiler`` (the fused trainer's
    windows)."""
    if not enabled():
        return _NULL_SPAN
    return _Span(name, attrs, step_num)


def record_span(name, t0, t1, parent=0, **attrs):
    """Append a span already over, ``t0`` to ``t1`` in ``perf_counter_ns``,
    under the span id ``parent``: work another thread saw end (the data
    set's crossing) or that jax timed itself (its compile spans).  It
    enters no ``TraceAnnotation`` and nothing is left open.  A span put
    under a parent that is not open on the calling thread ran beside it,
    not inside it: it carries ``thread``, the calling thread's name, and
    :func:`self_times` takes none of the parent's time for it.  Returns
    the ring's entry, a list, so that a later span can adopt it
    (``_on_jax_span``)."""
    if parent and all(s.id != parent
                      for s in _open.__dict__.get("stack", ())):
        attrs["thread"] = threading.current_thread().name
    entry = ["X", name, t0, t1 - t0, threading.get_ident(), attrs or None,
             next(_span_ids), parent]
    _ring.append(entry)
    return entry


def open_span_id():
    """The id of the innermost span open on the calling thread (0 where
    none is): the parent that work handed to another thread is recorded
    under (:func:`record_span`)."""
    stack = _open.__dict__.get("stack")
    return stack[-1].id if stack else 0


def instant(name, **attrs):
    """A zero-duration marker event (epoch boundaries etc.)."""
    if not enabled():
        return
    stack = _open.__dict__.get("stack")
    _ring.append(("i", name, time.perf_counter_ns(), 0,
                  threading.get_ident(), attrs or None, 0,
                  stack[-1].id if stack else 0))


def spans(ph="X"):
    """The ring's complete spans, oldest first, as ``(name, start_ns,
    dur_ns, id, parent, attrs)`` on ``time.perf_counter_ns()``;
    ``spans("i")`` gives the instant markers in the same shape (duration
    and id 0).  ``parent`` is 0 at the top of a thread."""
    return [(name, t0, dur, sid, parent, args or {})
            for p, name, t0, dur, _, args, sid, parent in _ring.events()
            if p == ph]


def self_times(span_list):
    """``{id: self_ns}`` for :func:`spans` output: a span's duration
    minus what its children cover.  The children of one span were
    opened one after the other on its thread, so what they cover is the
    sum of their durations; a child that another thread recorded
    (attr ``thread``, :func:`record_span`) ran beside it and covers
    none of it."""
    out = {sid: dur for _, _, dur, sid, _, _ in span_list}
    for _, _, dur, _, parent, attrs in span_list:
        if parent in out and "thread" not in attrs:
            out[parent] -= dur
    return {sid: max(0, t) for sid, t in out.items()}


def _process_index():
    try:
        import jax
        return int(jax.process_index())
    except Exception:
        return 0


def trace_events():
    """The buffered events as Chrome-trace dicts."""
    pid = _process_index()
    out = []
    for ph, name, t0, dur, tid, args, sid, parent in _ring.events():
        ev = {"name": name, "ph": ph,
              "ts": round((t0 - _T0_NS) / 1e3, 3), "pid": pid,
              "tid": tid, "cat": "znicz"}
        if ph == "X":
            ev["dur"] = round(dur / 1e3, 3)
            ev["span_id"] = sid
        elif ph == "i":
            ev["s"] = "t"
        if parent:
            ev["parent_id"] = parent
        if args:
            ev["args"] = args
        out.append(ev)
    return out


def export_trace(path):
    """Write the ring buffer as Chrome-trace/Perfetto JSON and return
    the path.  Loadable by chrome://tracing and ui.perfetto.dev."""
    events = trace_events()
    payload = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "znicz_tpu.telemetry",
            "process_index": _process_index(),
            "dropped_events": _ring.dropped,
        },
    }
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, default=str)
    return path


# ---------------------------------------------------------------------------
# Flight recorder — the black-box journal
# ---------------------------------------------------------------------------

def journal_enabled():
    """The flight recorder records when telemetry, the health monitor,
    the fault-injection registry, the serving SLO tracker OR the
    durable blackbox is on — a health-only run still wants its black
    box, a chaos run must journal what it injected and how recovery
    went, an SLO-only run must land its ``slo.burn`` threshold
    crossings, and an armed blackbox (core/blackbox.py) needs events
    to flow so its write-through sink can persist them."""
    if _cfg.get("enabled", False):
        return True
    if root.common.health.get("enabled", False):
        return True
    if root.common.faults.get("enabled", False):
        return True
    if root.common.serving.get("slo_enabled", False):
        return True
    return bool(_cfg.blackbox.get("enabled", False))


#: write-through sink: when the durable blackbox arms it installs a
#: callable here and every journal event ALSO lands on disk at emit
#: time (core/blackbox.py) — a ring-dump-at-crash cannot help a
#: SIGKILLed process.  None (one pointer compare on the emit path)
#: in every unarmed process.
_journal_sink = None


def set_journal_sink(fn):
    """Install (or, with None, remove) the durable write-through
    journal sink.  Sink exceptions are swallowed at the emit site —
    instrumentation must never take down the instrumented."""
    global _journal_sink
    _journal_sink = fn


def record_event(kind, **fields):
    """Append one structured event to the bounded journal.  Events are
    plain dicts stamped with wall time and seconds-since-import; the
    ring drops oldest first, so after a crash the journal holds the
    LAST N milestones — what a black box is for.  No-op (and ``None``)
    when neither telemetry nor health is enabled."""
    if not journal_enabled():
        return None
    ev = {"t": round(time.time(), 6),
          "elapsed": round(time.perf_counter() - _T0, 6),
          "kind": kind}
    ev.update(fields)
    _journal.append(ev)
    sink = _journal_sink
    if sink is not None:
        try:
            sink(ev)
        except Exception:  # noqa: BLE001 - never fail the emitter
            logger.debug("journal sink failed", exc_info=True)
    return ev


def journal_events():
    """The buffered journal events (oldest first), as plain dicts."""
    return _journal.events()


def journal_dropped():
    return _journal.dropped


def export_journal(path):
    """Write the journal as JSONL (one event per line — the format
    ``tools/profile_summary.py --journal`` pretty-prints) and return
    the path.  Writes whatever is buffered even when recording is
    currently off (a crash dump must not depend on live config)."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        for ev in _journal.events():
            f.write(json.dumps(ev, default=str) + "\n")
    return path


def write_crash_report(reason="unhandled-exception", exc_info=None,
                       directory=None):
    """Dump the black box to a fresh crash-report directory and return
    its path:

    * ``events.jsonl``  — the last-N journal events,
    * ``metrics.json``  — a full metrics snapshot,
    * ``traceback.txt`` — the active exception (``exc_info`` or
      ``sys.exc_info()``), when there is one,
    * ``report.json``   — reason / time / pid / dropped-event count.

    Called by the health monitor's ``halt`` policy, the launcher's
    unhandled-exception path, and the fatal-signal handler."""
    import sys
    import traceback
    base = (directory or root.common.health.get("crash_dir", None)
            or os.path.join(root.common.dirs.cache, "crash_reports"))
    stamp = time.strftime("%Y%m%d_%H%M%S")
    path = os.path.join(base, "crash_%s_pid%d" % (stamp, os.getpid()))
    n = 0
    while os.path.exists(path):  # same second, same pid: keep both
        n += 1
        path = os.path.join(base, "crash_%s_pid%d_%d"
                            % (stamp, os.getpid(), n))
    os.makedirs(path, exist_ok=True)
    export_journal(os.path.join(path, "events.jsonl"))
    with open(os.path.join(path, "metrics.json"), "w") as f:
        json.dump(snapshot(), f, indent=2, default=str)
    exc_info = exc_info or sys.exc_info()
    if exc_info and exc_info[0] is not None:
        with open(os.path.join(path, "traceback.txt"), "w") as f:
            f.write("".join(traceback.format_exception(*exc_info)))
    try:
        from znicz_tpu.core import blackbox
        blackbox_segment = blackbox.current_segment()
    except Exception:  # noqa: BLE001 - a crash dump must not crash
        blackbox_segment = None
    with open(os.path.join(path, "report.json"), "w") as f:
        json.dump({"reason": str(reason), "time": time.time(),
                   "pid": os.getpid(),
                   "journal_events": len(_journal),
                   "journal_dropped": _journal.dropped,
                   "blackbox_segment": blackbox_segment}, f, indent=2)
    logger.error("crash report -> %s (%s)", path, reason)
    return path


_crash_handler_installed = False


def install_crash_handler():
    """Chain a crash-dumping ``sys.excepthook`` and a SIGTERM handler
    (idempotent).  Both dump only when :func:`journal_enabled` — an
    instrumentation-free run must not grow a crash directory.  The
    SIGTERM handler re-raises the signal with the previous disposition
    restored, so default termination semantics are preserved."""
    global _crash_handler_installed
    if _crash_handler_installed:
        return True
    import sys
    prev_hook = sys.excepthook

    def hook(tp, val, tb):
        try:
            # skip when a report for THIS exception already exists
            # (health halt / the launcher tag the exception)
            if journal_enabled() and \
                    getattr(val, "crash_report", None) is None:
                write_crash_report(reason=repr(val),
                                   exc_info=(tp, val, tb))
        except Exception:  # noqa: BLE001 - never mask the real crash
            pass
        prev_hook(tp, val, tb)

    sys.excepthook = hook
    try:
        import signal
        prev_term = signal.getsignal(signal.SIGTERM)

        def on_term(signum, frame):
            try:
                if journal_enabled():
                    write_crash_report(reason="fatal signal SIGTERM")
            except Exception:  # noqa: BLE001 - still die properly
                pass
            if prev_term == signal.SIG_IGN:
                # the process was IGNORING SIGTERM before we hooked it
                # — dump the black box but preserve that disposition
                # (do not turn an ignored signal into a death)
                return
            signal.signal(signal.SIGTERM,
                          prev_term if prev_term is not None
                          else signal.SIG_DFL)
            os.kill(os.getpid(), signum)

        signal.signal(signal.SIGTERM, on_term)
    except (ValueError, OSError):  # non-main thread / exotic platform
        pass
    _crash_handler_installed = True
    return True


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

class _NullMetric(object):
    """Shared do-nothing metric — what the factories hand out when
    telemetry is disabled (no registry entry is created)."""

    __slots__ = ()

    def inc(self, n=1):
        pass

    def set(self, value):
        pass

    def observe(self, value, count=1):
        pass

    @property
    def value(self):
        return 0


_NULL_METRIC = _NullMetric()


class Counter(object):
    """Monotonic counter."""

    kind = "counter"

    def __init__(self, name):
        self.name = name
        self._value = 0
        self._lock = locksmith.lock("telemetry.metric")

    def inc(self, n=1):
        with self._lock:
            self._value += n

    @property
    def value(self):
        return self._value


class Gauge(object):
    """Last-write-wins instantaneous value."""

    kind = "gauge"

    def __init__(self, name):
        self.name = name
        self._value = 0.0

    def set(self, value):
        self._value = value

    @property
    def value(self):
        return self._value


#: default histogram bucket upper bounds — log-spaced seconds, wide
#: enough for sub-ms jitted steps and minute-scale compiles
DEFAULT_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                   0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                   10.0, 30.0, 60.0)


class Histogram(object):
    """Cumulative-bucket histogram + a bounded reservoir of recent
    observations for percentile queries.

    ``observe(v, count=k)`` records ``k`` occurrences of ``v`` in one
    call (the fused window path reports its per-step average once per
    window, weighted by the window's step count).  The reservoir gets
    ``min(k, 256)`` copies so percentile queries stay count-weighted —
    a 1-step epoch-tail window must not weigh as much as a 40-step
    one."""

    kind = "histogram"

    def __init__(self, name, buckets=DEFAULT_BUCKETS):
        self.name = name
        self.buckets = tuple(sorted(buckets))
        self._bucket_counts = [0] * (len(self.buckets) + 1)  # +Inf last
        self._count = 0
        self._sum = 0.0
        window = int(_cfg.get("histogram_window", 2048))
        self._recent = collections.deque(maxlen=window)
        self._lock = locksmith.lock("telemetry.metric")

    def observe(self, value, count=1):
        value = float(value)
        i = 0
        for i, b in enumerate(self.buckets):
            if value <= b:
                break
        else:
            i = len(self.buckets)
        with self._lock:
            self._bucket_counts[i] += count
            self._count += count
            self._sum += value * count
            if count == 1:
                self._recent.append(value)
            else:
                self._recent.extend([value] * min(int(count), 256))

    @property
    def count(self):
        return self._count

    @property
    def sum(self):
        return self._sum

    def percentile(self, p):
        """p in [0, 100] over the bounded reservoir of recent
        observations (None when empty)."""
        with self._lock:
            data = sorted(self._recent)
        if not data:
            return None
        k = max(0, min(len(data) - 1,
                       int(round(p / 100.0 * (len(data) - 1)))))
        return data[k]

    def stats(self):
        with self._lock:
            data = sorted(self._recent)
            count, total = self._count, self._sum
        st = {"count": count, "sum": round(total, 6)}
        if data:
            n = len(data)

            def q(p):
                return data[max(0, min(n - 1,
                                       int(round(p / 100.0 * (n - 1)))))]

            st.update({"min": data[0], "max": data[-1],
                       "p50": q(50), "p90": q(90), "p99": q(99)})
        return st


_metrics = {}


def _get_metric(name, factory):
    if not enabled():
        return _NULL_METRIC
    m = _metrics.get(name)
    if m is None:
        with _lock:
            m = _metrics.get(name)
            if m is None:
                m = factory(name)
                _metrics[name] = m
    return m


def counter(name):
    """Get-or-create the named counter (null metric when disabled)."""
    return _get_metric(name, Counter)


def gauge(name):
    return _get_metric(name, Gauge)


def histogram(name, buckets=DEFAULT_BUCKETS):
    return _get_metric(name, lambda n: Histogram(n, buckets))


def labeled(name, **labels):
    """THE naming convention for per-key series: labels become sorted
    ``key_value`` dotted suffixes — ``labeled("serving.predictions",
    bucket=8)`` -> ``"serving.predictions.bucket_8"``.  Prometheus
    exposition then sanitizes dots to underscores, so dashboards see
    one family prefix per logical series.  Used by the serving tier's
    per-bucket/per-route counters; use it for any bounded label set
    (never for unbounded values like request ids — each distinct name
    is a registry entry)."""
    if not labels:
        return name
    return name + "." + ".".join(
        "%s_%s" % (k, labels[k]) for k in sorted(labels))


def add_bytes(direction, nbytes):
    """Host↔device transfer meter (``direction`` is "d2h" or "h2d").
    Call sites guard with :func:`enabled` so the disabled path never
    computes nbytes."""
    counter("transfer.%s_bytes" % direction).inc(int(nbytes))
    counter("transfer.%s_calls" % direction).inc()


def reset():
    """Drop all metrics, trace events AND the flight-recorder journal
    (tests, bench isolation — a test's health violations must not leak
    into the next test's crash report)."""
    with _lock:
        _metrics.clear()
        _ring.clear()
        _journal.clear()
    _open.__dict__.pop("stack", None)
    _open.__dict__.pop("jax_top", None)
    _open.__dict__.pop("cache", None)


# ---------------------------------------------------------------------------
# Export: snapshot / Prometheus exposition / bench summary
# ---------------------------------------------------------------------------

def snapshot():
    """JSON-able view of every registered metric."""
    with _lock:
        metrics = list(_metrics.values())
    snap = {"counters": {}, "gauges": {}, "histograms": {}}
    for m in metrics:
        if m.kind == "counter":
            snap["counters"][m.name] = m.value
        elif m.kind == "gauge":
            snap["gauges"][m.name] = m.value
        else:
            snap["histograms"][m.name] = m.stats()
    snap["trace"] = {"buffered_events": len(_ring),
                     "dropped_events": _ring.dropped}
    return snap


def merged_snapshot():
    """:func:`snapshot`, reduced across hosts on multi-process runs
    (one merged view per the SPMD gang; identity single-process)."""
    snap = snapshot()
    try:
        import jax
        if jax.process_count() > 1:
            from znicz_tpu.parallel import multihost
            snap = multihost.aggregate_telemetry(snap)
    except Exception as e:  # noqa: BLE001 - report local rather than die
        logger.warning("telemetry aggregation failed (%s); "
                       "reporting local host only", e)
    return snap


def _prom_name(name):
    """Sanitize a dotted series name into Prometheus [a-zA-Z0-9_:]."""
    out = []
    for ch in name:
        out.append(ch if (ch.isalnum() and ch.isascii()) or ch in "_:"
                   else "_")
    s = "".join(out)
    if s and s[0].isdigit():
        s = "_" + s
    return "znicz_" + s


#: help-string registry: one-liner per series FAMILY, keyed by the
#: longest-matching dotted prefix of the (pre-sanitization) series
#: name.  Emitted as ``# HELP`` ahead of every ``# TYPE`` line of the
#: exposition; modules owning a family register theirs via
#: :func:`register_help` (serving/slo.py, core/timeseries.py)
_HELP = {
    "analysis": "static/runtime analysis layer (graftlint, locksmith)",
    "faults": "deterministic fault injection (core/faults.py)",
    "health": "numeric training-health monitor (core/health.py)",
    "jax.backend_compiles": "XLA backend compilations",
    "jax.compile_seconds": "XLA backend compile wall time",
    "jax.traces": "jaxpr traces (re-traces mean a missing jit cache)",
    "jax.trace_seconds": "jaxpr trace wall time",
    "jax.persistent_cache_hits":
        "persistent compilation-cache hits (core/compile_cache.py)",
    "jax.persistent_cache_misses": "persistent compilation-cache "
                                   "misses",
    "launcher": "supervised-restart lifecycle (launcher.py)",
    "loader": "minibatch loader pipeline",
    "memory": "device-memory ledger (core/profiler.py)",
    "moe": "routed experts' load, counted at each train readback "
           "(units/fused_trainer.py)",
    "profiler": "performance introspection (core/profiler.py)",
    "registry": "multi-model registry lifecycle "
                "(serving/registry.py)",
    "serving.request_seconds": "end-to-end request latency "
                               "(admission to reply)",
    "serving.queue_wait_seconds": "time queued before a dispatch "
                                  "slot took the request",
    "serving.assembly_seconds": "batch concatenation time",
    "serving.device_seconds": "engine dispatch time per request",
    "serving.batch_rows": "coalesced rows per dispatch",
    "serving.batch_fill": "coalesced rows over the dispatched bucket",
    "serving.pad_overhead": "padding fraction of the dispatched "
                            "bucket",
    "serving.tail_seconds": "per-scenario batch-1 tail latency "
                            "(serving/latency.py)",
    "serving": "online inference serving tier (znicz_tpu/serving/)",
    "snapshotter": "snapshot export/restore (core/snapshotter.py)",
    "trainer": "fused training control plane",
    "transfer": "host<->device transfer meters",
    "unit": "unit-graph execution",
    "workflow": "workflow lifecycle",
}


def register_help(prefix, text):
    """Register (or override) the one-line help for a series-family
    prefix — the ``# HELP`` text every series under it exports."""
    _HELP[str(prefix)] = str(text)
    return prefix


def help_for(name):
    """The registered help for a dotted series name: longest dotted
    prefix wins; a generic family fallback guarantees every exported
    series carries a ``# HELP`` line."""
    parts = name.split(".")
    for i in range(len(parts), 0, -1):
        text = _HELP.get(".".join(parts[:i]))
        if text is not None:
            return text
    return "znicz_tpu telemetry series (family %s)" % parts[0]


def escape_help(text):
    """Escape a ``# HELP`` string per the Prometheus text exposition
    format: backslash and line feed."""
    return str(text).replace("\\", "\\\\").replace("\n", "\\n")


def escape_label_value(value):
    """Escape a label VALUE per the exposition format: backslash,
    double quote and line feed (in that order — escaping the quote
    first would double-escape the added backslashes)."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def prometheus_text():
    """Prometheus text exposition (format version 0.0.4) of the whole
    registry — what ``/metrics`` serves.  Every series family gets a
    ``# HELP`` line ahead of its ``# TYPE`` (the help-string registry
    above; :func:`register_help` extends it)."""
    with _lock:
        metrics = sorted(_metrics.values(), key=lambda m: m.name)
    lines = []
    for m in metrics:
        name = _prom_name(m.name)
        lines.append("# HELP %s %s"
                     % (name, escape_help(help_for(m.name))))
        if m.kind == "counter":
            lines.append("# TYPE %s counter" % name)
            lines.append("%s %s" % (name, m.value))
        elif m.kind == "gauge":
            lines.append("# TYPE %s gauge" % name)
            lines.append("%s %s" % (name, _fmt(m.value)))
        else:
            lines.append("# TYPE %s histogram" % name)
            # consistent point-in-time view: a scrape racing observe()
            # must never emit +Inf bucket != count (the Prometheus
            # histogram invariant recording rules rely on)
            with m._lock:
                bucket_counts = list(m._bucket_counts)
                total, count = m._sum, m._count
            acc = 0
            for bound, c in zip(m.buckets, bucket_counts):
                acc += c
                lines.append('%s_bucket{le="%s"} %d'
                             % (name, escape_label_value(_fmt(bound)),
                                acc))
            acc += bucket_counts[-1]
            lines.append('%s_bucket{le="+Inf"} %d' % (name, acc))
            lines.append("%s_sum %s" % (name, _fmt(total)))
            lines.append("%s_count %d" % (name, count))
    return "\n".join(lines) + "\n"


def _fmt(v):
    """Float formatting without exponent-capital quirks ('1e-05' style
    is valid Prometheus; plain repr is fine)."""
    return repr(float(v)) if isinstance(v, float) else str(v)


def summary():
    """The compact why-block: compile count, transfer bytes,
    step-time percentiles."""
    snap = snapshot()
    c = snap["counters"]
    h = snap["histograms"]
    out = {
        "backend_compiles": int(c.get("jax.backend_compiles", 0)),
        "jaxpr_traces": int(c.get("jax.traces", 0)),
        "d2h_bytes": int(c.get("transfer.d2h_bytes", 0)),
        "d2h_calls": int(c.get("transfer.d2h_calls", 0)),
        "h2d_bytes": int(c.get("transfer.h2d_bytes", 0)),
    }
    if "trainer.readbacks" in c:
        # async control plane: batched decision-aggregate readbacks the
        # fused trainer paid (== segments when fully asynchronous)
        out["readbacks"] = int(c["trainer.readbacks"])
    g = snap.get("gauges") or {}
    if "trainer.data_shards" in g:
        # mesh-sharded control plane: the shard extents the trainer ran
        # under
        out["data_shards"] = int(g["trainer.data_shards"])
        out["model_shards"] = int(g.get("trainer.model_shards", 1))
    cs = h.get("jax.compile_seconds")
    if cs:
        out["compile_seconds_total"] = round(cs.get("sum", 0.0), 3)
    steps = h.get("trainer.step_seconds") or h.get("unit.run_seconds")
    if steps and steps.get("count"):
        out["step_seconds"] = {
            "count": steps["count"],
            "p50": steps.get("p50"),
            "p99": steps.get("p99"),
        }
    serving = serving_summary(snap)
    if serving is not None:
        out["serving"] = serving
    return out


def serving_summary(snap=None):
    """The serving-tier why-block (requests, rejections, latency
    p50/p99, batch fill) — read by the serving smoke; None when no
    serving series exist."""
    snap = snap or snapshot()
    c, h = snap["counters"], snap["histograms"]
    lat = h.get("serving.request_seconds")
    if not lat or not lat.get("count"):
        return None
    out = {
        "requests": int(lat["count"]),
        "latency_p50_ms": (round(lat["p50"] * 1e3, 3)
                           if lat.get("p50") is not None else None),
        "latency_p99_ms": (round(lat["p99"] * 1e3, 3)
                           if lat.get("p99") is not None else None),
        "rejected": int(c.get("serving.rejected", 0)),
        "timeouts": int(c.get("serving.timeouts", 0)),
        "batches": int(c.get("serving.batches", 0)),
    }
    fill = h.get("serving.batch_fill")
    if fill and fill.get("count"):
        out["batch_fill_p50"] = fill.get("p50")
    # request-trace breakdown (PR 3): where a request's latency went
    for series, key in (("serving.queue_wait_seconds",
                         "queue_wait_p50_ms"),
                        ("serving.device_seconds", "device_p50_ms")):
        part = h.get(series)
        if part and part.get("count") and part.get("p50") is not None:
            out[key] = round(part["p50"] * 1e3, 3)
    compiles = {name: int(v) for name, v in c.items()
                if name.startswith("serving.compiles.")}
    if compiles:
        out["bucket_compiles"] = compiles
    return out


# ---------------------------------------------------------------------------
# Self-check validators (shared by tests, the CI smoke, and users
# wiring scrapers/trace viewers — one definition of "valid")
# ---------------------------------------------------------------------------

def validate_trace(doc, require_names=(), require_nested=()):
    """Validate a Chrome-trace document (the dict ``export_trace``
    wrote, already json-loaded) and return its event list.

    * every event must carry the ``traceEvents`` schema fields
      (name/ph/ts, dur for complete events); ``ph: "M"`` metadata
      events (process_name tracks in a stitched cross-process trace,
      reqtrace.stitch) are tolerated and excluded from the span
      checks;
    * ``require_names`` — span names that must be present;
    * ``require_nested`` — (child, parent) name pairs: every child
      span must lie within some parent span on the timeline (the
      containment rule Perfetto nests by).

    Raises ``ValueError`` on any violation.
    """
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        raise ValueError("missing or empty traceEvents")
    names = set()
    for ev in events:
        if ev.get("ph") == "M":
            if "name" not in ev:
                raise ValueError("malformed metadata event: %r"
                                 % (ev,))
            continue
        if ev.get("ph") not in ("X", "i"):
            raise ValueError("unexpected event phase: %r" % (ev,))
        if not isinstance(ev.get("ts"), (int, float)) or "name" not in ev:
            raise ValueError("malformed event: %r" % (ev,))
        if ev["ph"] == "X" and not isinstance(ev.get("dur"),
                                              (int, float)):
            raise ValueError("complete event without dur: %r" % (ev,))
        names.add(ev["name"])
    missing = set(require_names) - names
    if missing:
        raise ValueError("missing spans %s (have %s)"
                         % (sorted(missing), sorted(names)))
    for child, parent in require_nested:
        spans = [(e["ts"], e["ts"] + e["dur"]) for e in events
                 if e["name"] == parent and e["ph"] == "X"]
        kids = [e for e in events
                if e["name"] == child and e["ph"] == "X"]
        if not kids:
            raise ValueError("no %r spans to nest-check" % child)
        for ev in kids:
            t0, t1 = ev["ts"], ev["ts"] + ev["dur"]
            if not any(a - 1e-3 <= t0 and t1 <= b + 1e-3
                       for a, b in spans):
                raise ValueError("%r span at ts=%s not nested in any "
                                 "%r span" % (child, ev["ts"], parent))
    return events


#: one Prometheus sample line: name{labels} value
_PROM_SAMPLE_RE = None


def parse_prometheus(text):
    """Validate Prometheus text exposition; return {family: type}.
    Raises ``ValueError`` on a malformed sample line."""
    import re
    global _PROM_SAMPLE_RE
    if _PROM_SAMPLE_RE is None:
        _PROM_SAMPLE_RE = re.compile(
            r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? [0-9eE+.-]+$")
    families = {}
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, fam, kind = line.split()
            families[fam] = kind
        elif line.startswith("#") or not line:
            continue
        elif not _PROM_SAMPLE_RE.match(line):
            raise ValueError("bad exposition line: %r" % line)
    return families


# ---------------------------------------------------------------------------
# JAX-aware counters (jax.monitoring listeners)
# ---------------------------------------------------------------------------

_jax_hooked = False

#: substring → our counter name for discrete jax.monitoring events
_JAX_EVENT_COUNTERS = (
    ("/jax/compilation_cache/cache_hits", "jax.persistent_cache_hits"),
    ("/jax/compilation_cache/cache_misses",
     "jax.persistent_cache_misses"),
)

#: substring of jax's time-span events → (span name, counter, histogram)
_JAX_SPANS = (
    ("jaxpr_trace", "jax.trace", "jax.traces", "jax.trace_seconds"),
    ("jaxpr_to_mlir_module", "jax.lower", None, None),
    ("backend_compile", "jax.compile", "jax.backend_compiles",
     "jax.compile_seconds"),
)

#: a span jax reports is taken to hold one reported before it that lies
#: inside it to this many ns: both ends come as float wall seconds
_NEST_SLACK_NS = 1000

#: top-level jax spans a thread keeps for an enclosing one to adopt
_JAX_KEPT = 4096


def _on_jax_event(event, **kwargs):
    if not enabled():
        return
    for needle, name in _JAX_EVENT_COUNTERS:
        if needle in event:
            # bounded by the literal _JAX_EVENT_COUNTERS table above
            counter(name).inc()  # graftlint: disable=telemetry-series
            # the persistent cache answers inside the compile, on its
            # thread: the compile span that closes next carries it
            _open.cache = "hit" if name.endswith("hits") else "miss"
            return


def _on_jax_span(event, start, end, fun_name="", **kwargs):
    """jax's trace, lowering and backend compile of a program, each a
    span of the ring: ``jax.trace`` / ``jax.lower`` / ``jax.compile``
    (attr ``fun``; a compile also ``cache``: ``hit``, ``miss`` or
    ``off`` from the cache events that fired inside it), under the span
    open on the calling thread, feeding the ``jax.traces`` /
    ``jax.backend_compiles`` series on the way.  jax reports a span as
    it ends, so a trace that holds others (a jitted function calling
    jitted ``jnp`` helpers) comes after them: it adopts the spans of its
    thread reported since it started."""
    if not enabled():
        return
    for needle, name, count, seconds in _JAX_SPANS:
        if needle in event:
            break
    else:
        return
    if count is not None:
        # bounded by the literal _JAX_SPANS table above
        took = end - start
        counter(count).inc()  # graftlint: disable=telemetry-series
        histogram(seconds).observe(took)  # graftlint: disable=telemetry-series
    wall_ns, perf_ns = _anchor or clock_anchor()
    t0 = perf_ns + int(round(start * 1e9)) - wall_ns
    t1 = perf_ns + int(round(end * 1e9)) - wall_ns
    attrs = {"fun": str(fun_name)}
    if name == "jax.compile":
        attrs["cache"] = _open.__dict__.pop("cache", "off")
    stack = _open.__dict__.get("stack")
    kept = _open.__dict__.setdefault(
        "jax_top", collections.deque(maxlen=_JAX_KEPT))
    entry = record_span(name, t0, t1, stack[-1].id if stack else 0, **attrs)
    while kept and kept[-1][2] >= t0 - _NEST_SLACK_NS and \
            kept[-1][2] + kept[-1][3] <= t1 + _NEST_SLACK_NS:
        kept.pop()[7] = entry[6]
    kept.append(entry)


def install_jax_hooks():
    """Register the jax.monitoring listeners (idempotent; tolerant of
    a jax-free interpreter so config-only tools can import this
    module).  The callbacks early-return when telemetry is off, so the
    standing cost is one predicate per compile/trace event."""
    global _jax_hooked
    if _jax_hooked:
        return True
    try:
        from jax import monitoring
    except Exception:  # pragma: no cover - jax is a baked-in dep
        return False
    with _lock:
        # re-check under the lock: the status-server thread and the
        # main thread can both see the first enabled() == True, and
        # jax.monitoring has no listener dedup — a double registration
        # would double-count every compile for the process lifetime
        if _jax_hooked:
            return True
        monitoring.register_event_listener(_on_jax_event)
        monitoring.register_event_time_span_listener(_on_jax_span)
        _jax_hooked = True
    return True
