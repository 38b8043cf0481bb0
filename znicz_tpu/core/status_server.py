"""Web status server — live workflow observability.

TPU-era equivalent of the reference core's tornado web UI (SURVEY.md
§5.5: workflow status + matplotlib plot streaming).  Dependency-free:
a stdlib ``ThreadingHTTPServer`` on a daemon thread serving

* ``/``            — a small auto-refreshing HTML dashboard,
* ``/status.json`` — workflow status (units, metrics, timings),
* ``/metrics``     — the telemetry registry in Prometheus text
  exposition format (core/telemetry.py; scrape it),
* ``/plots/``      — the pngs the plotters render into <cache>/plots,
* ``/debug/health`` — the numeric health monitor's status
  (core/health.py; 503 once a violation was recorded),
* ``/debug/events`` — the flight-recorder journal (core/telemetry.py),
* ``/debug/profile?seconds=N`` — on-demand ``jax.profiler`` capture
  (core/profiler.py; returns the trace directory),
* ``/debug/profiler`` — the performance-introspection report (cost
  registry, device-memory ledger),
* ``/debug/timeseries`` — the in-process metric time-series rings
  (core/timeseries.py),
* ``/debug/trace/<rid>`` — sampled per-request span trees
  (znicz_tpu/serving/reqtrace.py),
* ``/debug/pyprof?seconds=N`` — a windowed capture from the
  continuous Python sampling profiler (core/pyprof.py;
  ``format=collapsed|speedscope`` for renderer-ready output).

The HTTP plumbing (handler ``_send`` helpers, daemon-thread lifecycle,
idempotent ``stop()``) lives in :class:`HttpServerBase` /
:class:`HandlerBase`, shared with the serving front end
(:mod:`znicz_tpu.serving.server`).

Usage::

    server = StatusServer(workflow, port=8080).start()
    ...
    server.stop()
"""

import glob
import json
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from znicz_tpu.core.config import root
from znicz_tpu.core.logger import Logger
from znicz_tpu.core import telemetry
from znicz_tpu.analysis import locksmith

# ONE capture-concurrency guard shared by BOTH capture endpoints
# (/debug/profile and /debug/pyprof): a JAX device trace and a
# frame-walk capture interleaved on the same process would each
# distort what the other measures, so the second concurrent capture
# of EITHER kind gets the 409, not just a same-endpoint repeat.
_capture_guard = locksmith.lock("status_server.debug_capture")

_PAGE = """<html><head><title>znicz_tpu status</title>
<meta http-equiv="refresh" content="5"></head>
<body><h1>znicz_tpu — %(name)s</h1>
<pre id="status">%(status)s</pre>
%(plots)s
</body></html>"""


class BodyTooLargeError(ValueError):
    """Request body over ``root.common.serving.max_body_bytes`` —
    refused BEFORE reading (HTTP 413): one oversized upload must not
    be buffered into server memory.  Subclasses ``ValueError`` so
    body-draining helpers treat it like the other refuse-to-read
    case (Transfer-Encoding)."""


class HandlerBase(BaseHTTPRequestHandler):
    """Shared request-handler plumbing.  Subclasses (closed over their
    owning server) implement ``do_GET``/``do_POST`` with the ``_send*``
    helpers; ``owner`` is the :class:`HttpServerBase` that built the
    handler class."""

    owner = None
    #: served HTTP version — keep-alive for request streams
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):  # quiet: route to the logger
        if self.owner is not None:
            self.owner.debug(fmt, *args)

    def handle(self):
        # adopt the thread-name registry (core/pyprof.py) at request
        # entry: ThreadingHTTPServer spawns anonymous "Thread-N"
        # threads, and a sample attributed to "Thread-N" is a sample
        # lost to the "unnamed" bucket
        t = threading.current_thread()
        if not t.name.startswith("znicz:"):
            t.name = "znicz:http-handler"
        BaseHTTPRequestHandler.handle(self)

    def _send(self, code, ctype, body, headers=None):
        try:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            if self.close_connection:
                # tell keep-alive clients the truth before we drop the
                # socket (set e.g. when an unreadable body is refused)
                self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(body)
        except BrokenPipeError:  # client went away mid-reply
            pass

    def _send_json(self, code, obj, headers=None):
        self._send(code, "application/json",
                   json.dumps(obj, default=str).encode(),
                   headers=headers)

    def _read_body(self):
        if self.headers.get("Transfer-Encoding"):
            # only Content-Length bodies are spoken here; close the
            # connection so an UNREAD chunked payload cannot desync the
            # next request on a keep-alive socket
            self.close_connection = True
            raise ValueError("Transfer-Encoding is not supported — "
                             "send a Content-Length body")
        length = int(self.headers.get("Content-Length") or 0)
        cap = int(root.common.serving.get("max_body_bytes",
                                          16 << 20) or 0)
        if cap and length > cap:
            # refuse BEFORE reading: the unread bytes mean this
            # keep-alive socket cannot be reused, say so honestly
            self.close_connection = True
            raise BodyTooLargeError(
                "request body of %d bytes exceeds the %d-byte limit"
                % (length, cap))
        return self.rfile.read(length) if length > 0 else b""

    def _drain_body(self):
        """Consume (and discard) the request body before an early
        reply — replying with unread Content-Length bytes on the
        socket desyncs every later request of a keep-alive
        connection."""
        try:
            self._read_body()
        except ValueError:
            pass  # Transfer-Encoding: close_connection is already set

    def _send_metrics(self):
        """The Prometheus exposition endpoint — one definition shared
        by the status dashboard and the serving front end."""
        self._send(200, "text/plain; version=0.0.4; charset=utf-8",
                   telemetry.prometheus_text().encode())

    def _handle_debug(self):
        """The diagnostics endpoints every server built on this base
        exposes (status dashboard AND serving front end):

        * ``GET /debug/health`` — the health monitor's status JSON
          (healthz-style: 503 once a violation has been recorded),
        * ``GET /debug/events`` — the flight-recorder journal
          (``?n=`` newest-N cap, default 256; ``?kind=`` prefix
          filter; ``?rid=`` follows one request),
        * ``GET /debug/blackbox`` — the durable blackbox's writer
          stats and segment inventory (``core/blackbox.py``),
        * ``GET /debug/profile?seconds=N`` — capture a ``jax.profiler``
          device trace for N seconds (capped by
          ``root.common.profiler.capture_seconds_cap``) and reply with
          the trace directory; 409 while another capture runs,
        * ``GET /debug/profiler`` — the performance-introspection
          report (cost registry, memory ledger),
        * ``GET /debug/timeseries`` — the in-process metric
          time-series rings + trailing rates
          (``core/timeseries.py``; 404-style empty when disabled),
        * ``GET /debug/trace`` / ``GET /debug/trace/<rid>`` — the
          sampled per-request span trees
          (``znicz_tpu/serving/reqtrace.py``),
        * ``GET /debug/pyprof?seconds=N`` — a windowed capture from
          the continuous Python sampling profiler
          (``core/pyprof.py``; ``format=collapsed|speedscope``
          selects renderer-ready output, default raw JSON;
          ``{"enabled": false}`` when the knob is off).

        The two CAPTURE endpoints (``/debug/profile`` and
        ``/debug/pyprof``) share ONE concurrency guard: while either
        capture runs, the other answers 409 too.

        Returns True when the request was handled."""
        path, _, query = self.path.partition("?")
        if path == "/debug/timeseries":
            from znicz_tpu.core import timeseries
            self._send_json(200, timeseries.snapshot())
            return True
        if path == "/debug/trace" or path.startswith("/debug/trace/"):
            from znicz_tpu.serving import reqtrace
            rid = path[len("/debug/trace/"):] \
                if path.startswith("/debug/trace/") else ""
            if not rid:
                self._send_json(200, {
                    "enabled": reqtrace.enabled(),
                    "rids": reqtrace.rids()})
                return True
            tree = reqtrace.get(rid)
            if tree is None:
                self._send_json(404, {
                    "error": "no sampled trace for rid %r (sampling "
                             "%s; see root.common.serving."
                             "trace_sample_n)"
                             % (rid, "on" if reqtrace.enabled()
                                else "off")})
                return True
            self._send_json(200, tree)
            return True
        if path == "/debug/health":
            from znicz_tpu.core import health
            st = health.status()
            self._send_json(200 if st.get("ok", True) else 503, st)
            return True
        if path == "/debug/events":
            from urllib.parse import parse_qs
            qs = parse_qs(query)
            try:
                n = int(qs.get("n", ["256"])[0])
            except ValueError:
                self._send_json(400, {"error": "n must be an "
                                               "integer"})
                return True
            kind = qs.get("kind", [None])[0]
            rid = qs.get("rid", [None])[0]
            events = telemetry.journal_events()
            total = len(events)
            if kind:
                events = [e for e in events
                          if str(e.get("kind", "")).startswith(kind)]
            if rid:
                events = [e for e in events
                          if rid in (e.get("rid"),
                                     e.get("exemplar_rid"),
                                     e.get("request_id"))]
            matched = len(events)
            if n > 0:
                events = events[-n:]
            self._send_json(200,
                            {"events": events,
                             "total": total,
                             "matched": matched,
                             "dropped": telemetry.journal_dropped()})
            return True
        if path == "/debug/blackbox":
            from znicz_tpu.core import blackbox
            self._send_json(200, blackbox.stats())
            return True
        if path == "/debug/faults":
            from znicz_tpu.core import faults
            self._send_json(200, faults.status())
            return True
        if path == "/debug/profiler":
            from znicz_tpu.core import profiler
            self._send_json(200, profiler.snapshot())
            return True
        if path == "/debug/profile":
            from urllib.parse import parse_qs
            from znicz_tpu.core import profiler
            try:
                seconds = float(
                    parse_qs(query).get("seconds", ["3"])[0])
            except ValueError:
                self._send_json(400, {"error": "seconds must be a "
                                               "number"})
                return True
            if not _capture_guard.acquire(blocking=False):
                self._send_json(409, {
                    "error": "another debug capture (profile or "
                             "pyprof) is already running"})
                return True
            try:
                # blocks THIS handler thread for the capture window
                # (the server is threaded; other requests keep flowing)
                result = profiler.capture_trace(seconds)
            except RuntimeError as e:  # a capture is already running
                self._send_json(409, {"error": str(e)})
                return True
            except Exception as e:  # noqa: BLE001 - always answer HTTP
                self._send_json(500, {"error": repr(e)})
                return True
            finally:
                _capture_guard.release()
            self._send_json(200, result)
            return True
        if path == "/debug/pyprof":
            from urllib.parse import parse_qs
            from znicz_tpu.core import pyprof
            qs = parse_qs(query)
            try:
                seconds = float(qs.get("seconds", ["2"])[0])
            except ValueError:
                self._send_json(400, {"error": "seconds must be a "
                                               "number"})
                return True
            fmt = qs.get("format", ["json"])[0]
            if not pyprof.enabled():
                # the honest disabled answer — no capture, no guard
                self._send_json(200, {"enabled": False})
                return True
            if not _capture_guard.acquire(blocking=False):
                self._send_json(409, {
                    "error": "another debug capture (profile or "
                             "pyprof) is already running"})
                return True
            try:
                # blocks THIS handler thread for the capture window
                prof = pyprof.capture(seconds)
            except Exception as e:  # noqa: BLE001 - always answer HTTP
                self._send_json(500, {"error": repr(e)})
                return True
            finally:
                _capture_guard.release()
            if fmt == "collapsed":
                self._send(200, "text/plain; charset=utf-8",
                           (pyprof.collapsed(prof) + "\n").encode())
            elif fmt == "speedscope":
                self._send_json(200, pyprof.speedscope(prof))
            else:
                self._send_json(200, prof)
            return True
        return False


class _DeepBacklogHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with a PRODUCTION listen backlog.

    socketserver's default ``request_queue_size`` is 5: a burst of
    concurrent connections (a loadgen storm, a fleet router fanning
    requests at a replica) overflows the SYN backlog and the excess
    connects stall in kernel retransmit for 1–7 s — measured as a
    522 req/s sequential server collapsing to ~85 req/s under 32
    concurrent clients while its own request histogram read 1 ms.
    128 pending connections cost nothing and absorb any storm the
    handler threads can actually serve."""

    request_queue_size = 128


class HttpServerBase(Logger):
    """Daemon-thread stdlib HTTP server lifecycle.

    Subclasses implement :meth:`make_handler` returning a
    :class:`HandlerBase` subclass.  ``stop()`` is idempotent and
    thread-safe: any number of calls (including concurrent ones) shut
    the socket down exactly once and never raise on an already-stopped
    server.
    """

    def __init__(self, port=0, host="127.0.0.1", logger_name=None):
        super(HttpServerBase, self).__init__(
            logger_name=logger_name or type(self).__name__)
        self.host = host
        self.port = port
        self._httpd = None
        self._thread = None
        self._lifecycle_lock = locksmith.lock("status_server.lifecycle")

    def make_handler(self):
        """Return the request-handler class for this server."""
        raise NotImplementedError

    def start(self):
        with self._lifecycle_lock:
            if self._httpd is not None:
                return self
            self._httpd = _DeepBacklogHTTPServer(
                (self.host, self.port), self.make_handler())
            self.port = self._httpd.server_address[1]
            self._thread = threading.Thread(
                target=self._httpd.serve_forever,
                name="znicz:" + type(self).__name__.lower(),
                daemon=True)
            self._thread.start()
        # arm the metric time-series sampler and the continuous
        # Python profiler when their knobs are on — every HTTP
        # surface (status dashboard, serving front end) serves
        # /debug/timeseries and /debug/pyprof, so the server
        # lifecycle is the one natural arming point (each a no-op
        # single predicate when off)
        from znicz_tpu.core import timeseries
        from znicz_tpu.core import pyprof
        from znicz_tpu.core import blackbox
        timeseries.maybe_start()
        pyprof.maybe_start()
        blackbox.maybe_arm()
        self.info("%s on http://%s:%d/", type(self).__name__,
                  self.host, self.port)
        return self

    def stop(self):
        with self._lifecycle_lock:
            httpd, self._httpd = self._httpd, None
            thread, self._thread = self._thread, None
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        if thread is not None:
            thread.join(timeout=5)


class StatusServer(HttpServerBase):
    """Serves one workflow's live status over HTTP."""

    def __init__(self, workflow=None, port=0, host="127.0.0.1"):
        super(StatusServer, self).__init__(port=port, host=host,
                                           logger_name="StatusServer")
        self.workflow = workflow

    # -- status payload -----------------------------------------------------
    def status(self):
        """Status dict — TOLERANT of a workflow queried before (or
        mid-) ``initialize()``: units may lack ``run_count_``/timing
        attributes, the decision may be half-built.  Every section is
        gathered independently; a failing section lands in
        ``payload["errors"]`` instead of turning the whole endpoint
        into a 500 (the dashboard polls from the first second of a
        run)."""
        wf = self.workflow
        payload = {"workflow": None, "errors": {}}
        if wf is not None:
            payload["workflow"] = type(wf).__name__
            try:
                units = list(wf.units)
                payload["units"] = [getattr(u, "name", repr(u))
                                    for u in units]
                payload["run_counts"] = {
                    getattr(u, "name", repr(u)):
                        int(getattr(u, "run_count_", 0) or 0)
                    for u in units}
            except Exception as e:  # noqa: BLE001 - partial payload
                payload["errors"]["units"] = repr(e)
            try:
                decision = getattr(wf, "decision", None)
                if decision is not None:
                    for attr in ("epoch_number", "complete",
                                 "best_n_err_pt", "epoch_n_err_pt"):
                        v = getattr(decision, attr, None)
                        if v is not None:
                            payload[attr] = _plain(v)
            except Exception as e:  # noqa: BLE001 - partial payload
                payload["errors"]["decision"] = repr(e)
            try:
                if hasattr(wf, "unit_timings"):
                    payload["unit_timings"] = [
                        {"unit": u.name, "seconds": round(t, 4),
                         "runs": n}
                        for u, t, n in wf.unit_timings()]
            except Exception as e:  # noqa: BLE001 - partial payload
                payload["errors"]["unit_timings"] = repr(e)
        try:
            payload["plots"] = [os.path.basename(p)
                                for p in self._plot_files()]
        except Exception as e:  # noqa: BLE001 - partial payload
            payload["plots"] = []
            payload["errors"]["plots"] = repr(e)
        if telemetry.enabled():
            payload["telemetry"] = telemetry.snapshot()
        if not payload["errors"]:
            del payload["errors"]
        return payload

    @staticmethod
    def _plot_files():
        return sorted(glob.glob(os.path.join(
            root.common.dirs.cache, "plots", "*.png")))

    def make_handler(self):
        server = self

        class Handler(HandlerBase):
            owner = server

            def do_GET(self):
                if self.path in ("/", "/index.html"):
                    self._send(200, "text/html",
                               server._render_page().encode())
                elif self.path == "/status.json":
                    self._send_json(200, server.status())
                elif self.path == "/metrics":
                    self._send_metrics()
                elif self.path.startswith("/plots/"):
                    name = os.path.basename(self.path)
                    path = os.path.join(root.common.dirs.cache,
                                        "plots", name)
                    if os.path.exists(path):
                        with open(path, "rb") as f:
                            self._send(200, "image/png", f.read())
                    else:
                        self._send(404, "text/plain", b"not found")
                elif self._handle_debug():
                    pass
                else:
                    self._send(404, "text/plain", b"not found")

        return Handler

    def _render_page(self):
        st = self.status()
        plots = "".join('<img src="/plots/%s" width="400"/>' % p
                        for p in st.get("plots", ()))
        return _PAGE % {
            "name": st.get("workflow") or "(no workflow)",
            "status": json.dumps(st, indent=2, default=str),
            "plots": plots,
        }


def _plain(obj):
    import numpy
    if isinstance(obj, (list, tuple)):
        return [_plain(v) for v in obj]
    if isinstance(obj, numpy.ndarray):
        return obj.tolist()
    if isinstance(obj, numpy.generic):
        return obj.item()
    if hasattr(obj, "__bool__") and type(obj).__name__ == "Bool":
        return bool(obj)
    return obj
