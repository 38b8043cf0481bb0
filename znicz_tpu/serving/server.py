"""Production HTTP front end — single engine or a whole model registry.

Built on the shared stdlib HTTP plumbing of
:mod:`znicz_tpu.core.status_server` (``HttpServerBase``/``HandlerBase``
— one ``ThreadingHTTPServer`` on a daemon thread).  Every request
thread submits to the batcher and blocks on its future, so concurrent
HTTP clients coalesce without any extra machinery.  Two modes:

* **single-engine** (the PR 2 contract, unchanged): ``engine=`` + a
  :class:`~znicz_tpu.serving.batcher.MicroBatcher`;
* **registry** (``registry=``): a
  :class:`~znicz_tpu.serving.registry.ModelRegistry` of named engines
  behind a
  :class:`~znicz_tpu.serving.continuous.ContinuousBatcher` —
  per-model routing, hot add/remove/reload over HTTP, LRU residency.

Endpoints:

* ``POST /predict`` and ``POST /predict/<model>`` — JSON body
  ``{"inputs": [[...], ...], "model": optional, "priority":
  optional}`` (or a bare JSON array), or a raw ``.npy`` payload with
  ``Content-Type: application/octet-stream``.  The path segment wins
  over the body's ``model`` field; neither = the registry's default
  model.  The request's priority lane (``high``/``normal``/``low``,
  default normal — the ``X-Priority`` header wins over the body
  field; unknown spellings 400) picks the continuous batcher's
  admission/dispatch lane: low sheds first under overload
  (serving/continuous.py "Priority lanes").  Replies in kind:
  JSON ``{"outputs": ..., "argmax": ...,
  "model": ..., "model_version": ..., "request_id": ...}`` or raw
  ``.npy`` bytes.  Status codes: 400 malformed, 404 unknown model,
  413 body over ``root.common.serving.max_body_bytes`` (refused
  before reading), 429 queue full (backpressure), 503 not warmed
  up / draining / circuit open (the breaker 503 carries a
  ``Retry-After`` header — serving/breaker.py), 504 deadline expired.
  Every reply (success or error) echoes the request's tracing id in
  the ``X-Request-Id`` header; requests over
  ``root.common.serving.slow_request_ms`` are logged with their
  queue/assembly/device breakdown.
* ``GET /healthz`` — readiness probe.  Single-engine: 200 once warmup
  finished, 503 while compiling.  Registry: **per-model readiness** —
  the body carries ``{"models": {name: ready...}, "ready": all,
  "degraded": some-but-not-all}``; the status code is 503 only when NO
  model is ready (globally dead) — one broken model among healthy
  ones answers 200 + ``degraded`` so the balancer keeps routing the
  healthy traffic.  ``GET /healthz/<model>`` probes one model
  (200/503; 404 unknown).
* ``POST /models/<name>`` — admin: ``{"path": "..."}`` hot-ADDS a new
  model (loaded + warmed before it becomes routable) or hot-RELOADS
  an existing one (rollback scoped to that model).
  ``DELETE /models/<name>`` removes it; ``GET /models`` lists the
  registry (per-model stats + memory budget + compile-cache state).
* ``POST /reload`` — back-compat single-model hot swap
  (``{"path": "...", "model": optional}``).
* ``GET /metrics`` — the telemetry registry in Prometheus text format
  (per-model series carry ``model_<name>`` labels).
* ``GET /statusz`` (and ``/``) — JSON serving stats (registry + queue
  + compile-cache + slo blocks).
* ``GET /slo`` — the server-side SLO plane
  (:mod:`znicz_tpu.serving.slo`, behind
  ``root.common.serving.slo_enabled``): per-model good/total from
  request admission, fast/slow-window burn rates, error budget
  remaining — the feed the autoscaler consumes.
* ``GET /admitted/<rid>`` — the batcher's admitted-request-id oracle
  (was this rid ever admitted to a dispatch lane?): the fleet
  router's retry-safety check (serving/router.py) — a resend of an
  admitted rid on a peer would risk a duplicate dispatch.
* ``GET /debug/health`` / ``GET /debug/events`` /
  ``GET /debug/profile?seconds=N`` / ``GET /debug/profiler`` /
  ``GET /debug/timeseries`` / ``GET /debug/trace/<rid>`` — the
  health monitor status, the flight-recorder journal, on-demand
  ``jax.profiler`` capture, the performance-introspection report,
  the in-process metric time-series rings and the sampled
  per-request span trees (shared ``HandlerBase`` endpoints — same
  contract as the training status server).

CLI (the ``serve`` entry point of ``python -m znicz_tpu``)::

    python -m znicz_tpu serve wine_current.0.pickle --port 8899
    python -m znicz_tpu serve --latest wine          # newest snapshot
    python -m znicz_tpu serve model.zip --max-batch 32 --max-delay-ms 2
    # multi-model registry + continuous batching + persistent cache:
    python -m znicz_tpu serve wine=wine.pickle mnist=mnist.zip
    # low-precision serving: engine-wide --dtype, or per model via
    # NAME=PATH@DTYPE (docs/serving.md "Precision modes"):
    python -m znicz_tpu serve model.zip --dtype int8
    python -m znicz_tpu serve a=m.zip@int8 b=m.zip   # same model, 2 dtypes
    # multi-replica fleet: N replica subprocesses sharing one compile
    # cache behind the front-end router (serving/router.py), with the
    # SLO-burn autoscaler (serving/autoscaler.py) optionally armed:
    python -m znicz_tpu serve wine=wine.zip --fleet 2 --autoscale \
        --config common.serving.slo_enabled=True
"""

import argparse
import io
import json
import math
import os
import time
import uuid

import numpy

from znicz_tpu.core.config import root
from znicz_tpu.core.status_server import (BodyTooLargeError, HandlerBase,
                                          HttpServerBase)
from znicz_tpu.core import (backends, blackbox, compile_cache, pyprof,
                            telemetry)
from znicz_tpu.serving import reqtrace, slo, wire
from znicz_tpu.serving.batcher import (BatcherStoppedError, MicroBatcher,
                                       QueueFullError,
                                       RequestTimeoutError)
from znicz_tpu.serving.breaker import CircuitOpenError
from znicz_tpu.serving.continuous import normalize_priority
from znicz_tpu.serving.engine import InferenceEngine
from znicz_tpu.serving.registry import ModelRegistry, UnknownModelError
from znicz_tpu.serving.release import (LocalTarget,
                                       ReleaseConflictError,
                                       ReleaseController,
                                       generation_label)


class _WireExchange(object):
    """One binary-relay REQUEST frame presented as the handler surface
    :meth:`ServingServer._predict` speaks — the wire path runs the
    SAME /predict state machine as HTTP (SLO accounting, priority
    lanes, admitted-rid oracle, breaker, drain, tracing all ride
    along), only the codec differs.  The pre-parsed zero-copy array
    rides in ``wire_inputs``; ``t_recv`` back-dates admission to the
    frame's completion on the event loop; ``pre_spans`` carries the
    ``frame_decode`` span for sampled rids.  Replies go out as
    RESPONSE frames (200) or typed ERROR frames (everything else) the
    moment the state machine answers."""

    __slots__ = ("request", "meta", "wire_inputs", "t_recv",
                 "pre_spans", "headers", "status", "t_sent")

    def __init__(self, request, arr, decode_span):
        meta = request.meta
        self.request = request
        self.meta = meta
        self.wire_inputs = arr
        self.t_recv = request.t_recv
        self.pre_spans = (("frame_decode",) + decode_span,)
        self.status = None
        #: stamped just BEFORE the reply frame is written — the
        #: tracing wall must close no later than the router's frame
        #: read (its replica_wait end), and a post-write stamp can
        #: lag by a whole GIL switch interval while this worker
        #: waits to run again
        self.t_sent = None
        headers = {"Content-Type": "application/octet-stream"}
        rid = meta.get("rid")
        if rid:
            headers["X-Request-Id"] = str(rid)
        priority = meta.get("priority")
        if priority:
            headers["X-Priority"] = str(priority)
        sampled = meta.get("sampled")
        if sampled is not None:
            headers["X-Trace-Sampled"] = str(sampled)
        self.headers = headers

    # the handler surface _predict/_predict_inner touches
    def _read_body(self):
        return b""

    def _drain_body(self):
        pass

    def _send_json(self, code, obj, headers=None):
        headers = headers or {}
        self.status = int(code)
        if int(code) == 200:
            # a JSON-reply 200 (the router relays it verbatim to a
            # JSON client — the SAME serializer the HTTP surface
            # uses, so the two codecs answer bit-identical bodies)
            self._reply_frame(code, "application/json",
                              json.dumps(obj).encode(), headers)
            return
        self.t_sent = time.monotonic()
        self.request.reply(wire.error_frame(
            code, obj, rid=headers.get("X-Request-Id"),
            retry_after=headers.get("Retry-After")))

    def _send(self, code, ctype, body, headers=None):
        self.status = int(code)
        self._reply_frame(code, ctype, body, headers or {})

    def _reply_frame(self, code, ctype, body, headers):
        meta = {"status": int(code), "ctype": ctype}
        for header, key in (("X-Request-Id", "rid"),
                            ("X-Serving-Ms", "serving_ms"),
                            ("X-Serving-Generation", "generation")):
            if headers.get(header) is not None:
                meta[key] = headers[header]
        self.t_sent = time.monotonic()
        self.request.reply(
            wire.pack_frame(wire.KIND_RESPONSE, meta, body))


class ServingServer(HttpServerBase):
    """HTTP front end over an engine + micro-batcher, or a registry +
    continuous batcher.

    When ``batcher`` is None one is created (and owned: ``stop()``
    stops it too) with the ``root.common.serving`` defaults — a
    :class:`MicroBatcher` for ``engine=``, a
    :class:`~znicz_tpu.serving.continuous.ContinuousBatcher` for
    ``registry=``.
    """

    def __init__(self, engine=None, batcher=None, port=0, host=None,
                 registry=None):
        cfg = root.common.serving
        super(ServingServer, self).__init__(
            port=port, host=host or cfg.get("host", "127.0.0.1"),
            logger_name="ServingServer")
        if (engine is None) == (registry is None):
            raise ValueError(
                "pass exactly one of engine= (single-model) or "
                "registry= (multi-model)")
        self.engine = engine
        self.registry = registry
        self._owns_batcher = batcher is None
        if batcher is None:
            if registry is not None:
                from znicz_tpu.serving.continuous import \
                    ContinuousBatcher
                batcher = ContinuousBatcher(registry).start()
            else:
                batcher = MicroBatcher(engine).start()
        self.batcher = batcher
        #: whether the batcher routes by model name (continuous
        #: batcher / any batcher with a model kwarg)
        self._routed_batcher = registry is not None
        #: graceful-drain latch: once set, /predict answers 503
        #: ("draining") and /healthz reports not-ready so load
        #: balancers stop routing here while in-flight work flushes
        self._draining = False
        self._drained = False
        #: server-side SLO plane (serving/slo.py): per-model
        #: good/total accounting from request admission, burn rates,
        #: error budgets — fed by _predict behind the slo.enabled()
        #: gate, served at GET /slo and the /statusz slo block
        self.slo = slo.SloTracker()
        #: progressive-delivery controller (serving/release.py):
        #: canary split + shadow mirror over this registry, operated
        #: at POST/GET/DELETE /release/<model>.  Registry mode only;
        #: its background threads arm on the first release.
        self.release = None
        if registry is not None:
            self.release = ReleaseController(
                LocalTarget(registry, self.slo))
        #: the binary framed-relay listener (serving/wire.py) — armed
        #: by start() when root.common.serving.wire.enabled (the
        #: default transport a fleet router speaks to this replica)
        self._wire = None

    def start(self):
        # the relay listener arms BEFORE the HTTP surface opens: the
        # first healthz 200 a fleet router sees must already carry
        # wire_port (wait_ready stashes it from that very payload —
        # arming after would race the router's discovery)
        if root.common.serving.get("wire", {}).get("enabled", True):
            self._wire = wire.WireListener(
                self._wire_group, host=self.host,
                name="replica").start()
        super(ServingServer, self).start()
        return self

    @property
    def wire_port(self):
        return self._wire.port if self._wire is not None else None

    def _wire_group(self, group):
        """Handler for the framed-relay listener: the requests a
        readable pass drained together decode their ``.npy`` bodies
        in ONE sweep (coalesced frame decode — queued same-lane
        requests pay the codec as a group, the way their dispatch
        coalesces downstream), then each runs the SAME /predict state
        machine the HTTP surface runs.  The first request continues
        on this worker; the rest fan out to the listener's pool."""
        exchanges = []
        for req in group:
            t0 = time.monotonic()
            try:
                arr = wire.parse_npy(req.body)
            except ValueError as e:
                req.reply(wire.error_frame(
                    400, {"error": repr(e),
                          "request_id": req.meta.get("rid")},
                    rid=req.meta.get("rid")))
                continue
            exchanges.append(_WireExchange(req, arr,
                                           (t0, time.monotonic())))
        for ex in exchanges[1:]:
            self._wire.submit(self._wire_one, ex)
        if exchanges:
            self._wire_one(exchanges[0])

    def _wire_one(self, ex):
        try:
            self._predict(ex, model=ex.meta.get("model"))
        except Exception as e:  # noqa: BLE001 - always answer a frame
            self.warning("wire predict %s failed: %r",
                         ex.meta.get("rid"), e)
            if ex.status is None:
                ex.request.reply(wire.error_frame(
                    500, {"error": repr(e),
                          "request_id": ex.meta.get("rid")},
                    rid=ex.meta.get("rid")))

    def stop(self):
        if self._wire is not None:
            self._wire.stop()
            self._wire = None
        super(ServingServer, self).stop()
        if self.release is not None:
            self.release.stop()
        if self._owns_batcher:
            self.batcher.stop()

    def drain(self):
        """Graceful shutdown (the SIGTERM path): stop admitting new
        predictions, flush everything already queued through the
        batcher, then stop the HTTP server.  Idempotent."""
        if self._drained:
            return
        self._drained = True
        self._draining = True
        telemetry.record_event("serving.drain")
        self.info("draining: refusing new work, flushing %d queued "
                  "rows", self.batcher.queued_rows)
        # flush=True serves the queue to completion before the worker
        # exits — in-flight clients get their answers, not RSTs.  An
        # externally-owned (possibly shared) batcher is left running,
        # the same ownership contract stop() honors.
        if self._owns_batcher:
            self.batcher.stop(flush=True)
        self.stop()

    def _engine_for(self, model=None):
        """The engine serving ``model`` — registry resolution (raises
        :class:`UnknownModelError` → 404) or the single engine (a
        model name then only resolves if there is nothing to route
        by)."""
        if self.registry is not None:
            return self.registry.engine(model)
        if model is not None:
            raise UnknownModelError(model, ())
        return self.engine

    def statusz(self):
        if self.registry is not None:
            payload = {"registry": self.registry.stats(),
                       "ready": self.registry.ready}
        else:
            payload = dict(self.engine.stats())
            payload["compile_cache"] = compile_cache.stats()
        payload["queued_rows"] = self.batcher.queued_rows
        if self._wire is not None:
            payload["wire"] = {"port": self._wire.port}
        if slo.enabled():
            payload["slo"] = self.slo.status()
        if telemetry.enabled():
            serving = telemetry.serving_summary()
            if serving is not None:
                payload["serving"] = serving
        return payload

    def healthz(self):
        """(status code, payload) for /healthz — the per-model truth.

        Registry mode: 503 only when NO model is ready (globally
        dead); a mixed registry answers 200 with ``degraded: true``
        and the per-model map, so one broken model neither reads as
        global health nor pulls the healthy models out of rotation.
        """
        if self.registry is None:
            stats = dict(self.engine.stats(),
                         wire_port=self.wire_port)
            if self._draining:
                stats.update(ready=False, draining=True)
            return (200 if stats["ready"] else 503), stats
        readiness = self.registry.readiness()
        any_ready = any(readiness.values())
        all_ready = bool(readiness) and all(readiness.values())
        payload = {
            "ready": all_ready and not self._draining,
            "degraded": any_ready and not all_ready,
            "models": readiness,
            "default": self.registry.default,
            # the probe path stays cheap: the memory block alone (no
            # per-model stats, ONE compile-cache directory walk)
            "memory": self.registry.memory_stats(),
            "compile_cache": compile_cache.stats(),
            # where this replica's binary framed relay listens (None
            # = wire disabled) — the fleet router discovers the
            # relay port here when it enters a replica into rotation
            "wire_port": self.wire_port,
        }
        if self._draining:
            payload["draining"] = True
            return 503, payload
        return (200 if any_ready else 503), payload

    # -- request plumbing ---------------------------------------------------
    def _parse_predict(self, handler):
        """(array-or-None, timeout_ms, raw_reply, model, priority)
        from the request body; the array stays unparsed (None) until
        the model is known — it must parse straight into THAT model's
        dtype.  The ``X-Priority`` header wins over the body's
        ``priority`` field (the router forwards the header)."""
        arr = getattr(handler, "wire_inputs", None)
        if arr is not None:
            # binary relay (_WireExchange): the body already parsed
            # ZERO-COPY over the frame's memoryview on the listener —
            # request metadata rides in the frame, not in headers.
            # reply="json" asks for the JSON 200 schema (a router
            # relaying to a JSON client); the default is raw .npy.
            meta = handler.meta
            model = meta.get("model")
            if model is not None and not isinstance(model, str):
                raise ValueError('"model" must be a string')
            return (arr, meta.get("timeout_ms"),
                    meta.get("reply") != "json", model,
                    normalize_priority(meta.get("priority")))
        body = handler._read_body()
        ctype = (handler.headers.get("Content-Type") or "").split(";")[0]
        priority = (handler.headers.get("X-Priority") or "").strip() \
            or None
        if ctype == "application/octet-stream" or \
                body[:6] == b"\x93NUMPY":
            # same zero-copy ingest as the wire path: the array
            # materializes straight over the request body's buffer
            # (wire.parse_npy), no io.BytesIO/numpy.load copy
            return (wire.parse_npy(body), None, True, None,
                    normalize_priority(priority))
        doc = json.loads(body.decode() or "null")
        if isinstance(doc, dict):
            inputs = doc.get("inputs")
            timeout_ms = doc.get("timeout_ms")
            model = doc.get("model")
            if priority is None:
                priority = doc.get("priority")
        else:
            inputs, timeout_ms, model = doc, None, None
        if inputs is None:
            raise ValueError('body needs {"inputs": [[...], ...]} '
                             "(or a raw .npy payload)")
        if model is not None and not isinstance(model, str):
            raise ValueError('"model" must be a string')
        # validate HERE (the 400 path): an unknown priority must fail
        # before the request costs a parse or an admission attempt
        priority = normalize_priority(priority)
        return inputs, timeout_ms, False, model, priority

    @staticmethod
    def _request_id(handler):
        """The request's tracing id: the client's ``X-Request-Id``
        (truncated — it rides through logs and span attrs) or a fresh
        one.  Echoed on EVERY reply, success or error, so a client can
        quote it when reporting a failure."""
        rid = (handler.headers.get("X-Request-Id") or "").strip()
        return rid[:64] if rid else uuid.uuid4().hex[:12]

    def _predict(self, handler, model=None):
        """One /predict request: the inner handler answers it; this
        wrapper measures the SLO clock from ADMISSION (queue time,
        batching, dispatch — everything the client experiences), opens
        the sampled trace tree, and feeds the per-model SLO tracker
        with the final status code (serving/slo.py accounting rules:
        429/503/504/500 and over-SLO 200s burn the budget; 400-class
        client faults do not)."""
        rid = self._request_id(handler)
        # a wire exchange back-dates admission to the frame's
        # completion on the event loop — the decode + dispatch queue
        # time counts against the request, as a client experiences it
        t_admit = getattr(handler, "t_recv", None) or time.monotonic()
        if telemetry.enabled():
            telemetry.counter(telemetry.labeled(
                "serving.codec_requests",
                codec=("binary"
                       if getattr(handler, "wire_inputs", None)
                       is not None else "http"))).inc()
        sampled_hdr = (handler.headers.get("X-Trace-Sampled")
                       or "").strip()
        if sampled_hdr == "1":
            # a fleet router upstream sampled this rid — trace it
            # regardless of our own cursor (force=True leaves the
            # cursor untouched, so direct-traffic sampling cadence
            # is unaffected); both processes then hold the same rid
            # and GET /debug/trace/<rid> on the router can stitch
            traced = reqtrace.enabled() and reqtrace.begin(
                rid, now=t_admit, force=True)
        elif sampled_hdr == "0":
            # the router decided NOT to sample — honoring it keeps
            # the two rings aligned rid-for-rid
            traced = False
        else:
            traced = reqtrace.enabled() and reqtrace.begin(
                rid, now=t_admit)
        if traced:
            # relay pre-spans (frame_decode): stamped on the wire
            # listener before this state machine ran — NESTED inside
            # the admission window, so the partition stays exact
            for kind, t0, t1 in getattr(handler, "pre_spans", ()):
                reqtrace.add_span(rid, kind, t0, t1)
        code, slo_model = self._predict_inner(handler, rid, model,
                                              t_admit, traced)
        if traced:
            reqtrace.finish(rid, model=slo_model,
                            now=getattr(handler, "t_sent", None))
        if slo.enabled():
            self.slo.record(slo_model, code,
                            (time.monotonic() - t_admit) * 1e3,
                            rid=rid)

    def _predict_inner(self, handler, rid, model, t_admit, traced):
        """The /predict state machine; returns ``(status_code,
        model_name)`` for the SLO/trace wrapper after the reply went
        out."""
        echo = {"X-Request-Id": rid}
        if self._draining:
            # graceful shutdown: honest fast 503 so the balancer
            # re-routes; Retry-After hints "a replacement is coming"
            handler._drain_body()
            handler._send_json(
                503, {"error": "server draining", "ready": False,
                      "request_id": rid},
                headers=dict(echo, **{"Retry-After": "1"}))
            return 503, model
        try:
            inputs, timeout_ms, raw, body_model, priority = \
                self._parse_predict(handler)
        except BodyTooLargeError as e:
            # the unread oversized body already forced Connection:
            # close in _read_body — answer honestly and drop the socket
            handler._send_json(413, {"error": str(e),
                                     "request_id": rid}, headers=echo)
            return 413, model
        except Exception as e:  # noqa: BLE001 - client error
            handler._send_json(400, {"error": repr(e),
                                     "request_id": rid}, headers=echo)
            return 400, model
        # the URL path segment wins over the body's "model" field
        model = model if model is not None else body_model
        # canary split (serving/release.py): an active release may
        # rewrite the routed name to its candidate — deterministic
        # per rid, so a retry lands on the same generation, and the
        # candidate's SLO/metrics/lanes attribute to its own name
        routed = model
        ctl = self.release
        if ctl is not None and ctl.active():
            cand = ctl.route(model, rid)
            if cand is not None:
                routed = cand
        slo_model = routed
        try:
            try:
                engine = self._engine_for(routed)
            except UnknownModelError:
                if routed is model:
                    raise
                # the candidate vanished between split and resolution
                # (a rollback just removed it): fall back to the live
                # generation — clients are always answered
                routed = slo_model = model
                engine = self._engine_for(model)
            if slo_model is None and self.registry is not None:
                # the default model carries its real name in the SLO
                # accounting — budgets are per model, not per route
                slo_model = self.registry.default
        except UnknownModelError as e:
            handler._send_json(404, {"error": str(e),
                                     "request_id": rid}, headers=echo)
            return 404, slo_model
        if not engine.ready:
            handler._send_json(503, {"error": "model warming up",
                                     "ready": False, "model": model,
                                     "request_id": rid}, headers=echo)
            return 503, slo_model
        try:
            # parse straight into the routed model's compute dtype — a
            # float64 intermediate would cost a second full-batch copy
            x = numpy.asarray(inputs,
                              dtype=engine.dtype or numpy.float32)
        except Exception as e:  # noqa: BLE001 - client error
            handler._send_json(400, {"error": repr(e),
                                     "request_id": rid}, headers=echo)
            return 400, slo_model
        try:
            if traced:
                # admission span: HTTP receipt -> batcher submission
                # (parse + routing + readiness checks)
                reqtrace.add_span(rid, "admission", t_admit,
                                  time.monotonic())
            if self._routed_batcher:
                y = self.batcher.predict(x, model=routed,
                                         timeout_ms=timeout_ms,
                                         request_id=rid,
                                         priority=priority)
            else:
                # the micro-batcher has one FIFO lane: priority is
                # validated (a typo still 400s) but not enforced —
                # priority lanes are a continuous-batcher feature
                y = self.batcher.predict(x, timeout_ms=timeout_ms,
                                         request_id=rid)
        except UnknownModelError as e:
            # the model was removed between resolution and dispatch
            handler._send_json(404, {"error": str(e),
                                     "request_id": rid}, headers=echo)
            return 404, slo_model
        except BatcherStoppedError:
            # the submit raced drain()/stop(): same honest 503 the
            # pre-admission _draining check produces
            handler._send_json(
                503, {"error": "server draining", "ready": False,
                      "request_id": rid},
                headers=dict(echo, **{"Retry-After": "1"}))
            return 503, slo_model
        except QueueFullError as e:
            handler._send_json(429, {"error": str(e),
                                     "request_id": rid}, headers=echo)
            return 429, slo_model
        except RequestTimeoutError as e:
            handler._send_json(504, {"error": str(e),
                                     "request_id": rid}, headers=echo)
            return 504, slo_model
        except CircuitOpenError as e:
            # circuit breaking: the bucket's dispatch path is known-bad
            # — reject fast with the cooldown as the Retry-After hint
            # (no device work was attempted)
            handler._send_json(
                503, {"error": str(e), "request_id": rid,
                      "retry_after_seconds": round(e.retry_after, 3)},
                headers=dict(echo, **{
                    "Retry-After":
                        str(max(1, int(math.ceil(e.retry_after))))}))
            return 503, slo_model
        except (ValueError, TypeError) as e:
            # shape/dtype mismatches surface at trace time as
            # ValueError/TypeError — the client's fault, not ours
            handler._send_json(400, {"error": str(e),
                                     "request_id": rid}, headers=echo)
            return 400, slo_model
        except Exception as e:  # noqa: BLE001 - always answer HTTP
            self.warning("predict %s failed: %r", rid, e)
            handler._send_json(500, {"error": repr(e),
                                     "request_id": rid}, headers=echo)
            return 500, slo_model
        t_reply = time.monotonic()
        # replica-reported serving time: admission -> reply start, in
        # the X-Serving-Ms header.  A fleet router subtracts it from
        # its own wall clock per proxied 200 — the router_overhead_ms
        # surface in the fleet /slo and /statusz (what remains is the
        # hop: relay framing, sockets, and this reply's serialization)
        ok_headers = dict(echo, **{
            "X-Serving-Ms": "%.3f" % ((t_reply - t_admit) * 1e3),
            # which generation answered: a canary candidate pins its
            # encoded generation, the live model its engine version —
            # loadgen asserts canary split percentages from this
            "X-Serving-Generation": generation_label(slo_model or "",
                                                     engine.version)})
        if raw:
            buf = io.BytesIO()
            numpy.save(buf, numpy.ascontiguousarray(y))
            handler._send(200, "application/octet-stream",
                          buf.getvalue(), headers=ok_headers)
        else:
            payload = {"outputs": y.tolist(),
                       "model_version": engine.version,
                       "request_id": rid}
            if model is not None:
                payload["model"] = model
            if y.ndim == 2:
                payload["argmax"] = [int(i) for i in y.argmax(axis=1)]
            handler._send_json(200, payload, headers=ok_headers)
        if traced:
            # reply span: future resolved -> response bytes written
            # (a wire exchange stamped the write itself — closing at
            # "now" would bill this worker's re-schedule latency to
            # the reply and overflow the router's replica_wait window)
            reqtrace.add_span(rid, "reply", t_reply,
                              getattr(handler, "t_sent", None)
                              or time.monotonic())
        if ctl is not None and routed is model and ctl.active():
            # shadow mirror (serving/release.py): the client's reply
            # is already on the wire — the candidate compare happens
            # on the controller's worker thread, never here
            ctl.mirror(slo_model, rid, x, y)
        return 200, slo_model

    def _reload(self, handler, model=None):
        try:
            doc = json.loads(handler._read_body().decode() or "{}")
            path = doc["path"]
            model = model if model is not None else doc.get("model")
        except BodyTooLargeError as e:
            handler._send_json(413, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001 - client error
            handler._send_json(400, {"error": 'body needs {"path": '
                                              '"..."} (%r)' % e})
            return
        try:
            if self.registry is not None:
                version = self.registry.reload(model, path)
                engine = self.registry.engine(model)
            else:
                engine = self._engine_for(model)
                version = engine.load(path)
        except UnknownModelError as e:
            handler._send_json(404, {"error": str(e)})
            return
        except ReleaseConflictError as e:
            # the model is mid-release: promote/rollback belong to
            # the controller alone — a loud 409, never a silent race
            handler._send_json(409, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001 - bad model file
            # a failed (re)load rolled back scoped to this one model —
            # the registry keeps serving every other model untouched
            handler._send_json(400, {"error": repr(e)})
            return
        payload = {"model_version": version, "source": path,
                   "ready": engine.ready}
        if model is not None:
            payload["model"] = model
        handler._send_json(200, payload)

    # -- registry admin -----------------------------------------------------
    def _admin_add(self, handler, name):
        """POST /models/<name>: hot add (new name) or hot reload
        (existing name) — the model only becomes routable after load +
        warmup succeed."""
        if self.registry is None:
            handler._drain_body()  # keep-alive hygiene
            handler._send_json(400, {
                "error": "this server hosts a single engine — start "
                         "it with a ModelRegistry for admin routing"})
            return
        try:
            doc = json.loads(handler._read_body().decode() or "{}")
            path = doc["path"]
        except BodyTooLargeError as e:
            handler._send_json(413, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001 - client error
            handler._send_json(400, {"error": 'body needs {"path": '
                                              '"..."} (%r)' % e})
            return
        kwargs = {}
        for key in ("max_batch", "sample_shape"):
            if doc.get(key) is not None:
                kwargs[key] = doc[key]
        try:
            version = self.registry.add(name, path, **kwargs)
        except ReleaseConflictError as e:
            handler._send_json(409, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001 - bad model file/name
            handler._send_json(400, {"error": repr(e)})
            return
        handler._send_json(200, {
            "model": name, "model_version": version, "source": path,
            "models": self.registry.names()})

    # -- progressive delivery (serving/release.py) --------------------------
    def _release_post(self, handler, name):
        """POST /release/<model>: ``{"path": ..., "policy": {...}}``
        deploys the candidate generation and starts the shadow ->
        canary -> promote state machine."""
        if self.release is None:
            handler._drain_body()
            handler._send_json(400, {
                "error": "releases need a model registry — start the "
                         "server with NAME=PATH model specs"})
            return
        try:
            doc = json.loads(handler._read_body().decode() or "{}")
            path = doc["path"]
        except BodyTooLargeError as e:
            handler._send_json(413, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001 - client error
            handler._send_json(400, {"error": 'body needs {"path": '
                                              '"..."} (%r)' % e})
            return
        try:
            payload = self.release.start().start_release(
                name, path, policy=doc.get("policy"))
        except ReleaseConflictError as e:
            handler._send_json(409, {"error": str(e)})
            return
        except UnknownModelError as e:
            handler._send_json(404, {"error": str(e)})
            return
        except ValueError as e:
            handler._send_json(400, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001 - bad candidate file
            handler._send_json(400, {"error": repr(e)})
            return
        handler._send_json(200, payload)

    def _release_get(self, handler, name=None):
        if self.release is None:
            handler._send_json(200, {"active": {}, "recent": {}})
            return
        try:
            handler._send_json(200, self.release.status(name))
        except KeyError as e:
            handler._send_json(404, {"error": str(e)})

    def _release_delete(self, handler, name):
        if self.release is None:
            handler._send_json(404, {"error": "no release plane "
                                              "(single-engine mode)"})
            return
        try:
            handler._send_json(200, self.release.abort(name))
        except KeyError as e:
            handler._send_json(404, {"error": str(e)})

    def _admin_remove(self, handler, name):
        if self.registry is None:
            handler._send_json(400, {
                "error": "this server hosts a single engine"})
            return
        try:
            self.registry.remove(name)
        except UnknownModelError as e:
            handler._send_json(404, {"error": str(e)})
            return
        except ReleaseConflictError as e:
            handler._send_json(409, {"error": str(e)})
            return
        handler._send_json(200, {"removed": name,
                                 "models": self.registry.names()})

    def make_handler(self):
        server = self

        class Handler(HandlerBase):
            owner = server

            def do_GET(self):
                path = self.path.partition("?")[0]
                if path == "/healthz":
                    code, payload = server.healthz()
                    self._send_json(code, payload)
                elif path.startswith("/healthz/"):
                    name = path[len("/healthz/"):]
                    try:
                        # observation only: a health probe must never
                        # restore an evicted model (registry.peek) —
                        # only real traffic pays the lazy re-warm
                        engine = (server.registry.peek(name)
                                  if server.registry is not None
                                  else server._engine_for(name))
                    except UnknownModelError as e:
                        self._send_json(404, {"error": str(e)})
                        return
                    ready = engine.ready and not server._draining
                    self._send_json(200 if ready else 503,
                                    engine.stats())
                elif path == "/models":
                    if server.registry is not None:
                        self._send_json(200, server.registry.stats())
                    else:
                        self._send_json(200, {
                            "models": {"default":
                                       server.engine.stats()},
                            "default": "default"})
                elif path.startswith("/admitted/"):
                    # the fleet router's idempotency oracle: was this
                    # rid ever admitted to the batcher's dispatch
                    # lanes?  admitted = a resend on a peer risks a
                    # duplicate dispatch; the coverage fields say how
                    # far back a MISS counts as proof (serving/
                    # router.py retry safety rule)
                    rid = path[len("/admitted/"):]
                    probe = getattr(server.batcher,
                                    "admitted_status", None)
                    payload = {"rid": rid, "tracked":
                               probe is not None}
                    if probe is not None:
                        payload.update(probe(rid))
                    else:
                        payload["admitted"] = False
                    self._send_json(200, payload)
                elif path == "/metrics":
                    self._send_metrics()
                elif path == "/slo":
                    # the error-budget feed (serving/slo.py) — the
                    # payload the ROADMAP item-2 autoscaler consumes
                    self._send_json(200, server.slo.status())
                elif path == "/release":
                    server._release_get(self)
                elif path.startswith("/release/"):
                    server._release_get(
                        self, path[len("/release/"):])
                elif path in ("/", "/statusz"):
                    self._send_json(200, server.statusz())
                elif self._handle_debug():
                    pass
                else:
                    self._send_json(404, {"error": "not found"})

            def do_POST(self):
                path = self.path.partition("?")[0]
                if path == "/predict":
                    server._predict(self)
                elif path.startswith("/predict/"):
                    server._predict(self, model=path[len("/predict/"):])
                elif path == "/reload":
                    server._reload(self)
                elif path.startswith("/models/"):
                    server._admin_add(self, path[len("/models/"):])
                elif path.startswith("/release/"):
                    server._release_post(self,
                                         path[len("/release/"):])
                else:
                    self._drain_body()  # keep-alive hygiene
                    self._send_json(404, {"error": "not found"})

            def do_DELETE(self):
                path = self.path.partition("?")[0]
                if path.startswith("/models/"):
                    self._drain_body()
                    server._admin_remove(self, path[len("/models/"):])
                elif path.startswith("/release/"):
                    self._drain_body()
                    server._release_delete(
                        self, path[len("/release/"):])
                else:
                    self._drain_body()
                    self._send_json(404, {"error": "not found"})

        return Handler


def sys_argv_tail():
    """The serve subcommand's raw argv (``python -m znicz_tpu serve
    ...`` → everything after "serve") — the list the fleet mode strips
    its router-only flags from."""
    import sys
    argv = sys.argv[1:]
    if argv and argv[0] == "serve":
        argv = argv[1:]
    return argv


#: router-only serve flags, stripped from the replica argv
#: (flag -> takes a value)
_ROUTER_ONLY_FLAGS = {"--fleet": True, "--port": True, "--host": True,
                      "--autoscale": False}


def _replica_argv(raw_argv):
    """The argv every fleet replica runs: the operator's serve args
    minus the router-only flags (each replica binds its own port 0;
    model specs, knob overrides and batching flags pass through)."""
    out, i = [], 0
    while i < len(raw_argv):
        tok = raw_argv[i]
        flag = tok.split("=", 1)[0]
        if flag in _ROUTER_ONLY_FLAGS:
            i += 1
            if _ROUTER_ONLY_FLAGS[flag] and "=" not in tok and \
                    i < len(raw_argv):
                i += 1  # the flag's value
            continue
        out.append(tok)
        i += 1
    return out


def _fleet_main(args, raw_argv):
    """The ``serve --fleet N`` path: spawn the replica fleet behind
    the front-end router (serving/router.py), optionally armed with
    the autoscaler, and run the same SIGTERM-drain loop single-process
    serving uses."""
    from znicz_tpu.serving.autoscaler import Autoscaler
    from znicz_tpu.serving.router import FleetRouter

    telemetry.enable()  # the router's own series + journal
    # adopt the pyprof thread-name registry for the process's main
    # thread — it blocks in the drain loop, and an unnamed MainThread
    # would land every one of its samples in the "unnamed" bucket
    pyprof.name_current_thread("serve-main")
    cfg = root.common.serving
    replica_argv = _replica_argv(raw_argv)
    if "--compile-cache" not in replica_argv and \
            not compile_cache.env_dir():
        # the fleet's whole cold-start story: every replica after the
        # first deserializes the shared cache instead of compiling
        # (under JAX_COMPILATION_CACHE_DIR the replicas inherit the
        # variable and enable themselves — nothing to pass)
        replica_argv += ["--compile-cache",
                         compile_cache.configured_dir()]
    if blackbox.enabled():
        # the fleet shares ONE blackbox dir: arm the router under the
        # "router" role, pin the RESOLVED dir into every replica (a
        # relative --config dir or a changed dirs.cache must not
        # shear the fleet apart), and hand replicas their role so
        # `obs --postmortem replica` means what it says
        blackbox.maybe_arm("router")
        bb_dir = os.path.abspath(blackbox.configured_dir())
        replica_argv += [
            "--config", "common.telemetry.blackbox.dir=%s" % bb_dir,
            "--config", "common.telemetry.blackbox.role=replica"]
    router = FleetRouter(
        replica_argv, replicas=args.fleet,
        port=(args.port if args.port is not None
              else cfg.get("port", 8899)),
        host=args.host).start()
    if args.autoscale:
        router.autoscaler = Autoscaler(router).start()
    print("fleet of %d replica%s behind http://%s:%d/  (predict: "  # noqa
          "POST /predict[/<model>]; fleet health: GET /healthz; "
          "aggregated: GET /metrics, GET /slo%s)"
          % (args.fleet, "" if args.fleet == 1 else "s",
             router.host, router.port,
             "; autoscaler armed" if args.autoscale else ""))
    import signal
    import threading
    term = threading.Event()

    def _on_term(signum, frame):
        term.set()

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:  # non-main thread (embedding) — CTRL-C only
        pass
    try:
        while not term.wait(1.0):
            if router._thread is None or \
                    not router._thread.is_alive():
                break
    except KeyboardInterrupt:
        print("shutting down fleet")  # noqa: T201 - CLI feedback
    finally:
        if term.is_set():
            print("SIGTERM: draining the fleet")  # noqa: T201
        router.drain()
    return 0


def main(argv=None):
    """The ``python -m znicz_tpu serve`` entry point."""
    cfg = root.common.serving
    parser = argparse.ArgumentParser(
        prog="python -m znicz_tpu serve",
        description="Serve trained models (snapshot pickles or "
                    "deployment package zips) over HTTP.  One bare "
                    "PATH serves a single engine with dynamic "
                    "micro-batching; one or more NAME=PATH specs "
                    "serve a multi-model registry with continuous "
                    "batching and per-model /predict/<name> routing.")
    parser.add_argument("model", nargs="+",
                        help="snapshot/.zip path, NAME=PATH spec(s) "
                             "for a registry — or, with --latest, a "
                             "snapshot prefix (e.g. 'wine')")
    parser.add_argument("--latest", action="store_true",
                        help="treat MODEL as a snapshotter prefix and "
                             "serve the newest matching snapshot")
    parser.add_argument("--directory", default=None,
                        help="snapshot directory for --latest "
                             "(default: root.common.dirs.snapshots)")
    parser.add_argument("--host", default=None)
    parser.add_argument("--port", type=int, default=None)
    parser.add_argument("--max-batch", type=int, default=None)
    parser.add_argument("--max-delay-ms", type=float, default=None)
    parser.add_argument("--queue-limit", type=int, default=None)
    parser.add_argument("--timeout-ms", type=float, default=None)
    parser.add_argument("--max-inflight", type=int, default=None,
                        help="concurrent dispatch slots (registry "
                             "mode's continuous batcher)")
    parser.add_argument("--memory-budget-bytes", type=int,
                        default=None,
                        help="registry LRU device-memory budget "
                             "(0 = unlimited)")
    parser.add_argument("--sample-shape", default=None,
                        help="per-sample input shape override, e.g. "
                             "'28,28,1' (spatial packages without a "
                             "recorded shape)")
    parser.add_argument("--no-warmup", action="store_true",
                        help="serve immediately; first request per "
                             "bucket pays the compile")
    parser.add_argument("--dtype", default=None,
                        choices=("f32", "bf16", "int8"),
                        help="serving precision mode (default: the "
                             "source's recorded manifest, else f32); "
                             "per-model override via NAME=PATH@DTYPE "
                             "specs in registry mode")
    parser.add_argument("--compile-cache", nargs="?", const="",
                        default=None, metavar="DIR",
                        help="wire the persistent XLA compilation "
                             "cache (default dir: "
                             "root.common.compile_cache.dir) so a "
                             "restarted replica cold-starts with "
                             "zero fresh compiles; "
                             "JAX_COMPILATION_CACHE_DIR, when set, "
                             "decides the directory instead")
    parser.add_argument("--config", action="append", default=[],
                        metavar="KEY=VALUE",
                        help="config-root override (e.g. common."
                             "serving.slo_enabled=True) — applied "
                             "here AND forwarded to every --fleet "
                             "replica")
    parser.add_argument("--fleet", type=int, default=None,
                        metavar="N",
                        help="serve a fleet of N replica "
                             "subprocesses sharing one persistent "
                             "compile cache behind the front-end "
                             "router (serving/router.py): least-"
                             "outstanding balancing, health-aware "
                             "rotation, aggregated /metrics //slo/"
                             "/healthz//models")
    parser.add_argument("--autoscale", action="store_true",
                        help="fleet mode: arm the SLO-burn-driven "
                             "autoscaler (serving/autoscaler.py; "
                             "root.common.serving.fleet.* knobs)")
    args = parser.parse_args(argv)
    from znicz_tpu.core.config import apply_override
    for assignment in args.config:
        apply_override(assignment)
    if args.autoscale and args.fleet is None:
        parser.error("--autoscale needs --fleet N")
    if args.fleet is not None:
        if args.fleet < 1:
            parser.error("--fleet needs at least 1 replica")
        return _fleet_main(args, list(argv) if argv is not None
                           else sys_argv_tail())

    telemetry.enable()  # /metrics should work out of the box
    pyprof.name_current_thread("serve-main")  # sampler attribution
    # arm the durable blackbox BEFORE the engines build, so startup
    # milestones land on disk too (a fleet replica arrives here with
    # role=replica pinned into its config by _fleet_main; a plain
    # serve arms as "serve"; one predicate when the knob is off)
    blackbox.maybe_arm("serve")
    if args.compile_cache is not None:
        compile_cache.enable(args.compile_cache or None)
    else:
        compile_cache.maybe_enable()  # honor the env / config gate
    print("serve: %s" % backends.describe())  # noqa: T201 - CLI banner
    specs = [(m.split("=", 1) if "=" in m else (None, m))
             for m in args.model]
    named = [s for s in specs if s[0] is not None]
    if named and len(named) != len(specs):
        parser.error("mix of NAME=PATH and bare PATH model specs — "
                     "use one style")
    if named and args.latest:
        parser.error("--latest applies to single-model serving only")
    if not named and len(specs) > 1:
        parser.error("several models need NAME=PATH specs")
    sample_shape = None
    if args.sample_shape:
        sample_shape = tuple(int(d) for d in
                             args.sample_shape.split(","))
    def _split_dtype(path):
        """Optional per-model precision suffix: NAME=PATH@DTYPE.
        Only a suffix that parses as a known serving dtype splits —
        a literal '@' elsewhere in a path stays part of the path."""
        from znicz_tpu.serving import quant
        if "@" in path:
            base, _, suffix = path.rpartition("@")
            try:
                return base, quant.normalize_dtype(suffix)
            except ValueError:
                pass
        return path, None

    registry = engine = None
    if named:
        registry = ModelRegistry(
            memory_budget_bytes=args.memory_budget_bytes,
            max_batch=args.max_batch, sample_shape=sample_shape,
            warmup=not args.no_warmup, dtype=args.dtype)
        for name, path in named:
            path, dtype = _split_dtype(path)
            registry.add(name, path,
                         **({"dtype": dtype} if dtype else {}))
        from znicz_tpu.serving.continuous import ContinuousBatcher
        batcher = ContinuousBatcher(
            registry, max_inflight=args.max_inflight,
            queue_limit=args.queue_limit,
            timeout_ms=args.timeout_ms).start()
        label = ", ".join(sorted(registry.names()))
    else:
        model, spec_dtype = _split_dtype(specs[0][1])
        if args.latest:
            from znicz_tpu.launcher import newest_snapshot
            directory = args.directory or root.common.dirs.snapshots
            prefix = model
            model = newest_snapshot(directory, prefix)
            if model is None:
                raise SystemExit("no snapshot with prefix %r under %s"
                                 % (prefix, directory))
        engine = InferenceEngine(model, max_batch=args.max_batch,
                                 sample_shape=sample_shape,
                                 warmup=not args.no_warmup,
                                 dtype=spec_dtype or args.dtype)
        batcher = MicroBatcher(engine, max_delay_ms=args.max_delay_ms,
                               queue_limit=args.queue_limit,
                               timeout_ms=args.timeout_ms).start()
        label = str(model)
    server = ServingServer(engine, batcher, registry=registry,
                           port=(args.port if args.port is not None
                                 else cfg.get("port", 8899)),
                           host=args.host).start()
    print("serving %s on http://%s:%d/  (predict: POST /predict"  # noqa
          "[/<model>]; health: GET /healthz; metrics: GET /metrics)"
          % (label, server.host, server.port))
    # graceful drain on SIGTERM (the orchestrator's shutdown signal):
    # stop admitting, flush in-flight requests, then exit 0 — no
    # client sees a dropped connection on a routine pod rotation
    import signal
    import threading
    term = threading.Event()

    def _on_term(signum, frame):
        term.set()

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:  # non-main thread (embedding) — CTRL-C only
        pass
    try:
        while not term.wait(1.0):
            if server._thread is None or not server._thread.is_alive():
                break
    except KeyboardInterrupt:
        print("shutting down")  # noqa: T201 - CLI feedback
    finally:
        if term.is_set():
            print("SIGTERM: draining in-flight requests")  # noqa: T201
        server.drain()
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
