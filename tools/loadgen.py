"""Open-loop load generator for the serving control plane.

Closed-loop clients (the serving smoke) can never observe overload:
each client waits for its answer before sending the next request, so
the offered rate gracefully degrades to whatever the server sustains
and p99 looks flattering.
Production traffic does not wait.  This generator is **open-loop**: a
seeded Poisson process schedules arrivals ahead of time and fires them
at their scheduled instants whether or not earlier requests completed —
when the server falls behind, latency (measured from the *scheduled*
arrival, client-side queueing included) and the error mix show it
honestly.

* **Seeded** (``--seed``): the arrival schedule, the model mix and the
  batch-size mix are all drawn from one ``numpy.random.RandomState`` —
  two runs with the same seed offer byte-identical traffic, so CI can
  assert an SLO on a fixed workload.
* **Mixed models**: each arrival routes to one of the registry's
  models (weighted draw), exercising cross-model fairness and the
  per-model metric labels.
* **Mixed batch shapes**: request sizes draw log-uniformly over
  ``1..max_batch``, sweeping the engine's whole bucket ladder.
* **SLO report**: requests per second offered vs achieved, latency
  p50/p90/p95/p99/p999, and **goodput** — completed-OK responses
  within ``slo_ms`` (``root.common.serving.slo_ms``) per second.
  Under overload goodput is the number that matters: a server
  answering everything late has throughput but no goodput.
* **Priority mix** (``--priority-mix high:1,normal:2,low:1``): each
  arrival draws a priority lane from a weighted, SEPARATELY seeded
  stream (the arrival/model/rows tape is untouched by adding a mix),
  rides the ``X-Priority`` header, and the report grows per-priority
  goodput/latency/shed blocks — ``--assert-goodput-pct high:90``
  gates one lane's goodput specifically (the overload contract:
  low sheds first, high holds).
* **Per-generation attribution**: every HTTP reply's
  ``X-Serving-Generation`` header is retained per request, and the
  report grows a ``per_generation`` block (requests, share of
  traffic, goodput, latency tail per ``gen_<N>`` label) — during a
  canary release the ``share_pct`` IS the observed split, so a
  release run asserts the ladder percentage client-side.
* **Relative overload gate** (``--assert-goodput-gap high:low:10``):
  gates the high-vs-low goodput GAP instead of an absolute number —
  on a slow machine every absolute goodput sags together while the
  priority contract (low sheds first) still holds.
* **Binary bodies** (``--npy``): raw ``.npy`` payloads over
  keep-alive connections for capacity/fleet-scaling measurements —
  microseconds of codec per request instead of the JSON
  milliseconds.
* **Exact quantiles, per model × per bucket**: every completed
  request's latency is RETAINED and percentiles come from
  :func:`znicz_tpu.serving.latency.exact_percentile` (sorted order
  statistics + linear interpolation — never a bucketed
  approximation).  Besides the global block, the report breaks
  latency down per model AND per shape bucket — the bucket the
  request's OWN rows pad into (its nominal bucket; a coalescing
  batcher may ride some requests through a larger bucket's
  executable, so read the breakdown as "tail by request size", the
  client-side view) — so a tail regression on one request class of
  one model is visible in the artifact, not averaged away.

Two runners share the report:

* :func:`run` drives any ``submit(model, x, timeout_ms) -> Future``
  (in process: straight into a
  :class:`~znicz_tpu.serving.continuous.ContinuousBatcher`);
* the CLI drives a live server over HTTP, discovering the model fleet
  and sample shapes from ``GET /models``::

      python tools/loadgen.py http://127.0.0.1:8899 \\
          --rate 200 --duration 10 --seed 7 --assert-goodput-pct 90

Exit codes (CLI): 0 = ran (and SLO assertion held, when given),
1 = ``--assert-goodput-pct`` violated, 2 = usage error.
"""

import argparse
import json
import math
import os
import sys
import threading
import time

import numpy

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class ModelSpec(object):
    """One routable target: ``name`` (None = the server's default
    route), per-sample input shape, the largest request to draw, its
    share of the traffic mix, and the model's shape-bucket ladder
    (defaults to the engine's power-of-two ladder; ``discover_models``
    adopts the server's recorded ladder) — the per-bucket latency
    breakdown attributes each request to the bucket its rows pad
    into."""

    __slots__ = ("name", "sample_shape", "max_batch", "weight",
                 "buckets")

    def __init__(self, name, sample_shape, max_batch=8, weight=1.0,
                 buckets=None):
        self.name = name
        self.sample_shape = tuple(int(d) for d in sample_shape)
        self.max_batch = max(1, int(max_batch))
        self.weight = float(weight)
        if buckets:
            self.buckets = tuple(sorted(int(b) for b in buckets))
        else:
            # the engine's own default ladder rule — never a local
            # re-implementation that could drift (lazy import keeps
            # plain CLI startup light)
            from znicz_tpu.serving.engine import default_buckets
            self.buckets = default_buckets(self.max_batch)

    def bucket_for(self, rows):
        """The NOMINAL bucket for a ``rows``-row request — the
        smallest ladder entry >= rows, i.e. what the request pads
        into when dispatched alone (a coalescing batcher may ride it
        through a larger bucket).  Over-ladder rows clamp to the top
        bucket — the engine would have 400'd those, and they carry an
        error status anyway."""
        for b in self.buckets:
            if rows <= b:
                return b
        return self.buckets[-1]


def parse_priority_mix(spec):
    """``"high:1,normal:2,low:1"`` → ``[(name, weight), ...]``
    (sorted by name — a stable draw order so the tape is
    seed-deterministic regardless of spelling order).  Unknown lane
    names fail LOUDLY against the batcher's own vocabulary."""
    from znicz_tpu.serving.continuous import normalize_priority
    out = {}
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        name, sep, weight = part.partition(":")
        if not sep:
            raise ValueError(
                "priority mix wants PRIO:WEIGHT entries, got %r"
                % part)
        out[normalize_priority(name)] = float(weight)
    if not out:
        raise ValueError("empty priority mix %r" % spec)
    return sorted(out.items())


def make_plan(rate_rps, duration_s, seed, models, priority_mix=None):
    """The deterministic traffic tape: ``[(t, model_index, rows,
    priority)]`` sorted by arrival time ``t`` (seconds from start).
    Poisson arrivals at ``rate_rps``; the model is a weighted draw;
    ``rows`` is log-uniform over ``1..max_batch`` (every bucket sees
    traffic, small requests dominate — the realistic shape mix);
    ``priority`` is a weighted draw from ``priority_mix``
    (``[(name, weight), ...]`` or a ``"high:1,low:2"`` spec string) on
    a SEPARATE seeded stream — same-seed runs offer byte-identical
    traffic, and a run without a mix draws the exact tape it always
    drew (priority None = the server's "normal" default)."""
    rng = numpy.random.RandomState(int(seed))
    weights = numpy.array([m.weight for m in models], dtype=float)
    weights = weights / weights.sum()
    prio_names = prio_weights = prio_rng = None
    if priority_mix:
        if isinstance(priority_mix, str):
            priority_mix = parse_priority_mix(priority_mix)
        prio_names = [p for p, _ in priority_mix]
        prio_weights = numpy.array(
            [w for _, w in priority_mix], dtype=float)
        prio_weights = prio_weights / prio_weights.sum()
        # a dedicated stream: adding a mix must not perturb the
        # arrival/model/rows tape a seed has always produced
        prio_rng = numpy.random.RandomState(int(seed) + 2)
    plan = []
    t = float(rng.exponential(1.0 / rate_rps))
    while t < duration_s:
        mi = int(rng.choice(len(models), p=weights))
        # one octave past the ladder top, then clamp: the clamp mass
        # is what gives max_batch (the largest bucket) its share
        hi = math.log2(models[mi].max_batch) if \
            models[mi].max_batch > 1 else 0.0
        rows = int(2 ** rng.uniform(0.0, hi + 1.0))
        rows = max(1, min(rows, models[mi].max_batch))
        prio = None
        if prio_rng is not None:
            prio = prio_names[int(prio_rng.choice(
                len(prio_names), p=prio_weights))]
        plan.append((t, mi, rows, prio))
        t += float(rng.exponential(1.0 / rate_rps))
    return plan


def make_inputs(models, seed):
    """One ``(max_batch,) + sample_shape`` array per model (seeded);
    a request of ``rows`` rows is a leading slice — the generator
    measures the serving stack, not ``numpy.random``."""
    rng = numpy.random.RandomState(int(seed) + 1)
    return [rng.uniform(-1.0, 1.0, (m.max_batch,) + m.sample_shape)
            .astype(numpy.float32) for m in models]


def _percentile(values, q):
    """Exact quantile from the retained samples — ONE formula for the
    whole latency stack (znicz_tpu/serving/latency.py; unit-tested
    there down to n=1 and ties).  Imported lazily so the module stays
    importable before znicz_tpu's heavier imports are wanted."""
    from znicz_tpu.serving.latency import exact_percentile
    return exact_percentile(values, q)


def _pct_block(lat_s):
    """The per-series latency block: exact p50/p90/p95/p99/p999/max in
    ms over retained OK latencies (all None when the series is
    empty)."""
    # one real sort per series; exact_percentile's own sorted() is
    # O(n) on already-sorted input
    lat_s = sorted(lat_s)
    out = {}
    for label, q in (("p50", 50), ("p90", 90), ("p95", 95),
                     ("p99", 99), ("p999", 99.9)):
        v = _percentile(lat_s, q)
        out[label] = round(v * 1e3, 3) if v is not None else None
    out["max"] = round(max(lat_s) * 1e3, 3) if lat_s else None
    return out


def _classify(exc):
    """HTTP-status classification of a failure — in-process exceptions
    map exactly as the ServingServer's error handlers map them; HTTP
    errors carry their status verbatim."""
    from znicz_tpu.serving.batcher import (BatcherStoppedError,
                                           QueueFullError,
                                           RequestTimeoutError)
    from znicz_tpu.serving.breaker import CircuitOpenError
    from znicz_tpu.serving.registry import UnknownModelError
    if isinstance(exc, _HttpStatusError):
        return exc.code
    if isinstance(exc, QueueFullError):
        return 429
    if isinstance(exc, RequestTimeoutError):
        return 504
    if isinstance(exc, (CircuitOpenError, BatcherStoppedError)):
        return 503
    if isinstance(exc, UnknownModelError):
        return 404
    if isinstance(exc, (ValueError, TypeError)):
        return 400
    return 500


def run(plan, models, submit, slo_ms, duration_s, seed,
        timeout_ms=None, settle_s=30.0):
    """Fire ``plan`` open-loop through ``submit(model_name, x,
    timeout_ms) -> concurrent.futures.Future`` and return the SLO
    report.  Latency is measured from each request's SCHEDULED arrival
    — a dispatcher running late (server backpressure propagating into
    the client) counts against the request, exactly as a real user
    would experience it."""
    inputs = make_inputs(models, seed)
    lock = threading.Lock()
    # (model_index, rows, latency_s, status, priority, generation)
    records = []
    outstanding = threading.Semaphore(0)
    n_async = 0

    def _finish(rec_base, prio, scheduled_wall, future):
        done = time.monotonic()
        exc = future.exception()
        status = 200 if exc is None else _classify(exc)
        # HTTP submits resolve to the reply's X-Serving-Generation
        # label (which generation answered — the canary-split
        # evidence); in-process submits resolve to the output array,
        # which carries no attribution
        res = future.result() if exc is None else None
        gen = res if isinstance(res, str) else None
        with lock:
            records.append(rec_base + (done - scheduled_wall, status,
                                       prio, gen))
        outstanding.release()

    t0 = time.monotonic()
    behind_max = 0.0
    for t, mi, rows, prio in plan:
        scheduled_wall = t0 + t
        now = time.monotonic()
        if scheduled_wall > now:
            time.sleep(scheduled_wall - now)
        else:
            behind_max = max(behind_max, now - scheduled_wall)
        x = inputs[mi][:rows]
        try:
            future = submit(models[mi].name, x, timeout_ms, prio)
        except Exception as e:  # noqa: BLE001 - synchronous rejection
            with lock:
                records.append(
                    (mi, rows, time.monotonic() - scheduled_wall,
                     _classify(e), prio, None))
            continue
        n_async += 1
        future.add_done_callback(
            lambda f, rec=(mi, rows), p=prio, sw=scheduled_wall:
            _finish(rec, p, sw, f))
    deadline = time.monotonic() + settle_s
    for _ in range(n_async):
        if not outstanding.acquire(timeout=max(
                0.0, deadline - time.monotonic())):
            break
    wall_s = time.monotonic() - t0
    return report(records, len(plan), duration_s, slo_ms, seed,
                  models, behind_max, wall_s=wall_s)


def report(records, scheduled, duration_s, slo_ms, seed, models,
           dispatch_behind_max_s=0.0, wall_s=None):
    """Aggregate per-request records into the SLO report dict.

    ``achieved_rps``/``goodput_rps`` divide by the OFFERED window
    ``duration_s`` (the open-loop convention); ``wall_rps`` divides by
    the wall time to the LAST completion — under overload a backlog
    drains after the offered window closes, and wall_rps is the honest
    sustained-throughput number (use it to calibrate capacity)."""
    slo_s = float(slo_ms) / 1e3
    ok_lat = [r[2] for r in records if r[3] == 200]
    good = sum(1 for r in records if r[3] == 200 and r[2] <= slo_s)
    errors = {}
    for r in records:
        if r[3] != 200:
            errors[str(r[3])] = errors.get(str(r[3]), 0) + 1
    per_model = {}
    for i, m in enumerate(models):
        mine = [r for r in records if r[0] == i]
        m_ok = [r[2] for r in mine if r[3] == 200]
        m_pct = _pct_block(m_ok)
        per_bucket = {}
        for r in mine:
            if r[3] != 200:
                continue
            per_bucket.setdefault(m.bucket_for(r[1]), []).append(r[2])
        block = {
            "requests": len(mine),
            "ok": len(m_ok),
            "rows": int(sum(r[1] for r in mine)),
            # flat keys kept for existing consumers; the full exact-
            # quantile block sits under "latency_ms"
            "p50_ms": m_pct["p50"],
            "p99_ms": m_pct["p99"],
            "latency_ms": m_pct,
            # per NOMINAL shape bucket (what the request's own rows
            # pad into; coalescing may dispatch some through a larger
            # bucket — this is the client-side "tail by request size"
            # view): a p99 regression on one request class can no
            # longer hide in the model-wide aggregate
            "per_bucket": {
                str(b): dict(_pct_block(lats), count=len(lats))
                for b, lats in sorted(per_bucket.items())},
        }
        per_model[m.name or "<default>"] = block
    # per-priority breakdown (the overload contract's evidence):
    # goodput and the latency tail per lane — under overload the low
    # lane should show 429s where the high lane shows green goodput
    per_priority = {}
    prios = sorted({r[4] for r in records if len(r) > 4 and r[4]})
    for prio in prios:
        mine = [r for r in records if r[4] == prio]
        p_ok = [r[2] for r in mine if r[3] == 200]
        p_good = sum(1 for r in mine
                     if r[3] == 200 and r[2] <= slo_s)
        p_errors = {}
        for r in mine:
            if r[3] != 200:
                p_errors[str(r[3])] = p_errors.get(str(r[3]), 0) + 1
        per_priority[prio] = {
            "requests": len(mine),
            "ok": len(p_ok),
            "errors": p_errors,
            "shed_429": p_errors.get("429", 0),
            "goodput_pct": (round(100.0 * p_good / len(mine), 2)
                            if mine else None),
            "latency_ms": _pct_block(p_ok),
        }
    # per-generation breakdown (the release plane's client-side
    # evidence): each HTTP reply names the generation that answered
    # it in X-Serving-Generation — during a canary the share_pct here
    # IS the observed split, so a release run can assert the ladder
    # percentage from outside the fleet
    per_generation = {}
    gens = sorted({r[5] for r in records if len(r) > 5 and r[5]})
    for gen in gens:
        mine = [r for r in records if len(r) > 5 and r[5] == gen]
        g_ok = [r[2] for r in mine if r[3] == 200]
        g_good = sum(1 for r in mine
                     if r[3] == 200 and r[2] <= slo_s)
        per_generation[gen] = {
            "requests": len(mine),
            "ok": len(g_ok),
            "share_pct": (round(100.0 * len(mine) / len(records), 2)
                          if records else None),
            "goodput_pct": (round(100.0 * g_good / len(mine), 2)
                            if mine else None),
            "latency_ms": _pct_block(g_ok),
        }
    out = {
        "seed": int(seed),
        "duration_s": round(float(duration_s), 3),
        "slo_ms": float(slo_ms),
        "scheduled": int(scheduled),
        "completed": len(records),
        "ok": len(ok_lat),
        "errors": errors,
        "offered_rps": round(scheduled / duration_s, 2),
        "achieved_rps": round(len(ok_lat) / duration_s, 2),
        "wall_s": (round(wall_s, 3) if wall_s else None),
        "wall_rps": (round(len(ok_lat) / wall_s, 2)
                     if wall_s else None),
        "goodput_rps": round(good / duration_s, 2),
        "goodput_pct": (round(100.0 * good / scheduled, 2)
                        if scheduled else None),
        "latency_ms": _pct_block(ok_lat),
        "rows_ok": int(sum(r[1] for r in records if r[3] == 200)),
        "dispatch_behind_max_ms": round(
            dispatch_behind_max_s * 1e3, 3),
        "per_model": per_model,
        "per_priority": per_priority,
        "per_generation": per_generation,
    }
    return out


# -- HTTP mode -------------------------------------------------------------
class DaemonPool(object):
    """Minimal fixed-width thread pool over DAEMON threads returning
    Futures.  concurrent.futures' ThreadPoolExecutor joins its
    non-daemon workers at interpreter exit — a wedged server would
    hang the CLI for the full HTTP timeout after the report printed.
    Daemon workers let the process exit the moment main() returns."""

    def __init__(self, max_workers):
        import queue
        self._q = queue.Queue()
        for i in range(int(max_workers)):
            t = threading.Thread(target=self._worker,
                                 name="znicz:loadgen-%d" % i,
                                 daemon=True)
            t.start()

    def _worker(self):
        while True:
            fn, args, future = self._q.get()
            if not future.set_running_or_notify_cancel():
                continue
            try:
                future.set_result(fn(*args))
            except BaseException as e:  # noqa: BLE001 - to the future
                future.set_exception(e)

    def submit(self, fn, *args):
        from concurrent.futures import Future
        future = Future()
        self._q.put((fn, args, future))
        return future


def discover_models(base_url, timeout=10.0):
    """ModelSpecs from a live server's ``GET /models`` (the registry
    stats payload).  A single-engine server reports one pseudo-model
    named ``default`` — route it without a path segment."""
    import urllib.request
    with urllib.request.urlopen(base_url.rstrip("/") + "/models",
                                timeout=timeout) as resp:
        doc = json.loads(resp.read())
    specs = []
    for name in sorted(doc.get("models", {})):
        stats = doc["models"][name]
        shape = stats.get("sample_shape")
        if not shape:
            continue
        buckets = stats.get("buckets") or [8]
        specs.append(ModelSpec(
            None if name == "default" else name, shape,
            max_batch=int(buckets[-1]), buckets=buckets))
    if not specs:
        raise SystemExit(
            "loadgen: %s/models reports no servable model with a "
            "recorded sample shape" % base_url)
    return specs


def http_submit(base_url, pool, binary=False, rid_prefix=None):
    """A ``submit(model, x, timeout_ms) -> Future`` over HTTP: each
    request runs on the pool (open-loop up to the pool width; a full
    pool shows up as scheduled-latency, never as a lost arrival).

    ``rid_prefix`` stamps every request with a deterministic
    ``X-Request-Id`` (``<prefix>-<seq>``) so a caller can look
    sampled requests up afterwards at ``GET /debug/trace/<rid>`` —
    the fleet-tracing smoke drives loadgen traffic and then reads
    the stitched trees back by these ids.

    ``binary=True`` posts raw ``.npy`` bodies instead of JSON (the
    server's ``application/octet-stream`` path) over per-worker
    KEEP-ALIVE connections, and caches the encoded bytes per
    ``(model, rows)`` — the generator's inputs are fixed seeded
    slices, so the cache is exact.  JSON over one-shot connections
    costs ~3 ms of client GIL to encode, ~1.6 ms of server GIL to
    decode and a TCP handshake per 784-wide request; the binary path
    costs microseconds — at fleet scale the codec tax becomes the
    measurement, not the fleet.  (A binary body carries no
    per-request ``timeout_ms``; a request failing on a stale parked
    connection retries once on a fresh one.)"""
    import http.client
    import io
    import itertools
    import urllib.error
    import urllib.parse
    import urllib.request

    npy_cache = {}
    parsed = urllib.parse.urlsplit(base_url)
    local = threading.local()
    rid_seq = itertools.count()  # count() is atomic under the GIL

    def _body(model, x, timeout_ms):
        if not binary:
            doc = {"inputs": x.tolist()}
            if timeout_ms:
                doc["timeout_ms"] = timeout_ms
            return json.dumps(doc).encode(), "application/json"
        key = (model, x.shape[0])
        body = npy_cache.get(key)
        if body is None:
            buf = io.BytesIO()
            numpy.save(buf, numpy.ascontiguousarray(x))
            body = npy_cache[key] = buf.getvalue()
        return body, "application/octet-stream"

    def _do_binary(path, body, headers, wait):
        for attempt in (0, 1):
            conn = getattr(local, "conn", None)
            if conn is None:
                conn = http.client.HTTPConnection(
                    parsed.hostname, parsed.port, timeout=wait)
                local.conn = conn
            try:
                conn.request("POST", path, body=body,
                             headers=headers)
                resp = conn.getresponse()
                resp.read()
            except (OSError, http.client.HTTPException):
                conn.close()
                local.conn = None
                if attempt:
                    raise
                continue  # stale parked connection: one fresh retry
            if resp.will_close:
                conn.close()
                local.conn = None
            if resp.status >= 400:
                raise _HttpStatusError(resp.status)
            return resp.getheader("X-Serving-Generation") or True

    def _do(model, x, timeout_ms, priority):
        path = "/predict" if model is None else "/predict/" + model
        body, ctype = _body(model, x, timeout_ms)
        headers = {"Content-Type": ctype}
        if rid_prefix:
            headers["X-Request-Id"] = "%s-%06d" % (rid_prefix,
                                                   next(rid_seq))
        if priority is not None:
            headers["X-Priority"] = priority
        wait = (timeout_ms / 1e3 + 65.0) if timeout_ms else 120.0
        if binary:
            return _do_binary(path, body, headers, wait)
        req = urllib.request.Request(
            base_url.rstrip("/") + path, body, headers)
        try:
            with urllib.request.urlopen(req, timeout=wait) as resp:
                json.loads(resp.read())
                gen = resp.headers.get("X-Serving-Generation")
        except urllib.error.HTTPError as e:
            e.read()
            raise _HttpStatusError(e.code)
        return gen or True

    def submit(model, x, timeout_ms, priority=None):
        return pool.submit(_do, model, x, timeout_ms, priority)

    return submit


def discover_wire_port(base_url, timeout=10.0):
    """The server's framed-relay port from ``GET /healthz`` (both the
    replica and the fleet router publish ``wire_port`` there).  A
    not-ready 503 still carries the payload."""
    import urllib.error
    import urllib.request
    url = base_url.rstrip("/") + "/healthz"
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            doc = json.loads(resp.read())
    except urllib.error.HTTPError as e:
        doc = json.loads(e.read())
    port = doc.get("wire_port")
    if not port:
        raise SystemExit(
            "loadgen: %s reports no wire_port — the server runs "
            "with common.serving.wire.enabled=False; use --wire "
            "http" % url)
    return int(port)


def wire_submit(base_url, pool, rid_prefix=None):
    """A ``submit(model, x, timeout_ms) -> Future`` over the binary
    framed relay (serving/wire.py) — the client half of ``--wire
    binary``.  The traffic is seed-identical to the HTTP modes (same
    plan, same seeded input slices); only the transport differs:
    one persistent connection per pool worker, a length-prefixed
    REQUEST frame per request (rid/model/priority/timeout_ms in the
    frame meta, the raw ``.npy`` body cached per ``(model, rows)``
    exactly as ``--npy`` caches it), and the RESPONSE frame's
    ``generation`` meta resolving the future — the same per-
    generation attribution the HTTP header carries.  A request
    failing on a stale parked connection retries once on a fresh
    one; a typed ERROR frame raises its carried status verbatim."""
    import io
    import itertools
    import urllib.parse

    from znicz_tpu.serving import wire

    parsed = urllib.parse.urlsplit(base_url)
    port = discover_wire_port(base_url)
    npy_cache = {}
    local = threading.local()
    rid_seq = itertools.count()  # count() is atomic under the GIL

    def _body(model, x):
        key = (model, x.shape[0])
        body = npy_cache.get(key)
        if body is None:
            buf = io.BytesIO()
            numpy.save(buf, numpy.ascontiguousarray(x))
            body = npy_cache[key] = buf.getvalue()
        return body

    def _do(model, x, timeout_ms, priority):
        body = _body(model, x)
        meta = {"rid": "%s-%06d" % (rid_prefix or "wire",
                                    next(rid_seq))}
        if model is not None:
            meta["model"] = model
        if priority is not None:
            meta["priority"] = priority
        if timeout_ms:
            meta["timeout_ms"] = timeout_ms
        wait = (timeout_ms / 1e3 + 65.0) if timeout_ms else 120.0
        for attempt in (0, 1):
            conn = getattr(local, "conn", None)
            if conn is None:
                conn = wire.WireConn(parsed.hostname, port,
                                     timeout=wait)
                local.conn = conn
            try:
                kind, rmeta, _rbody = conn.request(meta, body,
                                                   timeout=wait)
            except (wire.WireProtocolError, OSError):
                conn.close()
                local.conn = None
                if attempt:
                    raise
                continue  # stale parked connection: one fresh retry
            status = int(rmeta.get("status", 500))
            if status >= 400:
                raise _HttpStatusError(status)
            return rmeta.get("generation") or True

    def submit(model, x, timeout_ms, priority=None):
        return pool.submit(_do, model, x, timeout_ms, priority)

    return submit


class _HttpStatusError(Exception):
    def __init__(self, code):
        self.code = int(code)
        super(_HttpStatusError, self).__init__("HTTP %d" % code)


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="python tools/loadgen.py",
        description="Open-loop (Poisson) load generator against a "
                    "znicz_tpu serving server; prints the SLO report "
                    "as one JSON line.")
    parser.add_argument("url", help="server base url, e.g. "
                                    "http://127.0.0.1:8899")
    parser.add_argument("--rate", type=float, default=100.0,
                        help="offered arrivals per second")
    parser.add_argument("--duration", type=float, default=10.0)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--slo-ms", type=float, default=None,
                        help="goodput latency bound (default: "
                             "root.common.serving.slo_ms)")
    parser.add_argument("--timeout-ms", type=float, default=None,
                        help="per-request deadline forwarded to the "
                             "server")
    parser.add_argument("--models", default=None,
                        help="comma list restricting the discovered "
                             "fleet (default: every servable model)")
    parser.add_argument("--concurrency", type=int, default=64,
                        help="HTTP worker pool width (the open-loop "
                             "outstanding-request bound)")
    parser.add_argument("--npy", action="store_true",
                        help="post raw .npy bodies instead of JSON "
                             "(microseconds of codec per request "
                             "instead of milliseconds — use for "
                             "capacity/fleet-scaling measurements; "
                             "note: per-request timeout_ms does not "
                             "ride in a binary body)")
    parser.add_argument("--wire", default="http",
                        choices=("http", "binary"),
                        help="client transport: 'http' (default; "
                             "--npy picks the body codec) or "
                             "'binary' — the persistent framed "
                             "relay (serving/wire.py) the router "
                             "itself speaks to replicas, with rid/"
                             "model/priority/timeout_ms in the "
                             "frame meta.  Same seed = byte-"
                             "identical traffic either way; only "
                             "the transport differs")
    parser.add_argument("--priority-mix", default=None,
                        metavar="PRIO:W[,PRIO:W...]",
                        help="weighted per-request priority draw "
                             "(e.g. 'high:1,normal:2,low:1'), on a "
                             "dedicated seeded stream — the report "
                             "then carries per-priority goodput/"
                             "latency blocks")
    parser.add_argument("--assert-goodput-pct", default=None,
                        metavar="PCT|PRIO:PCT[,...]",
                        help="exit 1 when goodput%% lands below this "
                             "(the CI SLO assertion).  A bare number "
                             "gates the GLOBAL goodput; a PRIO:PCT "
                             "entry gates that priority lane's "
                             "goodput (e.g. 'high:90' holds the "
                             "high lane under overload); comma-"
                             "separate to gate several")
    parser.add_argument("--assert-goodput-gap", default=None,
                        metavar="PRIO:PRIO:PTS[,...]",
                        help="exit 1 when lane A's goodput%% does not "
                             "exceed lane B's by at least PTS points "
                             "(e.g. 'high:low:10').  Gates the "
                             "RELATIVE overload contract — robust on "
                             "slow machines where every absolute "
                             "goodput number sags together")
    args = parser.parse_args(argv)

    from znicz_tpu.core.config import root
    slo_ms = (args.slo_ms if args.slo_ms is not None
              else float(root.common.serving.get("slo_ms", 100.0)))
    models = discover_models(args.url)
    if args.models:
        want = {m.strip() for m in args.models.split(",")}
        models = [m for m in models if (m.name or "default") in want]
        if not models:
            parser.error("--models %r matched nothing" % args.models)
    plan = make_plan(args.rate, args.duration, args.seed, models,
                     priority_mix=args.priority_mix)
    pool = DaemonPool(args.concurrency)
    if args.wire == "binary":
        submit = wire_submit(args.url, pool)
    else:
        submit = http_submit(args.url, pool, binary=args.npy)
    out = run(plan, models, submit, slo_ms,
              args.duration, args.seed, timeout_ms=args.timeout_ms)
    out["url"] = args.url
    out["wire"] = args.wire
    out["models"] = [m.name or "<default>" for m in models]
    print(json.dumps(out))
    if args.assert_goodput_pct is not None:
        failed = []
        for entry in str(args.assert_goodput_pct).split(","):
            entry = entry.strip()
            if not entry:
                continue
            prio, sep, pct = entry.rpartition(":")
            want = float(pct if sep else entry)
            if sep:
                block = out["per_priority"].get(prio)
                if block is None:
                    failed.append(
                        "%s: no %r traffic in the report (run with "
                        "--priority-mix including it)" % (entry,
                                                          prio))
                    continue
                got = block["goodput_pct"] or 0.0
                label = "%s-priority goodput" % prio
            else:
                got = out["goodput_pct"] or 0.0
                label = "goodput"
            if got < want:
                failed.append("%s %.2f%% below the %.2f%% SLO "
                              "assertion" % (label, got, want))
        if failed:
            for line in failed:
                print("loadgen: " + line, file=sys.stderr)
            return 1
    if args.assert_goodput_gap is not None:
        failed = []
        for entry in str(args.assert_goodput_gap).split(","):
            entry = entry.strip()
            if not entry:
                continue
            try:
                hi, lo, pts = entry.split(":")
                pts = float(pts)
            except ValueError:
                parser.error("--assert-goodput-gap wants "
                             "PRIO:PRIO:PTS, got %r" % entry)
            blocks = out["per_priority"]
            missing = [p for p in (hi, lo) if p not in blocks]
            if missing:
                failed.append(
                    "%s: no %s traffic in the report (run with "
                    "--priority-mix including it)"
                    % (entry, "/".join(missing)))
                continue
            got_hi = blocks[hi]["goodput_pct"] or 0.0
            got_lo = blocks[lo]["goodput_pct"] or 0.0
            if got_hi - got_lo < pts:
                failed.append(
                    "%s-vs-%s goodput gap %.2f points below the "
                    "%.2f-point assertion (%s=%.2f%%, %s=%.2f%%)"
                    % (hi, lo, got_hi - got_lo, pts, hi, got_hi,
                       lo, got_lo))
        if failed:
            for line in failed:
                print("loadgen: " + line, file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
