"""Per-bucket circuit breaker — serving's graceful-degradation valve.

A flaky backend (device resets, RESOURCE_EXHAUSTED churn, a wedged
runtime) must degrade into *fast, honest* 503s instead of a pile-up of
doomed dispatches.  Classic three-state machine, one breaker per shape
bucket (failures are usually shape-correlated: the one bucket whose
executable OOMs must not take the others down):

* **closed** — normal serving; consecutive dispatch failures count up,
  any success resets the count.  ``threshold`` consecutive failures
  trip it open.
* **open** — every :meth:`allow` raises :class:`CircuitOpenError`
  (mapped to HTTP 503 with a ``Retry-After`` header) without touching
  the device, until ``cooldown_s`` has elapsed.
* **half-open** — after the cooldown, up to ``half_open_max``
  concurrent probe dispatches are admitted; a probe success closes the
  breaker, a probe failure re-opens it (fresh cooldown).

The clock is injectable (``clock=``) so state transitions are testable
without sleeps — the acceptance pin drives the whole lifecycle with
injected faults and a fake clock.

Telemetry: ``serving.breaker_opens`` counter, per-bucket
``serving.breaker_open`` labeled gauges (1 = open/half-open), and
``serving.breaker`` journal events on every transition.
"""

import time

from znicz_tpu.core import telemetry
from znicz_tpu.analysis import locksmith

CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"


class CircuitOpenError(RuntimeError):
    """The breaker is open: the request was rejected WITHOUT a
    dispatch.  ``retry_after`` is the seconds until the next half-open
    probe window (the HTTP front end forwards it as ``Retry-After``)."""

    def __init__(self, name, retry_after):
        self.name = name
        self.retry_after = max(float(retry_after), 0.0)
        super(CircuitOpenError, self).__init__(
            "circuit %s is open; retry in %.3f s"
            % (name, self.retry_after))


class CircuitBreaker(object):
    """One protected dispatch path (see module docstring).

    ``threshold`` consecutive failures open it; ``cooldown_s`` later it
    half-opens for at most ``half_open_max`` concurrent probes.
    """

    def __init__(self, name, threshold=5, cooldown_s=1.0,
                 half_open_max=1, clock=time.monotonic):
        self.name = name
        self.threshold = max(int(threshold), 1)
        self.cooldown_s = float(cooldown_s)
        self.half_open_max = max(int(half_open_max), 1)
        self._clock = clock
        self._lock = locksmith.lock("serving.breaker")
        self.state = CLOSED
        self._failures = 0
        self._opened_at = None
        self._probes = 0
        self.opens = 0

    # -- the dispatch-path API ----------------------------------------------
    def allow(self):
        """Gate one dispatch.  Raises :class:`CircuitOpenError` while
        open (and while half-open with all probe slots taken); admits
        otherwise.  An admitted call MUST be followed by exactly one
        :meth:`record_success` / :meth:`record_failure` /
        :meth:`record_neutral`.  Returns True when the admission
        consumed a half-open probe slot — the caller threads that into
        :meth:`record_neutral` so a closed-era dispatch finishing
        during HALF_OPEN can never free a slot a real probe still
        holds."""
        with self._lock:
            if self.state == CLOSED:
                return False
            now = self._clock()
            if self.state == OPEN:
                remaining = self.cooldown_s - (now - self._opened_at)
                if remaining > 0:
                    raise CircuitOpenError(self.name, remaining)
                self._transition(HALF_OPEN)
                self._probes = 0
            # HALF_OPEN: bounded probe admission.  The rejection hint is
            # NOT the full cooldown — an in-flight probe may close the
            # breaker in milliseconds (success) or re-open it (failure),
            # so "retry soon" is the honest wait, not "retry in an hour"
            # under a long operator-configured cooldown.
            if self._probes >= self.half_open_max:
                raise CircuitOpenError(self.name,
                                       min(self.cooldown_s, 1.0))
            self._probes += 1
            return True

    def record_success(self):
        with self._lock:
            self._failures = 0
            if self.state != CLOSED:
                self._transition(CLOSED)

    def reconfigure(self, threshold, cooldown_s, half_open_max):
        """Adopt new knob values without touching breaker state — an
        open breaker stays open, but the (possibly shorter) cooldown
        applies at the next :meth:`allow` since remaining time is
        computed live from ``cooldown_s``."""
        with self._lock:
            self.threshold = max(int(threshold), 1)
            self.cooldown_s = float(cooldown_s)
            self.half_open_max = max(int(half_open_max), 1)

    def record_neutral(self, probe=True):
        """The admitted call produced no evidence about backend health
        (e.g. a client-caused trace error): release the half-open probe
        slot so neutral outcomes can never wedge the breaker with every
        slot consumed and no transition pending.  ``probe`` is
        :meth:`allow`'s return value — a call admitted while CLOSED
        holds no slot, and releasing one on its behalf would admit more
        than ``half_open_max`` concurrent probes."""
        with self._lock:
            if probe and self.state == HALF_OPEN and self._probes > 0:
                self._probes -= 1

    def record_failure(self):
        with self._lock:
            if self.state == HALF_OPEN:
                # the probe failed: straight back to open, fresh cooldown
                self._open()
                return
            self._failures += 1
            if self.state == CLOSED and \
                    self._failures >= self.threshold:
                self._open()

    # -- internals (lock held) ----------------------------------------------
    def _open(self):  # graftlint: guarded-by(self._lock)
        self._opened_at = self._clock()
        self.opens += 1
        if telemetry.enabled():
            telemetry.counter("serving.breaker_opens").inc()
        self._transition(OPEN)

    def _transition(self, state):  # graftlint: guarded-by(self._lock)
        prev, self.state = self.state, state
        if prev == state:
            return
        if telemetry.enabled():
            # label key "breaker", not "name" — labeled()'s first
            # positional parameter is itself called ``name``, so a
            # name= label kwarg collides and raises the moment a
            # breaker transitions with telemetry enabled
            telemetry.gauge(telemetry.labeled(
                "serving.breaker_open",
                breaker=self.name)).set(0 if state == CLOSED else 1)
        telemetry.record_event("serving.breaker", name=self.name,
                               state=state, previous=prev,
                               failures=self._failures)

    # -- introspection -------------------------------------------------------
    def status(self):
        with self._lock:
            st = {"state": self.state, "failures": self._failures,
                  "opens": self.opens}
            if self.state == OPEN and self._opened_at is not None:
                st["retry_after"] = round(max(
                    self.cooldown_s - (self._clock() - self._opened_at),
                    0.0), 3)
            return st
