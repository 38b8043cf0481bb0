"""Hierarchical attribute-dict configuration tree.

TPU-era equivalent of ``veles.config`` (reference usage:
samples/MNIST/mnist_config.py:43-89, standard_workflow_base.py:56-71).
Namespaces auto-vivify on attribute access; ``update`` merges nested dicts;
values may be arbitrary Python objects (including ``genetics.Range``).
"""

import json
import os

#: the checkout this package was imported from — the default data,
#: snapshot and cache directories live under it, so a copy of the tree
#: (a scratch clone, the chip machine's copy) keeps its files to itself
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class Config(object):
    """One node of the config tree.  Attribute access auto-creates children."""

    def __init__(self, path="root", **kwargs):
        object.__setattr__(self, "_path_", path)
        for k, v in kwargs.items():
            setattr(self, k, v)

    # -- auto-vivification --------------------------------------------------
    def __getattr__(self, name):
        if name.startswith("_") and name.endswith("_"):
            raise AttributeError(name)
        child = Config("%s.%s" % (self._path_, name))
        object.__setattr__(self, name, child)
        return child

    def __setattr__(self, name, value):
        if isinstance(value, dict):
            node = getattr(self, name)
            if isinstance(node, Config):
                node.update(value)
                return
            value_cfg = Config("%s.%s" % (self._path_, name))
            value_cfg.update(value)
            value = value_cfg
        object.__setattr__(self, name, value)

    # -- dict-ish interface -------------------------------------------------
    def update(self, value=None, **kwargs):
        """Recursively merge a dict (or another Config) into this node."""
        if value is None:
            value = kwargs
        if isinstance(value, Config):
            value = value.as_dict()
        if not isinstance(value, dict):
            raise TypeError(
                "Config.update takes a dict, got %s" % type(value))
        for k, v in value.items():
            if isinstance(v, dict):
                node = getattr(self, k)
                if isinstance(node, Config):
                    node.update(v)
                else:
                    setattr(self, k, v)
            else:
                object.__setattr__(self, k, v)
        return self

    def __contains__(self, name):
        return name in self.__dict__

    def get(self, name, default=None):
        v = self.__dict__.get(name, default)
        return v

    def items(self):
        return ((k, v) for k, v in self.__dict__.items()
                if not (k.startswith("_") and k.endswith("_")))

    def keys(self):
        return (k for k, _ in self.items())

    def as_dict(self):
        out = {}
        for k, v in self.items():
            out[k] = v.as_dict() if isinstance(v, Config) else v
        return out

    @property
    def __content__(self):
        """Reference-compatible dict view (StandardWorkflowBase.dictify)."""
        return self.as_dict()

    # -- presentation -------------------------------------------------------
    def __repr__(self):
        return "<Config %s: %s>" % (self._path_, sorted(self.__dict__))

    def print_(self, indent=0, file=None):
        import sys
        file = file or sys.stdout
        for k, v in sorted(self.items()):
            if isinstance(v, Config):
                print("%s%s:" % ("  " * indent, k), file=file)  # noqa
                v.print_(indent + 1, file)
            else:
                print("%s%s: %s" % ("  " * indent, k, v), file=file)  # noqa

    def to_json(self):
        def default(o):
            if isinstance(o, Config):
                return o.as_dict()
            return repr(o)
        return json.dumps(self.as_dict(), default=default, sort_keys=True)


#: The global configuration root (reference: ``veles.config.root``).
root = Config("root")


# ---------------------------------------------------------------------------
# Knob registry — declare-before-read config hygiene
# ---------------------------------------------------------------------------

#: declared LEAF knobs, dotted paths relative to ``root``
#: (e.g. "common.serving.max_batch")
_KNOBS = set()
#: declared NAMESPACE nodes (e.g. "common.serving") — reading a whole
#: node (to alias it or walk its keys) is legal; reading an undeclared
#: key under one is not
_NODES = set()


def declare(path, value):
    """Declare a knob (scalar ``value``) or a whole namespace (dict
    ``value``) under ``root.<path>``, installing its default and
    registering the path in the knob registry.

    The registry is THE vocabulary ``tools/graftlint.py``'s
    ``knob-vocabulary`` checker enforces: every ``root.common.*`` read
    or write anywhere in the library must resolve to a declared path.
    Auto-vivification makes a typo'd knob a silent default (an
    untouched Config node is even *truthy*), so new knobs must be
    declared here — in exactly one place — before any code reads them.
    """
    parts = path.split(".")
    if not parts or not all(parts):
        raise ValueError("bad knob path %r" % path)
    node = root
    for part in parts[:-1]:
        node = getattr(node, part)
        if not isinstance(node, Config):
            raise ValueError(
                "cannot declare %r: %s is a leaf knob, not a "
                "namespace" % (path, node))
    if isinstance(value, (dict, Config)):
        as_dict = value if isinstance(value, dict) else value.as_dict()
        setattr(node, parts[-1], as_dict)
        if as_dict:
            _register(path, getattr(node, parts[-1]).as_dict())
        else:
            # an empty dict declares an OPEN dict-valued knob — same
            # rule as a nested empty dict (e.g. common.faults.rules):
            # its payload is config data, not vocabulary
            _KNOBS.add(path)
    else:
        if parts[-1] not in node.__dict__:
            # an operator override set before the declaration wins
            setattr(node, parts[-1], value)
        _KNOBS.add(path)
    for i in range(1, len(parts)):
        _NODES.add(".".join(parts[:i]))
    return path


def _register(prefix, tree):
    _NODES.add(prefix)
    for k, v in tree.items():
        sub = "%s.%s" % (prefix, k)
        if isinstance(v, dict) and v:
            _register(sub, v)
        else:
            # an EMPTY dict default declares an open dict-valued knob
            # (e.g. common.faults.rules) — its content is config
            # payload, not vocabulary
            _KNOBS.add(sub)


def declared_knobs():
    """Frozen view of the declared LEAF knob paths."""
    return frozenset(_KNOBS)


def declared_nodes():
    """Frozen view of the declared NAMESPACE paths."""
    return frozenset(_NODES)


def knob_declared(path):
    """Is ``path`` (dotted, relative to ``root``) a legal config read?
    True for declared knobs and namespaces, and for any path UNDER a
    declared leaf knob (data inside a dict-valued knob like
    ``common.faults.rules`` is config payload, not vocabulary)."""
    if path in _KNOBS or path in _NODES:
        return True
    parts = path.split(".")
    for i in range(1, len(parts)):
        if ".".join(parts[:i]) in _KNOBS:
            return True
    return False


# Engine-level defaults observed in the reference
# (samples/CIFAR10/cifar_caffe_config.py:52-53, site_config.py:37-40).
declare("common", {
    "engine": {
        "precision_type": "float",    # "float" | "double" | "bfloat16"
        "precision_level": 0,         # 0: fast, 1: deterministic-ish
        "backend": "auto",            # "numpy" | "jax" | "auto"
        # explicit minibatch/staging dtype override read by
        # Loader.create_minibatch_data and the fused trainer (None:
        # follow the data / precision_type) — was read but UNDECLARED
        # until graftlint's knob-vocabulary checker flagged it
        "precision_dtype": None,
    },
    "dirs": {
        "datasets": os.path.join(CHECKOUT, ".data"),
        "snapshots": os.path.join(CHECKOUT, ".snapshots"),
        "cache": os.path.join(CHECKOUT, ".cache"),
    },
    "disable": {"plotting": True, "publishing": True},
    # interactive Shell unit gate (core/interaction.py) — MUST be
    # declared: an undeclared read would auto-vivify a truthy empty
    # Config node and silently force every Shell interactive on a tty
    "interactive": False,
    # static/runtime analysis layer (znicz_tpu/analysis/) — off by
    # default; when off the locksmith lock factories hand out plain
    # threading primitives after ONE config predicate
    "analysis": {
        "lock_sanitizer": False,
    },
    # unified telemetry (core/telemetry.py) — off by default so every
    # instrumented hot path reduces to a guard-only no-op
    "telemetry": {
        "enabled": False,
        "trace_capacity": 65536,    # span ring-buffer size (events)
        "histogram_window": 2048,   # percentile reservoir per series
        "journal_capacity": 4096,   # flight-recorder ring (events)
        # metric time-series (core/timeseries.py) — a background
        # sampler snapshotting selected counters/gauges/histogram
        # percentiles into bounded timestamped rings, served at
        # GET /debug/timeseries.  Off by default; when off the sampler
        # thread never starts and every hook is ONE config predicate.
        "timeseries": {
            "enabled": False,
            "interval_ms": 1000.0,  # sampling period
            "capacity": 512,        # points retained per series
            # comma-separated family prefixes worth a history (every
            # matching counter/gauge gets a ring; histograms record
            # their p50/p99) — keep it a bounded curated set
            "prefixes":
                "serving,slo,jax,trainer,transfer,loader,pyprof",
        },
        # durable blackbox (core/blackbox.py) — crash-safe on-disk
        # persistence for the journal/timeseries/SLO/trace planes as
        # length-delimited JSONL segments <role>.<pid>.<boot>.<nnn>
        # under ONE shared dir, queried by `python -m znicz_tpu obs`.
        # Off by default; when off maybe_arm() is ONE config predicate
        # and the process never touches the filesystem.
        "blackbox": {
            "enabled": False,
            "dir": None,              # default: <cache>/blackbox —
                                      # the fleet router pins its
                                      # resolved dir into every
                                      # replica so all processes share
            "role": None,             # segment-name role; the fleet
                                      # forwards "replica"/"router",
                                      # else the arming call site's
                                      # default wins
            "segment_bytes": 1 << 20,  # rotate (fsync file, then dir)
                                       # past this size
            "retention_bytes": 64 << 20,  # delete oldest whole
                                          # segments (never the live
                                          # one) past this dir total;
                                          # 0 disables retention
            "checkpoint_every_sweeps": 5,  # persist the timeseries
                                           # frontier every Nth
                                           # sampler sweep
        },
    },
    # numeric training-health monitor (core/health.py) — off by default;
    # when off every check site is a single predicate with ZERO device
    # syncs.  See docs/observability.md for each knob.
    "health": {
        "enabled": False,
        "interval": 1,            # check every N train steps/minibatches
        "policy": "warn",        # "warn" | "snapshot" | "halt"
        "grad_norm_limit": 0.0,   # 0 disables the explosion check
        "param_norm_limit": 0.0,
        "update_norm_limit": 0.0,
        "loss_window": 8,         # divergence detector window (epochs)
        "loss_ema_alpha": 0.3,    # EMA smoothing for the explosion test
        "divergence_factor": 3.0,  # loss > factor*EMA => explosion
        "loss_rise": 0.1,         # net rise across a full window => slope
        "crash_dir": None,        # default: <cache>/crash_reports
    },
    # performance introspection (core/profiler.py) — off by default;
    # when off every hook site is a single predicate with ZERO device
    # syncs and zero compiles.  See docs/observability.md for each knob.
    "profiler": {
        "enabled": False,
        "cost_rtol": 0.5,         # measured/analytic FLOPs agreement
                                  # band: [1-rtol, 1+rtol]
        "leak_epochs": 3,         # consecutive growing epochs before
                                  # the ledger flags a leak suspect
        "leak_min_bytes": 1 << 20,  # ignore sub-MiB epoch growth
        "capture_seconds_cap": 60.0,  # /debug/profile?seconds= ceiling
        "capture_dir": None,      # default: <cache>/profiles
        # continuous Python sampling profiler (core/pyprof.py) — off
        # by default; when off no sampler thread exists and every hook
        # is ONE config predicate.  Attributes sys._current_frames()
        # samples to znicz:<component> thread names and classifies
        # leaves into the fixed data-plane phase vocabulary; a
        # calibrated scheduling-delay probe estimates GIL wait.
        # Served at GET /debug/pyprof (fleet-merged on the router).
        "pyprof": {
            "enabled": False,
            "hz": 97.0,             # sample rate — off-beat on
                                    # purpose (coprime with the
                                    # 1000/100/5 ms plane cadences)
            "capacity": 512,        # distinct collapsed stacks kept
            "max_depth": 24,        # frames folded per stack
            "gil_probe": True,      # scheduling-delay probe thread
            "gil_interval_ms": 5.0,  # probe sleep quantum
            "gil_calib_probes": 20,  # overshoots -> median baseline
            "capture_seconds_cap": 30.0,  # /debug/pyprof?seconds= cap
        },
    },
    # deterministic fault injection (core/faults.py) — off by default;
    # when off every injection site is a single predicate with ZERO
    # device syncs and zero compiles.  Rules map site names to trigger
    # dicts ({"kind": "io"|"xla"|"crash"|"stall", "at": N | "every": K
    # | "p": x, "times": M, "stall_ms": ...}) so chaos tests replay
    # deterministically.  See docs/deployment.md "Fault tolerance".
    "faults": {
        "enabled": False,
        "seed": 0,            # default stream for p-mode rules
        "rules": {},          # site -> rule dict (declarative arming)
    },
    # bounded-retry policy for TRANSIENT faults (loader minibatch fill,
    # serving executable dispatch — core/faults.py retry_call); always
    # armed: a try/except around an already-expensive call costs
    # nothing until a fault actually fires
    "retry": {
        "attempts": 3,          # retries AFTER the first try
        "backoff_base_ms": 5.0,  # exponential base; doubles per retry
        "backoff_max_ms": 200.0,  # backoff ceiling
    },
    # engine timing behavior (was the mutable class global
    # Unit.sync_timings; config-backed so tests can't leak
    # blocking-sync mode into the rest of the suite)
    "timings": {"sync_each_run": False},
    # online inference serving defaults (znicz_tpu/serving/ — see
    # docs/serving.md for every knob's meaning)
    "serving": {
        "host": "127.0.0.1",
        "port": 8899,
        "max_batch": 64,        # micro-batch ceiling = largest bucket
        "max_delay_ms": 5.0,    # batching window after first request
        "queue_limit": 256,     # queued ROWS before 429 backpressure
        "timeout_ms": 1000.0,   # per-request deadline in the queue
        "warmup": True,         # compile every bucket before ready
        # default serving precision recorded in export warmup
        # manifests ("f32" | "f32-fast" | "bf16" | "int8"); engines
        # without an explicit dtype= adopt the source manifest's value
        "dtype": "f32",
        # batch-1 latency fast path (serving dtype "f32-fast"): shape
        # buckets up to this size dispatch the restructured forward —
        # the contraction runs as a STANDALONE dot (kept out of the
        # bias/activation fusion by an optimization barrier) over the
        # dot-native weight layout, which keeps XLA's low-batch dot on
        # its fast path.  Read at engine LOAD time (part of the
        # compile key); larger buckets keep the fused-epilogue path.
        "latency_bucket_max": 8,
        "slow_request_ms": 1000.0,  # log requests slower than this
        # graceful degradation (serving/breaker.py + HandlerBase):
        "breaker_threshold": 5,     # consecutive dispatch failures
                                    # before a bucket's breaker opens
                                    # (0 disables circuit breaking)
        "breaker_cooldown_ms": 1000.0,  # open -> half-open delay; also
                                        # the Retry-After hint on 503s
        "breaker_half_open_max": 1,  # concurrent half-open probes
        "max_body_bytes": 16 << 20,  # request bodies over this get 413
                                     # (0 disables the cap)
        # continuous batching (serving/continuous.py): dispatch slots
        # that admit queued requests the moment capacity frees —
        # max_inflight concurrent engine dispatches across all models
        "max_inflight": 2,
        # multi-model registry (serving/registry.py): device-memory
        # budget for resident models; the least-recently-used cold
        # model's executables + device params are evicted when the
        # resident total exceeds it (0 = unlimited, never evict)
        "registry_memory_budget_bytes": 0,
        # latency SLO used by tools/loadgen.py goodput accounting
        "slo_ms": 100.0,
        # server-side SLO tracking (serving/slo.py): per-model
        # good/total accounting against slo_ms measured from request
        # admission, Google-SRE multi-window burn rates and an
        # error-budget-remaining gauge — the feed for /slo, the
        # /statusz slo block and the future autoscaler.  Off by
        # default; when off the HTTP front end pays ONE predicate.
        "slo_enabled": False,
        "slo_target_pct": 99.0,     # availability target: good/total
        "slo_fast_window_s": 60.0,  # fast burn window (page-now)
        "slo_slow_window_s": 600.0,  # slow burn window (budget window)
        "slo_burn_threshold": 2.0,  # both windows over this -> one
                                    # slo.burn journal event (edge-
                                    # triggered with hysteresis)
        # per-request trace trees (serving/reqtrace.py): head-sample
        # every Nth admitted request into a rid-keyed span tree
        # (admission/queue_wait/assembly/dispatch/device/reply),
        # retrievable at GET /debug/trace/<rid>.  0 = off (the
        # default); when off every hook is ONE config predicate.
        "trace_sample_n": 0,
        "trace_capacity": 256,      # sampled trace trees retained
        # priority lanes (serving/continuous.py): each request carries
        # a priority ("high" | "normal" | "low"; X-Priority header or
        # the body's "priority" field).  Under queue pressure the low
        # lanes shed FIRST: a priority admits only while the queued
        # rows sit under its share of queue_limit, so under overload
        # low-priority traffic turns into fast 429s while
        # high-priority goodput holds.  "normal" (the default lane)
        # keeps the FULL queue — default traffic admits exactly as it
        # always did; lower it (e.g. 85) for three-tier shedding.
        # High additionally wins at DISPATCH (lane rank), so it holds
        # goodput even where admission ceilings tie.
        "priority_queue_pct": {
            "low": 50.0,        # low admits under 50% occupancy
            "normal": 100.0,    # default traffic: full queue
            "high": 100.0,      # high admits up to queue_limit
        },
        # admitted-request-id ring (serving/continuous.py): the
        # batcher remembers the last N admitted rids so the fleet
        # router can prove a request never reached a replica's batcher
        # before retrying it on a peer (GET /admitted/<rid>)
        "admitted_rid_capacity": 4096,
        # binary framed relay (serving/wire.py) — the persistent
        # length-prefixed router<->replica transport; see
        # docs/serving.md "Wire protocol" for the frame layout and
        # the zero-copy ingest contract
        "wire": {
            "enabled": True,         # the binary relay is the DEFAULT
                                     # router<->replica transport;
                                     # False falls back to HTTP/JSON
                                     # everywhere (the documented
                                     # compatibility surface)
            "conns_per_replica": 2,  # persistent mux connections the
                                     # router keeps per replica
            "max_frame_mb": 32.0,    # frame-body ceiling; oversize
                                     # answers a typed error frame
            "read_timeout_ms": 10000.0,  # half-frame (slowloris)
                                         # sweep deadline
            "workers": 128,          # listener dispatch threads.  A
                                     # worker PARKS through the whole
                                     # blocking /predict state
                                     # machine, so this bounds how
                                     # many in-flight frames reach
                                     # lane admission concurrently —
                                     # undersize it and overload
                                     # queues FIFO in the pool AHEAD
                                     # of the priority lanes (HTTP
                                     # got this for free from thread-
                                     # per-connection).  Sized past
                                     # queue-limit so every arriving
                                     # frame is shed or queued BY
                                     # PRIORITY, never by arrival.
        },
        # multi-replica serving fleet (serving/router.py +
        # serving/autoscaler.py) — see docs/serving.md "Fleet
        # topology" for every knob's meaning
        "fleet": {
            "replicas": 2,           # serve --fleet default size
            "spawn_timeout_s": 180.0,  # replica must print its URL +
                                       # pass /healthz within this
            "probe_interval_s": 1.0,   # health-monitor poll period
            "probe_failures": 3,       # consecutive failed probes
                                       # before an ejection
            "route_retries": 2,        # peer retries per request when
                                       # a resend is provably safe
            "overhead_window": 512,    # proxied 200s retained for the
                                       # router_overhead_ms summary
                                       # (/slo + /statusz; PR 16)
            # the autoscaler (serving/autoscaler.py):
            "min_replicas": 1,
            "max_replicas": 4,
            "autoscale_interval_s": 5.0,  # decision cadence
            "scale_up_burn_threshold": 2.0,  # fleet fast+slow burn
                                             # over this -> scale up
            "scale_up_queue_rows": 256.0,    # fleet queued rows per
                                             # replica over this ->
                                             # scale up
            "scale_down_budget_min": 0.97,   # budget comfortably
                                             # green before a retire
            "scale_down_evals": 3,   # consecutive green decisions
                                     # before a scale-down (hysteresis)
            "cooldown_s": 30.0,      # min seconds between actions
        },
        # progressive delivery (serving/release.py) — the SLO-judged
        # shadow -> canary -> promote pipeline; see docs/deployment.md
        # "Continuous delivery" for every knob's meaning.  A POST
        # /release body's "policy" object overrides any knob for that
        # one release.
        "release": {
            "shadow_sample_pct": 100.0,  # % of live traffic mirrored
                                         # to the candidate in shadow
            "shadow_min_compares": 8,    # compared replies required
                                         # before shadow can go green
            "shadow_mismatch_max": 0,    # tolerated out-of-tolerance
                                         # shadow replies (> -> red)
            "shadow_error_max": 3,       # candidate errors during
                                         # shadow before -> failed
            "canary_steps": [5.0, 25.0, 50.0],  # ramp ladder (% of
                                                # real traffic)
            "green_window_s": 5.0,   # BOTH burn windows must stay
                                     # green this long per step
            "min_requests": 12,      # candidate requests per step
                                     # before advancement counts
            "tick_interval_s": 0.25,  # controller evaluation cadence
        },
    },
    # persistent XLA compilation cache (core/compile_cache.py):
    # executables compile once, later processes of the same program
    # deserialize them from `dir` instead of recompiling.  Off by
    # default unless JAX_COMPILATION_CACHE_DIR is set — that variable
    # both enables the cache and fixes its directory, over `dir` and
    # over `serve --compile-cache`.
    "compile_cache": {
        "enabled": False,
        "dir": None,              # default: <cache dir>/xla_cache
        "min_compile_time_secs": 0.0,   # cache even instant compiles
        "min_entry_size_bytes": -1,     # ... and tiny executables
    },
})


def apply_override(assignment, root_cfg=None):
    """Apply one CLI ``dotted.path=value`` override onto the config
    root (the ``--config`` flag of the training launcher AND the
    serve CLI — one parser, one literal-or-string rule).  Values
    parse as Python literals, falling back to strings; a leading
    ``root.`` is accepted and stripped."""
    import ast
    path, sep, raw = assignment.partition("=")
    if not sep:
        raise SystemExit("--config needs KEY=VALUE, got %r"
                         % assignment)
    try:
        value = ast.literal_eval(raw)
    except (ValueError, SyntaxError):
        value = raw
    parts = path.strip().split(".")
    if parts and parts[0] == "root":
        parts = parts[1:]
    node = root_cfg if root_cfg is not None else root
    for p in parts[:-1]:
        node = getattr(node, p)
    setattr(node, parts[-1], value)


def get(value, default=None):
    """Return ``value`` unless it is an untouched auto-vivified Config node."""
    if value is None:
        return default
    if isinstance(value, Config) and not any(True for _ in value.keys()):
        return default
    return value


def dtype_map():
    """Numpy dtype for the configured
    ``root.common.engine.precision_type``: ``float`` (f32), ``double``
    (f64), or ``bfloat16`` (the ml_dtypes numpy dtype jax natively
    consumes — the low-precision serving/training tier).  Unknown
    strings fail LOUDLY with the accepted spellings — a typo'd
    precision must never surface as a bare ``KeyError`` deep inside
    workflow initialize."""
    import numpy
    precision = root.common.engine.precision_type
    if precision in ("float", "float32", "f32"):
        return numpy.float32
    if precision in ("double", "float64", "f64"):
        return numpy.float64
    if precision in ("bfloat16", "bf16"):
        import ml_dtypes
        return numpy.dtype(ml_dtypes.bfloat16)
    raise ValueError(
        "unknown root.common.engine.precision_type %r (accepted: "
        "float/float32/f32, double/float64/f64, bfloat16/bf16)"
        % (precision,))
