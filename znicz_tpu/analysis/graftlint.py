"""graftlint — project-invariant static analysis for the znicz_tpu tree.

Dependency-free (stdlib ``ast`` only, never imports jax) checkers for
the invariant classes the stack otherwise enforces only dynamically —
each grounded in a real shipped bug:

* ``knob-vocabulary`` — every ``root.common.*`` read/write (attribute
  chains, ``.get("key")`` literals, ``getattr``/``setattr``, and
  module aliases like ``_cfg = root.common.serving``) must resolve to
  a knob declared in ``core/config.py`` (``config.declare``).  The
  config tree auto-vivifies, so an undeclared read is a silent —
  *truthy* — default: ``core/interaction.py`` shipped with
  ``getattr(root.common, "interactive", False)`` returning an empty
  Config node that made every tty run interactive.
* ``telemetry-series`` / ``telemetry-collision`` /
  ``telemetry-cardinality`` — metric call sites must use the bounded
  series vocabulary, must not pass ``labeled()`` a label literally
  named ``name`` (it collides with the positional parameter — the
  PR 12 breaker bug, latent since PR 7), and must not derive label
  values from request data (every distinct label value is a registry
  entry forever).
* ``lock-guard`` — per class, an attribute ever written under ``with
  self.<lock>`` is flagged where written (or container-mutated)
  outside it; ``# graftlint: guarded-by(self._lock)`` on a ``def``
  declares a method that runs with the lock already held (the
  ``stats()``-iterating-a-mutating-dict and predict-racing-evict bug
  class from the PR 7/8 hardening rounds).
* ``jax-host-sync`` / ``jax-rng`` / ``jax-time`` / ``jax-donation`` —
  inside jitted / scanned function bodies: no ``float()`` / ``int()``
  / ``.item()`` / ``numpy.asarray`` on traced parameters (each is a
  device sync, breaking the zero-mid-epoch-d2h invariant), no Python
  RNG or wall-clock reads (baked in at trace time), and accumulator-
  shaped jit arguments should be donated.
* ``gate-order`` — the disabled-by-default subsystems (health,
  profiler, faults, telemetry, locksmith) must hit their one-predicate
  gate before any config walk or jax touch in the declared hot entry
  points — the zero-overhead-off contract every monkeypatch-boom test
  pins dynamically.

Plus the legacy style checks folded in from the retired
``tools/lint.py``: ``syntax``, ``tabs``, ``trailing-whitespace``,
``line-length``, ``unused-import`` (now also counting names used only
inside string constants — f-string templates, docstring doctests),
``bare-except``, ``library-print``.

Suppression: ``# noqa`` keeps its legacy meaning on style lines;
``# graftlint: disable=check-id[,check-id...]`` suppresses named
checks on that line (on a ``def``/``class`` line: for the whole
body); the CLI additionally honors a reviewed baseline file of
``path :: check :: token`` fingerprints (``tools/graftlint_baseline``).

Entry point: ``tools/graftlint.py`` (CLI + ``--selftest``).
"""

import ast
import os
import re

# ---------------------------------------------------------------------------
# Vocabulary
# ---------------------------------------------------------------------------

#: first dotted segment of every legal telemetry series name — extend
#: ONLY with a reviewed family prefix (each series is a /metrics entry)
SERIES_PREFIXES = frozenset((
    "analysis",
    # the steps the attention kernel's block maps ran and the static map's,
    # counted at each train readback (ISSUE 34): attention.blocks_visited,
    # attention.blocks_static (units/fused_trainer.py); the kernel
    # applications whose output a recomputed residual entry keeps:
    # attention.forwards_kept
    "attention",
    # the durable blackbox (ISSUE 19): writer meters — records/bytes
    # persisted, segment rotations, retention deletions, torn tails
    # found on recovery (core/blackbox.py)
    "blackbox",
    "faults",
    # the multi-replica serving fleet (ISSUE 15): replica-count
    # gauges + autoscaler decision counters (serving/router.py,
    # serving/autoscaler.py) and the front-end router's proxy/retry
    # counters; ISSUE 16 adds the fleet.hop_seconds.<kind> histogram
    # family — per-model router hop-phase timings fed from sampled
    # trace spans (kind is bounded by reqtrace.ROUTER_SPAN_KINDS)
    "fleet",
    "health", "jax", "launcher", "loader",
    "memory",
    # a routed mixture of experts' load, counted at each train readback
    # (ISSUE 33): moe.pairs_held, moe.tokens_unserved, moe.load_max;
    # ISSUE 36: moe.rows_moved, moe.rows_static (units/fused_trainer.py)
    "moe",
    # a second head graded at a shift (a multi-token prediction
    # module's), counted at each train readback: mtp.graded_tokens,
    # mtp.errors (units/fused_trainer.py)
    "mtp",
    "profiler",
    # the continuous Python sampling profiler (ISSUE 18):
    # pyprof.samples (sweep yield) and pyprof.gil_wait_ms (calibrated
    # scheduling-delay excess) — core/pyprof.py, sampled into rings
    # by core/timeseries.py
    "pyprof",
    "registry",
    # the release plane (ISSUE 17): shadow-compare / canary-state
    # series per (model, generation) — release.shadow_compares,
    # release.shadow_mismatches, release.shadow_dropped,
    # release.state, release.canary_pct (serving/release.py)
    "release",
    "router",
    "serving",
    # Mamba-2 mixers' scans, counted at each train readback: ssd.chunks,
    # ssd.resets, ssd.chunks_split, ssd.chunks_kernel, ssd.conv_kernel
    # (units/fused_trainer.py)
    "ssd",
    # the serving SLO plane (ISSUE 14): per-model good/total,
    # burn-rate and error-budget series (serving/slo.py) and the
    # time-series sampler's own meters (core/timeseries.py)
    "slo", "snapshotter", "timeseries",
    "trainer", "transfer", "unit",
    # the binary framed relay (ISSUE 20): frame/byte/error meters on
    # both the listener and the router-side mux (serving/wire.py) —
    # wire.frames_in, wire.bytes_in, wire.protocol_errors,
    # wire.round_trips, wire.dead_conns, ...
    "wire",
    "workflow",
))

#: legal ``labeled()`` label keys — a bounded set by design (every
#: (key, value) pair mints a new series)
LABEL_KEYS = frozenset((
    "bucket", "breaker", "device", "dtype",
    # the release plane (ISSUE 17): generation ordinals ("1", "2",
    # ...) on the release.* series — bounded by promote cadence (one
    # value per deployed generation), never by request data
    "gen",
    "model",
    # the priority lanes (ISSUE 15): bounded by the PRIORITIES
    # vocabulary in serving/continuous.py (high/normal/low)
    "priority",
    # the fleet tracing plane (ISSUE 16): replica ids ("r0", "r1",
    # ...) on the router's stitched-trace counters — bounded by fleet
    # membership (autoscaler churn is cooldown-limited), never by
    # request data
    "replica",
    "scenario", "site",
    # the binary framed relay (ISSUE 20): which transport carried a
    # request into serving.codec_requests — exactly two values
    # ("binary" / "http"), serving/server.py
    "codec",
))

#: identifiers that mark a label VALUE as derived from request data —
#: unbounded cardinality (one series per request id/payload)
LABEL_VALUE_DENY = frozenset((
    "request_id", "request_ids", "rid", "rids", "request", "req",
    "payload", "body", "uuid",
))

_SERIES_RE = re.compile(r"^[a-z][a-z0-9_.]*$")

#: Config methods that may terminate a knob chain
_CFG_METHODS = frozenset(("get", "update", "items", "keys", "as_dict",
                          "print_", "to_json"))

#: container-mutating method names counted as writes by lock-guard
_MUTATORS = frozenset((
    "append", "appendleft", "extend", "extendleft", "insert", "add",
    "discard", "remove", "pop", "popleft", "popitem", "clear",
    "update", "setdefault", "sort", "reverse", "rotate",
))

#: gated subsystems: per-module gate-function names and the hot entry
#: points REQUIRED to gate (the zero-overhead-off contract)
GATED_MODULES = {
    "znicz_tpu/core/health.py": {
        "gates": ("enabled",),
        "required": ("check_training_step", "check_gd_unit",
                     "observe_loss"),
    },
    "znicz_tpu/core/profiler.py": {
        "gates": ("enabled",),
        "required": ("register_jit_cost", "ledger_swap", "epoch_check"),
    },
    "znicz_tpu/core/faults.py": {
        "gates": ("enabled",),
        "required": (),
    },
    "znicz_tpu/core/telemetry.py": {
        "gates": ("enabled", "journal_enabled", "_get_metric"),
        "required": ("span", "instant", "record_event", "counter",
                     "gauge", "histogram"),
    },
    "znicz_tpu/analysis/locksmith.py": {
        "gates": ("enabled",),
        "required": ("lock", "rlock", "condition"),
    },
    "znicz_tpu/core/timeseries.py": {
        "gates": ("enabled",),
        "required": ("sample_once", "maybe_start"),
    },
    "znicz_tpu/core/pyprof.py": {
        "gates": ("enabled",),
        "required": ("sample_once", "maybe_start", "gil_probe_once"),
    },
    "znicz_tpu/serving/reqtrace.py": {
        "gates": ("enabled", "sampled"),
        "required": ("begin",),
    },
    "znicz_tpu/core/blackbox.py": {
        "gates": ("enabled",),
        "required": ("maybe_arm",),
    },
}

# legacy style-check knobs (tools/lint.py heritage)
MAX_LINE = 80
LIB_DIRS = ("znicz_tpu",)
PRINT_OK = ("samples", "__main__.py", "launcher.py", "parity.py")

#: accumulator-shaped jit parameters that should be donated
_ACC_PARAM_RE = re.compile(r"(^|_)acc(um)?(_|$|s$)")


class Finding(object):
    """One reported violation."""

    __slots__ = ("path", "line", "check", "message", "token")

    def __init__(self, path, line, check, message, token=""):
        self.path = path
        self.line = int(line)
        self.check = check
        self.message = message
        self.token = token or ""

    @property
    def fingerprint(self):
        """Line-number-free identity for the baseline file."""
        return "%s :: %s :: %s" % (self.path, self.check, self.token)

    def __str__(self):
        return "%s:%d: [%s] %s" % (self.path, self.line, self.check,
                                   self.message)

    def __repr__(self):
        return "<Finding %s>" % self


# ---------------------------------------------------------------------------
# Pragmas
# ---------------------------------------------------------------------------

_PRAGMA_RE = re.compile(r"#\s*graftlint:\s*([^#]*)")
_GUARDED_RE = re.compile(r"guarded-by\(([^)]+)\)")
_DISABLE_RE = re.compile(r"disable=([A-Za-z0-9_,-]+)")


class _Pragmas(object):
    """Per-file pragma index: line -> disabled checks / guard lock."""

    def __init__(self, lines):
        self.disabled = {}    # lineno -> set of check ids
        self.guarded = {}     # lineno -> lock attr name (e.g. "_lock")
        for i, line in enumerate(lines, 1):
            m = _PRAGMA_RE.search(line)
            if not m:
                continue
            text = m.group(1)
            d = _DISABLE_RE.search(text)
            if d:
                self.disabled[i] = set(
                    c.strip() for c in d.group(1).split(",") if c)
            g = _GUARDED_RE.search(text)
            if g:
                lock = g.group(1).strip()
                if lock.startswith("self."):
                    lock = lock[len("self."):]
                self.guarded[i] = lock

    def allows(self, check, lineno):
        return check in self.disabled.get(lineno, ())

    def allows_span(self, check, node):
        """A pragma anywhere on the lines a (possibly multi-line)
        expression spans suppresses it."""
        end = getattr(node, "end_lineno", None) or node.lineno
        return any(self.allows(check, i)
                   for i in range(node.lineno, end + 1))


# ---------------------------------------------------------------------------
# Shared AST helpers
# ---------------------------------------------------------------------------

def _attr_chain(node):
    """``a.b.c`` -> ["a", "b", "c"]; None for non-trivial bases."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return None


def _walk(node):
    """Depth-first pre-order (ast.walk is BFS; checker logic needs
    source order)."""
    yield node
    for child in ast.iter_child_nodes(node):
        for sub in _walk(child):
            yield sub


def _parent_map(tree):
    parents = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def _const_str(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _names_in(node):
    out = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            out.add(n.id)
        elif isinstance(n, ast.Attribute):
            out.add(n.attr)
    return out


# ---------------------------------------------------------------------------
# Knob vocabulary
# ---------------------------------------------------------------------------

def load_vocabulary():
    """The declared knob/namespace paths from ``core/config.py`` (a
    jax-free import)."""
    from znicz_tpu.core import config
    return config.declared_knobs(), config.declared_nodes()


def _knob_declared(path, knobs, nodes):
    if path in knobs or path in nodes:
        return True
    parts = path.split(".")
    for i in range(1, len(parts)):
        if ".".join(parts[:i]) in knobs:
            return True   # payload inside a dict-valued knob
    return False


def check_knobs(tree, rel, pragmas, knobs, nodes, findings):
    """Every ``root.common.*`` path must resolve to a declared knob."""
    if rel.replace(os.sep, "/").endswith("znicz_tpu/core/config.py"):
        return   # the declaration site itself
    parents = _parent_map(tree)
    # module/function aliases: NAME = root.common.<chain>
    aliases = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            chain = _attr_chain(node.value) \
                if isinstance(node.value, ast.Attribute) else None
            if chain and chain[:2] == ["root", "common"]:
                aliases[node.targets[0].id] = ".".join(chain[1:])

    def resolve(chain):
        """Dotted path relative to ``root`` or None if unrelated."""
        if chain[:2] == ["root", "common"]:
            return ".".join(chain[1:])
        if chain[0] in aliases:
            return ".".join([aliases[chain[0]]] + chain[1:])
        return None

    def report(path, node):
        if pragmas.allows("knob-vocabulary", node.lineno):
            return
        if not _knob_declared(path, knobs, nodes):
            findings.append(Finding(
                rel, node.lineno, "knob-vocabulary",
                "undeclared config knob root.%s — declare it in "
                "core/config.py (config.declare) or fix the typo; an "
                "undeclared read auto-vivifies a truthy empty node"
                % path, token=path))

    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute):
            parent = parents.get(node)
            if isinstance(parent, ast.Attribute) and \
                    parent.value is node:
                continue   # not a maximal chain
            chain = _attr_chain(node)
            if not chain:
                continue
            # chain ending in a Config method call: validate the base,
            # plus the literal key of .get(...)
            call = parent if isinstance(parent, ast.Call) and \
                parent.func is node else None
            if call is not None and chain[-1] in _CFG_METHODS:
                base = resolve(chain[:-1])
                if base is None:
                    continue
                report(base, node)
                if chain[-1] == "get" and call.args:
                    key = _const_str(call.args[0])
                    if key is not None:
                        report("%s.%s" % (base, key), node)
                continue
            path = resolve(chain)
            if path is not None:
                report(path, node)
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Name) and \
                node.func.id in ("getattr", "setattr") and \
                len(node.args) >= 2:
            chain = _attr_chain(node.args[0]) \
                if isinstance(node.args[0], ast.Attribute) else (
                    [node.args[0].id]
                    if isinstance(node.args[0], ast.Name) else None)
            if not chain:
                continue
            base = resolve(chain) if len(chain) > 1 else (
                "common" if chain == ["root"] else
                aliases.get(chain[0]))
            if chain == ["root"]:
                base = None   # root.<x> only matters under common
            if base is None and chain[:1] == ["root"]:
                continue
            if base is None:
                continue
            key = _const_str(node.args[1])
            if key is not None:
                report("%s.%s" % (base, key), node)


# ---------------------------------------------------------------------------
# Telemetry series / label discipline
# ---------------------------------------------------------------------------

def _series_static_prefix(node, constants):
    """(full_name, prefix) for a statically-known series-name
    expression; (None, None) when dynamic.  ``full_name`` is set only
    for complete literals; templates yield just their static prefix."""
    s = _const_str(node)
    if s is not None:
        return s, s
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod):
        left = _const_str(node.left)
        if left is not None:
            return None, left.split("%")[0]
    if isinstance(node, ast.JoinedStr) and node.values:
        head = _const_str(node.values[0])
        if head is not None:
            return None, head
    if isinstance(node, ast.Name) and node.id in constants:
        s = constants[node.id]
        return s, s
    return None, None


def _check_series_name(node, call, rel, pragmas, findings):
    """Validate one series-name expression; returns True if it was
    statically checkable."""
    # module-level string constants are resolved by the caller's
    # ``constants`` map threaded through check_telemetry
    full, prefix = node._graftlint_resolved
    lineno = node.lineno
    if pragmas.allows_span("telemetry-series", call):
        return True
    if full is not None:
        if not _SERIES_RE.match(full) or \
                full.split(".")[0] not in SERIES_PREFIXES or \
                "." not in full:
            findings.append(Finding(
                rel, lineno, "telemetry-series",
                "series name %r is outside the bounded vocabulary "
                "(family prefixes: %s)"
                % (full, ", ".join(sorted(SERIES_PREFIXES))),
                token=full))
        return True
    if prefix is not None:
        fam = prefix.split(".")[0]
        if "." not in prefix or fam not in SERIES_PREFIXES:
            findings.append(Finding(
                rel, lineno, "telemetry-series",
                "templated series name %r* does not start with a "
                "known family prefix" % prefix, token=prefix))
        return True
    findings.append(Finding(
        rel, lineno, "telemetry-series",
        "dynamic series name — metric names must be statically "
        "bounded (literal, literal template, or module constant)",
        token="<dynamic>"))
    return False


def _check_labels(call, rel, pragmas, findings):
    for kw in call.keywords:
        lineno = getattr(kw.value, "lineno", call.lineno)
        if kw.arg is None:
            if not pragmas.allows_span("telemetry-cardinality", call):
                findings.append(Finding(
                    rel, lineno, "telemetry-cardinality",
                    "**labels unpacking is not statically checkable "
                    "— pass explicit label keys (or pragma a reviewed "
                    "wrapper)", token="**"))
            continue
        if kw.arg == "name":
            if not pragmas.allows_span("telemetry-collision", call):
                findings.append(Finding(
                    rel, lineno, "telemetry-collision",
                    "label key 'name' collides with labeled()'s "
                    "positional parameter — TypeError at runtime "
                    "(the PR 12 breaker bug); pick another key",
                    token="name"))
            continue
        if kw.arg not in LABEL_KEYS:
            if not pragmas.allows_span("telemetry-cardinality", call):
                findings.append(Finding(
                    rel, lineno, "telemetry-cardinality",
                    "unknown label key %r — extend the reviewed "
                    "LABEL_KEYS vocabulary (analysis/graftlint.py) "
                    "only for bounded label sets" % kw.arg,
                    token=kw.arg))
            continue
        tainted = _names_in(kw.value) & LABEL_VALUE_DENY
        if tainted and not pragmas.allows_span(
                "telemetry-cardinality", call):
            findings.append(Finding(
                rel, lineno, "telemetry-cardinality",
                "label %r value derives from request data (%s) — "
                "unbounded cardinality mints one series per request"
                % (kw.arg, ", ".join(sorted(tainted))),
                token="%s=%s" % (kw.arg, ",".join(sorted(tainted)))))


def check_telemetry(tree, rel, pragmas, findings):
    in_telemetry = rel.replace(os.sep, "/").endswith(
        "znicz_tpu/core/telemetry.py")
    constants = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            s = _const_str(node.value)
            if s is not None:
                constants[node.targets[0].id] = s

    def api_name(func):
        if isinstance(func, ast.Attribute):
            chain = _attr_chain(func)
            if chain and len(chain) >= 2 and \
                    chain[-2] == "telemetry" and \
                    chain[-1] in ("counter", "gauge", "histogram",
                                  "labeled"):
                return chain[-1]
            return None
        if in_telemetry and isinstance(func, ast.Name) and \
                func.id in ("counter", "gauge", "histogram",
                            "labeled"):
            return func.id
        return None

    def resolve_mark(expr):
        expr._graftlint_resolved = _series_static_prefix(expr,
                                                         constants)

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        api = api_name(node.func)
        if api is None:
            continue
        name_arg = node.args[0] if node.args else None
        for kw in node.keywords:
            if kw.arg == "name":
                name_arg = kw.value if api != "labeled" else name_arg
        if api == "labeled":
            if name_arg is not None:
                resolve_mark(name_arg)
                _check_series_name(name_arg, node, rel, pragmas,
                                   findings)
            _check_labels(node, rel, pragmas, findings)
            continue
        # counter/gauge/histogram
        if name_arg is None:
            continue
        if isinstance(name_arg, ast.Call):
            inner_api = api_name(name_arg.func)
            if inner_api == "labeled":
                continue   # the labeled() call is checked on its own
            # wrapper pattern (engine._label(series, **labels)): the
            # first argument must be a checkable series name and the
            # keywords are labels
            if name_arg.args:
                resolve_mark(name_arg.args[0])
                _check_series_name(name_arg.args[0], name_arg, rel,
                                   pragmas, findings)
                _check_labels(name_arg, rel, pragmas, findings)
                continue
            if not pragmas.allows_span("telemetry-series", node):
                findings.append(Finding(
                    rel, name_arg.lineno, "telemetry-series",
                    "series name computed by an opaque call — not "
                    "statically bounded", token="<call>"))
            continue
        resolve_mark(name_arg)
        _check_series_name(name_arg, node, rel, pragmas, findings)


# ---------------------------------------------------------------------------
# Lock-guard discipline
# ---------------------------------------------------------------------------

_LOCK_FACTORIES = {
    ("threading", "Lock"), ("threading", "RLock"),
    ("threading", "Condition"),
    ("locksmith", "lock"), ("locksmith", "rlock"),
    ("locksmith", "condition"),
}


def _is_lock_factory(node):
    if not isinstance(node, ast.Call):
        return False
    chain = _attr_chain(node.func)
    return bool(chain) and len(chain) >= 2 and \
        (chain[-2], chain[-1]) in _LOCK_FACTORIES


def _self_attr_target(node):
    """'self.X' / 'self.X[...]' -> 'X' (write target extraction)."""
    if isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and \
            node.value.id == "self":
        return node.attr
    return None


def check_lock_guard(tree, rel, pragmas, findings):
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        methods = [n for n in cls.body
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))]
        lock_attrs = set()
        for m in methods:
            for node in ast.walk(m):
                if isinstance(node, ast.Assign) and \
                        _is_lock_factory(node.value):
                    for t in node.targets:
                        attr = _self_attr_target(t)
                        if attr is not None:
                            lock_attrs.add(attr)
        if not lock_attrs:
            continue
        writes = []   # (attr, lineno, held frozenset, method name)

        def visit(node, held, init):
            if isinstance(node, ast.With):
                extra = set()
                for item in node.items:
                    attr = _self_attr_target(item.context_expr)
                    if attr in lock_attrs:
                        extra.add(attr)
                inner = held | extra
                for child in node.body:
                    visit(child, inner, init)
                return
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef, ast.Lambda)):
                # a nested function runs LATER, not under the lock
                body = node.body if not isinstance(node, ast.Lambda) \
                    else [node.body]
                nested_held = frozenset()
                g = pragmas.guarded.get(node.lineno)
                if g in lock_attrs:
                    nested_held = frozenset((g,))
                for child in body:
                    visit(child, set(nested_held), init)
                return
            if isinstance(node, (ast.Assign, ast.AugAssign,
                                 ast.AnnAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    elts = t.elts if isinstance(t, (ast.Tuple,
                                                    ast.List)) else [t]
                    for e in elts:
                        attr = _self_attr_target(e)
                        if attr is not None and not init:
                            writes.append((attr, node.lineno,
                                           frozenset(held)))
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _MUTATORS:
                attr = _self_attr_target(node.func.value)
                if attr is not None and not init:
                    writes.append((attr, node.lineno, frozenset(held)))
            for child in ast.iter_child_nodes(node):
                visit(child, held, init)

        for m in methods:
            init = m.name in ("__init__", "__new__")
            held = set()
            g = pragmas.guarded.get(m.lineno)
            if g in lock_attrs:
                held.add(g)
            for child in m.body:
                visit(child, held, init)

        guarded_by = {}   # attr -> set of locks it is written under
        for attr, _, held in writes:
            if held:
                guarded_by.setdefault(attr, set()).update(held)
        for attr, lineno, held in writes:
            locks = guarded_by.get(attr)
            if not locks or held & locks:
                continue
            if attr in lock_attrs:
                continue
            if pragmas.allows("lock-guard", lineno):
                continue
            findings.append(Finding(
                rel, lineno, "lock-guard",
                "%s.%s is written under %s elsewhere but unguarded "
                "here — take the lock, or mark the method "
                "'# graftlint: guarded-by(self.%s)' if the caller "
                "already holds it"
                % (cls.name, attr,
                   "/".join("self.%s" % x for x in sorted(locks)),
                   sorted(locks)[0]),
                token="%s.%s" % (cls.name, attr)))


# ---------------------------------------------------------------------------
# JAX tracing hazards
# ---------------------------------------------------------------------------

def _is_jax_jit(func):
    chain = _attr_chain(func)
    return bool(chain) and chain[-2:] == ["jax", "jit"]


def _is_lax_scan(func):
    chain = _attr_chain(func)
    return bool(chain) and chain[-2:] == ["lax", "scan"]


def _static_params(fn, call):
    """Parameter names a jit call marks static (static_argnums /
    static_argnames) — their values are Python constants, not traced."""
    if call is None:
        return frozenset()
    names = set()
    ordered = [a.arg for a in fn.args.posonlyargs + fn.args.args] \
        if not isinstance(fn, ast.Lambda) \
        else [a.arg for a in fn.args.args]
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for n in ast.walk(kw.value):
                s = _const_str(n)
                if s is not None:
                    names.add(s)
        elif kw.arg == "static_argnums":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and \
                        isinstance(n.value, int):
                    if 0 <= n.value < len(ordered):
                        names.add(ordered[n.value])
    return frozenset(names)


def check_jax(tree, rel, pragmas, findings):
    # collect every def/lambda by name for call-site resolution
    defs = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, node)
    traced = []   # (fn node, why, static param names)

    def resolve_fn(arg):
        if isinstance(arg, ast.Lambda):
            return arg
        if isinstance(arg, ast.Name):
            return defs.get(arg.id)
        return None

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                call = dec if isinstance(dec, ast.Call) else None
                if _is_jax_jit(dec) or (
                        call is not None
                        and (_is_jax_jit(call.func)
                             or (_attr_chain(call.func) or [])[-1:]
                             == ["partial"]
                             and any(_is_jax_jit(a)
                                     for a in call.args))):
                    traced.append((node, "jit",
                                   _static_params(node, call)))
                    _check_donation(node, call, rel, pragmas,
                                    findings)
        elif isinstance(node, ast.Call):
            if _is_jax_jit(node.func) and node.args:
                fn = resolve_fn(node.args[0])
                if fn is not None:
                    traced.append((fn, "jit",
                                   _static_params(fn, node)))
                    _check_donation(fn, node, rel, pragmas, findings)
            elif _is_lax_scan(node.func) and node.args:
                fn = resolve_fn(node.args[0])
                if fn is not None:
                    traced.append((fn, "scan", frozenset()))

    seen = set()
    for fn, why, static in traced:
        if id(fn) in seen:
            continue
        seen.add(id(fn))
        _scan_traced_body(fn, why, static, rel, pragmas, findings)


def _fn_params(fn):
    args = fn.args
    names = [a.arg for a in args.args + args.posonlyargs +
             args.kwonlyargs]
    if args.vararg:
        names.append(args.vararg.arg)
    return set(n for n in names if n != "self")


def _check_donation(fn, call, rel, pragmas, findings):
    acc = sorted(p for p in _fn_params(fn) if _ACC_PARAM_RE.search(p))
    if not acc:
        return
    if call is not None and any(
            kw.arg in ("donate_argnums", "donate_argnames")
            for kw in call.keywords):
        return
    lineno = call.lineno if call is not None else fn.lineno
    if pragmas.allows("jax-donation", lineno):
        return
    findings.append(Finding(
        rel, lineno, "jax-donation",
        "jit of %r takes accumulator-shaped arg(s) %s without "
        "donate_argnums — the carried buffer is copied every dispatch"
        % (fn.name if hasattr(fn, "name") else "<lambda>",
           ", ".join(acc)),
        token=(fn.name if hasattr(fn, "name") else "<lambda>")))


def _scan_traced_body(fn, why, static, rel, pragmas, findings):
    params = _fn_params(fn) - static
    body = fn.body if not isinstance(fn, ast.Lambda) else [fn.body]
    for stmt in body:
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            lineno = node.lineno
            chain = _attr_chain(node.func) or []
            # host syncs on traced names
            if isinstance(node.func, ast.Name) and \
                    node.func.id in ("float", "int", "bool") and \
                    node.args and (_names_in(node.args[0]) & params) \
                    and not any(
                        isinstance(n, ast.Attribute)
                        and n.attr in ("shape", "ndim", "size")
                        for n in ast.walk(node.args[0])):
                # .shape/.ndim metadata is static even on traced values
                if not pragmas.allows("jax-host-sync", lineno):
                    findings.append(Finding(
                        rel, lineno, "jax-host-sync",
                        "%s() on a traced value inside a %s body is "
                        "a device sync" % (node.func.id, why),
                        token=node.func.id))
            elif chain[-1:] == ["item"] and len(chain) >= 2:
                if not pragmas.allows("jax-host-sync", lineno):
                    findings.append(Finding(
                        rel, lineno, "jax-host-sync",
                        ".item() inside a %s body is a device sync"
                        % why, token="item"))
            elif len(chain) >= 2 and chain[0] in ("numpy", "np") and \
                    chain[1] in ("asarray", "array") and node.args \
                    and (_names_in(node.args[0]) & params):
                if not pragmas.allows("jax-host-sync", lineno):
                    findings.append(Finding(
                        rel, lineno, "jax-host-sync",
                        "%s on a traced value inside a %s body "
                        "forces a host transfer"
                        % (".".join(chain[:2]), why),
                        token=".".join(chain[:2])))
            # wall clock
            elif chain[:1] == ["time"] and len(chain) == 2 and \
                    chain[1] in ("time", "monotonic", "perf_counter",
                                 "sleep"):
                if not pragmas.allows("jax-time", lineno):
                    findings.append(Finding(
                        rel, lineno, "jax-time",
                        "time.%s() inside a %s body is baked in at "
                        "trace time (and syncs nothing)"
                        % (chain[1], why), token="time." + chain[1]))
            # Python / numpy RNG
            elif (chain[:1] == ["random"] and len(chain) >= 2) or (
                    len(chain) >= 3 and chain[0] in ("numpy", "np")
                    and chain[1] == "random"):
                if not pragmas.allows("jax-rng", lineno):
                    findings.append(Finding(
                        rel, lineno, "jax-rng",
                        "Python/numpy RNG inside a %s body is drawn "
                        "ONCE at trace time — use jax.random with a "
                        "threaded key" % why,
                        token=".".join(chain[:2])))


# ---------------------------------------------------------------------------
# Gate discipline
# ---------------------------------------------------------------------------

def check_gate_order(tree, rel, pragmas, findings):
    spec = None
    rel_posix = rel.replace(os.sep, "/")
    for suffix, s in GATED_MODULES.items():
        if rel_posix.endswith(suffix):
            spec = s
            break
    if spec is None:
        return
    gates = set(spec["gates"])
    required = set(spec["required"])

    for fn in tree.body:
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if fn.name.startswith("_") and fn.name not in required:
            continue
        if fn.name in gates or fn.name in ("enable", "disable",
                                           "reset"):
            continue
        if pragmas.allows("gate-order", fn.lineno):
            continue
        gate_line = None
        hot = None   # (lineno, what) of the first hot touch
        for node in _walk(fn):
            if node is fn:
                continue
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name) and \
                    node.func.id in gates:
                gate_line = node.lineno
                break
            if hot is not None:
                continue
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                mod = getattr(node, "module", None) or ",".join(
                    a.name for a in node.names)
                if mod.split(".")[0] == "jax":
                    hot = (node.lineno, "jax import")
            elif isinstance(node, ast.Attribute):
                chain = _attr_chain(node)
                if not chain:
                    continue
                if chain[0] in ("jax", "jnp"):
                    hot = (node.lineno, "jax touch")
                elif chain[:2] == ["root", "common"]:
                    if chain[-1] == "enabled":
                        continue   # the gate's own knob
                    hot = (node.lineno,
                           "config walk root.%s" % ".".join(chain[1:]))
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "get" and node.args:
                key = _const_str(node.args[0])
                base = _attr_chain(node.func.value)
                if key not in (None, "enabled") and base and \
                        (base[0].endswith("cfg")
                         or base[:2] == ["root", "common"]):
                    hot = (node.lineno, "config read %r" % key)
        if fn.name in required and gate_line is None:
            findings.append(Finding(
                rel, fn.lineno, "gate-order",
                "%s() is a hot entry point of a disabled-by-default "
                "subsystem and never checks the %s gate"
                % (fn.name, "/".join(sorted(gates))), token=fn.name))
        elif gate_line is not None and hot is not None:
            findings.append(Finding(
                rel, hot[0], "gate-order",
                "%s() does %s before the gate at line %d — the "
                "disabled path must be ONE predicate"
                % (fn.name, hot[1], gate_line), token=fn.name))


def check_thread_name(tree, rel, pragmas, findings):
    """Every thread the codebase spawns must carry a stable
    ``znicz:<component>`` name — the thread-name registry half of the
    continuous profiler's contract (ISSUE 18, core/pyprof.py): the
    sampler attributes stack samples BY THREAD NAME, so a thread
    constructed without one surfaces as ``Thread-12`` and every one
    of its samples lands in the ``unnamed`` bucket.  Flags
    ``threading.Thread(...)`` construction without ``name=`` and
    ``ThreadPoolExecutor(...)`` without ``thread_name_prefix=``
    (tests are style-scope only and exempt; a ``**kwargs`` splat is
    trusted to carry the name)."""
    for node in _walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if isinstance(node.func, ast.Attribute):
            fname = node.func.attr
        elif isinstance(node.func, ast.Name):
            fname = node.func.id
        else:
            continue
        if fname not in ("Thread", "ThreadPoolExecutor"):
            continue
        kw = "name" if fname == "Thread" else "thread_name_prefix"
        passed = {k.arg for k in node.keywords}
        if None in passed or kw in passed:
            continue
        if pragmas.allows("thread-name", node.lineno):
            continue
        findings.append(Finding(
            rel, node.lineno, "thread-name",
            "%s(...) constructed without %s= — every spawned thread "
            "needs a stable znicz:<component> name so pyprof sample "
            "attribution never reads Thread-N (core/pyprof.py "
            "thread_name())" % (fname, kw), token=fname))


# ---------------------------------------------------------------------------
# Legacy style checks (tools/lint.py heritage)
# ---------------------------------------------------------------------------

def check_style(tree, lines, rel, pragmas, findings):
    rel_posix = rel.replace(os.sep, "/")
    for i, line in enumerate(lines, 1):
        stripped = line.rstrip("\n")
        indent = stripped[:len(stripped) - len(stripped.lstrip())]
        if "\t" in indent and not pragmas.allows("tabs", i):
            findings.append(Finding(rel, i, "tabs",
                                    "tab in indentation"))
        if stripped != stripped.rstrip() and \
                not pragmas.allows("trailing-whitespace", i):
            findings.append(Finding(rel, i, "trailing-whitespace",
                                    "trailing whitespace"))
        if len(stripped) > MAX_LINE and "noqa" not in stripped and \
                not pragmas.allows("line-length", i):
            findings.append(Finding(
                rel, i, "line-length",
                "line too long (%d > %d)" % (len(stripped),
                                             MAX_LINE)))
    findings.extend(_unused_imports(tree, lines, rel, pragmas))
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None \
                and not pragmas.allows("bare-except", node.lineno):
            findings.append(Finding(rel, node.lineno, "bare-except",
                                    "bare except"))
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "print"
                and rel_posix.startswith(LIB_DIRS)
                and not any(p in rel_posix for p in PRINT_OK)
                and node.lineno <= len(lines)
                and "noqa" not in lines[node.lineno - 1]
                and not pragmas.allows("library-print", node.lineno)):
            findings.append(Finding(
                rel, node.lineno, "library-print",
                "print() in library code (use the logger)"))


def _unused_imports(tree, lines, rel, pragmas):
    imported = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                name = alias.asname or alias.name.split(".")[0]
                imported[name] = node.lineno
        elif isinstance(node, ast.ImportFrom):
            for alias in node.names:
                if alias.name == "*":
                    continue
                imported[alias.asname or alias.name] = node.lineno
    if not imported:
        return []
    used = set()
    string_text = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            n = node
            while isinstance(n, ast.Attribute):
                n = n.value
            if isinstance(n, ast.Name):
                used.add(n.id)
        elif isinstance(node, ast.Constant) and \
                isinstance(node.value, str):
            string_text.append(node.value)
    # the legacy checker's blind spot: a name referenced only inside a
    # string constant — an f-string template kept as a plain string, a
    # docstring doctest (`>>> numpy.ones(...)`) — is still a use.
    # Only DOTTED usage (`name.attr`) or a doctest line mentioning the
    # name counts: a bare prose word ("baked in at trace time") must
    # not grandfather a dead `import time`
    blob = "\n".join(string_text)
    out = []
    for name, lineno in imported.items():
        if name in used:
            continue
        line = lines[lineno - 1] if lineno <= len(lines) else ""
        if "noqa" in line or pragmas.allows("unused-import", lineno):
            continue
        esc = re.escape(name)
        if blob and (re.search(r"\b%s\s*\.\s*\w" % esc, blob)
                     or re.search(r"^\s*>>>.*\b%s\b" % esc, blob,
                                  re.MULTILINE)):
            continue
        out.append(Finding(rel, lineno, "unused-import",
                           "unused import %r" % name, token=name))
    return out


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

#: directories the legacy style checks cover (lint.py heritage)
STYLE_SCAN = ("znicz_tpu", "tests", "tools")
#: scope of the project-invariant checkers (ISSUE 13: the library and
#: the tools — tests intentionally monkeypatch around every invariant
#: and are style-checked only)
INVARIANT_SCAN = ("znicz_tpu", "tools")
SKIP_PARTS = ("__pycache__",)


def check_source(src, rel, vocab=None, style=True, invariants=True):
    """Run every applicable checker over one source blob; the unit of
    both the CLI and the selftest fixtures."""
    findings = []
    lines = src.splitlines()
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as e:
        return [Finding(rel, e.lineno or 0, "syntax",
                        "syntax error: %s" % e.msg)]
    pragmas = _Pragmas(lines)
    if style:
        check_style(tree, lines, rel, pragmas, findings)
    if invariants:
        if vocab is None:
            vocab = load_vocabulary()
        knobs, nodes = vocab
        check_knobs(tree, rel, pragmas, knobs, nodes, findings)
        check_telemetry(tree, rel, pragmas, findings)
        check_lock_guard(tree, rel, pragmas, findings)
        check_jax(tree, rel, pragmas, findings)
        check_gate_order(tree, rel, pragmas, findings)
        check_thread_name(tree, rel, pragmas, findings)
    return findings


def iter_py(root):
    """(path, rel, style?, invariants?) over the repo scan scope."""
    seen = set()
    for base, style, inv in (
            ("znicz_tpu", True, True),
            ("tests", True, False),
            ("tools", True, True)):
        top = os.path.join(root, base)
        for dirpath, _, filenames in os.walk(top):
            if any(p in dirpath for p in SKIP_PARTS):
                continue
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                rel = os.path.relpath(path, root)
                if rel in seen:
                    continue
                seen.add(rel)
                yield path, rel, style, inv


def run(root, vocab=None):
    """Scan the whole tree; returns the finding list."""
    if vocab is None:
        vocab = load_vocabulary()
    findings = []
    for path, rel, style, inv in iter_py(root):
        with open(path, encoding="utf-8") as f:
            src = f.read()
        findings.extend(check_source(src, rel, vocab=vocab,
                                     style=style, invariants=inv))
    findings.sort(key=lambda f: (f.path, f.line, f.check))
    return findings


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------

def load_baseline(path):
    """Fingerprints from the reviewed baseline file (``path :: check
    :: token`` lines; '#' comments and blanks ignored)."""
    entries = set()
    if not path or not os.path.exists(path):
        return entries
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if line and not line.startswith("#"):
                entries.add(line)
    return entries


def apply_baseline(findings, baseline):
    """(kept, suppressed, stale-entries)."""
    kept, suppressed = [], []
    hit = set()
    for f in findings:
        if f.fingerprint in baseline:
            suppressed.append(f)
            hit.add(f.fingerprint)
        else:
            kept.append(f)
    return kept, suppressed, sorted(baseline - hit)


# ---------------------------------------------------------------------------
# Selftest — a seeded violation + clean twin per checker (the CI run
# proves every checker can still reject before trusting a clean scan)
# ---------------------------------------------------------------------------

#: check id -> {rel, bad, clean}.  The violating line carries the word
#: "seeded"; the clean twin must produce ZERO findings of any kind.
FIXTURES = {
    "knob-vocabulary": {
        "rel": "znicz_tpu/fixture_knob.py",
        "bad": '''\
from znicz_tpu.core.config import root

limit = root.common.serving.breaker_treshold  # seeded typo
''',
        "clean": '''\
from znicz_tpu.core.config import root

limit = root.common.serving.get("breaker_threshold", 5)
''',
    },
    "telemetry-series": {
        "rel": "znicz_tpu/fixture_series.py",
        "bad": '''\
from znicz_tpu.core import telemetry

telemetry.counter("oops.requests").inc()  # seeded bad family
''',
        "clean": '''\
from znicz_tpu.core import telemetry

telemetry.counter("serving.predictions").inc()
''',
    },
    "telemetry-collision": {
        "rel": "znicz_tpu/fixture_collision.py",
        "bad": '''\
from znicz_tpu.core import telemetry


def note(which):
    telemetry.gauge(telemetry.labeled(
        "serving.breaker_open", name=which)).set(1)  # seeded
''',
        "clean": '''\
from znicz_tpu.core import telemetry


def note(which):
    telemetry.gauge(telemetry.labeled(
        "serving.breaker_open", breaker=which)).set(1)
''',
    },
    "telemetry-cardinality": {
        "rel": "znicz_tpu/fixture_cardinality.py",
        "bad": '''\
from znicz_tpu.core import telemetry


def note(request_id):
    telemetry.counter(telemetry.labeled(
        "serving.rejected", model=request_id)).inc()  # seeded
''',
        "clean": '''\
from znicz_tpu.core import telemetry


def note(model):
    telemetry.counter(telemetry.labeled(
        "serving.rejected", model=model)).inc()
''',
    },
    "lock-guard": {
        "rel": "znicz_tpu/fixture_lock.py",
        "bad": '''\
import threading


class Box(object):
    def __init__(self):
        self._lock = threading.Lock()
        self.items = []

    def put(self, x):
        with self._lock:
            self.items.append(x)

    def drop(self):
        self.items = []  # seeded unguarded write
''',
        "clean": '''\
import threading


class Box(object):
    def __init__(self):
        self._lock = threading.Lock()
        self.items = []

    def put(self, x):
        with self._lock:
            self.items.append(x)

    def drop(self):
        with self._lock:
            self.items = []
''',
    },
    "jax-host-sync": {
        "rel": "znicz_tpu/fixture_sync.py",
        "bad": '''\
import jax


def step(x):
    return float(x) + 1.0  # seeded host sync


fn = jax.jit(step)
''',
        "clean": '''\
import jax


def step(x):
    return x + 1.0


fn = jax.jit(step)
''',
    },
    "jax-rng": {
        "rel": "znicz_tpu/fixture_rng.py",
        "bad": '''\
import jax
import numpy


def body(carry, x):
    noise = numpy.random.random()  # seeded trace-time draw
    return carry + noise, x


out = jax.lax.scan(body, 0.0, None)
''',
        "clean": '''\
import jax


def body(carry, x):
    return carry + x, x


out = jax.lax.scan(body, 0.0, None)
''',
    },
    "jax-time": {
        "rel": "znicz_tpu/fixture_time.py",
        "bad": '''\
import time

import jax


def step(x):
    return x + time.time()  # seeded trace-time clock


fn = jax.jit(step)
''',
        "clean": '''\
import time

import jax


def step(x):
    return x + 1.0


fn = jax.jit(step)
t0 = time.time()
''',
    },
    "jax-donation": {
        "rel": "znicz_tpu/fixture_donate.py",
        "bad": '''\
import jax


def step(acc, x):
    return acc + x


fn = jax.jit(step)  # seeded copy per dispatch
''',
        "clean": '''\
import jax


def step(acc, x):
    return acc + x


fn = jax.jit(step, donate_argnums=(0,))
''',
    },
    "gate-order": {
        "rel": "znicz_tpu/core/health.py",
        "bad": '''\
from znicz_tpu.core.config import root


def enabled():
    return bool(root.common.health.get("enabled", False))


def observe_loss(value):
    interval = root.common.health.get("interval", 1)  # seeded
    if not enabled():
        return None
    return interval + value
''',
        "clean": '''\
from znicz_tpu.core.config import root


def enabled():
    return bool(root.common.health.get("enabled", False))


def observe_loss(value):
    if not enabled():
        return None
    return root.common.health.get("interval", 1) + value


def check_training_step(steps=1):
    if not enabled():
        return None
    return steps


def check_gd_unit(unit):
    if not enabled():
        return None
    return unit
''',
    },
    "thread-name": {
        "rel": "znicz_tpu/fixture_thread.py",
        "bad": '''\
import threading


def start(worker):
    t = threading.Thread(target=worker, daemon=True)  # seeded
    t.start()
    return t
''',
        "clean": '''\
import threading


def start(worker):
    t = threading.Thread(target=worker, name="znicz:worker",
                         daemon=True)
    t.start()
    return t
''',
    },
    "syntax": {
        "rel": "znicz_tpu/fixture_syntax.py",
        "bad": "def broken(:\n",
        "clean": "X = 1\n",
    },
    "tabs": {
        "rel": "znicz_tpu/fixture_tabs.py",
        "bad": "def f():\n\treturn 1  # seeded tab indent\n",
        "clean": "def f():\n    return 1\n",
    },
    "trailing-whitespace": {
        "rel": "znicz_tpu/fixture_ws.py",
        "bad": "X = 1  # seeded trailing blanks   \n",
        "clean": "X = 1\n",
    },
    "line-length": {
        "rel": "znicz_tpu/fixture_len.py",
        "bad": ("X = 1  # seeded: " + "x" * 70 + "\n"),
        "clean": "X = 1\n",
    },
    "unused-import": {
        "rel": "znicz_tpu/fixture_imports.py",
        "bad": '''\
import os  # seeded: never referenced anywhere
import math

S = f"pi is {math.pi}"
''',
        # the legacy checker's blind spot: names used only inside a
        # docstring doctest (plain string constants) were flagged
        "clean": '''\
"""Helpers.

>>> import znicz_tpu.fixture_imports
>>> math.floor(1.5)
1
"""
import math

S = f"pi is {math.pi}"
''',
    },
    "bare-except": {
        "rel": "znicz_tpu/fixture_except.py",
        "bad": '''\
try:
    X = 1
except:  # seeded
    X = 2
''',
        "clean": '''\
try:
    X = 1
except ValueError:
    X = 2
''',
    },
    "library-print": {
        "rel": "znicz_tpu/fixture_print.py",
        "bad": '''\
def report(x):
    print(x)  # seeded stdout in library code
''',
        "clean": '''\
import logging


def report(x):
    logging.getLogger("fixture").info("%s", x)
''',
    },
}


def selftest(vocab=None):
    """Prove every checker still rejects its seeded violation (with
    the right check id and line) and passes the clean twin.  Returns a
    list of problem strings — empty means the selftest passed."""
    if vocab is None:
        vocab = load_vocabulary()
    problems = []
    for check, fx in sorted(FIXTURES.items()):
        bad = check_source(fx["bad"], fx["rel"], vocab=vocab)
        hits = [f for f in bad if f.check == check]
        if not hits:
            problems.append(
                "%s: seeded violation NOT rejected (findings: %s)"
                % (check, [str(f) for f in bad]))
        elif check != "syntax":
            expected = next(
                (i for i, line in
                 enumerate(fx["bad"].splitlines(), 1)
                 if "seeded" in line), None)
            if expected is not None and \
                    not any(f.line == expected for f in hits):
                problems.append(
                    "%s: rejected at line(s) %s, expected %d"
                    % (check, sorted(f.line for f in hits), expected))
        clean = check_source(fx["clean"], fx["rel"], vocab=vocab)
        if clean:
            problems.append(
                "%s: clean twin produced findings: %s"
                % (check, [str(f) for f in clean]))
    return problems
