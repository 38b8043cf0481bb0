"""Device time per layer scope, from the profiler's xplane.

The program names its device ops at trace time (``jax.named_scope`` in
``znicz_tpu/parallel/fused.py``): ``L00.conv`` ... per layer (the backward
ops inherit it as ``transpose(jvp(L00.conv))``), ``update.L00`` per layer's
optimizer step, ``gather``, ``acc``, ``loss``, ``eval_stats``.  XLA keeps the
name as each instruction's ``op_name``, and the TPU runtime writes it into
the xplane as the ``tf_op`` stat of the event's *metadata* (found on the
chip, PR 25).  ``jax.profiler.ProfileData`` shows an event's own stats
only, not its metadata's, so this module reads the file's protobuf wire
format itself: planes, the busiest device plane's event metadata, and the
events of its "XLA Ops" line.  No protobuf library is needed, and
``tests/test_scoped_trace.py`` builds a small xplane by hand with the same
few field numbers (``tsl/profiler/protobuf/xplane.proto``).

Scopes are reduced over the **busiest device plane only**, chosen as
``lib/trace.py`` chooses it (by the union of its ops' intervals, so every
device plane's ops line is parsed once), memoised for all readers of a run.

A fusion that spans two scopes carries its root's ``op_name`` and is booked
to that scope whole; a collective is booked to ``grad_exchange`` unless it
stands under ``acc`` (GSPMD puts the gradient all-reduce where the partial
sums arise, so it carries the backward product's name).
"""

import re
import sys
import time

from benchmarks.lib import trace as trace_mod

#: the module of a train window (softmax and MSE), as the runtime names it
#: on the modules line: ``jit_window_fn(<program id>)``
TRAIN_MODULE = "jit_window_fn"
MODULE = re.compile(r"^(.*)\((\d+)\)$")
SCOPE = re.compile(
    r"^(update\.L\d\d|L\d\d\.[a-z_]+|gather|acc|loss|eval_stats)$")
KIND_OF = re.compile(r"^L\d\d\.([a-z_]+)$")
#: what jax's transformations wrap a scope in (``jit(f)`` names a function)
WRAPPED = re.compile(r"^(?:jvp|transpose|vmap|remat|checkpoint)\((.*)\)$")

# -- protobuf wire format -----------------------------------------------------


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of one message: ints for varints, memoryview
    slices for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError("wire type %d in an xplane" % wire)
        yield key >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view):
    key = value = None
    for num, val in fields(view):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


# -- xplane.proto, as far as it is read -----------------------------------------
# XSpace.planes=1; XPlane: name=2 lines=3 event_metadata=4 stat_metadata=5;
# XLine: name=2 events=4; XEvent: metadata_id=1 offset_ps=2 duration_ps=3;
# XEventMetadata: id=1 name=2 stats=5; XStatMetadata: id=1 name=2;
# XStat: metadata_id=1 str_value=5 uint64_value=3 int64_value=4 ref_value=7

def _plane_parts(plane):
    name, lines, event_meta, stat_meta = "", [], [], []
    for num, val in fields(plane):
        if num == 2:
            name = _text(val)
        elif num == 3:
            lines.append(val)
        elif num == 4:
            event_meta.append(val)
        elif num == 5:
            stat_meta.append(val)
    return name, lines, event_meta, stat_meta


def _line_name(line):
    for num, val in fields(line):
        if num == 2:
            return _text(val)
    return ""


def _events(line):
    """[(metadata id, offset_ps, duration_ps)] of a line."""
    out = []
    for num, val in fields(line):
        if num != 4:
            continue
        meta = offset = dur = 0
        for n2, v2 in fields(val):
            if n2 == 1:
                meta = v2
            elif n2 == 2:
                offset = v2
            elif n2 == 3:
                dur = v2
        out.append((meta, offset, dur))
    return out


def _stat_names(stat_meta):
    names = {}
    for entry in stat_meta:
        _, value = _map_entry(entry)
        sid, name = 0, ""
        for num, val in fields(value):
            if num == 1:
                sid = val
            elif num == 2:
                name = _text(val)
        names[sid] = name
    return names


def _event_metadata(event_meta, stat_names):
    """{metadata id: (name, op_name, program id)}: ``tf_op`` is the HLO
    ``op_name``, ``program_id`` the module the instruction belongs to (a
    copy that the layout pass added has no ``op_name`` but has that)."""
    ids = {name: sid for sid, name in stat_names.items()}
    tf_op, program_id = ids.get("tf_op"), ids.get("program_id")
    out = {}
    for entry in event_meta:
        _, value = _map_entry(entry)
        mid, name, op_name, program = 0, "", "", 0
        for num, val in fields(value):
            if num == 1:
                mid = val
            elif num == 2:
                name = _text(val)
            elif num == 5:
                stat_id, text, number = 0, None, 0
                for n2, v2 in fields(val):
                    if n2 == 1:
                        stat_id = v2
                    elif n2 == 5:
                        text = _text(v2)
                    elif n2 == 7:
                        # a string held once in the stat metadata
                        text = stat_names.get(v2, "")
                    elif n2 in (3, 4):
                        number = v2
                if stat_id == tf_op and text is not None:
                    op_name = text
                elif stat_id == program_id:
                    program = number
        out[mid] = (name, op_name, program)
    return out


# -- scopes ---------------------------------------------------------------------

def scope_of(op_name):
    """(scope, backward) of an HLO ``op_name`` such as
    ``jit(window_fn)/while/body/transpose(jvp(L00.conv))/mul:``: the
    innermost component that is a scope, through its autodiff wrappers.
    The last component is the primitive (``gather`` the primitive is not
    ``gather`` the scope)."""
    for part in reversed(op_name.split("/")[1:-1]):
        inner, backward = part, False
        while True:
            m = WRAPPED.match(inner)
            if not m:
                break
            backward = backward or inner.startswith("transpose(")
            inner = m.group(1)
        if SCOPE.match(inner):
            return inner, backward
    return None, False


def kind_of(scope):
    """``conv`` of ``L00.conv``; None of any other scope."""
    m = KIND_OF.match(scope or "")
    return m.group(1) if m else None


def read_xplane(path):
    """The busiest device plane of an xplane file as ``{"plane": name,
    "ops": [(name, op_name, module, offset_ps, duration_ps)]}`` (leaves
    of its "XLA Ops" line; ``module`` as ``jit_window_fn``), or None where
    no device plane has one.  Busiest as ``lib/trace.reduce_trace`` takes
    it (the union of the leaves' intervals, the first plane of equals), so
    that these readers and ``device_idle_pct`` describe one device."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    best = None
    for num, plane in fields(space):
        if num != 1:
            continue
        name, lines, event_meta, stat_meta = _plane_parts(plane)
        if not name.startswith("/device:TPU"):
            continue
        ops_line = next((line for line in lines
                         if _line_name(line) == trace_mod.OPS_LINE), None)
        if ops_line is None:
            continue
        meta = _event_metadata(event_meta, _stat_names(stat_meta))
        leaves = [(meta.get(mid, ("", "", 0)), offset, dur)
                  for mid, offset, dur in _events(ops_line)]
        leaves = [(m, offset, dur) for m, offset, dur in leaves
                  if not trace_mod.is_wrapper(m[0])]
        busy = trace_mod.busy_ns(leaves)
        if best is None or busy > best[0]:
            best = (busy, name, meta, leaves)
    if best is None:
        return None
    _, name, meta, leaves = best
    modules = {}
    for op, _, _ in meta.values():
        m = MODULE.match(op)
        if m:
            modules[int(m.group(2))] = m.group(1)
    return {"plane": name,
            "ops": [(op, op_name, modules.get(program, ""), offset, dur)
                    for (op, op_name, program), offset, dur in leaves]}


def reduce_scopes(ops):
    """Seconds per (train window or not, scope, backward) and in all, over
    ``read_xplane``'s ops.  The busy time is the union of the ops'
    intervals, as ``lib/trace.py`` takes it."""
    by_scope = {}
    total = 0.0
    for op, op_name, module, _, dur in ops:
        scope, backward = scope_of(op_name)
        if trace_mod.is_collective(op) and scope != "acc":
            scope, backward = "grad_exchange", False
        key = (module == TRAIN_MODULE, scope, backward)
        by_scope[key] = by_scope.get(key, 0.0) + dur / 1e12
        total += dur / 1e12
    busy = trace_mod.busy_ns([(op, off, dur)
                              for op, _, _, off, dur in ops])
    return {"by_scope": by_scope, "sum_s": total, "busy_s": busy / 1e12}


_MEMO = {}


def of_run(ctx):
    """The reduced scopes of a run's trace (``ctx["trace_dir"]``), read
    once; None where the trace has no device plane or none of its ops
    stands under a scope (a commit before PR 25)."""
    trace_dir = ctx.get("trace_dir")
    if not trace_dir:
        return None
    if trace_dir not in _MEMO:
        t0 = time.perf_counter()
        loaded = read_xplane(trace_mod.find_xplane(trace_dir))
        print("[bench scoped_trace] %s: %d ops read in %.1f s"
              % (loaded["plane"] if loaded else "no device plane",
                 len(loaded["ops"]) if loaded else 0,
                 time.perf_counter() - t0), file=sys.stderr, flush=True)
        reduced = None
        if loaded:
            reduced = reduce_scopes(loaded["ops"])
            reduced["plane"] = loaded["plane"]
            if not any(scope and scope != "grad_exchange"
                       for _, scope, _ in reduced["by_scope"]):
                reduced = None      # a program that names no scope
        _MEMO[trace_dir] = reduced
    return _MEMO[trace_dir]


def train_ms_per_step(ctx, wanted):
    """Device milliseconds a train step spends in the scopes ``wanted(scope)``
    picks, over the train window programs' ops; None where no op of the
    trace carries such a scope."""
    red = of_run(ctx)
    steps = ctx["images"] // ctx["batch"]
    if red is None or not steps:
        return None
    seconds = [s for (train, scope, _), s in red["by_scope"].items()
               if train and scope is not None and wanted(scope)]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / steps
