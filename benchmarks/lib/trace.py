"""Reduction of a profiler trace to busy time, idle gaps and op times.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into a
plain dict (the same shape as the small recorded trace under ``tests/``):

    {"devices": {"<plane name>": [[op name, start_ns, dur_ns], ...]},
     "host": [[span name, start_ns, dur_ns], ...]}

Device events are the leaves of each TPU plane's "XLA Ops" line (control
flow wrappers, which span their whole body, are dropped).  Host spans are
the benchmark's own, taken by ``lib/job.py`` around each unit's ``run`` on
the host's clock from the moment the trace started, and put under
``"host"`` by the caller.  Everything below works on that dict, so the
arithmetic is tested without a chip.
"""

import bisect
import glob
import os

#: ops whose event covers other events on the same line
WRAPPERS = ("while", "conditional", "call", "jit_", "pjit")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError("no *.xplane.pb under %s" % trace_dir)
    return paths[-1]


def is_wrapper(name):
    base = name.split(" = ")[0].lstrip("%")
    return base.startswith(WRAPPERS)


def load_xplane(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices = {}
    for plane in pd.planes:
        pname = plane.name
        if pname.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices[pname] = [
                    [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                    for ev in line.events if not is_wrapper(ev.name)]
    return {"devices": devices, "host": []}


# -- arithmetic ---------------------------------------------------------------

def union(intervals):
    """Merged [start, end] list of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(events):
    return sum(e - s for s, e in union(
        (s, s + d) for _, s, d in events if d > 0))


def gaps(events, lo=None, hi=None):
    """Idle [start, end] stretches between merged busy intervals, with the
    stretch before the first and after the last op where ``lo`` / ``hi``
    bound the window."""
    merged = union((s, s + d) for _, s, d in events if d > 0)
    out = []
    prev = lo
    for s, e in merged:
        if prev is not None and s > prev:
            out.append([prev, s])
        prev = e if prev is None else max(prev, e)
    if hi is not None and prev is not None and hi > prev:
        out.append([prev, hi])
    return out


def attribute_gap(gap, host):
    """Name of the host span that covers most of an idle gap."""
    return _Spans(host).covering(gap)


class _Spans(object):
    """Host spans sorted by start, so that a gap looks only at the spans
    that can reach it."""

    def __init__(self, host):
        self.spans = sorted((s, s + d, name) for name, s, d in host)
        self.starts = [sp[0] for sp in self.spans]
        self.longest = max((e - s for s, e, _ in self.spans), default=0.0)

    def covering(self, gap):
        g0, g1 = gap
        best, best_cover = "unattributed", 0.0
        i = bisect.bisect_left(self.starts, g1) - 1
        while i >= 0 and self.spans[i][0] >= g0 - self.longest:
            s, e, name = self.spans[i]
            cover = min(g1, e) - max(g0, s)
            if cover > best_cover:
                best, best_cover = name, cover
            i -= 1
        return best


def is_collective(name):
    base = name.split(" = ")[0]
    return any(c in base for c in COLLECTIVES)


def reduce_trace(trace, window_s, top=10):
    """Busy seconds (mean over devices and of the fullest), the ops that
    took most device time, the longest idle gaps named by host span, and
    the collectives' time on the ops line of the busiest device."""
    devs = trace["devices"]
    if not devs:
        return None
    busy = {name: busy_ns(evs) / 1e9 for name, evs in devs.items()}
    busiest = max(busy, key=busy.get)
    evs = devs[busiest]
    by_op = {}
    for name, _, d in evs:
        key = name.split(" = ")[0]
        by_op[key] = by_op.get(key, 0.0) + d / 1e9
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    by_gap = {}
    spans = _Spans(trace["host"])
    for g in gaps(evs):
        name = spans.covering(g)
        by_gap[name] = by_gap.get(name, 0.0) + (g[1] - g[0]) / 1e9
    idle = sorted(by_gap.items(), key=lambda kv: -kv[1])[:top]
    # the ops line is serial: while a collective's event runs there, no
    # other op does, so its whole duration is exposed
    exposed = sum(d for name, _, d in evs if is_collective(name)) / 1e9
    return {
        "busy_s_mean": sum(busy.values()) / len(busy),
        "busy_s_busiest": busy[busiest],
        "window_s": window_s,
        "device_ops": [[k, v] for k, v in ops],
        "idle_gaps": [[k, v] for k, v in idle],
        "collective_exposed_s": exposed,
        "n_events": sum(len(v) for v in devs.values()),
    }
