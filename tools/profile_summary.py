"""Summarize a profiler trace into a time table.

Usage::

    python tools/profile_summary.py <trace_dir> [top_n]      # XLA xplane
    python tools/profile_summary.py <trace.json> [top_n]     # telemetry
    python tools/profile_summary.py --journal <events.jsonl|blackbox_dir> \
        [--rid RID] [--kind PREFIX]                          # black box
    python tools/profile_summary.py --roofline <report.json> # cost registry
    python tools/profile_summary.py --ledger <report.json>   # memory ledger
    python tools/profile_summary.py --timeseries <ts.json>   # /debug rings
    python tools/profile_summary.py --pyprof <url|file> [top_n]

Input kinds, dispatched on the argument:

* a DIRECTORY is what ``jax.profiler.trace`` (or ``python -m
  znicz_tpu profile``) wrote; the tool finds the ``*.xplane.pb`` planes,
  aggregates DEVICE event durations by HLO op and by coarse category
  (convolution / matmul / reduce / elementwise-fusion / copy-transpose
  / gather-scatter / infeed-outfeed / other), and prints a markdown
  table.  Parsing uses tensorflow's bundled XPlane
  proto only (no tensorboard server needed); the trace itself remains
  viewable in xprof/tensorboard.

* a ``.json`` FILE is a Chrome-trace export from the telemetry span
  tracer (``telemetry.export_trace``); the tool prints the top-N span
  names by SELF time (wall time minus the time spent in nested child
  spans on the same thread) — where the host-side control plane
  actually spends its time.

* ``--journal <file-or-dir>`` is a flight-recorder JSONL
  (``telemetry.export_journal``, or the ``events.jsonl`` of a crash
  report) — or a durable-blackbox segment DIRECTORY
  (``core/blackbox.py``), in which case the tool merges every
  process's durable journal records into one cross-process timeline
  (source-tagged) and reports torn tails loudly.  ``--rid RID``
  keeps only the events naming one request (follow it across
  planes); ``--kind PREFIX`` keeps only matching kinds (``slo``
  matches ``slo.burn``).  The tool prints the event timeline with
  timestamps relative to the first event, health violations and slow
  serving requests highlighted with a ``!!`` marker, and a per-kind
  count summary — the first thing to read after a crash.

* ``--roofline <file.json>`` renders the executable cost registry
  (``profiler.export_report`` output, or a BENCH_*.json carrying a
  ``roofline`` block): per-executable XLA-measured FLOPs, bytes
  accessed, operational intensity and the measured-vs-analytic ratio.

* ``--ledger <file.json>`` renders the device-memory ledger from the
  same inputs: live/high-water bytes, alloc/free counts, the balance
  invariant, and the per-Array-name attribution table.

* ``--timeseries <file.json>`` renders a saved ``GET
  /debug/timeseries`` payload (``core/timeseries.py``): per-series
  point counts, first→last span, last value, min/max and the
  trailing per-second rate for counters — the over-time view of the
  metric registry.

* ``--pyprof <url|file>`` renders a continuous-profiler capture
  (``core/pyprof.py``; a saved ``GET /debug/pyprof`` payload, or an
  ``http(s)://...`` URL fetched live — point it at the fleet router
  for the stitched fleet view): per-component and per-phase
  percentage tables, the top-N hot collapsed stacks, the GIL-wait
  summary from the scheduling-delay probe, and the sampler's own
  overhead self-meter.
"""

import collections
import glob
import json
import os
import sys


def _categorize(name):
    # categorize by the RESULT name only (the text before " = "): the
    # full HLO line lists operand names and layouts, so e.g. an
    # elementwise fusion consuming a %copy-done operand would be
    # miscounted as copy-transpose
    n = name.split(" = ")[0].lower()
    if "convolution" in n:
        return "convolution"
    if "convert" in n:
        # pure dtype casts, NOT convolutions — must precede the bare
        # "conv" test (%convert_element_type would otherwise count as
        # convolution, while %convolution_convert_fusion is caught by
        # the full-word test above)
        return "copy-transpose"
    if "conv" in n:
        return "convolution"
    if "dot" in n or "matmul" in n or "gemm" in n:
        return "matmul"
    if "gather" in n or "scatter" in n or "select-and-scatter" in n \
            or "dynamic-slice" in n or "dynamic-update" in n:
        return "gather-scatter"
    if "reduce-window" in n:
        return "reduce-window"
    if "all-reduce" in n or "all-gather" in n or "collective" in n \
            or "permute" in n:
        return "collective"
    if "reduce" in n or "argmax" in n or "argmin" in n:
        return "reduce"
    if "copy" in n or "transpose" in n or "reshape" in n \
            or "bitcast" in n:
        return "copy-transpose"
    if "infeed" in n or "outfeed" in n or "transfer" in n \
            or "host" in n:
        return "infeed-outfeed"
    if "fusion" in n or "fused" in n:
        return "elementwise-fusion"
    return "other"


def summarize(trace_dir, top_n=25):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise SystemExit("no *.xplane.pb under %s" % trace_dir)
    by_op = collections.Counter()
    by_cat = collections.Counter()
    total_ps = 0
    device_planes = 0
    for path in paths:
        xs = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            xs.ParseFromString(f.read())
        for plane in xs.planes:
            # device planes carry the actual kernel timings; skip the
            # pure-host planes (their spans overlap device time).  TPU
            # planes are named "/device:TPU:N"; on the CPU backend the
            # XLA runtime lines live under "/host:CPU" as tf_xla-* /
            # PjRt client lines.
            name = plane.name.lower()
            is_device = ("tpu" in name or "gpu" in name
                         or "/device" in name)
            is_cpu_xla = name == "/host:cpu"
            if not (is_device or is_cpu_xla):
                continue
            device_planes += 1
            emeta = plane.event_metadata
            # avoid double counting the op hierarchy: TPU planes carry
            # "Steps" / "XLA Modules" (parents) AND "XLA Ops" (leaves) —
            # sum leaves only.  "Async XLA Ops" (DMA copies) run on a
            # separate engine overlapping the compute line; count them
            # separately so overlap is visible, not added to the total.
            lines = {l.name: l for l in plane.lines}
            if "XLA Ops" in lines:
                chosen = [lines["XLA Ops"]]
            elif is_cpu_xla:
                chosen = [l for n, l in lines.items()
                          if "xla-cpu-codegen" in n.lower()]
            else:
                chosen = [l for n, l in lines.items()
                          if "step" not in n.lower()
                          and "module" not in n.lower()
                          and n.lower() != "python"]
            for line in chosen:
                for ev in line.events:
                    op = emeta[ev.metadata_id].name
                    # control-flow wrappers span their whole body — the
                    # body's ops are separate events on the same line,
                    # so counting the wrapper double-counts everything
                    # inside it
                    if op.startswith(("%while", "%conditional",
                                      "%call", "jit_")):
                        continue
                    by_op[op] += ev.duration_ps
                    by_cat[_categorize(op)] += ev.duration_ps
                    total_ps += ev.duration_ps
    if not total_ps:
        raise SystemExit("no device events found (planes scanned: %d "
                         "files)" % len(paths))
    lines = []
    lines.append("trace: %s  (device planes: %d)" % (trace_dir,
                                                     device_planes))
    lines.append("")
    lines.append("| category | time (ms) | share |")
    lines.append("|---|---|---|")
    for cat, ps in by_cat.most_common():
        lines.append("| %s | %.3f | %.1f%% |"
                     % (cat, ps / 1e9, 100.0 * ps / total_ps))
    lines.append("| **total device time** | **%.3f** | |"
                 % (total_ps / 1e9))
    lines.append("")
    lines.append("| top op | time (ms) | share |")
    lines.append("|---|---|---|")
    for op, ps in by_op.most_common(top_n):
        lines.append("| `%s` | %.3f | %.1f%% |"
                     % (op[:70], ps / 1e9, 100.0 * ps / total_ps))
    return "\n".join(lines)


# -- telemetry Chrome-trace summaries ---------------------------------------

def _span_self_times(events):
    """{name: [count, total_us, self_us]} over ph="X" events.  Self
    time = duration minus directly-nested child durations on the same
    (pid, tid) — computed with an interval stack per thread, the same
    containment rule Perfetto uses to draw nesting."""
    by_thread = collections.defaultdict(list)
    for ev in events:
        if ev.get("ph") == "X" and "name" in ev and "ts" in ev:
            by_thread[(ev.get("pid"), ev.get("tid"))].append(ev)
    agg = {}
    for evs in by_thread.values():
        # by start time; ties (same ts) put the LONGER event first so
        # the parent is on the stack before its zero-gap child
        evs.sort(key=lambda e: (float(e["ts"]), -float(e.get("dur", 0))))
        stack = []  # [end_ts, name, dur, child_dur_sum]

        def pop_one():
            end, name, dur, child = stack.pop()
            a = agg.setdefault(name, [0, 0.0, 0.0])
            a[0] += 1
            a[1] += dur
            a[2] += max(0.0, dur - child)
            if stack:
                stack[-1][3] += dur

        for ev in evs:
            ts = float(ev["ts"])
            dur = float(ev.get("dur", 0))
            while stack and stack[-1][0] <= ts + 1e-6:
                pop_one()
            stack.append([ts + dur, ev["name"], dur, 0.0])
        while stack:
            pop_one()
    return agg


def summarize_chrome_trace(path, top_n=25):
    """Markdown top-N spans by self time for a telemetry trace file."""
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    agg = _span_self_times(events)
    if not agg:
        raise SystemExit("no complete (ph=X) events in %s" % path)
    total_self = sum(a[2] for a in agg.values()) or 1.0
    lines = ["trace: %s  (%d spans, %d distinct names)"
             % (path, sum(a[0] for a in agg.values()), len(agg)), ""]
    lines.append("| span | runs | total (ms) | self (ms) | self share |")
    lines.append("|---|---|---|---|---|")
    rows = sorted(agg.items(), key=lambda kv: -kv[1][2])[:top_n]
    for name, (count, total, self_t) in rows:
        lines.append("| `%s` | %d | %.3f | %.3f | %.1f%% |"
                     % (name[:60], count, total / 1e3, self_t / 1e3,
                        100.0 * self_t / total_self))
    return "\n".join(lines)


# -- flight-recorder journal timelines ---------------------------------------

#: event kinds that get the "!!" attention marker in the timeline
_ALARM_KINDS = ("health.violation", "serving.slow_request")


def _format_event(ev, t0):
    """One timeline line: +relative-seconds, marker, kind, fields."""
    t = float(ev.get("t", t0))
    kind = str(ev.get("kind", "?"))
    mark = "!!" if kind in _ALARM_KINDS else "  "
    fields = []
    for k in sorted(ev):
        if k in ("t", "elapsed", "kind"):
            continue
        v = ev[k]
        if isinstance(v, dict):
            v = "{%d keys}" % len(v)
        elif isinstance(v, list) and len(v) > 6:
            v = "[%d items]" % len(v)
        fields.append("%s=%s" % (k, v))
    return "%+12.3fs %s %-22s %s" % (t - t0, mark, kind,
                                     " ".join(fields))


def _load_journal(path, rid=None, kind=None):
    """Journal events from a JSONL file OR a blackbox segment dir
    (merged cross-process, source-tagged).  Returns ``(events,
    torn)`` — ``torn`` maps segment path -> truncated-tail bytes."""
    if os.path.isdir(path):
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from znicz_tpu.core import blackbox
        out = blackbox.timeline(path, n=0, kind=kind, rid=rid)
        return out["events"], out["torn"]
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(json.loads(line))
    if kind:
        events = [e for e in events
                  if str(e.get("kind", "")).startswith(kind)]
    if rid:
        events = [e for e in events
                  if rid in (e.get("rid"), e.get("exemplar_rid"),
                             e.get("request_id"))]
    return events, {}


def summarize_journal(path, rid=None, kind=None):
    """Pretty-print a flight-recorder JSONL (or durable-blackbox
    dir): relative-time event timeline (violations highlighted) +
    per-kind counts; ``rid``/``kind`` filter before printing."""
    events, torn = _load_journal(path, rid=rid, kind=kind)
    if not events:
        raise SystemExit("no%s events in %s"
                         % (" matching" if (rid or kind) else "",
                            path))
    t0 = float(events[0].get("t", 0.0))
    counts = collections.Counter(str(e.get("kind", "?"))
                                 for e in events)
    alarms = sum(counts[k] for k in _ALARM_KINDS if k in counts)
    filters = "".join([", rid=%s" % rid if rid else "",
                       ", kind=%s*" % kind if kind else ""])
    lines = ["journal: %s  (%d events, %d kinds, %d alarm%s%s)"
             % (path, len(events), len(counts), alarms,
                "" if alarms == 1 else "s", filters), ""]
    lines += [_format_event(ev, t0) for ev in events]
    for seg, nbytes in sorted(torn.items()):
        lines.append("!! torn tail: %d byte%s truncated at the end "
                     "of %s (every complete record above was "
                     "recovered)"
                     % (nbytes, "" if nbytes == 1 else "s", seg))
    lines.append("")
    lines.append("| kind | count |")
    lines.append("|---|---|")
    for kind, n in counts.most_common():
        lines.append("| %s%s | %d |"
                     % ("**" if kind in _ALARM_KINDS else "",
                        kind + ("**" if kind in _ALARM_KINDS else ""),
                        n))
    return "\n".join(lines)


# -- profiler report tables (cost registry / memory ledger) ------------------

def _load_report(path):
    with open(path) as f:
        return json.load(f)


def summarize_roofline(path):
    """Markdown table of the executable cost registry — from a
    ``profiler.export_report`` JSON or a BENCH_*.json ``roofline``
    block."""
    doc = _load_report(path)
    roof = doc.get("roofline") if isinstance(doc.get("roofline"), dict) \
        else None
    entries = doc.get("cost_registry")
    if entries is None and roof is not None:
        entries = roof.get("executables")
    if not entries:
        raise SystemExit("no cost-registry entries in %s" % path)
    lines = ["cost registry: %s  (%d executables)" % (path, len(entries))]
    if roof:
        hdr = []
        if roof.get("peak_flops"):
            hdr.append("peak %.0f TFLOP/s%s"
                       % (roof["peak_flops"] / 1e12,
                          " (nominal)" if roof.get("peak_nominal")
                          else ""))
        if roof.get("ridge_intensity_flops_per_byte"):
            hdr.append("ridge %.0f FLOP/B"
                       % roof["ridge_intensity_flops_per_byte"])
        if roof.get("mfu_pct_measured") is not None:
            hdr.append("measured MFU %.2f%%" % roof["mfu_pct_measured"])
        if roof.get("roofline_bound"):
            hdr.append("%s-bound" % roof["roofline_bound"])
        if hdr:
            lines.append("  ".join(hdr))
    lines.append("")
    lines.append("| executable | GFLOP/dispatch | MB accessed "
                 "| FLOP/B | measured/analytic | agree |")
    lines.append("|---|---|---|---|---|---|")
    for e in entries:
        flops = e.get("flops")
        nbytes = e.get("bytes_accessed")
        oi = e.get("operational_intensity")
        ratio = e.get("flops_ratio_measured_vs_analytic")
        agree = e.get("agreement")
        lines.append("| `%s` | %s | %s | %s | %s | %s |" % (
            e["name"][:48],
            "%.3f" % (flops / 1e9) if flops else
            (e.get("error", "-")[:24] if e.get("error") else "-"),
            "%.2f" % (nbytes / 1e6) if nbytes else "-",
            "%.1f" % oi if oi is not None else "-",
            "%.3f" % ratio if ratio is not None else "-",
            {True: "yes", False: "NO", None: "-"}[agree]))
    return "\n".join(lines)


def summarize_ledger(path):
    """Markdown view of the device-memory ledger — totals, the balance
    invariant, and the per-Array-name attribution."""
    doc = _load_report(path)
    led = doc.get("ledger") or doc.get("memory_ledger") \
        or (doc if "by_name" in doc else None)
    if not led:
        raise SystemExit("no ledger block in %s" % path)
    lines = ["device-memory ledger: %s" % path, ""]
    lines.append("live %.3f MiB   high water %.3f MiB   "
                 "allocs %d   frees %d   balanced=%s"
                 % (led.get("live_bytes", 0) / 2 ** 20,
                    led.get("high_water_bytes", 0) / 2 ** 20,
                    led.get("allocs", 0), led.get("frees", 0),
                    led.get("balanced")))
    suspects = doc.get("leak_suspects")
    if suspects:
        lines.append("!! %d leak suspect%s flagged — see the journal "
                     "profiler.leak_suspect events"
                     % (suspects, "" if suspects == 1 else "s"))
    by_name = led.get("by_name") or {}
    if by_name:
        lines.append("")
        lines.append("| array | live bytes |")
        lines.append("|---|---|")
        for name, nbytes in sorted(by_name.items(),
                                   key=lambda kv: -kv[1]):
            lines.append("| `%s` | %d |" % (str(name)[:48], nbytes))
    return "\n".join(lines)


def summarize_timeseries(path):
    """Markdown view of a ``GET /debug/timeseries`` payload: one row
    per ring with span, last value, min/max and (counters) the
    trailing per-second rate."""
    doc = _load_report(path)
    series = doc.get("series") or {}
    if not series:
        raise SystemExit("no time-series rings in %s (is "
                         "root.common.telemetry.timeseries.enabled "
                         "on?)" % path)
    rates = doc.get("rates") or {}
    lines = ["timeseries: %s  (%d series, %s sweeps, interval %s ms)"
             % (path, len(series), doc.get("sweeps", "?"),
                doc.get("interval_ms", "?")), ""]
    lines.append("| series | kind | points | span (s) | last "
                 "| min | max | rate/s |")
    lines.append("|---|---|---|---|---|---|---|---|")
    for name in sorted(series):
        s = series[name]
        pts = s.get("points") or []
        if pts:
            span = pts[-1][0] - pts[0][0]
            values = [p[1] for p in pts]
            last, lo, hi = values[-1], min(values), max(values)
        else:
            span = last = lo = hi = None

        def f(v):
            return "%.6g" % v if isinstance(v, (int, float)) else "-"

        rate = rates.get(name)
        lines.append("| `%s` | %s | %d | %s | %s | %s | %s | %s |"
                     % (name[:48], s.get("kind", "?"), len(pts),
                        f(span), f(last), f(lo), f(hi),
                        f(rate) if rate is not None else "-"))
    return "\n".join(lines)


def _load_pyprof(source):
    """A pyprof payload from a saved JSON file or a live
    ``http(s)://`` URL (``?seconds=`` passes through; the default
    capture window applies otherwise)."""
    if str(source).startswith(("http://", "https://")):
        import urllib.request
        with urllib.request.urlopen(source, timeout=60) as resp:
            return json.loads(resp.read())
    return _load_report(source)


def summarize_pyprof(source, top_n=15):
    """Markdown view of a continuous-profiler capture: component and
    phase percentage tables, top-N hot stacks, GIL-wait and the
    sampler's overhead self-meter."""
    prof = _load_pyprof(source)
    if not prof.get("enabled") and not prof.get("samples"):
        raise SystemExit(
            "profiler disabled and no samples in %s (arm "
            "root.common.profiler.pyprof.enabled, or point at an "
            "armed /debug/pyprof)" % source)
    samples = int(prof.get("samples", 0)) or 1
    lines = ["pyprof: %s  (%d samples, %.1f%% attributed%s)"
             % (source, prof.get("samples", 0),
                float(prof.get("attributed_pct", 0.0)),
                ", fleet-merged over %d sources"
                % len(prof["sources"]) if prof.get("merged") else "")]
    if prof.get("truncated"):
        lines.append("!! %d samples fell off the %d-stack capacity "
                     "ring (raise root.common.profiler.pyprof."
                     "capacity for full fidelity)"
                     % (prof["truncated"], len(prof.get("stacks",
                                                        ()))))
    lines.append("")
    lines.append("| component | samples | share |")
    lines.append("|---|---|---|")
    comps = prof.get("components") or {}
    for comp in sorted(comps, key=lambda c: -comps[c]):
        lines.append("| %s | %d | %.1f%% |"
                     % (comp, comps[comp],
                        100.0 * comps[comp] / samples))
    lines.append("")
    lines.append("| phase | samples | share |")
    lines.append("|---|---|---|")
    phases = prof.get("phases") or {}
    for phase in sorted(phases, key=lambda p: -phases[p]):
        if phases[phase]:
            lines.append("| %s | %d | %.1f%% |"
                         % (phase, phases[phase],
                            100.0 * phases[phase] / samples))
    stacks = prof.get("stacks") or {}
    if stacks:
        lines.append("")
        lines.append("| top stack | samples | share |")
        lines.append("|---|---|---|")
        rows = sorted(stacks.items(), key=lambda kv: -kv[1])[:top_n]
        for key, n in rows:
            lines.append("| `%s` | %d | %.1f%% |"
                         % (key[-90:], n, 100.0 * n / samples))
    gil = prof.get("gil") or {}
    if gil.get("probes"):
        lines.append("")
        lines.append("GIL probe: %d probes, baseline %s ms, "
                     "%.3f ms excess wait attributed"
                     % (gil["probes"], gil.get("baseline_ms", "?"),
                        float(gil.get("wait_ms", 0.0))))
    ovh = prof.get("overhead") or {}
    if ovh:
        lines.append("sampler overhead self-meter: %.3f%% of wall "
                     "inside sample sweeps" % float(ovh.get("pct",
                                                            0.0)))
    return "\n".join(lines)


def _pop_opt(argv, name):
    """Remove ``name VALUE`` from argv and return VALUE (or None)."""
    if name not in argv:
        return None
    i = argv.index(name)
    if i + 1 >= len(argv):
        raise SystemExit(__doc__)
    value = argv[i + 1]
    del argv[i:i + 2]
    return value


if __name__ == "__main__":
    argv = sys.argv[1:]
    rid = _pop_opt(argv, "--rid")
    kind = _pop_opt(argv, "--kind")
    sys.argv = sys.argv[:1] + argv
    if len(sys.argv) < 2:
        raise SystemExit(__doc__)
    if sys.argv[1] in ("--journal", "--roofline", "--ledger",
                       "--timeseries", "--pyprof"):
        if len(sys.argv) < 3:
            raise SystemExit(__doc__)
        if sys.argv[1] == "--pyprof":
            top = int(sys.argv[3]) if len(sys.argv) > 3 else 15
            print(summarize_pyprof(sys.argv[2], top))
            sys.exit(0)
        if sys.argv[1] == "--journal":
            print(summarize_journal(sys.argv[2], rid=rid, kind=kind))
            sys.exit(0)
        mode = {"--roofline": summarize_roofline,
                "--ledger": summarize_ledger,
                "--timeseries": summarize_timeseries}[sys.argv[1]]
        print(mode(sys.argv[2]))
        sys.exit(0)
    target = sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 25
    if os.path.isfile(target) and target.endswith(".json"):
        print(summarize_chrome_trace(target, top))
    else:
        print(summarize(target, top))
