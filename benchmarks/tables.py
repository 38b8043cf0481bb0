"""The two tables of PERF.md section 5, from one traced run of a cell.

    python3 benchmarks/tables.py --workload <cell> --seed <n> [--seconds 20]

Runs the cell once with ``--trace 1``'s instrumentation (telemetry on, the
profiler around the window) and without the reference's comparison, then
prints, as markdown:

* the run before the timed epochs (set-up's part inside ``workflow.run``)
  and the host's epoch: for each span name (split by parent where one name
  stands under several, and by its ``bytes`` where it moves a few distinct
  sizes: the call sites of one name) its count, total and self
  milliseconds an epoch and the self time's share of the epoch
  (``lib/program_spans.py``), and the trainer's spans beside the growth of
  its ``run_time_``, which the benchmark takes from outside;
* the device's step: for each scope of the train window programs the
  forward and backward milliseconds a step, beside the least time the chip
  could take for that layer at the cell's rows a chip
  (``layer_costs``: the larger of FLOPs over peak and bytes over peak
  bandwidth), and the validation forward's milliseconds a pass
  (``lib/scoped_trace.py``).

Needs the chip, like ``run.py``.  ``host_rows`` and ``device_rows`` are
plain arithmetic, tested without one.
"""

import argparse
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import layer_costs          # noqa: E402
from benchmarks import run as run_mod       # noqa: E402
from benchmarks.lib import program_spans as ps      # noqa: E402
from benchmarks.lib import scoped_trace     # noqa: E402


#: a name that moves more distinct sizes than this is not split by size
MAX_SIZES = 6


def _rows(ring, spans, whole_ns, n):
    """[(label, count, total ms, self ms, share %)] of ``spans`` over
    ``n`` stretches of ``whole_ns`` each, by self time, the shares adding
    up to the stretch."""
    from znicz_tpu.core import telemetry    # after run.place_cache()
    own = telemetry.self_times(ring)
    name_of = {s[3]: s[0] for s in ring}
    parents, sizes = {}, {}
    for s in spans:
        parents.setdefault(s[0], set()).add(name_of.get(s[4], ""))
        sizes.setdefault(s[0], set()).add(s[5].get("bytes"))
    rows = {}
    for s in spans:
        label = s[0]
        if len(parents[s[0]]) > 1:
            label += " (in %s)" % (name_of.get(s[4]) or "no span")
        if 1 < len(sizes[s[0]]) <= MAX_SIZES:
            label += " (%s B)" % s[5].get("bytes")
        row = rows.setdefault(label, [0, 0, 0])
        row[0] += 1
        row[1] += s[2]
        row[2] += own[s[3]]
    out = [(label, count / n, total / 1e6 / n, self_t / 1e6 / n,
            100.0 * self_t / n / whole_ns)
           for label, (count, total, self_t) in rows.items()]
    # what is left of the stretch: the scheduler between the units (the
    # self time of workflow.run, which starts before the epochs kept)
    rest = max(0.0, whole_ns - sum(r[2] for r in rows.values()) / n)
    out.append(("outside these spans", 0.0, 0.0, rest / 1e6,
                100.0 * rest / whole_ns))
    return sorted(out, key=lambda r: -r[3])


def host_rows(ring, markers, n_epochs):
    """The timed epochs' rows, an epoch; None where the ring holds too few
    epochs."""
    kept = ps.cut(ring, markers, n_epochs)
    if kept is None:
        return None
    spans, lo, hi = kept
    return _rows(ring, spans, (hi - lo) / n_epochs, n_epochs)


def setup_rows(ring, markers, n_epochs):
    """The rows of the run before the timed epochs (the warm-up epochs:
    placing the data set, each program's first call), as one stretch from
    the ring's first span; None where the ring holds too few epochs."""
    kept = ps.cut(ring, markers, n_epochs)
    if kept is None:
        return None
    lo = kept[1]
    spans = [s for s in ring if s[1] < lo and s[0] != "workflow.run"]
    if not spans:
        return None
    return _rows(ring, spans, lo - min(s[1] for s in spans), 1)


def least_ms(net, batch, peaks):
    """{layer index: (forward, backward, update)} least milliseconds of
    one step of ``batch`` rows."""
    out = {}
    for i, (_, c, _) in enumerate(layer_costs.net_costs(net, batch)):
        def t(flops, nbytes):
            return 1e3 * max(flops / peaks["flops_per_s"],
                             nbytes / peaks["hbm_bytes_per_s"])
        out[i] = (t(c["flops_fwd"], c["bytes_fwd"]),
                  t(c["flops_bwd"], c["bytes_bwd"]),
                  t(0.0, c["bytes_update"]))
    return out


def device_rows(by_scope, steps, passes, least):
    """[(scope, fwd ms, bwd ms, least ms or None, ratio or None, ms a
    validation pass)]: the train window programs' ops a step, every other
    program's a pass."""
    scopes = sorted({scope for _, scope, _ in by_scope},
                    key=lambda s: (s is None, s or ""))
    rows = []
    for scope in scopes:
        fwd = 1e3 * by_scope.get((True, scope, False), 0.0) / steps
        bwd = 1e3 * by_scope.get((True, scope, True), 0.0) / steps
        other = 1e3 * sum(v for (train, s, _), v in by_scope.items()
                          if not train and s == scope) / max(passes, 1)
        need = None
        if scope and scope[0] == "L" and int(scope[1:3]) in least:
            need = sum(least[int(scope[1:3])][:2])
        elif scope and scope.startswith("update.L"):
            need = least.get(int(scope[8:10]), (0, 0, None))[2]
        ratio = (fwd + bwd) / need if need else None
        rows.append((scope or "no scope", fwd, bwd, need, ratio, other))
    return rows


def _fmt(value, spec="%.2f"):
    return "" if value is None else spec % value


def _print_rows(title, rows, out, least_share=0.0):
    print("\n| Span, %s | count | total ms | self ms | share %% |" % title,
          file=out)
    print("| --- | --- | --- | --- | --- |", file=out)
    for label, count, total, self_t, share in rows or ():
        if share >= least_share:
            print("| `%s` | %.1f | %.2f | %.2f | %.2f |"
                  % (label, count, total, self_t, share), file=out)


def print_tables(run, cell, peaks, net, out=sys.stdout):
    n = run["epochs"]
    _print_rows("the run before the timed epochs",
                setup_rows(ps.ring(), ps.ring("i"), n), out,
                least_share=0.5)
    rows = host_rows(ps.ring(), ps.ring("i"), n)
    _print_rows("an epoch", rows, out)
    t0, t1, name = run["unit_time0"], run["unit_time1"], run["trainer_name"]
    print("\n`unit.%s` spans %.2f ms an epoch; its `run_time_` grew %.2f ms "
          "an epoch over the timed window" % (
              name, sum(r[2] for r in rows or () if r[0] == "unit." + name),
              1e3 * (t1[name][0] - t0[name][0]) / n), file=out)
    red = scoped_trace.of_run(run)
    if red is None:
        print("\nno scope in the device trace", file=out)
        return
    steps = run["images"] // run["batch"]
    per_chip = run["batch"] // int(cell["chips"])
    print("\nbusiest plane %s: busy %.3f s, ops' sum %.3f s, %d steps, "
          "%d rows a chip\n" % (red["plane"], red["busy_s"], red["sum_s"],
                                steps, per_chip), file=out)
    print("| Scope | forward ms | backward ms | least ms | ratio | "
          "validation pass ms |", file=out)
    print("| --- | --- | --- | --- | --- | --- |", file=out)
    for scope, fwd, bwd, need, ratio, other in device_rows(
            red["by_scope"], steps, n, least_ms(net, per_chip, peaks)):
        print("| `%s` | %.3f | %.3f | %s | %s | %.3f |"
              % (scope, fwd, bwd, _fmt(need, "%.3f"), _fmt(ratio),
                 other), file=out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    run_mod.place_cache()
    cell, cfg, mix, _, _ = run_mod.resolve(args.workload)
    _, peaks = run_mod.device_check(cell)
    from benchmarks import families
    from benchmarks.lib import job
    run = job.run_cell(cell, cfg, mix, args.seed, args.seconds, True,
                       run_mod.ROOT, T_PROCESS, run_mod.log)
    run_mod.log("traced window: %.1f images/s over %d epochs"
                % (run["images"] / run["window_s"], run["epochs"]))
    print_tables(run, cell, peaks, families.load(cfg).plan(cfg, mix))
    return 0


if __name__ == "__main__":
    sys.exit(main())
