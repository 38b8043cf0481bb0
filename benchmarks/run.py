"""Run one cell of BENCHMARK.json once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell names a configuration and a job mix; both are files found by name
(``configs/<config>.json``, ``traffic/<traffic>.json``), as are the cell's
limits (``limits/<cell>.json``), each per-layer metric's reader
(``layer_metrics/<metric>.py``) and the configuration's job family
(``families/<family>.py``: its data, what is captured of a train window and
what is compared).  Nothing here branches on a name.

Standard error carries the progress lines and, last, every number compared
for ``correct`` beside its limit.  Standard output's last line is the
result: one JSON object.
"""

import sys
import time

T_PROCESS = time.perf_counter()

import argparse     # noqa: E402
import importlib    # noqa: E402
import json         # noqa: E402
import os           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg):
    print("[bench %7.1fs] %s" % (time.perf_counter() - T_PROCESS, msg),
          file=sys.stderr, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def resolve(workload):
    """(cell, cfg, mix, limits, manifest) of a cell, all by name."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    # cells defined but not admitted to the manifest resolve the same way
    pending = load_json(os.path.join(BENCH, "pending_cells.json"))
    cells = {w["name"]: w for w in pending["workloads"]}
    cells.update({w["name"]: w for w in manifest["workloads"]})
    if workload not in cells:
        raise SystemExit("unknown workload %r (known: %s)"
                         % (workload, ", ".join(sorted(cells))))
    cell = cells[workload]
    configs = {c["name"]: c for c in pending["configs"]}
    configs.update({c["name"]: c for c in manifest["configs"]})
    cfg = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    mix = load_json(os.path.join(BENCH, "traffic",
                                 cell["traffic"] + ".json"))
    limits = load_json(os.path.join(BENCH, "limits",
                                    cell["name"] + ".json"))
    return cell, cfg, mix, limits, manifest


def metrics_for(manifest, cell, section):
    """The section's metrics that this cell reports."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def device_check(cell):
    """Platform, kind and count first; no chip, no run."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    log("platform=%s device_kind=%s devices=%d"
        % (d0.platform, d0.device_kind, len(devices)))
    if d0.platform != "tpu":
        raise SystemExit("no accelerator: platform is %r" % d0.platform)
    if len(devices) < int(cell["chips"]):
        raise SystemExit("cell needs %d chips, found %d"
                         % (cell["chips"], len(devices)))
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    if d0.device_kind not in peaks:
        raise SystemExit("device_kind %r is not in benchmarks/peaks.json"
                         % d0.device_kind)
    return d0, peaks[d0.device_kind]


def place_cache():
    """The compile cache lives at a fixed path inside the checkout, and the
    program takes the directory this variable gives it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".cache", "xla_cache")
    # no size cap: jax's LRU eviction (on where a machine sets a cap) stops
    # writing entries once one lacks its access-time file
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    place_cache()
    cell, cfg, mix, limits, manifest = resolve(args.workload)
    d0, peaks = device_check(cell)

    from benchmarks import families
    from benchmarks.lib import compare, job
    from benchmarks.lib import trace as trace_mod
    import jax

    fam = families.load(cfg)
    run = job.run_cell(cell, cfg, mix, args.seed, args.seconds,
                       bool(args.trace), ROOT, T_PROCESS, log)
    # a row the loader serves is the rate's "image", whatever the family
    rate = run["images"] / run["window_s"]
    log("window: %d epochs, %d rows in %.3f s; set-up %.1f s; peak %.2f GB"
        % (run["epochs"], run["images"], run["window_s"], run["setup_s"],
           run["memory_peak_bytes"] / 1e9))
    if run["row_tokens"]:
        log("%d tokens a row: %.1f tokens/s"
            % (run["row_tokens"], rate * run["row_tokens"]))

    net = fam.plan(cfg, mix)
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": int(cell["chips"]),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    out = {"correct": False, "attempted": run["epochs"], "failed": 0}
    if args.trace:
        t0 = time.perf_counter()
        loaded = trace_mod.load_xplane(
            trace_mod.find_xplane(run["trace_dir"]))
        loaded["host"] = run["host_spans"]
        reduced = trace_mod.reduce_trace(loaded, run["window_s"])
        log("trace read in %.1f s; first device op at %.1f ms, first host "
            "span at %.1f ms of the trace's clock"
            % (time.perf_counter() - t0,
               min((e[1] for evs in loaded["devices"].values()
                    for e in evs[:1]), default=0.0) / 1e6,
               min((sp[1] for sp in run["host_spans"]), default=0.0) / 1e6))
        if reduced is None or reduced["busy_s_mean"] <= 0:
            raise SystemExit("the trace shows no operation on the device")
        device["busy_s"] = reduced["busy_s_mean"]
        device["window_s"] = run["window_s"]
        ctx = dict(run, cell=cell, cfg=cfg, mix=mix, peaks=peaks,
                   rate=rate, trace=reduced, net=net)
        metrics = {}
        for m in metrics_for(manifest, cell, "per_layer"):
            reader = importlib.import_module(
                "benchmarks.layer_metrics." + m["name"])
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    else:
        values = {"train_images_per_s": rate, "setup_s": run["setup_s"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in metrics_for(manifest, cell, "end_to_end")}

    t0 = time.perf_counter()
    refout = fam.follow(cfg, mix, run, chips=int(cell["chips"]), log=log)
    nums, where = fam.numbers(run, refout, cfg, limits, net)
    log("reference followed %d windows in %.1f s (worst leaves: %s)"
        % (len(run["windows"]), time.perf_counter() - t0, where))
    out["correct"] = bool(compare.decide(nums))
    out["metrics"] = metrics
    out["device"] = device
    compared = {name: {"value": value, "limit": limit}
                for name, value, limit in nums}
    for name, value, limit in nums:
        print("compared %-24s %.6g  limit %.6g%s"
              % (name, value, limit, "" if value <= limit else "  FAILED"),
              file=sys.stderr)
    sys.stderr.flush()
    out["compared"] = compared
    print(json.dumps(out), flush=True)
    jax.effects_barrier()
    return 0


if __name__ == "__main__":
    sys.exit(main())
