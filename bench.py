"""Benchmark — prints ONE JSON line for the driver.

Measures fused train throughput (images/sec) THROUGH THE SHIPPED TRAINING
LOOP: a StandardWorkflow in fused mode with scan windows — repeater ->
loader -> fused trainer (one compiled ``lax.scan`` over ``window`` TRAIN
minibatches, minibatches gathered on device from the device-resident
dataset) -> evaluator (window stats) -> decision -> snapshotter.  This is
the same control plane ``--fused`` training runs use; bench.py no longer
times a private loop (VERDICT r3 weak #3/next #1).

Models:

* the MNIST conv flagship (primary metric — round-over-round
  comparability),
* the CIFAR-caffe topology (BASELINE.json's stated north-star model),
* a chip-filling wide conv model (128/256 channels) that shows the
  framework's MFU ceiling when the topology feeds the MXU.

Per-window spread: every steady-state epoch's images/sec is recorded in
the JSON (``*_window_ips``) so a regression can be told apart from
run-to-run noise (VERDICT r3 weak #1).

MFU attribution (measured on a v5e, see ``mfu_note`` and BENCH_NOTES.md):
the 2015-era flagship topologies are STRUCTURALLY bound — 1..87-channel
convs on a 128x128 MXU.  Evidence: (a) padding the 87-kernel layer to 128
leaves images/sec unchanged, (b) the same framework/step on MXU-aligned
128/256-channel convs reaches ~50% MFU, (c) bf16 over f32 gains only
~1.4x on the flagship (memory/overhead-bound) but the wide model is
GEMM-dominated.
"""

import json
import os
import re
import time

import numpy

METRIC = "mnist_conv_fused_train_images_per_sec"

#: (device-kind substring, peak dense-matmul FLOP/s, HBM bandwidth
#: bytes/s) — bf16 peaks for TPU.  The "cpu" row is a NOMINAL host
#: fallback so roofline math stays defined on the CPU backend (MFU
#: against it is not a hardware claim; the JSON marks it nominal).
PEAK_TABLE = (
    ("v5 lite", 197e12, 819e9),   # v5e
    ("v5e", 197e12, 819e9),
    ("v5p", 459e12, 2765e9),
    ("v6", 918e12, 1640e9),       # Trillium
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 46e12, 700e9),
    ("cpu", 2e11, 50e9),          # nominal host row
)

#: chip-filling wide conv model — MXU-aligned channel counts
WIDE_LAYERS = [
    {"type": "conv_relu", "->": {"n_kernels": 128, "kx": 3, "ky": 3,
                                 "padding": (1, 1, 1, 1)}},
    {"type": "conv_relu", "->": {"n_kernels": 256, "kx": 3, "ky": 3,
                                 "padding": (1, 1, 1, 1)}},
    {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
    {"type": "conv_relu", "->": {"n_kernels": 256, "kx": 3, "ky": 3,
                                 "padding": (1, 1, 1, 1)}},
    {"type": "conv_relu", "->": {"n_kernels": 256, "kx": 3, "ky": 3,
                                 "padding": (1, 1, 1, 1)}},
    {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
    {"type": "all2all_relu", "->": {"output_sample_shape": 1024}},
    {"type": "softmax", "->": {"output_sample_shape": 10}},
]


def _device_peaks(device_kind):
    """{"flops", "hbm_bytes_per_sec", "nominal"} for the device kind,
    or None when no row matches (the caller stamps the mfu keys null
    with a ``peak_flops_unknown`` note instead of omitting them)."""
    kind = device_kind.lower()
    for sub, peak, bw in PEAK_TABLE:
        if sub in kind:
            return {"flops": peak, "hbm_bytes_per_sec": bw,
                    "nominal": sub == "cpu"}
    return None


def _peak_flops(device_kind):
    peaks = _device_peaks(device_kind)
    return peaks["flops"] if peaks else None


def _measure(layers, loader_name, batch, compute_dtype, n_steps=40,
             n_epochs=5, profile_dir=None, fused_extra=None):
    """Steady-state throughput of the SHIPPED fused training loop.

    Builds a StandardWorkflow (synthetic full-batch dataset of
    ``n_steps * batch`` train samples, no validation split) in fused
    mode with ``window = n_steps // 4``: each epoch is SEVERAL compiled
    scan windows dispatched by the fused trainer THROUGH the control
    plane (loader / evaluator / decision / snapshotter all firing their
    reference roles), so the asynchronous steady state actually engages
    — mid-epoch windows pipeline with zero readbacks and the epoch pays
    ONE batched aggregate fetch (a single-window epoch would make every
    window segment-final and the stamped ``readbacks_per_epoch`` could
    never distinguish async from sync).  Per-epoch wall times come from
    the decision's end-of-train hook; the first epoch (compile +
    dataset placement) is discarded.  Returns (best_ips,
    [per-epoch ips...], train FLOPs/img).
    """
    from znicz_tpu.core import prng
    from znicz_tpu.core import telemetry
    from znicz_tpu.core.backends import JaxDevice
    from znicz_tpu.standard_workflow import StandardWorkflow
    from znicz_tpu.parallel.fused import flops_per_image
    import znicz_tpu.loader.loader_mnist  # noqa: F401
    import znicz_tpu.loader.loader_cifar  # noqa: F401

    # per-model isolation: the previous model's compiles and transfer
    # bytes must not stay in the registry this run's summary reads (nor
    # its check counts in the health monitor, nor its executables in
    # the profiler's cost registry)
    telemetry.reset()
    from znicz_tpu.core import health
    from znicz_tpu.core import profiler
    health.reset()
    profiler.reset()
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    wf = StandardWorkflow(
        None, layers=[dict(l) for l in layers], loader_name=loader_name,
        loader_config={"synthetic_train": batch * n_steps,
                       "synthetic_valid": 0, "synthetic": True,
                       "minibatch_size": batch,
                       "normalization_type": "none"},
        decision_config={"max_epochs": n_epochs,
                         "fail_iterations": 10 ** 9},
        snapshotter_config={"interval": 10 ** 9, "time_interval": 1e9,
                            "compression": ""},
        fused=dict({"window": max(2, n_steps // 4),
                    "compute_dtype": compute_dtype},
                   **(fused_extra or {})))
    wf.initialize(device=JaxDevice())
    assert wf.fused_trainer._use_device_data, \
        "bench requires the device-resident dataset path"

    times = []
    orig_hook = wf.decision.on_training_finished

    def hook():
        times.append(time.perf_counter())
        orig_hook()

    wf.decision.on_training_finished = hook
    times.append(time.perf_counter())
    if profile_dir:
        import jax
        # profile epochs 2.. (first is compile); trace the whole run and
        # slice by step markers in xprof
        with jax.profiler.trace(str(profile_dir)):
            wf.run()
    else:
        wf.run()
    dts = numpy.diff(times)
    if len(dts) < 3:
        raise RuntimeError("bench needs >= 3 epochs, got %d" % len(dts))
    # dts[0] is the compile epoch; dts[1] is a WARMUP window (the first
    # steady dispatch still pays allocator growth + async-pipeline
    # priming and used to land as the low outlier in *_window_ips,
    # making the spread read as noise).  Timing starts at dts[2].
    window_ips = [n_steps * batch / dt for dt in dts[2:]]
    fpi = 3 * flops_per_image(wf.fused_trainer.net.specs)
    return max(window_ips), window_ips, fpi


def _spread_pct(windows):
    if not windows:
        return None
    return round(100.0 * (max(windows) - min(windows)) / max(windows), 2)


def _outlier_ratio(telemetry_summary):
    """Step-time p99/p50 from the stamped telemetry block — the
    straggler signal BENCH_*.json tracks over time."""
    steps = (telemetry_summary or {}).get("step_seconds") or {}
    p50, p99 = steps.get("p50"), steps.get("p99")
    if not p50 or p99 is None:
        return None
    return round(p99 / p50, 3)


def _roofline_block(prof_snap, peaks, ips, device_kind):
    """The measured-cost why-block stamped into BENCH_*.json: the
    flagship window executable's XLA ``cost_analysis`` FLOPs / bytes
    accessed / operational intensity against the analytic
    ``flops_per_image`` estimate (tolerance band documented in
    BENCH_NOTES.md), plus measured MFU and the roofline ridge-point
    verdict for the device."""
    entries = (prof_snap or {}).get("cost_registry") or []
    win = next((e for e in entries
                if e["name"].startswith("fused.window")
                and e.get("flops")), None)
    out = {
        "device_kind": device_kind,
        "peak_flops": peaks["flops"] if peaks else None,
        "hbm_bytes_per_sec": (peaks["hbm_bytes_per_sec"]
                              if peaks else None),
        "executables": entries,
    }
    if peaks and peaks.get("nominal"):
        out["peak_nominal"] = True
    if win is None:
        out["note"] = "no fused.window executable registered"
        return out
    meta = win.get("meta") or {}
    images = max(int(meta.get("steps") or 1)
                 * int(meta.get("batch") or 1), 1)
    measured_fpi = win["flops"] / images
    out.update({
        "window_executable": win["name"],
        "measured_flops": win["flops"],
        "bytes_accessed": win.get("bytes_accessed"),
        "operational_intensity": win.get("operational_intensity"),
        "measured_flops_per_image": round(measured_fpi, 1),
        "analytic_flops_per_image": meta.get(
            "analytic_flops_per_image"),
        "flops_ratio_measured_vs_analytic": win.get(
            "flops_ratio_measured_vs_analytic"),
        "agreement": win.get("agreement"),
    })
    if peaks:
        out["mfu_pct_measured"] = round(
            100.0 * ips * measured_fpi / peaks["flops"], 2)
        ridge = peaks["flops"] / peaks["hbm_bytes_per_sec"]
        out["ridge_intensity_flops_per_byte"] = round(ridge, 1)
        oi = win.get("operational_intensity")
        if oi is not None:
            out["roofline_bound"] = ("memory" if oi < ridge
                                     else "compute")
    return out


def _fault_tolerance_block():
    """Measured recovery cost (ISSUE 7): train a small fused wine run
    that writes mid-epoch ``window_interval`` snapshots, then time the
    restart path a supervised job actually pays —

    * ``resume_overhead_seconds``: restoring the newest snapshot into a
      freshly built workflow (pickle read + device placement of params/
      optimizer/accumulators),
    * ``restart_to_first_window_seconds``: fresh build + initialize +
      restore + the first training window dispatched — the wall time
      from "process back up" to "training again".

    Tracked round over round next to throughput so recovery cost can
    never silently regress."""
    import shutil
    import tempfile

    import znicz_tpu.loader.loader_wine  # noqa: F401 (registry)

    tmp = tempfile.mkdtemp(prefix="bench_ft_")
    try:
        return _fault_tolerance_measure(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _fault_tolerance_measure(tmp):
    from znicz_tpu.core import prng
    from znicz_tpu.launcher import Launcher
    from znicz_tpu.standard_workflow import StandardWorkflow
    from znicz_tpu.units.nn_units import load_snapshot_into_workflow

    layers = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
         "<-": {"learning_rate": 0.1}},
        {"type": "softmax", "->": {"output_sample_shape": 3},
         "<-": {"learning_rate": 0.1}},
    ]

    def build():
        prng.get(1).seed(1234)
        prng.get(2).seed(5678)
        wf = StandardWorkflow(
            None, layers=[dict(l) for l in layers],
            loader_name="wine_loader",
            loader_config={"minibatch_size": 10},
            decision_config={"max_epochs": 2, "fail_iterations": 100},
            snapshotter_config={"prefix": "benchft",
                                "interval": 10 ** 9,
                                "time_interval": 1e9, "compression": "",
                                "directory": tmp,
                                "window_interval": 2},
            fused={"window": 4})
        wf.initialize()
        return wf

    build().run()  # leaves mid-epoch snapshots behind

    t_restart = time.perf_counter()
    wf = build()
    t_restore = time.perf_counter()
    state = Launcher(auto_resume=True)._find_resume_state(wf)
    load_snapshot_into_workflow(state, wf)
    resume_overhead = time.perf_counter() - t_restore
    first = {}
    orig_window = wf.fused_trainer._run_train_window

    def hooked():
        if "t" not in first:
            first["t"] = time.perf_counter()
        return orig_window()

    wf.fused_trainer._run_train_window = hooked
    wf.run()
    return {
        "resume_overhead_seconds": round(resume_overhead, 4),
        "restart_to_first_window_seconds": round(
            first["t"] - t_restart, 4),
        "resumed_suffix": state.get("suffix"),
    }


def main(profile_dir=None):
    from znicz_tpu.core.config import root
    from znicz_tpu.core import telemetry
    import znicz_tpu.samples.cifar  # noqa: F401 (root.cifar)
    import znicz_tpu.samples.mnist  # noqa: F401 (root.mnistr_conv)
    import jax
    import jax.numpy as jnp

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            "bench.py measures the chip and found platform=%s; a CPU "
            "timing is not a benchmark result" % platform)
    flagship_layers = root.mnistr_conv.layers
    device_kind = jax.devices()[0].device_kind
    peaks = _device_peaks(device_kind)
    peak = peaks["flops"] if peaks else None

    def mfu(eff):
        return round(100.0 * eff / peak, 2) if peak else None

    # telemetry rides the flagship run so every BENCH_*.json carries
    # the WHY (compile count, transfer bytes, step-time spread), not
    # just the img/s.  Hooks fire at window cadence — noise for a
    # 40-minibatch scan is one span + three counter bumps per epoch.
    # (_measure resets the registry per attempt, so the summary below
    # reflects exactly the surviving flagship run.)
    root.common.telemetry.enabled = True
    # the health monitor rides too (policy=warn, interval=1): the
    # stamped `health` block tracks its overhead round over round —
    # window mode means one fused check per dispatched window
    from znicz_tpu.core import health as health_mod
    health_mod.reset()
    health_mod.enable(policy="warn", interval=1)
    # ... and the performance profiler: the flagship's window
    # executable registers its XLA cost_analysis FLOPs (one extra
    # lowering, zero extra compiles) and each window's wall time is
    # partitioned into data/dispatch/device/readback — the `roofline`
    # and `step_breakdown` blocks below.  Overhead: one trace at first
    # dispatch plus one block_until_ready per window, right where
    # host_fetch would block anyway.
    from znicz_tpu.core import profiler as profiler_mod
    profiler_mod.reset()
    profiler_mod.enable()

    # primary: MNIST conv flagship, bf16 GEMMs + f32 master weights,
    # through the workflow control plane
    flagship_steps = 40
    flagship_epochs = 5
    batch = 16384
    ips, windows, fpi = _measure(
        flagship_layers, "mnist_loader", batch, jnp.bfloat16,
        n_steps=flagship_steps, n_epochs=flagship_epochs,
        profile_dir=profile_dir)
    # flagship-attributed telemetry, captured before the other models
    # pollute the counters
    flagship_telemetry = telemetry.summary()
    flagship_health = health_mod.summary()
    flagship_profiler = profiler_mod.snapshot()
    # secondary reference point (f32 needs ~2x the bf16 run's memory
    # on the same batch)
    ips_f32, _, _ = _measure(
        flagship_layers, "mnist_loader", batch, None,
        n_steps=10, n_epochs=4)
    eff = ips * fpi

    # the north-star model (BASELINE.json metric line)
    cifar_batch = 4096
    cifar_ips, cifar_windows, cifar_fpi = _measure(
        root.cifar.layers, "cifar_loader", cifar_batch, jnp.bfloat16,
        n_steps=10, n_epochs=5,
        profile_dir=(profile_dir + "_cifar") if profile_dir else None)

    # chip-filling wide model: the framework's MFU ceiling
    wide_batch = 1024
    wide_ips, wide_windows, wide_fpi = _measure(
        WIDE_LAYERS, "cifar_loader", wide_batch, jnp.bfloat16,
        n_steps=10, n_epochs=5)

    out = {
        "metric": METRIC,
        "value": round(ips, 1),
        "unit": "images/sec/chip",
        "batch": batch,
        "loop": "workflow-control-plane (%d minibatches/epoch in async "
                "scan windows of %d, device dataset, in-scan indexed "
                "gather)" % (flagship_steps, max(2, flagship_steps // 4)),
        "window_ips": [round(w, 1) for w in windows],
        "window_spread_pct": _spread_pct(windows),
        "train_tflops_effective": round(eff / 1e12, 2),
        "compute_dtype": "bfloat16",
        "f32_images_per_sec": round(ips_f32, 1),
        "cifar_caffe_images_per_sec": round(cifar_ips, 1),
        "cifar_caffe_batch": cifar_batch,
        "cifar_caffe_window_ips": [round(w, 1) for w in cifar_windows],
        # every model stamps its spread the way the flagship always has
        # — with the warmup window discarded, a wide spread points at
        # a real regression instead of the 181k-244k mystery noise of
        # r5/r6
        "cifar_caffe_window_spread_pct": _spread_pct(cifar_windows),
        "wide_conv_images_per_sec": round(wide_ips, 1),
        "wide_conv_batch": wide_batch,
        "wide_conv_window_ips": [round(w, 1) for w in wide_windows],
        "wide_conv_window_spread_pct": _spread_pct(wide_windows),
        # async-control-plane pins: batched decision-aggregate readbacks
        # and d2h traffic per epoch (one readback per segment when fully
        # asynchronous)
        "readbacks_per_epoch": round(
            (flagship_telemetry or {}).get("readbacks", 0)
            / flagship_epochs, 2),
        "d2h_bytes_per_epoch": int(
            (flagship_telemetry or {}).get("d2h_bytes", 0)
            / flagship_epochs),
        "mfu_note": "flagship topologies are MXU-starved by design "
                    "(1..87ch convs); wide 128/256ch model shows the "
                    "framework ceiling; see BENCH_NOTES.md",
        # the why-block: compile count, host<->device bytes, step-time
        # p50/p99 of the flagship run (core/telemetry.py summary())
        "telemetry": flagship_telemetry,
        # monitoring overhead pin: checks run, violations seen, fused
        # health-check p50 (core/health.py summary())
        "health": flagship_health,
        # steady-state jitter pin: a growing p99/p50 ratio means
        # stragglers (retrace, GC, dispatch hiccups), not a slower median
        "step_time_p99_over_p50": _outlier_ratio(flagship_telemetry),
        # measured (XLA cost_analysis) FLOPs/bytes of the flagship
        # window vs the analytic estimate + roofline verdict
        # (core/profiler.py cost registry; tolerance in BENCH_NOTES.md)
        "roofline": _roofline_block(flagship_profiler, peaks, ips,
                                    device_kind),
        # where the flagship window's wall time went (data-wait /
        # dispatch / device / readback) + the bound verdict
        "step_breakdown": flagship_profiler.get("breakdown"),
        # device-memory accounting of the flagship run
        "memory_ledger": flagship_profiler.get("ledger"),
    }
    # recovery cost (ISSUE 7): mid-epoch snapshot restore + restart-to-
    # first-window wall time — crash-guarded like the secondary models
    try:
        out["fault_tolerance"] = _fault_tolerance_block()
    except Exception as e:  # noqa: BLE001 - never kill the primary
        out["fault_tolerance"] = {"error": repr(e)}
    # serving control plane (ISSUE 8): two-model registry + continuous
    # batching under the seeded open-loop generator + compile-cache
    # cold start — stamped in the MAIN bench so req/s, p99 and
    # goodput-under-overload are tracked round over round (and gated
    # by tools/bench_gate.py)
    _stamp_serving_control_plane(out)
    # per-dtype serving data path (ISSUE 10): same memory-bound model
    # at f32 / bf16 / int8 — requests/sec, measured bytes-accessed,
    # operational intensity and accuracy deltas per dtype, with the
    # flat serving_<dtype>_requests_per_sec keys gated like all
    # throughput (tools/bench_gate.py)
    _stamp_serving_precision(out, peaks)
    # batch-1 tail latency (ISSUE 12): the f32-fast hot path under
    # adversarial mixes (steady / cold bucket / evict→restore /
    # breaker half-open probe) — req/s gated like throughput, exact
    # per-scenario p99s gated inverted (tools/bench_gate.py)
    _stamp_serving_tail(out)
    # SLO-plane overhead (ISSUE 14): armed sampler+tracing+SLO vs
    # disabled on the same HTTP mix — gated inverted so the
    # observability plane's cost stays a measured, bounded number
    _stamp_serving_observability(out)
    # multi-replica fleet (ISSUE 15): 2-replica scaling efficiency
    # behind the router (shared compile cache, zero-fresh-compile
    # scale-up) + high-priority goodput under 3x overload — both flat
    # keys gated (tools/bench_gate.py)
    _stamp_serving_fleet(out)
    # fleet-path tracing overhead (ISSUE 16): armed cross-process
    # tracing vs disabled on the real router, plus the router's
    # per-request hop overhead — both gated inverted
    _stamp_serving_fleet_observability(out)
    # shadow-mirroring tax (ISSUE 17): a release held in shadow at
    # 100% sampling vs the same armed fleet without one — gated
    # inverted so progressive delivery stays affordable
    _stamp_serving_release_shadow(out)
    # binary framed relay (ISSUE 20): relay wall_rps (gated) + the
    # per-request hop-overhead speedup vs the JSON/HTTP surface
    _stamp_serving_wire(out)
    # continuous-profiler cost ledger (ISSUE 18): armed 97 Hz sampler
    # vs disabled on the same HTTP mix (overhead gated inverted) +
    # the measured Python data-plane tax (stamped-nonzero in CI)
    _stamp_serving_pyprof(out)
    # durable-blackbox write-through tax (ISSUE 19): armed on-disk
    # persistence vs disabled on the same HTTP mix — gated inverted
    _stamp_serving_blackbox(out)
    prec = out.get("serving_precision", {}).get("dtypes")
    if prec and isinstance(out.get("roofline"), dict):
        # the roofline block grows the per-dtype serving axis: where
        # each precision mode sits relative to the ridge
        out["roofline"]["serving_per_dtype"] = {
            dt: {k: d.get(k) for k in ("operational_intensity",
                                       "mfu_pct", "roofline_bound",
                                       "bytes_accessed")}
            for dt, d in prec.items()}
    # mfu keys are ALWAYS stamped: null (with a visible note + a trace
    # instant) when the device kind has no PEAK_TABLE row — an unknown
    # accelerator must not silently drop the metric from BENCH_*.json
    out["mfu_pct"] = mfu(eff)
    out["cifar_caffe_mfu_pct"] = mfu(cifar_ips * cifar_fpi)
    out["wide_conv_mfu_pct"] = mfu(wide_ips * wide_fpi)
    if peak is None:
        out["peak_flops_unknown"] = device_kind
        telemetry.instant("bench.peak_flops_unknown",
                          device_kind=device_kind)
    print(json.dumps(out))


#: device counts the mesh-scaling bench sweeps (ISSUE 6: multi-device
#: throughput becomes a tracked number instead of an exit code)
MESH_DEVICE_COUNTS = (1, 2, 4, 8)


def _mesh_worker(n_devices):
    """Inner process of ``--mesh``: measure the flagship and the
    cifar-caffe workloads through the SHIPPED control plane on an
    ``n_devices`` data-parallel mesh (the caller forced
    ``--xla_force_host_platform_device_count``).  Prints ONE JSON line.

    Sizes are CPU-feasible (the forced-host-device sweep shares one
    machine's cores): relative scaling and the invariants — not
    absolute TPU throughput — are the tracked numbers."""
    from znicz_tpu.core.config import root
    from znicz_tpu.core import telemetry
    import znicz_tpu.samples.cifar  # noqa: F401 (root.cifar)
    import znicz_tpu.samples.mnist  # noqa: F401 (root.mnistr_conv)

    root.common.telemetry.enabled = True
    n_steps, n_epochs, batch = 8, 4, 64
    fused_extra = {} if n_devices == 1 else {"mesh": n_devices}
    out = {"devices": n_devices}
    ips, _, fpi = _measure(
        root.mnistr_conv.layers, "mnist_loader", batch, None,
        n_steps=n_steps, n_epochs=n_epochs, fused_extra=fused_extra)
    tele = telemetry.summary()
    out["flagship_images_per_sec"] = round(ips, 1)
    out["flagship_flops_per_image"] = fpi
    # the async-control-plane invariant, per device count: readbacks ==
    # segments (one per epoch here — no VALID split), and the d2h bytes
    # of one epoch split across the shards
    segs = float(n_epochs)
    out["readbacks_per_epoch"] = round(
        (tele or {}).get("readbacks", 0) / segs, 2)
    d2h_epoch = int((tele or {}).get("d2h_bytes", 0) / segs)
    out["d2h_bytes_per_epoch"] = d2h_epoch
    out["d2h_bytes_per_device_per_epoch"] = d2h_epoch // max(
        (tele or {}).get("data_shards", 1), 1)
    out["data_shards"] = (tele or {}).get("data_shards", 1)
    cifar_ips, _, _ = _measure(
        root.cifar.layers, "cifar_loader", batch, None,
        n_steps=n_steps, n_epochs=n_epochs, fused_extra=fused_extra)
    out["cifar_caffe_images_per_sec"] = round(cifar_ips, 1)
    print(json.dumps(out))


def main_mesh(max_devices=8):
    """``--mesh [N]``: sweep the fused training control plane over
    1/2/4/8 forced virtual CPU host devices (each count in its own
    subprocess — the device count is fixed at backend init) and print
    ONE JSON line with images/sec per device count, scaling efficiency
    (ips_N / (N * ips_1)), the readbacks-per-epoch invariant and
    per-device d2h bytes — the MULTICHIP stamp's payload."""
    import subprocess
    import sys
    counts = [n for n in MESH_DEVICE_COUNTS if n <= max_devices]
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if "xla_force_host_platform_device_count" not in f)
    here = os.path.dirname(os.path.abspath(__file__))
    per_n = {}
    for n in counts:
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=(flags +
                       " --xla_force_host_platform_device_count=%d"
                       % n).strip(),
        )
        code = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
                "import bench; bench._mesh_worker(%d)" % n)
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=here, env=env,
            capture_output=True, text=True, timeout=1800)
        if proc.returncode:
            raise RuntimeError(
                "mesh worker n=%d failed (rc=%d):\n%s"
                % (n, proc.returncode, proc.stderr[-4000:]))
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("{")][-1]
        per_n[n] = json.loads(line)

    def series(key):
        return {str(n): per_n[n][key] for n in counts}

    def efficiency(key):
        base = per_n[counts[0]][key]
        return {str(n): round(per_n[n][key] / (n * base), 3)
                for n in counts if base}

    out = {
        "metric": "mesh_scaling_images_per_sec",
        "device_counts": counts,
        "backend": "forced virtual CPU host devices "
                   "(--xla_force_host_platform_device_count; one "
                   "machine's cores shared across shards — relative "
                   "scaling + invariants, not absolute TPU throughput)",
        "flagship_images_per_sec": series("flagship_images_per_sec"),
        "flagship_scaling_efficiency": efficiency(
            "flagship_images_per_sec"),
        "cifar_caffe_images_per_sec": series(
            "cifar_caffe_images_per_sec"),
        "cifar_caffe_scaling_efficiency": efficiency(
            "cifar_caffe_images_per_sec"),
        # the sharded-async invariant, stamped per device count: must
        # stay == 1.0 (one batched readback per segment) at every width
        "readbacks_per_epoch": series("readbacks_per_epoch"),
        "d2h_bytes_per_epoch": series("d2h_bytes_per_epoch"),
        "d2h_bytes_per_device_per_epoch": series(
            "d2h_bytes_per_device_per_epoch"),
        "data_shards": series("data_shards"),
    }
    print(json.dumps(out))


def _loadgen_models(max_batch=8):
    """The serving control-plane bench fleet: two synthetic FC models
    with DIFFERENT topologies and sample shapes (so nothing shares an
    executable) as in-memory ``(manifest, arrays)`` engine sources.
    Deterministic — every bench process (and the cold-start
    subprocesses) builds byte-identical models."""
    def fc(name_seed, n_in, n_hidden, n_out):
        r = numpy.random.RandomState(name_seed)
        manifest = {
            "format": 1,
            "layers": [
                {"type": "all2all_tanh", "name": "fc0",
                 "arrays": {"weights": "w0.npy", "bias": "b0.npy"},
                 "include_bias": True, "weights_transposed": True},
                {"type": "softmax", "name": "out",
                 "arrays": {"weights": "w1.npy", "bias": "b1.npy"},
                 "include_bias": True, "weights_transposed": True},
            ],
            "input_sample_shape": [n_in],
        }
        arrays = {
            "w0.npy": r.normal(0, 0.05, (n_in, n_hidden))
            .astype(numpy.float32),
            "b0.npy": numpy.zeros(n_hidden, numpy.float32),
            "w1.npy": r.normal(0, 0.05, (n_hidden, n_out))
            .astype(numpy.float32),
            "b1.npy": numpy.zeros(n_out, numpy.float32),
        }
        return manifest, arrays
    return {"alpha": fc(11, 784, 256, 10),
            "beta": fc(22, 128, 64, 5)}


def _coldstart_worker(cache_dir, max_batch=8):
    """Inner process of the cold-start measurement: wire the
    persistent compile cache at ``cache_dir``, build the two-model
    registry (full warmup sweep), and print the compile accounting +
    time-to-ready as ONE JSON line.  Run twice against one directory:
    the first run compiles, the second must deserialize every
    executable (fresh_compiles == 0)."""
    from znicz_tpu.core import compile_cache, telemetry
    from znicz_tpu.serving import ModelRegistry

    telemetry.enable()
    compile_cache.enable(cache_dir)
    watch = compile_cache.watch()
    t0 = time.perf_counter()
    registry = ModelRegistry(models=_loadgen_models(max_batch),
                             max_batch=max_batch)
    ready_s = time.perf_counter() - t0
    assert registry.ready
    out = {"ready_seconds": round(ready_s, 3),
           "fresh_compiles": watch.fresh_compiles()}
    out.update(watch.delta())
    print("COLDSTART " + json.dumps(out))


def _coldstart_block(max_batch=8):
    """Replica cold start, cold vs warm persistent compile cache: two
    fresh subprocesses share one cache directory; the second must
    reach ready with ZERO fresh XLA compiles (every warmup "compile"
    is a cache load) and measurably faster."""
    import shutil
    import subprocess
    import sys as _sys
    import tempfile
    cache_dir = tempfile.mkdtemp(prefix="bench_xla_cache_")
    out = {}
    try:
        for label in ("cold", "warm"):
            proc = subprocess.run(
                [_sys.executable, os.path.abspath(__file__),
                 "--serving-coldstart", cache_dir],
                capture_output=True, text=True, timeout=600,
                cwd=os.path.dirname(os.path.abspath(__file__)))
            lines = [ln for ln in proc.stdout.splitlines()
                     if ln.startswith("COLDSTART ")]
            if proc.returncode != 0 or not lines:
                out[label] = {"error": (proc.stderr or "")[-500:]}
                return out
            out[label] = json.loads(lines[-1][len("COLDSTART "):])
        cold, warm = out["cold"], out["warm"]
        out["warm_zero_fresh_compiles"] = \
            warm.get("fresh_compiles") == 0
        if cold.get("ready_seconds"):
            out["warm_speedup"] = round(
                cold["ready_seconds"] / max(warm["ready_seconds"],
                                            1e-9), 2)
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    return out


def _stamp_serving_control_plane(out):
    """Run the serving control-plane block and stamp it plus the flat
    gated keys (crash-guarded with explicit ZERO stamps so a broken
    serving tier fails tools/bench_gate.py, not the bench) — shared by
    main() and main_serving() so the two entry points can never
    desynchronize the gated schema."""
    try:
        out["serving_control_plane"] = _serving_loadgen_block()
    except Exception as e:  # noqa: BLE001 - never kill the primary
        out["serving_control_plane"] = {"error": repr(e)}
    scp = out["serving_control_plane"]
    out["serving_loadgen_requests_per_sec"] = (
        scp.get("steady", {}).get("achieved_rps") or 0.0)
    out["serving_loadgen_p99_ms"] = (
        scp.get("steady", {}).get("latency_ms", {}).get("p99") or 0.0)
    out["serving_goodput_under_overload_pct"] = (
        scp.get("overload", {}).get("goodput_pct") or 0.0)


def _serving_loadgen_block(steady_s=4.0, overload_s=3.0, max_batch=8,
                           seed=7, coldstart=True):
    """The serving control-plane block: a TWO-MODEL registry behind
    the continuous batcher, driven by the seeded open-loop generator
    (tools/loadgen.py) at a steady rate and at ~3x capacity, plus the
    cold-start compile-cache measurement.  Returns the dict stamped
    under ``"serving_control_plane"``.

    Rates are calibrated in-run (a short probe finds this machine's
    capacity) so the steady block measures healthy-load latency and
    the overload block measures goodput degradation — comparable
    ratios even though absolute req/s differs per machine."""
    import sys as _sys
    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import loadgen
    from znicz_tpu.core.config import root
    from znicz_tpu.core import telemetry
    from znicz_tpu.serving import ContinuousBatcher, ModelRegistry

    telemetry.reset()
    root.common.telemetry.enabled = True
    sources = _loadgen_models(max_batch)
    registry = ModelRegistry(models=sources, max_batch=max_batch)
    batcher = ContinuousBatcher(registry, queue_limit=4096,
                                timeout_ms=0).start()
    models = [loadgen.ModelSpec(
        name, sources[name][0]["input_sample_shape"], max_batch)
        for name in sorted(sources)]

    def submit(name, x, timeout_ms, priority=None):
        return batcher.submit(x, model=name, timeout_ms=timeout_ms,
                              priority=priority)

    slo_ms = float(root.common.serving.get("slo_ms", 100.0))
    compiles0 = telemetry.counter("jax.backend_compiles").value
    try:
        # capacity probe: saturate briefly, read the achieved rate
        probe_plan = loadgen.make_plan(4000.0, 1.0, seed, models)
        probe = loadgen.run(probe_plan, models, submit, slo_ms, 1.0,
                            seed)
        # wall_rps (completions over time-to-last-completion) is the
        # honest capacity: the probe's backlog drains after its offered
        # window, and dividing by the window alone overstates capacity
        # several-fold, which would push the "steady" rate into
        # overload on a busy host
        capacity = max(probe.get("wall_rps") or 0.0, 50.0)
        steady_rate = max(capacity * 0.5, 20.0)
        overload_rate = capacity * 3.0
        # size the queue to HALF the SLO at the measured drain rate:
        # under overload the bounded queue sheds the excess as fast
        # 429s while admitted requests still meet their latency bound
        # — goodput then reads "what fraction of offered load was
        # served WITHIN the SLO", a stable tracked number, instead of
        # the near-zero noise an SLO-oblivious deep queue produces
        rows_per_s = max(
            probe["rows_ok"] / max(probe.get("wall_s") or 1.0, 1.0),
            100.0)
        batcher.queue_limit = max(
            2 * max_batch, int(rows_per_s * (slo_ms / 1e3) * 0.5))
        steady = loadgen.run(
            loadgen.make_plan(steady_rate, steady_s, seed, models),
            models, submit, slo_ms, steady_s, seed)
        overload = loadgen.run(
            loadgen.make_plan(overload_rate, overload_s, seed + 1,
                              models),
            models, submit, slo_ms, overload_s, seed + 1)
    finally:
        batcher.stop()
    out = {
        "models": [m.name for m in models],
        "max_batch": max_batch,
        "slo_ms": slo_ms,
        "probe_capacity_rps": round(capacity, 1),
        "steady": steady,
        "overload": overload,
        "recompiles_in_window":
            telemetry.counter("jax.backend_compiles").value - compiles0,
    }
    if coldstart:
        out["cold_start"] = _coldstart_block(max_batch)
    return out


#: the priority mix the fleet bench offers (ISSUE 15): weighted
#: per-request draw on a dedicated seeded stream — the overload pass
#: must show the low lane shedding while the high lane's goodput holds
FLEET_PRIORITY_MIX = (("high", 1.0), ("normal", 2.0), ("low", 1.0))


def _fleet_model_zip(tmp, n_in=784, n_hidden=1024, depth=6,
                     n_out=10, seed=33):
    """The fleet bench model written to disk (replica subprocesses
    need a loadable source path): a COMPUTE-BOUND deep FC stack
    (784 → 6×1024 → 10, ~24 MB of weights that stay cache-resident
    across dispatches) as a deployment-package zip.  The fleet
    scaling measurement needs per-request work that (a) dominates the
    per-hop proxy cost — scaling trivially cheap models measures the
    Python HTTP plumbing — and (b) is NOT host-DRAM-bandwidth-bound:
    a fleet of memory-bound models on ONE host shares the memory bus,
    which caps aggregate throughput no matter how many replica
    processes run (measured: the 93 MB batch-1 model flatlines at
    ~29 GB/s across any replica count)."""
    from znicz_tpu.testing import build_fc_package_zip
    return build_fc_package_zip(
        os.path.join(tmp, "fleet_model.zip"),
        [n_in] + [n_hidden] * depth + [n_out], seed=seed,
        scale=0.05, weights_transposed=False)


def _priority_overload_measure(seed=7, max_batch=8, overload_s=3.0):
    """Priority lanes under ~3x overload, in process: the two-model
    registry behind the continuous batcher, offered a seeded
    priority-mixed Poisson stream at 3x the probed capacity with the
    queue sized to half the SLO (the ISSUE 8 overload protocol).  The
    evidence the lanes exist for: HIGH-priority goodput holds near the
    healthy number while the LOW lane absorbs the shed as fast 429s.

    Protocol: the three-tier shed curve (low 50 / normal 85 / high
    100 — the documented operator setting for tiered traffic; the
    SHIPPED default keeps normal at the full queue for back-compat).
    With normal at 100 the default lane floods the queue to the brim
    and high-priority work sheds at ADMISSION no matter how dispatch
    ranks it — reserving admission headroom for the high lane is the
    whole point of the curve, and this block measures it."""
    import sys as _sys
    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import loadgen
    from znicz_tpu.core.config import root
    from znicz_tpu.core import telemetry
    from znicz_tpu.serving import ContinuousBatcher, ModelRegistry

    telemetry.reset()
    root.common.telemetry.enabled = True
    shed_curve = {"low": 50.0, "normal": 85.0, "high": 100.0}
    saved_curve = root.common.serving.priority_queue_pct.as_dict()
    root.common.serving.priority_queue_pct.update(shed_curve)
    sources = _loadgen_models(max_batch)
    registry = ModelRegistry(models=sources, max_batch=max_batch)
    batcher = ContinuousBatcher(registry, queue_limit=4096,
                                timeout_ms=0).start()
    models = [loadgen.ModelSpec(
        name, sources[name][0]["input_sample_shape"], max_batch)
        for name in sorted(sources)]

    def submit(name, x, timeout_ms, priority=None):
        return batcher.submit(x, model=name, timeout_ms=timeout_ms,
                              priority=priority)

    slo_ms = float(root.common.serving.get("slo_ms", 100.0))
    try:
        probe = loadgen.run(
            loadgen.make_plan(4000.0, 1.0, seed, models),
            models, submit, slo_ms, 1.0, seed)
        capacity = max(probe.get("wall_rps") or 0.0, 50.0)
        rows_per_s = max(
            probe["rows_ok"] / max(probe.get("wall_s") or 1.0, 1.0),
            100.0)
        batcher.queue_limit = max(
            2 * max_batch, int(rows_per_s * (slo_ms / 1e3) * 0.5))
        overload = loadgen.run(
            loadgen.make_plan(capacity * 3.0, overload_s, seed + 1,
                              models,
                              priority_mix=list(FLEET_PRIORITY_MIX)),
            models, submit, slo_ms, overload_s, seed + 1)
    finally:
        batcher.stop()
        root.common.serving.priority_queue_pct.update(saved_curve)
    return {
        "slo_ms": slo_ms,
        "probe_capacity_rps": round(capacity, 1),
        "offered_rps": overload["offered_rps"],
        "priority_mix": dict(FLEET_PRIORITY_MIX),
        "priority_queue_pct": shed_curve,
        "goodput_pct": overload["goodput_pct"],
        "per_priority": overload["per_priority"],
        "queue_limit_rows": batcher.queue_limit,
    }


#: the ``serve --fleet`` startup banner — the router's URL rides in
#: it (hostnames allowed, same rule as the replica banner regex)
_FLEET_URL_RE = re.compile(r"behind (http://[^/\s:]+:\d+)/")


def _serving_fleet_block(seed=7, max_batch=32, measure_s=4.0):
    """The multi-replica fleet block (ISSUE 15), two measurements:

    * **scaling** — the REAL ``serve --fleet 1`` CLI in its own
      process (router + replica subprocesses sharing one persistent
      compile cache): measure 1-replica wall_rps on a seeded
      saturating ``.npy`` mix, ``POST /fleet/scale_up`` (the new
      replica must reach ready with ZERO fresh compiles — every
      warmup executable deserializes from the fleet cache), then
      measure the 2-replica wall_rps on the SAME seeded mix.
      ``scaling_efficiency_pct`` = 100 * rps2 / (2 * rps1).  Three
      processes, three GILs: the loadgen client, the router and each
      replica all run apart, so the number measures the fleet, not
      one interpreter.  The replicas run on host CPU
      (``JAX_PLATFORMS=cpu``): this measures the control plane's
      horizontal scaling across processes — per-accelerator fleet
      placement is its own ROADMAP item.
    * **priority_overload** — the in-process priority-lane overload
      protocol above (runs on the bench's own backend).
    """
    import shutil
    import subprocess
    import sys as _sys
    import tempfile
    import urllib.request
    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import loadgen
    from znicz_tpu.core.config import root

    # the overload protocol keeps the ISSUE 8 shape (max_batch 8 —
    # comparable with serving_goodput_under_overload_pct); the
    # scaling measure uses larger batches so per-row compute (GIL
    # released, overlapping across replicas) dominates per-request
    # plumbing
    out = {"priority_overload": _priority_overload_measure(
        seed=seed, max_batch=8)}
    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="bench_fleet_")
    slo_ms = float(root.common.serving.get("slo_ms", 100.0))
    proc = None
    try:
        zip_path = _fleet_model_zip(tmp)
        cache_dir = os.path.join(tmp, "xla_cache")
        env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [_sys.executable, "-u", "-m", "znicz_tpu", "serve",
             "fleet_model=" + zip_path, "--fleet", "1", "--port", "0",
             "--max-batch", str(max_batch), "--queue-limit", "4096",
             "--timeout-ms", "0", "--compile-cache", cache_dir],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env, cwd=repo)
        url = None
        deadline = time.monotonic() + 300.0
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            m = _FLEET_URL_RE.search(line)
            if m:
                url = m.group(1)
                break
        if url is None:
            raise RuntimeError("serve --fleet never printed its URL")
        # keep the fleet's stdout drained (banner only — replicas log
        # to their own pipes inside the router process)
        import threading
        threading.Thread(target=proc.stdout.read,
                         name="znicz:bench-stdout-drain",
                         daemon=True).start()
        models = loadgen.discover_models(url)
        pool = loadgen.DaemonPool(256)
        # raw .npy bodies over keep-alive connections: the JSON codec
        # + per-request TCP handshakes cost milliseconds of GIL on
        # both sides — they would become the ceiling the bench
        # measures instead of the fleet
        submit = loadgen.http_submit(url, pool, binary=True)
        # the probe must OFFER well past capacity or it measures its
        # own rate; wall_rps then reads the true drain rate
        probe = loadgen.run(
            loadgen.make_plan(2500.0, 1.0, seed, models),
            models, submit, slo_ms, 1.0, seed)
        capacity = max(probe.get("wall_rps") or 0.0, 20.0)
        rate = capacity * 3.0

        def measure():
            return loadgen.run(
                loadgen.make_plan(rate, measure_s, seed + 1, models),
                models, submit, slo_ms, measure_s, seed + 1)

        one = measure()
        scale_req = urllib.request.Request(
            url + "/fleet/scale_up", b"",
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(scale_req, timeout=300) as resp:
            replica2 = json.loads(resp.read())["replica"]
        # the scale-up cold-start story: the new replica's warmup
        # must be pure cache deserialization (zero fresh compiles)
        with urllib.request.urlopen(replica2["url"] + "/metrics",
                                    timeout=10) as resp:
            metrics2 = resp.read().decode()

        def _counter(text, name):
            for line in text.splitlines():
                if line.startswith(name + " "):
                    return float(line.split()[-1])
            return 0.0

        compiles = _counter(metrics2, "znicz_jax_backend_compiles")
        hits = _counter(metrics2, "znicz_jax_persistent_cache_hits")
        two = measure()
        with urllib.request.urlopen(url + "/statusz",
                                    timeout=30) as resp:
            fleet_status = json.loads(resp.read())["fleet"]
        rps1 = one.get("wall_rps") or 0.0
        rps2 = two.get("wall_rps") or 0.0
        out["scaling"] = {
            "probe_capacity_rps": round(capacity, 1),
            "offered_rps": round(rate, 1),
            "wall_rps_1_replica": rps1,
            "wall_rps_2_replicas": rps2,
            "speedup": (round(rps2 / rps1, 3) if rps1 else None),
            "scaling_efficiency_pct": (
                round(100.0 * rps2 / (2.0 * rps1), 2)
                if rps1 else 0.0),
            "scale_up_backend_compiles": int(compiles),
            "scale_up_cache_hits": int(hits),
            "scale_up_fresh_compiles": int(compiles - hits),
            "scale_up_zero_fresh_compiles": compiles == hits,
            "replicas": fleet_status["replicas"],
        }
    finally:
        if proc is not None:
            proc.terminate()
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _stamp_serving_fleet(out):
    """Run the fleet block and stamp it plus the flat gated keys
    (crash-guarded ZERO stamps — a broken fleet tier fails
    tools/bench_gate.py, never the bench)."""
    try:
        out["serving_fleet"] = _serving_fleet_block()
    except Exception as e:  # noqa: BLE001 - never kill the primary
        out["serving_fleet"] = {"error": repr(e)}
    fleet = out["serving_fleet"]
    out["serving_fleet_scaling_efficiency_pct"] = (
        fleet.get("scaling", {}).get("scaling_efficiency_pct")
        or 0.0)
    out["serving_priority_high_goodput_under_overload_pct"] = (
        (fleet.get("priority_overload", {}).get("per_priority", {})
         .get("high", {}) or {}).get("goodput_pct") or 0.0)


def _serving_fleet_observability_block(seed=11, max_batch=32,
                                       measure_s=3.0):
    """The FLEET-path tracing overhead measurement (ISSUE 16): the
    same seeded open-loop mix against two sequential ``serve --fleet
    1`` fleets sharing ONE persistent compile cache — first with the
    observability plane at its shipped defaults (disabled), then with
    cross-process tracing ARMED (every admission head-sampled at the
    router, propagated to the replica, plus SLO tracking and the
    time-series sampler).  The throughput delta is the armed plane's
    fleet-path cost; separate spawns because the sampling knobs are
    per-process config, and the shared cache keeps the second fleet's
    warmup compile-free so no compile asymmetry pollutes the delta.

    Also reads the armed router's ``/slo`` for the per-request hop
    overhead (router wall minus the replica-reported ``X-Serving-Ms``)
    and proves the armed lap really traced: the router's trace index
    must hold sampled rids and at least one of them must stitch into
    a cross-process tree.

    Stamps follow the ISSUE 14 honest-zero rule: ``overhead_pct`` is
    floored at 1.0 and ``router_hop_overhead_ms`` at 0.01, so an
    honest ~zero measurement never reads as tools/bench_gate.py's
    crash-guard zero; the unfloored values ride along as ``*_raw``."""
    import shutil
    import subprocess
    import sys as _sys
    import tempfile
    import threading
    import urllib.request
    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import loadgen
    from znicz_tpu.core.config import root

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="bench_fleet_obs_")
    slo_ms = float(root.common.serving.get("slo_ms", 100.0))
    try:
        zip_path = _fleet_model_zip(tmp)
        cache_dir = os.path.join(tmp, "xla_cache")
        env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")

        def lap(extra_argv, rid_prefix=None, armed=False):
            proc = subprocess.Popen(
                [_sys.executable, "-u", "-m", "znicz_tpu", "serve",
                 "fleet_model=" + zip_path, "--fleet", "1",
                 "--port", "0", "--max-batch", str(max_batch),
                 "--queue-limit", "4096", "--timeout-ms", "0",
                 "--compile-cache", cache_dir] + list(extra_argv),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env, cwd=repo)
            try:
                url = None
                deadline = time.monotonic() + 300.0
                while time.monotonic() < deadline:
                    line = proc.stdout.readline()
                    if not line:
                        break
                    m = _FLEET_URL_RE.search(line)
                    if m:
                        url = m.group(1)
                        break
                if url is None:
                    raise RuntimeError(
                        "serve --fleet never printed its URL")
                threading.Thread(target=proc.stdout.read,
                                 name="znicz:bench-stdout-drain",
                                 daemon=True).start()
                models = loadgen.discover_models(url)
                pool = loadgen.DaemonPool(128)
                submit = loadgen.http_submit(url, pool, binary=True,
                                             rid_prefix=rid_prefix)
                probe = loadgen.run(
                    loadgen.make_plan(2500.0, 1.0, seed, models),
                    models, submit, slo_ms, 1.0, seed)
                capacity = max(probe.get("wall_rps") or 0.0, 20.0)
                measured = loadgen.run(
                    loadgen.make_plan(capacity * 3.0, measure_s,
                                      seed + 1, models),
                    models, submit, slo_ms, measure_s, seed + 1)

                def fetch(path):
                    with urllib.request.urlopen(
                            url + path, timeout=30) as resp:
                        return json.loads(resp.read())

                extras = {}
                if armed:
                    extras["router_overhead_summary"] = (
                        fetch("/slo").get("router_overhead_ms")
                        or {})
                    index = fetch("/debug/trace")
                    rids = index.get("rids") or []
                    extras["traces_sampled"] = len(rids)
                    extras["fleet_index"] = bool(index.get("fleet"))
                    stitched = False
                    for rid in rids[:8]:  # newest first
                        tree = fetch("/debug/trace/" + rid)
                        if tree.get("stitched"):
                            stitched = True
                            break
                    extras["stitched_tree"] = stitched
                    extras["timeseries_sources"] = (
                        fetch("/debug/timeseries").get("sources")
                        or [])
                return (measured.get("wall_rps") or 0.0), extras
            finally:
                proc.terminate()
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()

        rps_off, _ = lap([])
        rps_on, extras = lap(
            ["--config", "common.serving.trace_sample_n=1",
             "--config", "common.serving.slo_enabled=True",
             "--config", "common.telemetry.timeseries.enabled=True"],
            rid_prefix="benchobs", armed=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    raw = (1.0 - rps_on / max(rps_off, 1e-9)) * 100.0
    hop_raw = (extras.get("router_overhead_summary", {})
               .get("mean_ms") or 0.0)
    return {
        "measure_s": measure_s,
        "disabled_requests_per_sec": round(rps_off, 1),
        "armed_requests_per_sec": round(rps_on, 1),
        "overhead_pct_raw": round(raw, 2),
        "overhead_pct": round(max(raw, 1.0), 2),
        "router_hop_overhead_ms_raw": round(hop_raw, 3),
        "router_hop_overhead_ms": round(max(hop_raw, 0.01), 3),
        "router_overhead_summary":
            extras.get("router_overhead_summary", {}),
        # proof the armed fleet actually traced cross-process (a knob
        # that silently failed to arm would stamp a flattering zero)
        "armed_traces_sampled": extras.get("traces_sampled", 0),
        "armed_fleet_index": extras.get("fleet_index", False),
        "armed_stitched_tree": extras.get("stitched_tree", False),
        "armed_timeseries_sources":
            extras.get("timeseries_sources", []),
    }


def _stamp_serving_fleet_observability(out):
    """Stamp the fleet-tracing overhead block + the flat gated keys
    (crash-guarded ZERO stamps gated INVERTED by tools/bench_gate.py
    — a rise past the band, or a crash-guard zero where the previous
    round had a number, fails the round) — shared by main(),
    main_serving() and the ``--serving-fleet`` CI entry."""
    try:
        out["serving_fleet_observability"] = (
            _serving_fleet_observability_block())
    except Exception as e:  # noqa: BLE001 - never kill the primary
        out["serving_fleet_observability"] = {"error": repr(e)}
    block = out["serving_fleet_observability"]
    out["serving_fleet_observability_overhead_pct"] = (
        block.get("overhead_pct") or 0.0)
    out["serving_router_hop_overhead_ms"] = (
        block.get("router_hop_overhead_ms") or 0.0)


def _serving_release_shadow_block(seed=13, max_batch=32,
                                  measure_s=3.0):
    """The shadow-mirroring tax measurement (ISSUE 17): the same
    seeded open-loop mix against two sequential ``serve --fleet 1``
    fleets sharing ONE persistent compile cache — both with the SLO
    plane armed (a release requires it), the second additionally
    HOLDING a release in shadow at 100% sampling (policy
    ``{"hold": true}``), so every admitted request is mirrored to a
    bit-identical candidate and compared under f32 bit identity.
    The throughput delta is what shadow mirroring costs the live
    path; the candidate shares the compile cache, so no compile
    asymmetry pollutes the delta.

    Proves the shadow lap really mirrored (``shadow.compares`` > 0
    with zero mismatches — same params — before the release is
    aborted) and stamps under the ISSUE 14 honest-zero rule:
    ``overhead_pct`` floored at 1.0, the unfloored value riding
    along as ``*_raw``."""
    import shutil
    import subprocess
    import sys as _sys
    import tempfile
    import threading
    import urllib.request
    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import loadgen
    from znicz_tpu.core.config import root

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="bench_release_")
    slo_ms = float(root.common.serving.get("slo_ms", 100.0))
    try:
        zip_path = _fleet_model_zip(tmp)
        cache_dir = os.path.join(tmp, "xla_cache")
        env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")

        def lap(shadow):
            proc = subprocess.Popen(
                [_sys.executable, "-u", "-m", "znicz_tpu", "serve",
                 "fleet_model=" + zip_path, "--fleet", "1",
                 "--port", "0", "--max-batch", str(max_batch),
                 "--queue-limit", "4096", "--timeout-ms", "0",
                 "--compile-cache", cache_dir,
                 "--config", "common.serving.slo_enabled=True"],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env, cwd=repo)
            try:
                url = None
                deadline = time.monotonic() + 300.0
                while time.monotonic() < deadline:
                    line = proc.stdout.readline()
                    if not line:
                        break
                    m = _FLEET_URL_RE.search(line)
                    if m:
                        url = m.group(1)
                        break
                if url is None:
                    raise RuntimeError(
                        "serve --fleet never printed its URL")
                threading.Thread(target=proc.stdout.read,
                                 name="znicz:bench-stdout-drain",
                                 daemon=True).start()

                def call(path, doc=None, method=None):
                    req = urllib.request.Request(
                        url + path,
                        json.dumps(doc).encode()
                        if doc is not None else None,
                        {"Content-Type": "application/json"},
                        method=method)
                    with urllib.request.urlopen(
                            req, timeout=60) as resp:
                        return json.loads(resp.read())

                models = loadgen.discover_models(url)
                pool = loadgen.DaemonPool(128)
                submit = loadgen.http_submit(url, pool, binary=True)
                probe = loadgen.run(
                    loadgen.make_plan(2500.0, 1.0, seed, models),
                    models, submit, slo_ms, 1.0, seed)
                capacity = max(probe.get("wall_rps") or 0.0, 20.0)
                extras = {}
                if shadow:
                    # a held release: the bit-identical candidate
                    # (same package) shadows 100% of admissions and
                    # never leaves the shadow stage.  The error /
                    # mismatch ceilings are lifted out of the way:
                    # under the 3x overload mix mirrored predictions
                    # legitimately 429, and a release that FAILS
                    # mid-window stops paying the tax being measured
                    # (the block asserts zero mismatches itself)
                    call("/release/fleet_model",
                         {"path": zip_path,
                          "policy": {"hold": True,
                                     "shadow_sample_pct": 100.0,
                                     "shadow_error_max": 10 ** 9,
                                     "shadow_mismatch_max": 10 ** 9}})
                measured = loadgen.run(
                    loadgen.make_plan(capacity * 3.0, measure_s,
                                      seed + 1, models),
                    models, submit, slo_ms, measure_s, seed + 1)
                if shadow:
                    st = call("/release/fleet_model")
                    extras["shadow"] = st.get("shadow") or {}
                    extras["state"] = st.get("state")
                    if st.get("state") == "shadow":
                        call("/release/fleet_model",
                             method="DELETE")
                return (measured.get("wall_rps") or 0.0), extras
            finally:
                proc.terminate()
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()

        rps_off, _ = lap(shadow=False)
        rps_on, extras = lap(shadow=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    sh = extras.get("shadow", {})
    if extras.get("state") != "shadow":
        raise RuntimeError(
            "release left the shadow stage mid-window (state=%r): "
            "part of the measured lap paid no mirroring tax"
            % extras.get("state"))
    if not sh.get("compares"):
        raise RuntimeError(
            "shadow lap never compared a mirrored request "
            "(state=%r): the overhead number would be fiction"
            % extras.get("state"))
    raw = (1.0 - rps_on / max(rps_off, 1e-9)) * 100.0
    return {
        "measure_s": measure_s,
        "live_requests_per_sec": round(rps_off, 1),
        "shadowed_requests_per_sec": round(rps_on, 1),
        "overhead_pct_raw": round(raw, 2),
        "overhead_pct": round(max(raw, 1.0), 2),
        # proof the lap mirrored (and how much backpressure dropped):
        # a release that silently failed to shadow would stamp a
        # flattering zero.  mismatches ride along as DATA, not a
        # failure: under co-batching the same row can land in
        # different buckets live vs mirrored, and XLA picks a
        # different f32 GEMM tiling per bucket — reassociation, not
        # a broken candidate (the release plane's bit-identity gate
        # is for like-for-like deployments, which quiet traffic is
        # and a 3x-overload mirror is not)
        "shadow_compares": sh.get("compares", 0),
        "shadow_mismatches": sh.get("mismatches", 0),
        "shadow_dropped": sh.get("dropped", 0),
        "shadow_state": extras.get("state"),
    }


def _stamp_serving_release_shadow(out):
    """Stamp the shadow-mirroring overhead block + the flat gated key
    (crash-guarded ZERO stamp gated INVERTED by tools/bench_gate.py)
    — shared by main(), main_serving() and the ``--serving-fleet``
    CI entry."""
    try:
        out["serving_release_shadow"] = (
            _serving_release_shadow_block())
    except Exception as e:  # noqa: BLE001 - never kill the primary
        out["serving_release_shadow"] = {"error": repr(e)}
    out["serving_release_shadow_overhead_pct"] = (
        out["serving_release_shadow"].get("overhead_pct") or 0.0)


def _serving_wire_block(seed=17, max_batch=32, measure_s=3.0):
    """The binary framed-relay measurement (ISSUE 20): the same
    seeded open-loop mix against two sequential ``serve --fleet 1``
    fleets sharing ONE persistent compile cache — first with the
    relay at its shipped default (ENABLED: the client speaks
    ``--wire binary`` frames to the router, the router multiplexes
    persistent frame connections to the replica, the ``.npy`` body is
    decoded exactly once fleet-wide), then with the relay DISABLED
    (``common.serving.wire.enabled=False``: the documented JSON/HTTP
    compatibility surface end to end — per-request ``http.client``
    round-trips, JSON decoded at the replica).

    Two numbers matter:

    * ``wall_rps`` over the binary transport (GATED: a round where
      the relay throughput drops out of band fails bench_gate);
    * ``hop_speedup_x`` — the router's per-request hop overhead
      (router wall minus the replica-reported ``X-Serving-Ms``, the
      /slo aggregation's mean) under HTTP/JSON divided by the same
      mean under the relay.  The ISSUE 20 acceptance wants >= 2x.

    The hop read comes from a SERIAL closed-loop lap (one request in
    flight at a time, the same seeded row mix both codecs) taken
    BEFORE any overload traffic: /slo's overhead aggregation is a
    rolling window of OK requests, and an open-loop overload lap
    fills it with queue-wait (the relay pools round trips where HTTP
    queues inside the replica's serving window — the two codecs
    park their backlog on opposite sides of the ``X-Serving-Ms``
    boundary, so an overloaded window measures backlog placement,
    not the hop).  Serial traffic has no backlog anywhere, so the
    window holds pure per-request transport cost for both codecs.
    ``wall_rps`` then comes from the usual saturating probe +
    3x-overload open-loop lap (the drain-rate protocol every other
    fleet block uses) AFTER the hop read.

    Proves the relay lap really rode the wire (the router statusz
    mux block must show round trips and zero protocol errors) and
    floors the stamped hop means at 0.005 ms — the honest-zero rule:
    a ~zero measurement must never read as bench_gate's crash-guard
    zero."""
    import shutil
    import subprocess
    import sys as _sys
    import tempfile
    import threading
    import urllib.request
    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import loadgen
    from znicz_tpu.core.config import root

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="bench_wire_")
    slo_ms = float(root.common.serving.get("slo_ms", 100.0))
    try:
        zip_path = _fleet_model_zip(tmp)
        cache_dir = os.path.join(tmp, "xla_cache")
        env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")

        def lap(wire):
            argv = ["--config", "common.serving.slo_enabled=True"]
            if not wire:
                argv += ["--config",
                         "common.serving.wire.enabled=False"]
            proc = subprocess.Popen(
                [_sys.executable, "-u", "-m", "znicz_tpu", "serve",
                 "fleet_model=" + zip_path, "--fleet", "1",
                 "--port", "0", "--max-batch", str(max_batch),
                 "--queue-limit", "4096", "--timeout-ms", "0",
                 "--compile-cache", cache_dir] + argv,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env, cwd=repo)
            try:
                url = None
                deadline = time.monotonic() + 300.0
                while time.monotonic() < deadline:
                    line = proc.stdout.readline()
                    if not line:
                        break
                    m = _FLEET_URL_RE.search(line)
                    if m:
                        url = m.group(1)
                        break
                if url is None:
                    raise RuntimeError(
                        "serve --fleet never printed its URL")
                threading.Thread(target=proc.stdout.read,
                                 name="znicz:bench-stdout-drain",
                                 daemon=True).start()
                models = loadgen.discover_models(url)
                pool = loadgen.DaemonPool(64)
                if wire:
                    submit = loadgen.wire_submit(url, pool)
                else:
                    submit = loadgen.http_submit(url, pool)

                def fetch(path):
                    with urllib.request.urlopen(
                            url + path, timeout=30) as resp:
                        return json.loads(resp.read())

                # --- hop lap: serial closed loop, nothing queues.
                # The seeded plan supplies the row mix; the schedule
                # times are ignored — each request waits for the
                # previous reply, so /slo's rolling overhead window
                # ends up holding exactly these unqueued samples.
                inputs = loadgen.make_inputs(models, seed)
                for _, mi, rows, prio in loadgen.make_plan(
                        1000.0, 1.0, seed, models)[:48]:
                    try:
                        submit(models[mi].name, inputs[mi][:rows],
                               None, prio).result(timeout=120)
                    except Exception:  # noqa: BLE001 - hop lap is
                        pass           # best-effort; /slo only
                                       # aggregates OK requests
                hop = (fetch("/slo").get("router_overhead_ms")
                       or {})
                # --- throughput lap: saturating probe calibrates
                # capacity, then the 3x-overload open-loop mix reads
                # the drain rate (wall_rps) — same protocol as the
                # fleet scaling block
                probe = loadgen.run(
                    loadgen.make_plan(300.0, 1.0, seed, models),
                    models, submit, slo_ms, 1.0, seed)
                capacity = max(probe.get("wall_rps") or 0.0, 10.0)
                time.sleep(2.0)  # let the probe backlog shed
                measured = loadgen.run(
                    loadgen.make_plan(capacity * 3.0, measure_s,
                                      seed + 1, models),
                    models, submit, slo_ms, measure_s, seed + 1)
                mux = fetch("/statusz").get("wire") or {}
                return ((measured.get("wall_rps") or 0.0), hop,
                        mux)
            finally:
                proc.terminate()
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()

        rps_wire, hop_wire, mux = lap(wire=True)
        rps_http, hop_http, _ = lap(wire=False)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if not mux.get("round_trips"):
        raise RuntimeError(
            "the wire lap shows zero mux round trips — the relay "
            "never carried the traffic and the speedup would be "
            "fiction (statusz wire: %r)" % (mux,))
    wire_ms = max(hop_wire.get("mean_ms") or 0.0, 0.005)
    http_ms = max(hop_http.get("mean_ms") or 0.0, 0.005)
    return {
        "measure_s": measure_s,
        "wire_wall_rps": round(rps_wire, 1),
        "http_wall_rps": round(rps_http, 1),
        "hop_overhead_wire_ms": round(wire_ms, 3),
        "hop_overhead_http_ms": round(http_ms, 3),
        "hop_speedup_x": round(http_ms / wire_ms, 2),
        "router_overhead_summary_wire": hop_wire,
        "router_overhead_summary_http": hop_http,
        # proof the relay lap rode the wire (a silently-disabled
        # listener would fall back to HTTP and stamp speedup ~1.0)
        "wire_mux": mux,
    }


def _stamp_serving_wire(out):
    """Stamp the binary-relay block + the flat gated keys
    (crash-guarded ZERO stamps — ``serving_wire_wall_rps`` is a
    regular throughput gate in tools/bench_gate.py: a relay that
    broke, or silently fell back to HTTP, fails the gate, never the
    bench) — shared by main(), main_serving() and the
    ``--serving-fleet`` CI entry."""
    try:
        out["serving_wire"] = _serving_wire_block()
    except Exception as e:  # noqa: BLE001 - never kill the primary
        out["serving_wire"] = {"error": repr(e)}
    block = out["serving_wire"]
    out["serving_wire_wall_rps"] = block.get("wire_wall_rps") or 0.0
    out["serving_wire_hop_speedup_x"] = (
        block.get("hop_speedup_x") or 0.0)


#: the serving precision axis the bench sweeps (ISSUE 10; ISSUE 12
#: adds the f32-fast batch-1 latency mode to the same roofline)
PRECISION_DTYPES = ("f32", "f32_fast", "bf16", "int8")


def _precision_model(n_in=784, n_hidden=2048, n_out=10, seed=33):
    """The MEMORY-BOUND serving model for the precision sweep: a wide
    FC stack (~23 MB of f32 weights) whose batch-1 forward reads every
    weight byte per prediction — operational intensity ~1 FLOP/byte,
    far under any ridge point, so requests/sec tracks weight bytes and
    the 4x/2x byte cuts of int8/bf16 are directly measurable.
    Weights store in the standard ``(out, in)`` layout every unit's
    ``package_export`` emits.  Deterministic in-memory (manifest,
    arrays) source."""
    r = numpy.random.RandomState(seed)
    manifest = {
        "format": 1,
        "layers": [
            {"type": "all2all_tanh", "name": "fc0",
             "arrays": {"weights": "w0.npy", "bias": "b0.npy"},
             "include_bias": True, "weights_transposed": False},
            {"type": "all2all_tanh", "name": "fc1",
             "arrays": {"weights": "w1.npy", "bias": "b1.npy"},
             "include_bias": True, "weights_transposed": False},
            {"type": "softmax", "name": "out",
             "arrays": {"weights": "w2.npy", "bias": "b2.npy"},
             "include_bias": True, "weights_transposed": False},
        ],
        "input_sample_shape": [n_in],
    }
    arrays = {
        "w0.npy": r.normal(0, 0.05, (n_hidden, n_in))
        .astype(numpy.float32),
        "b0.npy": numpy.zeros(n_hidden, numpy.float32),
        "w1.npy": r.normal(0, 0.05, (n_hidden, n_hidden))
        .astype(numpy.float32),
        "b1.npy": numpy.zeros(n_hidden, numpy.float32),
        "w2.npy": r.normal(0, 0.05, (n_out, n_hidden))
        .astype(numpy.float32),
        "b2.npy": numpy.zeros(n_out, numpy.float32),
    }
    return manifest, arrays


def _serving_precision_block(peaks, n_requests=300):
    """Per-dtype serving throughput + roofline on the memory-bound
    model (ISSUE 10): one engine per serving dtype (f32 / bf16 /
    int8), single-row requests against the batch-1 bucket — the
    low-latency regime where the forward is weight-bandwidth-bound —
    with the cost registry recording each dtype's measured
    bytes-accessed and operational intensity, and the accuracy harness
    stamping the per-bucket output deltas next to the throughput.

    The tracked claims: the int8 executable reads ~4x fewer weight
    bytes (operational intensity UP), and on the memory-bound model
    that converts into measurably higher requests/sec than f32 in the
    SAME run — `int8_faster_than_f32` / `int8_intensity_gain` make the
    memory-bound win a gated number, not a slogan.  (On CPU the win
    lives at batch 1: XLA's CPU backend materializes the dequant for
    real GEMMs, while the batch-1 matvec fuses it and reads int8
    straight from memory — the TPU backend fuses both.  docs/serving.md
    "Precision modes".)
    """
    from znicz_tpu.core import profiler, telemetry
    from znicz_tpu.serving import InferenceEngine, accuracy

    telemetry.enable()
    profiler.enable()
    src = _precision_model()
    n_in = src[0]["input_sample_shape"][0]
    row = numpy.random.RandomState(5).uniform(
        -1, 1, (1, n_in)).astype(numpy.float32)
    f32_bytes = sum(a.nbytes for a in src[1].values())
    out = {"model": "fc %d-%d-%d-%d, batch-1 bucket, %.1f MB f32 "
                    "weights"
                    % (n_in, src[1]["w0.npy"].shape[0],
                       src[1]["w1.npy"].shape[0],
                       src[1]["w2.npy"].shape[0], f32_bytes / 1e6),
           "n_requests": n_requests, "dtypes": {}}
    for dt in PRECISION_DTYPES:
        engine = InferenceEngine(src, max_batch=1, dtype=dt,
                                 name="prec_%s" % dt)
        y = engine.predict(row)  # bucket warm; prime the row path
        t0 = time.perf_counter()
        for _ in range(n_requests):
            engine.predict(row)
        elapsed = time.perf_counter() - t0
        # meta-addressed lookup (model + dtype + bucket) — survives
        # any drift in the engine's cost-entry NAMING convention,
        # which this block must not duplicate
        entries = profiler.cost_entries_by_meta(
            model="prec_%s" % dt, dtype=dt, bucket=1)
        entry = entries[0] if entries else {}
        rps = n_requests / elapsed
        # the roofline-relevant traffic of a weight-streaming forward:
        # the resident (dtype-sized) params plus request I/O — what
        # MUST cross device memory per dispatch.  The raw HLO
        # ``bytes_accessed`` (also stamped) counts every pre-fusion
        # intermediate, including the folded dequant's virtual f32
        # weights that never leave registers, so it would charge int8
        # for bytes it exists to avoid.
        traffic = engine.device_bytes + row.nbytes + y.nbytes
        d = {
            "requests_per_sec": round(rps, 1),
            "latency_ms_mean": round(1e3 * elapsed / n_requests, 3),
            "device_weight_bytes": engine.device_bytes,
            "cost_executable": entry.get("name"),
            "flops": entry.get("flops"),
            "bytes_accessed_hlo": entry.get("bytes_accessed"),
            "bytes_per_prediction": traffic,
        }
        if entry.get("flops"):
            d["operational_intensity"] = round(
                entry["flops"] / traffic, 4)
        if peaks and entry.get("flops"):
            d["mfu_pct"] = round(
                100.0 * rps * entry["flops"] / peaks["flops"], 3)
            ridge = peaks["flops"] / peaks["hbm_bytes_per_sec"]
            oi = d.get("operational_intensity")
            if oi is not None:
                d["roofline_bound"] = ("memory" if oi < ridge
                                       else "compute")
        out["dtypes"][dt] = d
    f32 = out["dtypes"]["f32"]
    for dt in ("f32_fast", "bf16", "int8"):
        d = out["dtypes"][dt]
        if f32["requests_per_sec"]:
            d["speedup_vs_f32"] = round(
                d["requests_per_sec"] / f32["requests_per_sec"], 3)
        if f32.get("operational_intensity") and \
                d.get("operational_intensity"):
            d["intensity_vs_f32"] = round(
                d["operational_intensity"]
                / f32["operational_intensity"], 3)
    int8 = out["dtypes"]["int8"]
    out["int8_faster_than_f32"] = bool(
        int8["requests_per_sec"] > f32["requests_per_sec"])
    out["int8_intensity_gain"] = int8.get("intensity_vs_f32")
    # the accuracy axis, same source, per bucket (ladder 1..4 keeps
    # the report to 12 small compiles) — deltas vs the documented pins
    out["accuracy"] = accuracy.dtype_delta_report(
        src, dtypes=("f32_fast", "bf16", "int8"), max_batch=4,
        n_rows=32)
    return out


#: the flat gated tail keys (tools/bench_gate.py GATED_INVERSE) and
#: the scenario each one tracks — one schema for the stamping helper,
#: the --serving-tail CI assertion and the gate
TAIL_P99_KEYS = {
    "serving_tail_p99_ms": "steady",
    "serving_tail_cold_bucket_p99_ms": "cold_bucket",
    "serving_tail_evict_restore_p99_ms": "evict_restore",
    "serving_tail_breaker_probe_p99_ms": "breaker_probe",
}


def _serving_tail_block(n_steady=300):
    """The batch-1 tail-latency block (ISSUE 12): the f32-fast engine
    on the memory-bound precision model, measured under the
    adversarial mixes real traffic hits —

    * ``steady`` — warmed batch-1 dispatches (the fast-path headline;
      its req/s is the gated ``serving_f32_batch1_requests_per_sec``
      and its exact p99 the gated ``serving_tail_p99_ms``),
    * ``cold_bucket`` — the FIRST request of every bucket on a fresh
      un-warmed replica (trace+compile on the request path; a
      persistent-cache load when the compile cache is wired),
    * ``evict_restore`` — the request that pays a registry-LRU
      evict's lazy restore (re-upload + rebuild + re-warm),
    * ``breaker_probe`` — the half-open probe through a recovering
      circuit breaker.

    A strict-f32 steady reference runs next to it so the stamped
    block carries the fast-vs-strict speedup (the number that closes
    ROADMAP item 5), and every scenario's samples land in the
    ``serving.tail_seconds.scenario_*`` histogram series.  Exact
    quantiles from retained samples throughout
    (znicz_tpu/serving/latency.py)."""
    from znicz_tpu.core import telemetry
    from znicz_tpu.serving import InferenceEngine
    from znicz_tpu.serving import latency

    telemetry.enable()
    src = _precision_model()
    n_in = src[0]["input_sample_shape"][0]
    row = numpy.random.RandomState(5).uniform(
        -1, 1, (1, n_in)).astype(numpy.float32)
    buckets = (1, 2, 4, 8)

    # strict f32 steady reference (today's shipped slow path — the
    # PR 10 73-117 req/s regime; a short loop, it is ~15x slower)
    strict = InferenceEngine(src, max_batch=1, dtype="f32",
                             name="tail_f32")
    s_samples, s_elapsed = latency.run_steady(strict, row,
                                              n=max(20, n_steady // 6))
    strict_block = dict(latency.quantile_summary(s_samples),
                        requests_per_sec=round(
                            len(s_samples) / s_elapsed, 1))

    engine = InferenceEngine(src, buckets=buckets, dtype="f32-fast",
                             name="tail_fast")
    compiles0 = telemetry.counter("jax.backend_compiles").value
    f_samples, f_elapsed = latency.run_steady(engine, row, n=n_steady)
    steady_recompiles = (telemetry.counter("jax.backend_compiles").value
                         - compiles0)
    scenarios = {"steady": latency.quantile_summary(f_samples)}

    cold = latency.run_cold_bucket(
        lambda: InferenceEngine(src, buckets=buckets, dtype="f32-fast",
                                warmup=False, name="tail_fast"),
        (n_in,), trials=2)
    scenarios["cold_bucket"] = latency.quantile_summary(cold)

    ev_samples, ev_replies = latency.run_evict_restore(engine, row,
                                                       n=3)
    scenarios["evict_restore"] = latency.quantile_summary(ev_samples)

    pr_samples, pr_replies = latency.run_breaker_probe(engine, row,
                                                       trials=2)
    scenarios["breaker_probe"] = latency.quantile_summary(pr_samples)

    y_strict = strict.predict(row)
    y_fast = engine.predict(row)
    fast_rps = len(f_samples) / f_elapsed
    out = {
        "model": src[0]["input_sample_shape"],
        "fast_dtype": engine.serve_dtype,
        "latency_bucket_max": engine.stats().get("latency_bucket_max"),
        "buckets": list(buckets),
        "strict_f32": strict_block,
        "scenarios": scenarios,
        "f32_batch1_requests_per_sec": round(fast_rps, 1),
        "fast_vs_strict_speedup": round(
            fast_rps / max(strict_block["requests_per_sec"], 1e-9), 2),
        "steady_recompiles": steady_recompiles,
        "fast_strict_max_delta": float(
            numpy.abs(y_fast - y_strict).max()),
        "fast_bit_identical_to_strict": bool(
            (y_fast == y_strict).all()),
        "compile_keys_distinct": engine.compile_key !=
        strict.compile_key,
        # correctness rides the latency numbers: scenario replies
        # must match the fast path's own steady answer exactly
        "scenario_replies_exact": bool(
            all((y == y_fast).all() for y in ev_replies) and
            all((y == y_fast).all() for y in pr_replies)),
    }
    return out


def _stamp_serving_tail(out):
    """Stamp the tail-latency block + the flat gated keys — req/s
    (gated like throughput) and the per-scenario exact p99s (gated
    INVERTED).  Crash-guarded ZERO stamps: a broken latency tier
    fails tools/bench_gate.py, never the bench."""
    try:
        out["serving_tail_latency"] = _serving_tail_block()
    except Exception as e:  # noqa: BLE001 - never kill the primary
        out["serving_tail_latency"] = {"error": repr(e)}
    block = out["serving_tail_latency"]
    out["serving_f32_batch1_requests_per_sec"] = (
        block.get("f32_batch1_requests_per_sec") or 0.0)
    scenarios = block.get("scenarios", {})
    for key, scenario in sorted(TAIL_P99_KEYS.items()):
        out[key] = (scenarios.get(scenario, {}) or {}).get("p99_ms") \
            or 0.0


def _serving_observability_block(duration=2.0, clients=8,
                                 max_batch=8):
    """The SLO-plane overhead measurement (ISSUE 14): the SAME
    closed-loop HTTP mix against one registry server twice — first
    with the observability plane DISABLED (its shipped default), then
    ARMED (time-series sampler at a fast interval + every request
    trace-sampled + SLO tracking) — and the throughput delta between
    the two laps is the plane's measured cost.  One server and one
    engine serve both laps, so no compile/warmup asymmetry pollutes
    the number; a short warm lap ahead of the timed laps absorbs
    first-dispatch jitter.

    ``overhead_pct`` is floored at 1.0 for the stamp: tools/bench_gate
    treats a zero as the crash-guard sentinel (a 100% regression), so
    an honest ~zero (or negative — noise) measurement must never read
    as a broken tier; the unfloored value rides along as
    ``overhead_pct_raw``."""
    import threading
    import urllib.request
    from znicz_tpu.core.config import root
    from znicz_tpu.core import telemetry, timeseries
    from znicz_tpu.serving import ModelRegistry, ServingServer

    telemetry.reset()
    timeseries.reset()
    root.common.telemetry.enabled = True
    sources = _loadgen_models(max_batch)
    registry = ModelRegistry(models=sources, max_batch=max_batch)
    server = ServingServer(registry=registry).start()
    url = "http://127.0.0.1:%d" % server.port
    names = sorted(sources)
    r = numpy.random.RandomState(3)
    bodies = {}
    for name in names:
        n_in = sources[name][0]["input_sample_shape"][0]
        bodies[name] = [
            json.dumps({"inputs": r.uniform(
                -1, 1, (1 + i % max_batch, n_in)).tolist()}).encode()
            for i in range(4)]

    def lap(seconds):
        stop = threading.Event()
        done = [0] * clients
        errors = []

        def client(k):
            i = k
            try:
                while not stop.is_set():
                    name = names[i % len(names)]
                    req = urllib.request.Request(
                        url + "/predict/" + name,
                        bodies[name][i % len(bodies[name])],
                        {"Content-Type": "application/json"})
                    with urllib.request.urlopen(req,
                                                timeout=60) as resp:
                        resp.read()
                        assert resp.status == 200
                    done[k] += 1
                    i += 1
            except Exception as e:  # noqa: BLE001 - re-raised below
                errors.append(repr(e))
                stop.set()

        threads = [threading.Thread(target=client, args=(k,),
                                    name="znicz:bench-client-%d" % k,
                                    daemon=True)
                   for k in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(seconds)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        if errors:
            # a dead client thread would silently skew the rps the
            # gated overhead number is computed from — fail the whole
            # block instead (the crash-guard stamps a LOUD zero that
            # fails bench_gate, never a quietly-wrong percentage)
            raise RuntimeError(
                "observability lap lost %d client(s): %s"
                % (len(errors), errors[:3]))
        return done, time.perf_counter() - t0

    cfg = root.common.serving
    saved = (cfg.get("slo_enabled", False),
             cfg.get("trace_sample_n", 0),
             root.common.telemetry.timeseries.get("enabled", False),
             root.common.telemetry.timeseries.get("interval_ms",
                                                  1000.0))
    try:
        lap(0.4)  # warm: dispatch paths hot before either timed lap
        done_off, wall_off = lap(duration)
        # arm the WHOLE plane: sampler on a fast interval, every
        # request sampled into a trace tree, SLO accounting on
        root.common.serving.slo_enabled = True
        root.common.serving.trace_sample_n = 1
        root.common.telemetry.timeseries.enabled = True
        root.common.telemetry.timeseries.interval_ms = 100.0
        from znicz_tpu.serving import reqtrace
        reqtrace.reset()
        timeseries.maybe_start()
        done_on, wall_on = lap(duration)
        slo_status = server.slo.status()
        ts_series = len(timeseries.series_names())
        traces = len(reqtrace.rids())
    finally:
        (root.common.serving.slo_enabled,
         root.common.serving.trace_sample_n,
         root.common.telemetry.timeseries.enabled,
         root.common.telemetry.timeseries.interval_ms) = saved
        timeseries.stop()
        server.stop()
    rps_off = sum(done_off) / wall_off
    rps_on = sum(done_on) / wall_on
    raw = (1.0 - rps_on / max(rps_off, 1e-9)) * 100.0
    tracked = sum(m.get("total", 0)
                  for m in slo_status.get("models", {}).values())
    return {
        "clients": clients,
        "duration_s": duration,
        "disabled_requests_per_sec": round(rps_off, 1),
        "armed_requests_per_sec": round(rps_on, 1),
        "overhead_pct_raw": round(raw, 2),
        "overhead_pct": round(max(raw, 1.0), 2),
        # proof the armed lap actually exercised the plane (a knob
        # that silently failed to arm would stamp a flattering zero)
        "armed_slo_requests_tracked": tracked,
        "armed_timeseries_series": ts_series,
        "armed_traces_sampled": traces,
    }


def _stamp_serving_observability(out):
    """Stamp the SLO-plane overhead block + the flat gated key
    (crash-guarded ZERO stamp; tools/bench_gate.py gates it INVERTED
    — a rise past the band fails the round) — shared by main(),
    main_serving() and the ``--serving-obs`` CI entry."""
    try:
        out["serving_observability"] = _serving_observability_block()
    except Exception as e:  # noqa: BLE001 - never kill the primary
        out["serving_observability"] = {"error": repr(e)}
    block = out["serving_observability"]
    out["serving_observability_overhead_pct"] = (
        block.get("overhead_pct") or 0.0)


def _serving_pyprof_block(duration=2.0, clients=8, max_batch=8):
    """The continuous-profiler cost ledger (ISSUE 18): the SAME
    closed-loop HTTP mix against one registry server twice — first
    with the sampler DISABLED (its shipped default), then ARMED at
    its stock 97 Hz — and, from the armed window's phase aggregates,
    the first continuously-measured Python data-plane tax:

    * ``overhead_pct`` — the armed-vs-disabled goodput delta, the
      PR 14 methodology (one server/engine both laps, warm lap
      first); floored at 1.0 for the stamp because tools/bench_gate
      treats zero as the crash-guard sentinel, raw rides along;
    * ``dataplane_python_pct`` — the share of non-idle samples
      (everything but ``lock_wait``: a parked worker awaiting a
      batch slot is capacity, not cost) spent in the Python
      codec/relay phases (``json_decode``/``npy_decode``/
      ``serialize``/``socket_io``).  The closed-loop clients run in
      process, so this is the END-TO-END per-request tax — client
      codec + server codec + socket relay — exactly the ledger
      ROADMAP item 3's zero-copy rewrite must measurably beat."""
    import threading
    import urllib.request
    from znicz_tpu.core.config import root
    from znicz_tpu.core import pyprof, telemetry
    from znicz_tpu.serving import ModelRegistry, ServingServer

    telemetry.reset()
    pyprof.reset()
    # the bench driver's own main thread shows up in every sweep
    # (it sleeps out the lap windows) — adopt the registry name so
    # the ledger attributes it instead of diluting attributed_pct
    pyprof.name_current_thread("bench-main")
    root.common.telemetry.enabled = True
    sources = _loadgen_models(max_batch)
    registry = ModelRegistry(models=sources, max_batch=max_batch)
    server = ServingServer(registry=registry).start()
    url = "http://127.0.0.1:%d" % server.port
    names = sorted(sources)
    r = numpy.random.RandomState(5)
    bodies = {}
    for name in names:
        n_in = sources[name][0]["input_sample_shape"][0]
        bodies[name] = [
            json.dumps({"inputs": r.uniform(
                -1, 1, (1 + i % max_batch, n_in)).tolist()}).encode()
            for i in range(4)]

    def lap(seconds):
        stop = threading.Event()
        done = [0] * clients
        errors = []

        def client(k):
            i = k
            try:
                while not stop.is_set():
                    name = names[i % len(names)]
                    req = urllib.request.Request(
                        url + "/predict/" + name,
                        bodies[name][i % len(bodies[name])],
                        {"Content-Type": "application/json"})
                    with urllib.request.urlopen(req,
                                                timeout=60) as resp:
                        resp.read()
                        assert resp.status == 200
                    done[k] += 1
                    i += 1
            except Exception as e:  # noqa: BLE001 - re-raised below
                errors.append(repr(e))
                stop.set()

        threads = [threading.Thread(target=client, args=(k,),
                                    name="znicz:bench-client-%d" % k,
                                    daemon=True)
                   for k in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(seconds)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        if errors:
            # a dead client thread would skew both the rps delta and
            # the phase mix — fail the block loudly instead
            raise RuntimeError(
                "pyprof lap lost %d client(s): %s"
                % (len(errors), errors[:3]))
        return done, time.perf_counter() - t0

    saved = bool(root.common.profiler.pyprof.get("enabled", False))
    try:
        lap(0.4)  # warm: dispatch paths hot before either timed lap
        done_off, wall_off = lap(duration)
        pyprof.enable()
        pyprof.maybe_start()
        before = pyprof.snapshot()
        done_on, wall_on = lap(duration)
        window = pyprof.diff_snapshots(before, pyprof.snapshot())
    finally:
        root.common.profiler.pyprof.enabled = saved
        pyprof.reset()   # stops the sampler, drops the aggregates
        server.stop()
    rps_off = sum(done_off) / wall_off
    rps_on = sum(done_on) / wall_on
    raw = (1.0 - rps_on / max(rps_off, 1e-9)) * 100.0
    phases = window.get("phases") or {}
    samples = int(window.get("samples", 0))
    active = max(1, samples - int(phases.get("lock_wait", 0)))
    dataplane = 100.0 * sum(
        int(phases.get(p, 0)) for p in pyprof.DATAPLANE_PHASES) \
        / active
    return {
        "clients": clients,
        "duration_s": duration,
        "disabled_requests_per_sec": round(rps_off, 1),
        "armed_requests_per_sec": round(rps_on, 1),
        "overhead_pct_raw": round(raw, 2),
        "overhead_pct": round(max(raw, 1.0), 2),
        "dataplane_python_pct": round(dataplane, 2),
        # proof the armed lap actually sampled (a knob that silently
        # failed to arm would stamp a flattering zero) + the per-
        # phase/per-component breakdown BENCH_NOTES records as the
        # ROADMAP item-3 baseline
        "armed_pyprof_samples": samples,
        "active_samples": active,
        "attributed_pct": window.get("attributed_pct", 0.0),
        "phases": phases,
        "components": window.get("components") or {},
        "gil_wait_ms": (window.get("gil") or {}).get("wait_ms", 0.0),
        "sampler_self_pct": (window.get("overhead")
                             or {}).get("pct", 0.0),
    }


def _stamp_serving_pyprof(out):
    """Stamp the continuous-profiler cost-ledger block + the two flat
    keys (crash-guarded ZERO stamps): ``serving_pyprof_overhead_pct``
    is gated INVERTED by tools/bench_gate.py (the sampler's tax must
    stay bounded); ``serving_dataplane_python_pct`` is deliberately
    NOT gated directionally — driving it DOWN is ROADMAP item 3's
    goal, so a band gate would punish the improvement — but CI
    asserts it stamps nonzero (a zero means the sampler armed and saw
    no data plane: broken).  Shared by main(), main_serving() and the
    ``--serving-pyprof`` CI entry."""
    try:
        out["serving_pyprof"] = _serving_pyprof_block()
    except Exception as e:  # noqa: BLE001 - never kill the primary
        out["serving_pyprof"] = {"error": repr(e)}
    block = out["serving_pyprof"]
    out["serving_pyprof_overhead_pct"] = (
        block.get("overhead_pct") or 0.0)
    out["serving_dataplane_python_pct"] = (
        block.get("dataplane_python_pct") or 0.0)


def _serving_blackbox_block(duration=2.0, clients=8, max_batch=8):
    """The durable blackbox's write-through tax (ISSUE 19): the SAME
    closed-loop HTTP mix against one registry server twice — both
    laps with the SLO tracker and 1-in-8 trace sampling on (the
    planes that actually feed the blackbox), first with the blackbox
    DISABLED (its shipped default), then ARMED into a tempdir — so
    the goodput delta isolates the on-disk write-through itself:
    per-event journal appends, finish-time trace persistence, and
    the sampler checkpoints.  ``overhead_pct`` is floored at 1.0 for
    the stamp (tools/bench_gate treats zero as the crash-guard
    sentinel); the raw delta and the armed writer's stats ride
    along, and the block FAILS if the armed lap persisted nothing
    (a knob that silently failed to arm would stamp a flattering
    zero)."""
    import shutil
    import tempfile
    import threading
    import urllib.request
    from znicz_tpu.core.config import root
    from znicz_tpu.core import blackbox, telemetry
    from znicz_tpu.serving import ModelRegistry, ServingServer
    from znicz_tpu.serving import reqtrace

    telemetry.reset()
    blackbox.reset()
    reqtrace.reset()
    root.common.telemetry.enabled = True
    # both laps: the feeding planes on (their cost is ISSUE 14/16's
    # number, not this one's)
    root.common.serving.slo_enabled = True
    root.common.serving.trace_sample_n = 8
    sources = _loadgen_models(max_batch)
    registry = ModelRegistry(models=sources, max_batch=max_batch)
    server = ServingServer(registry=registry).start()
    url = "http://127.0.0.1:%d" % server.port
    names = sorted(sources)
    r = numpy.random.RandomState(7)
    bodies = {}
    for name in names:
        n_in = sources[name][0]["input_sample_shape"][0]
        bodies[name] = [
            json.dumps({"inputs": r.uniform(
                -1, 1, (1 + i % max_batch, n_in)).tolist()}).encode()
            for i in range(4)]

    def lap(seconds):
        stop = threading.Event()
        done = [0] * clients
        errors = []

        def client(k):
            i = k
            try:
                while not stop.is_set():
                    name = names[i % len(names)]
                    req = urllib.request.Request(
                        url + "/predict/" + name,
                        bodies[name][i % len(bodies[name])],
                        {"Content-Type": "application/json"})
                    with urllib.request.urlopen(req,
                                                timeout=60) as resp:
                        resp.read()
                        assert resp.status == 200
                    done[k] += 1
                    i += 1
            except Exception as e:  # noqa: BLE001 - re-raised below
                errors.append(repr(e))
                stop.set()

        threads = [threading.Thread(target=client, args=(k,),
                                    name="znicz:bench-client-%d" % k,
                                    daemon=True)
                   for k in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(seconds)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        if errors:
            raise RuntimeError(
                "blackbox lap lost %d client(s): %s"
                % (len(errors), errors[:3]))
        return done, time.perf_counter() - t0

    bb_dir = tempfile.mkdtemp(prefix="znicz_bench_blackbox_")
    saved_en = bool(root.common.telemetry.blackbox.get("enabled",
                                                       False))
    saved_dir = root.common.telemetry.blackbox.get("dir", None)
    try:
        lap(0.4)  # warm: dispatch paths hot before either timed lap
        done_off, wall_off = lap(duration)
        blackbox.enable(dir=bb_dir)
        blackbox.maybe_arm("bench")
        done_on, wall_on = lap(duration)
        bb_stats = blackbox.stats()
    finally:
        blackbox.reset()
        root.common.telemetry.blackbox.enabled = saved_en
        root.common.telemetry.blackbox.dir = saved_dir
        root.common.serving.slo_enabled = False
        root.common.serving.trace_sample_n = 0
        server.stop()
        shutil.rmtree(bb_dir, ignore_errors=True)
    if not bb_stats.get("records"):
        raise RuntimeError("armed lap persisted no records — the "
                           "blackbox never armed, the overhead "
                           "number would be a lie")
    rps_off = sum(done_off) / wall_off
    rps_on = sum(done_on) / wall_on
    raw = (1.0 - rps_on / max(rps_off, 1e-9)) * 100.0
    return {
        "clients": clients,
        "duration_s": duration,
        "disabled_requests_per_sec": round(rps_off, 1),
        "armed_requests_per_sec": round(rps_on, 1),
        "overhead_pct_raw": round(raw, 2),
        "overhead_pct": round(max(raw, 1.0), 2),
        # proof the armed lap actually persisted + sizing context
        "armed_records": bb_stats.get("records", 0),
        "armed_bytes_written": bb_stats.get("bytes_written", 0),
        "armed_rotations": bb_stats.get("rotations", 0),
    }


def _stamp_serving_blackbox(out):
    """Stamp the durable-blackbox block + its flat key (crash-guarded
    ZERO stamp): ``serving_blackbox_overhead_pct`` is gated INVERTED
    by tools/bench_gate.py — the crash-safe write-through must stay
    affordable (ISSUE 19 budget: <= 2%) or arming it fleet-wide
    stops being a default anyone can afford.  Shared by main(),
    main_serving() and the ``--serving-blackbox`` CI entry."""
    try:
        out["serving_blackbox"] = _serving_blackbox_block()
    except Exception as e:  # noqa: BLE001 - never kill the primary
        out["serving_blackbox"] = {"error": repr(e)}
    block = out["serving_blackbox"]
    out["serving_blackbox_overhead_pct"] = (
        block.get("overhead_pct") or 0.0)


def _stamp_serving_precision(out, peaks):
    """Stamp the per-dtype serving block + the flat gated keys
    (crash-guarded with explicit ZERO stamps, so a broken precision
    path fails tools/bench_gate.py rather than silently vanishing) —
    shared by main() and main_serving()."""
    try:
        out["serving_precision"] = _serving_precision_block(peaks)
    except Exception as e:  # noqa: BLE001 - never kill the primary
        out["serving_precision"] = {"error": repr(e)}
    block = out["serving_precision"]
    for dt in PRECISION_DTYPES:
        out["serving_%s_requests_per_sec" % dt] = (
            block.get("dtypes", {}).get(dt, {})
            .get("requests_per_sec") or 0.0)


def main_serving(duration=5.0, clients=16, max_batch=64):
    """Serving-tier benchmark — prints ONE JSON line: sustained
    throughput (req/s, rows/s) and request latency p50/p99 of the
    online inference stack (engine + micro-batcher, in process — no
    HTTP socket cost) under ``clients`` closed-loop submitters firing
    mixed batch sizes 1..max_batch.

    The model is a synthetic 784->256->10 MLP with random weights
    (throughput does not depend on the values); the engine path is the
    SHIPPED one: bucketed pad-to-power-of-two dispatch, jitted fused
    forward, eager warmup — so zero compiles occur inside the timed
    window (stamped via the telemetry summary).

    Appends the ``serving_control_plane`` block (ISSUE 8): a
    two-model registry + continuous batcher under the seeded
    open-loop generator (tools/loadgen.py) at a calibrated steady
    rate and at 3x capacity, plus the persistent-compile-cache
    cold-start measurement — the same block the main bench stamps."""
    import threading
    from znicz_tpu.core.config import root
    from znicz_tpu.core import telemetry
    from znicz_tpu.serving import InferenceEngine, MicroBatcher

    telemetry.reset()
    root.common.telemetry.enabled = True
    r = numpy.random.RandomState(0)
    manifest = {
        "format": 1,
        "layers": [
            {"type": "all2all_tanh", "name": "fc0",
             "arrays": {"weights": "w0.npy", "bias": "b0.npy"},
             "include_bias": True, "weights_transposed": False},
            {"type": "softmax", "name": "out",
             "arrays": {"weights": "w1.npy", "bias": "b1.npy"},
             "include_bias": True, "weights_transposed": False},
        ],
        "input_sample_shape": [784],
    }
    arrays = {
        "w0.npy": r.normal(0, 0.05, (256, 784)).astype(numpy.float32),
        "b0.npy": numpy.zeros(256, numpy.float32),
        "w1.npy": r.normal(0, 0.05, (10, 256)).astype(numpy.float32),
        "b1.npy": numpy.zeros(10, numpy.float32),
    }
    engine = InferenceEngine((manifest, arrays), max_batch=max_batch)
    batcher = MicroBatcher(engine, max_delay_ms=2.0, queue_limit=4096,
                           timeout_ms=0).start()
    compiles0 = telemetry.counter("jax.backend_compiles").value

    # pre-generate one input per batch size: the clients measure the
    # serving stack, not numpy.random
    inputs = {n: r.uniform(-1, 1, (n, 784)).astype(numpy.float32)
              for n in range(1, max_batch + 1)}
    stop = threading.Event()
    done = [0] * clients
    rows = [0] * clients

    def client(k):
        i = k
        while not stop.is_set():
            x = inputs[1 + (i * 7) % max_batch]
            batcher.predict(x)
            done[k] += 1
            rows[k] += len(x)
            i += 1

    threads = [threading.Thread(target=client, args=(k,),
                                name="znicz:bench-client-%d" % k,
                                daemon=True)
               for k in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    elapsed = time.perf_counter() - t0
    batcher.stop()

    lat = telemetry.histogram("serving.request_seconds")
    serving = telemetry.serving_summary() or {}
    out = {
        "metric": "serving_fc_requests_per_sec",
        "value": round(sum(done) / elapsed, 1),
        "unit": "requests/sec",
        "rows_per_sec": round(sum(rows) / elapsed, 1),
        "latency_p50_ms": serving.get("latency_p50_ms"),
        "latency_p99_ms": serving.get("latency_p99_ms"),
        "queue_wait_p50_ms": serving.get("queue_wait_p50_ms"),
        "device_p50_ms": serving.get("device_p50_ms"),
        "requests": sum(done),
        "clients": clients,
        "max_batch": max_batch,
        "duration_sec": round(elapsed, 2),
        "batches": serving.get("batches"),
        "batch_fill_p50": serving.get("batch_fill_p50"),
        "recompiles_in_window":
            telemetry.counter("jax.backend_compiles").value - compiles0,
        "model": "fc 784-256-10 (synthetic weights)",
        "telemetry": telemetry.summary(),
    }
    assert lat.count == sum(done)
    # ISSUE 8: the serving control plane — two-model registry +
    # continuous batching under the seeded open-loop generator, plus
    # the persistent-compile-cache cold-start measurement
    _stamp_serving_control_plane(out)
    # ISSUE 10: the per-dtype serving data path on the memory-bound
    # model — the same block the main bench stamps
    import jax
    _stamp_serving_precision(
        out, _device_peaks(jax.devices()[0].device_kind))
    # ISSUE 12: the batch-1 tail-latency block — same stamps as the
    # main bench
    _stamp_serving_tail(out)
    # ISSUE 14: the SLO-plane overhead block — same stamps as the
    # main bench
    _stamp_serving_observability(out)
    # ISSUE 15: the multi-replica fleet block — same stamps as the
    # main bench
    _stamp_serving_fleet(out)
    # ISSUE 16: the fleet-path tracing overhead block — same stamps
    # as the main bench
    _stamp_serving_fleet_observability(out)
    # ISSUE 17: the shadow-mirroring tax block — same stamps as the
    # main bench
    _stamp_serving_release_shadow(out)
    # ISSUE 20: the binary framed relay — same stamps as the main
    # bench
    _stamp_serving_wire(out)
    # ISSUE 18: the continuous-profiler cost ledger — same stamps as
    # the main bench
    _stamp_serving_pyprof(out)
    # ISSUE 19: the durable-blackbox write-through tax — same stamp
    # as the main bench
    _stamp_serving_blackbox(out)
    print(json.dumps(out))


def main_serving_fleet():
    """``--serving-fleet``: ONLY the fleet block + the fleet-tracing
    overhead block (ISSUE 16) + the shadow-mirroring tax block
    (ISSUE 17) + the binary-relay block (ISSUE 20) + their flat
    gated keys, as one JSON line — the CPU-feasible CI entry
    (tools/ci.sh pipes it through ``bench_gate --assert-stamped`` so
    a fleet tier whose crash guard stamped zeros fails the gate, not
    the bench)."""
    from znicz_tpu.core import telemetry
    telemetry.reset()
    out = {"metric": "serving_fleet"}
    _stamp_serving_fleet(out)
    _stamp_serving_fleet_observability(out)
    _stamp_serving_release_shadow(out)
    _stamp_serving_wire(out)
    print(json.dumps(out))


def main_serving_tail():
    """``--serving-tail``: ONLY the batch-1 tail-latency block + its
    flat gated keys, as one JSON line — the CPU-feasible CI entry
    (tools/ci.sh pipes it through ``bench_gate --assert-stamped`` so
    a latency tier that stops producing numbers fails the gate, not
    the bench)."""
    from znicz_tpu.core import telemetry
    telemetry.reset()
    out = {"metric": "serving_tail_latency"}
    _stamp_serving_tail(out)
    print(json.dumps(out))


def main_serving_obs():
    """``--serving-obs``: ONLY the SLO-plane overhead block + its flat
    gated key, as one JSON line — the CPU-feasible CI entry
    (tools/ci.sh pipes it through ``bench_gate --assert-stamped
    serving_observability_overhead_pct`` so an observability plane
    that broke, or stopped arming, fails the gate)."""
    from znicz_tpu.core import telemetry
    telemetry.reset()
    out = {"metric": "serving_observability_overhead_pct"}
    _stamp_serving_observability(out)
    print(json.dumps(out))


def main_serving_blackbox():
    """``--serving-blackbox``: ONLY the durable-blackbox write-through
    tax block + its flat key, as one JSON line — the CPU-feasible CI
    entry (tools/ci.sh pipes it through ``bench_gate --assert-stamped
    serving_blackbox_overhead_pct`` so a blackbox that broke, or
    stopped arming, fails the gate)."""
    from znicz_tpu.core import telemetry
    telemetry.reset()
    out = {"metric": "serving_blackbox"}
    _stamp_serving_blackbox(out)
    print(json.dumps(out))


def main_serving_pyprof():
    """``--serving-pyprof``: ONLY the continuous-profiler cost-ledger
    block + its two flat keys, as one JSON line — the CPU-feasible CI
    entry (tools/ci.sh pipes it through ``bench_gate --assert-stamped
    serving_pyprof_overhead_pct,serving_dataplane_python_pct`` so a
    sampler that broke, stopped arming, or stopped seeing the data
    plane fails the gate)."""
    from znicz_tpu.core import telemetry
    telemetry.reset()
    out = {"metric": "serving_pyprof"}
    _stamp_serving_pyprof(out)
    print(json.dumps(out))


if __name__ == "__main__":
    import sys
    if "--mesh" in sys.argv:
        index = sys.argv.index("--mesh")
        max_devices = 8
        if index + 1 < len(sys.argv) and sys.argv[index + 1].isdigit():
            max_devices = int(sys.argv[index + 1])
        main_mesh(max_devices=max_devices)
        sys.exit(0)
    if "--serving-coldstart" in sys.argv:
        # internal: one replica of the cold-start measurement
        _coldstart_worker(
            sys.argv[sys.argv.index("--serving-coldstart") + 1])
        sys.exit(0)
    if "--serving-fleet" in sys.argv:
        main_serving_fleet()
        sys.exit(0)
    if "--serving-tail" in sys.argv:
        main_serving_tail()
        sys.exit(0)
    if "--serving-obs" in sys.argv:
        main_serving_obs()
        sys.exit(0)
    if "--serving-pyprof" in sys.argv:
        main_serving_pyprof()
        sys.exit(0)
    if "--serving-blackbox" in sys.argv:
        main_serving_blackbox()
        sys.exit(0)
    if "--serving" in sys.argv:
        kwargs = {}
        if "--duration" in sys.argv:
            kwargs["duration"] = float(
                sys.argv[sys.argv.index("--duration") + 1])
        main_serving(**kwargs)
        sys.exit(0)
    profile_dir = None
    if "--profile" in sys.argv:
        index = sys.argv.index("--profile")
        if index + 1 >= len(sys.argv):
            sys.exit("usage: bench.py [--profile TRACE_DIR] "
                     "[--serving [--duration S]]")
        profile_dir = sys.argv[index + 1]
    main(profile_dir=profile_dir)
