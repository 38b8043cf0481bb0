"""chip_smoke.py — does the train -> snapshot -> serve path start on the chip?

Run it from the checkout's root on a machine with one TPU::

    python3 chip_smoke.py            # one chip: train, serve, unit graph
    python3 chip_smoke.py --chips 4  # four chips: the data-parallel mesh only

It drives the entry points a user would call, at cifar-caffe's full width
(``root.cifar.layers``: 5x5 convs 32/32/64, overlapping 3x3/2 max and avg
pooling, LRN, softmax) at a training size (minibatch
4096, bf16 compute, f32 master weights, scan windows of 2), on seeded
synthetic data:

* **train** — ``python -m znicz_tpu chip_smoke.py --fused ...``: this file
  doubles as the workflow module, and its :func:`run` is the cifar sample's
  own ``run(load, main)`` with probes around it.  Two epochs, a snapshot;
  then a second process of the same program, which must compile nothing.
* **serve** — ``python -m znicz_tpu serve --latest cifar_caffe`` on that
  snapshot (default ``--max-batch 64``, warmup), JSON and ``.npy``
  requests against the numpy interpreter's answers, ``/metrics``,
  SIGTERM -> exit 0; then the same behind ``--fleet 1``, whose router must
  never touch the accelerator.
* **unit graph** — ``python -m znicz_tpu chip_smoke.py`` without
  ``--fused``: 30 minibatches of 100 through the per-unit path, where the
  Pallas max-pool kernel runs; twice, for the compile cache.

One process holds the chip at a time: this process starts the others and
stays off the accelerator (it pins itself to ``JAX_PLATFORMS=cpu`` and
hands its children the environment it was started with); the device facts
in the last line come from the children.  A phase that fails makes the
script exit non-zero; the last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

``--rehearse`` runs every phase at a tiny size on whatever platform jax
finds (``JAX_PLATFORMS=cpu`` here) to find wrong paths and arguments
before chip time is spent; it never prints ``"ok": true``.
"""

import argparse
import io
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

if __name__ != "__main__":
    # imported by ``python -m znicz_tpu chip_smoke.py`` as the workflow
    # module: the sample installs its root.cifar defaults at import, and
    # the CLI applies --config overrides right after importing us
    import znicz_tpu.samples.cifar  # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".cache", "chip_smoke")
SNAPSHOT_PREFIX = "cifar_caffe"

#: the job, at cifar-caffe's chip size and at the rehearsal's (whose
#: mesh batch leaves each of four shards enough rows for the bf16
#: gradient sums to agree as closely as the full size's do)
FULL = {"batch": 4096, "mesh_batch": 4096, "steps": 10, "unit_steps": 30,
        "unit_valid": 500}
TINY = {"batch": 64, "mesh_batch": 512, "steps": 4, "unit_steps": 3,
        "unit_valid": 100}
#: rows per /predict request, JSON and .npy by turns: buckets 1, 4, 8, 64
REQUESTS = (1, 3, 8, 33, 64, 64)
WINDOW = 2
EPOCHS = 2
UNIT_BATCH = 100

#: |probability - numpy interpreter's| allowed on the chip, as a share of
#: the spread (max - min) of the interpreter's own probabilities on the
#: batch, so that a net two epochs from its initialisation, whose outputs
#: are all near 1/10, is still held to its logits.  The reference is
#: float64; the chip multiplies in bf16 (explicitly in training, and as
#: the TPU's default f32 matmul precision in serving): 2^-8 relative per
#: product, accumulated in f32, through three 5x5 convolutions, two LRNs
#: and the dense head.  Tests pin ``jax_default_matmul_precision=highest``
#: and hold 1e-4 absolute instead.  Measured on a v5e at this size: 0.32 %
#: in training's bf16 forward, 0.26 % through the f32 server (PR 21).
TOLERANCE = 2e-2
#: mesh=4 against one device.  tools/mesh_smoke.py demands equal integer
#: aggregates and parameters to rtol 1e-5 / atol 1e-6 — of an f32 dense
#: net; this job multiplies in bf16, where another split of the batch is
#: another rounding of every gradient (on virtual CPU devices too).  Held
#: here: error counts within 2 % of the class's samples, max error sums to
#: 1 %, and every parameter tensor within 5 % of the distance training
#: moved it.  Whether mesh_smoke's own tolerance held is printed beside it.
MESH_COUNT_SHARE, MESH_SUM_RTOL, MESH_UPDATE_SHARE = 0.02, 1e-2, 0.05
MESH_SMOKE_RTOL, MESH_SMOKE_ATOL = 1e-5, 1e-6


class SmokeFailure(Exception):
    """A phase did not pass."""


# ---------------------------------------------------------------------------
# inside the training processes: the workflow module python -m znicz_tpu runs
# ---------------------------------------------------------------------------

def _device_facts():
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _require_tpu(device, rehearse):
    if device["platform"] != "tpu" and not rehearse:
        raise SystemExit("chip_smoke: jax found platform=%s; the smoke "
                         "needs a TPU" % device["platform"])


class _Checks(object):
    """Named checks of one process: each prints its verdict, the failed
    ones are kept for the result file and the exit code."""

    def __init__(self):
        self.failures = []

    def __call__(self, ok, what):
        print("chip_smoke: %s %s" % ("ok  " if ok else "FAIL", what))
        if not ok:
            self.failures.append(what)

    def close(self, result, out_path):
        result["failures"] = self.failures
        with open(out_path, "w") as f:
            json.dump(result, f)
        if self.failures:
            raise SystemExit("chip_smoke: %d check(s) failed"
                             % len(self.failures))


def _host_params(wf):
    """Per-layer {"w": ...} host copies, fused or unit graph."""
    import numpy
    if wf.fused_trainer is not None:
        return wf.fused_trainer.host_params()
    return [{"w": numpy.array(f.weights.mem)} if f.weights else {}
            for f in wf.forwards]


def _compile_counts():
    from znicz_tpu.core import telemetry
    return int(telemetry.counter("jax.backend_compiles").value)


def _attach_probes(wf, probe):
    """Observe the run the launcher is about to make: the parameters
    before the first step, and the compile counter at each epoch's end
    (the snapshotter fires once per epoch, after validation)."""
    probe["wf"] = wf
    probe["epoch_compiles"] = []
    run_workflow, run_snapshotter = wf.run, wf.snapshotter.run

    def run():
        probe["params0"] = _host_params(wf)
        probe["t_run"] = time.time()
        return run_workflow()

    def snapshot():
        wrote = run_snapshotter()
        probe["epoch_compiles"].append(_compile_counts())
        return wrote

    wf.run = run
    wf.snapshotter.run = snapshot


def _params_changed(before, after):
    import numpy
    changed = finite = True
    for p0, p1 in zip(before, after):
        for k in p1:
            finite = finite and bool(numpy.isfinite(p1[k]).all())
            changed = changed and not numpy.array_equal(p0[k], p1[k])
    return changed, finite


def _disagreement(got, ref):
    """max |got - ref| and its share of ``ref``'s spread."""
    import numpy
    worst = float(numpy.abs(got - ref).max())
    spread = float(ref.max() - ref.min())
    return {"max_abs": worst, "spread": spread,
            "share": worst / spread if spread else float("inf")}


def _package(wf, path, state=None):
    """``wf``'s forward stack as a deployment package — from the live
    parameters, or from a snapshot's when ``state`` is given."""
    from znicz_tpu.export import export_package
    from znicz_tpu.units.nn_units import load_snapshot_into_workflow
    fwd_wf = wf.extract_forward_workflow()
    if state is not None:
        load_snapshot_into_workflow(state, fwd_wf)
    return export_package(fwd_wf, path)


def _check_fused(wf, probe, workdir, check):
    import jax
    import numpy
    from znicz_tpu.core.snapshotter import SnapshotterToFile
    from znicz_tpu.export import run_package_numpy
    from znicz_tpu.launcher import newest_snapshot
    net = wf.fused_trainer.net
    device0 = jax.devices()[0]
    out = {}

    leaves = jax.tree.leaves(net.params)
    check(all(leaf.devices() == {device0} for leaf in leaves),
          "every parameter lives on %s" % device0)
    n_rows = len(wf.loader.original_data.mem)
    datasets = [a for a in jax.live_arrays()
                if a.shape == (n_rows, 32, 32, 3)]
    check(datasets and all(a.devices() == {device0} for a in datasets),
          "the %d-row dataset is resident on %s" % (n_rows, device0))
    check(wf.fused_trainer._use_device_data,
          "windows gather from the device-resident dataset")

    changed, finite = _params_changed(probe["params0"], _host_params(wf))
    check(finite and net.params_finite(), "parameters finite")
    check(changed, "every parameter tensor changed")

    marks = probe["epoch_compiles"]
    check(len(marks) == EPOCHS, "%d epochs ran" % EPOCHS)
    out["epoch2_compiles"] = marks[-1] - marks[0]
    check(out["epoch2_compiles"] == 0,
          "no compile inside epoch 2 (%d)" % out["epoch2_compiles"])

    out["epoch_err_pt"] = [wf.decision.epoch_n_err_pt[c] for c in (1, 2)]
    out["best_err_pt"] = list(wf.decision.best_n_err_pt)
    out["steps"] = int(wf.loader.epoch_number) * (
        wf.loader.class_lengths[2] // wf.loader.max_minibatch_size)

    # trained parameters against the numpy interpreter, on a seeded batch
    # of raw-pixel-range inputs
    r = numpy.random.RandomState(20260926)
    x = r.uniform(0, 255, (64, 32, 32, 3)).astype(numpy.float32)
    labels = r.randint(0, 10, 64)
    probs = numpy.asarray(net.predict(x), dtype=numpy.float64)
    ref = run_package_numpy(
        _package(wf, os.path.join(workdir, "trained.zip")), x)
    out["loss"] = float(-numpy.log(numpy.maximum(
        probs[numpy.arange(64), labels], 1e-30)).mean())
    out["train_vs_numpy"] = _disagreement(probs, ref)
    check(numpy.isfinite(out["loss"]) and numpy.isfinite(probs).all(),
          "loss %.4f and outputs finite" % out["loss"])
    check(probs.shape == (64, 10) and
          out["train_vs_numpy"]["share"] <= TOLERANCE,
          "trained forward agrees with the numpy interpreter: %s"
          % out["train_vs_numpy"])

    # what the serve phase must answer: the newest snapshot's parameters
    # through the same interpreter
    snapshot = newest_snapshot(wf.snapshotter.directory, SNAPSHOT_PREFIX)
    check(snapshot is not None, "the run wrote a snapshot")
    served = _package(wf, os.path.join(workdir, "served.zip"),
                      state=SnapshotterToFile.import_(snapshot))
    numpy.savez(os.path.join(workdir, "reference.npz"), x=x,
                y=run_package_numpy(served, x))
    out["snapshot"] = snapshot
    return out


def _check_unit_graph(wf, probe, check):
    import jax
    import numpy
    from znicz_tpu.ops import pallas_pooling
    from znicz_tpu.ops import pooling as pool_ops
    from znicz_tpu.units.nn_units import as_nhwc
    from znicz_tpu.units.pooling import MaxPooling
    out = {"pooling": []}
    changed, finite = _params_changed(probe["params0"], _host_params(wf))
    check(finite, "weights finite")
    check(changed, "every weight tensor changed")
    check(all(numpy.isfinite(numpy.array(f.output.mem)).all()
              for f in wf.forwards), "every layer's output finite")
    on_tpu = jax.default_backend() == "tpu"
    for unit in wf.forwards:
        if not isinstance(unit, MaxPooling):
            continue
        shape = as_nhwc(numpy.empty(unit.input.shape, numpy.bool_)).shape
        x = jax.ShapeDtypeStruct(shape, unit.input.dtype)
        covered = pallas_pooling.supported(
            x, unit.ky, unit.kx, unit.sliding, unit.USE_ABS)
        # the program the unit's op really builds at the unit's shapes
        hlo = jax.jit(lambda a, u=unit: pool_ops.max_pooling_jax(
            a, u.ky, u.kx, u.sliding, use_abs=u.USE_ABS)).lower(
                x).as_text()
        kernel = "tpu_custom_call" in hlo
        out["pooling"].append({
            "unit": unit.name, "shape": list(shape),
            "dtype": str(unit.input.dtype), "supported": bool(covered),
            "lowering": "pallas" if kernel else "gather",
            "runs": int(unit.run_count_)})
        check(unit.run_count_ > 0, "%s ran" % unit.name)
        if on_tpu:
            check(kernel == bool(covered),
                  "%s %s: supported()=%s and the compiled kernel %s"
                  % (unit.name, shape, covered,
                     "ran" if kernel else "did not run"))
    check(out["pooling"], "the graph has a max-pooling unit")
    out["epoch_err_pt"] = [wf.decision.epoch_n_err_pt[c] for c in (1, 2)]
    out["steps"] = int(sum(f.run_count_ for f in wf.forwards[:1]))
    return out


def run(load, main):
    """The launcher contract (``python -m znicz_tpu chip_smoke.py``):
    the cifar sample's own ``run(load, main)``, observed.  Results go to
    the JSON file ``--config chip_smoke.out=...`` names."""
    from znicz_tpu.core import compile_cache, prng, telemetry
    from znicz_tpu.core.config import root
    from znicz_tpu.samples import cifar
    cfg = root.chip_smoke
    device = _device_facts()
    _require_tpu(device, cfg.get("rehearse", False))
    telemetry.enable()
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    watch = compile_cache.watch()
    probe, check = {}, _Checks()

    def load_probed(factory, **kwargs):
        wf, restored = load(factory, **kwargs)
        _attach_probes(wf, probe)
        return wf, restored

    t0 = time.time()
    cifar.run(load_probed, main)
    wf = probe["wf"]
    result = {"device": device,
              "seconds": round(time.time() - t0, 2),
              "run_seconds": round(time.time() - probe["t_run"], 2)}
    counts = watch.delta()
    result.update(
        compiles=counts["backend_compiles"],
        cache_hits=counts["persistent_cache_hits"],
        fresh_compiles=watch.fresh_compiles(),
        compile_seconds=telemetry.summary().get("compile_seconds_total"),
        cache_dir=compile_cache.active_dir())
    workdir = os.path.dirname(cfg.out)
    if wf.fused_trainer is not None:
        result.update(_check_fused(wf, probe, workdir, check))
    else:
        result.update(_check_unit_graph(wf, probe, check))
    check.close(result, cfg.out)


# ---------------------------------------------------------------------------
# four chips: mesh=4 against one device, one process
# ---------------------------------------------------------------------------

def _shards(array):
    """(device ids, shard shapes) as the runtime reports them."""
    shards = array.addressable_shards
    return (sorted(s.device.id for s in shards),
            sorted({tuple(s.data.shape) for s in shards}))


def _mesh_job(size, snapshots, mesh):
    import jax
    import numpy
    from znicz_tpu.core import prng, telemetry
    from znicz_tpu.core.backends import JaxDevice
    from znicz_tpu.samples import cifar
    telemetry.reset()
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    fused = {"window": WINDOW, "compute_dtype": "bfloat16"}
    if mesh:
        fused["mesh"] = mesh
    batch = size["mesh_batch"]
    wf = cifar.build(
        loader_config={"minibatch_size": batch, "synthetic": True,
                       "synthetic_train": batch * size["steps"],
                       "synthetic_valid": batch},
        decision_config={"max_epochs": EPOCHS},
        snapshotter_config={"directory": snapshots,
                            "prefix": "mesh%d" % mesh},
        fused=fused)
    wf.initialize(device=JaxDevice())
    net = wf.fused_trainer.net
    seen = {"params0": wf.fused_trainer.host_params()}
    place_window, run_window = net._place_window, net.run_window_indexed

    # the only place the window's input exists as a device array is
    # inside the dispatch — look at it there, and at the accumulators
    # right after the window that folded into them
    def placed(arr, tail_dims):
        out = place_window(arr, tail_dims)
        seen.setdefault("window_input", _shards(out) + (out.shape,))
        return out

    def window(*args, **kwargs):
        stats = run_window(*args, **kwargs)
        if net.window_acc is not None:
            seen.setdefault("accumulators", {
                k: _shards(v) + (v.shape,)
                for k, v in net.window_acc.items()})
        return stats

    net._place_window, net.run_window_indexed = placed, window
    t0 = time.time()
    wf.run()
    seen["params"] = [_shards(leaf) + (leaf.shape,)
                      for leaf in jax.tree.leaves(net.params)]
    return {
        "wf": wf, "seen": seen, "seconds": round(time.time() - t0, 2),
        "telemetry": telemetry.summary(),
        "params": wf.fused_trainer.host_params(),
        "finite": bool(net.params_finite()) and all(
            numpy.isfinite(m) for m in wf.decision.max_err_y_sums),
    }


def mesh_phase(out_path, rehearse):
    """``--chips 4``: the cifar-caffe fused job on ``mesh=4`` and on one
    device, same seeds, in this one process."""
    import numpy
    from znicz_tpu.core import telemetry
    device = _device_facts()
    _require_tpu(device, rehearse)
    if device["count"] < 4:
        raise SystemExit("chip_smoke --chips 4: jax found %d device(s)"
                         % device["count"])
    size = TINY if rehearse else FULL
    telemetry.enable()
    check = _Checks()
    snapshots = os.path.join(os.path.dirname(out_path), "snapshots")
    one = _mesh_job(size, snapshots, 0)
    four = _mesh_job(size, snapshots, 4)
    d1, d4 = one["wf"].decision, four["wf"].decision
    check(one["finite"] and four["finite"], "both runs finite")
    check(four["wf"].fused_trainer.net.data_shards == 4,
          "mesh=4 run has 4 data shards")
    exact = list(d1.epoch_n_err) == list(d4.epoch_n_err) and \
        d1.max_err_y_sums == d4.max_err_y_sums
    lengths = one["wf"].loader.class_lengths
    confusion_off = 0
    for clazz in (1, 2):
        allowed = int(numpy.ceil(MESH_COUNT_SHARE * lengths[clazz]))
        n1, n4 = d1.epoch_n_err[clazz], d4.epoch_n_err[clazz]
        check(abs(n1 - n4) <= allowed,
              "class %d error counts %d vs %d of %d (within %d)"
              % (clazz, n1, n4, lengths[clazz], allowed))
        confusion_off = max(confusion_off, int(numpy.abs(
            d1.confusion_matrixes[clazz] -
            d4.confusion_matrixes[clazz]).sum()) - 2 * allowed)
        s1, s4 = d1.max_err_y_sums[clazz], d4.max_err_y_sums[clazz]
        check(abs(s1 - s4) <= MESH_SUM_RTOL * abs(s1),
              "class %d max error sums %r vs %r" % (clazz, s1, s4))
    check(confusion_off <= 0, "confusion matrices as close as the counts")
    worst = smoke_worst = 0.0
    for p0, la, lb in zip(one["seen"]["params0"], one["params"],
                          four["params"]):
        for k in la:
            moved = float(numpy.abs(la[k] - p0[k]).max())
            apart = float(numpy.abs(la[k] - lb[k]).max())
            worst = max(worst, apart / moved if moved else float("inf"))
            smoke_worst = max(smoke_worst, float((
                numpy.abs(la[k] - lb[k]) -
                MESH_SMOKE_RTOL * numpy.abs(lb[k])).max()))
    exact = exact and smoke_worst <= MESH_SMOKE_ATOL
    check(worst <= MESH_UPDATE_SHARE,
          "parameters %.2e of their update apart (<= %g)"
          % (worst, MESH_UPDATE_SHARE))
    print("chip_smoke: note tools/mesh_smoke.py's tolerance (equal "
          "counts and sums, parameters rtol %g atol %g) %s"
          % (MESH_SMOKE_RTOL, MESH_SMOKE_ATOL,
             "held" if exact else "did not hold"))

    seen = four["seen"]
    ids, shapes, full = seen["window_input"]
    check(len(set(ids)) == 4 and shapes == [(full[0], full[1] // 4)],
          "window input %s is split %s over devices %s"
          % (full, shapes, ids))
    for name, (ids, shapes, full) in seen["accumulators"].items():
        check(len(set(ids)) == 4 and shapes == [(1,) + tuple(full[1:])],
              "accumulator %s %s is split %s over devices %s"
              % (name, full, shapes, ids))
    check(all(len(set(ids)) == 4 and shapes == [tuple(full)]
              for ids, shapes, full in seen["params"]),
          "all %d parameter tensors are whole on each of 4 devices"
          % len(seen["params"]))
    check(all(ids == [ids[0]] for ids, _, _ in one["seen"]["params"]),
          "the one-device run kept its parameters on one device")

    for name, job in (("one device", one), ("mesh=4", four)):
        tele = job["telemetry"]
        check(tele.get("readbacks") == EPOCHS,
              "%s: %s readbacks for %d train segments"
              % (name, tele.get("readbacks"), EPOCHS))
    check(one["telemetry"].get("d2h_calls") ==
          four["telemetry"].get("d2h_calls"),
          "device-to-host calls equal (%s vs %s)"
          % (one["telemetry"].get("d2h_calls"),
             four["telemetry"].get("d2h_calls")))
    result = {
        "device": device,
        "seconds": {"one": one["seconds"], "mesh4": four["seconds"]},
        "epoch_n_err": [list(d1.epoch_n_err), list(d4.epoch_n_err)],
        "placement": {"window_input": seen["window_input"],
                      "accumulators": seen["accumulators"],
                      "params": seen["params"][:2]},
        "max_err_y_sums": [list(d1.max_err_y_sums),
                           list(d4.max_err_y_sums)],
        "params_apart_share_of_update": worst,
        "mesh_smoke_tolerance_held": bool(exact),
    }
    check.close(result, out_path)


# ---------------------------------------------------------------------------
# the process that starts the others (never touches the accelerator)
# ---------------------------------------------------------------------------

def _child_env(base_env):
    env = dict(base_env)
    env["PYTHONPATH"] = os.pathsep.join(
        [HERE] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _run_child(argv, env, log_path, timeout):
    """Run one child to its end; its output goes to ``log_path`` and, on
    failure, its tail to ours."""
    t0 = time.time()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(argv, cwd=HERE, env=env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            code = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            _kill_group(proc)
            code = "timeout after %d s" % timeout
    if code != 0:
        with open(log_path) as log:
            sys.stdout.write("".join(log.readlines()[-40:]))
        raise SmokeFailure("%s exited with %s" % (" ".join(argv[:6]), code))
    return round(time.time() - t0, 2)


def _kill_group(proc):
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def _train_argv(size, out, snapshots, rehearse, env, fused):
    argv = [sys.executable, "-m", "znicz_tpu",
            os.path.join(HERE, "chip_smoke.py")]
    if fused:
        argv += ["--fused", "window=%d,compute_dtype=bfloat16" % WINDOW]
        batch, steps, valid = size["batch"], size["steps"], size["batch"]
        epochs = EPOCHS
    else:
        batch, steps, valid = (UNIT_BATCH, size["unit_steps"],
                               size["unit_valid"])
        epochs = 1
    overrides = [
        "cifar.loader.minibatch_size=%d" % batch,
        "cifar.loader.synthetic=True",
        "cifar.loader.synthetic_train=%d" % (batch * steps),
        "cifar.loader.synthetic_valid=%d" % valid,
        "cifar.decision.max_epochs=%d" % epochs,
        "common.dirs.snapshots=%s" % snapshots,
        "chip_smoke.out=%s" % out,
        "chip_smoke.rehearse=%s" % rehearse,
    ]
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        # the program's own default directory, <checkout>/.cache/xla_cache
        overrides.append("common.compile_cache.enabled=True")
    for assignment in overrides:
        argv += ["--config", assignment]
    return argv


def _train_twice(name, size, rehearse, env, fused, timeout):
    """One training phase: the program, then a second process of the same
    program, which must find every executable in the compile cache."""
    runs = []
    for attempt in (1, 2):
        tag = "%s%d" % (name, attempt)
        out = os.path.join(WORK, tag + ".json")
        wall = _run_child(
            _train_argv(size, out, os.path.join(WORK, "snap_" + tag),
                        rehearse, env, fused),
            env, os.path.join(WORK, tag + ".log"), timeout)
        with open(out) as f:
            result = json.load(f)
        result["wall_seconds"] = wall
        runs.append(result)
        print("%s process %d: %s" % (name, attempt, json.dumps(
            {k: result[k] for k in (
                "device", "wall_seconds", "run_seconds", "steps", "compiles",
                "cache_hits", "fresh_compiles", "compile_seconds",
                "cache_dir", "epoch_err_pt") if k in result})))
    if runs[1]["fresh_compiles"] != 0:
        raise SmokeFailure(
            "%s: the second process compiled %d program(s) the cache "
            "should have held" % (name, runs[1]["fresh_compiles"]))
    return runs


class _Server(object):
    """One ``python -m znicz_tpu serve ...`` process."""

    def __init__(self, name, argv, env):
        self.name = name
        self.lines = []
        self._found = threading.Event()
        self.url = None
        self.t0 = time.time()
        self.proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "znicz_tpu", "serve"] + argv,
            cwd=HERE, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, start_new_session=True)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        for line in self.proc.stdout:
            self.lines.append(line.rstrip("\n"))
            match = re.search(r"(?:on|behind) (http://[^/\s]+)/", line)
            if match and self.url is None:
                self.url = match.group(1)
                self._found.set()
        self._found.set()

    def fail(self, why):
        print("\n".join(self.lines[-40:]))
        raise SmokeFailure("%s: %s" % (self.name, why))

    def wait_ready(self, timeout):
        self._found.wait(timeout)
        if self.url is None:
            self.fail("no URL within %d s" % timeout)
        deadline = self.t0 + timeout
        while time.time() < deadline:
            if self.proc.poll() is not None:
                self.fail("exited with %s before ready"
                          % self.proc.returncode)
            try:
                with urllib.request.urlopen(self.url + "/healthz",
                                            timeout=5) as resp:
                    if resp.status == 200:
                        return round(time.time() - self.t0, 2)
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.2)
        self.fail("not ready within %d s" % timeout)

    def get(self, path):
        with urllib.request.urlopen(self.url + path, timeout=30) as resp:
            return resp.read().decode()

    def metrics(self):
        """{series: value} of the Prometheus exposition's plain series."""
        out = {}
        for line in self.get("/metrics").splitlines():
            match = re.match(r"^(znicz_[A-Za-z0-9_:]+) ([-+0-9.eE]+)$", line)
            if match:
                out[match.group(1)] = float(match.group(2))
        return out

    def predict(self, x, npy):
        import numpy
        if npy:
            buf = io.BytesIO()
            numpy.save(buf, x)
            body, ctype = buf.getvalue(), "application/octet-stream"
        else:
            body = json.dumps({"inputs": x.tolist()}).encode()
            ctype = "application/json"
        req = urllib.request.Request(
            self.url + "/predict", data=body,
            headers={"Content-Type": ctype})
        with urllib.request.urlopen(req, timeout=120) as resp:
            raw = resp.read()
        if npy:
            return numpy.load(io.BytesIO(raw))
        return numpy.asarray(json.loads(raw)["outputs"])

    def stop(self, timeout=120):
        """SIGTERM and the exit code (the drain must end in 0)."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            code = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.fail("still running %d s after SIGTERM" % timeout)
        self._reader.join(10)
        return code

    def kill(self):
        if self.proc.poll() is None:
            _kill_group(self.proc)


def _exchange(server, reference):
    """POST the request mix; the worst disagreement with the numpy
    interpreter."""
    import numpy
    x, y = reference["x"], reference["y"]
    replies, rows_sent, start = [], [], 0
    for i, rows in enumerate(REQUESTS):
        idx = numpy.arange(start, start + rows) % len(x)
        start += rows
        got = server.predict(x[idx], npy=bool(i % 2))
        if got.shape != (rows, 10) or not numpy.isfinite(got).all():
            server.fail("reply to %d rows has shape %s" % (rows, got.shape))
        replies.append(got)
        rows_sent.append(idx)
    worst = _disagreement(numpy.concatenate(replies),
                          y[numpy.concatenate(rows_sent)])
    if worst["share"] > TOLERANCE:
        server.fail("replies differ from the numpy interpreter: %s (> %g "
                    "of the spread)" % (worst, TOLERANCE))
    return len(replies), worst


def _children(pid):
    out = []
    for entry in os.listdir("/proc"):
        if entry.isdigit():
            try:
                with open("/proc/%s/stat" % entry) as f:
                    fields = f.read().rsplit(")", 1)[1].split()
            except OSError:
                continue
            if int(fields[1]) == pid:
                out.append(int(entry))
    return out


def _backend_mapped(pid):
    """Names under which a process has an accelerator runtime or device
    open (Linux /proc): the check that a router stayed off the chip."""
    found = set()
    with open("/proc/%d/maps" % pid) as f:
        for line in f:
            if "libtpu" in line:
                found.add("libtpu mapped")
    for fd in os.listdir("/proc/%d/fd" % pid):
        try:
            target = os.readlink("/proc/%d/fd/%s" % (pid, fd))
        except OSError:
            continue
        if target.startswith(("/dev/accel", "/dev/vfio")):
            found.add(target)
    return sorted(found)


def _drive(server, reference, timeout, look=None):
    """Ready -> (``look`` at the live process) -> the request mix ->
    SIGTERM; the numbers every serving process is held to."""
    try:
        ready = server.wait_ready(timeout)
        seen = look(server) if look else {}
        warm = server.metrics()
        done, worst = _exchange(server, reference)
        after = server.metrics()
        code = server.stop()
    finally:
        server.kill()
    compiles = "znicz_jax_backend_compiles"
    out = dict(seen, **{
        "ready_seconds": ready, "requests": done, "vs_numpy": worst,
        "warmup_compile_seconds": round(
            warm.get("znicz_jax_compile_seconds_sum", 0.0), 3),
        "warmup_compiles": int(warm.get(compiles, 0)),
        "warmup_cache_hits": int(
            warm.get("znicz_jax_persistent_cache_hits", 0)),
        "compiles_after_warmup": int(
            after.get(compiles, 0) - warm.get(compiles, 0)),
        "exit_code": code})
    print("%s: %s" % (server.name, json.dumps(out)))
    if out["compiles_after_warmup"] != 0:
        raise SmokeFailure("%s compiled %d program(s) after warmup"
                           % (server.name, out["compiles_after_warmup"]))
    if code != 0:
        raise SmokeFailure("%s exited with %s after SIGTERM"
                           % (server.name, code))
    return out


def _device_banner(server):
    for line in server.lines:
        match = re.match(
            r"serve: platform=(\S+) device_kind=(.+) devices=(\d+)$", line)
        if match:
            return {"device": {"platform": match.group(1),
                               "kind": match.group(2),
                               "count": int(match.group(3))}}
    server.fail("no device banner")


def _who_holds_the_chip(fleet):
    """The router must not; the replica must, or the look at the router
    proves nothing."""
    return {"router_holds": _backend_mapped(fleet.proc.pid),
            "replica_holds": sorted({
                name for pid in _children(fleet.proc.pid)
                for name in _backend_mapped(pid)})}


def _serve_phase(env, snapshots, timeout):
    import numpy
    reference = numpy.load(os.path.join(WORK, "reference.npz"))
    argv = ["--latest", SNAPSHOT_PREFIX, "--directory", snapshots,
            "--port", "0"]
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        argv.append("--compile-cache")
    serve = _drive(_Server("serve", argv, env), reference, timeout,
                   _device_banner)
    # the same behind a one-replica fleet: the replica is a second
    # process of the serve program (zero fresh compiles), the router is
    # a process that must not hold the chip
    fleet = _drive(_Server("fleet", argv + ["--fleet", "1"], env),
                   reference, timeout, _who_holds_the_chip)
    if fleet["router_holds"]:
        raise SmokeFailure("the fleet router touched the accelerator: %s"
                           % fleet["router_holds"])
    if serve["device"]["platform"] == "tpu" and not fleet["replica_holds"]:
        raise SmokeFailure("cannot see the replica's hold on the chip, "
                           "so the router's clean record proves nothing")
    if fleet["warmup_compiles"] != fleet["warmup_cache_hits"]:
        raise SmokeFailure(
            "the fleet replica compiled %d program(s) the serve phase "
            "had cached" % (fleet["warmup_compiles"] -
                            fleet["warmup_cache_hits"]))
    return {"serve": serve, "fleet": fleet}


def _same_device(devices):
    first = devices[0]
    if any(d != first for d in devices):
        raise SmokeFailure("the phases saw different devices: %s"
                           % devices)
    return first


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the mesh=4 job and its "
                             "one-device comparison")
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes on any platform; never ok")
    parser.add_argument("--mesh-child", metavar="OUT",
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.mesh_child:
        mesh_phase(args.mesh_child, args.rehearse)
        return 0

    # children get the environment we were started with; this process
    # pins itself to the CPU so that nothing it imports can take the chip
    env = _child_env(os.environ)
    os.environ["JAX_PLATFORMS"] = "cpu"
    size = TINY if args.rehearse else FULL
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    t0 = time.time()
    cache_dir = env.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(HERE, ".cache", "xla_cache")
    print("chip_smoke: work dir %s, compile cache %s%s"
          % (WORK, cache_dir,
             " (JAX_COMPILATION_CACHE_DIR)"
             if env.get("JAX_COMPILATION_CACHE_DIR") else ""))
    try:
        if args.chips == 4:
            out = os.path.join(WORK, "mesh.json")
            child = [sys.executable, os.path.join(HERE, "chip_smoke.py"),
                     "--mesh-child", out]
            if args.rehearse:
                child.append("--rehearse")
            wall = _run_child(child, env, os.path.join(WORK, "mesh.log"),
                              900)
            with open(os.path.join(WORK, "mesh.log")) as log:
                sys.stdout.write("".join(
                    ln for ln in log if ln.startswith("chip_smoke:")))
            with open(out) as f:
                result = json.load(f)
            print("mesh: %s" % json.dumps(dict(
                {k: result[k] for k in (
                    "seconds", "epoch_n_err", "max_err_y_sums",
                    "params_apart_share_of_update",
                    "mesh_smoke_tolerance_held", "placement")},
                wall_seconds=wall)))
            device = result["device"]
        else:
            train = _train_twice("train", size, args.rehearse, env, True,
                                 600)
            serve = _serve_phase(env, os.path.join(WORK, "snap_train1"),
                                 300)
            unit = _train_twice("unit", size, args.rehearse, env, False,
                                600)
            print("unit graph pooling: %s" % json.dumps(unit[0]["pooling"]))
            print("comparison errors: %s" % json.dumps({
                "tolerance_share_of_spread": TOLERANCE,
                "train_vs_numpy": train[0]["train_vs_numpy"],
                "serve_vs_numpy": serve["serve"]["vs_numpy"],
                "fleet_vs_numpy": serve["fleet"]["vs_numpy"]}))
            device = _same_device([r["device"] for r in train + unit] +
                                  [serve["serve"]["device"]])
    except SmokeFailure as e:
        print("chip_smoke: FAILED after %.0f s: %s" % (time.time() - t0, e))
        return 1
    print("chip_smoke: all phases passed in %.0f s" % (time.time() - t0))
    if args.rehearse or device["platform"] != "tpu":
        print(json.dumps({"ok": False, "rehearsal": "passed",
                          "device": device}))
        return 0 if args.rehearse else 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
