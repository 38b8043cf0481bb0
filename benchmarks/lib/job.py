"""Drive one training cell: build the sample's workflow, warm it, time whole
epochs for ``--seconds``, and keep what the comparison needs.

The entry the window drives is the program's own: the sample module's
``build()`` with ``fused={...}``, ``initialize(JaxDevice())`` and ONE
``wf.run()``.  Everything the benchmark adds is hung on instances from
outside (no change to the program):

* ``decision.stop_condition`` is wrapped: it is called once an epoch, so it
  is where set-up ends (after ``warm_epochs`` whole epochs with their
  validation pass), where epoch times are taken, and where the run is told
  to stop once ``--seconds`` have passed.  The window opens and closes on a
  device sync.
* the fused net's train-window entry is wrapped for the first
  ``check_windows`` dispatches: the feed and what came back (the outputs
  kept, parameters and optimizer state) are what ``correct`` is decided on,
  against the plain reference.  Later dispatches go straight through.
* with ``--trace 1`` every unit's ``run`` is timed as a
  span on the host clock, so idle gaps on the device get a name.

What the rows are, which entry is wrapped and what is kept of it, and what is
compared belong to the configuration's family (``benchmarks/families/``);
nothing here knows a label, a class or a leaf of optimizer state by name.
"""

import gc
import importlib
import os
import time

import numpy

from benchmarks import families
from benchmarks.lib import data as data_mod


def _plain(obj):
    """Tuples to lists, all the way down (JSON's view of a layer list)."""
    if isinstance(obj, dict):
        return {k: _plain(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_plain(v) for v in obj]
    return obj


def program_layers(module, cfg):
    """The layer list as the program makes it, checked against the copy in
    the configuration's file (the sizes the reference reads)."""
    src = cfg["layers_from"]
    if "call" in src:
        layers = getattr(module, src["call"])(*src.get("args", ()))
    else:
        from znicz_tpu.core.config import root
        node = root
        for part in src["config_node"].split("."):
            node = getattr(node, part)
        layers = node
    layers = [dict(layer) for layer in layers]
    if _plain(layers) != cfg["layers"]:
        raise SystemExit("%s: the program's layers differ from the "
                         "configuration file's" % cfg["name"])
    return layers


class CompileCounter(object):
    """Counts XLA backend compiles through ``jax.monitoring`` (the
    benchmark's own listener: telemetry stays off in timed runs)."""

    def __init__(self):
        from jax import monitoring
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **kwargs):
        if "backend_compile" in event:
            self.count += 1


class WindowCapture(object):
    """Records the first ``n`` train-window dispatches of a FusedNet, as
    the family ``fam`` says: which entry, what of its feed and outputs."""

    def __init__(self, trainer, n, cfg, mix, fam, sabotage=None):
        import jax
        import jax.numpy as jnp
        self._jax, self._jnp = jax, jnp
        self.trainer = trainer
        self.net = net = trainer.net
        self.n = int(n)
        self.cfg, self.mix, self.fam = cfg, mix, fam
        self.numbers = None
        self.windows = []
        self.p0 = None          # parameters before the first dispatch
        self.state1 = None      # optimizer state after the first dispatch
        self._orig = getattr(net, fam.ENTRY)
        self._sabotage = sabotage
        setattr(net, fam.ENTRY, self._call)

    def _copy(self, tree):
        return self._jax.tree.map(self._jnp.copy, tree)

    def _dispatch(self, args, final):
        if self._sabotage is not None:
            return self._sabotage(self._orig, self.net, *args, final)
        return self._orig(*args, final=final)

    def _call(self, *args, final=False):
        if len(self.windows) >= self.n:
            return self._dispatch(args, final)
        if not self.windows:
            self.p0 = self._copy(self.net.params)
        rec = self.fam.feed(self.trainer, *args)
        stats = self._dispatch(args, final)
        rec["stats"] = self.fam.keep(stats, rec)
        self.windows.append(rec)
        if len(self.windows) == 1:
            self.state1 = self._copy(self.net.state)
        if len(self.windows) == self.n:
            # worked out at once (still set-up) so that the copies do not
            # sit on the device through the window
            self.numbers = self.fam.leaf_numbers(
                self.cfg, self.mix, self.p0, self.state1, self.net.params)
            self.p0 = self.state1 = None
        return stats

    def fetch(self):
        """Pull the small per-window outputs to the host (call once the
        windows have run)."""
        for rec in self.windows:
            rec["stats"] = self.fam.fetch(self._jax.device_get(rec["stats"]))


def leaf_norms(p0, state1, p_end, masks, state_leaves):
    """Per-leaf l2 norms worked out on the device from a program's own
    list-of-dicts trees: ``{"<leaf>1": {"<i>.<name>": norm}}`` for each of
    the optimizer's ``state_leaves`` after the first captured window, and
    ``"dparam"``, the parameters' change from ``p0`` (``w`` under its
    layer's mask, where ``masks[i]`` is not None) to ``p_end``."""
    import jax
    import jax.numpy as jnp

    def norms(p0, v_after, p_end):
        s_norm, d_norm = tuple({} for _ in state_leaves), {}
        for i, (a, v, e, m) in enumerate(zip(p0, v_after, p_end, masks)):
            for name in a:
                w0 = a[name]
                if name == "w" and m is not None:
                    w0 = w0 * jnp.asarray(m, w0.dtype)
                key = "%d.%s" % (i, name)
                for leaf, norm in zip(state_leaves, s_norm):
                    norm[key] = jnp.sqrt(jnp.sum(jnp.square(v[name][leaf])))
                d_norm[key] = jnp.sqrt(jnp.sum(jnp.square(e[name] - w0)))
        return s_norm, d_norm

    s_norm, d_norm = jax.device_get(jax.jit(norms)(p0, state1, p_end))
    out = {leaf + "1": {k: float(v) for k, v in norm.items()}
           for leaf, norm in zip(state_leaves, s_norm)}
    out["dparam"] = {k: float(v) for k, v in d_norm.items()}
    return out


def _wrap_unit_spans(wf, spans):
    """Record every unit's ``run`` as (name, start, duration) on the host's
    monotonic clock.  The profiler's own host tracer is left off: on this
    runtime it writes millions of events a window and slows the host path
    threefold (my chip runs, PR 24)."""
    for unit in wf.units:
        name = "bench.unit.%s" % unit.name
        orig = unit.run

        def run(orig=orig, name=name):
            t0 = time.perf_counter_ns()
            try:
                return orig()
            finally:
                spans.append([name, t0, time.perf_counter_ns() - t0])
        unit.run = run


def run_cell(cell, cfg, mix, seed, seconds, trace, root_dir, t_process,
             log, sabotage=None):
    """Returns a dict of everything measured and captured; raises
    SystemExit on a run that may not report (compiles in the window, the
    job stopping by itself)."""
    import jax
    import jax.numpy as jnp
    from znicz_tpu.core import compile_cache, prng, telemetry
    from znicz_tpu.core.backends import JaxDevice
    from znicz_tpu.core.config import root

    compile_cache.enable()
    compiles = CompileCounter()
    if trace:
        telemetry.enable()

    fam = families.load(cfg)
    n_train, n_valid = int(mix["n_train"]), int(mix["n_valid"])
    batch = int(mix["minibatch"])
    t0 = time.perf_counter()
    made = fam.make_data(seed, cfg, mix)
    log("data: %s in %.1f s" % (
        ", ".join("%s %s" % (k, numpy.shape(v)) for k, v in made.items()),
        time.perf_counter() - t0))

    module = importlib.import_module(cfg["sample"])
    layers = program_layers(module, cfg)
    loader_cls, loader_config = fam.loader(made, mix)
    getattr(root, cfg["config_root"]).loader_name = loader_cls.MAPPING
    weight_seed = data_mod.sub_seed(seed, data_mod.TAG_WEIGHTS)
    dropout_seed = data_mod.sub_seed(seed, data_mod.TAG_DROPOUT)
    prng.get(1).seed(weight_seed)
    prng.get(2).seed(data_mod.sub_seed(seed, data_mod.TAG_SHUFFLE))
    fused = {"window": int(mix["window"]),
             "compute_dtype": getattr(jnp, mix["compute_dtype"]),
             "dropout_seed": dropout_seed}
    if int(cell["chips"]) > 1:
        fused["mesh"] = int(cell["chips"])
    wf = module.build(
        layers=layers,
        loader_config=dict(loader_config, minibatch_size=batch),
        decision_config={"max_epochs": 10 ** 9,
                         "fail_iterations": 10 ** 9},
        # no snapshot inside the window (stall per save is a later cell)
        snapshotter_config={"interval": 10 ** 9, "time_interval": 1e9,
                            "compression": ""},
        fused=fused)
    log("workflow built")
    wf.initialize(device=JaxDevice())
    log("workflow initialised")
    trainer, decision = wf.fused_trainer, wf.decision
    net = trainer.net
    if not trainer._use_device_data:
        raise SystemExit("the device-resident window path did not engage")
    capture = WindowCapture(trainer, mix["check_windows"], cfg, mix, fam,
                            sabotage)
    spans = []
    if trace:
        _wrap_unit_spans(wf, spans)

    warm_epochs = int(mix["warm_epochs"])
    st = {"epoch_ends": [], "t_start": None, "t_end": None,
          "first_epoch": None, "unit_time0": None, "unit_time1": None,
          "compiles0": None, "compiles1": None, "readbacks0": None,
          "readbacks1": None, "trace_dir": None, "trace_t0": 0}
    orig_stop = decision.stop_condition

    def unit_times():
        return {u.name: (u.run_time_, u.run_count_) for u in wf.units}

    def readbacks():
        return telemetry.counter("trainer.readbacks").value if trace else 0

    def at_epoch_end():
        n_done = len(st["epoch_ends"]) + 1
        if st["first_epoch"] is None:
            st["first_epoch"] = fam.first_epoch(decision)
        if n_done < warm_epochs:
            st["epoch_ends"].append(None)
            return orig_stop()
        if n_done == warm_epochs:
            # set-up ends here: every shape of an epoch has run
            jax.block_until_ready(net.params)
            st["epoch_ends"].append(None)
            st["unit_time0"] = unit_times()
            st["compiles0"] = compiles.count
            st["readbacks0"] = readbacks()
            log("set-up done: %d warm epochs, %d backend compiles"
                % (warm_epochs, compiles.count))
            if trace:
                st["trace_dir"] = os.path.join(
                    root_dir, ".cache", "bench_trace", cell["name"])
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 0
                jax.profiler.start_trace(st["trace_dir"],
                                         profiler_options=opts)
                # the trace's clock starts with the session: host spans
                # are set on it from here
                st["trace_t0"] = time.perf_counter_ns()
                del spans[:]
            st["t_start"] = time.perf_counter()
            return orig_stop()
        now = time.perf_counter()
        if now - st["t_start"] < seconds:
            st["epoch_ends"].append(now)
            return orig_stop()
        jax.block_until_ready(net.params)
        st["t_end"] = time.perf_counter()
        st["epoch_ends"].append(st["t_end"])
        if trace:
            jax.profiler.stop_trace()
        st["unit_time1"] = unit_times()
        st["compiles1"] = compiles.count
        st["readbacks1"] = readbacks()
        return True

    decision.stop_condition = at_epoch_end
    wf.run()
    if st["t_end"] is None:
        raise SystemExit("the job stopped by itself before the window "
                         "closed")
    window_compiles = st["compiles1"] - st["compiles0"]
    if window_compiles:
        raise SystemExit("%d compile(s) inside the timed window"
                         % window_compiles)

    peaks = []
    for d in jax.devices()[:int(cell["chips"])]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    ends = [t for t in st["epoch_ends"] if t is not None]
    epoch_times = numpy.diff([st["t_start"]] + ends)
    result = {
        "setup_s": st["t_start"] - t_process,
        "window_s": st["t_end"] - st["t_start"],
        "epochs": len(ends),
        # the rows given a training step in the window (the rate's
        # "images"), and a row's tokens where a row is a sequence
        "images": fam.rows_trained(mix, len(ends)),
        "row_tokens": fam.row_tokens(cfg, mix),
        "epoch_times": epoch_times,
        "memory_peak_bytes": max(peaks) if peaks else 0,
        "unit_time0": st["unit_time0"], "unit_time1": st["unit_time1"],
        "readbacks": st["readbacks1"] - st["readbacks0"],
        "trace_dir": st["trace_dir"],
        "host_spans": [[n, float(t - st["trace_t0"]), float(d)]
                       for n, t, d in spans],
        "first_epoch": st["first_epoch"],
        "trainer_name": trainer.name,
        "weight_seed": weight_seed, "dropout_seed": dropout_seed,
        "data": made,
        "n_train": n_train, "n_valid": n_valid, "batch": batch,
    }
    capture.fetch()
    if capture.numbers is None:
        raise SystemExit("fewer train windows ran than check_windows asks")
    result["program"] = capture.numbers
    result["windows"] = capture.windows
    # free the program's device state before the reference runs
    fam.release(net)
    decision.stop_condition = orig_stop
    del wf, trainer, decision, net, capture
    gc.collect()
    return result
