"""Workflow launcher — the reference ``run(load, main)`` contract + CLI
backend.

Reference contract (every sample module ends with it — samples/MNIST/
mnist.py:128-137, samples/Wine/wine.py:178-181): the veles CLI imports the
workflow module and calls ``module.run(load, main)`` where

* ``load(factory, **kwargs) -> (workflow, snapshot_loaded)`` constructs
  the workflow — or marks it for restoration when the launcher carries a
  ``--snapshot`` path;
* ``main(**kwargs)`` initializes (forwarding kwargs), applies any pending
  snapshot state, and runs.

The reference launcher's other role — master/slave distribution over
sockets (veles launcher.py, nn_units.py:178-211 broadcast/aggregate) — is
deliberately NOT reproduced: the TPU-native equivalent is SPMD over a
``jax.sharding.Mesh`` (:mod:`znicz_tpu.parallel`), where XLA's collectives
replace the parameter-server cycle.  This launcher runs the unit-graph
control plane in one process, standalone.
"""

import importlib
import importlib.util
import os

from znicz_tpu.core.logger import Logger


class Launcher(Logger):
    """Standalone launcher implementing ``load``/``main``.

    Modes:
    * ``testing`` — forward-only run (the reference ``--test`` flag):
      after initialize, decision/loader are put into testing mode when
      they support it;
    * ``dry_run`` — build + initialize only, skip ``run()``;
    * ``snapshot`` — path of a :class:`SnapshotterToFile` pickle to
      restore into the freshly-built workflow before running.
    """

    def __init__(self, testing=False, snapshot=None, device=None,
                 dry_run=False, fused=None, auto_resume=False):
        super(Launcher, self).__init__(logger_name="Launcher")
        # multi-host SPMD: bring up jax.distributed from the env
        # (JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES/JAX_PROCESS_ID or
        # a managed-cluster runtime) BEFORE any backend use; a no-op
        # for single-process runs.  A failed init degrades to
        # single-process ONLY for autodetected cluster markers (a stale
        # SLURM_JOB_ID in an interactive shell); with an EXPLICIT
        # JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES config it stays
        # fatal — silently training unsynced on one host while the
        # gang expects gradient sync would corrupt the job
        import os as _os
        from znicz_tpu.parallel import multihost
        explicit = bool(_os.environ.get("JAX_COORDINATOR_ADDRESS")
                        or _os.environ.get("JAX_NUM_PROCESSES"))
        try:
            up = multihost.initialize()
        except Exception as e:
            if explicit:
                raise
            self.warning("jax.distributed init failed (%s); continuing "
                         "single-process", e)
            up = False
        if up:
            self.info("jax.distributed up: process %d of %d",
                      __import__("jax").process_index(),
                      __import__("jax").process_count())
        self.testing = testing
        self.snapshot_path = snapshot
        self.device = device
        self.dry_run = dry_run
        #: fused execution mode forwarded to StandardWorkflow-based
        #: samples (True or a config dict — see link_fused_trainer)
        self.fused = fused
        #: job-level elastic recovery (reference slave-loss semantics
        #: re-provided at the job level, SURVEY.md §2.8 / nn_rollback.py
        #: 87-97): on start, restore the NEWEST matching snapshot the
        #: workflow's snapshotter would have written, fast-forward (the
        #: snapshot carries loader position + PRNG streams + optimizer
        #: state) and continue training
        self.auto_resume = auto_resume
        self.workflow = None
        self.interactive = False
        self._state = None

    # -- the role the workflow sees (reference Launcher interface) ----------
    @property
    def is_master(self):
        return False

    @property
    def is_slave(self):
        return False

    @property
    def is_standalone(self):
        return True

    def add_unit(self, unit):
        # a Workflow constructed with the launcher as parent registers here
        self.workflow = unit

    add_ref = add_unit

    def del_ref(self, unit):
        pass

    # -- run(load, main) contract -------------------------------------------
    def load(self, factory, **kwargs):
        """Construct the workflow.  ``factory`` is a Workflow subclass
        (instantiated with this launcher as parent) or a builder callable
        returning the workflow.  Returns (workflow, snapshot_loaded)."""
        if self.snapshot_path:
            from znicz_tpu.core.snapshotter import SnapshotterToFile
            self._state = SnapshotterToFile.import_(self.snapshot_path)
            self.info("will restore snapshot %s", self.snapshot_path)
        if self.fused is not None:
            kwargs.setdefault("fused", self.fused)
        if isinstance(factory, type):
            wf = factory(self, **kwargs)
        else:
            wf = factory(**kwargs)
        self.workflow = wf
        if self.fused is not None and \
                getattr(wf, "fused_trainer", None) is None:
            # no quiet downgrade to the unit graph: the run asked for
            # the compiled step and would report unit-graph numbers
            raise SystemExit(
                "--fused requested but %s does not build a fused "
                "trainer (hand-wired workflow?)" % type(wf).__name__)
        return wf, self._state is not None

    def _snapshot_incompatible(self, state, wf):
        """Reason the snapshot cannot be applied to ``wf`` (None = OK):
        a different workflow class, or any exported Array whose shape
        differs from the live one (e.g. the same snapshot prefix used by
        two topologies) — applying blindly would corrupt state or crash
        deep inside the first train step."""
        import numpy
        from znicz_tpu.core.memory import Array
        snap_wf = state.get("workflow")
        if snap_wf not in (None, type(wf).__name__):
            return "workflow class %r != %r" % (snap_wf,
                                                type(wf).__name__)
        units = {u.name: u for u in wf.units}
        for uname, ustate in state.get("units", {}).items():
            u = units.get(uname)
            if u is None:
                continue
            for attr, value in ustate.items():
                if value is None:
                    continue
                if attr == "epoch_acc":
                    # mid-epoch accumulator capture: validate against
                    # the net's zero-acc layout (host-side shapes — the
                    # live getattr would force a device drain per
                    # candidate).  A lead-dim mismatch means a
                    # different data-shard count; resuming it would
                    # crash the first window dispatch and, under
                    # run_supervised, burn every restart on the same
                    # bad snapshot instead of falling back
                    net = getattr(u, "net", None)
                    if net is None or not isinstance(value, dict):
                        continue
                    expect = net.window_acc_zeros()
                    for leaf, zero in expect.items():
                        got = value.get(leaf)
                        if got is None or \
                                tuple(numpy.shape(got)) != zero.shape:
                            return "unit %s.epoch_acc[%s] shape %s " \
                                "!= %s" % (
                                    uname, leaf,
                                    None if got is None
                                    else tuple(numpy.shape(got)),
                                    zero.shape)
                    continue
                cur = getattr(u, attr, None)
                if isinstance(cur, Array) and cur and \
                        tuple(cur.shape) != tuple(numpy.shape(value)):
                    return "unit %s.%s shape %s != %s" % (
                        uname, attr, numpy.shape(value), tuple(cur.shape))
                if attr == "fused_state" and isinstance(value, dict) and \
                        getattr(u, "net", None) is not None:
                    cur_sd = u.fused_state
                    snap_params = list(value.get("params", ()))
                    # zip would truncate: a different topology with
                    # fewer/more layers whose leading shapes agree must
                    # still be rejected
                    if len(snap_params) != len(cur_sd["params"]):
                        return ("fused layer count %d != %d"
                                % (len(snap_params),
                                   len(cur_sd["params"])))
                    for p_cur, p_new in zip(cur_sd["params"],
                                            snap_params):
                        if set(p_cur) != set(p_new):
                            return ("fused param keys %s != %s"
                                    % (sorted(p_new), sorted(p_cur)))
                        for k in p_cur:
                            if numpy.shape(p_cur[k]) != \
                                    numpy.shape(p_new[k]):
                                return ("fused param shape %s != %s"
                                        % (numpy.shape(p_new[k]),
                                           numpy.shape(p_cur[k])))
        # shape agreement is not enough: a DIFFERENT topology under the
        # same snapshot prefix has disjoint unit names, every check
        # above passes vacuously, and "resume" would restore epoch
        # bookkeeping with freshly random weights.  Require the snapshot
        # to actually cover the workflow's trainable state (directly or
        # via the cross-mode fused<->unit-graph mapping).
        forwards = [f for f in getattr(wf, "forwards", ())]
        has_fused_state = any(
            isinstance(us.get("fused_state"), dict)
            for us in state.get("units", {}).values())
        has_unit_weights = any(
            us.get("weights") is not None
            for us in state.get("units", {}).values())
        trainable = [f for f in forwards
                     if getattr(f, "weights", None) is not None
                     and f.weights] or \
                    ([wf.fused_trainer]
                     if getattr(wf, "fused_trainer", None) is not None
                     else [])
        if trainable and not (has_fused_state or has_unit_weights):
            return "snapshot carries no trainable weights"
        if trainable and has_unit_weights and not has_fused_state:
            trainer = getattr(wf, "fused_trainer", None)
            if trainer is None:
                covered = sum(
                    1 for f in forwards
                    if state.get("units", {}).get(f.name, {})
                    .get("weights") is not None)
            else:
                # fused target: the cross-mode map looks the layers up
                # by their unit-graph forward names
                covered = 0
                for i, layer in enumerate(trainer.layers):
                    name = (layer["name"] + "_forward") \
                        if "name" in layer \
                        else "%s_%d_forward" % (layer.get("type"), i)
                    if state.get("units", {}).get(name, {}) \
                            .get("weights") is not None:
                        covered += 1
            if not covered:
                return ("snapshot's unit names cover none of this "
                        "workflow's layers (different topology under "
                        "the same prefix?)")
        return None

    def _find_resume_state(self, wf):
        """Newest importable AND compatible snapshot matching the
        workflow's snapshotter prefix/directory; corrupt files (a crash
        can interrupt even an atomic-rename write of the PREVIOUS run's
        file on some systems) and incompatible topologies are skipped
        newest-first."""
        from znicz_tpu.core.snapshotter import SnapshotterToFile
        snap = getattr(wf, "snapshotter", None)
        if snap is None:
            self.warning("--auto-resume: workflow has no snapshotter")
            return None
        from znicz_tpu.core import telemetry
        for path in snapshot_candidates(snap.directory, snap.prefix):
            try:
                state = SnapshotterToFile.import_(path)
            except Exception as e:  # noqa: BLE001 - corrupt snapshot
                self.warning("auto-resume: skipping unreadable snapshot "
                             "%s (%s)", path, e)
                telemetry.record_event("resume.skipped", path=path,
                                       why="unreadable",
                                       error=repr(e))
                continue
            reason = self._snapshot_incompatible(state, wf)
            if reason:
                self.warning("auto-resume: skipping incompatible "
                             "snapshot %s (%s)", path, reason)
                telemetry.record_event("resume.skipped", path=path,
                                       why="incompatible",
                                       reason=reason)
                continue
            self.info("auto-resume: restoring %s", path)
            return state
        return None

    def main(self, **kwargs):
        """Initialize (+restore), then run unless dry_run."""
        wf = self.workflow
        if wf is None:
            raise RuntimeError("main() before load()")
        from znicz_tpu.core import backends, compile_cache
        cache_dir = compile_cache.maybe_enable()
        if not isinstance(self.device, backends.NumpyDevice):
            self.info("%s; compile cache: %s", backends.describe(),
                      cache_dir or "off")
        wf.initialize(device=self.device, **kwargs)
        if self.auto_resume:
            found = self._find_resume_state(wf)
            if found is not None:
                # the newest resumable state wins over an explicit
                # --snapshot (which stays the fallback seed): a
                # supervised restart that crashed BEFORE the first new
                # snapshot write must re-enter the user's warm start,
                # and one that crashed after must continue the run,
                # not rewind to the seed
                self._state = found
            elif self._state is not None:
                self.info("auto-resume: no resumable snapshot; "
                          "falling back to explicit snapshot %s",
                          self.snapshot_path)
        if self._state is not None:
            from znicz_tpu.units.nn_units import load_snapshot_into_workflow
            load_snapshot_into_workflow(self._state, wf)
        if self.testing:
            for unit in wf.units:
                if hasattr(unit, "testing"):
                    unit.testing = True
        if not self.dry_run:
            from znicz_tpu.core import telemetry
            # black-box the run: SIGTERM and unhandled exceptions dump
            # the flight recorder + metrics + traceback to a crash
            # directory (only when telemetry/health journaling is on)
            telemetry.install_crash_handler()
            try:
                wf.run()
            except Exception as e:
                if telemetry.journal_enabled() and \
                        getattr(e, "crash_report", None) is None:
                    # the health halt policy already wrote its own
                    import sys
                    path = telemetry.write_crash_report(
                        reason="workflow run failed: %r" % e,
                        exc_info=sys.exc_info())
                    try:
                        # tag it so the sys.excepthook crash handler
                        # does not write a SECOND report for the same
                        # exception on its way out
                        e.crash_report = path
                    except AttributeError:  # __slots__ exception type
                        pass
                raise
        return wf


def snapshot_candidates(directory, prefix):
    """Snapshot paths under ``directory`` matching the snapshotter
    naming scheme for ``prefix``, newest first — the one listing shared
    by ``--auto-resume`` (Launcher) and ``serve --latest``
    (znicz_tpu.serving).  In-flight ``.part`` files are excluded."""
    if not directory or not os.path.isdir(directory):
        return []
    cands = [os.path.join(directory, f) for f in os.listdir(directory)
             if f.startswith(prefix + "_")
             and ".pickle" in f and not f.endswith(".part")]
    cands.sort(key=os.path.getmtime, reverse=True)
    return cands


def newest_snapshot(directory, prefix):
    """The newest snapshot for ``prefix`` (None when there is none)."""
    cands = snapshot_candidates(directory, prefix)
    return cands[0] if cands else None


def resolve_workflow_module(spec):
    """CLI workflow argument -> imported module.

    Accepts a file path (``samples/mnist.py``), a dotted module name
    (``znicz_tpu.samples.mnist``), or a bare registered sample name
    (``mnist``)."""
    if os.path.sep in spec or spec.endswith(".py"):
        path = os.path.abspath(spec)
        name = os.path.splitext(os.path.basename(path))[0]
        module_spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        return module
    try:
        return importlib.import_module(spec)
    except ImportError as e:
        # fall back to the samples namespace only when SPEC itself was
        # not found (for dotted names like "research.stl10" the error
        # names the unresolvable first component).  A spec already under
        # the project namespace never falls back: its ImportErrors come
        # from INSIDE the module and must surface.
        first = spec.split(".")[0]
        if spec.startswith("znicz_tpu") or \
                e.name not in (spec, first) or first == "znicz_tpu":
            raise
        try:
            return importlib.import_module("znicz_tpu.samples." + spec)
        except ImportError as e2:
            if e2.name != "znicz_tpu.samples." + first:
                raise
            # a research sample by its bare name (``looped_lm``)
            return importlib.import_module(
                "znicz_tpu.samples.research." + spec)


def list_samples():
    """Registered sample names (modules under znicz_tpu.samples,
    including the research tier as ``research.<name>``)."""
    import znicz_tpu.samples as samples_pkg
    pkg_dir = os.path.dirname(samples_pkg.__file__)
    names = []
    for prefix, directory in (("", pkg_dir),
                              ("research.",
                               os.path.join(pkg_dir, "research"))):
        if not os.path.isdir(directory):
            continue
        for fn in sorted(os.listdir(directory)):
            if fn.endswith(".py") and not fn.startswith("_"):
                names.append(prefix + fn[:-3])
    return names


def run_workflow(spec, snapshot=None, testing=False, dry_run=False,
                 device=None, fused=None, auto_resume=False):
    """Drive a workflow module's ``run(load, main)``.

    ``spec`` is a module object or anything
    :func:`resolve_workflow_module` accepts.  Falls back to the module's
    ``run_sample()`` when no ``run`` is exported (plain-run only — the
    fallback cannot honor snapshot/testing/dry_run).  Returns the
    workflow."""
    module = spec if hasattr(spec, "__file__") else \
        resolve_workflow_module(spec)
    launcher = Launcher(testing=testing, snapshot=snapshot,
                        device=device, dry_run=dry_run, fused=fused,
                        auto_resume=auto_resume)
    if hasattr(module, "run"):
        module.run(launcher.load, launcher.main)
        return launcher.workflow
    if hasattr(module, "run_sample"):
        if snapshot or testing or dry_run or fused is not None \
                or auto_resume:
            raise SystemExit(
                "%s exposes only run_sample(); --snapshot/--testing/"
                "--dry-run/--fused/--auto-resume need the "
                "run(load, main) contract" % spec)
        return module.run_sample(device=device)
    raise SystemExit(
        "%s exposes neither run(load, main) nor run_sample()" % spec)


def run_supervised(spec, max_restarts=0, restart_backoff_ms=1000.0,
                   restart_backoff_max_ms=30000.0, snapshot=None,
                   testing=False, dry_run=False, device=None, fused=None,
                   auto_resume=False):
    """Supervised :func:`run_workflow`: a crashed run is caught, backed
    off (exponentially, ``restart_backoff_ms * 2**attempt`` capped at
    ``restart_backoff_max_ms``) and re-entered up to ``max_restarts``
    times with ``auto_resume`` forced on — the restarted attempt
    rebuilds the workflow and restores the newest readable snapshot,
    including mid-epoch ``window_interval`` captures, so a preempted
    training run continues instead of restarting the epoch.

    The job-level twin of the reference's slave-loss recovery
    (a worker dies, the master re-issues its work): here the whole
    process is the worker and the snapshot directory is the master.

    Deliberately NOT restarted:

    * ``KeyboardInterrupt`` / ``SystemExit`` — operator intent;
    * :class:`~znicz_tpu.core.health.HealthViolationError` — the halt
      policy asked to stop; resuming would replay into the same
      violation, forever.

    Each restart is metered (``launcher.restarts`` counter) and
    journaled (``launcher.restart`` events carry the attempt number,
    the error and the backoff).  Returns the finished workflow.
    """
    import time

    from znicz_tpu.core import telemetry
    from znicz_tpu.core.health import HealthViolationError
    from znicz_tpu.core.logger import Logger

    log = Logger(logger_name="Supervisor")
    attempt = 0
    while True:
        try:
            # the explicit snapshot rides along on EVERY attempt: with
            # auto-resume forced on, a restart prefers the newest
            # resumable snapshot but a crash before the first write
            # falls back to the user's warm start instead of fresh
            # random weights
            return run_workflow(
                spec, snapshot=snapshot,
                testing=testing, dry_run=dry_run, device=device,
                fused=fused, auto_resume=auto_resume or attempt > 0)
        except (KeyboardInterrupt, SystemExit):
            raise
        except HealthViolationError:
            raise
        except Exception as e:  # noqa: BLE001 - the supervised surface
            attempt += 1
            if attempt > max_restarts:
                raise
            delay = min(float(restart_backoff_ms) / 1e3
                        * (2 ** (attempt - 1)),
                        float(restart_backoff_max_ms) / 1e3)
            if telemetry.enabled():
                telemetry.counter("launcher.restarts").inc()
            telemetry.record_event("launcher.restart", attempt=attempt,
                                   max_restarts=max_restarts,
                                   error=repr(e),
                                   backoff_ms=round(delay * 1e3, 3))
            log.warning(
                "run crashed (%r); restart %d/%d with auto-resume in "
                "%.1f s", e, attempt, max_restarts, delay)
            if delay > 0:
                time.sleep(delay)
