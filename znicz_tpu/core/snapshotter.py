"""Checkpoint / resume.

TPU-era equivalent of ``veles.snapshotter`` (SURVEY.md §5.4).  The reference
pickles the entire workflow object (Python-version-fragile — SURVEY hard part
6); znicz_tpu defines an explicit format instead: a compressed pickle of

    {"format": 1, "workflow": <class qualname>, "config": <json>,
     "units": {unit.name: {attr: numpy value for attr in unit.exports}},
     "suffix": "...", "time": ...}

Gating/naming behavior matches the reference: linked after decision, gated
``epoch_ended & improved``, filename suffix like
``validation_1.92_train_0.04`` (standard_workflow.py:493-516,
decision.py:540-548).  Compression gz/bz2/xz selected by ``compression``
kwarg (forge URL parity).  Resume: ``SnapshotterToFile.import_(path)``
returns the state dict; ``Workflow.apply_snapshot`` style loading is done by
NNSnapshotterBase subclasses (znicz_tpu.units.nn_units).
"""

import bz2
import gzip
import lzma
import os
import pickle
import time

from znicz_tpu.core.units import Unit
from znicz_tpu.core.config import root
from znicz_tpu.core.memory import Array
from znicz_tpu.core import faults
from znicz_tpu.core import telemetry

import numpy


_WRITERS = {
    "": open,
    "gz": gzip.open,
    "bz2": bz2.open,
    "xz": lzma.open,
}


class SnapshotterRegistry(type):
    mapping = {}

    def __init__(cls, name, bases, clsdict):
        super(SnapshotterRegistry, cls).__init__(name, bases, clsdict)
        mapping = clsdict.get("MAPPING", None)
        if mapping:
            SnapshotterRegistry.mapping[mapping] = cls


class SnapshotterBase(Unit, metaclass=SnapshotterRegistry):
    """Collects unit exports and writes a snapshot when fired."""

    def __init__(self, workflow, **kwargs):
        super(SnapshotterBase, self).__init__(workflow, **kwargs)
        self.prefix = kwargs.get("prefix", "snapshot")
        self.compression = kwargs.get("compression", "gz")
        self.directory = kwargs.get(
            "directory", root.common.dirs.snapshots)
        self.interval = kwargs.get("interval", 1)
        self.time_interval = kwargs.get("time_interval", 0)
        #: mid-epoch trigger: every N dispatched fused training windows
        #: the trainer's ``window_tick`` call captures a resumable
        #: snapshot under the ``midepoch`` suffix (0 = off).  With the
        #: loader cursor + PRNG streams + the trainer's drained epoch
        #: accumulators all in the payload, a SIGKILLed run resumes
        #: mid-epoch with aggregates exactly equal to an uninterrupted
        #: one (tests/functional/test_fault_tolerance.py).
        self.window_interval = int(kwargs.get("window_interval", 0))
        self.suffix = None
        self.destination = None
        self._last_time = 0.0
        self._since_fire = 0
        self._windows_since = 0

    def initialize(self, device=None, **kwargs):
        super(SnapshotterBase, self).initialize(device=device, **kwargs)
        os.makedirs(self.directory, exist_ok=True)

    def run(self):
        self._since_fire += 1
        if self._since_fire < self.interval:
            return
        if time.time() - self._last_time < self.time_interval:
            return
        self._metered_export("snapshotter.export")
        # interval state advances ONLY after a successful export (a
        # failed write above raised out of run()): a transient write
        # failure must not silently push the next snapshot a full
        # interval/time_interval out — the next fire retries instead
        self._since_fire = 0
        self._last_time = time.time()

    def window_tick(self):
        """Mid-epoch trigger — the fused trainer calls this once per
        dispatched NON-segment-final training window.  Every
        ``window_interval`` windows it exports a snapshot under the
        ``midepoch`` suffix; 0 (the default) keeps this a single
        predicate.  Like :meth:`run`, the counter resets only after a
        successful export, so a failed write retries on the very next
        window.  Returns the written path (None when off/not due)."""
        if not self.window_interval:
            return None
        self._windows_since += 1
        if self._windows_since < self.window_interval:
            return None
        saved = self.suffix
        self.suffix = "midepoch"
        try:
            wrote = self._metered_export("snapshotter.midepoch")
        finally:
            self.suffix = saved
        self._windows_since = 0
        return wrote

    def _metered_export(self, span_name):
        """Telemetry shell shared by the decision-gated :meth:`run` and
        the window-interval :meth:`window_tick` trigger."""
        if not telemetry.enabled():
            return self.export()
        t0 = time.perf_counter()
        with telemetry.span(span_name, prefix=self.prefix):
            wrote = self.export()
        # the series are created on EVERY rank (registries must stay
        # SPMD-identical or cross-host aggregation refuses to merge)
        # but recorded only for actual writes: export() returns the
        # written path, None when it skipped (non-zero ranks of a
        # multi-host gang) — merged counters must not multiply one
        # snapshot by process_count
        exports = telemetry.counter("snapshotter.exports")
        seconds = telemetry.histogram("snapshotter.export_seconds")
        if wrote:
            exports.inc()
            seconds.observe(time.perf_counter() - t0)
        return wrote

    def export(self):
        """Write a snapshot; return the destination path, or None when
        this process skipped the write (telemetry counts only actual
        writes)."""
        raise NotImplementedError

    # -- state collection ---------------------------------------------------
    def collect_state(self):
        """Gather {unit_name: {attr: plain numpy}} from units' ``exports``."""
        wf = self.workflow
        state = {}
        for unit in wf.units:
            exports = getattr(unit, "exports", None)
            if not exports:
                continue
            ustate = {}
            for attr in exports:
                try:
                    v = getattr(unit, attr)
                except AttributeError:
                    continue
                if isinstance(v, Array):
                    v = None if not v else numpy.array(v.mem)
                ustate[attr] = v
            state[unit.name] = ustate
        return state


class SnapshotterToFile(SnapshotterBase):
    """File snapshots (reference MAPPING "file"/"nnfile" family)."""

    MAPPING = "file"

    def export(self, units_state=None):
        from znicz_tpu.core import prng
        import jax
        if jax.process_count() > 1 and jax.process_index() != 0:
            # multi-host SPMD runs the same gang-scheduled program on
            # every process with identical state — one writer (process
            # 0) is sufficient AND necessary (concurrent writers would
            # race on the same prefix); every process restores from the
            # shared directory on resume
            return None
        payload = {
            "format": 1,
            "workflow": type(self.workflow).__name__,
            "config": root.to_json(),
            # a subclass that already collected (NNSnapshotterBase's
            # tensor-stat logging) passes the state through — the
            # epoch_acc export drains the async pipeline, so one
            # collection per capture, not two
            "units": self.collect_state() if units_state is None
            else units_state,
            # PRNG stream states make resume-retrain EXACT (the reference
            # gets this by pickling the whole workflow, prng included)
            "prng": prng.states(),
            "suffix": self.suffix,
            "time": time.time(),
        }
        topology = self._forward_topology()
        if topology is not None:
            payload["topology"] = topology
        ext = "" if not self.compression else "." + self.compression
        name = "%s_%s.%d.pickle%s" % (
            self.prefix, self.suffix or "current", os.getpid(), ext)
        self.destination = os.path.join(self.directory, name)
        opener = _WRITERS[self.compression or ""]
        # atomic publish: a crash/SIGKILL mid-write must never leave a
        # truncated file where auto-resume (launcher --auto-resume) will
        # look for the newest snapshot
        if faults.enabled():
            faults.check("snapshot.write")
        tmp = self.destination + ".part"
        with opener(tmp, "wb") as f:
            pickle.dump(payload, f, protocol=4)
        # crash-DURABLE publish: os.replace is atomic against readers
        # but not against power loss — the .part data blocks (fsynced
        # after close so compressed trailers are included) and the
        # directory entry must both hit disk, or a crash can leave the
        # published name pointing at truncated bytes
        fd = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
        os.replace(tmp, self.destination)
        dfd = os.open(os.path.dirname(self.destination) or ".",
                      os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
        self.info("snapshot -> %s", self.destination)
        telemetry.record_event("snapshot", path=self.destination,
                               suffix=self.suffix)
        return self.destination

    def _forward_topology(self):
        """Typed layer list describing the workflow's forward stack
        (export.forward_topology) — the sidecar that lets the serving
        engine reconstruct a jitted forward straight from the snapshot.
        None for a workflow without a forward stack; one that has a
        stack and cannot describe it fails here, at the snapshot, not
        later at ``serve --latest``."""
        wf = self.workflow
        if not getattr(wf, "forwards", None):
            return None
        from znicz_tpu.export import forward_topology
        topology = forward_topology(wf)
        return topology if topology["layers"] else None

    @staticmethod
    def import_(file_name):
        """Load a snapshot state dict (resume contract,
        reference test: test_mnist_all2all.py:118+)."""
        ext = os.path.splitext(file_name)[1].lstrip(".")
        opener = _WRITERS.get(ext if ext in _WRITERS else "", open)
        with opener(file_name, "rb") as f:
            return pickle.load(f)


class SnapshotterToDB(SnapshotterBase):
    """ODBC snapshot parity stub — stores to a file-backed 'db' directory.

    The reference's ToDB variant (nn_units.py:849-854) needs an ODBC server;
    out of scope for a single-box build, behavior-compatible via files.
    """

    MAPPING = "odbc"

    def export(self):  # pragma: no cover - parity stub
        return SnapshotterToFile.export(self)
