"""Loader base classes — the minibatch-serving contract.

The veles core loader is external to the reference repo; this implements the
contract observed at every use site (SURVEY.md §2.5): attributes
``minibatch_data/labels/indices/class/size/offset``, ``class_lengths``,
``total_samples``, ``last_minibatch``, ``epoch_ended``, ``epoch_number``,
``complete``; methods ``load_data``, ``create_minibatch_data``,
``fill_minibatch``.

Epoch semantics:
* One epoch serves every class segment with samples, in order
  TEST -> TRAIN -> VALID.  **Deliberate deviation** from the reference
  core's numeric order: serving VALID last is what the reference's own
  DecisionGD assumes at epoch end (decision.py:478-482 — "minibatch_class
  will be VALID if validation exists"), and measures validation *after*
  that epoch's training, which is the ML-standard reading.
* ``last_minibatch`` is true on each class segment's final minibatch;
  ``epoch_ended`` additionally on the epoch's final segment.
* ``epoch_number`` increments as the epoch wraps — after 3 full epochs
  ``epoch_number == 3`` (reference test contract,
  tests/functional/test_mnist_all2all.py:118).
* The TRAIN segment is reshuffled every epoch from the loader's PRNG
  stream (stream 2 — the functional-test harness seeds it separately).
* The tail minibatch of a segment keeps the buffer size constant
  (static shapes for XLA) and sets ``minibatch_size`` to the true count;
  consumers zero the padded tail (evaluator contract).
"""

import numpy

from znicz_tpu.core.units import Unit
from znicz_tpu.core.memory import Array
from znicz_tpu.core.mutable import Bool
from znicz_tpu.core import faults
from znicz_tpu.core import profiler
from znicz_tpu.core import prng
from znicz_tpu.core import telemetry
from znicz_tpu.core.config import root

TEST, VALID, TRAIN = 0, 1, 2
CLASS_NAME = {TEST: "test", VALID: "validation", TRAIN: "train"}

#: serving order within one epoch (see module docstring)
SERVE_ORDER = (TEST, TRAIN, VALID)


class ILoader(object):
    """Marker interface (parity: veles.loader.ILoader)."""


class IFullBatchLoader(ILoader):
    pass


class UserLoaderRegistry(type):
    """Registry of loader classes by MAPPING name
    (reference: standard_workflow_base.py:113)."""

    loaders = {}

    def __init__(cls, name, bases, clsdict):
        super(UserLoaderRegistry, cls).__init__(name, bases, clsdict)
        mapping = clsdict.get("MAPPING", None)
        if mapping:
            UserLoaderRegistry.loaders[mapping] = cls

    @staticmethod
    def get_factory(name):
        try:
            kls = UserLoaderRegistry.loaders[name]
        except KeyError:
            raise KeyError(
                "Unknown loader %r; known: %s" % (
                    name, sorted(UserLoaderRegistry.loaders)))
        return kls


class Loader(Unit, metaclass=UserLoaderRegistry):
    """Serves minibatches; subclasses provide the data."""

    def __init__(self, workflow, **kwargs):
        super(Loader, self).__init__(workflow, **kwargs)
        self.max_minibatch_size = kwargs.get("minibatch_size", 100)
        self.prng = kwargs.get("prng", prng.get(2))
        self.shuffle_limit = kwargs.get(
            "shuffle_limit", numpy.iinfo(numpy.uint32).max)
        self.normalization_type = kwargs.get("normalization_type", "none")
        self.normalization_parameters = kwargs.get(
            "normalization_parameters", {})
        self.testing = kwargs.get("testing", False)

        self.class_lengths = [0, 0, 0]
        #: CONTRACT under skip_fill (below): on TRAIN minibatches nothing
        #: is filled, so minibatch_data / minibatch_labels (and
        #: minibatch_targets) keep an EARLIER fill's contents — only
        #: minibatch_indices / size / class / offsets are valid; units
        #: reading data or labels on TRAIN must link through the fused
        #: trainer's window stats instead.  On VALID/TEST labels and
        #: targets are filled at once and minibatch_data on its first
        #: read (Array.defer), with the rows an eager fill would give
        self.minibatch_data = Array(name="minibatch_data")
        self.minibatch_labels = Array(name="minibatch_labels")
        self.minibatch_indices = Array(name="minibatch_indices")
        self.minibatch_size = 0
        self.minibatch_offset = 0
        self.minibatch_class = TRAIN
        self.last_minibatch = Bool(False)
        self.epoch_ended = Bool(False)
        self.epoch_number = 0
        self.complete = Bool(False)
        self.train_ended = Bool(False)
        #: set by the fused trainer when it takes every minibatch's rows
        #: from the data set it holds on the device, by minibatch_indices:
        #: TRAIN minibatches skip the host fill, VALID/TEST minibatches
        #: put the row copy off until a unit reads minibatch_data (none
        #: does in a stock fused graph; a saver or plotter that does gets
        #: the rows then).  Only loaders whose fill is the stock
        #: FullBatchLoader copy are asked (the trainer checks)
        self.skip_fill = False
        #: bumped every time the TRAIN order actually reshuffles — the
        #: fused trainer's device-resident permuted dataset is
        #: rematerialized when this changes (per-epoch, not per-window)
        self.shuffle_serial = 0
        #: this minibatch's start offset WITHIN its class segment — for
        #: TRAIN, the row range [offset, offset+size) of the epoch's
        #: shuffled order (minibatches are contiguous slices of
        #: ``_indices[clazz]`` by construction, see run())
        self.minibatch_class_offset = 0
        self._indices = {}       # class -> index array into the dataset
        self._segment = 0        # position in the serving order
        self._offset_in_class = 0
        self._global_offset = 0
        #: snapshotted iteration state — with the PRNG states this makes
        #: resume-retrain exact (epoch position + the shuffled order)
        self.exports = ["epoch_number", "_segment", "_offset_in_class",
                        "_global_offset", "_indices", "shuffle_serial"]
        self.normalizer = None
        self._labels_mapping = {}

    # -- to be provided by subclasses ---------------------------------------
    def load_data(self):
        """Fill class_lengths and prepare the dataset."""
        raise NotImplementedError

    def create_minibatch_data(self):
        """Allocate minibatch_data for max_minibatch_size samples."""
        raise NotImplementedError

    def fill_minibatch(self):
        """Copy the samples at minibatch_indices into minibatch buffers."""
        raise NotImplementedError

    # -- common ------------------------------------------------------------
    #: optional hook called after load_data during initialize (reference:
    #: real_loader.on_initialized, standard_workflow_base.py:334-336)
    on_initialized = None

    @property
    def total_samples(self):
        return int(sum(self.class_lengths))

    @property
    def unique_labels_count(self):
        """Number of distinct labels — sets the softmax head width
        (reference standard_workflow_base.py:324-334)."""
        labels = getattr(self, "original_labels", None)
        if labels is not None and len(labels):
            return len(set(labels))
        raise AttributeError("loader cannot derive unique_labels_count")

    @property
    def effective_class_lengths(self):
        return self.class_lengths

    @property
    def labels_mapping(self):
        return self._labels_mapping

    @property
    def has_labels(self):
        """Whether the dataset carries labels (reference loader/base.py
        Loader.has_labels).  NOT derived from minibatch_labels — that
        buffer is always allocated; subclasses override from their actual
        label source (see FullBatchLoader)."""
        return bool(self._labels_mapping)

    @property
    def shuffled_indices(self):
        """Serving-order -> dataset-index permutation across the whole
        epoch (segments in SERVE_ORDER, matching minibatch_offset) — what
        result exporters need to write per-sample outputs in dataset
        order (reference loader exposes shuffled_indices)."""
        parts = [self._indices[c] for c in self._serve_order()
                 if c in self._indices and len(self._indices[c])]
        if not parts:
            return numpy.arange(0)
        return numpy.concatenate(parts)

    def _serve_order(self):
        return [c for c in SERVE_ORDER if self.class_lengths[c] > 0]

    def class_index_range(self, clazz):
        """[start, end) of this class inside the dataset's sample axis,
        assuming dataset layout [TEST | VALID | TRAIN] (numeric order)."""
        start = sum(self.class_lengths[:clazz])
        return start, start + self.class_lengths[clazz]

    def initialize(self, device=None, **kwargs):
        super(Loader, self).initialize(device=device, **kwargs)
        self.load_data()
        if self.total_samples == 0:
            raise ValueError("%s loaded zero samples" % self.name)
        if self.max_minibatch_size < 1:
            raise ValueError("minibatch_size must be >= 1")
        self.max_minibatch_size = min(self.max_minibatch_size,
                                      max(self.class_lengths))
        for clazz in range(3):
            start, end = self.class_index_range(clazz)
            self._indices[clazz] = numpy.arange(start, end,
                                                dtype=numpy.int32)
        self._shuffle()
        self.create_minibatch_data()
        if not self.minibatch_data:
            raise ValueError("create_minibatch_data did not allocate "
                             "minibatch_data")
        if not self.minibatch_labels:
            self.minibatch_labels.reset(numpy.zeros(
                self.max_minibatch_size, dtype=numpy.int32))
        self.minibatch_indices.reset(numpy.zeros(
            self.max_minibatch_size, dtype=numpy.int32))
        self._segment = 0
        self._offset_in_class = 0
        self._global_offset = 0
        if self.on_initialized is not None:
            self.on_initialized()
        self.info(
            "%s: %d samples (test %d, validation %d, train %d), mb=%d",
            self.name, self.total_samples, self.class_lengths[TEST],
            self.class_lengths[VALID], self.class_lengths[TRAIN],
            self.max_minibatch_size)

    @property
    def train_indices(self):
        """The epoch's shuffled TRAIN order (global dataset indices) —
        the permutation the fused sliced-window path materializes on
        device once per :attr:`shuffle_serial` change."""
        return self._indices[TRAIN]

    def _shuffle(self):
        if self.epoch_number < self.shuffle_limit:
            self.prng.shuffle(self._indices[TRAIN])
            self.shuffle_serial += 1

    def run(self):
        order = self._serve_order()
        clazz = order[self._segment]
        length = self.class_lengths[clazz]
        off = self._offset_in_class
        n = min(self.max_minibatch_size, length - off)
        sel = self._indices[clazz][off:off + n]

        self.minibatch_class = clazz
        self.minibatch_size = int(n)
        self.minibatch_class_offset = int(off)
        self._global_offset += n
        self.minibatch_offset = self._global_offset

        idx = self.minibatch_indices.mem
        idx[:n] = sel
        idx[n:] = -1
        traced = telemetry.enabled()
        if traced:
            telemetry.counter("loader.minibatches").inc()
        if not (self.skip_fill and clazz == TRAIN):
            if traced:
                with telemetry.span("loader.fill", size=int(n),
                                    clazz=CLASS_NAME[clazz]):
                    self._fill_resilient()
            else:
                self._fill_resilient()
            if n < self.max_minibatch_size:
                self.minibatch_labels.map_write()
                self.minibatch_labels.mem[n:] = -1
                targets = getattr(self, "minibatch_targets", None)
                if targets:
                    targets.map_write()
                    targets.mem[n:] = 0

        seg_done = off + n >= length
        epoch_done = seg_done and self._segment == len(order) - 1
        self.last_minibatch <<= seg_done
        self.epoch_ended <<= epoch_done
        self.train_ended <<= seg_done and clazz == TRAIN

        if epoch_done:
            self.epoch_number += 1
            if telemetry.enabled():
                telemetry.counter("loader.epochs").inc()
                telemetry.instant("loader.epoch_end",
                                  epoch=self.epoch_number)
            if profiler.enabled():
                # epoch-boundary ledger leak check (core/profiler.py)
                profiler.epoch_check(self.epoch_number)
            self._segment = 0
            self._offset_in_class = 0
            self._global_offset = 0
            self._shuffle()
        elif seg_done:
            self._segment += 1
            self._offset_in_class = 0
        else:
            self._offset_in_class = off + n

    def _serve_fill(self):
        """One fill attempt, with the ``loader.fill`` fault-injection
        site INSIDE the retried region — an injected (or organic)
        transient I/O error is recovered by the retry below exactly
        like a flaky disk read would be; ``stall`` faults model a slow
        source and simply delay the fill."""
        if faults.enabled():
            faults.check("loader.fill")
        self.fill_minibatch()

    def _fill_resilient(self):
        """``fill_minibatch`` with bounded exponential-backoff retry on
        TRANSIENT failures (core/faults.py classifier + the
        ``root.common.retry`` policy).  A loader that raises a terminal
        error still fails the run; a flaky one costs a logged retry
        instead of an epoch of device-resident state."""
        faults.retry_call(self._serve_fill, "loader.fill")

    def fill_window_slot(self, x_out=None, labels_out=None,
                         targets_out=None, indices_out=None):
        """Overlap-aware window collection: copy the just-served
        minibatch's host buffers straight into caller-owned staging rows
        (the fused trainer's pipelined window assembly,
        units/fused_trainer.py).

        The caller owns the staging lifetime — the trainer rotates
        ``pipeline_depth + 1`` buffer sets so a row is never rewritten
        while the window it was dispatched with may still be reading it
        (``jax.device_put`` may alias aligned host buffers on the CPU
        backend).  ONE copy per minibatch replaces the previous
        per-step ``numpy.array`` copy + ``numpy.stack`` re-copy, and the
        loader's own buffers are free for the next ``run()`` the moment
        this returns — which is what lets collection of window K+1
        overlap the device executing window K.  Padded tail rows carry
        whatever the loader's fill discipline put there (labels -1,
        targets 0 — ``run()``); ``indices_out`` rows are valid under
        ``skip_fill`` too (only index/size/class bookkeeping serves
        then).

        Destination views may carry a PER-SHARD staging layout — under a
        data-parallel mesh the trainer's staging ring is shard-major
        ``(S, B // S, ...)`` so every shard's rows stay one contiguous
        host block for ``device_put`` — so each source reshapes to the
        destination's shape (a view of the contiguous minibatch buffer;
        still exactly one copy per minibatch)."""
        if x_out is not None:
            self.minibatch_data.map_read()
            x_out[...] = self.minibatch_data.mem.reshape(x_out.shape)
        if labels_out is not None:
            self.minibatch_labels.map_read()
            labels_out[...] = self.minibatch_labels.mem.reshape(
                labels_out.shape)
        if targets_out is not None:
            targets = self.minibatch_targets  # MSE mixin contract
            targets.map_read()
            targets_out[...] = targets.mem.reshape(targets_out.shape)
        if indices_out is not None:
            indices_out[...] = self.minibatch_indices.mem.reshape(
                indices_out.shape)

    # -- master-slave stubs (kept for protocol parity) ----------------------
    def generate_data_for_slave(self, slave=None):
        return None

    def apply_data_from_master(self, data):
        pass


class FullBatchLoader(Loader):
    """Loader keeping the whole dataset in memory
    (contract: original_data/original_labels + normalization)."""

    def __init__(self, workflow, **kwargs):
        super(FullBatchLoader, self).__init__(workflow, **kwargs)
        self.original_data = Array(name="original_data")
        self._original_labels = []
        #: cached numpy copy of the label list, rebuilt in initialize
        #: (after load_data) and when the list LENGTH changes; a loader
        #: that relabels IN PLACE mid-run with the same length must
        #: clear this cache itself
        self._labels_array = None
        self.force_numpy = kwargs.get("force_numpy", False)

    @property
    def original_labels(self):
        return self._original_labels

    @property
    def has_labels(self):
        return bool(self._original_labels) or bool(self._labels_mapping)

    def create_minibatch_data(self):
        sample_shape = self.original_data.shape[1:]
        # side-effect-free lookup (plain getattr would auto-vivify an empty
        # Config node into the global config)
        dtype = root.common.engine.get("precision_dtype")
        if dtype is None:
            dtype = self.original_data.dtype
        self.minibatch_data.reset(numpy.zeros(
            (self.max_minibatch_size,) + tuple(sample_shape), dtype=dtype))

    def initialize(self, device=None, **kwargs):
        # load_data just (re)filled the labels — drop any stale cache
        # (re-initialize after an in-place relabel must not serve the
        # old values)
        self._labels_array = None
        super(FullBatchLoader, self).initialize(device=device, **kwargs)
        self._apply_normalization()

    def _fit_and_normalize(self, array, norm_type, norm_params):
        """Fit a normalizer on the TRAIN slice of ``array`` and normalize
        the whole array in place (reference semantics: normalizer analyzed
        on the training set, applied everywhere).  Returns the
        normalizer."""
        from znicz_tpu.core import normalization
        if norm_type in (None, "none"):
            return normalization.NoneNormalizer()
        normalizer = normalization.create(norm_type, **norm_params)
        data = array.mem
        flat = data.reshape(data.shape[0], -1)
        start, end = self.class_index_range(TRAIN)
        fit_on = flat[start:end] if end > start else flat
        normalizer.analyze(fit_on)
        array.map_write()
        normalizer.normalize(flat)
        return normalizer

    def _apply_normalization(self):
        self.normalizer = self._fit_and_normalize(
            self.original_data, self.normalization_type,
            self.normalization_parameters)

    def _fill_rows(self, sel):
        # one fancy-index copy, not a per-sample python loop (the hot
        # host-side path of every epoch)
        self.minibatch_data.map_invalidate()
        self.minibatch_data.mem[:len(sel)] = self.original_data.mem[sel]

    def _fill_rows_forced(self, sel):
        """A row copy put off under ``skip_fill`` that a reader of
        ``minibatch_data`` needs after all."""
        if telemetry.enabled():
            telemetry.counter("loader.fill_forced").inc()
        self._fill_rows(sel)

    def fill_minibatch(self):
        sel = self.minibatch_indices.mem[:self.minibatch_size]
        if self.skip_fill:
            # the trainer gathers these rows on the device; the copy
            # keeps its own indices (the next run() rewrites the buffer)
            # and is made if something reads minibatch_data
            sel = sel.copy()
            self.minibatch_data.defer(lambda: self._fill_rows_forced(sel))
            if telemetry.enabled():
                telemetry.counter("loader.fill_deferred").inc()
        else:
            self._fill_rows(sel)
        self.minibatch_labels.map_write()
        n = len(sel)
        if self._original_labels:
            labels = self._labels_array
            if labels is None or len(labels) != len(self._original_labels):
                labels = self._labels_array = numpy.asarray(
                    self._original_labels)
            self.minibatch_labels.mem[:n] = labels[sel]


class LoaderMSEMixin(object):
    """Per-sample regression targets — the contract EvaluatorMSE trains
    against (reference veles.loader.LoaderMSEMixin, SURVEY.md §2.9;
    used by Kanji/Approximator, evaluator.py:334-556).

    Adds ``minibatch_targets`` (wired to the evaluator's ``target`` by
    StandardWorkflow.link_evaluator), optional ``class_targets`` (enables
    the nearest-class-target error metric), and a targets normalizer
    separate from the data normalizer.
    """

    def __init__(self, workflow, **kwargs):
        super(LoaderMSEMixin, self).__init__(workflow, **kwargs)
        self.minibatch_targets = Array(name="minibatch_targets")
        self.targets_normalization_type = kwargs.get(
            "targets_normalization_type", "none")
        self.targets_normalization_parameters = kwargs.get(
            "targets_normalization_parameters", {})
        self.target_normalizer = None
        self.class_targets = None

    @property
    def targets_shape(self):
        return tuple(self.minibatch_targets.shape[1:])


class FullBatchLoaderMSEMixin(LoaderMSEMixin):
    """FullBatch variant: whole ``original_targets`` in memory, sliced per
    minibatch alongside the data (reference FullBatchLoaderMSEMixin)."""

    def __init__(self, workflow, **kwargs):
        super(FullBatchLoaderMSEMixin, self).__init__(workflow, **kwargs)
        self.original_targets = Array(name="original_targets")

    def create_minibatch_data(self):
        super(FullBatchLoaderMSEMixin, self).create_minibatch_data()
        if not self.original_targets:
            raise ValueError(
                "%s.load_data must fill original_targets" % self.name)
        self.minibatch_targets.reset(numpy.zeros(
            (self.max_minibatch_size,) +
            tuple(self.original_targets.shape[1:]),
            dtype=self.minibatch_data.dtype))

    def initialize(self, device=None, **kwargs):
        super(FullBatchLoaderMSEMixin, self).initialize(
            device=device, **kwargs)
        self._apply_target_normalization()

    def _apply_target_normalization(self):
        self.target_normalizer = self._fit_and_normalize(
            self.original_targets, self.targets_normalization_type,
            self.targets_normalization_parameters)

    def fill_minibatch(self):
        super(FullBatchLoaderMSEMixin, self).fill_minibatch()
        n = self.minibatch_size
        idx = self.minibatch_indices.mem[:n]
        self.minibatch_targets.map_invalidate()
        self.minibatch_targets.mem[:n] = self.original_targets.mem[idx]


class FullBatchLoaderMSE(FullBatchLoaderMSEMixin, FullBatchLoader):
    """Convenience concrete base for full-batch MSE loaders."""
