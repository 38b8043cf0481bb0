"""Evaluator units — produce err_output + metrics from the last forward.

TPU-era equivalent of reference evaluator.py (556 LoC — SURVEY.md §2.4).
The evaluator is the forward/backward boundary: EvaluatorSoftmax fuses the
softmax-CE gradient, error count, confusion matrix and max-gradient-sum into
one jitted op (:mod:`znicz_tpu.ops.evaluator`) exactly like the reference's
single fused kernel (evaluator.jcl).
"""

import numpy

from znicz_tpu.core.accelerated_units import AcceleratedUnit
from znicz_tpu.core.memory import Array
from znicz_tpu.ops import evaluator as ev_ops


class EvaluatorsRegistry(type):
    """LOSS-string registry (reference evaluator.py:58-68)."""

    evaluators = {}

    def __init__(cls, name, bases, clsdict):
        super(EvaluatorsRegistry, cls).__init__(name, bases, clsdict)
        loss = clsdict.get("LOSS", None)
        if loss:
            EvaluatorsRegistry.evaluators[loss] = cls


class IResultProvider(object):
    def get_metric_names(self):
        return set()

    def get_metric_values(self):
        return {}


class EvaluatorBase(AcceleratedUnit, IResultProvider,
                    metaclass=EvaluatorsRegistry):
    """Allocates err_output; testing mode merges per-minibatch outputs
    (reference evaluator.py:73-141)."""

    LOSS = None

    def __init__(self, workflow, **kwargs):
        kwargs.setdefault("view_group", "EVALUATOR")
        super(EvaluatorBase, self).__init__(workflow, **kwargs)
        self.mean = kwargs.get("mean", True)
        self.err_output = Array(name="err_output")
        self._merged_output = None
        self.krn_constants_i_ = None
        self.testing = kwargs.get("testing", False)
        self.demand("output", "batch_size")
        if self.testing:
            # merge_output needs the loader's running sample offset
            self.demand("offset")

    @property
    def merged_output(self):
        return self._merged_output

    def initialize(self, device=None, **kwargs):
        super(EvaluatorBase, self).initialize(device=device, **kwargs)
        if not self.err_output or \
                self.err_output.shape != self.output.shape:
            self.err_output.reset(numpy.zeros(
                self.output.shape, dtype=self.output.dtype))
        if self.testing:
            total = getattr(self, "class_lengths", None)
            n = sum(total) if total else self.output.shape[0]
            self._merged_output = numpy.zeros(
                (n,) + tuple(self.output.shape[1:]),
                dtype=self.output.dtype)

    def merge_output(self):
        """Testing mode: collect minibatch outputs into one array
        (reference evaluator.py:122-131)."""
        if self._merged_output is None:
            return
        bs = int(self.batch_size)
        off = int(self.offset)
        self.output.map_read()
        self._merged_output[off - bs:off] = self.output.mem[:bs]


class EvaluatorSoftmax(EvaluatorBase):
    """Softmax cross-entropy gradient + classification stats
    (reference evaluator.py:145-330)."""

    MAPPING = "evaluator_softmax"
    LOSS = "softmax"

    def __init__(self, workflow, **kwargs):
        super(EvaluatorSoftmax, self).__init__(workflow, **kwargs)
        self.compute_confusion_matrix = kwargs.get(
            "compute_confusion_matrix", True)
        self.confusion_matrix = Array(name="confusion_matrix")
        self.n_err = Array(name="n_err")
        self.max_err_output_sum = Array(name="max_err_output_sum")
        self.class_keys = None
        #: a unit exposing ``window_stats`` (the fused trainer in scan-
        #: window mode): when it carries stats for the just-run dispatch,
        #: accumulate those — the output buffer holds only the window's
        #: last minibatch, and the stats were computed evaluator-
        #: identically inside the compiled window (fused._eval_stats)
        self.stats_source = None
        self.demand("labels", "max_idx")
        #: segment-partial host accumulators ride snapshots so a
        #: MID-epoch resume (snapshotter window_interval) continues the
        #: fold exactly where the interrupted run left it — in async
        #: windowed mode these are zero mid-segment (the partials live
        #: in the trainer's device epoch_acc), in sync/per-minibatch
        #: mode they carry the segment so far
        self.exports = ["n_err", "confusion_matrix",
                        "max_err_output_sum"]

    def initialize(self, device=None, **kwargs):
        super(EvaluatorSoftmax, self).initialize(device=device, **kwargs)
        out_size = int(numpy.prod(self.output.shape[1:]))
        self.n_err.reset(numpy.zeros(2, dtype=numpy.int32))
        self.max_err_output_sum.reset(numpy.zeros(1, self.output.dtype))
        if self.compute_confusion_matrix:
            self.confusion_matrix.reset(numpy.zeros(
                (out_size, out_size), dtype=numpy.int32))
        else:
            self.confusion_matrix.reset()

    def _accumulate_stats(self, n_err_delta, conf_delta, max_err_sum):
        """Fold tiny per-minibatch stats into host accumulators.

        The err_output tensor itself stays wherever the compute ran —
        device-resident on the jax path (the GD chain reads ``.dev``; no
        D2H round-trip on the hot loop), host on the numpy path.
        """
        self.n_err.map_write()
        self.n_err.mem += numpy.asarray(n_err_delta)
        if self.confusion_matrix:
            self.confusion_matrix.map_write()
            self.confusion_matrix.mem += numpy.asarray(conf_delta)
        self.max_err_output_sum.map_write()
        self.max_err_output_sum.mem[0] = max(
            float(self.max_err_output_sum.mem[0]), float(max_err_sum))

    def _consume_window_stats(self):
        ws = getattr(self.stats_source, "window_stats", None) \
            if self.stats_source is not None else None
        if ws is None:
            return False
        if ws.get("deferred"):
            # asynchronous control plane: this mid-epoch window's
            # aggregates are riding the trainer's device-resident epoch
            # accumulators — the segment-final window delivers the whole
            # segment's totals in ONE batched readback, and THAT is when
            # the host fold below runs (bit-identical to per-window
            # folding: int adds and max are associative, and the device
            # fold replays the exact host op order)
            return True
        self._accumulate_stats(ws["n_err"], ws["confusion"],
                               ws["max_err_sum"])
        if self.testing:
            self.merge_output()
        return True

    def numpy_run(self):
        if self._consume_window_stats():
            return
        self.output.map_read()
        self.max_idx.map_read()
        self.labels.map_read()
        out2 = self.output.matrix
        err, n_err_delta, conf, mx = ev_ops.softmax_ce_numpy(
            out2, self.max_idx.mem, self.labels.mem,
            int(self.batch_size), out2.shape[1], mean=self.mean)
        self.err_output.map_invalidate()
        self.err_output.mem[...] = err.reshape(self.output.shape)
        self._accumulate_stats(n_err_delta, conf, mx)
        if self.testing:
            self.merge_output()

    def jax_run(self):
        if self._consume_window_stats():
            return
        out = self.output.dev
        out2 = out.reshape(out.shape[0], -1)
        err, n_err_delta, conf, mx = ev_ops.softmax_ce_jax(
            out2, self.max_idx.dev, self.labels.dev,
            int(self.batch_size), int(out2.shape[1]), mean=self.mean)
        self.err_output.set_dev(err.reshape(self.output.shape))
        # stats are tiny ((2,), (C,C), scalar); accumulate on host
        self._accumulate_stats(n_err_delta, conf, mx)
        if self.testing:
            self.merge_output()

    def get_metric_names(self):
        return {"n_err", "confusion"} if not self.testing else {"Output"}

    def get_metric_values(self):
        if self.testing and self._merged_output is not None:
            return {"Output": numpy.array(self._merged_output)}
        return {}


class EvaluatorTokens(EvaluatorBase):
    """Token objective: folds the fused trainer's counts (the loss and
    the errors are worked out on the device, where the logits are; no
    output comes back and there is no confusion matrix).  ``n_err`` is
    ``[errors, graded tokens, rows]`` of the class segment so far and
    ``loss_sum`` the graded tokens' summed loss; of a routed net
    ``expert_load`` holds the pairs every expert of every ``moe``
    application took in the segment's train windows; of a net with a head
    graded at a shift ``mtp`` is that head's ``[loss sum, errors, graded
    tokens]``."""

    MAPPING = "evaluator_tokens"
    LOSS = "tokens"

    def __init__(self, workflow, **kwargs):
        super(EvaluatorTokens, self).__init__(workflow, **kwargs)
        self.n_err = Array(name="n_err")
        self.loss_sum = Array(name="loss_sum")
        self.expert_load = Array(name="expert_load")
        self.mtp = Array(name="mtp")
        self.stats_source = None
        #: mid-epoch resume: see EvaluatorSoftmax.exports
        self.exports = ["n_err", "loss_sum"]

    def initialize(self, device=None, **kwargs):
        super(EvaluatorTokens, self).initialize(device=device, **kwargs)
        self.n_err.reset(numpy.zeros(3, dtype=numpy.int64))
        self.loss_sum.reset(numpy.zeros(1, dtype=numpy.float64))

    def run(self):
        ws = getattr(self.stats_source, "window_stats", None)
        if ws is None:
            raise RuntimeError("the tokens evaluator reads the fused "
                               "trainer's window stats only")
        if ws.get("deferred"):
            return      # riding the device accumulators (async windows)
        self.n_err.map_write()
        self.n_err.mem += numpy.asarray(ws["n_err"], dtype=numpy.int64)
        self.loss_sum.map_write()
        self.loss_sum.mem[0] += ws["loss_sum"]
        if "expert_load" in ws:
            if not self.expert_load:
                self.expert_load.reset(numpy.zeros_like(ws["expert_load"]))
            self.expert_load.map_write()
            self.expert_load.mem += ws["expert_load"]
        if "mtp" in ws:
            if not self.mtp:
                self.mtp.reset(numpy.zeros(3, dtype=numpy.float64))
            self.mtp.map_write()
            self.mtp.mem += ws["mtp"]

    def get_metric_names(self):
        return {"n_err", "loss"}


class EvaluatorMSE(EvaluatorBase):
    """MSE gradient + [sum,max,min] metrics + optional class-target
    nearest-neighbour error (reference evaluator.py:334-556)."""

    MAPPING = "evaluator_mse"
    LOSS = "mse"

    def __init__(self, workflow, **kwargs):
        super(EvaluatorMSE, self).__init__(workflow, **kwargs)
        self.metrics = Array(name="metrics")
        self.mse = Array(name="mse")
        self.n_err = Array(name="n_err")
        self.root = kwargs.get("root", True)
        self.squared_mse = kwargs.get("squared_mse", False)
        self.class_targets = None
        self.labels = None
        #: a unit exposing ``window_stats`` with "metrics" (the fused
        #: trainer in MSE scan-window mode) — same contract as the
        #: softmax evaluator's stats_source
        self.stats_source = None
        self.demand("target")
        #: mid-epoch resume: see EvaluatorSoftmax.exports
        self.exports = ["metrics", "mse", "n_err"]

    def initialize(self, device=None, **kwargs):
        super(EvaluatorMSE, self).initialize(device=device, **kwargs)
        if self.output.size != self.target.size or \
                self.output.shape[0] != self.target.shape[0]:
            # same batch + same per-sample size; sample RANK may differ
            # (e.g. a flat RBM reconstruction vs an image target)
            raise ValueError(
                "output shape %s and target shape %s are incompatible"
                % (self.output.shape, self.target.shape))
        self.metrics.reset(numpy.zeros(3, dtype=self.output.dtype))
        self.metrics.mem[2] = numpy.inf
        self.mse.reset(numpy.zeros(self.output.shape[0],
                                   dtype=self.output.dtype))
        self.n_err.reset(numpy.zeros(2, dtype=numpy.int32))

    def _accumulate_stats(self, metrics_delta, mse_per):
        self.metrics.map_write()
        md = numpy.asarray(metrics_delta)
        self.metrics.mem[0] += md[0]
        self.metrics.mem[1] = max(self.metrics.mem[1], md[1])
        self.metrics.mem[2] = min(self.metrics.mem[2], md[2])
        self.mse.map_invalidate()
        self.mse.mem[...] = numpy.asarray(mse_per)
        if (self.class_targets is not None and self.labels is not None):
            self._nn_class_error()

    def _nn_class_error(self):
        """Nearest class-target error (reference mse_find_closest kernel)."""
        self.class_targets.map_read()
        self.labels.map_read()
        self.output.map_read()
        ct = self.class_targets.matrix
        out = self.output.matrix
        n_ok = 0
        bs = int(self.batch_size)
        for i in range(bs):
            d = ((ct - out[i]) ** 2).sum(axis=1)
            if int(numpy.argmin(d)) == int(self.labels.mem[i]):
                n_ok += 1
        self.n_err.map_write()
        self.n_err.mem[0] += bs - n_ok
        self.n_err.mem[1] += bs

    def _consume_window_stats(self):
        """Fold a just-run MSE scan window's in-scan stats (trainer's
        fused._get_window_fn_mse — evaluator-identical [sum,max,min]
        metrics, last-step per-sample mse, optional class-target
        n_err) instead of recomputing from the (last-minibatch-only)
        output buffer."""
        ws = getattr(self.stats_source, "window_stats", None) \
            if self.stats_source is not None else None
        if ws is None:
            return False
        if ws.get("deferred"):
            # async control plane mid-epoch window: aggregates ride the
            # device accumulators until the segment-final readback (see
            # EvaluatorSoftmax._consume_window_stats)
            return True
        if "metrics" not in ws:
            return False
        md = numpy.asarray(ws["metrics"])
        self.metrics.map_write()
        self.metrics.mem[0] += md[0]
        self.metrics.mem[1] = max(self.metrics.mem[1], md[1])
        self.metrics.mem[2] = min(self.metrics.mem[2], md[2])
        if ws.get("mse_per") is not None:
            self.mse.map_invalidate()
            self.mse.mem[...] = numpy.asarray(ws["mse_per"])
        if (self.class_targets is not None and self.labels is not None
                and ws.get("n_err") is not None):
            self.n_err.map_write()
            self.n_err.mem += numpy.asarray(ws["n_err"])
        if self.testing:
            self.merge_output()
        return True

    def numpy_run(self):
        if self._consume_window_stats():
            return
        self.output.map_read()
        self.target.map_read()
        err, md, mse_per = ev_ops.mse_numpy(
            self.output.matrix, self.target.matrix, int(self.batch_size),
            mean=self.mean, root=self.root)
        self.err_output.map_invalidate()
        self.err_output.mem[...] = err.reshape(self.output.shape)
        self._accumulate_stats(md, mse_per)
        if self.testing:
            self.merge_output()

    def jax_run(self):
        if self._consume_window_stats():
            return
        err, md, mse_per = ev_ops.mse_jax(
            self.output.dev, self.target.dev, int(self.batch_size),
            mean=self.mean, root=self.root)
        self.err_output.set_dev(err)
        self._accumulate_stats(md, mse_per)
        if self.testing:
            self.merge_output()
