"""Decision units — own the training loop termination and bookkeeping.

TPU-era equivalent of reference decision.py (768 LoC — SURVEY.md §2.4).
DecisionGD tracks per-class epoch errors, best/minimax history, early
stopping (``fail_iterations``), builds the snapshot suffix
(``validation_1.92_train_0.04``), and gates the backward chain
(``gd_skip <<= minibatch_class != TRAIN``).
"""

import time

import numpy

from znicz_tpu.core.units import Unit
from znicz_tpu.core.mutable import Bool
from znicz_tpu.core.workflow import NoMoreJobs
from znicz_tpu.core import health
from znicz_tpu.core import telemetry
from znicz_tpu.loader.base import TEST, VALID, TRAIN, CLASS_NAME


def nvl(value, default):
    return default if value is None else value


def nmax(*values):
    """max of the non-None values; last arg is the fallback."""
    vals = [v for v in values[:-1] if v is not None]
    return max(vals) if vals else values[-1]


def pt_str(pt, percent_sign=True):
    if pt is None:
        return "None"
    return ("%.2f%%" % pt) if percent_sign else ("%.2f" % pt)


class DecisionsRegistry(type):
    """MAPPING registry (reference decision.py:71-80)."""

    decisions = {}

    def __init__(cls, name, bases, clsdict):
        super(DecisionsRegistry, cls).__init__(name, bases, clsdict)
        mapping = clsdict.get("MAPPING", None)
        if mapping:
            DecisionsRegistry.decisions[mapping] = cls


class IDecision(object):
    """Interface (reference decision.py:83-126)."""


class DecisionBase(Unit, IDecision, metaclass=DecisionsRegistry):
    """Epoch bookkeeping base (reference decision.py:131-291)."""

    def __init__(self, workflow, **kwargs):
        kwargs.setdefault("view_group", "TRAINER")
        super(DecisionBase, self).__init__(workflow, **kwargs)
        self.complete = Bool(False, name="complete")
        self.improved = Bool(False, name="improved")
        self.train_improved = Bool(False, name="train_improved")
        self.max_epochs = kwargs.get("max_epochs", None)
        self.improved_epoch_number = 0
        self.snapshot_suffix = ""
        self.testing = kwargs.get("testing", False)
        self._epoch_timestamp = None
        self.demand("last_minibatch", "minibatch_class", "class_lengths",
                    "epoch_number", "epoch_ended")

    def initialize(self, device=None, **kwargs):
        super(DecisionBase, self).initialize(device=device, **kwargs)
        if self.max_epochs is not None:
            self.info("Will allow max %d epochs", self.max_epochs)

    def run(self):
        if self._epoch_timestamp is None:
            self._epoch_timestamp = time.time()
        self.on_run()
        if self.is_slave:
            self.complete <<= True
            self.on_last_minibatch()
            self._print_statistics()
        elif self.last_minibatch:
            self._on_last_minibatch()

    def _on_last_minibatch(self):
        self.on_last_minibatch()
        if self.epoch_ended:
            self.train_improved <<= self.train_improve_condition()
            improved = self.improve_condition()
            if improved:
                self.improved_epoch_number = self.epoch_number
            self.improved <<= improved
            suffixes = []
            self.fill_snapshot_suffixes(suffixes)
            self.snapshot_suffix = "_".join(suffixes)
            self.complete <<= self._stop_condition()
            # flight-recorder milestone (no-op unless telemetry/health
            # is on): the last-N of these are what a crash report shows
            telemetry.record_event(
                "train.epoch", epoch=int(self.epoch_number),
                improved=bool(self.improved),
                suffix=self.snapshot_suffix)
        if self.minibatch_class == TRAIN:
            self.on_training_finished()
            if health.enabled():
                metric = self.health_metric()
                if metric is not None:
                    # per-epoch train metric feeds the rolling
                    # loss-divergence detector (EMA + window slope)
                    health.observe_loss(metric, unit=self,
                                        source="epoch_train")
        self._print_statistics()

    def _stop_condition(self):
        if self.testing:
            return True
        return self.stop_condition() or (
            self.max_epochs is not None and
            self.epoch_number >= self.max_epochs)

    def _print_statistics(self):
        stats = []
        self.fill_statistics(stats)
        now = time.time()
        self.info("Epoch %d class %s %s in %.2f sec",
                  self.epoch_number, CLASS_NAME[self.minibatch_class],
                  " ".join(stats), now - self._epoch_timestamp)
        self._epoch_timestamp = now

    # -- subclass hooks ------------------------------------------------------
    def on_run(self):
        pass

    def on_last_minibatch(self):
        pass

    def improve_condition(self):
        return False

    def train_improve_condition(self):
        return False

    def stop_condition(self):
        return False

    def on_training_finished(self):
        pass

    def fill_statistics(self, stats):
        pass

    def fill_snapshot_suffixes(self, suffixes):
        pass

    def health_metric(self):
        """Scalar the divergence detector watches, one per TRAIN-epoch
        end (subclass hook; None = nothing to observe)."""
        return None

    # -- master-slave protocol (reference decision.py:213-241) --------------
    def generate_data_for_slave(self, slave=None):
        if self.complete:
            raise NoMoreJobs()
        data = {}
        self.on_generate_data_for_slave(data)
        return data

    def generate_data_for_master(self):
        data = {}
        self.on_generate_data_for_master(data)
        return data

    def apply_data_from_master(self, data):
        self.complete <<= False
        self.on_apply_data_from_master(data)

    def apply_data_from_slave(self, data, slave=None):
        self.on_apply_data_from_slave(data, slave)
        if self.last_minibatch:
            self._on_last_minibatch()

    def on_generate_data_for_slave(self, data):
        pass

    def on_generate_data_for_master(self, data):
        pass

    def on_apply_data_from_master(self, data):
        pass

    def on_apply_data_from_slave(self, data, slave):
        pass


class TrivialDecision(DecisionBase):
    """No-op decision (reference decision.py:295)."""


class DecisionGD(DecisionBase):
    """Classification decision (reference decision.py:334-585)."""

    MAPPING = "decision_gd"
    LOSS = "softmax"
    BIGNUM = 1.0e30

    def __init__(self, workflow, **kwargs):
        super(DecisionGD, self).__init__(workflow, **kwargs)
        self.fail_iterations = kwargs.get("fail_iterations", 100)
        self.gd_skip = Bool(False, name="gd_skip")
        self.epoch_n_err = [None] * 3
        self.epoch_n_evaluated_samples = [0] * 3
        self.epoch_n_err_pt = [None] * 3
        self.best_n_err_pt = [None] * 3
        self.best_n_err_pt_epoch_number = [None] * 3
        self.best_minimax_n_err_pt = [None] * 3
        self.best_minimax_n_err_pt_epoch_number = -1
        self.minibatch_n_err = None          # linked from evaluator
        self.minibatch_confusion_matrix = None
        self.minibatch_max_err_y_sum = None
        self.confusion_matrixes = [None] * 3
        self.max_err_y_sums = [0] * 3
        self.autoencoder = False
        self.exports = ["epoch_n_err", "epoch_n_err_pt", "best_n_err_pt",
                        "snapshot_suffix", "improved_epoch_number",
                        # the FULL bookkeeping rides along so a
                        # mid-epoch resume replays improve/stop
                        # decisions exactly (fault-tolerant training,
                        # docs/deployment.md)
                        "epoch_n_evaluated_samples",
                        "best_n_err_pt_epoch_number",
                        "best_minimax_n_err_pt",
                        "best_minimax_n_err_pt_epoch_number",
                        "confusion_matrixes", "max_err_y_sums"]
        self.demand("minibatch_size")

    def on_run(self):
        self.gd_skip <<= (self.minibatch_class != TRAIN)

    def on_last_minibatch(self):
        clazz = self.minibatch_class
        if self.minibatch_confusion_matrix is not None and \
                self.minibatch_confusion_matrix:
            self.minibatch_confusion_matrix.map_read()
            self.confusion_matrixes[clazz] = numpy.array(
                self.minibatch_confusion_matrix.mem)
        if self.minibatch_n_err:
            self.minibatch_n_err.map_read()
            self.epoch_n_err[clazz] = int(self.minibatch_n_err[0])
            self.epoch_n_evaluated_samples[clazz] = int(
                self.minibatch_n_err[1])
            if self.epoch_n_evaluated_samples[clazz]:
                self.epoch_n_err_pt[clazz] = (
                    100.0 * self.epoch_n_err[clazz] /
                    self.epoch_n_evaluated_samples[clazz])
                if (self.epoch_n_err_pt[clazz] <
                        nvl(self.best_n_err_pt[clazz], self.BIGNUM)):
                    self.best_n_err_pt[clazz] = self.epoch_n_err_pt[clazz]
                    self.best_n_err_pt_epoch_number[clazz] = \
                        self.epoch_number
        if self.minibatch_max_err_y_sum is not None and \
                self.minibatch_max_err_y_sum:
            self.minibatch_max_err_y_sum.map_read()
            self.max_err_y_sums[clazz] = float(
                self.minibatch_max_err_y_sum[0])

    def improve_condition(self):
        """Minimax(valid, train) improvement — called at epoch end where
        minibatch_class is VALID when validation exists
        (reference decision.py:478-497)."""
        clazz = self.minibatch_class
        if (nmax(self.epoch_n_err_pt[clazz], self.epoch_n_err_pt[TRAIN],
                 self.BIGNUM) <
                nmax(self.best_minimax_n_err_pt[clazz],
                     self.best_minimax_n_err_pt[TRAIN], self.BIGNUM)):
            for i in (clazz, TRAIN, TEST):
                self.best_minimax_n_err_pt[i] = self.epoch_n_err_pt[i]
            self.best_minimax_n_err_pt_epoch_number = self.epoch_number
            return True
        return False

    def train_improve_condition(self):
        if (nvl(self.epoch_n_err_pt[TRAIN], self.BIGNUM) <
                nvl(self.best_n_err_pt[TRAIN], self.BIGNUM)):
            self.best_n_err_pt[TRAIN] = self.epoch_n_err_pt[TRAIN]
            self.best_n_err_pt_epoch_number[TRAIN] = self.epoch_number
            return True
        return False

    def stop_condition(self):
        if all(nvl(self.best_minimax_n_err_pt[i], 0) <= 0
               for i in (VALID, TRAIN)):
            return True
        if (self.epoch_number - self.improved_epoch_number >
                self.fail_iterations):
            return True
        return False

    def fill_statistics(self, stats):
        clazz = self.minibatch_class
        if self.minibatch_n_err is not None and not self.autoencoder and \
                self.epoch_n_err[clazz] is not None:
            stats.append("n_err %d of %d (%.2f%%)" % (
                self.epoch_n_err[clazz],
                self.epoch_n_evaluated_samples[clazz],
                nvl(self.epoch_n_err_pt[clazz], 0.0)))
        if not self.is_slave:
            self.reset_statistics()

    def fill_snapshot_suffixes(self, suffixes):
        for clazz in (TEST, VALID, TRAIN):
            if self.epoch_n_err_pt[clazz] is not None:
                suffixes.append("%s_%s" % (
                    CLASS_NAME[clazz],
                    pt_str(self.epoch_n_err_pt[clazz], False)))

    def health_metric(self):
        return self.epoch_n_err_pt[TRAIN]

    def reset_statistics(self):
        for vec in (self.minibatch_n_err, self.minibatch_max_err_y_sum,
                    self.minibatch_confusion_matrix):
            if vec is None or not vec:
                continue
            vec.map_invalidate()
            vec.mem[:] = 0

    # -- metrics (reference decision.py:401-437) ----------------------------
    def get_metric_names(self):
        if not self.testing:
            return {"Min errors", "Accuracy", "EvaluationFitness",
                    "Best epoch"}
        return set()

    def get_metric_values(self):
        if self.testing:
            return {}
        t, v = CLASS_NAME[TRAIN], CLASS_NAME[VALID]
        return {
            "Min errors": {t: pt_str(self.best_n_err_pt[TRAIN]),
                           v: pt_str(self.best_n_err_pt[VALID])},
            "EvaluationFitness": 1 - nvl(self.best_n_err_pt[VALID],
                                         100.0) / 100.0,
            "Best epoch": {
                t: nvl(self.best_n_err_pt_epoch_number[TRAIN], "None"),
                v: nvl(self.best_n_err_pt_epoch_number[VALID], "None")},
        }

    # -- master-slave aggregation (reference decision.py:511-544) -----------
    def on_generate_data_for_master(self, data):
        for attr in ("minibatch_n_err", "minibatch_max_err_y_sum",
                     "minibatch_confusion_matrix"):
            vec = getattr(self, attr)
            if vec is not None and vec:
                data[attr] = numpy.array(vec.mem)

    def on_generate_data_for_slave(self, data):
        data["improved"] = bool(self.improved)

    def on_apply_data_from_master(self, data):
        self.improved <<= data["improved"]
        self.reset_statistics()

    def on_apply_data_from_slave(self, data, slave):
        if self.minibatch_n_err and "minibatch_n_err" in data:
            self.minibatch_n_err.map_write()
            self.minibatch_n_err.mem += data["minibatch_n_err"]
        if self.minibatch_max_err_y_sum is not None and \
                self.minibatch_max_err_y_sum and \
                "minibatch_max_err_y_sum" in data:
            self.minibatch_max_err_y_sum.map_write()
            numpy.maximum(self.minibatch_max_err_y_sum.mem,
                          data["minibatch_max_err_y_sum"],
                          out=self.minibatch_max_err_y_sum.mem)
        if self.minibatch_confusion_matrix is not None and \
                self.minibatch_confusion_matrix and \
                "minibatch_confusion_matrix" in data:
            self.minibatch_confusion_matrix.map_write()
            self.minibatch_confusion_matrix.mem += data[
                "minibatch_confusion_matrix"]


class DecisionTokens(DecisionGD):
    """Decision of the token objective: errors and loss per graded token
    and class of minibatch; ``epoch_n_evaluated_samples`` counts graded
    tokens, ``epoch_rows`` the rows they stood in.  No confusion
    matrix.  Of a routed net ``epoch_expert_load`` is the train epoch's
    pairs by ``moe`` application and expert."""

    MAPPING = "decision_tokens"
    LOSS = "tokens"

    def __init__(self, workflow, **kwargs):
        super(DecisionTokens, self).__init__(workflow, **kwargs)
        self.epoch_loss = [None] * 3
        self.epoch_rows = [0] * 3
        self.epoch_expert_load = None
        self.minibatch_loss_sum = None  # linked from evaluator
        self.minibatch_expert_load = None   # linked from evaluator
        self.demand("minibatch_loss_sum")
        self.exports = list(self.exports) + ["epoch_loss", "epoch_rows"]

    def on_last_minibatch(self):
        super(DecisionTokens, self).on_last_minibatch()
        clazz = self.minibatch_class
        self.minibatch_loss_sum.map_read()
        self.epoch_rows[clazz] = int(self.minibatch_n_err[2])
        graded = self.epoch_n_evaluated_samples[clazz]
        if graded:
            self.epoch_loss[clazz] = \
                float(self.minibatch_loss_sum[0]) / graded
        if clazz == TRAIN and self.minibatch_expert_load:
            self.minibatch_expert_load.map_read()
            self.epoch_expert_load = numpy.array(
                self.minibatch_expert_load.mem)

    def fill_statistics(self, stats):
        clazz = self.minibatch_class
        if self.epoch_loss[clazz] is not None:
            stats.append("loss %.6f a token, %d rows"
                         % (self.epoch_loss[clazz], self.epoch_rows[clazz]))
        load = self.epoch_expert_load
        if clazz == TRAIN and load is not None and load.any():
            stats.append("experts' load max/mean %.2f"
                         % (load.max() / load.mean()))
        super(DecisionTokens, self).fill_statistics(stats)

    def health_metric(self):
        return self.epoch_loss[TRAIN]

    def reset_statistics(self):
        super(DecisionTokens, self).reset_statistics()
        if self.minibatch_loss_sum is not None and self.minibatch_loss_sum:
            self.minibatch_loss_sum.map_invalidate()
            self.minibatch_loss_sum.mem[:] = 0
        if self.minibatch_expert_load:
            self.minibatch_expert_load.map_invalidate()
            self.minibatch_expert_load.mem[:] = 0


class DecisionMSE(DecisionGD):
    """Regression decision tracking epoch MSE metrics
    (reference decision.py:587-768)."""

    MAPPING = "decision_mse"
    LOSS = "mse"

    def __init__(self, workflow, **kwargs):
        super(DecisionMSE, self).__init__(workflow, **kwargs)
        self.epoch_metrics = [None] * 3
        self.best_metrics = [None] * 3
        self.minibatch_metrics = None  # linked from evaluator ("metrics")
        self.demand("minibatch_metrics")
        self.exports = list(self.exports) + ["epoch_metrics",
                                             "best_metrics"]

    def on_last_minibatch(self):
        super(DecisionMSE, self).on_last_minibatch()
        clazz = self.minibatch_class
        if self.minibatch_metrics is not None and self.minibatch_metrics:
            self.minibatch_metrics.map_read()
            n = max(self.class_lengths[clazz], 1)
            self.epoch_metrics[clazz] = (
                float(self.minibatch_metrics[0]) / n,
                float(self.minibatch_metrics[1]),
                float(self.minibatch_metrics[2]))

    def improve_condition(self):
        clazz = self.minibatch_class
        cur = self.epoch_metrics[clazz]
        if cur is None:
            return False
        if self.best_metrics[clazz] is None or \
                cur[0] < self.best_metrics[clazz][0]:
            self.best_metrics[clazz] = cur
            return True
        return False

    def stop_condition(self):
        return (self.epoch_number - self.improved_epoch_number >
                self.fail_iterations)

    def fill_statistics(self, stats):
        clazz = self.minibatch_class
        if self.epoch_metrics[clazz] is not None:
            stats.append("avg_mse %.6f max %.6f min %.6f" %
                         self.epoch_metrics[clazz])
        super(DecisionMSE, self).fill_statistics(stats)

    def fill_snapshot_suffixes(self, suffixes):
        for clazz in (TEST, VALID, TRAIN):
            if self.epoch_metrics[clazz] is not None:
                suffixes.append("%s_%.6f" % (CLASS_NAME[clazz],
                                             self.epoch_metrics[clazz][0]))

    def health_metric(self):
        m = self.epoch_metrics[TRAIN]
        return m[0] if m is not None else None

    def reset_statistics(self):
        super(DecisionMSE, self).reset_statistics()
        if self.minibatch_metrics is not None and self.minibatch_metrics:
            self.minibatch_metrics.map_invalidate()
            self.minibatch_metrics.mem[:] = 0
            self.minibatch_metrics.mem[2] = numpy.inf
