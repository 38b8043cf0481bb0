"""Fused-mode training unit — the SPMD hot loop inside the unit graph.

SURVEY.md §7 design stance made literal: the unit graph stays the
*epoch-level control plane* (loader -> train step -> evaluator stats ->
decision -> snapshotter -> lr_adjuster/rollback), while the per-minibatch
forward + backward + update collapses into ONE jitted XLA computation
(:class:`znicz_tpu.parallel.fused.FusedNet`), optionally sharded over a
``(data, model)`` device mesh.

:class:`FusedForwardBackward` replaces the whole forwards[0..n] +
gds[n..0] chain of the reference graph (standard_workflow.py:173-208).
On TRAIN minibatches it runs the fused train step with the CURRENT
hyperparameters (traced arguments — LR schedules apply per iteration with
no recompile, reference lr_adjust.py:61); on VALID/TEST minibatches it
runs the compiled inference forward.  Either way it exposes ``output`` and
``max_idx`` exactly like the last forward unit would, so the evaluator,
decision, snapshotter and plotter units keep their reference roles
unchanged.

:class:`GDProxy` stands in for one GD unit's hyperparameter surface
(learning_rate, weights_decay, ... — reference nn_units.py:339-441) so
``LearningRateAdjust`` and rollback mutate fused-layer hyperparameters
through the same attribute contract they use on real GD units.

:class:`FusedNNRollback` is the divergence-recovery twin of
``NNRollback`` (reference nn_rollback.py:44-190) for the fused path:
whole-net state snapshots instead of per-GD-unit weight histories.
"""

import collections
import time

import numpy

import jax

from znicz_tpu.core.units import Unit
from znicz_tpu.core.memory import Array
from znicz_tpu.core.mutable import Bool
from znicz_tpu.core.config import root
from znicz_tpu.core import faults
from znicz_tpu.core import health
from znicz_tpu.core import prng
from znicz_tpu.core import telemetry
from znicz_tpu.loader.base import TEST, TRAIN, VALID
from znicz_tpu.parallel import fused
from znicz_tpu.ops import gd_math, transformer


#: sentinel ``window_stats`` value for mid-epoch windows under the
#: asynchronous control plane: "this window's decision aggregates are
#: riding the device-resident epoch accumulators — nothing to fold on
#: the host until the segment-final batched readback".  The evaluator
#: treats it as consumed (units/evaluator.py _consume_window_stats).
DEFERRED_WINDOW_STATS = {"deferred": True}


class _StagingRing(object):
    """Rotating preallocated host staging buffers for window assembly.

    ``depth`` independent buffer sets per key rotate round-robin: under
    the pipelined dispatch the PREVIOUS window may still be consuming
    its staging rows (``jax.device_put`` can alias aligned host memory
    on the CPU backend), so a buffer set is only reused once its window
    is at least ``depth`` dispatches old — the trainer bounds in-flight
    windows at ``pipeline_depth = depth - 1``.  One copy per collected
    minibatch lands straight in its (K, B, ...) row; the dispatch hands
    the leading-axis view over with no ``numpy.stack`` re-copy."""

    def __init__(self, depth):
        self.depth = max(1, int(depth))
        self._slots = {}   # key -> [[buffers...], next_turn]

    def get(self, key, shape, dtype, shards=1):
        """The next staging buffer for ``key`` (allocated on first use
        or when the window geometry changed).

        ``shards > 1`` (a data-parallel mesh): the logical
        ``(K, B, ...)`` window is allocated SHARD-MAJOR as
        ``(shards, K, B // shards, ...)`` so each data shard's rows are
        one contiguous host block — ``FusedNet._place_window`` feeds
        ``device_put`` per-shard memcpys instead of strided splits.
        The trainer writes minibatch ``i`` through the ``base[:, i]``
        view (``Loader.fill_window_slot`` reshapes its source to the
        destination layout)."""
        shape = tuple(int(s) for s in shape)
        if shards > 1:
            k, b = shape[0], shape[1]
            if b % shards:
                raise ValueError(
                    "window batch %d not divisible by %d data shards"
                    % (b, shards))
            shape = (shards, k, b // shards) + shape[2:]
        slot = self._slots.get(key)
        if slot is None or slot[0][0].shape != shape or \
                slot[0][0].dtype != numpy.dtype(dtype):
            slot = [[numpy.zeros(shape, dtype)
                     for _ in range(self.depth)], 0]
            self._slots[key] = slot
        bufs, turn = slot
        slot[1] = (turn + 1) % self.depth
        return bufs[turn]


class GDProxy(object):
    """Hyperparameter proxy for one fused layer — the attribute surface
    of a GD unit (reference nn_units.py:339-441) without the compute."""

    #: scalar attributes persisted in snapshots (so rollback/schedule
    #: mutations survive resume)
    STATE_ATTRS = ("learning_rate", "learning_rate_bias",
                   "weights_decay", "weights_decay_bias",
                   "l1_vs_l2", "l1_vs_l2_bias",
                   "gradient_moment", "gradient_moment_bias",
                   "factor_ortho", "acc_alpha", "acc_beta",
                   "gd_alpha", "gd_beta")

    def __init__(self, name, hyper, hyper_bias):
        #: bumped by every STATE_ATTRS assignment (schedules, rollback,
        #: state restore) — the trainer's hyper-collection cache key:
        #: unchanged serials mean the per-step hyper pytree (and its
        #: stacked window form) can be reused instead of rebuilt per
        #: minibatch
        self.serial = 0
        self.name = name
        self.gate_skip = Bool(False)
        self.learning_rate = hyper["lr"]
        self.learning_rate_bias = hyper_bias["lr"]
        self.weights_decay = hyper["wd"]
        self.weights_decay_bias = hyper_bias["wd"]
        self.l1_vs_l2 = hyper["l1_vs_l2"]
        self.l1_vs_l2_bias = hyper_bias["l1_vs_l2"]
        self.gradient_moment = hyper["moment"]
        self.gradient_moment_bias = hyper_bias["moment"]
        self.factor_ortho = hyper["factor_ortho"]
        self.acc_alpha = hyper["acc_alpha"]
        self.acc_beta = hyper["acc_beta"]
        self.gd_alpha = hyper["gd_alpha"]
        self.gd_beta = hyper["gd_beta"]
        #: a solver's own hyperparameters (AdamW's betas and epsilon),
        #: where the layer asks for that solver: fed as the layer states
        #: them, beside the scheduled ones
        self.solver_hyper = {k: float(hyper[k])
                             for k in gd_math.ADAMW_HYPER if k in hyper}

    def __setattr__(self, name, value):
        if name in self.STATE_ATTRS:
            # a hyper MUTATION (schedule tick, rollback, restore)
            # invalidates the trainer's collected-hypers cache.  Value
            # compare, not assignment count: LR adjusters re-assign the
            # same value every train minibatch (lr_adjust.run), which
            # must not defeat the cache.
            if getattr(self, name, None) != value:
                object.__setattr__(self, "serial",
                                   getattr(self, "serial", 0) + 1)
        object.__setattr__(self, name, value)

    def hyper_dicts(self):
        """(hyper, hyper_bias) in gd_math.update vocabulary — rebuilt from
        the live attribute values every step."""
        common = dict(self.solver_hyper,
                      acc_alpha=self.acc_alpha, acc_beta=self.acc_beta,
                      gd_alpha=self.gd_alpha, gd_beta=self.gd_beta)
        hyper = dict(common, lr=float(self.learning_rate),
                     wd=float(self.weights_decay),
                     l1_vs_l2=float(self.l1_vs_l2),
                     moment=float(self.gradient_moment),
                     factor_ortho=float(self.factor_ortho))
        hyper_bias = dict(common, lr=float(self.learning_rate_bias),
                          wd=float(self.weights_decay_bias),
                          l1_vs_l2=float(self.l1_vs_l2_bias),
                          moment=float(self.gradient_moment_bias),
                          factor_ortho=0.0)
        return hyper, hyper_bias

    def state_dict(self):
        return {a: float(getattr(self, a)) for a in self.STATE_ATTRS}

    def load_state_dict(self, sd):
        for a, v in sd.items():
            if a in self.STATE_ATTRS:
                setattr(self, a, v)


class FusedForwardBackward(Unit):
    """One unit = the whole compiled train/eval step over the layer stack.

    Demands ``input``/``labels``/``minibatch_class``/``minibatch_size``
    from the loader; provides ``output``/``max_idx`` like the last forward
    unit of the reference graph, so downstream evaluator/decision/plotters
    are unchanged.
    """

    def __init__(self, workflow, **kwargs):
        kwargs.setdefault("view_group", "WORKER")
        super(FusedForwardBackward, self).__init__(workflow, **kwargs)
        import copy
        self.layers = copy.deepcopy(list(kwargs["layers"]))
        self.mesh = kwargs.get("mesh")
        self.dtype = kwargs.get("dtype")
        self.compute_dtype = kwargs.get("compute_dtype")
        self.defaults = kwargs.get("defaults")
        self.dropout_seed = kwargs.get("dropout_seed", 0)
        #: max-pool lowering: None or "reduce_window" (the default:
        #: select-and-scatter VJP, what every benchmark cell runs) or
        #: "gather" (unit-path summation-order parity, the tests'
        #: reference) — see fused.PoolSpec.impl
        self.pool_impl = kwargs.get("pool_impl")
        self.rand = kwargs.get("rand", prng.get())
        self.output = Array(name="output")
        self.max_idx = Array(name="max_idx")
        #: training objective: "softmax" (CE + argmax stats), "mse", or
        #: "tokens" (a label at every position; counts of graded tokens)
        self.loss = kwargs.get("loss", "softmax")
        #: TRAIN minibatches batched per compiled dispatch: the unit
        #: collects up to ``window`` minibatches from the loader and runs
        #: them as ONE ``lax.scan`` window (FusedNet.run_window) — no
        #: per-minibatch dispatch or host readback inside the window.
        #: window=1 keeps the per-minibatch step (the executable spec the
        #: window path is pinned against).  The DEFAULT is adaptive:
        #: windows engage (8) when the loader qualifies for the device-
        #: resident dataset path, else stay per-minibatch — an explicit
        #: ``window=K`` forces K either way.  MSE topologies window too:
        #: in-scan evaluator-identical [sum,max,min] mse metrics +
        #: optional nearest-class-target n_err, sliced or host-stacked.
        self.window = kwargs.get("window")
        if self.window is not None:
            self.window = int(self.window)
        #: "auto" places a qualifying FullBatchLoader's dataset on device
        #: once and gathers minibatches INSIDE the compiled window (only
        #: the index arrays cross the host boundary); False forces the
        #: host-stacked path; True fails loudly if the loader does not
        #: qualify
        self.device_data = kwargs.get("device_data", "auto")
        if "device_perm" in kwargs:
            raise ValueError(
                "fused option device_perm is gone: the code chooses the "
                "resident window's form (softmax and tokens gather rows "
                "by index inside the window, MSE reads contiguous "
                "slices of the epoch's shuffled copy)")
        #: asynchronous control plane (windowed mode): mid-epoch windows
        #: issue ZERO synchronous d2h transfers — the decision aggregates
        #: ride device-resident epoch accumulators (fused.FusedNet
        #: window_acc) and the host fetches ONE batched transfer per
        #: segment (accumulators + segment-final output/argmax), so the
        #: trainer collects and dispatches window K+1 while window K is
        #: still in flight.  False restores the synchronous per-window
        #: readback — the equivalence pin's reference mode.
        self.async_windows = bool(kwargs.get("async_windows", True))
        #: bound on dispatched-but-unfinished windows before collection
        #: blocks on the oldest (a completion WAIT, not a transfer):
        #: caps live input buffers under donation and gates the staging
        #: ring's reuse
        self.pipeline_depth = int(kwargs.get("pipeline_depth", 2))
        #: in-flight window tokens (one tiny device array per dispatched
        #: mid-epoch window, oldest first)
        self._inflight = collections.deque()
        self._staging = _StagingRing(self.pipeline_depth + 1)
        #: hyper-collection cache (GDProxy.serial keyed): the per-step
        #: hyper pytree and its stacked (K-leading-axis) window form are
        #: rebuilt ONLY when a proxy attribute actually changed — with
        #: no schedule running this removes the per-minibatch dict
        #: rebuild + per-window restack from the host path entirely
        self._hyper_serials = None
        self._hyper_cache = None
        self._hyper_stacked = {}
        #: the loader unit driven directly during window collection
        #: (wired by StandardWorkflow.link_fused_trainer)
        self.loader_unit = None
        #: optional callable fired after each collected minibatch —
        #: link_lr_adjuster points it at the adjuster's run so LR
        #: policies advance per MINIBATCH, not per window
        self.hyper_tick = None
        #: aggregated stats of the last dispatched window (n_err[2],
        #: confusion, max_err_sum) — the evaluator accumulates these
        #: instead of recomputing from the (last-step-only) output
        self.window_stats = None
        #: evaluator ``mean`` flag mirror (link_evaluator sets it)
        self.stats_mean = True
        #: EvaluatorMSE ``root`` flag mirror (per-sample sqrt in the
        #: windowed mse metrics; link_evaluator sets it)
        self.stats_root = True
        self.net = None
        self.forward_mode = False
        #: loader whose label count / target shape sets the head width
        #: (wired by StandardWorkflow.link_fused_trainer;
        #: link_forwards parity)
        self.label_source = None
        self._pending_state = None
        self.gd_proxies = []
        # a tied deconv's "<-" governs the SHARED weights' update — its
        # hyper seeds the tied conv's proxy (build_specs applies the
        # same override to the spec)
        overrides = {}
        # proxies, views and specs go by the leaves of the layer list
        leaf_layers, _ = fused.flatten_layers(self.layers)
        for i, layer in enumerate(leaf_layers):
            if layer.get("type") == "deconv" and layer.get("<-"):
                tied = layer.get("->", {}).get("tied_to")
                if tied is not None:
                    overrides[tied] = layer
        #: device-backed per-layer weight views for the plotter tier
        #: (Weights2D & friends read ``weights`` Arrays); empty until
        #: initialize, re-pointed at the current params after every
        #: train step and state restore
        self.weight_views = []
        for i, layer in enumerate(leaf_layers):
            tpe = layer.get("type")
            if tpe in fused.FC_TYPES or tpe in fused.CONV_TYPES \
                    or tpe in transformer.KINDS:
                name = layer.get("name", "%s_%d" % (tpe, i))
                hyper, hyper_bias, _ = fused.layer_hyper(
                    overrides.get(name, layer), self.defaults)
                self.gd_proxies.append(GDProxy("gd_" + name, hyper,
                                               hyper_bias))
                if tpe not in transformer.KINDS:
                    self.weight_views.append(
                        (i, Array(name=name + "_weights")))
        self.demand("input", "minibatch_class", "minibatch_size")
        if self.loss == "mse":
            self.demand("target")
        else:
            self.demand("labels")
        self._pending_acc = None
        #: the ``window`` the spans of one unit of work share: a running
        #: count of dispatched train windows and validation minibatches
        #: (advanced only while telemetry is on)
        self._span_serial = 0
        #: snapshot payload: params + optimizer state + dropout key +
        #: live hyperparameters (bit-exact fused resume), plus the
        #: device-resident epoch accumulators drained to host — the
        #: piece that makes MID-epoch snapshots resumable with
        #: aggregates exactly equal to an uninterrupted run
        self.exports = ["fused_state", "epoch_acc"]

    # -- head-width parity with link_forwards --------------------------------
    def _fix_head_width(self):
        last = self.layers[-1]
        if self.label_source is None or self.loss == "tokens":
            return
        if self.loss == "mse":
            # last FC width from the loader's target sample shape
            # (reference standard_workflow_base.py:324-334, MSE path)
            if last.get("type") not in fused.FC_TYPES:
                return
            tshape = getattr(self.label_source, "targets_shape", None)
            if not tshape:
                return
            fwd = last.setdefault("->", {})
            oss = fwd.get("output_sample_shape")
            if oss is not None and \
                    int(numpy.prod(oss)) != int(numpy.prod(tshape)):
                self.warning("Overriding output_sample_shape %s with %s "
                             "(loader targets)", oss, tshape)
                fwd["output_sample_shape"] = tuple(tshape)
            elif oss is None:
                fwd["output_sample_shape"] = tuple(tshape)
            return
        if last.get("type") != "softmax":
            return
        try:
            ulc = int(self.label_source.unique_labels_count)
        except (AttributeError, TypeError):
            return
        if not ulc:
            return
        fwd = last.setdefault("->", {})
        oss = fwd.get("output_sample_shape")
        if oss is not None and int(numpy.prod(oss)) != ulc:
            self.warning("Overriding softmax output_sample_shape %s "
                         "with (%d,)", oss, ulc)
        fwd["output_sample_shape"] = ulc

    def initialize(self, device=None, **kwargs):
        super(FusedForwardBackward, self).initialize(device=device, **kwargs)
        if self.net is not None:
            return
        self._fix_head_width()
        dtype = self.dtype
        if dtype is None:
            dtype = root.common.engine.get("precision_dtype")
        if dtype is None:
            dtype = numpy.float32
        sample_shape = tuple(self.input.shape[1:])
        self.net = fused.FusedNet(
            self.layers, input_sample_shape=sample_shape, mesh=self.mesh,
            rand=self.rand, dtype=dtype, defaults=self.defaults,
            dropout_seed=self.dropout_seed,
            compute_dtype=self.compute_dtype, objective=self.loss,
            pool_impl=self.pool_impl)
        self.net.stats_mean = self.stats_mean
        if self.loss == "mse":
            self.net.mse_root = bool(self.stats_root)
            # nearest-class-target metric rides the scan when the
            # loader provides class targets (kanji-style MSE
            # classification; evaluator host loop semantics)
            ct = getattr(self.loader_unit, "class_targets", None)
            if ct is not None and ct:
                mem = numpy.asarray(ct.mem)
                self.net.class_targets = mem.reshape(mem.shape[0], -1)
        self._setup_device_data()
        if self._use_device_data:
            self._place_dataset()
            if not self._use_sliced:
                self._prepare_programs()
        self._refresh_weight_views()
        if telemetry.enabled() and self.net.mesh is not None:
            # mesh-aware observability: every counter the async control
            # plane exports (readbacks, inflight, d2h bytes) can be read
            # per shard against these gauges (telemetry.summary())
            telemetry.gauge("trainer.data_shards").set(
                self.net.data_shards)
            telemetry.gauge("trainer.model_shards").set(
                int(self.net.mesh.shape["model"]))
        batch = int(self.input.shape[0])
        out_shape = (batch,) + tuple(self.net.specs[-1].out_shape)
        if self.loss == "tokens":
            # no output comes back (a pass's logits are gigabytes): the
            # counts go to the evaluator as window stats
            out_shape = (batch, 1)
            if not self._use_device_data:
                raise ValueError(
                    "the tokens objective trains from the resident data "
                    "set: a stock TokenRowsLoader and fused window > 1")
        self.output.reset(numpy.zeros(out_shape, dtype=dtype))
        if self.loss != "mse":
            self.max_idx.reset(numpy.zeros(batch, dtype=numpy.int32))
        if self._pending_state is not None:
            self._apply_state(self._pending_state)
            self._pending_state = None
        if self._pending_acc is not None:
            self.net.set_window_acc(self._pending_acc)
            self._pending_acc = None

    # -- device-resident dataset (windowed TPU-first data path) -------------
    def _loader_qualifies_for_device_data(self):
        """The loader's fill is the stock FullBatchLoader fancy-index copy
        (no per-sample transform override) — a device gather from the
        normalized dataset produces identical rows.  MSE additionally
        needs the stock MSE-mixin fill and original_targets (labels are
        optional — only the nearest-class-target metric consumes them)."""
        from znicz_tpu.loader.base import (FullBatchLoader,
                                           FullBatchLoaderMSEMixin)
        lu = self.loader_unit
        if not (isinstance(lu, FullBatchLoader) and lu.original_data):
            return False
        if self.loss == "mse":
            # BOTH fills must be stock: the mixin's targets fill AND
            # the underlying data fill its super() call reaches — a
            # custom base with a per-minibatch transform would satisfy
            # the mixin check alone while the device path served raw
            # rows
            if not (isinstance(lu, FullBatchLoaderMSEMixin)
                    and type(lu).fill_minibatch
                    is FullBatchLoaderMSEMixin.fill_minibatch
                    and bool(lu.original_targets)):
                return False
            mro = type(lu).__mro__
            after_mixin = mro[mro.index(FullBatchLoaderMSEMixin) + 1:]
            for klass in after_mixin:
                fill = klass.__dict__.get("fill_minibatch")
                if fill is not None:
                    return fill is FullBatchLoader.__dict__[
                        "fill_minibatch"]
            return False
        if self.loss == "tokens" and (
                getattr(lu, "token_labels", None) is None
                or getattr(lu, "token_segments", None) is None):
            return False
        return (type(lu).fill_minibatch is FullBatchLoader.fill_minibatch
                and len(lu.original_labels) > 0)

    def _place_dataset(self):
        """The loader's whole data set onto the device, once, as the
        device-data path reads it (:meth:`FusedNet.set_dataset`)."""
        loader = self.loader_unit
        data = numpy.asarray(loader.original_data.mem,
                             dtype=self.input.dtype)
        targets = None
        if self.loss == "mse":
            targets = numpy.asarray(loader.original_targets.mem,
                                    dtype=self.target.dtype)
        rows = int(loader.max_minibatch_size)
        if self.loss == "tokens":
            self.net.set_dataset(data, loader.token_labels,
                                 segments=loader.token_segments,
                                 minibatch=rows)
        else:
            self.net.set_dataset(data, loader.original_labels,
                                 targets=targets, minibatch=rows)

    def _prepare_programs(self):
        """Every program the first segment calls, traced, lowered and
        compiled from shapes while the net's parameters are still drawn
        (:meth:`FusedNet.prepare`): the train windows its minibatches fall
        into (``window`` at a time, the segment-final one apart on a data
        mesh) and, where the loader serves validation or test rows, the
        validation forward."""
        loader = self.loader_unit
        batch = int(loader.max_minibatch_size)
        steps = -(-int(loader.class_lengths[TRAIN]) // batch)
        full, tail = divmod(steps, self.window)
        sizes = [self.window] * full + ([tail] if tail else [])
        split = self.async_windows and self.net.data_shards > 1
        windows = sorted({(k, split and i == len(sizes) - 1)
                          for i, k in enumerate(sizes)})
        self.net.prepare(
            windows, batch, self._collect_hypers(),
            valid=bool(loader.class_lengths[VALID]
                       or loader.class_lengths[TEST]))

    def _loader_serves_contiguous_slices(self):
        """The sliced fast path additionally needs the STOCK minibatch
        walk (run) and reshuffle (_shuffle): TRAIN minibatch at class
        offset ``o`` must be rows ``train_indices[o:o+n]`` and the order
        must only change when ``shuffle_serial`` bumps.  Overriding
        loaders fall back to the per-row gather window."""
        from znicz_tpu.loader.base import Loader
        lu = self.loader_unit
        return (type(lu).run is Loader.run
                and type(lu)._shuffle is Loader._shuffle)

    def _setup_device_data(self):
        self._use_device_data = False
        self._use_sliced = False
        self._mat_serial = None
        qualifies = (self.device_data in ("auto", True)
                     and self.loader_unit is not None
                     and not self.forward_mode
                     and self._loader_qualifies_for_device_data())
        if self.loss == "mse":
            # MSE has no indexed-gather window; the device path IS the
            # sliced path (host-stacked windows remain for the rest)
            qualifies = qualifies and \
                self._loader_serves_contiguous_slices()
        if self.window is None:
            # adaptive default: scan windows over the device-resident
            # dataset where the loader qualifies; per-minibatch
            # otherwise (a host-stacked window helps only when dispatch
            # latency dominates — force with window=K)
            self.window = 8 if qualifies else 1
            if not qualifies and self.device_data in ("auto", True) \
                    and self.loader_unit is not None \
                    and not self.forward_mode:
                # the fallback must be VISIBLE: image-transform
                # loaders etc. lose the windowed loop
                if not self._loader_qualifies_for_device_data():
                    why = "loader %s has a custom fill or missing " \
                          "labels/targets" % type(self.loader_unit).__name__
                else:
                    why = "loader %s overrides the stock run/_shuffle " \
                          "slice contract" % type(self.loader_unit).__name__
                self.info(
                    "device-resident window path not engaged (%s); "
                    "training per minibatch — force a host-stacked "
                    "window with fused={'window': K}", why)
        if qualifies and self.window > 1:
            self._use_device_data = True
            # TRAIN minibatches are consumed on device; the loader
            # skips its host fill for them (VALID/TEST still fill —
            # they run per-minibatch through predict).  Softmax and
            # tokens gather rows by index inside the window; MSE
            # windows are sliced — their only device-data form
            self.loader_unit.skip_fill = True
            self._use_sliced = self.loss == "mse"
        elif self.device_data is True and not qualifies:
            raise ValueError(
                "fused device_data=True needs a stock FullBatchLoader "
                "(no fill_minibatch override) with labels")

    def _run_train_window(self):
        """Telemetry shell around :meth:`_run_train_window_inner`: spans
        the device-window path and reports per-step time (the window's
        wall time divided by its step count, weighted by that count —
        so `trainer.step_seconds` percentiles read as per-minibatch
        time across windows) plus the minibatch counter."""
        if not telemetry.enabled():
            n = self._run_train_window_inner()
        else:
            t0 = time.perf_counter()
            self._span_serial += 1
            with telemetry.span("fused.window",
                                step_num=self._span_serial,
                                window=self._span_serial,
                                sliced=self._use_sliced,
                                device_data=self._use_device_data) \
                    as sp:
                n = self._run_train_window_inner()
                sp.set(steps=n,
                       final=bool(self.loader_unit.last_minibatch))
            dt = time.perf_counter() - t0
            telemetry.counter("trainer.minibatches").inc(n)
            telemetry.counter("trainer.windows").inc()
            telemetry.histogram("trainer.step_seconds").observe(
                dt / max(n, 1), count=n)
        if health.enabled():
            # one fused device reduction per due check — params and
            # optimizer slots (vel carries the last update) already sit
            # on device; NaN grads poison the params on the same step,
            # so interval=1 detects on the step that produced them
            health.check_training_step(
                self, steps=n, params=self.net.params,
                updates=self.net.state, context="fused_window")
        # mid-epoch checkpointing (snapshotter window_interval): fired
        # only on NON-segment-final windows — boundaries already have
        # the decision-gated snapshot — and always at a window
        # boundary, so an interrupted run resumed from the capture
        # re-partitions the remaining minibatches into the exact same
        # windows the uninterrupted run dispatches
        snap = getattr(self.workflow, "snapshotter", None)
        if snap is not None and getattr(snap, "window_interval", 0) \
                and not bool(self.loader_unit.last_minibatch):
            snap.window_tick()

    def _run_train_window_inner(self):
        """Collect up to ``window`` TRAIN minibatches (driving the loader
        directly; the LR adjuster ticks per minibatch via hyper_tick) and
        dispatch them as ONE compiled scan window.  The window never
        crosses a segment boundary — collection stops at the loader's
        last_minibatch, so epoch/segment bookkeeping, snapshotter gating
        and decision semantics are untouched (reference decision.py only
        consumes segment aggregates + end-of-segment output).

        Asynchronous control plane (``async_windows``, the default):
        mid-epoch windows return WITHOUT any host readback — the
        decision aggregates were folded into device-resident epoch
        accumulators inside the dispatched executable, the evaluator
        gets the DEFERRED sentinel, and the next iteration collects
        window K+1 while this one is still in flight (bounded at
        ``pipeline_depth``).  The segment-final window fetches the
        accumulators + output/argmax in ONE batched transfer and zeros
        them for the next segment.

        Returns the number of minibatches dispatched."""
        loader = self.loader_unit
        batch = int(self.input.shape[0])
        dp = self.net.data_shards
        with telemetry.span("trainer.collect"):
            win = self._collect_window(batch, dp)
        n = win["n"]
        # segment-final windows are known BEFORE dispatch (collection
        # stopped at last_minibatch) — under a data mesh the final
        # window selects the executable variant that folds the
        # per-segment stats all-reduce (fused._get_window_fn).  Sync
        # mode reads per-window sharded partials and host-folds them
        # instead, so it never compiles (or pays) the final variant.
        pull_output = bool(loader.last_minibatch)
        dispatch_final = pull_output and self.async_windows
        if faults.enabled():
            # window-dispatch injection site (transient XlaRuntimeError
            # / RESOURCE_EXHAUSTED class, or a hard crash standing in
            # for preemption).  Deliberately NOT retried here: a failed
            # dispatch under donation cannot re-use its arguments — the
            # supervised launcher's restart + mid-epoch resume is the
            # recovery path (launcher.run_supervised).
            faults.check("fused.dispatch")
        # trainer.place and trainer.dispatch are FusedNet's, inside
        stats = self._dispatch_window(win, batch, dispatch_final)
        if self.async_windows and not pull_output:
            # asynchronous steady state: ZERO host readback — this
            # window's aggregates were folded into the device-resident
            # epoch accumulators inside the dispatched executable, and
            # the host moves straight on to collecting window K+1 while
            # this one is still in flight.  Bound the pipeline so live
            # input buffers (and the staging ring) stay capped under
            # donation: waiting on a tiny result token is a completion
            # wait, NOT a transfer.
            self.window_stats = DEFERRED_WINDOW_STATS
            # the per-window n_err delta is the wait token: tiny, and —
            # unlike the accumulator leaves — never DONATED into the
            # next window's dispatch (blocking on a donated buffer
            # raises once the successor consumes it)
            self._inflight.append(stats["n_err"])
            # retire tokens whose windows already finished (is_ready is
            # a host-side peek, no sync) so the deque — and the gauge —
            # count windows that are genuinely still executing: under a
            # forced per-window sync (armed health checks) it correctly
            # reads 0, the regression it exists to surface
            while self._inflight and self._inflight[0].is_ready():
                self._inflight.popleft()
            if len(self._inflight) > self.pipeline_depth:
                with telemetry.span("trainer.wait"):
                    while len(self._inflight) > self.pipeline_depth:
                        jax.block_until_ready(self._inflight.popleft())
            if telemetry.enabled():
                telemetry.gauge("trainer.inflight_windows").set(
                    len(self._inflight))
            self._refresh_weight_views()
            return n
        self._read_back_window(stats, pull_output, dp)
        self._refresh_weight_views()
        return n

    def _collect_window(self, batch, dp):
        """The ``trainer.collect`` span's work: the epoch's shuffled copy
        on the sliced path, then the loader driven up to ``window`` times
        (each minibatch lands in its staging row: indices on the
        device-data path, rows on the streaming path), the hypers stacked
        (the data set is placed in :meth:`initialize`).  Returns
        what the dispatch needs: ``n``, ``sizes``, ``hypers``, and the
        staged ``starts`` / ``idx`` / ``x`` / ``lbl`` / ``tgt``."""
        loader = self.loader_unit
        if self._use_device_data and self._use_sliced:
            # materialize BEFORE driving the loader: when TRAIN is the
            # epoch's last served segment (no VALID split), the loader
            # reshuffles IN PLACE while serving the epoch-final
            # minibatch — i.e. mid collection — so the order the
            # collected starts index into is the one current NOW, not
            # the one after the window is collected
            if self._mat_serial != loader.shuffle_serial:
                self.net.set_epoch_perm(
                    numpy.asarray(loader.train_indices),
                    pad=int(loader.max_minibatch_size))
                self._mat_serial = loader.shuffle_serial
        starts, sizes, hyper_steps = [], [], []
        stage_x = stage_l = stage_t = stage_idx = None

        def _row(stage, i):
            # shard-major staging keeps the step axis SECOND: minibatch
            # i's rows are the (S, B // S, ...) cross-shard view
            return stage[:, i] if dp > 1 else stage[i]

        def _win(stage, n):
            if stage is None:
                return None
            if dp > 1:
                return fused.ShardMajorWindow(stage[:, :n])
            return stage[:n]

        if self._use_device_data and not self._use_sliced:
            stage_idx = self._staging.get(
                "idx", (self.window, batch), numpy.int32, shards=dp)
        elif not self._use_device_data:
            # overlap-aware collection: each minibatch lands straight in
            # its staging row (ONE copy; the old per-step numpy.array +
            # numpy.stack paid two).  The ring rotates pipeline_depth+1
            # buffer sets so dispatched windows never see a reused row.
            # Under a data mesh the buffers are SHARD-MAJOR (one
            # contiguous block per shard) so device_put splits nothing.
            stage_x = self._staging.get(
                "x", (self.window,) + tuple(self.input.shape),
                self.input.dtype, shards=dp)
            stage_l = self._staging.get(
                "lbl", (self.window, batch), numpy.int32, shards=dp)
            if self.loss == "mse":
                stage_t = self._staging.get(
                    "tgt", (self.window,) + tuple(self.target.shape),
                    self.target.dtype, shards=dp)
        while True:
            i = len(sizes)
            if self._use_device_data and self._use_sliced:
                starts.append(int(loader.minibatch_class_offset))
            elif self._use_device_data:
                loader.fill_window_slot(indices_out=_row(stage_idx, i))
            elif self.loss == "mse":
                lbls = getattr(loader, "minibatch_labels", None)
                want_lbl = self.net.class_targets is not None and lbls
                loader.fill_window_slot(
                    x_out=_row(stage_x, i),
                    labels_out=_row(stage_l, i) if want_lbl else None,
                    targets_out=_row(stage_t, i))
                if not want_lbl:
                    _row(stage_l, i)[...] = -1
            else:
                loader.fill_window_slot(x_out=_row(stage_x, i),
                                        labels_out=_row(stage_l, i))
            sizes.append(int(self.minibatch_size))
            hyper_steps.append(self._current_hypers())
            n = len(sizes)
            if n >= self.window or bool(loader.last_minibatch):
                break
            loader.run()
            if self.hyper_tick is not None:
                self.hyper_tick()
        # stack per-step hypers along a leading K axis; cast to the
        # master param dtype (a float64 leaf would promote the f32
        # optimizer state inside the scan — the per-minibatch path's
        # python-float hypers are weakly typed and never promote).
        # All-same windows (no schedule ticked mid-window — the common
        # case) reuse the cached stacked pytree instead of restacking:
        # the SAME object every window, and the net's kept placed copy
        # hangs on that identity (FusedNet._place_window_scalars), so
        # the cached form is never changed in place.
        if all(h is hyper_steps[0] for h in hyper_steps):
            hypers_s = self._hyper_stacked.get(n)
            if hypers_s is None:
                hypers_s = jax.tree.map(
                    lambda *leaves: numpy.asarray(
                        leaves, dtype=self.net.dtype), *hyper_steps)
                self._hyper_stacked[n] = hypers_s
        else:
            hypers_s = jax.tree.map(
                lambda *leaves: numpy.asarray(leaves,
                                              dtype=self.net.dtype),
                *hyper_steps)
        return {"n": n, "sizes": sizes, "hypers": hypers_s,
                "starts": starts, "idx": _win(stage_idx, n),
                "x": _win(stage_x, n), "lbl": _win(stage_l, n),
                "tgt": _win(stage_t, n)}

    def _dispatch_window(self, win, batch, final):
        """Hand a collected window to the net's ``run_window*`` variant
        of this data path."""
        sizes, hypers_s = win["sizes"], win["hypers"]
        if self._use_device_data:
            if self.loss == "mse":
                return self.net.run_window_mse_sliced(
                    win["starts"], batch, sizes, hypers_s, final=final)
            return self.net.run_window_indexed(
                win["idx"], sizes, hypers_s, final=final)
        if self.loss == "mse":
            return self.net.run_window_mse(
                win["x"], win["tgt"], win["lbl"], sizes, hypers_s,
                final=final)
        return self.net.run_window(
            win["x"], win["lbl"], sizes, hypers_s, final=final)

    def _read_back_window(self, stats, pull_output, dp):
        """The synchronous end of a window: fetch the decision
        aggregates (``trainer.readback`` is ``host_fetch``'s span) and,
        on a segment-final window, the output the downstream units
        read."""
        # ONE pipelined batched host readback (device_get issues all
        # async copies before waiting — per-leaf numpy.asarray would pay
        # one blocking dispatch + copy EACH).
        # Async mode reads it once per SEGMENT: the device accumulators
        # carry the whole segment's decision aggregates (max_err_sum
        # included — no per-window scalar sync), and the (batch, classes)
        # output/argmax buffers ride the same transfer because every
        # reference consumer of ``output`` (evaluator merge, image
        # saver, plotters, decision bookkeeping) fires at segment
        # boundaries.  Sync mode (async_windows=False) keeps the
        # reference per-window delta readback.
        use_acc = self.async_windows
        # under a data mesh the segment-final executable already folded
        # the one per-segment all-reduce — read the replicated totals;
        # the sync mode's per-window deltas stay SHARDED partials (no
        # device collective) and are reduced on host after the fetch
        if use_acc and dp > 1:
            acc = stats["acc_reduced"]
        else:
            acc = self.net.window_acc
        reduce_host = dp > 1 and not use_acc
        if self.loss == "tokens":
            # the experts' load of a routed net rides the same fetch
            src = acc if use_acc else stats
            host = self.net.host_fetch(
                {k: src[k] for k in ("n_err", "loss_sum", "moe_load",
                                     "moe_unserved", "moe_rows",
                                     "moe_load_max",
                                     "moe_load_max_all", "moe_bias_abs_max",
                                     "attention_blocks", "attention_kept",
                                     "mtp_n_err", "mtp_loss_sum",
                                     "ssd_counts")
                 if k in src})
            self._set_token_stats(host, train=True)
        elif self.loss == "mse":
            fetch = {
                "metrics": acc["metrics"] if use_acc else stats["metrics"],
                "n_err": acc["n_err"] if use_acc else stats["n_err"]}
            if pull_output:
                fetch["output"] = stats["output"]
                fetch["mse_per"] = stats["mse_per"]
            host = self.net.host_fetch(fetch)
            if reduce_host:
                host = fused.reduce_window_partials(host, "mse")
            self.window_stats = {
                "metrics": host["metrics"],
                "n_err": host["n_err"],
            }
            if pull_output:
                self.window_stats["mse_per"] = host["mse_per"]
        else:
            fetch = {
                "n_err": acc["n_err"] if use_acc else stats["n_err"],
                "confusion": (acc["confusion"] if use_acc
                              else stats["confusion"]),
                "max_err_sum": (acc["max_err_sum"] if use_acc
                                else stats["max_err_sum"])}
            if pull_output:
                fetch["output"] = stats["output"]
                fetch["max_idx"] = stats["max_idx"]
            host = self.net.host_fetch(fetch)
            if reduce_host:
                host = fused.reduce_window_partials(host, "softmax")
            self.window_stats = {
                "n_err": host["n_err"],
                "confusion": host["confusion"],
                "max_err_sum": float(host["max_err_sum"]),
            }
        if telemetry.enabled():
            telemetry.counter("trainer.readbacks").inc()
        if pull_output:
            # segment boundary: the accumulators were consumed whole —
            # the next segment starts from zeros, and nothing remains in
            # flight (this fetch transitively waited on every ancestor
            # window)
            self.net.reset_window_acc()
            self._inflight.clear()
            if telemetry.enabled():
                telemetry.gauge("trainer.inflight_windows").set(0)
            if self.loss == "tokens":
                return
            self.output.map_invalidate()
            self.output.mem[...] = numpy.asarray(host["output"],
                                                 dtype=self.output.dtype)
            if self.loss != "mse":
                self.max_idx.map_invalidate()
                self.max_idx.mem[...] = host["max_idx"]

    def _set_token_stats(self, host, train):
        """The token objective's counts, as the evaluator takes them:
        ``n_err`` ``[errors, graded tokens, rows]`` and the graded
        tokens' loss sum (a train readback's cover every window since
        the last one)."""
        self.window_stats = {"n_err": numpy.asarray(host["n_err"]),
                             "loss_sum": float(host["loss_sum"])}
        if "mtp_n_err" in host:
            # a head graded at a shift: [loss sum, errors, graded tokens]
            errors, graded = (int(n) for n in host["mtp_n_err"])
            self.window_stats["mtp"] = numpy.array(
                [float(host["mtp_loss_sum"]), errors, graded])
            if train and telemetry.enabled():
                telemetry.counter("mtp.graded_tokens").inc(graded)
                telemetry.counter("mtp.errors").inc(errors)
        if "moe_load" in host:
            # (applications of ``moe`` entries, experts) pairs since the
            # last readback; a sync window's comes a step, the epoch
            # accumulator's summed, with the most an expert took in a step
            load = numpy.asarray(host["moe_load"], numpy.int64)
            held = self.net.moe_held
            most = int(host["moe_load_max"]) if "moe_load_max" in host \
                else int((load * held).max())
            load = load.reshape((-1,) + held.shape).sum(axis=0)
            self.window_stats["expert_load"] = load
            if train and telemetry.enabled():
                telemetry.counter("moe.pairs_held").inc(
                    int(load[held].sum()))
                telemetry.counter("moe.tokens_unserved").inc(
                    int(numpy.sum(host["moe_unserved"])))
                if "moe_rows" in host:
                    # rows the entries' two row movements fetched forward,
                    # and rows that movements over every pair would have
                    moved, static = (int(n) for n in numpy.reshape(
                        host["moe_rows"], (-1, 2)).sum(axis=0))
                    telemetry.counter("moe.rows_moved").inc(moved)
                    telemetry.counter("moe.rows_static").inc(static)
                telemetry.gauge("moe.load_max").set(max(
                    most, int(telemetry.gauge("moe.load_max").value or 0)))
                if "moe_load_max_all" in host:
                    # of a net whose selection bias the load moves: the
                    # most ANY expert took in a step since the last
                    # readback (not the run's: the first steps' load is
                    # the initialisation's, which the bias has yet to
                    # move), and the bias now
                    telemetry.gauge("moe.load_max_all").set(
                        int(host["moe_load_max_all"]))
                    telemetry.gauge("moe.bias_abs_max").set(
                        float(host["moe_bias_abs_max"]))
        if train and telemetry.enabled():
            if "attention_blocks" in host:
                # steps the attention kernel's block maps ran and steps the
                # static map would have, since the last readback
                visited, static = (int(n) for n in host["attention_blocks"])
                telemetry.counter("attention.blocks_visited").inc(visited)
                telemetry.counter("attention.blocks_static").inc(static)
            if "attention_kept" in host:
                # kernel applications whose output and log-sum the backward
                # took from the forward, since the last readback
                telemetry.counter("attention.forwards_kept").inc(
                    int(host["attention_kept"]))
            if "ssd_counts" in host:
                # chunks the ``ssd`` entries scanned, document starts
                # their state was cut at, and chunks a start split (mask
                # and carry both cut), since the last readback
                chunks, resets, split = (int(n) for n in host["ssd_counts"])
                telemetry.counter("ssd.chunks").inc(chunks)
                telemetry.counter("ssd.resets").inc(resets)
                telemetry.counter("ssd.chunks_split").inc(split)
                # of them, the chunks the TPU's kernels scanned
                telemetry.counter("ssd.chunks_kernel").inc(
                    chunks if self.net.ssd_kernel else 0)
                # the conv applications the TPU's kernels ran: a row each
                # of the mixers that take them
                telemetry.counter("ssd.conv_kernel").inc(
                    self.net.ssd_conv_kernel * int(host["n_err"][2]))
            telemetry.counter("trainer.graded_tokens").inc(
                int(host["n_err"][1]))
            telemetry.counter("trainer.rows").inc(int(host["n_err"][2]))

    def _current_hypers(self):
        """The live hyper pytree, rebuilt ONLY when a proxy attribute
        actually changed (GDProxy.serial).  Returns the SAME object
        while nothing mutates, which lets the window path hand the net
        one stacked K-axis form, by identity, for as long: the net
        places that object once and keeps the placed copy (PERF.md
        section 6, PR 29: placing it anew every window was 72 % of a
        four-chip epoch).  A moved serial drops the stacked forms, so
        the next window hands a new object and is placed."""
        s = tuple(p.serial for p in self.gd_proxies)
        if s != self._hyper_serials:
            self._hyper_cache = self._collect_hypers()
            self._hyper_serials = s
            self._hyper_stacked.clear()
        return self._hyper_cache

    def _collect_hypers(self):
        """Rebuild the traced hyper pytree from the live proxies."""
        hypers = []
        it = iter(self.gd_proxies)
        for spec in self.net.specs:
            if spec.kind in ("fc", "conv"):
                proxy = next(it)
                hyper, hyper_bias = proxy.hyper_dicts()
                h = {"w": hyper}
                if spec.include_bias:
                    h["b"] = hyper_bias
                hypers.append(h)
            elif spec.kind in transformer.KINDS:
                hypers.append(transformer.leaf_hypers(
                    spec, *next(it).hyper_dicts()))
            else:
                hypers.append({})
        return hypers

    def run(self):
        train = int(self.minibatch_class) == TRAIN and not self.forward_mode
        self.window_stats = None
        if (train and self.window > 1
                and self.loader_unit is not None):
            self._run_train_window()
            return
        if train or not telemetry.enabled():
            self._run_minibatch(train)
            return
        self._span_serial += 1
        with telemetry.span("trainer.valid", window=self._span_serial):
            self._run_minibatch(train)

    def _run_minibatch(self, train):
        """One minibatch outside the window path: a validation/test
        forward (the ``trainer.valid`` span), or a per-minibatch train
        step where windows are off."""
        t0 = time.perf_counter()
        idx = None
        if train and faults.enabled():
            faults.check("fused.dispatch")
        if self.loss == "tokens":
            if train or not self.net.has_dataset:
                raise RuntimeError(
                    "the tokens objective runs windows over the "
                    "resident data set only")
            # counts and loss sum from the device, never logits
            self._set_token_stats(self.net.host_fetch(
                self.net.predict_indexed(
                    self.loader_unit.minibatch_indices.mem)),
                train=False)
            return
        elif not train and self._use_device_data \
                and self.net.has_dataset and self.input.pending:
            # the loader has put the row copy off (skip_fill) and
            # nothing has read the buffer since: these are the
            # resident set's rows at its indices, taken as a train
            # window takes them.  Reading ``self.input`` here would
            # force the host copy
            out = self.net.predict_indexed(
                self.loader_unit.minibatch_indices.mem,
                with_idx=self.loss != "mse")
            if self.loss != "mse":
                out, idx = out
        elif self.loss == "mse":
            x = self.input.mem
            self.target.map_read()
            if train:
                metrics = self.net.step_mse(
                    x, self.target.mem, int(self.minibatch_size),
                    hypers=self._current_hypers())
                out = metrics["output"]
            else:
                out = self.net.predict(x)
        else:
            x = self.input.mem
            self.labels.map_read()
            labels = numpy.asarray(self.labels.mem,
                                   dtype=numpy.int32)
            if train:
                metrics = self.net.step(
                    x, labels, hypers=self._current_hypers())
                out, idx = metrics["output"], metrics["max_idx"]
            else:
                out, idx = self.net.predict_with_idx(x)
        # host copies: the downstream evaluator mixes these with
        # single-device loader arrays — a mesh-committed jax.Array
        # would clash there, and the per-minibatch pull is small.
        # device_get pipelines the transfers (one round trip, not
        # one per array).
        out, idx = self.net.host_fetch((out, idx))
        self.output.map_invalidate()
        self.output.mem[...] = numpy.asarray(out, dtype=self.output.dtype)
        if idx is not None:
            self.max_idx.map_invalidate()
            self.max_idx.mem[...] = numpy.asarray(idx)
        if train:
            # re-point the plotter views at the post-update params
            # (zero-copy; plotters pull to host only when they fire)
            self._refresh_weight_views()
            if telemetry.enabled():
                telemetry.counter("trainer.minibatches").inc()
                telemetry.histogram("trainer.step_seconds").observe(
                    time.perf_counter() - t0)
            if health.enabled():
                health.check_training_step(
                    self, steps=1, params=self.net.params,
                    updates=self.net.state, context="fused_step")

    # -- snapshot / resume ---------------------------------------------------
    @property
    def fused_state(self):
        if self.net is None:
            return self._pending_state
        sd = self.net.state_dict()
        sd["proxies"] = [p.state_dict() for p in self.gd_proxies]
        return sd

    @fused_state.setter
    def fused_state(self, value):
        if value is None:
            return
        if self.net is None:
            self._pending_state = value
        else:
            self._apply_state(value)

    @property
    def epoch_acc(self):
        """The device-resident epoch accumulators drained to host (the
        existing one-readback machinery — :meth:`FusedNet.host_fetch`
        waits on every in-flight window, so the capture is consistent
        under the async pipeline and under a data mesh, where the
        leaves are the sharded ``(S, ...)`` partials).  None at segment
        boundaries (nothing mid-flight to save)."""
        if self.net is None:
            return self._pending_acc
        return self.net.window_acc_host()

    @epoch_acc.setter
    def epoch_acc(self, value):
        if self.net is None:
            self._pending_acc = value
        else:
            self.net.set_window_acc(value)

    def _refresh_weight_views(self):
        for i, view in self.weight_views:
            view.set_dev(self.net.params[i]["w"])

    def _apply_state(self, sd):
        self.net.load_state_dict(sd)
        for proxy, ps in zip(self.gd_proxies, sd.get("proxies", ())):
            proxy.load_state_dict(ps)
        # load_state_dict REPLACES the params pytree — re-point the
        # plotter views or they keep showing the pre-restore weights
        self._refresh_weight_views()

    # -- inference extraction / broadcast parity ----------------------------
    def host_params(self):
        if self.net is not None:
            return self.net.host_params()
        if self._pending_state is not None:
            return self._pending_state["params"]
        raise RuntimeError("fused trainer not initialized")

    #: forward hyperparameters each spec kind contributes to its
    #: serving-topology entry — the names the unit-graph forwards export
    _TOPOLOGY_ATTRS = {
        "fc": ("include_bias",),
        "conv": ("include_bias", "kx", "ky", "n_kernels", "padding",
                 "sliding"),
        "pool": ("kx", "ky", "sliding"),
        "lrn": ("alpha", "beta", "k", "n"),
    }

    def topology_layers(self):
        """The stack as ``export.forward_topology`` entries, one per
        spec: what lets ``serve --latest`` rebuild the forward from a
        fused-mode snapshot.  The arrays stay in ``fused_state``; the
        engine maps them onto these entries by position
        (serving/engine.py ``_fill_from_fused_state``)."""
        if self.net is None:
            raise RuntimeError("fused trainer not initialized")
        layers = []
        for spec in self.net.specs:
            entry = {"type": spec.type, "unit": self.name, "arrays": []}
            if spec.kind in transformer.KINDS:
                # named, so that a snapshot can be taken; serving refuses
                # the kind by this name (ops/transformer.refuse)
                layers.append(entry)
                continue
            for attr in self._TOPOLOGY_ATTRS.get(spec.kind, ()):
                entry[attr] = getattr(spec, attr)
            if spec.kind in ("fc", "conv"):
                entry["weights_transposed"] = False
                entry["arrays"] = ["weights", "bias"] \
                    if spec.include_bias else ["weights"]
            layers.append(entry)
        return layers

    def generate_data_for_slave(self, slave=None):
        return None

    def apply_data_from_master(self, data):
        pass


class FusedNNRollback(Unit):
    """Divergence recovery for the fused path (reference
    nn_rollback.py:44-190 semantics over whole-net snapshots).

    On improvement: bump every proxy's LR by ``lr_plus`` and push the
    net's full state onto a bounded history.  After ``minus_steps``
    consecutive non-improvements (or any NaN in the parameters): decay
    LRs by ``lr_minus`` and restore the oldest stored state.
    """

    def __init__(self, workflow, **kwargs):
        super(FusedNNRollback, self).__init__(workflow, **kwargs)
        self.trainer = kwargs["trainer"]
        self.lr_plus = kwargs.get("lr_plus", 1.04)
        self.lr_minus = kwargs.get("lr_minus", 0.65)
        self.plus_steps = kwargs.get("plus_steps", 1)
        self.minus_steps = kwargs.get("minus_steps", 3)
        self._plus_steps = self.plus_steps
        self._minus_steps = self.minus_steps
        self.history_limit = kwargs.get("history_limit", 2)
        self.improved = None
        self.demand("improved")
        self._history = []
        self._first_run = True

    def _scale_lrs(self, k):
        for proxy in self.trainer.gd_proxies:
            proxy.learning_rate *= k
            proxy.learning_rate_bias *= k

    def _has_nans(self):
        # one jitted isfinite reduction on device — no whole-model host
        # pull on the failure path
        return not self.trainer.net.params_finite()

    def run(self):
        if self.improved:
            self._plus_steps += 1
            if self._plus_steps < self.plus_steps:
                return
            self._plus_steps = 0
            self._minus_steps = 0
            self._scale_lrs(self.lr_plus)
            self._history.append(self.trainer.fused_state)
            while len(self._history) > self.history_limit:
                self._history.pop(0)
        elif not self._first_run:
            if self._has_nans():
                self.warning("NaNs encountered, rolling back")
                self._minus_steps = self.minus_steps
            self._minus_steps += 1
            if self._minus_steps < self.minus_steps:
                return
            self._minus_steps = 0
            self._plus_steps = 0
            self._scale_lrs(self.lr_minus)
            if not self._history:
                self.warning("No rollback state stored")
            else:
                self.info("Rolling back fused net state")
                sd = self._history[0]
                del self._history[1:]
                # LRs keep their decayed values; restore net tensors only
                saved = [p.state_dict()
                         for p in self.trainer.gd_proxies]
                self.trainer.fused_state = sd
                for proxy, ps in zip(self.trainer.gd_proxies, saved):
                    proxy.load_state_dict(ps)
        self._first_run = False

    # IDistributable stubs
    def generate_data_for_slave(self, slave=None):
        return None

    def apply_data_from_master(self, data):
        pass
