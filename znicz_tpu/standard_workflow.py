"""StandardWorkflow — the one-stop training-graph builder.

TPU-era equivalent of reference standard_workflow.py (1201 LoC — SURVEY.md
§2.1).  ``create_workflow`` assembles the canonical train graph::

    repeater -> loader -> forwards[0..n] -> evaluator -> decision
      -> snapshotter -> gds[n..0] -> (loop back to repeater) -> end_point

from the declarative ``layers`` config, pairing each forward with its
registered backward (reference standard_workflow.py:173-208, 289-374).
"""

from znicz_tpu.standard_workflow_base import StandardWorkflowBase
from znicz_tpu.core.snapshotter import SnapshotterRegistry
from znicz_tpu.units.conv import ConvolutionalBase
from znicz_tpu.units.gd_pooling import GDPooling
from znicz_tpu.units.decision import DecisionsRegistry
from znicz_tpu.units.evaluator import EvaluatorsRegistry
# Importing the units package registers every layer type — keep even if
# it looks unused (reference standard_workflow.py:58-60).
import znicz_tpu.units  # noqa: F401


class StandardWorkflow(StandardWorkflowBase):
    """(reference standard_workflow.py:81-1172)"""

    def __init__(self, workflow=None, **kwargs):
        super(StandardWorkflow, self).__init__(workflow, **kwargs)
        self.loss_function = kwargs.get("loss_function", "softmax")
        if self.loss_function not in EvaluatorsRegistry.evaluators:
            raise ValueError("Unknown loss_function %r (known: %s)" % (
                self.loss_function,
                sorted(EvaluatorsRegistry.evaluators)))
        self.decision_name = kwargs.get(
            "decision_name",
            {"softmax": "decision_gd", "tokens": "decision_tokens"}.get(
                self.loss_function, "decision_mse"))
        self.snapshotter_name = kwargs.get("snapshotter_name", "nnfile")
        self.evaluator_config = self.config2kwargs(
            kwargs.get("evaluator_config"))
        self.decision_config = self.config2kwargs(
            kwargs.get("decision_config"))
        self.snapshotter_config = self.config2kwargs(
            kwargs.get("snapshotter_config"))
        if not self.preprocessing:
            self.create_workflow()

    # -- canonical graph (reference 173-208) --------------------------------
    def create_workflow(self):
        if self.fused_config is not None:
            return self.create_fused_workflow()
        self.link_repeater(self.start_point)
        self.link_loader(self.repeater)
        self.link_forwards(("input", "minibatch_data"), self.loader)
        self.link_evaluator(self.forwards[-1])
        self.link_decision(self.evaluator)
        self.link_snapshotter(self.decision)
        last_gd = self.link_gds(self.snapshotter)
        self.link_loop(last_gd)
        self.link_end_point(last_gd)

    def create_fused_workflow(self):
        """The same control-plane graph with the forwards+gds chain
        collapsed into one compiled SPMD train-step unit (SURVEY.md §7
        design stance: unit graph = epoch-level control plane around the
        jitted step)."""
        self.link_repeater(self.start_point)
        self.link_loader(self.repeater)
        self.link_fused_trainer(self.loader)
        self.link_evaluator(self.fused_trainer)
        self.link_decision(self.evaluator)
        self.link_snapshotter(self.decision)
        self.link_loop(self.snapshotter)
        self.link_end_point(self.snapshotter)

    def link_fused_trainer(self, *parents):
        """Create the fused train-step unit from the ``layers`` config
        (fused twin of link_forwards + link_gds).  ``fused_config`` keys:
        ``mesh`` (a jax Mesh, or an int device count),
        ``model_parallel`` (with an int mesh), ``compute_dtype``,
        ``dtype``, ``dropout_seed``, ``defaults``."""
        from znicz_tpu.units.fused_trainer import FusedForwardBackward
        cfg = dict(self.fused_config or {})
        mesh = cfg.pop("mesh", None)
        if mesh == "hybrid":
            # all processes' devices, model axis inside one host's ICI
            # domain (multi-host SPMD; launcher calls
            # multihost.initialize() from env before this)
            from znicz_tpu.parallel import multihost
            mesh = multihost.make_hybrid_mesh(
                model_parallel=cfg.pop("model_parallel", 1))
        elif isinstance(mesh, int):
            from znicz_tpu.parallel import make_mesh
            mesh = make_mesh(mesh,
                             model_parallel=cfg.pop("model_parallel", 1))
        cfg.setdefault("loss", self.loss_function)
        self.fused_trainer = FusedForwardBackward(
            self, name="fused_trainer", layers=self.layers, mesh=mesh,
            **cfg)
        self.fused_trainer.link_from(*parents)
        self.fused_trainer.link_attrs(
            self.loader, ("input", "minibatch_data"),
            "minibatch_class", "minibatch_size")
        if self.loss_function == "mse":
            self.fused_trainer.link_attrs(
                self.loader, ("target", "minibatch_targets"))
        else:
            self.fused_trainer.link_attrs(
                self.loader, ("labels", "minibatch_labels"))
        self.fused_trainer.label_source = self.real_loader
        # window collection drives the loader directly (scan windows —
        # the compiled hot loop batches K TRAIN minibatches per dispatch)
        self.fused_trainer.loader_unit = self.loader
        # the trainer IS the forward chain for downstream linkers
        # (link_evaluator/link_image_saver read forwards[-1])
        self.forwards[:] = [self.fused_trainer]
        return self.fused_trainer

    # -- backward chain (reference 289-374) ---------------------------------
    def link_gds(self, *parents):
        if not isinstance(self.layers, (tuple, list)):
            raise ValueError("layers should be a list of dicts")
        self.gds[:] = [None] * len(self.layers)
        first_gd = None
        units_to_delete = []
        for i, layer in reversed(list(enumerate(self.layers))):
            tpe, _, kwargs = self._get_layer_type_kwargs(layer, i)
            if not isinstance(self.forwards[i], self.layer_map[tpe].forward):
                raise TypeError(
                    "Forward layer %s at position %d is not an instance "
                    "of %s" % (self.forwards[i], i,
                               self.layer_map[tpe].forward))
            try:
                backward_cls = next(self.layer_map[tpe].backwards)
            except StopIteration:
                units_to_delete.append(i)
                continue
            unit = backward_cls(self, **kwargs)
            self.gds[i] = unit
            if hasattr(unit, "bind_forward"):
                # pairs sharing structured parameters (e.g. the scan
                # LSTM's gate pytree) take the forward directly instead
                # of linking singular weights/bias Arrays
                unit.bind_forward(self.forwards[i])

            if first_gd is not None:
                unit.link_from(first_gd) \
                    .link_attrs(first_gd, ("err_output", "err_input"))
            else:
                unit.link_from(*parents) \
                    .link_attrs(self.evaluator, "err_output")
            first_gd = unit

            try_link = {"input", "weights", "bias", "input_offset",
                        "mask", "output"}
            if isinstance(unit, ConvolutionalBase):
                try_link.update(ConvolutionalBase.CONV_ATTRS)
            if isinstance(unit, GDPooling):
                try_link.update(GDPooling.POOL_ATTRS)
            attrs = [a for a in sorted(try_link)
                     if getattr(self.forwards[i], a, None) is not None]
            unit.link_attrs(self.forwards[i], *attrs)
            unit.link_attrs(self.loader, ("batch_size", "minibatch_size"))
            if getattr(unit, "mask", None) is not None or "mask" in attrs:
                unit.link_attrs(self.loader, "minibatch_class")
            unit.gate_skip = self.decision.gd_skip

        for i in units_to_delete:
            del self.gds[i]
        self.gds[0].need_err_input = False
        return first_gd

    # -- evaluator (reference 413-448) --------------------------------------
    def link_evaluator(self, *parents):
        self.evaluator = EvaluatorsRegistry.evaluators[self.loss_function](
            self, name="evaluator", **self.evaluator_config)
        self.evaluator.link_from(*parents) \
            .link_attrs(self.forwards[-1], "output") \
            .link_attrs(self.loader,
                        ("batch_size", "minibatch_size"),
                        ("labels", "minibatch_labels"),
                        ("max_samples_per_epoch", "total_samples"),
                        "class_lengths",
                        ("offset", "minibatch_offset"))
        if self.loss_function == "softmax":
            self.evaluator.link_attrs(self.forwards[-1], "max_idx")
            if self.fused_trainer is not None:
                # windowed TRAIN dispatches hand the evaluator their
                # in-scan aggregated stats (the output buffer holds only
                # the window's LAST minibatch)
                self.evaluator.stats_source = self.fused_trainer
                self.fused_trainer.stats_mean = self.evaluator.mean
        elif self.loss_function == "tokens":
            if self.fused_trainer is None:
                raise ValueError("loss_function 'tokens' trains through "
                                 "the fused trainer only (fused={...})")
            self.evaluator.stats_source = self.fused_trainer
        elif self.loss_function == "mse":
            self.evaluator.link_attrs(
                self.loader, ("target", "minibatch_targets"))
            # linked attrs resolve lazily, so this works for loaders that
            # only fill class_targets inside load_data (the evaluator
            # checks for None again at run time)
            if hasattr(self.loader, "class_targets"):
                self.evaluator.link_attrs(self.loader, "class_targets",
                                          ("labels", "minibatch_labels"))
            if self.fused_trainer is not None:
                # windowed MSE TRAIN dispatches hand the evaluator
                # their in-scan [sum,max,min] metrics (+ class-target
                # n_err); mirror the evaluator's flags into the scan
                self.evaluator.stats_source = self.fused_trainer
                self.fused_trainer.stats_mean = self.evaluator.mean
                self.fused_trainer.stats_root = self.evaluator.root
        return self.evaluator

    # -- decision (reference 451-490) ---------------------------------------
    def link_decision(self, *parents):
        self.decision = DecisionsRegistry.decisions[self.decision_name](
            self, name="decision", **self.decision_config)
        self.decision.link_from(*parents) \
            .link_attrs(self.loader, "minibatch_class", "last_minibatch",
                        "minibatch_size", "class_lengths", "epoch_ended",
                        "epoch_number")
        self.decision.link_attrs(self.evaluator,
                                 ("minibatch_n_err", "n_err"))
        if self.decision_name == "decision_gd":
            self.decision.link_attrs(
                self.evaluator,
                ("minibatch_confusion_matrix", "confusion_matrix"),
                ("minibatch_max_err_y_sum", "max_err_output_sum"))
        elif self.decision_name == "decision_tokens":
            self.decision.link_attrs(
                self.evaluator, ("minibatch_loss_sum", "loss_sum"),
                ("minibatch_expert_load", "expert_load"),
                ("minibatch_mtp", "mtp"))
        elif self.decision_name == "decision_mse":
            self.decision.link_attrs(self.loader, "minibatch_offset")
            self.decision.link_attrs(self.evaluator,
                                     ("minibatch_metrics", "metrics"),
                                     ("minibatch_mse", "mse"))
        self.repeater.gate_block = self.decision.complete
        self.real_loader.gate_block = self.decision.complete
        return self.decision

    # -- snapshotter (reference 493-516) ------------------------------------
    def link_snapshotter(self, *parents):
        name = self.snapshotter_name or "nnfile"
        self.snapshotter = SnapshotterRegistry.mapping[name](
            self, name="snapshotter", **self.snapshotter_config)
        self.snapshotter.link_from(*parents) \
            .link_attrs(self.decision, ("suffix", "snapshot_suffix"))
        self.snapshotter.gate_skip = ~self.loader.epoch_ended
        self.snapshotter.skip = ~self.decision.improved
        return self.snapshotter

    def link_loop(self, *parents):
        """Close the training loop back into the repeater."""
        self.repeater.link_from(*parents)
        return self.repeater

    # -- training amenities (reference 533-600, 573-591) --------------------
    def link_lr_adjuster(self, *parents, **kwargs):
        """Per-iteration LR schedules on every GD unit
        (reference standard_workflow.py:573-591)."""
        from znicz_tpu.units.lr_adjust import LearningRateAdjust
        cfg = self.config2kwargs(kwargs.pop("lr_adjuster_config", None)) \
            or kwargs
        self.lr_adjuster = LearningRateAdjust(
            self, name="lr_adjuster", **cfg)
        if self.fused_trainer is not None:
            # fused mode: the proxies carry the hyperparameter surface;
            # the schedule's new LR reaches the jitted step as a traced
            # argument (no recompile).  The adjuster fires between the
            # loader and the train step — the unit graph runs it before
            # the GD updates of the SAME minibatch (snapshotter ->
            # adjuster -> gds), so update k must use policy(k), not
            # policy(k-1); ``parents`` are ignored for this insertion.
            for proxy in self.fused_trainer.gd_proxies:
                self.lr_adjuster.add_gd_unit(proxy)
            self.lr_adjuster.train_gate_loader = self.loader
            self.fused_trainer.unlink_from(self.loader)
            self.lr_adjuster.link_from(self.loader)
            self.fused_trainer.link_from(self.lr_adjuster)
            # window collection ticks the schedule per collected
            # minibatch, so policy(k) reaches step k INSIDE the window
            self.fused_trainer.hyper_tick = self.lr_adjuster.run
            return self.lr_adjuster
        for gd in self.gds:
            self.lr_adjuster.add_gd_unit(gd)
        self.lr_adjuster.link_from(*parents)
        return self.lr_adjuster

    def link_rollback(self, *parents, **kwargs):
        """Divergence recovery (reference standard_workflow.py:594-600)."""
        if self.fused_trainer is not None:
            from znicz_tpu.units.fused_trainer import FusedNNRollback
            self.rollback = FusedNNRollback(
                self, name="rollback", trainer=self.fused_trainer,
                **kwargs)
            self.rollback.link_from(*parents)
            self.rollback.link_attrs(self.decision, "improved")
            self.rollback.gate_skip = ~self.loader.epoch_ended
            return self.rollback
        from znicz_tpu.units.nn_rollback import NNRollback
        self.rollback = NNRollback(self, name="rollback", **kwargs)
        self.rollback.link_from(*parents)
        self.rollback.link_attrs(self.decision, "improved")
        self.rollback.gate_skip = ~self.loader.epoch_ended
        for gd in self.gds:
            self.rollback.add_gd(gd)
        return self.rollback

    def link_image_saver(self, *parents, **kwargs):
        """Dump misclassified samples, gated on improvement
        (reference standard_workflow.py:533-569)."""
        from znicz_tpu.units.image_saver import ImageSaver
        self.image_saver = ImageSaver(self, name="image_saver", **kwargs)
        self.image_saver.link_from(*parents)
        self.image_saver.link_attrs(self.forwards[-1], "output")
        if self.loss_function == "softmax":
            self.image_saver.link_attrs(self.forwards[-1], "max_idx")
        self.image_saver.link_attrs(
            self.loader,
            ("input", "minibatch_data"),
            ("indices", "minibatch_indices"),
            ("labels", "minibatch_labels"),
            "minibatch_class", "minibatch_size", "epoch_number")
        self.image_saver.gate_skip = ~self.decision.improved
        return self.image_saver

    def link_error_plotter(self, *parents):
        """Per-epoch error curve (reference standard_workflow.py:672-700)."""
        from znicz_tpu.core.plotting_units import AccumulatingPlotter
        self.error_plotter = []
        prev = parents
        for i in (1, 2):  # validation, train
            p = AccumulatingPlotter(self, name="error_%d" % i,
                                    input_field=i)
            p.input = self.decision.epoch_n_err_pt
            p.link_from(*prev)
            p.gate_skip = ~self.decision.epoch_ended
            self.error_plotter.append(p)
            prev = (p,)
        return self.error_plotter[-1]

    def _plottable_weight_sources(self):
        """[(index, weights Array)] across both execution modes — the
        unit graph's forward units or the fused trainer's device-backed
        weight views (created at construction, populated at
        initialize; Weights2D.fill skips empty Arrays at run time)."""
        if self.fused_trainer is not None:
            return list(self.fused_trainer.weight_views)
        out = []
        for i, fwd in enumerate(self.forwards):
            if getattr(fwd, "weights", None) is not None:
                out.append((i, fwd.weights))
        return out

    def link_weights_plotter(self, *parents, **kwargs):
        """Weight-image grids per layer
        (reference standard_workflow.py:853-891); works in fused mode
        through the trainer's weight views."""
        from znicz_tpu.units.nn_plotting_units import Weights2D
        limit = kwargs.get("limit", 64)
        self.weights_plotter = []
        prev = parents
        for i, weights in self._plottable_weight_sources():
            p = Weights2D(self, name="weights_%d" % i, limit=limit)
            p.input = weights
            p.link_from(*prev)
            p.gate_skip = ~self.decision.epoch_ended
            self.weights_plotter.append(p)
            prev = (p,)
        return self.weights_plotter[-1] if self.weights_plotter \
            else parents[0]

    def link_conf_matrix_plotter(self, *parents):
        """(reference standard_workflow.py:723-743)"""
        from znicz_tpu.core.plotting_units import MatrixPlotter
        self.conf_matrix_plotter = MatrixPlotter(
            self, name="conf_matrix")
        self.conf_matrix_plotter.input = self.evaluator.confusion_matrix
        self.conf_matrix_plotter.link_from(*parents)
        self.conf_matrix_plotter.gate_skip = ~self.decision.epoch_ended
        return self.conf_matrix_plotter

    def link_mse_plotter(self, *parents):
        """(reference standard_workflow.py:702-721)"""
        from znicz_tpu.units.nn_plotting_units import MSEHistogram
        self.mse_plotter = MSEHistogram(self, name="mse_histogram")
        self.mse_plotter.link_attrs(self.evaluator, "mse")
        self.mse_plotter.link_from(*parents)
        self.mse_plotter.gate_skip = ~self.decision.epoch_ended
        return self.mse_plotter

    def link_err_y_plotter(self, *parents):
        """Last-layer max gradient sum curve
        (reference standard_workflow.py:738-771)."""
        from znicz_tpu.core.plotting_units import AccumulatingPlotter
        self.err_y_plotters = []
        prev = parents
        for i in (1, 2):  # validation, train
            p = AccumulatingPlotter(
                self, name="err_y_%d" % i, input_field=i)
            p.input = self.decision.max_err_y_sums
            p.link_from(*prev)
            p.gate_skip = ~self.decision.epoch_ended
            self.err_y_plotters.append(p)
            prev = (p,)
        return self.err_y_plotters[-1]

    def link_multi_hist_plotter(self, *parents, **kwargs):
        """Per-layer weight histograms
        (reference standard_workflow.py:773-816)."""
        from znicz_tpu.core.plotting_units import MultiHistogram
        weights_input = kwargs.get("weights_input", "weights")
        self.multi_hist_plotter = []
        prev = parents
        if weights_input == "weights":
            sources = self._plottable_weight_sources()
        else:
            sources = [(i, getattr(fwd, weights_input))
                       for i, fwd in enumerate(self.forwards)
                       if getattr(fwd, weights_input, None) is not None]
        for i, arr in sources:
            p = MultiHistogram(self, name="hist_%d" % i,
                               hist_number=kwargs.get("hist_number", 16),
                               n_bars=kwargs.get("n_bars", 25))
            p.input = arr
            p.link_from(*prev)
            p.gate_skip = ~self.decision.epoch_ended
            self.multi_hist_plotter.append(p)
            prev = (p,)
        return self.multi_hist_plotter[-1] if self.multi_hist_plotter \
            else parents[0]

    def link_similar_weights_plotter(self, *parents, **kwargs):
        """Weight-diversity grids (reference standard_workflow.py:874-931,
        znicz diversity.SimilarWeights2D)."""
        from znicz_tpu.units.diversity import SimilarWeights2D
        weights_input = kwargs.pop("weights_input", "weights")
        self.similar_weights_plotter = []
        prev = parents
        for i, fwd in enumerate(self.forwards):
            if getattr(fwd, weights_input, None) is None:
                continue
            # non-square weight rows are skipped at RUN time by
            # SimilarWeights2D.fill (shapes are unknown at link time)
            p = SimilarWeights2D(self, name="similar_%d" % i, **kwargs)
            p.input = getattr(fwd, weights_input)
            p.link_from(*prev)
            p.gate_skip = ~self.decision.epoch_ended
            self.similar_weights_plotter.append(p)
            prev = (p,)
        return self.similar_weights_plotter[-1] \
            if self.similar_weights_plotter else parents[0]

    def link_table_plotter(self, *parents):
        """Max/min table over weights and gradients
        (reference standard_workflow.py:934-969)."""
        from znicz_tpu.core.plotting_units import TableMaxMin
        self.table_plotter = TableMaxMin(self, name="table")
        for i, fwd in enumerate(self.forwards):
            if getattr(fwd, "weights", None) is None:
                continue
            self.table_plotter.y.append(fwd.weights)
            self.table_plotter.col_labels.append("weights_%d" % i)
        for i, g in enumerate(self.gds):
            if g is None or getattr(g, "gradient_weights", None) is None:
                continue
            self.table_plotter.y.append(g.gradient_weights)
            self.table_plotter.col_labels.append("gd_%d" % i)
        self.table_plotter.link_from(*parents)
        self.table_plotter.gate_skip = ~self.decision.epoch_ended
        return self.table_plotter

    def link_min_max_plotter(self, is_min, *parents):
        """Epoch-metric extremum curve
        (reference standard_workflow.py:1004-1042)."""
        from znicz_tpu.core.plotting_units import AccumulatingPlotter
        p = AccumulatingPlotter(
            self, name="mse_min" if is_min else "mse_max",
            input_field=2, input_offset=2 if is_min else 1)
        p.input = self.decision.epoch_metrics
        p.link_from(*parents)
        p.gate_skip = ~self.decision.epoch_ended
        if is_min:
            self.min_plotter = p
        else:
            self.max_plotter = p
        return p

    def link_image_plotter(self, *parents):
        """Output vs input sample images
        (reference standard_workflow.py:1044-1066)."""
        from znicz_tpu.core.plotting_units import ImagePlotter
        self.image_plotter = ImagePlotter(self, name="output_sample")
        self.image_plotter.inputs.append(self.forwards[-1].output)
        self.image_plotter.input_fields.append(0)
        self.image_plotter.inputs.append(self.forwards[0].input)
        self.image_plotter.input_fields.append(0)
        self.image_plotter.link_from(*parents)
        self.image_plotter.gate_skip = ~self.decision.epoch_ended
        return self.image_plotter

    def link_immediate_plotter(self, *parents):
        """Data / target / output curves
        (reference standard_workflow.py:1068-1101)."""
        from znicz_tpu.core.plotting_units import ImmediatePlotter
        self.immediate_plotter = ImmediatePlotter(
            self, name="immediate")
        del self.immediate_plotter.inputs[:]
        del self.immediate_plotter.input_fields[:]
        for src in (self.loader.minibatch_data,
                    getattr(self.loader, "minibatch_targets", None),
                    self.forwards[-1].output):
            if src is None:
                continue
            self.immediate_plotter.inputs.append(src)
            self.immediate_plotter.input_fields.append(0)
        self.immediate_plotter.link_from(*parents)
        self.immediate_plotter.gate_skip = ~self.decision.epoch_ended
        return self.immediate_plotter

    # -- aux-service linkers (reference 386-411, 648-670, 1121-1149) --------
    def link_avatar(self, *extra_attrs):
        """Replace the just-linked loader with its prefetching Avatar so
        host-side loading overlaps device compute.  Call right after
        link_loader, BEFORE anything links against the loader (same
        constraint as the reference, standard_workflow.py:386-404)."""
        from znicz_tpu.core.avatar import Avatar
        real = self.loader
        avatar = Avatar(self, loader=real, extra_attrs=tuple(extra_attrs),
                        name="avatar")
        parents = list(real.links_from)
        real.unlink_all()  # the producer thread drives the real loader
        # and remove it from the unit container: the snapshotter must not
        # pickle loader state the producer thread is mutating (and which
        # runs AHEAD of the consumed stream).  Trade-off vs the plain
        # loader: snapshots of avatar workflows restart the data stream
        # at an epoch boundary instead of the exact minibatch position.
        self.del_ref(real)
        if parents:
            avatar.link_from(*parents)
        self.real_loader = real
        self.loader = avatar
        return avatar

    def link_meandispnorm(self, *parents):
        """On-the-fly minibatch normalization from the loader's
        mean/rdisp arrays (reference standard_workflow.py:603-624);
        wire the forwards from its ("input", "output")."""
        from znicz_tpu.units.mean_disp_normalizer import \
            MeanDispNormalizer
        self.meandispnorm = MeanDispNormalizer(self, name="meandispnorm")
        self.meandispnorm.link_attrs(
            self.loader, ("input", "minibatch_data"), "mean", "rdisp")
        self.meandispnorm.link_from(*parents)
        return self.meandispnorm

    def link_gd_diff_stats(self, *parents, **kwargs):
        """Gradient-statistics probe over the backward chain
        (reference standard_workflow.py:626-646).  The history is
        flushed to ``file_name`` when the workflow finishes."""
        from znicz_tpu.units.diff_stats import DiffStats
        kwargs.setdefault("arrays",
                          {u: ("gradient_weights",)
                           for u in self.gds if u is not None})
        self.gd_diff_stats = DiffStats(self, name="gd_diff_stats",
                                       **kwargs)
        self.gd_diff_stats.link_from(*parents)
        self.gd_diff_stats.gate_skip = self.decision.gd_skip
        self.on_workflow_finished(self.gd_diff_stats.flush)
        return self.gd_diff_stats

    def link_downloader(self, *parents, **kwargs):
        """(reference standard_workflow.py:407-411)"""
        from znicz_tpu.core.downloader import Downloader
        self.downloader = Downloader(self, name="downloader", **kwargs)
        self.downloader.link_from(*parents)
        return self.downloader

    def link_ipython(self, *parents):
        """Between-epochs interactive shell
        (reference standard_workflow.py:648-661)."""
        from znicz_tpu.core.interaction import Shell
        self.ipython = Shell(self, name="shell")
        self.ipython.link_from(*parents)
        self.ipython.gate_skip = ~self.decision.epoch_ended
        return self.ipython

    def link_publisher(self, *parents, **kwargs):
        """End-of-training report (reference standard_workflow.py:663-670)."""
        from znicz_tpu.core.publishing import Publisher
        self.publisher = Publisher(self, name="publisher", **kwargs)
        self.publisher.link_from(*parents)
        self.publisher.result_providers.add(self.decision)
        self.publisher.loader_unit = getattr(self, "real_loader",
                                             self.loader)
        self.publisher.gate_skip = ~self.decision.complete
        return self.publisher

    def link_data_saver(self, *parents, **kwargs):
        """Record the observed minibatch stream
        (reference standard_workflow.py:1121-1149)."""
        from znicz_tpu.loader.saver import MinibatchesSaver
        self.data_saver = MinibatchesSaver(self, name="data_saver",
                                           **kwargs)
        self.data_saver.link_attrs(
            self.loader, "minibatch_data", "minibatch_labels",
            "minibatch_class", "minibatch_size", "class_lengths",
            "max_minibatch_size", "has_labels", "epoch_ended")
        self.data_saver.link_from(*parents)
        return self.data_saver

    def link_end_point(self, *parents):
        self.end_point.link_from(*parents)
        self.end_point.gate_block = ~self.decision.complete
        return self.end_point

    # -- inference extraction (reference 210-286) ---------------------------
    def extract_forward_workflow(self, loader_name=None, loader_config=None,
                                 loader_factory=None):
        """Build a forward-only workflow with this one's weights copied in
        via the master-slave broadcast protocol
        (reference standard_workflow.py:282-286)."""
        from znicz_tpu.export import refuse_token_kinds
        refuse_token_kinds(self.layers)
        kwargs = dict(layers=self.layers, preprocessing=False)
        if loader_name is not None:
            kwargs["loader_name"] = loader_name
        elif loader_factory is not None:
            kwargs["loader_factory"] = loader_factory
        else:
            kwargs["loader_factory"] = self.loader_factory
        if loader_config is not None:
            kwargs["loader_config"] = loader_config
        fwd_wf = StandardWorkflowBase(None, **kwargs)
        fwd_wf.create_workflow()
        if self.fused_trainer is not None:
            # fused params map 1:1 onto the layer list — inject through
            # the same master->slave broadcast entry point
            params = self.fused_trainer.host_params()
            for fwd_imp, p in zip(fwd_wf.forwards, params):
                if p:
                    fwd_imp.apply_data_from_master(
                        [p.get("w"), p.get("b")])
                fwd_imp.forward_mode = True
            return fwd_wf
        for fwd_exp, fwd_imp in zip(self.forwards, fwd_wf.forwards):
            data = fwd_exp.generate_data_for_slave(None)
            if data is not None:
                fwd_imp.apply_data_from_master(data)
            fwd_imp.forward_mode = True
        return fwd_wf
