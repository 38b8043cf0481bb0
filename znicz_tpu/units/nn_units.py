"""NN unit base classes + the type-string registry.

TPU-era equivalent of the reference's nn_units.py (854 LoC — SURVEY.md §2.1).
Provides:

* ``Match``/``MatchingObject`` — the registry keystone: every forward unit
  declares ``MAPPING = {"type-string"}``; backward units register under the
  same names; ``StandardWorkflowBase`` instantiates from config via this
  mapping (reference nn_units.py:64-107).
* ``Forward`` — weight/bias init (filling, stddev), package_export, weight
  broadcast protocol (reference nn_units.py:119-211).
* ``GradientDescentBase`` — every GD hyperparameter (lr/wd/l1_vs_l2/moment/
  accumulate alpha-beta/ortho), per-layer optimizer state, gradient protocol
  (reference nn_units.py:339-724).  The update algebra itself lives in
  :mod:`znicz_tpu.ops.gd_math` so the jitted fused path and the
  unit-at-a-time path share one implementation.
* ``NNWorkflow`` — repeater/loader/forwards/evaluator/decision/gds slots
  (reference nn_units.py:727-805).
* ``NNSnapshotterBase``/``ToFile`` — tensor-stat logging + NaN/inf detection
  on every snapshot (reference nn_units.py:808-854).
"""

import numpy

from znicz_tpu.core.accelerated_units import (
    AcceleratedUnit, AcceleratedWorkflow)
from znicz_tpu.core.distributable import IDistributable
from znicz_tpu.core.memory import Array
from znicz_tpu.core import health
from znicz_tpu.core import profiler
from znicz_tpu.core import prng
from znicz_tpu.core.snapshotter import SnapshotterToFile
from znicz_tpu.core.workflow import Repeater
from znicz_tpu.ops import gd_math


class Match(object):
    """One registry row: the forward class + its backward classes."""

    def __init__(self):
        self._forward = None
        self._backwards = []

    @property
    def forward(self):
        if self._forward is None:
            raise KeyError("no forward unit registered")
        return self._forward

    @property
    def backwards(self):
        """Iterator over registered GD classes (reference semantics:
        standard_workflow.py:336 takes ``next(...)``)."""
        return iter(self._backwards)

    @property
    def has_forward(self):
        return self._forward is not None


#: The global type-string registry.
mapping = {}


class MatchingObject(type):
    """Metaclass registering classes by their MAPPING type strings."""

    def __init__(cls, name, bases, clsdict):
        super(MatchingObject, cls).__init__(name, bases, clsdict)
        types = clsdict.get("MAPPING", None)
        if not types or clsdict.get("hide_from_registry"):
            return
        if not isinstance(types, (set, frozenset)):
            raise TypeError(
                "%s.MAPPING must be a set of type strings, got %s"
                % (name, type(types).__name__))
        for tpe in types:
            match = mapping.setdefault(tpe, Match())
            if getattr(cls, "_registry_role", None) == "backward":
                match._backwards.append(cls)
            else:
                if match._forward is not None and match._forward is not cls:
                    raise ValueError(
                        "duplicate forward registration for %r" % tpe)
                match._forward = cls


def fill_array(rand, filling, array, stddev):
    """Weight-init fillings (reference all2all.py:119-127) — shared by the
    unit path and the fused path so init parity holds by construction."""
    if filling == "uniform":
        rand.fill(array, -stddev, stddev)
    elif filling == "gaussian":
        rand.fill_normal_real(array, 0, stddev)
    elif filling == "constant":
        array[:] = stddev
    else:
        raise ValueError("Invalid filling type %s" % filling)


def weights_magnitude(c, n_in, n_out, filling="uniform"):
    """Initial-weight range heuristic (reference all2all.py:106-117)."""
    vle = numpy.sqrt(c / (n_in + n_out))
    if filling == "gaussian":
        vle /= 3
    return vle


def as_nhwc(arr):
    """4D NHWC view of a 3D (B, H, W) or 4D array — the implicit
    single-channel convention shared by every spatial unit (the reference
    derives channels from size, conv.py:159-160)."""
    if arr.ndim == 3:
        return arr.reshape(arr.shape + (1,))
    return arr


class ForwardBase(AcceleratedUnit, metaclass=MatchingObject):
    """Base for forward-propagation units."""
    hide_from_registry = True
    MAPPING = set()
    _registry_role = "forward"


class Forward(ForwardBase, IDistributable):
    """Forward unit with weights/bias (reference nn_units.py:119-211)."""

    hide_from_registry = True
    MAPPING = set()

    def __init__(self, workflow, **kwargs):
        kwargs.setdefault("view_group", "WORKER")
        super(Forward, self).__init__(workflow, **kwargs)
        self.weights_stddev = kwargs.get("weights_stddev")
        self.bias_stddev = kwargs.get("bias_stddev", self.weights_stddev)
        self.weights_filling = kwargs.get("weights_filling", "uniform")
        self.bias_filling = kwargs.get("bias_filling", "uniform")
        self.rand = kwargs.get("rand", prng.get())
        self.weights_transposed = kwargs.get("weights_transposed", False)
        self.include_bias = kwargs.get("include_bias", True)
        self.demand("input")
        self.output = Array(name="output")
        self.weights = Array(name="weights")
        self.bias = Array(name="bias")
        self.forward_mode = False
        self.exports = ["weights", "bias", "include_bias",
                        "weights_transposed"]

    def fill_array(self, filling, array, stddev):
        fill_array(self.rand, filling, array, stddev)

    def package_export(self):
        """Public-state dict for deployment packages
        (reference nn_units.py:152-161)."""
        data = {}
        for attr in self.exports:
            value = getattr(self, attr, None)
            if value is None:
                continue
            if isinstance(value, Array):
                if not value:
                    continue
                value = numpy.array(value.mem)
            data[attr] = value
        return data

    # -- weight broadcast protocol (reference nn_units.py:178-208) ----------
    def generate_data_for_slave(self, slave=None):
        if self.forward_mode:
            return None
        data = [None, None]
        if self.weights:
            data[0] = numpy.array(self.weights.mem)
        if self.bias:
            data[1] = numpy.array(self.bias.mem)
        return data

    def apply_data_from_master(self, data):
        if self.forward_mode:
            return
        if data[0] is not None:
            if self.weights:
                self.weights.map_invalidate()
                numpy.copyto(self.weights.mem, data[0])
            else:
                self.weights.reset(numpy.array(data[0]))
        if data[1] is not None:
            if self.bias:
                self.bias.map_invalidate()
                numpy.copyto(self.bias.mem, data[1])
            else:
                self.bias.reset(numpy.array(data[1]))


class NNLayerBase(Forward):
    """Adds the generic run-and-log behavior (reference nn_units.py:214)."""
    hide_from_registry = True
    MAPPING = set()


class FullyConnectedOutput(object):
    """Output-geometry mixin (reference nn_units.py:248-296)."""

    def __init__(self, *args, **kwargs):
        super(FullyConnectedOutput, self).__init__(*args, **kwargs)
        self._output_sample_shape = tuple()
        self.output_sample_shape = kwargs.get("output_sample_shape", tuple())
        self.output_samples_number = kwargs.get("output_samples_number")
        self.output_dtype = kwargs.get("output_dtype")

    @property
    def output_sample_shape(self):
        return self._output_sample_shape

    @output_sample_shape.setter
    def output_sample_shape(self, value):
        if isinstance(value, (int, numpy.integer)):
            self._output_sample_shape = (int(value),)
        elif hasattr(value, "shape"):
            self._output_sample_shape = tuple(value.shape[1:])
        elif hasattr(value, "__iter__"):
            self._output_sample_shape = tuple(value)
        else:
            raise TypeError("Unsupported output_sample_shape type: %s"
                            % type(value))

    @property
    def output_samples_number(self):
        if getattr(self, "input", None):
            return self.input.shape[0]
        return self._output_samples_number

    @output_samples_number.setter
    def output_samples_number(self, value):
        self._output_samples_number = value

    @property
    def output_shape(self):
        return (self.output_samples_number,) + self.output_sample_shape

    @property
    def neurons_number(self):
        return int(numpy.prod(self.output_sample_shape))


class GradientDescentWithActivation(object):
    """Mixin: backward starts by err_output *= f'(output)
    (reference nn_units.py:299-334)."""

    ACTIVATION = "linear"

    def __init__(self, workflow, **kwargs):
        super(GradientDescentWithActivation, self).__init__(workflow, **kwargs)
        # The chain-rule pre-step reads the forward's activation output;
        # fail at initialize, not mid-run (reference nn_units.py:299-306).
        self.demand("output")


class GradientDescentBase(AcceleratedUnit, IDistributable,
                          metaclass=MatchingObject):
    """Base for backward (gradient-descent) units.

    Parity: every hyperparameter and the full update algebra of the
    reference (nn_units.py:339-724); the math itself is
    :func:`znicz_tpu.ops.gd_math.update`.
    """

    hide_from_registry = True
    MAPPING = set()
    _registry_role = "backward"

    def __init__(self, workflow, **kwargs):
        kwargs.setdefault("view_group", "TRAINER")
        super(GradientDescentBase, self).__init__(workflow, **kwargs)
        self.err_input = Array(name="err_input")
        self.weights = None
        self.bias = None
        self.output = None
        self.demand("input", "err_output")
        self.learning_rate = kwargs.get("learning_rate", 0.01)
        self.learning_rate_bias = kwargs.get("learning_rate_bias",
                                             self.learning_rate)
        self.weights_decay = kwargs.get("weights_decay", 0.00005)
        self.weights_decay_bias = kwargs.get("weights_decay_bias", 0.0)
        self.l1_vs_l2 = kwargs.get("l1_vs_l2", 0)
        self.l1_vs_l2_bias = kwargs.get("l1_vs_l2_bias", self.l1_vs_l2)
        self.gradient_moment = kwargs.get("gradient_moment", 0)
        self.gradient_moment_bias = kwargs.get("gradient_moment_bias",
                                               self.gradient_moment)
        self.weights_transposed = kwargs.get("weights_transposed", False)
        self.err_input_alpha = kwargs.get("err_input_alpha", 1.0)
        self.err_input_beta = kwargs.get("err_input_beta", 0.0)
        self.need_err_input = kwargs.get("need_err_input", True)
        self.need_gradient_weights = kwargs.get("need_gradient_weights", True)
        self.include_bias = kwargs.get("include_bias", True)
        self.factor_ortho = kwargs.get("factor_ortho", 0)
        self.accumulate_gradient = kwargs.get("accumulate_gradient", False)
        self.acc_alpha = kwargs.get("acc_alpha", 0.0)
        self.acc_beta = kwargs.get("acc_beta", 0.0)
        self.gd_alpha = kwargs.get("gd_alpha", 0.0)
        self.gd_beta = kwargs.get("gd_beta", 1.0)
        self.solvers = frozenset(kwargs.get("solvers", ()))
        self.variant_gradient = kwargs.get("variant_gradient", True)
        self.variant_moment_gradient = kwargs.get(
            "variant_moment_gradient", True)
        # Reference-visible state arrays
        self.gradient_weights = Array(name="gradient_weights")
        self.gradient_bias = Array(name="gradient_bias")
        self.accumulated_gradient_weights = Array()
        self.accumulated_gradient_bias = Array()
        self.gradient_weights_with_moment = Array()
        self.gradient_bias_with_moment = Array()
        self.gradient_changed = False
        self.apply_gradient = kwargs.get("apply_gradient",
                                         not workflow.is_slave)
        #: optimizer state in snapshots (velocity/accumulator restore makes
        #: resumed momentum training exact)
        self.exports = ["gradient_weights_with_moment",
                        "gradient_bias_with_moment",
                        "accumulated_gradient_weights",
                        "accumulated_gradient_bias"]
        # jax-side optimizer state pytrees (device-resident twins)
        self._jstate_w = None
        self._jstate_b = None

    @property
    def current_batch_size(self):
        batch_size = getattr(self, "batch_size", None)
        if batch_size is None:
            return self.err_output.shape[0]
        return int(batch_size)

    def initialize(self, device=None, **kwargs):
        super(GradientDescentBase, self).initialize(device=device, **kwargs)
        for attr in ("learning_rate", "weights_decay", "gradient_moment",
                     "learning_rate_bias", "weights_decay_bias",
                     "gradient_moment_bias"):
            setattr(self, attr, kwargs.get(attr, getattr(self, attr)))

        if self.need_gradient_weights and self.weights:
            if not self.gradient_weights:
                self.gradient_weights.reset(
                    numpy.zeros_like(self.weights.mem))
            if self.accumulate_gradient and \
                    not self.accumulated_gradient_weights:
                self.accumulated_gradient_weights.reset(
                    numpy.zeros_like(self.weights.mem))
            if (self.gradient_moment or not self.is_standalone or
                    self.solvers) and not self.gradient_weights_with_moment:
                self.gradient_weights_with_moment.reset(
                    numpy.zeros_like(self.weights.mem))
        if (self.need_gradient_weights and self.include_bias and self.bias):
            if not self.gradient_bias:
                self.gradient_bias.reset(numpy.zeros_like(self.bias.mem))
            if self.accumulate_gradient and not self.accumulated_gradient_bias:
                self.accumulated_gradient_bias.reset(
                    numpy.zeros_like(self.bias.mem))
            if (self.gradient_moment_bias or not self.is_standalone or
                    self.solvers) and not self.gradient_bias_with_moment:
                self.gradient_bias_with_moment.reset(
                    numpy.zeros_like(self.bias.mem))
        if self.need_err_input and not self.err_input:
            self.err_input.reset(numpy.zeros(self.input.shape,
                                             self.err_output.dtype))
        self._solver_state_np = {}
        for key, ref in (("weights", self.weights), ("bias", self.bias)):
            if ref is None or not ref:
                continue
            # acc/vel live in the reference-visible Arrays above; only the
            # solver slots come from the shared allocator.
            self._solver_state_np[key] = gd_math.init_state(
                ref.mem, {"solvers": self.solvers, "accumulate": False,
                          "need_vel": False})

    # -- shared update plumbing --------------------------------------------
    def _hyper(self, bias=False):
        if bias:
            return dict(lr=self.learning_rate_bias,
                        wd=self.weights_decay_bias,
                        l1_vs_l2=self.l1_vs_l2_bias,
                        moment=self.gradient_moment_bias,
                        acc_alpha=self.acc_alpha, acc_beta=self.acc_beta,
                        gd_alpha=self.gd_alpha, gd_beta=self.gd_beta,
                        factor_ortho=0.0)
        return dict(lr=self.learning_rate, wd=self.weights_decay,
                    l1_vs_l2=self.l1_vs_l2, moment=self.gradient_moment,
                    acc_alpha=self.acc_alpha, acc_beta=self.acc_beta,
                    gd_alpha=self.gd_alpha, gd_beta=self.gd_beta,
                    factor_ortho=float(self.factor_ortho))

    def _flags(self, bias=False):
        return dict(accumulate=bool(self.accumulate_gradient),
                    apply=bool(self.apply_gradient),
                    solvers=self.solvers,
                    # ortho regularizes weight ROWS — never the 1-D bias
                    ortho=bool(self.factor_ortho) and not bias,
                    variant_moment=self.variant_moment_gradient)

    def _numpy_apply_update(self, which):
        """Run the update algebra on host for 'weights' or 'bias'."""
        vec = getattr(self, which)
        grad = getattr(self, "gradient_" + which)
        acc = getattr(self, "accumulated_gradient_" + which)
        vel = getattr(self, "gradient_%s_with_moment" % which)
        state = {"acc": acc.mem if acc else None,
                 "vel": vel.mem if vel else None}
        state.update(self._solver_state_np.get(which, {}))
        hyper = self._hyper(bias=(which == "bias"))
        vec.map_write()
        new_w, new_state = gd_math.update_numpy(
            vec.mem, grad.mem, state, hyper,
            self._flags(bias=(which == "bias")))
        vec.mem[...] = new_w
        if acc and new_state.get("acc") is not None:
            acc.map_write()
            acc.mem[...] = new_state["acc"]
        if vel and new_state.get("vel") is not None:
            vel.map_write()
            vel.mem[...] = new_state["vel"]
        for k in self._solver_state_np.get(which, {}):
            self._solver_state_np[which][k] = new_state[k]

    def _jax_apply_update(self, which, grad_dev):
        """Run the update algebra on device for 'weights' or 'bias'."""
        vec = getattr(self, which)
        acc = getattr(self, "accumulated_gradient_" + which)
        vel = getattr(self, "gradient_%s_with_moment" % which)
        stash_attr = "_jstate_w" if which == "weights" else "_jstate_b"
        state = getattr(self, stash_attr)
        if state is None:
            state = {"acc": acc.dev if acc else None,
                     "vel": vel.dev if vel else None}
            for k, v in self._solver_state_np.get(which, {}).items():
                import jax
                state[k] = jax.device_put(v)
        hyper = self._hyper(bias=(which == "bias"))
        flags = self._flags(bias=(which == "bias"))
        if profiler.enabled():
            # cost registry: the GD update kernel's lowered FLOPs/bytes
            # (dedup'd by name — one lowering per unit+tensor, reusing
            # the trace the dispatch below needs anyway)
            gd_math.register_update_cost(
                "gd.update.%s.%s" % (self.name, which),
                vec.dev, grad_dev, state, hyper, flags)
        new_w, new_state = gd_math.update_jax(
            vec.dev, grad_dev, state, hyper, flags)
        if self.apply_gradient:
            vec.set_dev(new_w)
        setattr(self, stash_attr, new_state)
        if acc and new_state.get("acc") is not None:
            acc.set_dev(new_state["acc"])
        if vel and new_state.get("vel") is not None:
            vel.set_dev(new_state["vel"])

    # -- master-slave gradient protocol (reference nn_units.py:644-694) ----
    def generate_data_for_slave(self, slave=None):
        return (self.learning_rate, self.weights_decay, self.gradient_moment,
                self.learning_rate_bias, self.weights_decay_bias,
                self.gradient_moment_bias)

    @staticmethod
    def fill_zeros(vector):
        if not vector:
            return
        vector.map_invalidate()
        vector.mem[:] = 0

    def apply_data_from_master(self, data):
        (self.learning_rate, self.weights_decay, self.gradient_moment,
         self.learning_rate_bias, self.weights_decay_bias,
         self.gradient_moment_bias) = data
        for v in (self.gradient_weights_with_moment,
                  self.gradient_bias_with_moment,
                  self.gradient_weights, self.gradient_bias,
                  self.accumulated_gradient_weights,
                  self.accumulated_gradient_bias):
            self.fill_zeros(v)
        self._jstate_w = self._jstate_b = None

    def generate_data_for_master(self):
        if not self.gradient_changed:
            return None
        self.gradient_changed = False
        return (numpy.array(self.gradient_weights_with_moment.mem)
                if self.gradient_weights_with_moment else None,
                numpy.array(self.gradient_bias_with_moment.mem)
                if self.gradient_bias_with_moment else None)

    def apply_data_from_slave(self, data, slave=None):
        if self.weights and data[0] is not None:
            self.weights.map_write()
            self.gradient_weights_with_moment.map_write()
            self.gradient_weights_with_moment.mem *= self.gradient_moment
            self.gradient_weights_with_moment.mem += data[0]
            self.weights.mem += self.gradient_weights_with_moment.mem
        if self.bias and data[1] is not None:
            self.bias.map_write()
            self.gradient_bias_with_moment.map_write()
            self.gradient_bias_with_moment.mem *= self.gradient_moment_bias
            self.gradient_bias_with_moment.mem += data[1]
            self.bias.mem += self.gradient_bias_with_moment.mem

    def run(self):
        self.gradient_changed = True
        super(GradientDescentBase, self).run()
        if health.enabled():
            # per-update numeric check (interval-gated inside): reads
            # whichever side of each Array is authoritative, so the jax
            # path stays device-resident and pays only the tiny flag
            # readback
            health.check_gd_unit(self)


class NNWorkflow(AcceleratedWorkflow):
    """Workflow with the canonical NN slots (reference nn_units.py:727-805)."""

    def __init__(self, workflow=None, **kwargs):
        super(NNWorkflow, self).__init__(workflow, **kwargs)
        self.repeater = Repeater(self, name="repeater")
        self.loader = None
        self.forwards = []
        self.evaluator = None
        self.decision = None
        self.gds = []


class NNSnapshotterBase(SnapshotterToFile):
    """Snapshotter that logs min/max/avg of every exported tensor and
    detects NaN/inf (reference nn_units.py:808-854)."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        super(NNSnapshotterBase, self).__init__(workflow, **kwargs)
        self.skip = kwargs.get("skip", None)  # extra Bool gate

    def _log_attr(self, name, value):
        if not isinstance(value, numpy.ndarray) or value.size == 0:
            return
        mn, mx, avg = value.min(), value.max(), value.mean()
        self.debug("%s: min %.6f max %.6f avg %.6f", name, mn, mx, avg)
        if numpy.isnan(value).any() or numpy.isinf(value).any():
            self.warning("NaN/inf detected in %s", name)

    def export(self):
        state = self.collect_state()
        for uname, ustate in state.items():
            for attr, value in ustate.items():
                self._log_attr("%s.%s" % (uname, attr), value)
        # pass the collected state through: the epoch_acc export's
        # host_fetch drains the async pipeline — one drain per capture
        return super(NNSnapshotterBase, self).export(units_state=state)

    def run(self):
        if self.skip is not None and bool(self.skip):
            return
        super(NNSnapshotterBase, self).run()


class NNSnapshotterToFile(NNSnapshotterBase):
    MAPPING = "nnfile"


def load_snapshot_into_workflow(state, workflow):
    """Resume helper: apply a snapshot state dict onto a built workflow.

    Restores per-unit exports (weights, optimizer state, decision stats,
    loader position) and the PRNG stream states, making
    train-snapshot-resume-retrain bit-exact on the numpy path.
    """
    if "prng" in state:
        from znicz_tpu.core import prng
        prng.restore(state["prng"])
    from znicz_tpu.core import telemetry
    telemetry.record_event("snapshot.restore",
                           workflow=getattr(workflow, "name", None),
                           suffix=state.get("suffix"))
    units = {u.name: u for u in workflow.units}
    for uname, ustate in state["units"].items():
        u = units.get(uname)
        if u is None:
            continue
        for attr, value in ustate.items():
            cur = getattr(u, attr, None)
            if isinstance(cur, Array):
                if value is not None:
                    cur.reset(numpy.array(value))
            else:
                try:
                    setattr(u, attr, value)
                except AttributeError:
                    pass
    _map_cross_mode_state(state, workflow)


def _map_cross_mode_state(state, workflow):
    """Snapshots restore across EXECUTION MODES: fused params map 1:1
    onto the layer list, so a fused-mode snapshot restored into a
    unit-graph workflow injects its weights into the forwards (via the
    broadcast protocol, like extract_forward_workflow) and vice versa.
    Optimizer state does not transfer between representations — warn,
    because momentum restarts cold."""
    snap_units = state.get("units", {})
    fused_state = snap_units.get("fused_trainer", {}).get("fused_state")
    trainer = getattr(workflow, "fused_trainer", None)
    forwards = [f for f in getattr(workflow, "forwards", ())]
    if fused_state is not None and trainer is None and forwards:
        workflow.warning(
            "snapshot was written in FUSED mode; mapping its params onto "
            "the unit graph (optimizer momentum restarts cold — pass "
            "--fused to resume bit-exactly)")
        for fwd, p in zip(forwards, fused_state.get("params", ())):
            if p and hasattr(fwd, "apply_data_from_master"):
                fwd.apply_data_from_master([p.get("w"), p.get("b")])
        return
    if fused_state is None and trainer is not None and \
            "fused_trainer" not in snap_units:
        # unit-graph snapshot into a fused run: collect per-forward
        # weights saved under their unit names (the builder names them
        # "<layer name>_forward" / "<type>_<i>_forward",
        # standard_workflow_base._get_layer_type_kwargs)
        params = []
        ok = False
        for i, layer in enumerate(trainer.layers):
            tpe = layer.get("type")
            name = (layer["name"] + "_forward") if "name" in layer \
                else "%s_%d_forward" % (tpe, i)
            ustate = snap_units.get(name, {})
            p = {}
            if ustate.get("weights") is not None:
                p["w"] = numpy.array(ustate["weights"])
                ok = True
                if ustate.get("bias") is not None:
                    p["b"] = numpy.array(ustate["bias"])
            params.append(p)
        if ok:
            workflow.warning(
                "snapshot was written in UNIT-GRAPH mode; mapping its "
                "weights onto the fused trainer (optimizer momentum "
                "restarts cold — drop --fused to resume bit-exactly)")
            sd = trainer.fused_state
            if sd is not None:
                for tgt, src in zip(sd["params"], params):
                    for k, v in src.items():
                        if k in tgt and tgt[k].shape == v.shape:
                            tgt[k] = v.astype(tgt[k].dtype)
                trainer.fused_state = sd
