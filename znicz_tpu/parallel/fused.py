"""Fused SPMD training — one jitted XLA computation per minibatch.

SURVEY.md §7 design stance: the unit graph remains the epoch-level control
plane, but the hot loop — forward, loss gradient, backward, per-layer
update — compiles to a single XLA computation.  This module is the fused
path for whole feed-forward topologies: the FC family (reference
all2all.py:53-474 + gd.py:73-551), the conv family (conv.py:71-568 +
gd_conv.py:60-750), pooling (pooling.py:122-548), LRN (normalization.py),
standalone activations (activation.py) and dropout (dropout.py).

Parity: weight init matches the unit path exactly (magnitude heuristics
all2all.py:106-117 / conv.py:137-146, fill semantics all2all.py:119-127,
same PRNG draw order), and the update algebra is literally
:func:`znicz_tpu.ops.gd_math.update` with ``xp=jnp`` — the same function
the unit-at-a-time path runs.  Gradients come from ``jax.grad`` of the
softmax-CE loss, which reproduces the reference's hand-written chain rule
(verified by the float64 parity tests against the unit-graph path in
tests/unit/test_fused.py).

Sharding: parameters and inputs carry ``NamedSharding`` annotations over a
``(data, model)`` mesh; GSPMD inserts the gradient all-reduce (psum over
``data``) and the activation all-gathers (over ``model``) — the TPU-native
replacement for the reference's parameter-server broadcast/aggregate cycle
(nn_units.py:178-208, 644-694).  Conv parameters replicate (they are
small); wide FC layers shard over ``model``.
"""

from dataclasses import dataclass, field

import numpy

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import NamedSharding, PartitionSpec as P

from znicz_tpu.core import faults
from znicz_tpu.core import profiler
from znicz_tpu.core import prng
from znicz_tpu.core import telemetry
from znicz_tpu.parallel import mesh as mesh_mod
from znicz_tpu.ops import activations, gd_math
from znicz_tpu.ops import conv as conv_ops
from znicz_tpu.ops import pooling as pool_ops
from znicz_tpu.ops import normalization as norm_ops
from znicz_tpu.ops import transformer

#: the FC family (reference all2all.py classes); activation + magnitude
#: constants come from the registered unit classes — single source of truth
#: with the unit-graph path.
FC_TYPES = ("all2all", "all2all_tanh", "all2all_relu", "all2all_str",
            "all2all_sigmoid", "softmax")
CONV_TYPES = ("conv", "conv_tanh", "conv_sigmoid", "conv_relu", "conv_str")
#: stochastic variants sample winners from a jax PRNG key on the fused
#: path — same distribution as the unit path's host uint16 stream
#: (reference pooling.py:368-508), exact host-stream parity explicitly
#: waived like dropout's (docs/distributed.md)
POOL_TYPES = ("max_pooling", "maxabs_pooling", "avg_pooling",
              "stochastic_pooling", "stochastic_abs_pooling",
              "stochastic_pool_depool", "stochastic_abs_pool_depool")
_POOL_MODES = {"max_pooling": "max", "maxabs_pooling": "maxabs",
               "avg_pooling": "avg",
               "stochastic_pooling": "stochastic",
               "stochastic_abs_pooling": "stochasticabs",
               "stochastic_pool_depool": "stochastic_depool",
               "stochastic_abs_pool_depool": "stochasticabs_depool"}
ACTIVATION_TYPES = ("activation_tanh", "activation_sigmoid",
                    "activation_relu", "activation_str", "activation_log",
                    "activation_tanhlog", "activation_sincos")


def _forward_class(tpe):
    from znicz_tpu.units import nn_units
    import znicz_tpu.units  # noqa: F401 (registers every unit module)
    return nn_units.mapping[tpe].forward

#: strictly monotonically increasing activations — safe to commute past a
#: following max pooling (see forward()).  NOTE "relu" is excluded: the
#: reference's "relu" is log(1 + exp(x)) with a piecewise seam at x=15
#: (activations.py) and is not monotonic across the seam.
_MONOTONIC_ACTS = frozenset(("linear", "tanh", "sigmoid"))

DEFAULT_HYPER = dict(lr=0.01, wd=0.00005, l1_vs_l2=0.0, moment=0.0,
                     acc_alpha=0.0, acc_beta=0.0, gd_alpha=0.0, gd_beta=1.0,
                     factor_ortho=0.0)


def layer_hyper(layer, defaults=None):
    """(hyper, hyper_bias, flags) for one layer dict — the same parse
    ``build_specs`` runs: shared top-level keys merged under the "<-"
    backward kwargs (the reference routes shared kwargs to both sides,
    standard_workflow_base.py:406-422)."""
    layer = dict(layer)
    for k in ("type", "name", "->"):
        layer.pop(k, None)
    bwd = dict(layer.pop("<-", {}))
    merged = dict(layer)
    merged.update(bwd)
    return _parse_hyper(merged, dict(DEFAULT_HYPER, **(defaults or {})))


def _parse_hyper(bwd, defaults):
    """Extract (hyper, hyper_bias, flags) from a layer's "<-" dict —
    the reference backward-kwargs contract (standard_workflow_base.py:
    406-422)."""
    hyper = dict(defaults)
    hyper.update(
        lr=bwd.get("learning_rate", defaults["lr"]),
        wd=bwd.get("weights_decay", defaults["wd"]),
        l1_vs_l2=bwd.get("l1_vs_l2", defaults["l1_vs_l2"]),
        moment=bwd.get("gradient_moment", defaults["moment"]),
        acc_alpha=bwd.get("acc_alpha", defaults["acc_alpha"]),
        acc_beta=bwd.get("acc_beta", defaults["acc_beta"]),
        gd_alpha=bwd.get("gd_alpha", defaults["gd_alpha"]),
        gd_beta=bwd.get("gd_beta", defaults["gd_beta"]),
        factor_ortho=bwd.get("factor_ortho", defaults["factor_ortho"]))
    hyper_bias = dict(hyper)
    hyper_bias.update(
        lr=bwd.get("learning_rate_bias", hyper["lr"]),
        wd=bwd.get("weights_decay_bias", 0.0),
        l1_vs_l2=bwd.get("l1_vs_l2_bias", hyper["l1_vs_l2"]),
        moment=bwd.get("gradient_moment_bias", hyper["moment"]),
        factor_ortho=0.0)
    flags = dict(accumulate=bool(bwd.get("accumulate_gradient", False)),
                 apply=True,
                 solvers=frozenset(bwd.get("solvers", ())),
                 ortho=bool(hyper["factor_ortho"]),
                 variant_moment=bwd.get("variant_moment_gradient", True))
    if "adamw" in flags["solvers"]:
        # a solver's own hyperparameters ride the traced pytree of the
        # layers that ask for it, and of no other layer
        for k, v in gd_math.ADAMW_HYPER.items():
            hyper[k] = hyper_bias[k] = bwd.get(k, v)
    return hyper, hyper_bias, flags


@dataclass
class FCSpec:
    """One fully-connected layer of the fused stack."""
    type: str
    n_in: int
    n_out: int
    activation: str
    hyper: dict = field(default_factory=dict)        # weights hyper
    hyper_bias: dict = field(default_factory=dict)   # bias hyper
    flags: dict = field(default_factory=dict)
    weights_stddev: float = None
    bias_stddev: float = None
    weights_filling: str = "uniform"
    bias_filling: str = "uniform"
    include_bias: bool = True

    kind = "fc"

    @property
    def is_softmax(self):
        return self.type == "softmax"

    @property
    def out_shape(self):
        return (self.n_out,)

    def init_stddev(self):
        """Reference magnitude heuristic (all2all.py:106-117), using the
        registered unit class's C constant."""
        if self.weights_stddev is not None:
            return self.weights_stddev
        from znicz_tpu.units.nn_units import weights_magnitude
        vle = weights_magnitude(_forward_class(self.type).C,
                                self.n_in, self.n_out, self.weights_filling)
        return min(vle, 0.5)


@dataclass
class ConvSpec:
    """One convolutional layer (reference conv.py:71-475 geometry:
    NHWC, weights (n_kernels, ky*kx*C), padding LTRB, sliding (x, y))."""
    type: str
    in_shape: tuple      # sample (H, W, C)
    out_shape: tuple     # sample (ny, nx, K)
    n_kernels: int
    kx: int
    ky: int
    padding: tuple
    sliding: tuple
    activation: str
    hyper: dict = field(default_factory=dict)
    hyper_bias: dict = field(default_factory=dict)
    flags: dict = field(default_factory=dict)
    weights_stddev: float = None
    bias_stddev: float = None
    weights_filling: str = "uniform"
    bias_filling: str = "uniform"
    include_bias: bool = True
    max_supposed: float = 1.0

    kind = "conv"
    is_softmax = False

    @property
    def n_channels(self):
        return self.in_shape[2]

    def init_stddev(self):
        """Reference conv magnitude heuristic (conv.py:137-146), capped at
        0.05 like Conv.initialize."""
        if self.weights_stddev is not None:
            return self.weights_stddev
        vle = 1.0 / (self.max_supposed *
                     numpy.sqrt(self.kx * self.ky * self.n_channels))
        if self.weights_filling == "gaussian":
            vle /= 3
        return min(vle, 0.05)


@dataclass
class PoolSpec:
    """max / maxabs / avg pooling (reference pooling.py ceil-mode
    geometry; winner-take-all gradient comes from the VJP of the gather —
    the same scatter-add the unit path runs, gd_pooling.py:233-247).

    ``impl`` is the max-pool lowering:

    * "reduce_window" (DEFAULT): XLA select-and-scatter VJP; tie
      routing implementation-defined.  What every benchmark cell runs
      (its device time a step: PERF.md section 5, the ``L*.pool`` rows).
    * "gather": argmax + gather with a scatter-add VJP — the float64
      parity/golden tests use it (its backward's summation ORDER
      matches the unit path's scatter on overlapping windows).

    avg uses reduce_window (no ties to break)."""
    type: str
    in_shape: tuple
    out_shape: tuple
    mode: str            # "max" | "maxabs" | "avg" | stochastic modes
    kx: int
    ky: int
    sliding: tuple
    impl: str = "reduce_window"

    kind = "pool"
    is_softmax = False


@dataclass
class LRNSpec:
    """Cross-channel local response normalization (normalization.py)."""
    type: str
    in_shape: tuple
    out_shape: tuple
    alpha: float = 1e-4
    beta: float = 0.75
    k: float = 2.0
    n: int = 5

    kind = "lrn"
    is_softmax = False


@dataclass
class ActivationSpec:
    """Standalone activation layer (activation.py)."""
    type: str
    in_shape: tuple
    out_shape: tuple
    activation: str = "linear"

    kind = "activation"
    is_softmax = False


@dataclass
class DeconvSpec:
    """Transposed conv SHARING the weights of a tied conv layer
    (reference deconv.py:55-347 — Deconv always demands external
    weights).  ``tied`` is the spec index of the conv whose weights it
    applies; reference parity means the loss gradient reaches those
    weights ONLY through the deconv application (MnistAE/ImagenetAE
    train GDDeconv alone, mnist_ae.py:146-153), so the tied conv's own
    application runs under ``stop_gradient``."""
    type: str
    in_shape: tuple      # (ny, nx, K)
    out_shape: tuple     # (H, W, C) — the tied conv's input shape
    tied: int
    n_kernels: int
    kx: int
    ky: int
    padding: tuple
    sliding: tuple
    unsafe_padding: bool = False

    kind = "deconv"
    is_softmax = False


@dataclass
class DepoolSpec:
    """Depooling — scatters activations to the winner offsets recorded
    by the tied pooling layer during THIS forward pass (reference
    depooling.py:48-144; the offsets contract of OffsetPooling)."""
    type: str
    in_shape: tuple
    out_shape: tuple     # the tied pool's input shape
    tied: int            # spec index of the pooling whose offsets to use

    kind = "depool"
    is_softmax = False


@dataclass
class ZeroFillSpec:
    """Placeholder for a ``zero_filter`` layer (reference
    weights_zerofilling.py:46-137): identity in the forward chain; its
    grouping mask attaches to the NEXT parameterized spec (the unit
    graph links the next forward's weights into the ZeroFiller).  Kept
    as a spec so the spec list stays 1:1 with the layer list."""
    type: str
    in_shape: tuple
    out_shape: tuple
    grouping: int

    kind = "zerofill"
    is_softmax = False


@dataclass
class DropoutSpec:
    """Inverted dropout: keep-mask / (1 - ratio) in train mode
    (reference dropout.py:147-153; the fused path draws the mask from a
    jax PRNG key instead of the host stream — same Bernoulli(1-ratio)
    distribution, device-resident)."""
    type: str
    in_shape: tuple
    out_shape: tuple
    ratio: float = 0.5

    kind = "dropout"
    is_softmax = False


def flatten_layers(layers):
    """(leaf layers in order, topology) of a ``layers`` config.

    Two structural entries hold a sub-chain under ``"layers"``:
    ``{"type": "residual"}`` adds its input to the sub-chain's output
    (``"remat": True`` recomputes the sub-chain in the backward pass
    instead of keeping its activations), and ``{"type": "loop", "times":
    T}`` applies the sub-chain ``T`` times in sequence with ONE set of
    weights.  Specs, parameters, optimizer state and hyperparameters stay
    flat lists over the leaves; the topology is a list of nodes over their
    indices: ``i``, ``("residual", remat, [nodes])`` or ``("loop", times,
    [nodes])``.  It is None for a straight chain."""
    flat = []

    def walk(entries):
        nodes = []
        for layer in entries:
            tpe = layer.get("type")
            if tpe == "residual":
                nodes.append(("residual", bool(layer.get("remat", False)),
                              walk(layer["layers"])))
            elif tpe == "loop":
                times = int(layer.get("times", 1))
                if times < 1:
                    raise ValueError("loop times %d is invalid" % times)
                nodes.append(("loop", times, walk(layer["layers"])))
            else:
                nodes.append(len(flat))
                flat.append(layer)
        return nodes

    nodes = walk(layers)
    if all(isinstance(n, int) for n in nodes):
        return list(layers), None
    return flat, nodes


def _looped_leaves(topology):
    """The leaf indices that stand inside a loop entry."""
    found = set()

    def walk(nodes, inside):
        for node in nodes or ():
            if isinstance(node, int):
                if inside:
                    found.add(node)
            else:
                walk(node[2], inside or node[0] == "loop")

    walk(topology, False)
    return found


def _applications(topology, n_leaves):
    """How often a step applies each leaf (a loop runs its sub-chain
    ``times`` over)."""
    counts = [1 if topology is None else 0] * n_leaves

    def walk(nodes, times):
        for node in nodes or ():
            if isinstance(node, int):
                counts[node] += times
            else:
                walk(node[2], times * (node[1] if node[0] == "loop" else 1))

    walk(topology, 1)
    return counts


def _normalize_sample_shape(shape):
    if isinstance(shape, (int, numpy.integer)):
        return (int(shape),)
    shape = tuple(int(s) for s in shape)
    if len(shape) == 2:    # (H, W) -> single implicit channel, as_nhwc
        shape = shape + (1,)
    return shape


def build_specs(layers, input_sample_shape, defaults=None):
    """Build the spec list from a declarative ``layers`` config.

    Each entry is a dict with "type" plus forward kwargs (optionally under
    "->") and backward kwargs (under "<-") — the reference config format
    (standard_workflow_base.py:406-422).  Sample shapes thread through the
    conv/pooling geometry exactly as the unit graph's initialize() chain
    does.
    """
    defaults = dict(DEFAULT_HYPER, **(defaults or {}))
    layers, topology = flatten_layers(layers)
    looped = _looped_leaves(topology)
    specs = []
    names = {}  # layer name -> spec index (for tied deconv/depool)
    pending_grouping = None  # zero_filter masks the NEXT layer's weights
    shape = _normalize_sample_shape(input_sample_shape)
    for index, layer in enumerate(layers):
        orig_layer = layer
        layer = dict(layer)
        tpe = layer.pop("type")
        name = layer.pop("name", None) or "%s_%d" % (tpe, index)
        fwd = dict(layer.pop("->", {}))
        layer.pop("<-", None)
        fwd.update({k: v for k, v in layer.items()})
        if tpe in FC_TYPES:
            oshape = fwd.get("output_sample_shape",
                             fwd.get("output_samples"))
            if oshape is None:
                raise ValueError("layer %r needs output_sample_shape" % tpe)
            n_out = int(numpy.prod(oshape))
            # ONE merge implementation shared with the GDProxy
            # surface (units/fused_trainer.py seeds proxies from the
            # same parse)
            hyper, hyper_bias, flags = layer_hyper(orig_layer, defaults)
            specs.append(FCSpec(
                type=tpe, n_in=int(numpy.prod(shape)), n_out=n_out,
                activation=("linear" if tpe == "softmax"
                            else _forward_class(tpe).ACTIVATION),
                hyper=hyper, hyper_bias=hyper_bias, flags=flags,
                weights_stddev=fwd.get("weights_stddev"),
                bias_stddev=fwd.get("bias_stddev"),
                weights_filling=fwd.get("weights_filling", "uniform"),
                bias_filling=fwd.get("bias_filling", "uniform"),
                include_bias=fwd.get("include_bias", True)))
            shape = (n_out,)
        elif tpe in CONV_TYPES:
            if len(shape) != 3:
                raise ValueError(
                    "conv layer %r needs a (H, W, C) input, have %r"
                    % (tpe, shape))
            kx, ky = int(fwd["kx"]), int(fwd["ky"])
            n_kernels = int(fwd["n_kernels"])
            padding = tuple(fwd.get("padding", (0, 0, 0, 0)))
            sliding = tuple(fwd.get("sliding", (1, 1)))
            ny, nx = conv_ops.output_spatial(
                shape[0], shape[1], ky, kx, padding, sliding)
            # ONE merge implementation shared with the GDProxy
            # surface (units/fused_trainer.py seeds proxies from the
            # same parse)
            hyper, hyper_bias, flags = layer_hyper(orig_layer, defaults)
            specs.append(ConvSpec(
                type=tpe, in_shape=shape, out_shape=(ny, nx, n_kernels),
                n_kernels=n_kernels, kx=kx, ky=ky,
                padding=padding, sliding=sliding,
                activation=_forward_class(tpe).ACTIVATION,
                hyper=hyper, hyper_bias=hyper_bias, flags=flags,
                weights_stddev=fwd.get("weights_stddev"),
                bias_stddev=fwd.get("bias_stddev"),
                weights_filling=fwd.get("weights_filling", "uniform"),
                bias_filling=fwd.get("bias_filling", "uniform"),
                include_bias=fwd.get("include_bias", True),
                max_supposed=fwd.get("input_max_supposed", 1.0)))
            shape = (ny, nx, n_kernels)
        elif tpe in POOL_TYPES:
            if len(shape) != 3:
                raise ValueError(
                    "pooling layer %r needs a (H, W, C) input, have %r"
                    % (tpe, shape))
            kx, ky = int(fwd["kx"]), int(fwd["ky"])
            sliding = tuple(fwd.get("sliding") or (kx, ky))
            mode = _POOL_MODES[tpe]
            if mode.endswith("_depool"):
                # pool+depool runs in place: output keeps the input
                # shape (reference stochastic_pooling_depooling kernel)
                out_shape = shape
            else:
                ny, nx = pool_ops.output_spatial(
                    shape[0], shape[1], ky, kx, sliding)
                out_shape = (ny, nx, shape[2])
            specs.append(PoolSpec(
                type=tpe, in_shape=shape, out_shape=out_shape,
                mode=mode, kx=kx, ky=ky, sliding=sliding))
            shape = out_shape
        elif tpe in transformer.KINDS:
            hyper, hyper_bias, flags = layer_hyper(orig_layer, defaults)
            if tpe == "lm_head":
                # the exit gate is a looped model's: a head that no loop
                # runs has none, and no dead leaves
                fwd["exit_gate"] = index in looped
            specs.append(transformer.build(tpe, fwd, shape, hyper,
                                           hyper_bias, flags, name))
            shape = specs[-1].out_shape
        elif tpe == "norm":
            if len(shape) != 3:
                raise ValueError(
                    "LRN layer needs a (H, W, C) input, have %r" % (shape,))
            specs.append(LRNSpec(
                type=tpe, in_shape=shape, out_shape=shape,
                alpha=fwd.get("alpha", 1e-4), beta=fwd.get("beta", 0.75),
                k=fwd.get("k", 2), n=fwd.get("n", 5)))
        elif tpe in ACTIVATION_TYPES:
            specs.append(ActivationSpec(
                type=tpe, in_shape=shape, out_shape=shape,
                activation=_forward_class(tpe).ACTIVATION))
        elif tpe == "dropout":
            specs.append(DropoutSpec(
                type=tpe, in_shape=shape, out_shape=shape,
                ratio=fwd.get("dropout_ratio", 0.5)))
        elif tpe == "zero_filter":
            pending_grouping = int(fwd.get("grouping", 2))
            if pending_grouping < 2:
                raise ValueError("grouping value %d is invalid"
                                 % pending_grouping)
            specs.append(ZeroFillSpec(
                type=tpe, in_shape=shape, out_shape=shape,
                grouping=pending_grouping))
        elif tpe == "deconv":
            tied_name = fwd.get("tied_to")
            if tied_name is None or tied_name not in names:
                raise ValueError(
                    "fused deconv needs tied_to=<conv layer name> "
                    "(the reference Deconv always shares weights, "
                    "deconv.py:55)")
            tied = names[tied_name]
            conv_spec = specs[tied]
            if conv_spec.kind != "conv":
                raise ValueError("tied_to %r is not a conv layer"
                                 % tied_name)
            if shape != conv_spec.out_shape:
                raise ValueError(
                    "deconv input %r != tied conv output %r"
                    % (shape, conv_spec.out_shape))
            out_shape = conv_spec.in_shape
            # the deconv runs in the tied conv's geometry — padding
            # included (reference AE stages link_conv_attrs copy the
            # conv's CONV_ATTRS onto the Deconv, mnist_ae.py:148-151)
            sl = conv_spec.sliding
            kx, ky = conv_spec.kx, conv_spec.ky
            padding = tuple(conv_spec.padding)
            # reference parity: only the deconv application trains the
            # shared weights (GDDeconv is the sole gradient unit in the
            # AE stages) — mark the conv to stop_gradient its own use
            conv_spec.stop_gradient = True
            # a "<-" on the deconv governs the SHARED weights' update
            # (reference: GDDeconv's kwargs), overriding the conv's
            if orig_layer.get("<-"):
                (conv_spec.hyper, conv_spec.hyper_bias,
                 conv_spec.flags) = layer_hyper(orig_layer, defaults)
            specs.append(DeconvSpec(
                type=tpe, in_shape=shape, out_shape=out_shape, tied=tied,
                n_kernels=conv_spec.n_kernels, kx=kx, ky=ky,
                padding=padding, sliding=sl,
                unsafe_padding=fwd.get("unsafe_padding", False)))
            shape = out_shape
        elif tpe == "depooling":
            tied_name = fwd.get("tied_to")
            if tied_name is None or tied_name not in names:
                raise ValueError(
                    "fused depooling needs tied_to=<pooling layer name>")
            tied = names[tied_name]
            pool_spec = specs[tied]
            if pool_spec.kind != "pool" or pool_spec.mode not in (
                    "max", "maxabs", "stochastic", "stochasticabs"):
                raise ValueError(
                    "tied_to %r is not an offset-recording pooling"
                    % tied_name)
            if shape != pool_spec.out_shape:
                raise ValueError(
                    "depooling input %r != tied pool output %r"
                    % (shape, pool_spec.out_shape))
            # the tied max pool must run the gather path to yield
            # offsets (stochastic pools always record winners)
            pool_spec.impl = "gather"
            pool_spec.record_offsets = True
            specs.append(DepoolSpec(
                type=tpe, in_shape=shape, out_shape=pool_spec.in_shape,
                tied=tied))
            shape = pool_spec.in_shape
        else:
            raise ValueError("fused path does not support layer type %r"
                             % tpe)
        names[name] = len(specs) - 1
        spec = specs[-1]
        if pending_grouping is not None and spec.kind in ("fc", "conv"):
            # the zero_filter grouping mask for this layer's weights
            # (reference mask: (k % G != c % G), zerofilling.py)
            if spec.kind == "fc":
                kernels, chans = spec.n_out, spec.n_in
            else:
                kernels = spec.n_kernels
                chans = spec.kx * spec.ky * spec.n_channels
            g = pending_grouping
            if chans % g:
                raise ValueError(
                    "Non-multiple of grouping weights shape: (%d, %d), "
                    "grouping=%d" % (kernels, chans, g))
            krow = numpy.arange(kernels)[:, None] % g
            ccol = numpy.arange(chans)[None, :] % g
            spec.weight_mask = (krow != ccol).astype(numpy.float64)
            pending_grouping = None
    return specs


def build_fc_specs(layers, input_sample_size, defaults=None):
    """FC-only builder (back-compat): rejects non-FC layer types."""
    specs = build_specs(layers, int(input_sample_size), defaults)
    for spec in specs:
        if spec.kind != "fc":
            raise ValueError("fused FC path does not support layer type %r"
                             % spec.type)
    return specs


def init_params(specs, rand=None, dtype=numpy.float32):
    """Host-side init with the unit path's exact draw order and fill
    semantics (weights then bias per layer, all2all.py:119-127 /
    conv.py:100-111; param-less layers draw nothing)."""
    rand = rand or prng.get()
    params = []
    for spec in specs:
        if spec.kind == "fc":
            w_shape = (spec.n_out, spec.n_in)
            n_bias = spec.n_out
        elif spec.kind == "conv":
            w_shape = (spec.n_kernels,
                       spec.kx * spec.ky * spec.n_channels)
            n_bias = spec.n_kernels
        elif spec.kind in transformer.KINDS:
            params.append(transformer.init(spec, rand, dtype, _fill))
            continue
        else:
            params.append({})
            continue
        stddev = spec.init_stddev()
        bias_stddev = spec.bias_stddev if spec.bias_stddev is not None \
            else stddev
        w = numpy.zeros(w_shape, dtype=dtype)
        _fill(rand, spec.weights_filling, w, stddev)
        p = {"w": w}
        if spec.include_bias:
            b = numpy.zeros(n_bias, dtype=dtype)
            _fill(rand, spec.bias_filling, b, bias_stddev)
            p["b"] = b
        params.append(p)
    return params


def _fill(rand, filling, array, stddev):
    from znicz_tpu.units.nn_units import fill_array
    fill_array(rand, filling, array, stddev)


def init_opt_state(specs, params):
    """Optimizer-state pytree mirroring the per-layer Arrays of the unit
    path (vel = gradient_*_with_moment, acc, solver slots)."""
    states = []
    for spec, p in zip(specs, params):
        # a leaf the optimizer does not know (a ``moe`` entry's selection
        # bias) has no state
        mine = transformer.leaf_hypers(spec) \
            if spec.kind in transformer.KINDS else p
        states.append({name: gd_math.init_state(
            leaf, dict(spec.flags, need_vel=True)) if name in mine else {}
            for name, leaf in p.items()})
    return states


def layer_scope(i, spec):
    """``L00.conv``: the ``jax.named_scope`` of spec ``i``'s ops — names
    at trace time only (HLO ``op_name`` metadata; the compiled program
    and its cache key are the same with or without them).  Device
    traces are read per layer by these names (docs/observability.md)."""
    return "L%02d.%s" % (i, spec.kind)


def forward(params, x, specs, return_logits=False, key=None, train=False,
            compute_dtype=None):
    """Pure forward pass through the whole spec stack.

    With ``return_logits`` the softmax head is left un-normalized (for the
    CE loss); otherwise softmax is applied.  ``key``/``train`` drive
    dropout masks; inference leaves dropout as identity (reference
    dropout.py:84-190 TRAIN gating).

    ``compute_dtype`` (e.g. ``jnp.bfloat16``) casts activations and
    parameters at each matmul/conv so the GEMMs run at the MXU's native
    rate; master parameters stay float32 and the softmax/loss math is
    always done in float32.
    """
    cd = compute_dtype

    def _p(arr):
        return arr if (cd is None or arr is None) else arr.astype(cd)

    y = x if cd is None else x.astype(cd)
    deferred_act = None  # activation commuted past a following max-pool
    offsets = {}         # spec index -> winner offsets (for tied depool)
    for i, (p, spec) in enumerate(zip(params, specs)):
        if deferred_act is not None and spec.kind != "pool":
            raise AssertionError("deferred activation not consumed")
        # trace-time only: the layer's ops (and, through autodiff, its
        # backward ops as transpose(jvp(L00.conv))) carry this name in
        # their HLO op_name, so a device trace can be read per layer
        with jax.named_scope(layer_scope(i, spec)):
            if spec.kind == "fc":
                y = y.reshape(y.shape[0], -1)
                w = _p(p["w"])
                mask = getattr(spec, "weight_mask", None)
                if mask is not None:
                    w = w * jnp.asarray(mask, w.dtype)
                y = y @ w.T
                if "b" in p:
                    y = y + _p(p["b"])
                if not spec.is_softmax:
                    y = activations.apply_jax(spec.activation, y)
                elif not return_logits:
                    if cd is not None:
                        y = y.astype(jnp.float32)
                    y = jax.nn.softmax(y, axis=1)
            elif spec.kind == "conv":
                y = y.reshape((y.shape[0],) + spec.in_shape)
                w = _p(p["w"])
                mask = getattr(spec, "weight_mask", None)
                if mask is not None:
                    w = w * jnp.asarray(mask, w.dtype)
                if getattr(spec, "stop_gradient", False):
                    # weights shared with a tied deconv: only the DECONV
                    # application trains them (reference AE stages run
                    # GDDeconv as the sole gradient unit)
                    w = jax.lax.stop_gradient(w)
                act = spec.activation
                # strictly monotonic activations commute with max pooling
                # (max(f(x)) == f(max(x)), bit-exact for the same winner);
                # applying f AFTER the pool does 1/(kx*ky) the transcendental
                # + HBM work — the dominant non-GEMM cost on TPU
                if (act in _MONOTONIC_ACTS
                        and i + 1 < len(specs)
                        and specs[i + 1].kind == "pool"
                        and specs[i + 1].mode == "max"):
                    deferred_act, act = act, "linear"
                y = conv_ops.forward_jax(
                    y, w, _p(p.get("b")), spec.ky, spec.kx,
                    spec.padding, spec.sliding, activation=act,
                    include_bias="b" in p)
            elif spec.kind == "pool":
                y = y.reshape((y.shape[0],) + spec.in_shape)
                if spec.mode.startswith("stochastic"):
                    # winners sampled from the jax PRNG key (distribution
                    # parity with the unit path's host uint16 stream,
                    # reference pooling.py:434-480; exact stream parity
                    # waived like dropout's) — the SAME op as the unit jax
                    # path, fed device-drawn u16s
                    if key is None:
                        raise ValueError(
                            "stochastic pooling needs a PRNG key (fused nets "
                            "with stochastic specs thread one through "
                            "predict too)")
                    key, sub = jax.random.split(key)
                    b = y.shape[0]
                    if spec.mode.endswith("_depool"):
                        ny, nx = pool_ops.output_spatial(
                            spec.in_shape[0], spec.in_shape[1], spec.ky,
                            spec.kx, (spec.kx, spec.ky))
                    else:
                        ny, nx, _ = spec.out_shape
                    n = b * ny * nx * spec.in_shape[2]
                    u16 = jax.random.randint(
                        sub, (n,), 0, 65536, dtype=jnp.int32).astype(
                            jnp.uint16)
                    use_abs = "abs" in spec.mode
                    if spec.mode.endswith("_depool"):
                        y, offs = pool_ops.stochastic_pool_depool_jax(
                            y, u16, spec.ky, spec.kx, use_abs=use_abs)
                    else:
                        y, offs = pool_ops.stochastic_pooling_jax(
                            y, u16, spec.ky, spec.kx, spec.sliding,
                            use_abs=use_abs)
                    offsets[i] = offs
                elif getattr(spec, "record_offsets", False):
                    y, offs = pool_ops.max_pooling_gather_jax(
                        y, spec.ky, spec.kx, spec.sliding,
                        use_abs=spec.mode == "maxabs")
                    offsets[i] = offs
                elif spec.mode != "avg" and spec.impl == "gather":
                    # gather path: gradient scatters to the FIRST maximum —
                    # exact tie parity with the unit path (flat regions tie;
                    # reduce_window's select-and-scatter routes ties
                    # implementation-defined, maxabs even breaks |tie|s
                    # toward the positive value).  NOT max_pooling_jax: that
                    # routes to the Pallas kernel, which has no autodiff rule
                    # (this forward is grad'd).
                    y, _ = pool_ops.max_pooling_gather_jax(
                        y, spec.ky, spec.kx, spec.sliding,
                        use_abs=spec.mode == "maxabs")
                else:
                    y = pool_ops.pooling_fwd_jax(
                        y, spec.ky, spec.kx, spec.sliding, mode=spec.mode)
                if deferred_act is not None:
                    y = activations.apply_jax(deferred_act, y)
                    deferred_act = None
            elif spec.kind == "deconv":
                y = y.reshape((y.shape[0],) + spec.in_shape)
                w = _p(params[spec.tied]["w"])
                out_shape = (y.shape[0],) + spec.out_shape
                y = conv_ops.deconv_forward_jax(
                    y, w, spec.ky, spec.kx, spec.padding, spec.sliding,
                    out_shape)
                if spec.unsafe_padding:
                    hits = conv_ops.deconv_hits_jax(
                        (y.shape[0],) + spec.in_shape[:2], spec.ky, spec.kx,
                        spec.padding, spec.sliding, out_shape)
                    div = y / jnp.maximum(hits, 1).astype(
                        y.dtype)[:, :, :, None]
                    # value = y/hits, gradient = identity: the reference
                    # GDDeconv backpropagates the UNDIVIDED scatter (the
                    # hits normalization is absent from gd_deconv's
                    # gradient, deconv.py/gd_deconv.py) — keep that parity
                    y = y + jax.lax.stop_gradient(div - y)
            elif spec.kind == "depool":
                y = y.reshape((y.shape[0],) + spec.in_shape)
                full = (y.shape[0],) + spec.out_shape
                y = pool_ops.max_pooling_backward_jax(
                    y, offsets[spec.tied],
                    int(numpy.prod(full)), full)
            elif spec.kind == "lrn":
                y = y.reshape((y.shape[0],) + spec.in_shape)
                y = norm_ops.lrn_forward_jax(
                    y, alpha=spec.alpha, beta=spec.beta, k=spec.k, n=spec.n)
            elif spec.kind == "activation":
                y = activations.apply_jax(spec.activation, y)
            elif spec.kind == "dropout":
                if train and key is not None:
                    key, sub = jax.random.split(key)
                    keep = jax.random.uniform(sub, y.shape) >= spec.ratio
                    y = y * keep.astype(y.dtype) / (1.0 - spec.ratio)
            elif spec.kind == "zerofill":
                pass  # identity: its mask is applied at the target layer
            else:  # pragma: no cover - build_specs rejects unknown kinds
                raise AssertionError(spec.kind)
    return y


def _run_nodes(nodes, params, specs, y, ctx):
    """Walk a topology (:func:`flatten_layers`) of token-sequence kinds:
    a leaf applies its kind under its ``L00.<kind>`` scope, a residual
    entry adds its sub-chain's output to its input, a loop entry scans its
    sub-chain ``times`` over ONE set of weights (the scan's body is traced
    and compiled once; the weights' gradients are summed over the passes
    by the scan's transpose) and stacks what the sub-chain's head emits.

    Two more things travel with the walk.  ``ctx["side"]``: values an
    entry leaves for a later one by name (a router's logits for its
    ``moe``); a sub-chain is handed its parents' as an argument, through
    ``jax.checkpoint`` and the scan, and what it adds stays inside it.
    ``ctx["routed"]``: what a ``moe`` entry reports (``load``, ``route``)
    and an ``attention`` entry that runs the TPU kernel (``blocks``: the
    steps its block map runs and the static map's), by leaf index; it
    leaves a sub-chain as a value, stacked over the passes of a loop."""
    ctx.setdefault("side", {})
    ctx.setdefault("routed", {})
    for node in nodes:
        if isinstance(node, int):
            spec = specs[node]
            if spec.kind not in transformer.KINDS:
                raise ValueError(
                    "layer type %r does not chain with the token-sequence "
                    "kinds" % spec.type)
            ctx["node"] = node
            with jax.named_scope(layer_scope(node, spec)):
                y = transformer.apply(spec, params[node], y, ctx)
            continue
        what, arg, body = node

        def sub_chain(y, side, body=body):
            sub = dict(ctx, emit={}, routed={}, side=dict(side))
            return (_run_nodes(body, params, specs, y, sub), sub["emit"],
                    sub["routed"])

        if what == "residual":
            def branch(y, side):
                out, emitted, routed = sub_chain(y, side)
                if emitted:
                    raise ValueError("an lm_head stands inside a residual "
                                     "entry")
                return out, routed
            if arg and ctx["train"]:
                branch = jax.checkpoint(branch)
            out, routed = branch(y, ctx["side"])
            y = y + out
        else:
            side = ctx["side"]
            if arg == 1:
                y, emitted, routed = sub_chain(y, side)
                emitted, routed = jax.tree.map(lambda a: a[None],
                                               (emitted, routed))
            else:
                def one_pass(y, _, side=side):
                    y, emitted, routed = sub_chain(y, side)
                    return y, (emitted, routed)
                y, (emitted, routed) = jax.lax.scan(one_pass, y, None,
                                                    length=arg)
            if emitted:
                if ctx["emit"]:
                    raise ValueError("one lm_head a net")
                ctx["emit"].update(emitted)
        ctx["routed"].update(routed)
    return y


def forward_tokens(params, ids, segments, labels, specs, topology,
                   compute_dtype=None, train=False, sample=None):
    """A token-sequence net's forward over ``ids (B, S)``: what its head
    emits for every position and pass (``ce``, ``pred``, ``gate``, each
    ``(T, B*S)``; with ``sample`` positions into the flattened batch also
    the normed state there, ``hidden (T, n, d)``).  ``segments (B, S)``
    cuts attention at document boundaries, ``labels (B, S)`` are the next
    ids (-1 where none is graded).  Logits never exist whole."""
    rope, maps = {}, {}
    for spec in specs:
        if spec.kind == "attention":
            key_ = (int(spec.attrs["head_dim"]),
                    float(spec.attrs.get("rope_base", 10000.0)))
            if key_ not in rope:
                rope[key_] = transformer.rope_tables(ids.shape[1], *key_)
            # the kernel's block maps of these rows and the steps they run,
            # one set a window: every layer under it, a loop's passes and a
            # recomputed forward share them
            window = spec.attrs.get("window")
            if window not in maps and transformer.kernel_suits(
                    ids.shape[1], key_[0]):
                maps[window] = transformer.block_maps(
                    segments, window, int(spec.attrs["heads"])
                    // int(spec.attrs["kv_heads"]))
    ctx = {"cd": compute_dtype, "segments": segments, "labels": labels,
           "train": train, "sample": sample, "rope": rope,
           "block_maps": maps, "emit": {}}
    nodes = topology if topology is not None else list(range(len(specs)))
    _run_nodes(nodes, params, specs, ids, ctx)
    emit = ctx["emit"]
    if not emit:
        raise ValueError("the token objective needs an lm_head")
    if emit["ce"].ndim == 1:
        emit = jax.tree.map(lambda a: a[None], emit)
    reports = [ctx["routed"][i] for i in sorted(ctx["routed"])]
    blocks = [r["blocks"].reshape(-1, 2) for r in reports if "blocks" in r]
    if blocks:
        # summed over the ``attention`` entries' applications
        emit["attention_blocks"] = jnp.concatenate(blocks).sum(axis=0)
    routed = [r for r in reports if "load" in r]
    if routed:
        # one row an application of a ``moe`` entry, in the chain's order
        emit["moe_load"] = jnp.concatenate([
            r["load"].reshape(-1, r["load"].shape[-1]) for r in routed])
        emit["moe_unserved"] = jnp.concatenate([
            r["unserved"].reshape(-1) for r in routed])
        # summed over the ``moe`` entries' applications
        emit["moe_rows"] = jnp.concatenate([
            r["rows"].reshape(-1, 2) for r in routed]).sum(
                axis=0, dtype=jnp.int32)
        emit["moe_route"] = jnp.concatenate([
            r["route"].reshape((-1,) + r["route"].shape[-2:])
            for r in routed])
        if all("weight" in r for r in routed):
            emit["moe_weight"] = jnp.concatenate([
                r["weight"].reshape(-1, r["weight"].shape[-1])
                for r in routed])
    return emit


#: what a step of a net with ``moe`` entries reports beside its loss and
#: counts: every application's load of every expert ``(entries, experts)``
#: its tokens none of whose experts is held here ``(entries,)`` and ``[rows
#: the entries' two row movements fetched forward, rows that movements over
#: all ``tokens x top_k`` pairs would have]``, which a window also sums into
#: the epoch's accumulator, and the choice made
#: ``(entries, tokens, top_k)`` int8 (int16 past 128 experts); where every
#: entry has a selection bias also the routing weight every expert took,
#: ``moe_weight (entries, experts)`` float32
MOE_COUNTS = ("moe_load", "moe_unserved", "moe_rows")
MOE_STATS = MOE_COUNTS + ("moe_route", "moe_weight")

#: what a step of a net with ``attention`` entries counts where they run the
#: TPU kernel: ``[steps the rows' forward block maps run, steps the static
#: map runs]`` over the entries' applications (zeros where none ran it); a
#: window sums it into the epoch's accumulator
ATTENTION_COUNTS = ("attention_blocks",)


def _token_stats(emit, labels, specs):
    """The token objective from a head's outputs, under the ``loss``
    scope: (loss, {"n_err": [errors, graded], "loss_sum"}, exit
    distribution)."""
    head = next(s for s in specs if s.kind == "lm_head")
    with jax.named_scope("loss"):
        loss, counts, loss_sum, probs = transformer.token_loss(
            emit, labels, float(head.attrs.get("exit_entropy_weight", 0.0)))
    return loss, {"n_err": counts, "loss_sum": loss_sum}, probs


def _train_step_tokens(params, state, ids, labels, segments, specs,
                       topology, compute_dtype=None, hypers=None,
                       sample=None):
    """One train step of the token objective.  Metrics: ``loss`` (mean
    over the minibatch's graded tokens), ``n_err`` ``[errors, graded]``,
    ``loss_sum``; with ``sample`` positions also every pass's normed state
    there and, of a gated head, the exit distribution; of a net with
    ``moe`` entries ``moe_load (entries, experts)`` and ``moe_route
    (entries, tokens, top_k)``."""
    def loss_fn(p):
        emit = forward_tokens(p, ids, segments, labels, specs, topology,
                              compute_dtype, train=True, sample=sample)
        loss, aux, probs = _token_stats(emit, labels, specs)
        if sample is not None:
            if probs is not None:
                aux["exit_sample"] = jnp.take(probs, sample, axis=1)
            aux["hidden_sample"] = emit["hidden"]
        aux.update({k: emit[k] for k in MOE_STATS + ATTENTION_COUNTS
                    if k in emit})
        return loss, aux

    (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    new_params, new_state = _apply_updates(
        specs, params, state, grads, hypers,
        _balanced_loads(specs, topology, aux.get("moe_load")))
    return new_params, new_state, dict(aux, loss=loss)


def _balanced(spec):
    """Whether ``spec`` is a ``moe`` entry whose selection bias the load
    moves."""
    return spec.kind == "moe" and spec.attrs.get("balance_rate") is not None


def _balanced_loads(specs, topology, moe_load):
    """{leaf index: (experts,) tokens this step sent to every expert} of
    the ``moe`` entries whose selection bias the load moves, from a step's
    ``moe_load (applications, experts)``; a loop's applications of one
    entry are summed."""
    loads, row = {}, 0
    for i, (spec, n) in enumerate(zip(specs, _applications(
            topology, len(specs)))):
        if _balanced(spec):
            loads[i] = moe_load[row:row + n].sum(axis=0)
        row += n * (spec.kind == "moe")
    return loads


def _loss_and_stats(params, x, labels, specs, key=None, compute_dtype=None):
    """Mean softmax-CE loss (matches evaluator err_output scaling,
    ops/evaluator.py) + error count + softmax output/argmax.  Loss math is
    float32 even when the forward GEMMs run in a lower ``compute_dtype``."""
    y = forward(params, x, specs, return_logits=True, key=key, train=True,
                compute_dtype=compute_dtype)
    with jax.named_scope("loss"):
        if compute_dtype is not None:
            y = y.astype(jnp.float32)
        logp = jax.nn.log_softmax(y, axis=1)
        valid = labels >= 0
        lbl = jnp.maximum(labels, 0)
        ce = -jnp.take_along_axis(logp, lbl[:, None], axis=1)[:, 0]
        ce = jnp.where(valid, ce, 0.0)
        loss = ce.sum() / jnp.maximum(valid.sum(), 1)
        max_idx = jnp.argmax(y, axis=1).astype(jnp.int32)
        n_err = (valid & (max_idx != lbl)).sum()
        probs = jnp.exp(logp)
    return loss, (n_err, probs, max_idx)


def _loss_and_stats_mse(params, x, target, batch_size, specs, key=None,
                        compute_dtype=None):
    """MSE objective: loss = sum((y-t)^2) / (2*batch) so that
    d(loss)/dy == (y - t)/batch — exactly the unit evaluator's
    ``err_output`` scaling (ops/evaluator.py mse, mean=True; reference
    evaluator.py:334-556).  Rows past ``batch_size`` (padded tail
    minibatch) are masked out like the evaluator does."""
    y = forward(params, x, specs, key=key, train=True,
                compute_dtype=compute_dtype)
    with jax.named_scope("loss"):
        if compute_dtype is not None:
            y = y.astype(jnp.float32)
        B = y.shape[0]
        o2 = y.reshape(B, -1)
        t2 = target.reshape(B, -1).astype(o2.dtype)
        valid = jnp.arange(B) < batch_size
        diff = jnp.where(valid[:, None], o2 - t2, 0)
        loss = 0.5 * (diff * diff).sum() / jnp.maximum(batch_size, 1)
    return loss, y


def _eval_stats(probs, max_idx, labels, batch_size, n_classes, mean,
                shards=1):
    """Evaluator-identical per-minibatch stats computed INSIDE the
    compiled window (ops/evaluator.softmax_ce_jax semantics, reference
    evaluator.py:271-312): n_err_delta[2], confusion_delta[C,C],
    max_err_output_sum.  Same masking (in-batch AND label >= 0) and the
    same ``err = (probs - onehot) * mult`` row math, so the windowed
    control plane accumulates the exact integers/floats the per-minibatch
    evaluator would.

    ``shards > 1`` (a data-parallel mesh): every reduction runs over the
    LOCAL batch rows only — outputs gain a leading ``shards`` axis
    (n_err[S,2], confusion[S,C,C], max_err_sum[S]) that stays sharded
    ``P("data", ...)``, so mid-epoch windows insert NO stats collective;
    the per-segment all-reduce folds the partials once, at the
    segment-final window (see _get_window_fn).  Integer partials reduce
    exactly; the max is order-independent — the sharded aggregates equal
    the single-device fold bit for bit (docs/distributed.md)."""
    B = probs.shape[0]
    idx = jnp.arange(B)
    in_batch = idx < batch_size
    valid = in_batch & (labels >= 0)
    hits = valid & (max_idx == labels)
    if shards == 1:
        n_total = valid.sum()
        n_ok = hits.sum()
        n_err2 = jnp.stack([n_total - n_ok, n_total]).astype(jnp.int32)
        onehot = jax.nn.one_hot(jnp.maximum(labels, 0), n_classes,
                                dtype=probs.dtype)
        # confusion[pred, label] += valid — as a one-hot GEMM, not a
        # scatter-add (TPU scatters with duplicate indices serialize; the
        # f32 accumulation is exact for counts < 2^24)
        pred_onehot = jax.nn.one_hot(max_idx, n_classes, dtype=jnp.float32)
        conf = ((pred_onehot * valid[:, None].astype(jnp.float32)).T
                @ onehot.astype(jnp.float32)).astype(jnp.int32)
        mult = jnp.where(mean, 1.0 / jnp.maximum(batch_size, 1), 1.0)
        err = (probs - onehot) * mult.astype(probs.dtype)
        mx = jnp.where(valid, jnp.abs(err).sum(axis=1), 0).max()
        return n_err2, conf, mx
    b = B // shards
    n_total = valid.reshape(shards, b).sum(axis=1)
    n_ok = hits.reshape(shards, b).sum(axis=1)
    n_err2 = jnp.stack([n_total - n_ok, n_total],
                       axis=-1).astype(jnp.int32)
    onehot = jax.nn.one_hot(jnp.maximum(labels, 0), n_classes,
                            dtype=probs.dtype)
    pred_onehot = jax.nn.one_hot(max_idx, n_classes, dtype=jnp.float32)
    pv = (pred_onehot * valid[:, None].astype(jnp.float32)).reshape(
        shards, b, n_classes)
    oh = onehot.astype(jnp.float32).reshape(shards, b, n_classes)
    # per-shard one-hot GEMM: the batch contraction stays inside the
    # shard's local rows — no cross-shard traffic
    conf = jnp.einsum("sbp,sbl->spl", pv, oh).astype(jnp.int32)
    mult = jnp.where(mean, 1.0 / jnp.maximum(batch_size, 1), 1.0)
    err = (probs - onehot) * mult.astype(probs.dtype)
    mx = jnp.where(valid, jnp.abs(err).sum(axis=1),
                   0).reshape(shards, b).max(axis=1)
    return n_err2, conf, mx


def _train_step_mse(params, state, x, target, batch_size, specs, key=None,
                    compute_dtype=None, hypers=None):
    params = _apply_weight_masks(params, specs)
    (loss, y), grads = jax.value_and_grad(
        lambda p: _loss_and_stats_mse(p, x, target, batch_size, specs,
                                      key, compute_dtype),
        has_aux=True)(params)
    new_params, new_state = _apply_updates(specs, params, state, grads,
                                           hypers)
    return new_params, new_state, {"loss": loss, "output": y}


def _pin_to_data(x, mesh):
    """Constrain a minibatch-leading array to the mesh's ``data`` axis
    inside a compiled program."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(
        mesh, P("data", *([None] * (x.ndim - 1)))))


def _gather_rows(data, idx):
    """The rows of the device-resident data set at ``idx`` (``-1`` marks
    a padded slot: it reads row 0 and is masked by the batch size
    downstream).  The train window and the indexed validation forward
    share this gather, and :meth:`FusedNet.set_dataset` stores the set in
    the layout its operand takes (:func:`gather_format`), so neither
    program copies the set to read it.  Returns the rows and the clamped
    indices."""
    with jax.named_scope("gather"):
        safe = jnp.maximum(idx, 0)
        return jnp.take(data, safe, axis=0), safe


def gather_format(shape, dtype, sharding, minibatch):
    """Two ``Format`` s (layout and sharding) of a set of ``shape`` and
    ``dtype`` under ``sharding``, from one compile of :func:`_gather_rows`
    over ``minibatch`` rows of each: the one the compiler gives the
    gather's operand when it may choose, and the one the runtime places
    such a set in when nothing is said.  Nothing runs and nothing is
    placed; a described device serves as an attached one does
    (tests/unit/test_tpu_compile.py)."""
    data = jax.ShapeDtypeStruct(shape, dtype)
    compiled = jax.jit(
        lambda free, placed, idx: (_gather_rows(free, idx)[0],
                                   _gather_rows(placed, idx)[0]),
        in_shardings=(Format(Layout.AUTO, sharding), sharding, sharding)
    ).lower(data, data,
            jax.ShapeDtypeStruct((minibatch,), jnp.int32)).compile()
    return compiled.input_formats[0][:2]


#: blocks of rows that :func:`_store` writes a set in.  Their programs wait
#: in line behind the set's own crossing, and the TPU runtime lets 32
#: programs wait: the 33rd dispatch blocks the host until the first has run
#: (28 s behind AlexNet's 5.2 GB; my chip run, PR 31)
_STORE_BLOCKS = 16


def _store(src, fmt, dtype):
    """The device array ``src`` cast to ``dtype`` in the layout of ``fmt``,
    written a block of rows at a time (``_STORE_BLOCKS`` of them) into one
    buffer that every call hands on (donated) with the row the next one
    starts at: nothing but ``src``, the stored set and one block is ever
    live, nothing crosses from the host, and the host waits for nothing
    (all of it is enqueued behind the set's own crossing, as the cast
    always was).  A cast of the whole set into another layout keeps a
    third copy (the compiler converts, then copies: arguments 5.34 + temp
    2.67 + output 3.01 GB for AlexNet's, compiled for a described v5e;
    a loop over blocks inside one program does too), and so does a whole
    relayout behind the cast unless the host first waits 24 s for the set
    to cross, which the first window program's load otherwise hides (my
    chip runs, PR 31).

    The two programs are never written to jax's persistent compilation
    cache.  Loaded from there (jax 0.9.0) an executable hands its outputs
    back LABELLED with the default layout, whatever layout their bytes
    have, and every program then compiled for that label reads the set
    wrongly (the CPU) or is refused its buffer (the TPU: my chip run,
    PR 31); compiled anew they take half a second."""
    n = len(src)
    rows = -(-n // _STORE_BLOCKS)

    def put(buf, start, src):
        block = jax.lax.dynamic_slice_in_dim(src, start, rows)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, block.astype(dtype), start, 0)
        # the last block ends with the set: it may write some rows again
        return buf, jnp.minimum(start + rows, n - rows)

    floor = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, floor)
    jax.config.update(floor, float("inf"))
    try:
        buf, start = jax.jit(
            lambda: (jnp.zeros(src.shape, dtype), jnp.int32(0)),
            out_shardings=(fmt, fmt.sharding))()
        put = jax.jit(put, out_shardings=(fmt, fmt.sharding),
                      donate_argnums=(0, 1))
        for _ in range(-(-n // rows)):
            buf, start = put(buf, start, src)
    finally:
        jax.config.update(floor, was)
    return buf


def _gather_token_rows(data, lbl_all, idx):
    """A minibatch of the resident token rows at ``idx``: ids, labels (-1
    all along a padded slot), segment ids, and the count of real rows as a
    ``(1,)`` int32."""
    x, safe = _gather_rows(data, idx)
    with jax.named_scope("gather"):
        lbl = jnp.where((idx < 0)[:, None], jnp.int32(-1),
                        jnp.take(lbl_all[0], safe, axis=0))
        seg = jnp.take(lbl_all[1], safe, axis=0)
        rows = (idx >= 0).sum()[None].astype(jnp.int32)
    return x, lbl, seg, rows


class ShardMajorWindow(object):
    """A host-staged ``(K, B, ...)`` window laid out SHARD-MAJOR:
    ``base`` has shape ``(S, K, B // S, ...)`` where ``S`` is the data-
    parallel shard count, so each shard's rows are one contiguous host
    block (``base[s]``) and :meth:`FusedNet._place_window` can feed
    ``device_put`` per-shard memcpys instead of strided splits of a
    batch-major stack (units/fused_trainer.py allocates these via the
    staging ring; Loader.fill_window_slot writes straight into the
    per-step ``base[:, i]`` views)."""

    __slots__ = ("base",)

    def __init__(self, base):
        self.base = base

    @property
    def shape(self):
        """The LOGICAL (K, B, ...) window shape."""
        s, k, b = self.base.shape[:3]
        return (k, s * b) + tuple(self.base.shape[3:])

    @property
    def ndim(self):
        return self.base.ndim - 1


def _nbytes(*trees):
    """Bytes of the trees' host leaves: a leaf that is a ``jax.Array``
    already lies on the device and crosses nothing."""
    return sum(leaf.nbytes for leaf in jax.tree.leaves(trees)
               if hasattr(leaf, "nbytes")
               and not isinstance(leaf, jax.Array))


def _h2d_span(name, *host_trees):
    """The span of one host → device placement; with telemetry on, the
    bytes placed go on the span and on the ``transfer.h2d`` meter (as
    ``host_fetch`` meters ``d2h``)."""
    if not telemetry.enabled():
        return telemetry.span(name)
    nbytes = _nbytes(*host_trees)
    telemetry.add_bytes("h2d", nbytes)
    return telemetry.span(name, bytes=nbytes)


def reduce_window_partials(stats, objective):
    """Host-side fold of per-shard window partials (leading ``S`` axis,
    see ``_eval_stats(shards=...)``) into the single-device aggregate
    shapes — the synchronous control plane's per-window host reduce
    under a data mesh (the async path folds the same reduction into the
    segment-final window executable instead)."""
    out = dict(stats)
    if objective == "mse":
        m = numpy.asarray(stats["metrics"])
        out["metrics"] = numpy.stack(
            [m[:, 0].sum(), m[:, 1].max(), m[:, 2].min()])
        out["n_err"] = numpy.asarray(stats["n_err"]).sum(axis=0)
    else:
        out["n_err"] = numpy.asarray(stats["n_err"]).sum(axis=0)
        if "confusion" in stats:
            out["confusion"] = numpy.asarray(
                stats["confusion"]).sum(axis=0)
        if "max_err_sum" in stats:
            out["max_err_sum"] = numpy.asarray(
                stats["max_err_sum"]).max(axis=0)
    return out


def flops_per_image(specs):
    """Analytic forward FLOPs per sample (matmul/conv MACs × 2) — the
    basis for the bench's MFU estimate (train step ≈ 3 × forward)."""
    total = 0
    for spec in specs:
        if spec.kind == "fc":
            total += 2 * spec.n_in * spec.n_out
        elif spec.kind == "conv":
            ny, nx, k = spec.out_shape
            total += 2 * ny * nx * k * spec.kx * spec.ky * spec.n_channels
        elif spec.kind == "deconv":
            ny, nx, k = spec.in_shape
            total += 2 * ny * nx * k * spec.kx * spec.ky * spec.out_shape[2]
    return total


class FusedNet:
    """Compiled trainer for a feed-forward spec stack over an optional
    device mesh."""

    def __init__(self, layers, input_sample_shape, mesh=None, rand=None,
                 dtype=numpy.float32, defaults=None, dropout_seed=0,
                 compute_dtype=None, pool_impl=None,
                 objective="softmax"):
        self.specs = build_specs(layers, input_sample_shape, defaults)
        #: how the flat specs chain (None: one after another)
        _, self.topology = flatten_layers(layers)
        #: (applications of ``moe`` entries in a step, experts) bool: the
        #: experts an application holds here (a loop runs its own ``times``
        #: over); the rows of the experts' load
        self.moe_held = numpy.array([
            [transformer.holds(spec.attrs, e)
             for e in range(int(spec.attrs["experts"]))]
            for spec, n in zip(self.specs, _applications(
                self.topology, len(self.specs)))
            if spec.kind == "moe" for _ in range(n)], dtype=bool)
        self._moe_entries = len(self.moe_held)
        #: the ``moe`` entries whose selection bias the load moves
        self._balanced = [i for i, spec in enumerate(self.specs)
                          if _balanced(spec)]
        self._attention_entries = sum(
            spec.kind == "attention" for spec in self.specs)
        if pool_impl not in (None, "reduce_window", "gather"):
            raise ValueError(
                "pool_impl=%r is gone: the code lowers max pooling as "
                "reduce_window, and \"gather\" stays as the tests' "
                "reference for the summation order" % (pool_impl,))
        if pool_impl is not None:
            for spec in self.specs:
                if spec.kind == "pool" and \
                        not getattr(spec, "record_offsets", False):
                    spec.impl = pool_impl
        self.compute_dtype = compute_dtype
        self.input_sample_shape = _normalize_sample_shape(input_sample_shape)
        self.objective = objective
        #: master-parameter dtype (the forward's output dtype when no
        #: compute_dtype is forced)
        self.dtype = dtype
        #: evaluator ``mean`` flag mirrored into the in-scan stats
        #: (window mode) — the trainer unit copies it from the linked
        #: evaluator before initialize
        self.stats_mean = True
        #: compiled window functions keyed by (n_steps, mode[, batch])
        self._window_fns = {}
        #: device-resident epoch accumulators for the decision aggregates
        #: (n_err / confusion / max_err_sum, or the MSE [sum,max,min]
        #: metrics + class-target n_err).  Every window executable takes
        #: the running accumulator as a donated argument and returns the
        #: folded total under ``stats["acc"]`` — the asynchronous control
        #: plane reads them back ONCE per segment instead of per window
        #: (units/fused_trainer.py).  None = zeros on the next window.
        self._win_acc = None
        #: window length -> (the stacked host hyper pytree last placed,
        #: its placed form): see :meth:`_place_window_scalars`
        self._placed_hypers = {}
        self._data_d = None
        #: the compiler's Format for the row gather's operand, which the
        #: resident set is stored in (:meth:`set_dataset`)
        self._data_format = None
        #: without a mesh: the sharding that parameters, optimizer state,
        #: key and accumulators are committed to once a relaid set is
        #: (:meth:`set_dataset`); None leaves them uncommitted, as jax
        #: places by default
        self._home = None
        self._labels_d = None
        #: per-epoch materialized permutation of the device dataset
        #: (set_epoch_perm) — consumed by contiguous dynamic slices
        self._data_p = None
        self._labels_p = None
        self._targets_d = None
        self._targets_p = None
        #: the token objective's segment ids, beside data and labels
        self._segments_d = None
        #: positions a caller asks the next indexed windows' logits at
        #: (tokens objective; see :meth:`run_window_indexed`): set around
        #: the windows a comparison checks, None otherwise
        self.sample_positions = None
        self._sample_logits = None
        self._perm_fns = {}
        #: MSE extras mirrored from the evaluator by the trainer unit
        #: BEFORE the first window: per-sample sqrt (EvaluatorMSE.root)
        #: and the optional nearest-class-target matrix
        self.mse_root = True
        self.class_targets = None
        if objective == "softmax":
            if not self.specs[-1].is_softmax:
                raise ValueError(
                    "the fused softmax objective needs a 'softmax' head "
                    "(got %r); pass objective='mse' for regression/AE "
                    "topologies." % self.specs[-1].type)
            if any(s.is_softmax for s in self.specs[:-1]):
                raise ValueError(
                    "softmax is only supported as the head of a fused net")
        elif objective == "mse":
            if any(s.is_softmax for s in self.specs):
                raise ValueError(
                    "the mse objective does not take a softmax head")
        elif objective == "tokens":
            if sum(s.kind == "lm_head" for s in self.specs) != 1 or any(
                    s.kind not in transformer.KINDS for s in self.specs):
                raise ValueError(
                    "the tokens objective takes a chain of %s with one "
                    "lm_head" % ", ".join(sorted(transformer.KINDS)))
            if mesh is not None:
                raise ValueError("the tokens objective runs on one device "
                                 "(no mesh yet)")
        else:
            raise ValueError("unknown objective %r" % objective)
        if objective != "tokens" and (self.topology is not None or any(
                s.kind in transformer.KINDS for s in self.specs)):
            raise ValueError("the token-sequence kinds and the residual / "
                             "loop entries train under objective='tokens'")
        self.mesh = mesh
        #: data-parallel shard count (1 without a mesh).  When > 1 the
        #: windowed epoch accumulators keep a leading shard axis
        #: (sharded P("data", ...)) and mid-epoch windows run with ZERO
        #: stats collectives; the segment-final window folds the one
        #: all-reduce per segment (_get_window_fn final=True).
        self._dp = 1 if mesh is None else int(mesh.shape["data"])
        params_host = init_params(self.specs, rand, dtype)
        states_host = init_opt_state(self.specs, params_host)
        self.params = self._place_params(params_host)
        # state slots shard exactly like their parameter (vel mirrors w);
        # mismatched initial placement would force a second full compile
        # when the donated step returns GSPMD-sharded state.
        self.state = self._place_state(states_host)
        self._key = jax.random.PRNGKey(dropout_seed)
        if mesh is not None:
            # replicate the key over the mesh up front: a default single-
            # device placement would differ from the sharding the compiled
            # step/scan returns, costing a recompile on the second call
            self._key = jax.device_put(
                self._key, NamedSharding(mesh, P()))
        self._has_dropout = any(s.kind == "dropout" for s in self.specs)
        self._has_stochastic = any(
            s.kind == "pool" and s.mode.startswith("stochastic")
            for s in self.specs)
        #: specs that consume PRNG draws per step (dropout masks,
        #: stochastic-pool winners) advance the key chain
        self._needs_key = self._has_dropout or self._has_stochastic
        #: live hyperparameters — mutated by LR schedules / rollback and
        #: passed to the jitted step as traced scalars (no recompile)
        self.hypers = default_hypers(self.specs)
        # specs close over the traced functions (they carry dicts, so they
        # can't be hashable static args); only the FLAGS stay compile-time
        # constants — hyper values are traced arguments.
        specs = tuple(self.specs)
        if objective == "mse":
            step_fn = lambda p, s, x, t, bs, k, hy: _train_step_mse(  # noqa: E731,E501
                p, s, x, t, bs, specs, k, compute_dtype, hy)
        else:
            step_fn = lambda p, s, x, l, k, hy: _train_step(  # noqa: E731
                p, s, x, l, specs, k, compute_dtype, hy, with_output=True)
        #: multi-host: batch-sharded outputs are not fully addressable
        #: for device_get.  The WINDOW outputs stay data-sharded (they
        #: are read only on segment-final windows — replicating inside
        #: every compiled window would pay a per-window DCN all-gather
        #: for unread buffers) and :meth:`host_fetch` reshards at
        #: readback; the PREDICT outputs are consumed every call, so
        #: those jits return replicated directly
        self._replicate_outputs = (mesh is not None
                                   and jax.process_count() > 1)
        if mesh is not None:
            # Pin output shardings to the input placements: GSPMD would
            # otherwise return spec variants (P('model',) vs
            # P('model', None)) that hash differently and force a second
            # full compile of the donated step.
            pshard = [{k: NamedSharding(mesh, self._param_spec(s, k))
                       for k in p} for s, p in zip(self.specs, self.params)]
            sshard = [{k: {kk: NamedSharding(mesh, self._param_spec(s, k))
                           for kk in slots.keys()}
                       for k, slots in st.items()}
                      for s, st in zip(self.specs, self.state)]
            out_ndim = 1 + len(self.specs[-1].out_shape)
            rep = NamedSharding(mesh, P())
            oshard = NamedSharding(
                mesh, P("data", *([None] * (out_ndim - 1))))
            ishard = NamedSharding(mesh, P("data"))
            if objective == "mse":
                mshard = {"loss": rep, "output": oshard}
            else:
                mshard = {"loss": rep, "n_err": rep,
                          "output": oshard, "max_idx": ishard}
            self._pshard, self._sshard = pshard, sshard
            self._step = jax.jit(step_fn, donate_argnums=(0, 1),
                                 out_shardings=(pshard, sshard, mshard))
        else:
            self._pshard = self._sshard = None
            self._step = jax.jit(step_fn, donate_argnums=(0, 1))
        # stochastic-pool nets sample winners at inference too (reference
        # StochasticPooling draws on every run, pooling.py:368-460) — the
        # compiled forward takes a key; others keep the keyless signature
        fwd_kw = {}
        if self._replicate_outputs:
            # inference outputs are host-read by the evaluator — same
            # multi-host addressability rule as the train-step outputs
            fwd_kw["out_shardings"] = NamedSharding(mesh, P())

        def fwd(p, x, k=None):
            return forward(p, x, specs, key=k, compute_dtype=compute_dtype)

        def fwd_idx(p, x, k=None):
            probs = fwd(p, x, k)
            return probs, jnp.argmax(probs, axis=1).astype(jnp.int32)

        def rows(data, idx):
            # the train window's own gather and pin (_get_window_fn body)
            x, _ = _gather_rows(data, idx)
            return _pin_to_data(x, mesh) if self._dp > 1 else x

        # the same two forwards over rows gathered from the resident
        # data set (:meth:`predict_indexed`)
        def fwd_at(p, data, idx, k=None):
            return fwd(p, rows(data, idx), k)

        def fwd_idx_at(p, data, idx, k=None):
            return fwd_idx(p, rows(data, idx), k)

        topology = self.topology

        def fwd_tokens_at(p, data, labels, segments, idx, k=None):
            # a validation minibatch of the token objective: its counts
            # and loss sum, never its logits
            x, lbl, seg, rows = _gather_token_rows(
                data, (labels, segments), idx)
            emit = forward_tokens(p, x, seg, lbl, specs, topology,
                                  compute_dtype)
            _, stats, _ = _token_stats(emit, lbl, specs)
            with jax.named_scope("eval_stats"):
                stats["n_err"] = jnp.concatenate([stats["n_err"], rows])
            return stats

        self._fwd_tokens_at = jax.jit(fwd_tokens_at)
        idx_kw = ({"out_shardings": (fwd_kw["out_shardings"],) * 2}
                  if fwd_kw else {})
        self._fwd = jax.jit(fwd, **fwd_kw)
        self._fwd_idx = jax.jit(fwd_idx, **idx_kw)
        self._fwd_at = jax.jit(fwd_at, **fwd_kw)
        self._fwd_idx_at = jax.jit(fwd_idx_at, **idx_kw)

    # -- sharding -----------------------------------------------------------
    @property
    def data_shards(self):
        """The mesh's data-parallel extent (1 when unsharded)."""
        return self._dp

    def _param_spec(self, spec, name):
        """model-axis sharding for wide FC layers, replicated otherwise
        (conv kernels are small — replication beats the all-gather)."""
        if self.mesh is None:
            return None
        msize = self.mesh.shape["model"]
        if (spec.kind == "fc" and msize > 1 and spec.n_out % msize == 0):
            return P("model", None) if name == "w" else P("model")
        return P()

    def _place_params(self, params_host):
        if self.mesh is None:
            return jax.device_put(params_host, self._home)
        placed = []
        for spec, p in zip(self.specs, params_host):
            q = {}
            for name, arr in p.items():
                ns = NamedSharding(self.mesh, self._param_spec(spec, name))
                q[name] = jax.device_put(arr, ns)
            placed.append(q)
        return placed

    def _place_state(self, states_host):
        if self.mesh is None:
            return jax.device_put(states_host, self._home)
        placed = []
        for spec, st in zip(self.specs, states_host):
            q = {}
            for name, slots in st.items():
                ns = NamedSharding(self.mesh, self._param_spec(spec, name))
                q[name] = {k: jax.device_put(v, ns)
                           for k, v in slots.items()}
            placed.append(q)
        return placed

    def _place_batch(self, x, labels, span="trainer.place"):
        with _h2d_span(span, x, labels):
            if self.mesh is None:
                return jax.device_put(x), jax.device_put(labels)
            mesh_mod.check_data_batch(self.mesh, x.shape[0])
            xs = NamedSharding(self.mesh,
                               P("data", *([None] * (x.ndim - 1))))
            ls = NamedSharding(self.mesh, P("data"))
            return jax.device_put(x, xs), jax.device_put(labels, ls)

    def _place_valid(self, x):
        """The padded validation/test minibatch, host → device, under
        the ``trainer.valid.place`` span (its ``bytes`` also count the
        int32 stand-in for the labels that ``_place_batch`` takes)."""
        x, _ = self._place_batch(x, numpy.zeros(x.shape[0], numpy.int32),
                                 span="trainer.valid.place")
        return x

    def _place_valid_indices(self, idx):
        """The validation/test minibatch as its ``(batch,)`` row indices
        into the resident data set, under the same span: one
        ``device_put`` of a private copy (the loader rewrites its index
        buffer, which the CPU backend's ``device_put`` may alias),
        sharded on ``data`` under a mesh."""
        idx = numpy.array(idx, dtype=numpy.int32)
        with _h2d_span("trainer.valid.place", idx):
            if self.mesh is None:
                return jax.device_put(idx)
            mesh_mod.check_data_batch(self.mesh, idx.shape[0])
            return jax.device_put(idx, NamedSharding(self.mesh, P("data")))

    # -- cost accounting ----------------------------------------------------
    def _register_cost(self, name, fn, args, steps, batch, train=True):
        """Executable cost-registry hook (core/profiler.py): lower the
        already-traced jit BEFORE its first dispatch and record XLA's
        ``cost_analysis`` FLOPs/bytes next to the analytic estimate
        (train step ≈ 3 × forward — the bench's MFU convention;
        forward-only for predict).  Window executables pass their step
        count as ``scan_steps`` — HLO cost analysis counts the scan
        body once, so the profiler scales by K.  Registered names are
        checked FIRST so the armed steady-state cost really is one
        dict lookup per dispatch — the analytic spec walk and the meta
        tuple are built only for the first dispatch of each name."""
        if profiler.cost_entry(name) is not None:
            return
        fpi = flops_per_image(self.specs)
        mult = 3.0 if train else 1.0
        profiler.register_jit_cost(
            name, fn, args,
            analytic_flops=mult * fpi * int(batch) * int(steps),
            scan_steps=int(steps),
            steps=int(steps), batch=int(batch),
            analytic_flops_per_image=mult * fpi)

    # -- public api ---------------------------------------------------------
    def step(self, x, labels, hypers=None):
        """One fused train step.  Returns {"loss", "n_err", "output",
        "max_idx"} (output/max_idx device-resident).  ``hypers`` overrides
        the live hyperparameter pytree for this step (traced — schedules
        cost no recompile)."""
        if self.objective != "softmax":
            raise ValueError("use step_mse for objective %r"
                             % self.objective)
        x, labels = self._place_batch(x, labels)
        if self._needs_key:
            self._key, key = jax.random.split(self._key)
        else:
            key = self._key
        hy = self.hypers if hypers is None else hypers
        if profiler.enabled():
            self._register_cost(
                "fused.step", self._step,
                (self.params, self.state, x, labels, key, hy),
                steps=1, batch=x.shape[0])
        self.params, self.state, metrics = self._step(
            self.params, self.state, x, labels, key, hy)
        return metrics

    def step_mse(self, x, target, batch_size=None, hypers=None):
        """One fused MSE train step.  ``batch_size`` masks the padded
        tail rows (defaults to the full batch).  Returns {"loss",
        "output"}."""
        if self.objective != "mse":
            raise ValueError("use step for objective %r" % self.objective)
        if batch_size is None:
            batch_size = x.shape[0]
        x, _ = self._place_batch(x, numpy.zeros(x.shape[0], numpy.int32))
        target = jax.device_put(
            numpy.asarray(target),
            None if self.mesh is None else NamedSharding(
                self.mesh, P("data", *([None] * (target.ndim - 1)))))
        if self._needs_key:
            self._key, key = jax.random.split(self._key)
        else:
            key = self._key
        hy = self.hypers if hypers is None else hypers
        if profiler.enabled():
            self._register_cost(
                "fused.step_mse", self._step,
                (self.params, self.state, x, target,
                 numpy.int32(batch_size), key, hy),
                steps=1, batch=x.shape[0])
        self.params, self.state, metrics = self._step(
            self.params, self.state, x, target,
            numpy.int32(batch_size), key, hy)
        return metrics

    def run_steps(self, xs, labels_s):
        """Many fused train steps in ONE compiled call via ``lax.scan``.

        ``xs``: (n_steps, batch, *sample), ``labels_s``: (n_steps, batch).
        The whole loop is a single XLA computation — no per-step dispatch,
        which matters whenever a dispatch's launch latency is comparable
        with the step, and is the idiomatic TPU epoch loop.  Returns stacked
        per-step metrics.
        """
        if self.objective != "softmax":
            raise ValueError("run_steps supports the softmax objective; "
                             "drive step_mse per minibatch instead")
        if not hasattr(self, "_scan_step"):
            specs = tuple(self.specs)
            cd = self.compute_dtype

            def body(carry, batch):
                p, s, k, hy = carry
                x, l = batch
                if self._needs_key:
                    k, sub = jax.random.split(k)
                else:
                    sub = k
                p, s, m = _train_step(p, s, x, l, specs, sub, cd, hy)
                return (p, s, k, hy), m

            def scan_fn(p, s, k, xs, ls, hy):
                (p, s, k, hy), ms = jax.lax.scan(body, (p, s, k, hy),
                                                 (xs, ls))
                return p, s, k, ms

            if self.mesh is not None:
                # pin output shardings to the input placements, same as
                # _step in __init__: un-pinned GSPMD output spec variants
                # would force a full recompile of the donated scan on the
                # next call
                rep = NamedSharding(self.mesh, P())
                mshard = {"loss": rep, "n_err": rep}
                self._scan_step = jax.jit(
                    scan_fn, donate_argnums=(0, 1),
                    out_shardings=(self._pshard, self._sshard, rep, mshard))
            else:
                self._scan_step = jax.jit(scan_fn, donate_argnums=(0, 1))
        if self.mesh is not None:
            mesh_mod.check_data_batch(self.mesh, xs.shape[1])
            xs = jax.device_put(xs, NamedSharding(
                self.mesh, P(None, "data", *([None] * (xs.ndim - 2)))))
            labels_s = jax.device_put(
                labels_s, NamedSharding(self.mesh, P(None, "data")))
        else:
            xs = jax.device_put(xs)
            labels_s = jax.device_put(labels_s)
        self.params, self.state, self._key, metrics = self._scan_step(
            self.params, self.state, self._key, xs, labels_s, self.hypers)
        return metrics

    # -- windowed training (the control plane's hot loop) -------------------
    def set_dataset(self, data, labels, targets=None, segments=None,
                    minibatch=None):
        """Place the WHOLE training dataset on device once (replicated
        over the mesh).  Windowed train steps then gather their
        minibatches on device from ``(window, batch)`` index arrays — the
        TPU-native data path: per window only the indices cross the
        host/device boundary (SURVEY.md §7; the reference's equivalent is
        the loader's host-side fancy-index fill, loader/base observed
        contract).

        The set is STORED in the layout the row gather reads: the
        compiler is asked which layout it gives the operand of
        :func:`_gather_rows` for the set at hand and ``minibatch`` rows
        (the trainer's minibatch, 1,024 where a caller names none: a
        gather of one row is a slice and says nothing;
        :func:`gather_format`, kept as ``_data_format``), and where that
        is not the layout the runtime would place the set in, the set is
        written into it (:func:`_store`).  The runtime's default for
        images with 3 channels
        last puts the ROW index in the lanes, and every program that
        gathered from such a set first copied all of it into a layout with
        the rows major-most: ``copy.56`` / ``copy.5``, 9.46 ms a program
        for AlexNet's bf16[8448,227,227,3] whatever the batch and the mesh,
        five programs an epoch, 8.4 % of one chip's busy time and 22 % of
        four chips' (PERF.md sections 5 and 6).  Stored so once, the set is
        larger by the layout's padding only (3,010,461,696 against
        2,669,432,832 bytes: 227 x 227 padded to 232 x 256).  Where the
        compiler's answer is the default layout (2-d sets, token ids, the
        CPU backend) the set is placed as it always was.

        Under a bf16 ``compute_dtype`` the dataset is STORED in bf16:
        the forward casts x to bf16 anyway, gather commutes with the
        cast (bit-identical), and the row gather is bound by the bytes
        it moves (3.65 ms of AlexNet's 68.5 ms step on one chip for
        1,024 bf16 rows, PERF.md section 5).
        Integers stay integers (token ids: bf16 holds none above 256);
        ``labels`` may be one int a row or, with ``segments`` beside them,
        one a position."""
        with telemetry.span("trainer.set_dataset") as sp:
            data = numpy.ascontiguousarray(data)
            if labels is None or not len(labels):
                # MSE datasets may carry no labels; the padded sentinel
                # keeps every label-consuming path inert
                labels = numpy.full(len(data), -1, numpy.int32)
            labels = numpy.asarray(labels, dtype=numpy.int32)
            if targets is not None:
                # targets keep float32 (not the bf16 compute dtype): the
                # MSE loss/stats math is float32 even in bf16 mode and
                # the per-minibatch path feeds it unrounded targets —
                # storing bf16 would change the loss, unlike the data
                # rows where the forward's cast commutes with the gather
                targets = numpy.ascontiguousarray(targets)
                if self.compute_dtype is not None:
                    targets = numpy.asarray(targets, dtype=numpy.float32)
            if segments is not None:
                segments = numpy.asarray(segments, dtype=numpy.int32)
            if telemetry.enabled():
                nbytes = _nbytes(data, labels, targets, segments)
                telemetry.add_bytes("h2d", nbytes)
                sp.set(bytes=nbytes)
            rep = None if self.mesh is None \
                else NamedSharding(self.mesh, P())
            cast = self.compute_dtype is not None and not numpy.issubdtype(
                data.dtype, numpy.integer)
            stored = self.compute_dtype if cast else data.dtype
            data = jnp.asarray(data) if cast else jax.device_put(data, rep)
            fmt, default = gather_format(
                data.shape, stored, data.sharding if rep is None else rep,
                min(len(data), minibatch or 1024))
            relaid = fmt.layout != default.layout
            if relaid:
                # two statements: the uncast set on its first device alone
                # is let go before the stored one is written
                data = jax.device_put(data, rep)
                data = _store(data, fmt, stored)
                if self.mesh is None:
                    # the layout of a committed argument alone is taken as
                    # the program's, and a committed argument commits what
                    # a program hands back: parameters, state and key go in
                    # committed from the first call on, or every program
                    # compiles a second time for the second
                    self._home = data.sharding
                    self.params, self.state, self._key = jax.device_put(
                        (self.params, self.state, self._key), self._home)
            elif cast:
                data = jax.device_put(data.astype(stored), rep)
            self._data_d, self._data_format = data, fmt
            if telemetry.enabled():
                sp.set(layout=fmt.layout.major_to_minor if relaid
                       else "default")
                if relaid:
                    telemetry.counter("trainer.dataset_relayouts").inc()
            self._labels_d = jax.device_put(labels, rep)
            self._targets_d = None if targets is None \
                else jax.device_put(targets, rep)
            self._segments_d = None if segments is None \
                else jax.device_put(segments, rep)

    @property
    def has_dataset(self):
        return self._data_d is not None

    def set_epoch_perm(self, perm, pad):
        """Materialize the epoch's shuffled dataset ON DEVICE, once per
        reshuffle: ``data_p[i] = data[perm[i]]`` plus ``pad`` trailing
        zero rows (labels -1) so every window's dynamic slice stays in
        range on the tail minibatch.

        ONE gather per epoch; the MSE window's steps then read their
        minibatches as contiguous ``dynamic_slice`` loads
        (:meth:`run_window_mse_sliced`, MSE's only resident form).
        Identical rows to a per-window gather by construction — the
        loader serves TRAIN minibatches as contiguous slices of its
        shuffled order (loader/base.py run())."""
        if not self.has_dataset:
            raise RuntimeError("set_dataset() before set_epoch_perm")
        has_targets = self._targets_d is not None
        key_ = (int(len(perm)), int(pad), has_targets)
        fn = self._perm_fns.get(key_)
        if fn is None:
            def _mat_one(arr, p, fill):
                ap = jnp.take(arr, p, axis=0)
                tail = jnp.full((pad,) + ap.shape[1:], fill, ap.dtype)
                return jnp.concatenate([ap, tail])

            def materialize(data, labels, targets, p):
                out = (_mat_one(data, p, 0), _mat_one(labels, p, -1),
                       _mat_one(targets, p, 0) if has_targets else 0)
                return out

            if self.mesh is not None:
                rep = NamedSharding(self.mesh, P())
                fn = jax.jit(materialize,
                             out_shardings=(rep, rep,
                                            rep if has_targets else None))
            else:
                fn = jax.jit(materialize)
            self._perm_fns[key_] = fn
        rep = None if self.mesh is None else NamedSharding(self.mesh, P())
        # SNAPSHOT the permutation (numpy.array copies; asarray would
        # alias): device_put may alias aligned host memory on the CPU
        # backend and the materialize dispatch below is ASYNCHRONOUS —
        # the caller's buffer is the loader's live train_indices, which
        # the epoch-end reshuffle mutates IN PLACE mid window-collection.
        # Without the copy the gather raced the shuffle and the epoch's
        # tail window could train on next-epoch rows
        # (test_mse_window8_equals_window1 with no VALID split).
        perm_d = jax.device_put(
            numpy.array(perm, dtype=numpy.int32), rep)
        self._data_p, self._labels_p, tp = fn(
            self._data_d, self._labels_d,
            self._targets_d if has_targets else 0, perm_d)
        self._targets_p = tp if has_targets else None

    @property
    def has_epoch_perm(self):
        return self._data_p is not None

    def _get_window_fn(self, n_steps, mode, final=False):
        """Build (and cache) the compiled K-step window: one ``lax.scan``
        over ``_train_step`` with per-step traced hypers + in-scan
        evaluator stats.  Aggregates (n_err, confusion, max_err_sum) ride
        the carry so only the per-step losses stack; the LAST step's
        output/max_idx come back for the downstream units
        (evaluator/decision/plotters keep their reference roles).

        ``mode``: "stacked" (host-stacked minibatches) or "indexed"
        (device-resident dataset + per-row gather, what every
        benchmark cell runs).

        Data-parallel mesh (data shards S > 1): per-step stats and the
        epoch accumulator keep a leading ``S`` shard axis sharded
        ``P("data", ...)`` — every in-scan reduction is LOCAL to its
        shard's batch rows, so mid-epoch windows insert no stats
        collective beyond the gradient psum the update itself needs.
        ``final=True`` (the segment-final window) additionally folds the
        segment's ONE stats all-reduce into the executable and returns
        the replicated totals under ``stats["acc_reduced"]`` — exactly
        one aggregate all-reduce per segment, none on the host path."""
        dp = self._dp
        final = bool(final) and dp > 1
        key_ = (int(n_steps), mode, final)
        fn = self._window_fns.get(key_)
        if fn is not None:
            return fn
        specs = tuple(self.specs)
        cd = self.compute_dtype
        mesh = self.mesh
        needs_key = self._needs_key
        # what the scan carries and returns is the objective's: the token
        # objective has no confusion matrix and hands back no output
        tokens = self.objective == "tokens"
        if tokens and mode != "indexed":
            raise ValueError("the tokens objective trains from the "
                             "resident data set (indexed windows)")
        topology = self.topology
        n_classes = 0 if tokens else int(self.specs[-1].n_out)
        mean = bool(self.stats_mean)
        out_dtype = jnp.float32 if cd is not None else self.dtype

        # a net with ``moe`` entries also carries the experts' load: summed
        # over the window's steps for the epoch's accumulator, and every
        # step's own beside the choice made, which stay on the device
        # unless a caller reads them
        routed, held = self._moe_entries > 0, self.moe_held
        balanced = self._balanced
        # the counts a step adds to the epoch's accumulator beside its
        # errors and loss
        counts = (MOE_COUNTS if routed else ()) \
            + (ATTENTION_COUNTS if self._attention_entries else ())

        def body_tokens(carry, step):
            p, s, k, nerr, lsum = carry[:5]
            data, lbl_all, idx, sample, hy = step
            x, lbl, seg, rows = _gather_token_rows(data, lbl_all, idx)
            ys = {}
            if sample is not None:
                # the head's weight as this step's product takes it
                head = next(i for i, sp in enumerate(specs)
                            if sp.kind == "lm_head")
                ys["head_w"] = p[head]["w"] if cd is None \
                    else p[head]["w"].astype(cd)
            p, s, m = _train_step_tokens(p, s, x, lbl, seg, specs,
                                         topology, cd, hy, sample)
            with jax.named_scope("eval_stats"):
                d_nerr = jnp.concatenate([m["n_err"], rows])
            with jax.named_scope("acc"):
                # (a step counts no block where no entry ran the kernel)
                carry = (p, s, k, nerr + d_nerr, lsum + m["loss_sum"]) \
                    + tuple({name: c[name] + m.get(name, 0) for name in c}
                            for c in carry[5:])
            ys["loss"] = m["loss"]
            if sample is not None:
                ys["hidden"] = m["hidden_sample"]
                if "exit_sample" in m:
                    ys["exit"] = m["exit_sample"]
            if routed:
                ys.update({name: m[name] for name in MOE_STATS
                           if name in m})
            return carry, ys

        def window_tokens(p, s, k, data, lbl_all, xs, sample, hy_s, acc):
            def scan_body(carry, step):
                idx, hy = step
                return body_tokens(carry, (data, lbl_all, idx, sample, hy))
            carry0 = (p, s, k, jnp.zeros((3,), jnp.int32),
                      jnp.zeros((), jnp.float32))
            if counts:
                carry0 += ({name: jnp.zeros_like(acc[name])
                            for name in counts},)
            (p, s, k, nerr, lsum, *counted), ys = jax.lax.scan(
                scan_body, carry0, (xs, hy_s))
            with jax.named_scope("acc"):
                new = {"n_err": acc["n_err"] + nerr,
                       "loss_sum": acc["loss_sum"] + lsum}
                if counts:
                    new.update({name: acc[name] + counted[0][name]
                                for name in counts})
                if routed:
                    new["moe_load_max"] = jnp.maximum(
                        acc["moe_load_max"], (ys["moe_load"] * jnp.asarray(
                            held, jnp.int32)).max())
                if balanced:
                    # what the bias rule acts on and how far it has gone
                    new["moe_load_max_all"] = jnp.maximum(
                        acc["moe_load_max_all"], ys["moe_load"].max())
                    new["moe_bias_abs_max"] = jnp.stack(
                        [jnp.abs(p[i]["sb"]).max() for i in balanced]
                    ).max().astype(jnp.float32)
                acc = new
            stats = {"loss": ys["loss"], "n_err": nerr, "loss_sum": lsum,
                     "acc": acc}
            if routed:
                stats.update({name: ys[name] for name in MOE_STATS
                              if name in ys})
            if self._attention_entries:
                stats["attention_blocks"] = counted[0]["attention_blocks"]
            if sample is not None:
                # the last step's: the state the head read at the positions
                # asked for and the weight it used (their product, every
                # pass's logits there, is gigabytes at a real vocabulary:
                # ``run_window_indexed`` makes it once this program's
                # buffers are gone)
                stats["hidden_sample"] = ys["hidden"][-1]
                stats["head_w"] = ys["head_w"][-1]
                if "exit" in ys:
                    stats["exit_sample"] = ys["exit"][-1]
            return p, s, k, stats

        def body(carry, step):
            if dp > 1:
                p, s, k, _, _, nerr, conf, mx, i, lbuf = carry
            else:
                p, s, k, _, _, nerr, conf, mx = carry
            if mode == "indexed":
                data, lbl_all, idx, bs, hy = step
                x, safe = _gather_rows(data, idx)
                with jax.named_scope("gather"):
                    lbl = jnp.where(idx < 0, jnp.int32(-1),
                                    jnp.take(lbl_all, safe, axis=0))
            else:
                x, lbl, bs, hy = step
            if dp > 1:
                # pin the minibatch to the data axis INSIDE the scan:
                # the indexed gather reads a replicated
                # dataset, and without the constraint GSPMD is free to
                # keep the whole step replicated (no scaling)
                x = _pin_to_data(x, mesh)
                lbl = _pin_to_data(lbl, mesh)
            if needs_key:
                k, sub = jax.random.split(k)
            else:
                sub = k
            p, s, m = _train_step(p, s, x, lbl, specs, sub, cd, hy,
                                  with_output=True)
            with jax.named_scope("eval_stats"):
                d_nerr, d_conf, d_mx = _eval_stats(
                    m["output"], m["max_idx"], lbl, bs, n_classes, mean,
                    shards=dp)
            with jax.named_scope("acc"):
                stats_c = (nerr + d_nerr, conf + d_conf,
                           jnp.maximum(mx, d_mx))
            if dp > 1:
                # per-step losses accumulate into a CARRIED buffer via a
                # one-hot add instead of the scan's ys stacking: a
                # dynamic-update-slice over a (K,) buffer is sharded by
                # GSPMD whenever K divides by the shard count, and the
                # installed jaxlib's partitioner then emits a mixed
                # s64/s32 offset compare under x64 (hlo verifier error).
                # The elementwise add partitions trivially.
                loss = jax.lax.with_sharding_constraint(
                    m["loss"], NamedSharding(mesh, P()))
                lbuf = lbuf + loss.astype(lbuf.dtype) * \
                    jax.nn.one_hot(i, lbuf.shape[0], dtype=lbuf.dtype)
                carry = (p, s, k, m["output"], m["max_idx"]) + stats_c \
                    + (i + 1, lbuf)
                return carry, None
            carry = (p, s, k, m["output"], m["max_idx"]) + stats_c
            return carry, m["loss"]

        def window_fn(p, s, k, data, lbl_all, xs, ls, bs_s, hy_s, acc):
            if tokens:
                # ``ls`` carries the positions a caller asks logits at
                return window_tokens(p, s, k, data, lbl_all, xs, ls, hy_s,
                                     acc)
            b = xs.shape[1]
            out0 = jnp.zeros((b, n_classes), dtype=out_dtype)
            idx0 = jnp.zeros((b,), dtype=jnp.int32)
            lead = (dp,) if dp > 1 else ()
            nerr0 = jnp.zeros(lead + (2,), dtype=jnp.int32)
            conf0 = jnp.zeros(lead + (n_classes, n_classes),
                              dtype=jnp.int32)
            mx0 = jnp.zeros(lead, dtype=out_dtype)
            if mode == "indexed":
                # the dataset enters once as a plain argument (closing
                # over it would bake a huge constant into the program;
                # scanning it would copy it per step)
                def scan_body(carry, step):
                    idx, bs, hy = step
                    return body(carry, (data, lbl_all, idx, bs, hy))
                xs_scan = (xs, bs_s, hy_s)
            else:
                xs_scan = (xs, ls, bs_s, hy_s)
                scan_body = body
            carry0 = (p, s, k, out0, idx0, nerr0, conf0, mx0)
            if dp > 1:
                carry0 = carry0 + (jnp.int32(0),
                                   jnp.zeros((n_steps,), dtype=out_dtype))
                carry1, _ = jax.lax.scan(scan_body, carry0, xs_scan)
                (p, s, k, out, midx, nerr, conf, mx) = carry1[:8]
                losses = carry1[9]
            else:
                (p, s, k, out, midx, nerr, conf, mx), losses = \
                    jax.lax.scan(scan_body, carry0, xs_scan)
            # fold this window's deltas into the device-resident epoch
            # accumulator OUTSIDE the scan (acc + window_delta is the
            # exact f32/int op sequence the synchronous host fold ran,
            # so the async segment total is bit-identical; under a data
            # mesh the fold stays per-shard — elementwise, no collective)
            with jax.named_scope("acc"):
                acc = {"n_err": acc["n_err"] + nerr,
                       "confusion": acc["confusion"] + conf,
                       "max_err_sum": jnp.maximum(acc["max_err_sum"], mx)}
            stats = {"loss": losses, "n_err": nerr, "confusion": conf,
                     "max_err_sum": mx, "output": out, "max_idx": midx,
                     "acc": acc}
            if final:
                # the segment's ONE stats all-reduce: integer sums and a
                # max over the shard axis — order-independent, so the
                # reduced totals equal the single-device fold bit for bit
                with jax.named_scope("acc"):
                    stats["acc_reduced"] = {
                        "n_err": acc["n_err"].sum(axis=0),
                        "confusion": acc["confusion"].sum(axis=0),
                        "max_err_sum": acc["max_err_sum"].max(axis=0)}
            return p, s, k, stats

        if self.mesh is not None:
            rep = NamedSharding(self.mesh, P())
            oshard = NamedSharding(self.mesh, P("data", None))
            ishard = NamedSharding(self.mesh, P("data"))
            if dp > 1:
                sh1 = NamedSharding(self.mesh, P("data"))
                sh2 = NamedSharding(self.mesh, P("data", None))
                sh3 = NamedSharding(self.mesh, P("data", None, None))
                stat_shard = {"n_err": sh2, "confusion": sh3,
                              "max_err_sum": sh1}
            else:
                stat_shard = {"n_err": rep, "confusion": rep,
                              "max_err_sum": rep}
            mshard = dict(stat_shard)
            mshard.update({"loss": rep, "output": oshard,
                           "max_idx": ishard, "acc": dict(stat_shard)})
            if final:
                mshard["acc_reduced"] = {"n_err": rep, "confusion": rep,
                                         "max_err_sum": rep}
            fn = jax.jit(window_fn, donate_argnums=(0, 1, 9),
                         out_shardings=(self._pshard, self._sshard, rep,
                                        mshard))
        else:
            fn = jax.jit(window_fn, donate_argnums=(0, 1, 9))
        self._window_fns[key_] = fn
        return fn

    # -- device-resident epoch accumulators ---------------------------------
    def _window_acc(self):
        """The running decision-aggregate accumulator (device arrays),
        created as zeros on the first window after a
        :meth:`reset_window_acc`.  Carried INTO every window executable
        as a donated argument and OUT under ``stats["acc"]`` — the async
        control plane's one readback per segment.

        Data-parallel mesh: the leaves keep a leading ``data_shards``
        axis and live SHARDED ``P("data", ...)`` — each shard
        accumulates its local batch rows' partials with no collective
        until the segment-final window's one all-reduce."""
        if self._win_acc is not None:
            return self._win_acc
        acc = self.window_acc_zeros()
        shard = self._acc_shardings(acc)
        with _h2d_span("trainer.place", acc):
            self._win_acc = {k: jax.device_put(v, shard[k])
                             for k, v in acc.items()}
        return self._win_acc

    def window_acc_zeros(self):
        """Host-side zero epoch accumulators — the shape/dtype
        authority for the device leaves.  Shared by the zero-init path
        and by launcher auto-resume's compatibility check, which must
        validate a candidate snapshot's ``epoch_acc`` (including the
        leading data-shard axis — a mesh=4 capture cannot resume into a
        mesh=2 run) WITHOUT forcing a device drain."""
        out_dtype = jnp.float32 if self.compute_dtype is not None \
            else self.dtype
        lead = (self._dp,) if self._dp > 1 else ()
        if self.objective == "tokens":
            # [errors, graded tokens, rows] and the graded tokens' loss
            acc = {"n_err": numpy.zeros((3,), numpy.int32),
                   "loss_sum": numpy.zeros((), numpy.float32)}
            if self._moe_entries:
                # since the last readback: the pairs every expert of every
                # ``moe`` application took, the tokens an application
                # served with no expert, and the most pairs an expert held
                # here took in one step
                acc["moe_load"] = numpy.zeros(self.moe_held.shape,
                                              numpy.int32)
                acc["moe_unserved"] = numpy.zeros(self._moe_entries,
                                                  numpy.int32)
                acc["moe_rows"] = numpy.zeros((2,), numpy.int32)
                acc["moe_load_max"] = numpy.zeros((), numpy.int32)
            if self._balanced:
                # the most tokens ANY expert of any entry took in one step,
                # held here or not, and the selection biases' largest
                # magnitude as the last window left them
                acc["moe_load_max_all"] = numpy.zeros((), numpy.int32)
                acc["moe_bias_abs_max"] = numpy.zeros((), numpy.float32)
            if self._attention_entries:
                acc["attention_blocks"] = numpy.zeros((2,), numpy.int32)
            return acc
        if self.objective == "mse":
            metrics = numpy.zeros(lead + (3,), dtype=out_dtype)
            metrics[..., 2] = numpy.inf
            return {"metrics": metrics,
                    "n_err": numpy.zeros(lead + (2,), numpy.int32)}
        n_classes = int(self.specs[-1].n_out)
        return {"n_err": numpy.zeros(lead + (2,), numpy.int32),
                "confusion": numpy.zeros(
                    lead + (n_classes, n_classes), numpy.int32),
                "max_err_sum": numpy.zeros(lead, out_dtype)}

    def _acc_shardings(self, acc):
        """Accumulator leaf placements — replicated off-mesh, sharded
        ``P("data", ...)`` partials under a data mesh (shared by the
        zero-init path and mid-epoch resume's :meth:`set_window_acc`)."""
        if self.mesh is None:
            return {k: self._home for k in acc}
        if self._dp > 1:
            return {k: NamedSharding(
                self.mesh, P("data", *([None] * (numpy.ndim(v) - 1))))
                for k, v in acc.items()}
        rep = NamedSharding(self.mesh, P())
        return {k: rep for k in acc}

    @property
    def window_acc(self):
        """The last window's folded epoch accumulator (device; None
        before the first window of a segment)."""
        return self._win_acc

    def window_acc_host(self):
        """Drained host copy of the epoch accumulator for the mid-epoch
        snapshot payload — ONE batched readback (:meth:`host_fetch`),
        transitively waiting on every in-flight window.  None when the
        accumulator is at its zero state (segment boundary)."""
        if self._win_acc is None:
            return None
        return self.host_fetch(self._win_acc)

    def set_window_acc(self, host_acc):
        """Restore a :meth:`window_acc_host` capture (mid-epoch
        resume): leaves re-placed with the accumulator shardings, so
        the next dispatched window folds onto the pre-crash partials —
        async and mesh modes included."""
        if host_acc is None:
            self._win_acc = None
            return
        host_acc = {k: numpy.asarray(v) for k, v in host_acc.items()}
        shard = self._acc_shardings(host_acc)
        self._win_acc = {k: jax.device_put(v, shard[k])
                         for k, v in host_acc.items()}

    def reset_window_acc(self):
        """Zero the epoch accumulator (the trainer calls this at every
        segment boundary, after its one batched readback)."""
        self._win_acc = None

    def _place_window(self, arr, tail_dims):
        """Device-put a (K, batch, ...) stacked window input: scan dim
        unsharded, batch dim over ``data``.  A :class:`ShardMajorWindow`
        (the trainer's shard-aligned staging layout) is assembled from
        its per-shard contiguous blocks — each device receives one
        memcpy'able block instead of a strided split of the batch-major
        stack."""
        if isinstance(arr, ShardMajorWindow):
            with _h2d_span("trainer.place", arr.base):
                return self._place_window_shard_major(arr.base,
                                                      tail_dims)
        with _h2d_span("trainer.place", arr):
            if self.mesh is None:
                return jax.device_put(arr)
            return jax.device_put(arr, NamedSharding(
                self.mesh, P(None, "data", *([None] * tail_dims))))

    def _place_window_shard_major(self, base, tail_dims):
        """Build the global sharded (K, B, ...) window array from a
        shard-major host base ``(S, K, B // S, ...)``: every addressable
        device gets its data shard's contiguous block via one
        ``device_put`` and the global array is assembled without a host
        restack (``jax.make_array_from_single_device_arrays``)."""
        if self.mesh is None or self._dp == 1:
            raise ValueError("shard-major staging needs a data mesh")
        dp, k, b = base.shape[:3]
        if dp != self._dp:
            raise ValueError("staging shards %d != mesh data shards %d"
                             % (dp, self._dp))
        gshape = (k, dp * b) + tuple(base.shape[3:])
        ns = NamedSharding(self.mesh,
                           P(None, "data", *([None] * tail_dims)))
        bufs = []
        for dev, idx in ns.addressable_devices_indices_map(
                gshape).items():
            start = idx[1].start or 0
            bufs.append(jax.device_put(base[start // b], dev))
        return jax.make_array_from_single_device_arrays(gshape, ns, bufs)

    def _place_window_scalars(self, batch_sizes, hypers_s):
        """Commit the per-step (K,) scalar rails: batch sizes and the
        stacked hyper pytree.  With a mesh they go REPLICATED: left
        unpinned, GSPMD is free to shard a (K,) rail over ``data``
        whenever K is divisible by the shard count, which both
        serializes the scan's per-step reads behind collectives and
        trips the installed jaxlib's s64/s32 dynamic-slice partitioner
        bug under x64.  Without one they go to the default device.

        The placed hypers are KEPT, one entry a window length, beside
        the host pytree they were made from: while the caller hands that
        very object in again (the trainer's cached stacked form, as long
        as no schedule moves a rate) the kept copy is handed back and
        nothing crosses but the batch sizes; any other object is placed
        and replaces the entry.  The hypers are never donated, so a kept
        buffer outlives its dispatch.  The entries live and die with the
        net (its mesh and dtype are fixed at construction) and are no
        part of :meth:`state_dict`.  (PERF.md section 6, PR 29: 144
        leaves placed anew every window held four chips 72 % idle.)"""
        bs = numpy.asarray(batch_sizes, dtype=numpy.int32)
        rep = None if self.mesh is None else NamedSharding(self.mesh, P())
        leaves = jax.tree.leaves(hypers_s)
        if not leaves or isinstance(leaves[0], jax.Array):
            # a caller's own placed pytree: nothing to place or to keep
            with _h2d_span("trainer.place", bs):
                return jax.device_put(bs, rep), hypers_s
        kept = self._placed_hypers.get(len(bs))
        reuse = kept is not None and kept[0] is hypers_s
        with _h2d_span("trainer.place", bs, None if reuse else hypers_s):
            if not reuse:
                kept = (hypers_s, jax.device_put(hypers_s, rep))
                self._placed_hypers[len(bs)] = kept
            if telemetry.enabled():
                if reuse:
                    telemetry.counter("trainer.hypers_reused").inc()
                else:
                    telemetry.counter("trainer.hypers_placed").inc()
            return jax.device_put(bs, rep), kept[1]

    def _place_starts(self, starts):
        """The sliced window's (K,) row offsets, replicated."""
        starts = numpy.asarray(starts, dtype=numpy.int32)
        rep = None if self.mesh is None else NamedSharding(self.mesh, P())
        with _h2d_span("trainer.place", starts):
            return jax.device_put(starts, rep)

    def _check_window_batch(self, batch):
        if self.mesh is not None:
            mesh_mod.check_data_batch(self.mesh, batch)

    def _cost_name(self, kind, n_steps, final):
        name = "fused.window.%s.k%d" % (kind, n_steps)
        if final and self._dp > 1:
            # the segment-final variant is a DISTINCT executable (it
            # folds the per-segment stats all-reduce) — keep the cost
            # registry 1:1 with compiled programs
            name += ".final"
        return name

    def _dispatch_window(self, kind, fn, inputs, n_steps, batch, final):
        """The one call of a compiled window every ``run_window*``
        variant ends in: ``fn(params, state, key, *inputs, acc)``.  The
        ``trainer.dispatch`` span is the host's time inside the jitted
        call (argument handling, enqueue on every device).  Every
        argument is on the device by now, the hyper leaves among them
        (:meth:`_place_window_scalars`): the call transfers nothing."""
        args = (self.params, self.state, self._key) + tuple(inputs) \
            + (self._window_acc(),)
        if profiler.enabled():
            self._register_cost(self._cost_name(kind, n_steps, final),
                                fn, args, steps=n_steps, batch=batch)
        with telemetry.span("trainer.dispatch"):
            self.params, self.state, self._key, stats = fn(*args)
        self._win_acc = stats["acc"]
        return stats

    def run_window(self, xs, labels_s, batch_sizes, hypers_s,
                   final=False):
        """K train steps in ONE compiled dispatch over host-stacked
        minibatches ``xs (K, B, *sample)`` / ``labels_s (K, B)``.
        ``batch_sizes (K,)`` masks padded tail minibatches exactly like
        the per-minibatch evaluator; ``hypers_s`` is the hyper pytree
        with a leading K axis (policy(k) applies to step k — LR-schedule
        step accuracy inside the window).  Returns the aggregated window
        stats (see _get_window_fn).  ``final`` marks the segment-final
        window (under a data mesh it selects the executable variant
        that folds the per-segment stats all-reduce); ``xs``/``labels_s``
        may be :class:`ShardMajorWindow` staging views."""
        if self.objective != "softmax":
            raise ValueError("run_window supports the softmax objective")
        self._check_window_batch(xs.shape[1])
        n_steps = xs.shape[0]
        fn = self._get_window_fn(n_steps, "stacked", final=final)
        if not isinstance(xs, ShardMajorWindow):
            xs = numpy.ascontiguousarray(xs)
        xs = self._place_window(xs, xs.ndim - 2)
        if not isinstance(labels_s, ShardMajorWindow):
            labels_s = numpy.asarray(labels_s, dtype=numpy.int32)
        labels_s = self._place_window(labels_s, 0)
        bs, hypers_s = self._place_window_scalars(batch_sizes, hypers_s)
        return self._dispatch_window(
            "stacked", fn, (0, 0, xs, labels_s, bs, hypers_s), n_steps,
            xs.shape[1], final)

    def run_window_indexed(self, idx_s, batch_sizes, hypers_s,
                           final=False):
        """Windowed training over the device-resident dataset
        (:meth:`set_dataset`): ``idx_s (K, B)`` dataset row indices
        (-1 = padded tail slot).  Only the indices cross the host/device
        boundary; the gather runs inside the compiled window.  Under the
        tokens objective :attr:`sample_positions` ``(n,)``, positions into
        a minibatch's flattened ``B * S`` tokens, asks for the last step's
        logits of every pass and its exit distribution there
        (``stats["logits_sample"] (T, n, V)``, ``stats["exit_sample"]
        (T, n)``): a program of its own, for a caller that compares."""
        if not self.has_dataset:
            raise RuntimeError("set_dataset() before run_window_indexed")
        self._check_window_batch(idx_s.shape[1])
        n_steps = idx_s.shape[0]
        fn = self._get_window_fn(n_steps, "indexed", final=final)
        if not isinstance(idx_s, ShardMajorWindow):
            idx_s = numpy.asarray(idx_s, dtype=numpy.int32)
        idx_s = self._place_window(idx_s, 0)
        bs, hypers_s = self._place_window_scalars(batch_sizes, hypers_s)
        labels, sample = self._labels_d, None
        if self.objective == "tokens":
            labels = (self._labels_d, self._segments_d)
            if self.sample_positions is not None:
                sample = jnp.asarray(self.sample_positions,
                                     dtype=jnp.int32)
        stats = self._dispatch_window(
            "indexed", fn,
            (self._data_d, labels, idx_s, sample, bs, hypers_s),
            n_steps, idx_s.shape[1], final)
        if "hidden_sample" in stats:
            # the product the head makes, of the state it read and the
            # weight it used: (T, n, d) x (V, d) -> (T, n, V) float32
            if self._sample_logits is None:
                cd = self.compute_dtype
                self._sample_logits = jax.jit(lambda hid, w: jax.vmap(
                    lambda h: transformer.head_logits(h, w, cd))(hid))
            stats["logits_sample"] = self._sample_logits(
                stats.pop("hidden_sample"), stats.pop("head_w"))
        return stats

    # -- windowed MSE (the AE/regression hot loop) --------------------------
    def _get_window_fn_mse(self, n_steps, mode, batch=None, final=False):
        """K-step MSE scan window (reference evaluator contract:
        /root/reference/evaluator.py:334-556).  Carry aggregates the
        evaluator-identical metrics ([sum, max, min] of per-sample mse,
        ops/evaluator.mse_jax semantics, with ``mse_root`` mirrored
        from EvaluatorMSE.root) and — when ``class_targets`` is set —
        the nearest-class-target n_err integers.  The LAST step's
        output and per-sample mse come back for the downstream units.

        ``mode``: "stacked" or "sliced" (MSE has no indexed-gather
        variant; non-contiguous loaders use the host-stacked window).

        Data-parallel mesh: same sharded-partial discipline as
        :meth:`_get_window_fn` — metrics/n_err keep a leading shard
        axis, ``final=True`` folds the per-segment all-reduce into the
        executable (``stats["acc_reduced"]``).  The mse SUM partial is
        f32-reassociated across shards (per-shard sums, then one
        cross-shard sum) — the ONE documented reduction-order deviation
        from the single-device fold (MESH_MSE_SUM; max/min and the
        integer n_err stay exact)."""
        dp = self._dp
        final = bool(final) and dp > 1
        ct = self.class_targets
        key_ = ("mse", int(n_steps), mode, batch, ct is not None, final)
        fn = self._window_fns.get(key_)
        if fn is not None:
            return fn
        specs = tuple(self.specs)
        cd = self.compute_dtype
        mesh = self.mesh
        needs_key = self._needs_key
        root = bool(self.mse_root)
        mean = bool(self.stats_mean)
        out_dtype = jnp.float32 if cd is not None else self.dtype
        ct_c = None if ct is None else jnp.asarray(ct, out_dtype)
        out_shape = tuple(self.specs[-1].out_shape)

        def _stats(out, target, lbl, bs):
            """Evaluator-identical per-minibatch MSE stats — THE
            evaluator op itself runs inside the scan (its err output is
            unused and dead-code-eliminated under jit), so the windowed
            parity has one source of truth — plus the optional
            nearest-class-target error (the evaluator's host loop:
            squared distance summed over the sample axis, argmin vs
            label).  Under a data mesh the reductions run per shard
            (leading ``dp`` axis, see _eval_stats)."""
            from znicz_tpu.ops import evaluator as ev_ops
            out = out.astype(out_dtype)
            B = out.shape[0]
            o2 = out.reshape(B, -1)
            t2 = target.reshape(B, -1).astype(out_dtype)
            _, md, mse_per = ev_ops.mse_jax(o2, t2, bs, mean=mean,
                                            root=root)
            in_batch = jnp.arange(B) < bs
            if dp > 1:
                b = B // dp
                m2 = mse_per.reshape(dp, b)
                md = jnp.stack(
                    [m2.sum(axis=1), m2.max(axis=1),
                     jnp.where(in_batch.reshape(dp, b), m2,
                               jnp.inf).min(axis=1)], axis=-1)
            if ct_c is None:
                lead = (dp,) if dp > 1 else ()
                nerr_d = jnp.zeros(lead + (2,), jnp.int32)
            else:
                d = ((ct_c[None, :, :] - o2[:, None, :]) ** 2).sum(-1)
                pred = jnp.argmin(d, axis=1).astype(jnp.int32)
                if dp > 1:
                    b = B // dp
                    cnt = in_batch.reshape(dp, b).sum(axis=1)
                    n_ok = (in_batch & (pred == lbl)).reshape(
                        dp, b).sum(axis=1)
                    nerr_d = jnp.stack([cnt - n_ok, cnt],
                                       axis=-1).astype(jnp.int32)
                else:
                    n_ok = (in_batch & (pred == lbl)).sum()
                    nerr_d = jnp.stack([bs - n_ok, bs]).astype(jnp.int32)
            return md, mse_per, nerr_d, out

        def body(carry, step):
            if dp > 1:
                p, s, k, _, _, msum, mmax, mmin, nerr, i, lbuf = carry
            else:
                p, s, k, _, _, msum, mmax, mmin, nerr = carry
            if mode == "sliced":
                data, tgt_all, lbl_all, start, bs, hy = step
                with jax.named_scope("gather"):
                    x = jax.lax.dynamic_slice_in_dim(data, start, batch,
                                                     axis=0)
                    t = jax.lax.dynamic_slice_in_dim(tgt_all, start,
                                                     batch, axis=0)
                    lbl = jax.lax.dynamic_slice_in_dim(lbl_all, start,
                                                       batch)
                    lbl = jnp.where(jnp.arange(batch) < bs, lbl,
                                    jnp.int32(-1))
            else:
                x, t, lbl, bs, hy = step
            if dp > 1:
                # pin the minibatch to the data axis (see _get_window_fn)
                x = _pin_to_data(x, mesh)
                t = _pin_to_data(t, mesh)
                lbl = _pin_to_data(lbl, mesh)
            if needs_key:
                k, sub = jax.random.split(k)
            else:
                sub = k
            p, s, m = _train_step_mse(p, s, x, t, bs, specs, sub, cd, hy)
            with jax.named_scope("eval_stats"):
                md, mse_per, nerr_d, out = _stats(m["output"], t, lbl, bs)
            with jax.named_scope("acc"):
                stats_c = (msum + md[..., 0],
                           jnp.maximum(mmax, md[..., 1]),
                           jnp.minimum(mmin, md[..., 2]), nerr + nerr_d)
            if dp > 1:
                # carried one-hot loss accumulation — see _get_window_fn
                # (the scan ys dynamic-update-slice trips the jaxlib
                # partitioner when K divides by the shard count)
                loss = jax.lax.with_sharding_constraint(
                    m["loss"], NamedSharding(mesh, P()))
                lbuf = lbuf + loss.astype(lbuf.dtype) * \
                    jax.nn.one_hot(i, lbuf.shape[0], dtype=lbuf.dtype)
                carry = (p, s, k, out, mse_per) + stats_c + (i + 1, lbuf)
                return carry, None
            carry = (p, s, k, out, mse_per) + stats_c
            return carry, m["loss"]

        def window_fn(p, s, k, data, tgt_all, lbl_all, xs, ts, ls,
                      bs_s, hy_s, acc):
            b = batch if mode == "sliced" else xs.shape[1]
            lead = (dp,) if dp > 1 else ()
            out0 = jnp.zeros((b,) + out_shape, dtype=out_dtype)
            mse0 = jnp.zeros((b,), dtype=out_dtype)
            msum0 = jnp.zeros(lead, dtype=out_dtype)
            mmax0 = jnp.zeros(lead, dtype=out_dtype)
            mmin0 = jnp.full(lead, jnp.inf, dtype=out_dtype)
            nerr0 = jnp.zeros(lead + (2,), dtype=jnp.int32)
            if mode == "sliced":
                def scan_body(carry, step):
                    start, bs, hy = step
                    return body(carry, (data, tgt_all, lbl_all, start,
                                        bs, hy))
                xs_scan = (xs, bs_s, hy_s)
            else:
                xs_scan = (xs, ts, ls, bs_s, hy_s)
                scan_body = body
            carry0 = (p, s, k, out0, mse0, msum0, mmax0, mmin0, nerr0)
            if dp > 1:
                carry0 = carry0 + (jnp.int32(0),
                                   jnp.zeros((n_steps,), dtype=out_dtype))
                carry1, _ = jax.lax.scan(scan_body, carry0, xs_scan)
                (p, s, k, out, mse_per, msum, mmax, mmin,
                 nerr) = carry1[:9]
                losses = carry1[10]
            else:
                (p, s, k, out, mse_per, msum, mmax, mmin, nerr), \
                    losses = jax.lax.scan(scan_body, carry0, xs_scan)
            # epoch-accumulator fold — the exact op sequence of the
            # synchronous host fold (window sum computed in-scan from
            # zero, THEN one add onto the running total), so the async
            # segment aggregate is bit-identical (see _get_window_fn);
            # under a data mesh the fold stays per-shard (axis -1 keeps
            # the leading shard axis) with no collective
            with jax.named_scope("acc"):
                acc = {"metrics": jnp.stack(
                           [acc["metrics"][..., 0] + msum,
                            jnp.maximum(acc["metrics"][..., 1], mmax),
                            jnp.minimum(acc["metrics"][..., 2], mmin)],
                           axis=-1),
                       "n_err": acc["n_err"] + nerr}
            stats = {"loss": losses,
                     "metrics": jnp.stack([msum, mmax, mmin], axis=-1),
                     "mse_per": mse_per, "n_err": nerr, "output": out,
                     "acc": acc}
            if final:
                # the segment's ONE stats all-reduce (the mse SUM is the
                # documented f32 reassociation — max/min/integers exact)
                with jax.named_scope("acc"):
                    stats["acc_reduced"] = {
                        "metrics": jnp.stack(
                            [acc["metrics"][:, 0].sum(),
                             acc["metrics"][:, 1].max(),
                             acc["metrics"][:, 2].min()]),
                        "n_err": acc["n_err"].sum(axis=0)}
            return p, s, k, stats

        if self.mesh is not None:
            rep = NamedSharding(self.mesh, P())
            oshard = NamedSharding(
                self.mesh, P("data", *([None] * len(out_shape))))
            if dp > 1:
                sh2 = NamedSharding(self.mesh, P("data", None))
                stat_shard = {"metrics": sh2, "n_err": sh2}
            else:
                stat_shard = {"metrics": rep, "n_err": rep}
            mshard = dict(stat_shard)
            mshard.update({"loss": rep,
                           "mse_per": NamedSharding(self.mesh, P("data")),
                           "output": oshard,
                           "acc": dict(stat_shard)})
            if final:
                mshard["acc_reduced"] = {"metrics": rep, "n_err": rep}
            fn = jax.jit(window_fn, donate_argnums=(0, 1, 11),
                         out_shardings=(self._pshard, self._sshard, rep,
                                        mshard))
        else:
            fn = jax.jit(window_fn, donate_argnums=(0, 1, 11))
        self._window_fns[key_] = fn
        return fn

    def run_window_mse(self, xs, ts, lbl_s, batch_sizes, hypers_s,
                       final=False):
        """K MSE train steps in ONE compiled dispatch over host-stacked
        minibatches ``xs (K, B, *sample)`` / ``ts (K, B, *target)``;
        ``lbl_s (K, B)`` feeds the nearest-class-target error when
        ``class_targets`` is set (pass -1s otherwise)."""
        if self.objective != "mse":
            raise ValueError("run_window_mse needs the mse objective")
        self._check_window_batch(xs.shape[1])
        n_steps = xs.shape[0]
        fn = self._get_window_fn_mse(n_steps, "stacked", final=final)
        if not isinstance(xs, ShardMajorWindow):
            xs = numpy.ascontiguousarray(xs)
        xs = self._place_window(xs, xs.ndim - 2)
        if not isinstance(ts, ShardMajorWindow):
            ts = numpy.ascontiguousarray(ts)
        ts = self._place_window(ts, ts.ndim - 2)
        if not isinstance(lbl_s, ShardMajorWindow):
            lbl_s = numpy.asarray(lbl_s, dtype=numpy.int32)
        lbl_s = self._place_window(lbl_s, 0)
        bs, hypers_s = self._place_window_scalars(batch_sizes, hypers_s)
        return self._dispatch_window(
            "mse", fn, (0, 0, 0, xs, ts, lbl_s, bs, hypers_s), n_steps,
            xs.shape[1], final)

    def run_window_mse_sliced(self, starts, batch, batch_sizes, hypers_s,
                              final=False):
        """Windowed MSE training over the epoch-materialized permuted
        dataset (:meth:`set_epoch_perm`): ``starts (K,)`` are the
        minibatches' row offsets into the epoch order (the loader's
        ``minibatch_class_offset``); each step reads its ``batch`` rows
        as one contiguous ``dynamic_slice``.  Needs targets passed to
        :meth:`set_dataset`."""
        if self.objective != "mse":
            raise ValueError("run_window_mse_sliced needs the mse "
                             "objective")
        if not self.has_epoch_perm or self._targets_p is None:
            raise RuntimeError("set_epoch_perm() with targets before "
                               "run_window_mse_sliced")
        self._check_window_batch(batch)
        n_steps = len(starts)
        fn = self._get_window_fn_mse(n_steps, "sliced", int(batch),
                                     final=final)
        starts = self._place_starts(starts)
        bs, hypers_s = self._place_window_scalars(batch_sizes, hypers_s)
        return self._dispatch_window(
            "mse_sliced", fn,
            (self._data_p, self._targets_p, self._labels_p, starts, None,
             None, bs, hypers_s), n_steps, batch, final)

    def host_fetch(self, tree):
        """``jax.device_get`` that works across processes: leaves whose
        shards live on other hosts are resharded to replicated first
        (one all-gather at READBACK time — window outputs stay
        data-sharded on the hot path and only segment-final reads pay
        the transfer).  Metered on the telemetry d2h byte/call counters
        (ONE call per fetch, however many leaves ride it) — the async
        control plane's zero-mid-epoch-readback pin reads this meter."""
        if faults.enabled():
            # readback injection site (transient RESOURCE_EXHAUSTED /
            # stalled-transfer class).  Like the dispatch site, not
            # retried in place — the supervised launcher's restart +
            # mid-epoch resume is the recovery path.
            faults.check("fused.host_fetch")
        with telemetry.span("trainer.readback") as sp:
            if not self._replicate_outputs:
                host = jax.device_get(tree)
            else:
                rep = NamedSharding(self.mesh, P())

                def _rep(x):
                    if isinstance(x, jax.Array) and \
                            not x.is_fully_addressable:
                        return jax.jit(lambda a: a, out_shardings=rep)(x)
                    return x

                host = jax.device_get(jax.tree.map(_rep, tree))
            if telemetry.enabled():
                nbytes = _nbytes(host)
                telemetry.add_bytes("d2h", nbytes)
                sp.set(bytes=nbytes)
        return host

    def params_finite(self):
        """Device-side all-finite reduction over every parameter — the
        rollback's NaN probe without a full host pull (reference
        nn_rollback.py:105-111 counts NaNs on host; at AlexNet scale
        that is a whole-model D2H per epoch)."""
        if not hasattr(self, "_finite_fn"):
            self._finite_fn = jax.jit(lambda ps: jnp.all(jnp.stack(
                [jnp.isfinite(leaf).all()
                 for leaf in jax.tree.leaves(ps)])))
        return bool(self._finite_fn(self.params))

    def _predict_key(self):
        """Stochastic-pool nets consume PRNG draws at inference too
        (advancing the same key chain the train steps use — resume
        stays exact because the key is snapshot state)."""
        if not self._has_stochastic:
            return None
        self._key, sub = jax.random.split(self._key)
        return sub

    def _predict(self, fn, cost, *inputs):
        """The one call every ``predict*`` variant ends in:
        ``fn(params, *inputs, key)`` under ``trainer.valid.dispatch``."""
        args = (self.params,) + inputs + (self._predict_key(),)
        batch = inputs[-1].shape[0]
        if profiler.enabled():
            self._register_cost("fused.%s.b%d" % (cost, batch), fn, args,
                                steps=1, batch=batch, train=False)
        with telemetry.span("trainer.valid.dispatch"):
            return fn(*args)

    def predict(self, x):
        return self._predict(self._fwd, "predict", self._place_valid(x))

    def predict_with_idx(self, x):
        """Compiled inference: (softmax output, argmax) — what the
        evaluator unit consumes on VALID/TEST minibatches."""
        return self._predict(self._fwd_idx, "predict_idx",
                             self._place_valid(x))

    def predict_indexed(self, idx, with_idx=False):
        """:meth:`predict` (``with_idx``: :meth:`predict_with_idx`) of
        the resident data set's rows at ``idx (batch,)`` (-1 = padded
        slot), as a train window takes its minibatches: only the indices
        cross to the device, the rows are gathered there (and are the
        same bits as the host rows: :meth:`set_dataset`)."""
        if not self.has_dataset:
            raise RuntimeError("set_dataset() before predict_indexed")
        if self.objective == "tokens":
            # {"n_err": [errors, graded, rows], "loss_sum"} on the device
            return self._predict(
                self._fwd_tokens_at, "predict_tokens", self._data_d,
                self._labels_d, self._segments_d,
                self._place_valid_indices(idx))
        fn, cost = ((self._fwd_idx_at, "predict_idx_indexed") if with_idx
                    else (self._fwd_at, "predict_indexed"))
        return self._predict(fn, cost, self._data_d,
                             self._place_valid_indices(idx))

    def host_params(self):
        return jax.tree.map(lambda a: numpy.asarray(a), self.params)

    # -- checkpoint / resume ------------------------------------------------
    def state_dict(self):
        """Full training state as host numpy pytrees: parameters,
        optimizer slots (vel/acc/solver), the dropout PRNG key, and the
        live hyperparameters — everything needed for bit-exact resume
        (the fused twin of the unit path's exports, nn_units.py:316-319)."""
        return {
            "params": jax.tree.map(numpy.asarray, self.params),
            "opt": jax.tree.map(numpy.asarray, self.state),
            "key": numpy.asarray(self._key),
            "hypers": jax.tree.map(float, self.hypers),
        }

    def load_state_dict(self, sd):
        """Restore :meth:`state_dict` output, re-placing every leaf with
        its mesh sharding."""
        self.params = self._place_params(sd["params"])
        self.state = self._place_state(sd["opt"])
        self._key = jax.device_put(
            jnp.asarray(sd["key"]), self._home if self.mesh is None
            else NamedSharding(self.mesh, P()))
        if sd.get("hypers") is not None:
            self.hypers = jax.tree.map(float, sd["hypers"])


class FusedMLP(FusedNet):
    """FC-only fused trainer (back-compat name; flat input)."""

    def __init__(self, layers, input_sample_size, **kwargs):
        # validate BEFORE the base init so a rejected config consumes no
        # PRNG draws from a shared rand (fail-fast like build_fc_specs)
        build_fc_specs(layers, int(input_sample_size),
                       kwargs.get("defaults"))
        super(FusedMLP, self).__init__(
            layers, int(input_sample_size), **kwargs)


def default_hypers(specs):
    """The live hyperparameter pytree: one ``{"w": {...}, "b": {...}}`` per
    parameterized spec (``{}`` for param-less layers), seeded from the
    config values.  Passed to the jitted step as a TRACED argument so LR
    schedules (lr_adjust.py policies) apply per iteration without a
    recompile — the reference mutates ``gd.learning_rate`` the same way
    (lr_adjust.py:61)."""
    hypers = []
    for spec in specs:
        if spec.kind in ("fc", "conv"):
            h = {"w": dict(spec.hyper)}
            if spec.include_bias:
                h["b"] = dict(spec.hyper_bias)
            hypers.append(h)
        elif spec.kind in transformer.KINDS:
            hypers.append(transformer.leaf_hypers(spec))
        else:
            hypers.append({})
    return hypers


def _apply_weight_masks(params, specs):
    """The zero_filter pass: re-zero grouped weight positions before the
    step (the unit graph's ZeroFiller masks the shared Array in place
    each forward pass, BEFORE the GD update — so weight decay/ortho see
    masked weights; parity requires the same order here)."""
    out = []
    for i, (spec, p) in enumerate(zip(specs, params)):
        mask = getattr(spec, "weight_mask", None)
        if mask is not None and "w" in p:
            with jax.named_scope("update.L%02d" % i):
                p = dict(p, w=p["w"] * jnp.asarray(mask, p["w"].dtype))
        out.append(p)
    return out


def _apply_updates(specs, params, state, grads, hypers=None, loads=None):
    """The optimizer pass both objectives share: one ``gd_math.update``
    per parameter leaf, each layer's under its ``update.L00`` scope.
    Under a data mesh GSPMD puts the gradient all-reduce where the
    partial sums arise (the backward product), so the exchange carries
    that op's name and not a scope of its own.  A leaf that has no
    hyperparameters takes no gradient: a ``moe`` entry's selection bias,
    which the step's load of every expert moves (``loads``, by leaf index;
    :func:`transformer.balance`, under ``update.balance``)."""
    new_params, new_state = [], []
    if hypers is None:
        hypers = [None] * len(params)
    for i, (spec, p, st, g, hy) in enumerate(
            zip(specs, params, state, grads, hypers)):
        np_, nst = {}, {}
        with jax.named_scope("update.L%02d" % i):
            if spec.kind in transformer.KINDS:
                leaf_hy = hy if hy else transformer.leaf_hypers(spec)
                for name in p:
                    if name not in leaf_hy:
                        with jax.named_scope("update.balance"):
                            np_[name] = transformer.balance(
                                p[name], loads[i],
                                spec.attrs["balance_rate"])
                        nst[name] = st[name]
                        continue
                    np_[name], nst[name], _ = gd_math.update(
                        jnp, p[name], g[name].astype(p[name].dtype),
                        st[name], leaf_hy[name], spec.flags)
            elif "w" in p:
                np_["w"], nst["w"], _ = gd_math.update(
                    jnp, p["w"], g["w"].astype(p["w"].dtype), st["w"],
                    hy["w"] if hy else spec.hyper, spec.flags)
            if "b" in p:
                hyper_b = hy["b"] if hy else spec.hyper_bias
                flags_b = dict(spec.flags, ortho=False)
                np_["b"], nst["b"], _ = gd_math.update(
                    jnp, p["b"], g["b"].astype(p["b"].dtype), st["b"],
                    hyper_b, flags_b)
        new_params.append(np_)
        new_state.append(nst)
    return new_params, new_state


def _train_step(params, state, x, labels, specs, key=None,
                compute_dtype=None, hypers=None, with_output=False):
    params = _apply_weight_masks(params, specs)
    (loss, (n_err, probs, max_idx)), grads = jax.value_and_grad(
        lambda p: _loss_and_stats(p, x, labels, specs, key, compute_dtype),
        has_aux=True)(params)
    new_params, new_state = _apply_updates(specs, params, state, grads,
                                           hypers)
    metrics = {"loss": loss, "n_err": n_err}
    if with_output:
        metrics["output"] = probs
        metrics["max_idx"] = max_idx
    return new_params, new_state, metrics
