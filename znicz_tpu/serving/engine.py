"""Inference engine — snapshot/package-backed jitted forward with a
shape-bucketed compile cache.

The engine closes the gap between the paper's deployment story (a zip
package consumed by the C++ runtime — ``export.py``) and online
serving: it loads either

* a **training snapshot** (``core/snapshotter.py`` pickle) through the
  ``topology`` sidecar the snapshotter records (the array-free manifest
  of the forward stack; arrays come from the per-unit snapshot state),
  or
* a **deployment package** (``export.import_package``: ``manifest.json``
  + ``.npy`` layers — the same zip libZnicz consumes),

normalizes both into one internal form (typed layer entries + a params
pytree) and builds ONE ``jax.jit``-compiled pure function
``forward(params, x)``.  Params are an *argument*, not a closure, so a
hot reload with an unchanged topology reuses every compiled
executable — zero recompiles across model version bumps.

**Precision modes.** Serving precision is a first-class, measured
axis (``dtype=`` / ``serve --dtype`` / the source's recorded warmup
manifest): ``f32`` is bit-identical to the training forward,
``f32-fast`` serves the same f32 bits through the batch-1 LATENCY
fast path (dot-native weight layout + standalone-dot epilogue for
buckets up to ``root.common.serving.latency_bucket_max`` — see
:func:`_apply_fast_layer`; measured ~15x batch-1 req/s over strict
f32 on the CPU backend, replies within a tight documented pin),
``bf16`` casts params once at load and runs activations in bfloat16
(f32 replies), ``int8`` serves per-output-channel symmetrically
quantized weights with the dequant folded into the executable — 4x
fewer weight bytes per dispatch (:mod:`znicz_tpu.serving.quant`).
The dtype joins
the compile-cache key, the per-dtype cost-registry entries and the
``dtype_<mode>`` telemetry labels; accuracy deltas per bucket are
measured and pinned by :mod:`znicz_tpu.serving.accuracy`.

**Shape buckets.** jit compiles per input shape, so free-form batch
sizes would recompile constantly.  ``predict`` pads every batch up to
the next bucket (powers of two up to ``max_batch`` by default) and
slices the padding back off; :meth:`warmup` eagerly compiles every
bucket so steady-state requests NEVER trigger a compile (asserted by
``tools/serving_smoke.py`` via the ``jax.backend_compiles`` telemetry
counter).

Telemetry (when enabled): per-bucket compile counters
(``serving.compiles.<bucket>``) and prediction counters
(``serving.predictions.bucket_<n>``), a ``serving.warm_buckets`` gauge
(compile-cache coverage at a glance on ``/metrics``), a
``serving.predict`` span per dispatch (carrying the request ids it
served), and a ``serving.model_version`` gauge.  Model swaps land in
the flight recorder as ``serving.reload`` events.
"""

import json
import os
import threading
import time
import zipfile

import numpy

from znicz_tpu.core.config import root
from znicz_tpu.core.logger import Logger
from znicz_tpu.core import faults
from znicz_tpu.core import telemetry
from znicz_tpu.analysis import locksmith
from znicz_tpu.serving import quant, reqtrace
from znicz_tpu.ops import transformer


def default_buckets(max_batch):
    """Powers of two up to (and always including) ``max_batch``."""
    max_batch = int(max_batch)
    if max_batch < 1:
        raise ValueError("max_batch must be >= 1, got %d" % max_batch)
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


#: fused-layer activation epilogues by package type string (the same
#: tables run_package_numpy pins the numpy/C++ runners to)
_FC_ACT = {"all2all": "linear", "all2all_tanh": "tanh",
           "all2all_relu": "relu", "all2all_str": "strict_relu",
           "all2all_sigmoid": "sigmoid"}
_CONV_ACT = {"conv": "linear", "conv_tanh": "tanh", "conv_relu": "relu",
             "conv_str": "strict_relu", "conv_sigmoid": "sigmoid"}
_STANDALONE_ACT = {"activation_tanh": "tanh",
                   "activation_sigmoid": "sigmoid",
                   "activation_relu": "relu",
                   "activation_str": "strict_relu"}


def _nhwc(y):
    """The implicit single-channel NHWC convention every spatial unit
    shares (nn_units.as_nhwc): 3-D (B, H, W) batches gain a channel
    axis; 4-D pass through."""
    if y.ndim == 3:
        return y.reshape(y.shape + (1,))
    return y


def _apply_quantized_layer(entry, params, y):
    """One int8-quantized FC/conv layer: the dot runs against the
    int8 weights (converted in registers — XLA fuses the convert into
    the contraction's operand read, so the executable streams int8
    bytes from device memory) and the per-output-channel dequant
    scale applies to the dot's OUTPUT — algebraically identical to
    scaling the weights, but it keeps the scale multiply out of the
    matmul operand, where it would force the backend to materialize a
    full f32 copy of the weights per dispatch."""
    import jax.numpy as jnp
    from znicz_tpu.ops import activations, dense
    from znicz_tpu.ops import conv as conv_ops

    tpe = entry["type"]
    q = params["weights_q8"].astype(jnp.float32)
    scale = params["weights_scale"]
    b = params.get("bias")
    include_bias = bool(entry.get("include_bias", True)) and \
        b is not None
    if tpe == "softmax" or tpe.startswith("all2all"):
        y = y.reshape(y.shape[0], -1)
        z = dense.forward_jax(
            y, q, None, activation="linear",
            weights_transposed=bool(entry.get("weights_transposed")),
            include_bias=False)
        z = z * scale.reshape(1, -1)
        if include_bias:
            z = z + b
        if tpe == "softmax":
            z, _ = dense.softmax_jax(z)
            return z
        return activations.apply_jax(_FC_ACT[tpe], z)
    if tpe.startswith("conv"):
        z = conv_ops.forward_jax(
            _nhwc(y), q, None, int(entry["ky"]), int(entry["kx"]),
            tuple(int(v) for v in entry["padding"]),
            tuple(int(v) for v in entry["sliding"]),
            activation="linear", include_bias=False)
        # NHWC output: kernels are the trailing channel axis
        z = z * scale.reshape(1, 1, 1, -1)
        if include_bias:
            z = z + b
        return activations.apply_jax(_CONV_ACT[tpe], z)
    raise ValueError(
        "quantized serving: unsupported layer type %r" % tpe)


def _apply_fast_layer(entry, params, y):
    """One FC layer on the batch-1 LATENCY fast path (serving dtype
    ``f32-fast``, buckets <= ``root.common.serving.latency_bucket_max``):
    the contraction runs as a STANDALONE dot — an optimization
    barrier between the dot and the bias/activation epilogue stops
    XLA from output-fusing them, which on the CPU backend would turn
    the small-batch dot into a naive loop instead of the GEMV/GEMM
    runtime call.  The weights already sit in the dot-native layout
    (:func:`znicz_tpu.serving.quant.convert_host_params`), so the
    program carries no weight transpose either.  The barrier is the
    identity on values — the dot, the bias add and the activation
    compute exactly what the fused epilogue computes, in the same
    order.  Non-FC layers (conv/pool/norm/standalone activations)
    keep the standard path."""
    import jax
    from znicz_tpu.ops import activations, dense

    tpe = entry["type"]
    if not (tpe == "softmax" or tpe.startswith("all2all")) or \
            "weights_q8" in params:
        return _apply_layer(entry, params, y)
    b = params.get("bias")
    include_bias = bool(entry.get("include_bias", True)) and \
        b is not None
    y = y.reshape(y.shape[0], -1)
    z = dense.forward_jax(
        y, params["weights"], None, activation="linear",
        weights_transposed=bool(entry.get("weights_transposed")),
        include_bias=False)
    z = jax.lax.optimization_barrier(z)
    if include_bias:
        z = z + b
    if tpe == "softmax":
        z, _ = dense.softmax_jax(z)
        return z
    return activations.apply_jax(_FC_ACT[tpe], z)


def _apply_layer(entry, params, y):
    """One manifest layer as a pure jax computation (the jax twin of
    ``export.run_package_numpy`` — same layer scope, same semantics).
    Layers carrying int8-quantized weights route through
    :func:`_apply_quantized_layer`."""
    if "weights_q8" in params:
        return _apply_quantized_layer(entry, params, y)
    from znicz_tpu.ops import activations, dense
    from znicz_tpu.ops import conv as conv_ops
    from znicz_tpu.ops import normalization as norm_ops
    from znicz_tpu.ops import pooling as pool_ops

    tpe = entry["type"]
    if tpe == "softmax" or tpe.startswith("all2all"):
        w = params["weights"]
        b = params.get("bias")
        include_bias = bool(entry.get("include_bias", True)) and \
            b is not None
        transposed = bool(entry.get("weights_transposed", False))
        y = y.reshape(y.shape[0], -1)
        act = "linear" if tpe == "softmax" else _FC_ACT[tpe]
        y = dense.forward_jax(y, w, b, activation=act,
                              weights_transposed=transposed,
                              include_bias=include_bias)
        if tpe == "softmax":
            y, _ = dense.softmax_jax(y)
        return y
    if tpe.startswith("conv"):
        w = params["weights"]
        b = params.get("bias")
        include_bias = bool(entry.get("include_bias", True)) and \
            b is not None
        if entry.get("weights_transposed"):
            w = w.T
        return conv_ops.forward_jax(
            _nhwc(y), w, b, int(entry["ky"]), int(entry["kx"]),
            tuple(int(v) for v in entry["padding"]),
            tuple(int(v) for v in entry["sliding"]),
            activation=_CONV_ACT[tpe], include_bias=include_bias)
    if tpe in ("max_pooling", "avg_pooling"):
        return pool_ops.pooling_fwd_jax(
            _nhwc(y), int(entry["ky"]), int(entry["kx"]),
            tuple(int(v) for v in entry["sliding"]),
            mode=("max" if tpe == "max_pooling" else "avg"))
    if tpe == "norm":
        return norm_ops.lrn_forward_jax(
            y, alpha=float(entry["alpha"]), beta=float(entry["beta"]),
            k=float(entry["k"]), n=int(entry["n"]))
    if tpe == "activation_mul":
        return y * float(entry["factor"])
    if tpe.startswith("activation_"):
        act = _STANDALONE_ACT.get(tpe)
        if act is not None:
            return activations.apply_jax(act, y)
        return activations.ext_apply_jax(tpe[len("activation_"):], y)
    if tpe == "dropout":
        return y  # inference identity
    raise ValueError("serving engine: unsupported layer type %r" % tpe)


_EXT_ACT = ("log", "tanhlog", "sincos")


def _validate_layers(layers):
    """Fail at LOAD time for anything _apply_layer would reject at
    trace time — a bad model must never take the first request down."""
    for entry in layers:
        tpe = entry["type"]
        name = entry.get("name", tpe)
        if tpe in transformer.KINDS or tpe in transformer.STRUCTURAL:
            transformer.refuse(tpe, "serving engine")
        if tpe == "activation_mul":
            if entry.get("factor") is None:
                raise ValueError(
                    "layer %r: activation_mul factor is unset — the "
                    "snapshot/package was written before the first "
                    "minibatch auto-set it" % name)
            continue
        if tpe == "softmax" or tpe in _FC_ACT or tpe in _CONV_ACT or \
                tpe in ("max_pooling", "avg_pooling", "norm", "dropout"):
            continue
        if tpe.startswith("activation_") and (
                tpe in _STANDALONE_ACT or
                tpe[len("activation_"):] in _EXT_ACT):
            continue
        raise ValueError("serving engine: unsupported layer type %r "
                         "(layer %r)" % (tpe, name))


class _Model(object):
    """One loaded model generation — swapped atomically on reload.

    ``warm`` (the compiled-bucket set) lives HERE, not on the engine:
    an in-flight predict on the outgoing model during a topology-
    changing reload must mark the OLD generation's buckets, never the
    new one's (which would make warmup skip a bucket that was never
    compiled for the new function).

    ``host_params`` keeps the pre-upload numpy arrays so
    :meth:`InferenceEngine.evict` can release the device copies (and
    the executables) and :meth:`~InferenceEngine.restore` can bring
    them back without re-reading the source."""

    __slots__ = ("layers", "params", "fn", "key", "dtype",
                 "sample_shape", "source", "version", "warm",
                 "host_params", "dev_bytes", "serve_dtype",
                 "fast_max")

    def __init__(self, layers, params, fn, key, dtype, sample_shape,
                 source, version, warm, host_params=None,
                 serve_dtype="f32", fast_max=0):
        self.layers = layers
        self.params = params
        self.fn = fn
        self.key = key
        self.dtype = dtype
        self.sample_shape = sample_shape
        self.source = source
        self.version = version
        self.warm = warm
        self.host_params = host_params
        #: the serving precision mode ("f32" | "f32_fast" | "bf16" |
        #: "int8") this generation's params are stored in — fixed per
        #: load
        self.serve_dtype = serve_dtype
        #: f32-fast only: the largest bucket dispatching the
        #: standalone-dot fast variant (the latency_bucket_max knob
        #: captured at load — it shapes the traced program, so it
        #: lives on the generation and in the compile key)
        self.fast_max = int(fast_max)
        #: resident param footprint, computed ONCE — the registry's
        #: budget sweep reads this per request and must not walk the
        #: whole pytree each time (sizes never change for a generation)
        self.dev_bytes = sum(
            int(v.nbytes) for p in (params or []) for v in p.values())


def _build_forward(layers, serve_dtype="f32", fast_max=0):
    """Compose the layer chain into one jitted ``forward(params, x)``.

    ``layers`` is static (closed over); ``params`` is a pytree argument
    so param-only reloads hit the existing executable.

    ``serve_dtype`` selects the low-precision data path
    (:mod:`znicz_tpu.serving.quant`):

    * ``"f32"`` — the historical bit-identical path (identical jaxpr).
    * ``"f32_fast"`` — the batch-1 latency path: shape buckets up to
      ``fast_max`` (the ``latency_bucket_max`` knob captured at load)
      trace the standalone-dot variant (:func:`_apply_fast_layer`) —
      the batch size is static at trace time, so each bucket's
      executable picks its variant at COMPILE time and the dispatch
      path is branch-free.  Larger buckets keep the standard
      fused-epilogue program over the same dot-native weight layout.
    * ``"bf16"`` — activations run in bfloat16 end to end (params
      arrive pre-cast), outputs cast back to f32 at the jit boundary.
    * ``"int8"`` — quantized layers carry ``weights_q8`` (int8) +
      ``weights_scale`` (f32); the dequant is folded INTO the jitted
      program (:func:`_apply_quantized_layer`), so the executable's
      weight reads are int8 — 4x fewer bytes from device memory than
      f32 — while activations and accumulation stay in the model's
      float dtype.
    """
    import jax
    import jax.numpy as jnp
    out_f32 = serve_dtype == "bf16"
    fast_mode = serve_dtype == "f32_fast"
    fast_max = int(fast_max)

    def forward(params, x):
        apply_one = (_apply_fast_layer
                     if fast_mode and x.shape[0] <= fast_max
                     else _apply_layer)
        y = x
        for entry, p in zip(layers, params):
            y = apply_one(entry, p, y)
        if out_f32:
            # bf16 serves float32 replies — clients never see bf16
            y = y.astype(jnp.float32)
        return y

    return jax.jit(forward)


class InferenceEngine(Logger):
    """Serves a trained forward stack as a pure jitted function.

    ``source`` is a snapshot pickle path, a package zip path, or a
    ``(manifest, arrays)`` pair (``export.import_package`` output, the
    in-memory path).  ``max_batch`` caps the
    largest bucket; ``buckets`` overrides the power-of-two ladder.
    ``sample_shape`` overrides the per-sample input shape when the
    source does not record one (old packages).

    ``dtype`` pins the serving precision mode — ``"f32"`` (default,
    bit-identical), ``"f32-fast"`` (same f32 bits, batch-1 latency
    fast path — its own compile key + accuracy pin), ``"bf16"``
    (params + activations bfloat16, f32 replies) or ``"int8"``
    (per-output-channel quantized weights with the dequant folded
    into the executable) — see :mod:`znicz_tpu.serving.quant`.
    ``None`` follows the source's recorded warmup manifest
    (``serving.dtype``), falling back to f32.  Unknown strings raise
    immediately.
    """

    def __init__(self, source=None, max_batch=None, buckets=None,
                 sample_shape=None, warmup=None, name=None,
                 dtype=None):
        super(InferenceEngine, self).__init__(
            logger_name="InferenceEngine")
        cfg = root.common.serving
        #: operator-pinned serving dtype (validated NOW — a typo must
        #: fail the constructor, not silently serve f32); None follows
        #: the source manifest
        self._dtype_pin = (quant.normalize_dtype(dtype)
                           if dtype is not None else None)
        #: registry model name; when set, every telemetry series /
        #: breaker / journal event this engine emits carries a
        #: ``model_<name>`` label so multi-model metrics never collide
        self.name = name
        #: True when the caller pinned the bucket ladder — a source's
        #: recorded warmup manifest must not override an explicit choice
        self._buckets_explicit = bool(buckets) or max_batch is not None
        if buckets:
            self.buckets = tuple(sorted(int(b) for b in buckets))
            if max_batch is not None and \
                    int(max_batch) != self.buckets[-1]:
                raise ValueError(
                    "max_batch %r contradicts buckets %r"
                    % (max_batch, buckets))
        else:
            self.buckets = default_buckets(
                max_batch if max_batch is not None
                else cfg.get("max_batch", 64))
        self.max_batch = self.buckets[-1]
        self._warmup_manifest = None
        self._evictions = 0
        self._warmup_wanted = (bool(cfg.get("warmup", True))
                               if warmup is None else bool(warmup))
        self._sample_shape_override = (
            tuple(sample_shape) if sample_shape is not None else None)
        self._model = None
        self._load_lock = locksmith.lock("serving.engine.load")
        self._version = 0
        self._ready = threading.Event()
        #: per-bucket circuit breakers (serving/breaker.py), created
        #: lazily on first dispatch of each bucket; they deliberately
        #: survive hot reloads — backend flakiness is not a property of
        #: one model generation
        self._breakers = {}
        self._breaker_lock = locksmith.lock("serving.engine.breakers")
        if source is not None:
            self.load(source)

    # -- introspection ------------------------------------------------------
    @property
    def ready(self):
        """True once a model is loaded AND warmup (when wanted) ran."""
        return self._ready.is_set()

    @property
    def version(self):
        return self._version

    @property
    def source(self):
        m = self._model
        return m.source if m is not None else None

    @property
    def sample_shape(self):
        m = self._model
        return m.sample_shape if m is not None else None

    @property
    def dtype(self):
        """The loaded model's activation/input dtype (None before a
        load) — the HTTP front end parses request bodies straight into
        it.  bf16 engines take bf16 activations; int8 engines quantize
        WEIGHTS only, so their inputs stay in the model's float dtype."""
        m = self._model
        return m.dtype if m is not None else None

    @property
    def serve_dtype(self):
        """The serving precision mode ("f32" | "f32_fast" | "bf16" |
        "int8") — the dtype axis of the compile-cache key, the warmup
        manifest, the per-dtype cost-registry entries and the
        continuous batcher's dispatch lanes."""
        m = self._model
        if m is not None:
            return m.serve_dtype
        return self._dtype_pin or "f32"

    @property
    def compile_key(self):
        """The loaded generation's compile-cache key (None before a
        load): serving dtype + f32-fast bucket ceiling + topology +
        array shapes/dtypes.  Exposed so tests and the serving smoke
        can PROVE two engine modes never alias executables (the
        fast/strict distinctness pin) without reaching into model
        internals."""
        m = self._model
        return m.key if m is not None else None

    @property
    def warm_buckets(self):
        m = self._model
        return tuple(sorted(m.warm)) if m is not None else ()

    @property
    def resident(self):
        """True when the model's params live on the device (False
        after :meth:`evict`, before the lazy :meth:`restore`)."""
        m = self._model
        return m is not None and m.params is not None

    @property
    def device_bytes(self):
        """Device footprint of the resident params (0 when evicted or
        unloaded) — the quantity the registry's LRU budget meters.
        A cached per-generation constant, safe on the hot path."""
        m = self._model
        if m is None or m.params is None:
            return 0
        return m.dev_bytes

    def _label(self, series, **labels):
        """Per-model telemetry naming: unnamed engines keep the exact
        historical series names; named (registry-hosted) engines get a
        ``model_<name>`` label so several models' metrics coexist on
        one /metrics page.  Low-precision engines additionally carry a
        ``dtype_<mode>`` label (f32 keeps the exact historical names),
        so the same model served at two precisions separates cleanly.
        """
        if self.name is not None:
            labels["model"] = self.name
        sd = self.serve_dtype
        if sd != "f32":
            labels["dtype"] = sd
        # reviewed naming wrapper: graftlint checks every _label CALL
        # site's literal series + label keys instead; the keys added
        # here (model/dtype) are both in the bounded vocabulary
        return telemetry.labeled(  # graftlint: disable=telemetry-series,telemetry-cardinality # noqa
            series, **labels)

    def stats(self):
        """healthz payload: what is loaded, how warm, how big."""
        m = self._model
        payload = {
            "ready": self.ready,
            "model_version": self._version,
            "source": m.source if m else None,
            "layers": [e["type"] for e in m.layers] if m else None,
            "sample_shape": (list(m.sample_shape)
                             if m and m.sample_shape else None),
            "dtype": str(numpy.dtype(m.dtype)) if m else None,
            "serve_dtype": self.serve_dtype,
            "buckets": list(self.buckets),
            "warm_buckets": list(self.warm_buckets),
            "resident": self.resident,
            "device_bytes": self.device_bytes,
            "evictions": self._evictions,
        }
        if self.name is not None:
            payload["model"] = self.name
        if m is not None and m.serve_dtype == "f32_fast":
            # the fast-variant ceiling this generation compiled with
            # (the /models truth for the latency_bucket_max knob)
            payload["latency_bucket_max"] = m.fast_max
        if self._warmup_manifest is not None:
            payload["warmup_manifest"] = self._warmup_manifest
        if self._breakers:
            # snapshot under the creation lock: a first dispatch of a
            # new bucket may be inserting concurrently
            with self._breaker_lock:
                items = sorted(self._breakers.items())
            payload["breakers"] = {
                str(bucket): breaker.status() for bucket, breaker in items}
        return payload

    # -- loading ------------------------------------------------------------
    def load(self, source, sample_shape=None):
        """Load (or hot-reload) a model; returns the new version.

        Serving continues on the old model until the new one is swapped
        in; with an unchanged topology the compiled executables (and
        the warm-bucket set) carry over, so a reload costs zero
        recompiles.
        """
        layers, arrays_list, label, src_shape, serving_mf = \
            self._load_source(source)
        _validate_layers(layers)
        host_params = []
        dtype = None
        for arrs in arrays_list:
            p = {}
            for attr, value in arrs.items():
                value = numpy.asarray(value)
                if dtype is None and not attr.startswith("quant_") \
                        and numpy.issubdtype(value.dtype,
                                             numpy.floating):
                    dtype = value.dtype
                p[attr] = value
            host_params.append(p)
        dtype = dtype or numpy.float32
        # serving precision: the constructor pin wins; otherwise the
        # source's recorded warmup manifest selects (a package exported
        # for int8 serving serves int8 everywhere it lands); f32 else.
        # Resolved per load so a reload of a different-manifest source
        # behaves like a topology change (the key below diverges).
        serve_dtype = self._dtype_pin or quant.normalize_dtype(
            (serving_mf or {}).get("dtype"))
        # f32-fast: the fast-variant bucket ceiling shapes each
        # bucket's traced program, so it is captured per load (live
        # config read — a reload adopts a changed knob) and joins the
        # compile key below
        fast_max = (int(root.common.serving.get(
            "latency_bucket_max", 8)) if serve_dtype == "f32_fast"
            else 0)
        # convert the HOST copies: quantized/cast arrays are what gets
        # uploaded, what evict keeps, and what restore re-uploads — an
        # int8 model's restore moves int8 bytes, not the f32 originals
        host_params = quant.convert_host_params(layers, host_params,
                                                serve_dtype)
        dtype = quant.input_dtype(serve_dtype, dtype)
        # pin the params device-resident ONCE — dispatches must not pay
        # a host->device upload per request (jit's cache key only sees
        # shape/dtype, so this changes nothing else)
        import jax
        params = jax.device_put(host_params)
        if sample_shape is not None:
            shape = tuple(sample_shape)
        else:
            shape = src_shape or self._sample_shape_override or \
                _derived_sample_shape(layers, params)
        # the compile-cache key: serving dtype (+ the f32-fast bucket
        # ceiling) + topology + array shapes/dtypes — any difference
        # means the old executables cannot be reused.  The fast mode
        # NEVER aliases strict-f32 executables: serve_dtype differs,
        # and two fast loads under different latency_bucket_max
        # values differ too.
        key = json.dumps(
            [serve_dtype, fast_max, layers,
             [{a: [str(v.dtype)] + list(v.shape)
               for a, v in p.items()} for p in params]],
            sort_keys=True, default=str)
        # manifest-ladder adoption happens LAST before the swap —
        # nothing below here raises until warmup, whose failure
        # handler restores these limits with the model.  (Adopting any
        # earlier would let a load that dies at device_put/shape
        # derivation leave the surviving generation with the failed
        # source's ladder: a shrunk max_batch 400ing request sizes
        # that were valid a second ago.)
        with self._load_lock:
            # limits snapshot + ladder adoption live INSIDE the load
            # lock with the swap: two concurrent load()s interleaving
            # here could snapshot each other's half-adopted ladder and
            # roll back to the WRONG limits (graftlint lock-guard
            # finding — buckets/max_batch/_warmup_manifest are
            # lock-guarded on the rollback path)
            old_limits = (self.buckets, self.max_batch,
                          self._warmup_manifest)
            if serving_mf is not None:
                self._warmup_manifest = serving_mf
                if not self._buckets_explicit and \
                        serving_mf.get("buckets"):
                    # adopt the ahead-of-time warmup manifest recorded
                    # at export/snapshot time: the replica warms the
                    # EXACT bucket ladder the exporter's serving
                    # config pinned
                    ladder = tuple(sorted(
                        int(b) for b in serving_mf["buckets"]))
                    if ladder and ladder[0] >= 1:
                        self.buckets = ladder
                        self.max_batch = ladder[-1]
            old = self._model
            old_bytes = self.device_bytes
            # an evicted old generation has no fn to carry over —
            # rebuild even when the topology key matches
            reused = old is not None and old.key == key and \
                old.fn is not None
            if reused:
                # unchanged topology: the compiled executables AND the
                # warm-bucket set carry over to the new generation
                fn, warm = old.fn, old.warm
            else:
                fn = _build_forward(layers, serve_dtype, fast_max)
                warm = set()
                self._ready.clear()
            self._version += 1
            model = _Model(layers, params, fn, key, dtype, shape,
                           label, self._version, warm,
                           host_params=host_params,
                           serve_dtype=serve_dtype,
                           fast_max=fast_max)
            self._model = model
            if telemetry.enabled():
                telemetry.gauge(self._label(
                    "serving.model_version")).set(self._version)
                telemetry.gauge(self._label(
                    "serving.warm_buckets")).set(len(model.warm))
        self._ledger_swap(old_bytes, self.device_bytes)
        event = {"version": self._version, "source": label,
                 "topology_changed": not reused,
                 "serve_dtype": serve_dtype}
        if self.name is not None:
            event["model"] = self.name
        telemetry.record_event("serving.reload", **event)
        self.info("model v%d <- %s (%d layers, dtype %s, serve %s, "
                  "sample shape %s)", self._version, label,
                  len(layers), numpy.dtype(dtype).name, serve_dtype,
                  shape)
        if not self._warmup_wanted:
            self._ready.set()
            return self._version
        try:
            self.warmup()
        except Exception:
            # a model that passed structural validation but fails at
            # trace/compile time must not brick a healthy server: roll
            # the swap back so serving continues on the old generation
            with self._load_lock:
                if self._model is model:
                    self._model = old
                    self._version = old.version if old else 0
                    # ... with ITS serving limits — the failed
                    # source's adopted ladder must not survive it
                    (self.buckets, self.max_batch,
                     self._warmup_manifest) = old_limits
                    if telemetry.enabled():
                        # keep the gauge on the version that SERVES
                        telemetry.gauge(self._label(
                            "serving.model_version")).set(self._version)
            if old is not None:
                self._ready.set()
                self.warning("reload of %s failed at warmup; still "
                             "serving v%d", label, old.version)
            raise
        return self._version

    def _load_source(self, source):
        """Normalize any source into (layers, per-layer arrays, label,
        sample_shape, warmup-manifest-or-None)."""
        if isinstance(source, tuple) and len(source) == 2:
            manifest, arrays = source
            return self._from_manifest(manifest, arrays, "<in-memory>")
        path = os.fspath(source)
        if zipfile.is_zipfile(path):
            from znicz_tpu.export import import_package
            manifest, arrays = import_package(path)
            return self._from_manifest(manifest, arrays, path)
        from znicz_tpu.core.snapshotter import SnapshotterToFile
        state = SnapshotterToFile.import_(path)
        return self._from_snapshot(state, path)

    def _from_manifest(self, manifest, arrays, label):
        layers, arrays_list = [], []
        for entry in manifest["layers"]:
            norm = {k: v for k, v in entry.items() if k != "arrays"}
            p = {}
            for attr, fname in entry.get("arrays", {}).items():
                if attr.startswith("zero_filter"):
                    continue  # provenance; weights arrive pre-masked
                p[attr] = arrays[fname]
            layers.append(norm)
            arrays_list.append(p)
        shape = manifest.get("input_sample_shape")
        shape = tuple(int(d) for d in shape) if shape else None
        return layers, arrays_list, label, shape, \
            manifest.get("serving")

    def _from_snapshot(self, state, label):
        topology = state.get("topology")
        if not topology or not topology.get("layers"):
            raise ValueError(
                "%s: snapshot carries no serving topology (written by "
                "an older snapshotter, or the workflow has no typed "
                "forwards) — re-snapshot with this version or serve a "
                "deployment package (export.export_package)" % label)
        units = state.get("units", {})
        layers, arrays_list = [], []
        for entry in topology["layers"]:
            norm = {k: v for k, v in entry.items()
                    if k not in ("arrays", "unit")}
            ustate = units.get(entry["unit"], {})
            p = {}
            for attr in entry.get("arrays", ()):
                value = ustate.get(attr)
                if value is not None:
                    p[attr] = numpy.asarray(value)
            layers.append(norm)
            arrays_list.append(p)
        _fill_from_fused_state(state, topology, layers, arrays_list,
                               label)
        shape = topology.get("input_sample_shape")
        shape = tuple(int(d) for d in shape) if shape else None
        return layers, arrays_list, label, shape, \
            topology.get("serving")

    # -- buckets / prediction ----------------------------------------------
    def bucket_for(self, n):
        """Smallest bucket >= n rows; raises for n over max_batch."""
        n = int(n)
        if n < 1:
            raise ValueError("batch of %d rows" % n)
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError("batch of %d rows exceeds max_batch %d"
                         % (n, self.max_batch))

    def _bucket_breaker(self, bucket):
        """The bucket's circuit breaker (None when
        ``root.common.serving.breaker_threshold`` is 0).

        Config is read on EVERY call: setting ``breaker_threshold=0``
        at runtime bypasses existing breakers immediately (an open
        bucket stops 503ing without a process restart), and live
        threshold/cooldown/half-open changes are adopted in place
        without resetting breaker state.
        """
        cfg = root.common.serving
        threshold = int(cfg.get("breaker_threshold", 5) or 0)
        if threshold <= 0:
            return None
        cooldown_s = float(cfg.get("breaker_cooldown_ms", 1000.0)) / 1e3
        half_open_max = int(cfg.get("breaker_half_open_max", 1))
        breaker = self._breakers.get(bucket)
        if breaker is None:
            from znicz_tpu.serving.breaker import CircuitBreaker
            with self._breaker_lock:
                breaker = self._breakers.get(bucket)
                if breaker is None:
                    bname = ("serving.b%d" % bucket
                             if self.name is None else
                             "serving.%s.b%d" % (self.name, bucket))
                    breaker = CircuitBreaker(
                        bname, threshold=threshold,
                        cooldown_s=cooldown_s,
                        half_open_max=half_open_max)
                    self._breakers[bucket] = breaker
                    return breaker
        if (breaker.threshold != max(threshold, 1)
                or breaker.cooldown_s != cooldown_s
                or breaker.half_open_max != max(half_open_max, 1)):
            breaker.reconfigure(threshold, cooldown_s, half_open_max)
        return breaker

    def predict(self, x, request_ids=None):
        """Forward ``x`` (batch-first) through the loaded model.

        Pads to the enclosing bucket, dispatches the jitted function,
        slices the padding back off, returns a numpy array.
        ``request_ids`` (propagated by the micro-batcher from the HTTP
        front end) rides into the ``serving.predict`` span so a trace
        ties each device dispatch back to the requests it served.
        """
        m = self._model
        if m is None:
            raise RuntimeError("no model loaded")
        # snapshot the callable + params: a concurrent evict() nulls
        # them on the generation in place, and an admitted dispatch
        # must keep the executable alive through its own forward (the
        # local refs do) instead of crashing mid-flight.  Bounded
        # retry: under budget thrash another request's evict can land
        # between our restore and the re-read — loop a few times, then
        # fail as the server error it is (NOT a client 400)
        fn = params = None
        for _ in range(3):
            fn, params = m.fn, m.params
            if fn is not None and params is not None:
                break
            # evicted by the registry's LRU budget: lazy re-warm —
            # params re-upload + executable rebuild (a persistent-
            # cache load when compile_cache is wired)
            self.restore()
            m = self._model
        else:
            raise RuntimeError(
                "model%s evicted faster than it restores — the "
                "registry memory budget is thrashing"
                % (" %r" % self.name if self.name else ""))
        x = numpy.asarray(x, dtype=m.dtype)
        if m.sample_shape is not None:
            sample = tuple(m.sample_shape)
            if matches_sample_shape(x.shape, sample):
                # single-sample convenience — shape-matched, never
                # rank-matched (a rank-only test would swallow e.g. a
                # 3-D (B, H, W) batch under a 3-D NHWC sample shape)
                x = x[None]
            _check_sample_shape(x.shape[1:], sample)
            if x.shape[1:] != sample:
                # normalize the accepted NHWC-equivalent convention to
                # the recorded shape — the jit cache keys on concrete
                # shapes, so the variant must share the warmed
                # executables, not silently compile its own
                x = x.reshape((x.shape[0],) + sample)
        n = x.shape[0]
        bucket = self.bucket_for(n)
        if bucket > n:
            padded = numpy.zeros((bucket,) + x.shape[1:], dtype=m.dtype)
            padded[:n] = x
            x = padded
        # graceful degradation: an open breaker rejects BEFORE any
        # device work (CircuitOpenError -> HTTP 503 + Retry-After).
        # Admitted dispatches report exactly one success/failure back,
        # and the breaker-gated region retries TRANSIENT faults
        # (RESOURCE_EXHAUSTED-class, injected or organic) with bounded
        # backoff first — only an exhausted retry counts as a failure.
        breaker = self._bucket_breaker(bucket)

        def _dispatch():
            if faults.enabled():
                faults.check("serving.forward")
                if self.name:
                    # per-model site: the release smoke sabotages ONE
                    # candidate generation without touching its live
                    # peer (serving/release.py)
                    faults.check("serving.forward.%s" % self.name)
            return fn(params, x)

        def _forward():
            return faults.retry_call(_dispatch, "serving.forward")

        # the one place a compile can happen: the first dispatch of
        # this (bucket, model-generation) pair.  Marked warm only AFTER
        # the dispatch succeeds — a failed trace must not make
        # warmup()/the counters believe the bucket compiled.
        first = bucket not in m.warm
        if first:
            from znicz_tpu.core import profiler
            if profiler.enabled():
                # cost registry: this bucket's forward executable
                # (lowered pre-dispatch — the dispatch reuses the
                # trace).  Low-precision entries grow a dtype suffix
                # (f32 keeps the exact historical names) and every
                # entry carries dtype= meta, so per-dtype bytes
                # accessed / operational intensity are separable.
                cost_name = ("serving.forward.b%d" % bucket
                             if self.name is None else
                             "serving.forward.%s.b%d"
                             % (self.name, bucket))
                if m.serve_dtype != "f32":
                    cost_name += "." + m.serve_dtype
                meta = {"bucket": bucket, "model_version": m.version,
                        "dtype": m.serve_dtype}
                if self.name is not None:
                    # meta-addressable per model: consumers look
                    # entries up via cost_entries_by_meta(model=...,
                    # dtype=...) instead of rebuilding name strings
                    meta["model"] = self.name
                profiler.register_jit_cost(
                    cost_name, fn, (params, x), **meta)
        # admission immediately adjacent to the recorded region: an
        # admitted call (half-open probe slot included) is ALWAYS
        # answered by exactly one record_* below — nothing that can
        # raise may sit between allow() and the try
        probe_slot = breaker.allow() if breaker is not None else False
        try:
            t_fwd0 = time.monotonic()
            if not telemetry.enabled():
                y = numpy.asarray(_forward())[:n]
            else:
                attrs = {"rows": n, "bucket": bucket}
                if self.name is not None:
                    attrs["model"] = self.name
                if request_ids:
                    attrs["request_ids"] = list(request_ids)
                with telemetry.span("serving.predict", **attrs):
                    y = numpy.asarray(_forward())[:n]
                # per-bucket traffic: which compiled executables earn
                # their keep (next to serving.compiles.<bucket> on
                # /metrics); named engines carry the model label
                telemetry.counter(self._label(
                    "serving.predictions", bucket=bucket)).inc()
            t_fwd1 = time.monotonic()
        except (ValueError, TypeError):
            # shape/dtype errors surfacing at trace time are the
            # CLIENT's fault (server.py maps them to 400) — no evidence
            # about backend health, so they must not push the breaker
            # toward open (malformed traffic could otherwise deny
            # service to valid requests)
            if breaker is not None:
                breaker.record_neutral(probe_slot)
            raise
        except Exception:
            if breaker is not None:
                breaker.record_failure()
            raise
        except BaseException:
            # KeyboardInterrupt/SystemExit mid-dispatch (a notebook
            # Ctrl-C) says nothing about backend health — release the
            # (possibly half-open probe) slot, or the bucket wedges
            # open forever with every probe slot consumed
            if breaker is not None:
                breaker.record_neutral(probe_slot)
            raise
        if breaker is not None:
            breaker.record_success()
        if request_ids and reqtrace.enabled():
            # the device leg of the sampled span trees: the jitted
            # executable's run (retries included), nested inside the
            # batcher's dispatch span.  A coalesced batch's requests
            # share the dispatch, so each sampled rid gets the span
            for r in request_ids:
                if reqtrace.sampled(r):
                    reqtrace.add_span(r, "device", t_fwd0, t_fwd1,
                                      bucket=bucket, rows=n)
        if first:
            m.warm.add(bucket)
            if telemetry.enabled():
                telemetry.counter(self._label(
                    "serving.compiles.%d" % bucket)).inc()
                telemetry.gauge(self._label(
                    "serving.warm_buckets")).set(len(m.warm))
        return y

    def warmup(self):
        """Eagerly compile every bucket; flips :attr:`ready`.

        Needs a known per-sample shape (recorded by snapshots/packages
        of initialized workflows, derivable for FC stacks, or passed as
        ``sample_shape=``); without one the engine stays lazy —
        readiness then means "first request compiles".
        """
        m = self._model
        if m is None:
            raise RuntimeError("no model loaded")
        if m.sample_shape is None:
            self.warning("cannot warm up: per-sample input shape "
                         "unknown — pass sample_shape=")
            self._ready.set()
            return
        for bucket in self.buckets:
            if bucket in m.warm:
                continue
            self.predict(numpy.zeros((bucket,) + m.sample_shape,
                                     dtype=m.dtype))
        self._ready.set()
        self.info("warm: buckets %s", list(self.buckets))

    # -- eviction (registry LRU) --------------------------------------------
    def _ledger_swap(self, old_bytes, new_bytes):
        """Attribute this model's device params in the PR 4 memory
        ledger (``serving.model.<name>``) so /debug/profiler and the
        leak check see serving-side residency next to training Arrays.
        """
        from znicz_tpu.core import profiler
        if not profiler.enabled() or old_bytes == new_bytes:
            return
        profiler.ledger_swap(
            "serving.model.%s" % (self.name or "default"),
            int(old_bytes), int(new_bytes))

    def evict(self):
        """Release the model's DEVICE footprint — params and compiled
        executables — keeping the host-side copy so :meth:`restore`
        (or the next :meth:`predict`) can bring it back without
        touching the source.  The registry's LRU budget calls this for
        the coldest model; readiness clears until the lazy re-warm.
        Returns True when something was actually released."""
        with self._load_lock:
            m = self._model
            if m is None or m.params is None:
                return False
            old_bytes = self.device_bytes
            # dropping the jitted callable drops the executable refs;
            # dropping the param arrays frees the device buffers — the
            # host_params numpy copies stay for restore()
            m.params = None
            m.fn = None
            m.warm.clear()
            self._ready.clear()
            self._evictions += 1
        self._ledger_swap(old_bytes, 0)
        if telemetry.enabled():
            telemetry.counter(self._label("serving.evictions")).inc()
            telemetry.gauge(self._label("serving.warm_buckets")).set(0)
        event = {"version": self._version, "released_bytes": old_bytes}
        if self.name is not None:
            event["model"] = self.name
        telemetry.record_event("serving.evict", **event)
        self.info("evicted: released %d device bytes%s", old_bytes,
                  " (model %s)" % self.name if self.name else "")
        return True

    def restore(self):
        """Undo :meth:`evict`: re-upload the params and rebuild the
        jitted forward, then re-warm (when warmup is wanted) — with the
        persistent compilation cache wired every bucket's "compile" is
        a cache load, so a restore costs an upload plus milliseconds.
        Returns True when a restore actually happened."""
        import jax
        with self._load_lock:
            m = self._model
            if m is None:
                raise RuntimeError("no model loaded")
            if m.params is not None and m.fn is not None:
                return False  # resident — nothing to do
            # host_params hold the CONVERTED arrays (bf16 casts / int8
            # weights + scales), so a low-precision model's restore
            # re-uploads the small representation, never f32 originals
            m.params = jax.device_put(m.host_params)
            m.fn = _build_forward(m.layers, m.serve_dtype, m.fast_max)
            m.warm.clear()
        self._ledger_swap(0, self.device_bytes)
        event = {"version": self._version,
                 "device_bytes": self.device_bytes}
        if self.name is not None:
            event["model"] = self.name
        telemetry.record_event("serving.restore", **event)
        if self._warmup_wanted and m.sample_shape is not None:
            self.warmup()
        else:
            self._ready.set()
        return True


def matches_sample_shape(shape, sample):
    """True when ``shape`` is ONE sample of a model whose per-sample
    shape is ``sample``: exact, or the implicit-single-channel NHWC
    equivalences every spatial unit honors (``(H, W)`` <->
    ``(H, W, 1)``).  The one batch-axis rule, shared by the engine and
    the micro-batcher."""
    shape, sample = tuple(shape), tuple(sample)
    return shape == sample or shape == sample + (1,) or \
        (sample[-1:] == (1,) and shape == sample[:-1])


def _check_sample_shape(trailing, sample):
    """Reject client batches whose per-sample shape the model was not
    warmed for — a novel trailing shape would silently compile a fresh
    executable per bucket on the serving hot path (unbounded compile
    cache, p99 collapse)."""
    if not matches_sample_shape(trailing, sample):
        raise ValueError(
            "per-sample shape %s does not match the model's input "
            "shape %s" % (tuple(trailing), tuple(sample)))


def _derived_sample_shape(layers, params):
    """Per-sample input shape when the first layer pins it (FC family:
    weights are (neurons, sample_size)); None for spatial stacks."""
    for entry, p in zip(layers, params):
        tpe = entry["type"]
        if tpe == "softmax" or tpe.startswith("all2all"):
            # int8 engines carry the quantized weights instead — same
            # shape, same derivation
            w = p.get("weights")
            if w is None:
                w = p.get("weights_q8")
            if w is None:
                return None
            size = (w.shape[0] if entry.get("weights_transposed")
                    else w.shape[1])
            return (int(size),)
        return None  # spatial/standalone head: shape not derivable
    return None


def _fill_from_fused_state(state, topology, layers, arrays_list, label):
    """Fused-mode snapshots keep params in the trainer's pytree, not in
    per-forward units — map them positionally onto the topology (the
    fused layer list and the forwards align 1:1 when both exist)."""
    missing = [i for i, (entry, p) in enumerate(zip(layers, arrays_list))
               if "weights" in topology["layers"][i].get("arrays", ())
               and "weights" not in p]
    if not missing:
        return
    fused = state.get("units", {}).get("fused_trainer", {}) \
        .get("fused_state")
    fused_params = list(fused.get("params", ())) if fused else None
    if not fused_params or len(fused_params) != len(layers):
        raise ValueError(
            "%s: layers %s have no weights in the snapshot (and no "
            "matching fused trainer state) — snapshot a trained "
            "workflow or export a package instead"
            % (label, [layers[i]["type"] for i in missing]))
    for i in missing:
        p = fused_params[i] or {}
        if p.get("w") is None:
            raise ValueError(
                "%s: fused state carries no weights for layer %d (%s)"
                % (label, i, layers[i]["type"]))
        arrays_list[i]["weights"] = numpy.asarray(p["w"])
        if p.get("b") is not None:
            arrays_list[i]["bias"] = numpy.asarray(p["b"])
