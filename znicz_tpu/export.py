"""Deployment package export/import.

TPU-era equivalent of the reference's ``Forward.package_export`` → zip →
libZnicz deployment path (reference nn_units.py:152-161, mnist.py:124-127,
libZnicz/src/all2all.cc).  The package is an **uncompressed** zip:

* ``manifest.json`` — human/python metadata: format version, workflow
  name, per-layer type string + attribute map;
* ``manifest.txt``  — the same layer list in a line-based form the C++
  runtime parses without a JSON dependency:
  ``type=all2all_tanh weights=layer0_weights.npy bias=layer0_bias.npy
  weights_transposed=0 include_bias=1``;
* ``layerN_<attr>.npy`` — one NumPy file per exported array.

Stored (not deflated) entries keep the C++ zip reader trivial; model
weights compress poorly anyway.  ``cpp/`` implements the consumer:
a C++ inference runtime covering the libZnicz unit scope.
"""

import io
import json
import zipfile

import numpy

#: the one format version this writer emits and the readers accept;
#: bump together with a manifest-schema change
PACKAGE_FORMAT = 1


def _layer_type(fwd):
    mapping = getattr(type(fwd), "MAPPING", None)
    if not mapping:
        raise ValueError("%s has no MAPPING type string" % type(fwd))
    return sorted(mapping)[0]


def _plain_scalar(value):
    if isinstance(value, (tuple, set, frozenset)):
        return list(value)
    return value


def input_sample_shape(workflow):
    """Per-sample input shape of the forward stack, when knowable (the
    first forward's allocated input minus the batch axis); None before
    initialize or for input-less stacks."""
    forwards = list(getattr(workflow, "forwards", ()))
    if not forwards:
        return None
    inp = getattr(forwards[0], "input", None)
    if inp is None or not inp:
        return None
    return tuple(int(d) for d in inp.shape[1:])


def forward_manifest(workflow):
    """``workflow``'s forward stack as (manifest dict, {fname: ndarray}).

    The single source of the package schema — :func:`export_package`
    writes exactly this, and the snapshot topology
    (:func:`forward_topology`) is its array-free sibling.
    """
    refuse_token_kinds(getattr(workflow, "layers", None) or ())
    forwards = list(workflow.forwards)
    layers = []
    files = {}
    pending_mask = None
    pending_grouping = None
    for i, fwd in enumerate(forwards):
        tpe = _layer_type(fwd)
        if tpe == "zero_filter":
            # fold the grouping mask into the NEXT layer's exported
            # weights (the runtime chains pure Execute calls; a
            # weight-mutating unit has no place there) — the masked
            # weights ARE what the training forward used.  The mask
            # comes from the ZeroFiller itself (single source of the
            # grouping formula).
            fwd._ensure_mask()
            pending_mask = numpy.array(fwd.mask.mem)
            pending_grouping = int(fwd.grouping)
            continue
        entry = {"type": tpe, "name": fwd.name, "arrays": {}}
        data = fwd.package_export()
        if pending_mask is not None:
            w = data.get("weights")
            if w is None:
                # silently dropping the mask would make the package
                # lossy (and the served forward wrong for weights the
                # runtime re-randomizes) — refuse instead
                raise ValueError(
                    "zero_filter precedes %r which exports no weights "
                    "to fold the grouping mask into" % entry["name"])
            if w.size != pending_mask.size:
                raise ValueError(
                    "zero_filter mask size %d does not match %r "
                    "weights size %d" % (pending_mask.size,
                                         entry["name"], w.size))
            data = dict(data, weights=(
                w.reshape(pending_mask.shape) *
                pending_mask.astype(w.dtype)).reshape(w.shape))
            # keep the mask itself so the fold round-trips losslessly:
            # import_package recovers grouping + mask instead of only
            # the (already masked) product
            fname = "layer%d_zero_filter_mask.npy" % i
            files[fname] = pending_mask
            entry["arrays"]["zero_filter_mask"] = fname
            entry["zero_filter_grouping"] = pending_grouping
            pending_mask = pending_grouping = None
        for attr, value in data.items():
            if isinstance(value, numpy.ndarray):
                fname = "layer%d_%s.npy" % (i, attr)
                files[fname] = value
                entry["arrays"][attr] = fname
            else:
                entry[attr] = _plain_scalar(value)
        if entry["type"] == "activation_mul" and \
                entry.get("factor") is None:
            # exporting before the first minibatch auto-sets the factor
            # would make the runners disagree (numpy: KeyError; C++:
            # silent identity) — refuse loudly instead
            raise ValueError(
                "%s: activation_mul factor is unset — run at least one "
                "minibatch (or pass factor=) before exporting"
                % entry["name"])
        layers.append(entry)
    if pending_mask is not None:
        raise ValueError(
            "zero_filter is the last forward — no next layer to fold "
            "its grouping mask into")
    manifest = {
        "format": PACKAGE_FORMAT,
        "workflow": type(workflow).__name__,
        "layers": layers,
    }
    shape = input_sample_shape(workflow)
    if shape is not None:
        manifest["input_sample_shape"] = list(shape)
        manifest["serving"] = serving_manifest(shape)
    return manifest, files


def serving_manifest(sample_shape):
    """The ahead-of-time **warmup manifest** recorded at export /
    snapshot time: the shape-bucket ladder a serving replica should
    precompile for this model (from the serving config active at
    export), plus the per-sample input shape and the serving
    **dtype** (``root.common.serving.dtype`` — "f32" unless the
    exporting cluster serves low precision).  A cold replica reads it
    and warms the EXACT executable set the exporter's cluster serves —
    same ladder, same precision mode, so with the persistent
    compilation cache (core/compile_cache.py) every one of those warms
    is a cache load, not a compile, and the replica is ready in
    seconds with zero fresh XLA work.  An engine constructed with an
    explicit ``dtype=`` keeps its pin; the manifest only selects when
    the operator left the choice to the source."""
    from znicz_tpu.core.config import root
    from znicz_tpu.serving.engine import default_buckets
    from znicz_tpu.serving.quant import normalize_dtype
    max_batch = int(root.common.serving.get("max_batch", 64))
    return {
        "buckets": list(default_buckets(max_batch)),
        "max_batch": max_batch,
        "sample_shape": list(sample_shape),
        "dtype": normalize_dtype(
            root.common.serving.get("dtype", None)),
    }


def refuse_token_kinds(layers):
    """A deployment package holds no token-sequence kind and no
    structural entry: refuse a ``layers`` config that has one, by name."""
    from znicz_tpu.ops import transformer
    for layer in layers:
        tpe = layer.get("type")
        if tpe in transformer.KINDS or tpe in transformer.STRUCTURAL:
            transformer.refuse(tpe, "export")


def forward_topology(workflow):
    """Array-free manifest of the forward stack for snapshot payloads:
    each entry carries the layer type string, the owning unit's name
    (whose snapshot state holds the arrays), the array attribute names,
    and the scalar hyperparameters.  ``zero_filter`` units are skipped —
    they mask the next layer's weights in place on every step, so the
    snapshotted weights are already masked.  In fused mode the one
    forward is the fused trainer, which describes its whole stack
    itself (``FusedForwardBackward.topology_layers``).

    Runs on EVERY snapshot, so unlike ``package_export()`` it never
    touches array contents — recording the attr names must not pull a
    full host copy of the weights per checkpoint."""
    from znicz_tpu.core.memory import Array
    layers = []
    for fwd in getattr(workflow, "forwards", ()):
        if hasattr(fwd, "topology_layers"):
            layers.extend(fwd.topology_layers())
            continue
        tpe = _layer_type(fwd)
        if tpe == "zero_filter":
            continue
        entry = {"type": tpe, "unit": fwd.name, "arrays": []}
        for attr in getattr(fwd, "exports", ()):
            value = getattr(fwd, attr, None)
            if value is None:
                continue
            if isinstance(value, Array):
                if value:  # allocated — snapshot state will carry it
                    entry["arrays"].append(attr)
            elif isinstance(value, numpy.ndarray):
                entry["arrays"].append(attr)
            else:
                entry[attr] = _plain_scalar(value)
        layers.append(entry)
    topology = {"layers": layers}
    shape = input_sample_shape(workflow)
    if shape is not None:
        topology["input_sample_shape"] = list(shape)
        topology["serving"] = serving_manifest(shape)
    return topology


def quantize_manifest(manifest, files):
    """Add the **int8 quantization sidecar** to a package manifest in
    place: for every weight-bearing layer, per-output-channel
    symmetric int8 weights (``layerN_weights_q8.npy``) and their f32
    scales (``layerN_weights_scale.npy``), referenced from the entry
    as ``quant_weights_q8`` / ``quant_weights_scale`` plus the scheme
    tag.  The f32 weights stay — the package still serves at any
    dtype; an ``int8`` engine adopts the sidecar verbatim (export-time
    quantization is authoritative) instead of re-quantizing at load.
    Like the zero_filter provenance arrays, the sidecar never appears
    in ``manifest.txt`` — the C++ runtime's flat parser only sees the
    f32 layers.  Returns the number of layers quantized."""
    from znicz_tpu.serving import quant
    quantized = 0
    for entry in manifest["layers"]:
        fname = entry.get("arrays", {}).get("weights")
        if fname is None or not quant.quantizable(entry):
            continue
        q, scale = quant.quantize_weights(files[fname],
                                          quant.quant_axis(entry))
        base = fname[:-len(".npy")]
        files[base + "_q8.npy"] = q
        files[base + "_scale.npy"] = scale
        entry["arrays"]["quant_weights_q8"] = base + "_q8.npy"
        entry["arrays"]["quant_weights_scale"] = base + "_scale.npy"
        entry["quant_scheme"] = quant.QUANT_SCHEME
        quantized += 1
    if quantized:
        manifest["quant_scheme"] = quant.QUANT_SCHEME
    return quantized


def export_package(workflow, path, quantize=False):
    """Write ``workflow``'s forward stack as a deployment package.

    ``workflow`` needs a ``forwards`` list (StandardWorkflow / NNWorkflow
    contract); returns the path written.  ``quantize=True`` adds the
    int8 weight sidecar (:func:`quantize_manifest`) so serving
    replicas in int8 mode load export-time scales instead of
    quantizing per replica.
    """
    manifest, files = forward_manifest(workflow)
    if quantize:
        quantize_manifest(manifest, files)
    layers = manifest["layers"]

    lines = []
    for i, entry in enumerate(layers):
        parts = ["type=%s" % entry["type"]]
        for attr, fname in sorted(entry["arrays"].items()):
            if attr.startswith("zero_filter") or \
                    attr.startswith("quant"):
                # python-side provenance only; the C++ runtime consumes
                # the already-masked f32 weights and its flat parser
                # must not see unknown array attrs
                continue
            parts.append("%s=%s" % (attr, fname))
        # scalar / tuple hyperparameters (conv & pooling geometry, LRN
        # constants, ...) serialize as key=value / key=a,b,c for the
        # C++ runtime's flat parser
        for attr in sorted(entry):
            if attr in ("type", "name", "arrays") or \
                    attr.startswith("zero_filter") or \
                    attr.startswith("quant"):
                continue
            value = entry[attr]
            if isinstance(value, bool):
                parts.append("%s=%d" % (attr, int(value)))
            elif isinstance(value, (int, float)):
                parts.append("%s=%s" % (attr, repr(value)))
            elif isinstance(value, (tuple, list)) and value and \
                    all(isinstance(v, (int, float)) for v in value):
                parts.append("%s=%s" % (attr,
                                        ",".join(repr(v) for v in value)))
        lines.append(" ".join(parts))

    with zipfile.ZipFile(path, "w", zipfile.ZIP_STORED) as zf:
        zf.writestr("manifest.json", json.dumps(manifest, indent=2,
                                                default=repr))
        zf.writestr("manifest.txt", "\n".join(lines) + "\n")
        for fname, value in files.items():
            buf = io.BytesIO()
            numpy.save(buf, numpy.ascontiguousarray(value))
            zf.writestr(fname, buf.getvalue())
    return path


def load_package(path):
    """Read a package back: (manifest dict, {filename: ndarray})."""
    with zipfile.ZipFile(path) as zf:
        manifest = json.loads(zf.read("manifest.json"))
        arrays = {}
        for info in zf.infolist():
            if info.filename.endswith(".npy"):
                arrays[info.filename] = numpy.load(
                    io.BytesIO(zf.read(info.filename)))
    return manifest, arrays


def import_package(path):
    """The validating counterpart of :func:`export_package` — what the
    Python side (the serving engine, tooling) loads packages through.

    Checks the manifest format version and that every referenced array
    file is present, so a truncated or future-format package fails here
    with a clear message instead of deep inside the first forward.
    Returns ``(manifest, arrays)`` like :func:`load_package`.
    """
    manifest, arrays = load_package(path)
    version = manifest.get("format")
    if version != PACKAGE_FORMAT:
        raise ValueError(
            "%s: unknown package format version %r (this build reads "
            "format %d) — re-export the package with a matching "
            "znicz_tpu version" % (path, version, PACKAGE_FORMAT))
    if not isinstance(manifest.get("layers"), list):
        raise ValueError("%s: manifest.json has no layers list" % path)
    for entry in manifest["layers"]:
        if "type" not in entry:
            raise ValueError("%s: manifest layer without type: %r"
                             % (path, entry))
        for attr, fname in entry.get("arrays", {}).items():
            if fname not in arrays:
                raise ValueError(
                    "%s: layer %r references missing array file %r"
                    % (path, entry.get("name", entry["type"]), fname))
    return manifest, arrays


def run_package_numpy(path, x):
    """Execute a package forward in pure numpy — the executable spec the
    C++ runtime (cpp/) must match to 1e-5.

    Supports the FC family plus the spatial tier (conv*, max/avg
    pooling, LRN, standalone activations, dropout-as-identity).  Spatial
    packages take NHWC input."""
    from znicz_tpu.ops import activations, dense
    from znicz_tpu.ops import conv as conv_ops
    from znicz_tpu.ops import normalization as norm_ops
    from znicz_tpu.ops import pooling as pool_ops
    manifest, arrays = load_package(path)
    x = numpy.asarray(x, dtype=numpy.float64)
    y = x
    for entry in manifest["layers"]:
        tpe = entry["type"]
        if tpe == "softmax" or tpe.startswith("all2all"):
            w = arrays[entry["arrays"]["weights"]]
            if entry.get("weights_transposed"):
                w = w.T
            b = arrays.get(entry["arrays"].get("bias", ""), None)
            include_bias = bool(entry.get("include_bias", True)) and \
                b is not None
            y = y.reshape(len(y), -1)
            if tpe == "softmax":
                y = dense.forward_numpy(y, w, b, activation="linear",
                                        include_bias=include_bias)
                y, _ = dense.softmax_numpy(y)
            else:
                act = {"all2all": "linear", "all2all_tanh": "tanh",
                       "all2all_relu": "relu",
                       "all2all_str": "strict_relu",
                       "all2all_sigmoid": "sigmoid"}[tpe]
                y = dense.forward_numpy(y, w, b, activation=act,
                                        include_bias=include_bias)
        elif tpe.startswith("conv"):
            w = arrays[entry["arrays"]["weights"]]
            if entry.get("weights_transposed"):
                w = w.T
            b = arrays.get(entry["arrays"].get("bias", ""), None)
            include_bias = bool(entry.get("include_bias", True)) and \
                b is not None
            act = {"conv": "linear", "conv_tanh": "tanh",
                   "conv_relu": "relu", "conv_str": "strict_relu",
                   "conv_sigmoid": "sigmoid"}[tpe]
            y = conv_ops.forward_numpy(
                y, w, b, int(entry["ky"]), int(entry["kx"]),
                tuple(int(v) for v in entry["padding"]),
                tuple(int(v) for v in entry["sliding"]),
                activation=act, include_bias=include_bias)
        elif tpe in ("max_pooling", "avg_pooling"):
            sliding = tuple(int(v) for v in entry["sliding"])
            if tpe == "max_pooling":
                y, _ = pool_ops.max_pooling_numpy(
                    y, int(entry["ky"]), int(entry["kx"]), sliding)
            else:
                y = pool_ops.avg_pooling_numpy(
                    y, int(entry["ky"]), int(entry["kx"]), sliding)
        elif tpe == "norm":
            y = norm_ops.lrn_forward_numpy(
                y, alpha=float(entry["alpha"]), beta=float(entry["beta"]),
                k=float(entry["k"]), n=int(entry["n"]))
        elif tpe == "activation_mul":
            y = y * float(entry["factor"])
        elif tpe.startswith("activation_"):
            act = {"activation_tanh": "tanh", "activation_sigmoid":
                   "sigmoid", "activation_relu": "relu",
                   "activation_str": "strict_relu"}.get(tpe)
            if act is not None:
                y = activations.apply_numpy(act, y)
            else:  # ext family: log / tanhlog / sincos
                y = activations.ext_apply_numpy(
                    tpe[len("activation_"):], y)
        elif tpe == "dropout":
            pass  # inference identity
        else:
            raise ValueError("package runner: unsupported type %r" % tpe)
    return y
