"""Plain reference for a veles.znicz ``layers`` configuration.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
forward, softmax cross-entropy, ``jax.grad``, and the momentum-SGD update
with weight decay and ``factor_ortho`` (veles.znicz gd.py / nn_units.py).
It reads the layer list from the configuration's JSON file and imports
nothing of ``znicz_tpu``; weights come from ``numpy.random.RandomState``
seeded the way the published workflow seeds its stream, so the reference
takes no array the program has made.

``mode`` selects the arithmetic of every matrix product and convolution:

* ``"f32"``  — the reference proper;
* ``"bf16"`` — operands rounded to bfloat16, float32 accumulation: what the
  configurations state (used to read how far two sound bf16 paths lie
  apart, never as the reference);
* ``"fp8"``  — the control: operands rounded to float8_e4m3fn under a
  per-tensor scale, the next precision below bfloat16.
"""

import functools

import numpy

import jax
import jax.numpy as jnp
from jax import lax

CONV_TYPES = {"conv": "linear", "conv_str": "strict_relu"}
FC_TYPES = {"all2all": "linear", "all2all_str": "strict_relu",
            "softmax": "linear"}
POOL_TYPES = {"max_pooling": "max", "avg_pooling": "avg"}
DEFAULTS = dict(lr=0.01, wd=0.00005, moment=0.0, factor_ortho=0.0)
FP8_MAX = 448.0


# -- geometry and parameters --------------------------------------------------

def _fwd_kwargs(layer):
    kw = {k: v for k, v in layer.items()
          if k not in ("type", "name", "->", "<-")}
    kw.update(layer.get("->", {}))
    return kw


def _hyper(layer):
    kw = {k: v for k, v in layer.items()
          if k not in ("type", "name", "->", "<-")}
    kw.update(layer.get("<-", {}))
    w = dict(lr=kw.get("learning_rate", DEFAULTS["lr"]),
             wd=kw.get("weights_decay", DEFAULTS["wd"]),
             moment=kw.get("gradient_moment", DEFAULTS["moment"]),
             factor_ortho=kw.get("factor_ortho", DEFAULTS["factor_ortho"]))
    if kw.get("l1_vs_l2") or kw.get("accumulate_gradient") \
            or kw.get("solvers"):
        raise ValueError("reference knows plain momentum SGD only")
    b = dict(lr=kw.get("learning_rate_bias", w["lr"]),
             wd=kw.get("weights_decay_bias", 0.0),
             moment=kw.get("gradient_moment_bias", w["moment"]),
             factor_ortho=0.0)
    return {"w": w, "b": b}


def plan(layers, input_sample_shape):
    """Per-layer static description: kind, shapes, hypers, masks."""
    shape = tuple(int(s) for s in input_sample_shape)
    out = []
    grouping = None
    for layer in layers:
        tpe = layer["type"]
        kw = _fwd_kwargs(layer)
        ent = {"type": tpe, "name": layer.get("name", tpe),
               "in_shape": shape}
        if tpe in CONV_TYPES:
            kx, ky, k = int(kw["kx"]), int(kw["ky"]), int(kw["n_kernels"])
            left, top, right, bottom = kw.get("padding", (0, 0, 0, 0))
            sx, sy = kw.get("sliding", (1, 1))
            nx = (left + shape[1] + right - kx) // sx + 1
            ny = (top + shape[0] + bottom - ky) // sy + 1
            ent.update(kind="conv", kx=kx, ky=ky, n_kernels=k,
                       padding=(left, top, right, bottom), sliding=(sx, sy),
                       activation=CONV_TYPES[tpe],
                       w_shape=(k, ky * kx * shape[2]))
            shape = (ny, nx, k)
        elif tpe in FC_TYPES:
            n_in = int(numpy.prod(shape))
            n_out = int(numpy.prod(kw["output_sample_shape"]))
            ent.update(kind="fc", activation=FC_TYPES[tpe],
                       w_shape=(n_out, n_in), is_softmax=tpe == "softmax")
            shape = (n_out,)
        elif tpe in POOL_TYPES:
            kx, ky = int(kw["kx"]), int(kw["ky"])
            sx, sy = kw.get("sliding") or (kx, ky)
            # ceil-mode geometry (veles.znicz pooling.py)
            nx = -(-(shape[1] - kx) // sx) + 1
            ny = -(-(shape[0] - ky) // sy) + 1
            ent.update(kind="pool", mode=POOL_TYPES[tpe], kx=kx, ky=ky,
                       sliding=(sx, sy))
            shape = (ny, nx, shape[2])
        elif tpe == "norm":
            ent.update(kind="lrn", alpha=kw.get("alpha", 1e-4),
                       beta=kw.get("beta", 0.75), k=kw.get("k", 2),
                       n=int(kw.get("n", 5)))
        elif tpe == "activation_str":
            ent.update(kind="activation", activation="strict_relu")
        elif tpe == "dropout":
            ent.update(kind="dropout", ratio=kw.get("dropout_ratio", 0.5))
        elif tpe == "zero_filter":
            grouping = int(kw.get("grouping", 2))
            ent.update(kind="zerofill")
        else:
            raise ValueError("reference does not know layer type %r" % tpe)
        ent["out_shape"] = shape
        if ent["kind"] in ("conv", "fc"):
            ent["hyper"] = _hyper(layer)
            ent["init"] = dict(
                weights_filling=kw.get("weights_filling"),
                weights_stddev=kw.get("weights_stddev"),
                bias_filling=kw.get("bias_filling"),
                bias_stddev=kw.get("bias_stddev"))
            ent["mask"] = None
            if grouping is not None:
                rows, cols = ent["w_shape"]
                ent["mask"] = (
                    numpy.arange(rows)[:, None] % grouping
                    != numpy.arange(cols)[None, :] % grouping)
                grouping = None
        out.append(ent)
    return out


def init_params(net, seed):
    """Weights then bias, layer by layer, from one legacy numpy stream
    seeded with ``[seed]`` as uint32 words (the published harness
    contract); gaussian weights, constant biases."""
    rs = numpy.random.RandomState(numpy.asarray([seed], dtype=numpy.uint32))
    params = []
    for ent in net:
        if ent["kind"] not in ("conv", "fc"):
            params.append({})
            continue
        ini = ent["init"]
        if ini["weights_filling"] != "gaussian" \
                or ini["bias_filling"] != "constant" \
                or ini["weights_stddev"] is None \
                or ini["bias_stddev"] is None:
            raise ValueError("reference initialises gaussian weights and "
                             "constant biases with stated stddevs only")
        w = rs.normal(0, ini["weights_stddev"],
                      size=ent["w_shape"]).astype(numpy.float32)
        b = numpy.full(ent["w_shape"][0], ini["bias_stddev"], numpy.float32)
        params.append({"w": w, "b": b})
    return params


# -- arithmetic modes ---------------------------------------------------------

def _quant(x, mode):
    """Round a matmul/conv operand as ``mode`` says; gradient passes
    straight through the rounding."""
    if mode == "f32":
        return x
    if mode == "bf16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(mode)
    return x + lax.stop_gradient(q - x)


def _strict_relu(x):
    return jnp.where(x > 0, x, 0.0)


def _lrn(x, alpha, beta, k, n):
    half = n // 2
    c = x.shape[-1]
    sq = jnp.pad(x * x, ((0, 0),) * (x.ndim - 1) + ((half, half),))
    ssum = sum(sq[..., j:j + c] for j in range(2 * half + 1))
    return x / jnp.power(k + alpha * ssum, beta)


def _pool(x, mode, ky, kx, sliding, out_shape):
    sy_in, sx_in = x.shape[1], x.shape[2]
    ny, nx = out_shape[0], out_shape[1]
    pad_y = (ny - 1) * sliding[1] + ky - sy_in
    pad_x = (nx - 1) * sliding[0] + kx - sx_in
    pads = ((0, 0), (0, pad_y), (0, pad_x), (0, 0))
    dims, strides = (1, ky, kx, 1), (1, sliding[1], sliding[0], 1)
    if mode == "max":
        return lax.reduce_window(x, -numpy.inf, lax.max, dims, strides, pads)
    s = lax.reduce_window(x, 0.0, lax.add, dims, strides, pads)
    # truncated windows divide by the cells they really cover
    ty = numpy.minimum(ky, sy_in - numpy.arange(ny) * sliding[1])
    tx = numpy.minimum(kx, sx_in - numpy.arange(nx) * sliding[0])
    cnt = (ty[:, None] * tx[None, :]).astype(numpy.float32)
    return s / cnt[None, :, :, None]


def logits_fn(params, x, net, masks, mode):
    """Forward to the softmax layer's logits.  ``masks`` holds one
    dropout keep-mask per dropout layer (None at inference)."""
    hi = lax.Precision.HIGHEST
    y = x.astype(jnp.float32)
    drop = iter(masks or ())
    for p, ent in zip(params, net):
        kind = ent["kind"]
        if kind == "conv":
            y = y.reshape((y.shape[0],) + ent["in_shape"])
            w = p["w"]
            if ent["mask"] is not None:
                w = w * ent["mask"].astype(numpy.float32)
            c = ent["in_shape"][2]
            w4 = w.reshape(ent["n_kernels"], ent["ky"], ent["kx"], c)
            left, top, right, bottom = ent["padding"]
            y = lax.conv_general_dilated(
                _quant(y, mode), _quant(jnp.transpose(w4, (1, 2, 3, 0)), mode),
                window_strides=(ent["sliding"][1], ent["sliding"][0]),
                padding=((top, bottom), (left, right)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)
            y = y + p["b"]
            if ent["activation"] == "strict_relu":
                y = _strict_relu(y)
        elif kind == "fc":
            y = y.reshape(y.shape[0], -1)
            w = p["w"]
            if ent["mask"] is not None:
                w = w * ent["mask"].astype(numpy.float32)
            y = jnp.dot(_quant(y, mode), _quant(w, mode).T, precision=hi)
            y = y + p["b"]
            if ent["activation"] == "strict_relu":
                y = _strict_relu(y)
        elif kind == "pool":
            y = _pool(y, ent["mode"], ent["ky"], ent["kx"], ent["sliding"],
                      ent["out_shape"])
        elif kind == "lrn":
            y = _lrn(y, ent["alpha"], ent["beta"], ent["k"], ent["n"])
        elif kind == "activation":
            y = _strict_relu(y)
        elif kind == "dropout":
            if masks is not None:
                y = y * next(drop).astype(y.dtype) / (1.0 - ent["ratio"])
        elif kind == "zerofill":
            pass
        else:
            raise AssertionError(kind)
    return y


def dropout_masks(net, key, batch):
    """The keep-masks of one step: one key split per dropout layer, a
    uniform draw of the layer's whole (batch, ...) shape, keep >= ratio."""
    masks = []
    for ent in net:
        if ent["kind"] == "dropout":
            key, sub = jax.random.split(key)
            u = jax.random.uniform(sub, (batch,) + tuple(ent["in_shape"]))
            masks.append(u >= ent["ratio"])
    return masks


def has_dropout(net):
    return any(ent["kind"] == "dropout" for ent in net)


# -- one training step, in blocks of rows ------------------------------------

def _block_sums(params, x, labels, masks, net, mode):
    """Summed cross-entropy over a block's labelled rows (labels < 0 are
    padding), its gradient, the error count and the predictions."""
    def loss_sum(p):
        z = logits_fn(p, x, net, masks, mode)
        logp = jax.nn.log_softmax(z, axis=1)
        valid = labels >= 0
        lbl = jnp.maximum(labels, 0)
        ce = -jnp.take_along_axis(logp, lbl[:, None], axis=1)[:, 0]
        return jnp.where(valid, ce, 0.0).sum(), z
    (s, z), g = jax.value_and_grad(loss_sum, has_aux=True)(params)
    return s, g, z


def _update(params, vel, grads, hyper, net):
    new_p, new_v = [], []
    for p, v, g, hy, ent in zip(params, vel, grads, hyper, net):
        q, u = {}, {}
        for name in p:
            w = p[name]
            if name == "w" and ent["mask"] is not None:
                w = w * ent["mask"].astype(numpy.float32)
            h = hy[name]
            step = g[name] + h["wd"] * w
            if name == "w" and ent["hyper"]["w"]["factor_ortho"]:
                step = step + (w.sum(axis=0)[None, :] - w) * (
                    h["factor_ortho"] / w.shape[0])
            u[name] = -h["lr"] * step + h["moment"] * v[name]
            q[name] = w + u[name]
        new_p.append(q)
        new_v.append(u)
    return new_p, new_v


def make_step(net, mode, block):
    """``step(params, vel, x, labels, key, hyper) -> (params, vel, out)``
    over a (B, ...) minibatch cut into B // block blocks of rows, so that
    float32 activations of the whole minibatch never live at once."""
    drop = has_dropout(net)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, vel, x, labels, key, hyper):
        b = x.shape[0]
        nblk = b // block
        masks = dropout_masks(net, key, b) if drop else None
        xs = x.reshape((nblk, block) + x.shape[1:])
        ls = labels.reshape(nblk, block)
        ms = None if masks is None else [
            m.reshape((nblk, block) + m.shape[1:]) for m in masks]
        zero = jax.tree.map(jnp.zeros_like, params)

        def body(carry, blk):
            s_acc, g_acc = carry
            xb, lb, mb = blk
            s, g, z = _block_sums(params, xb, lb, mb, net, mode)
            return (s_acc + s, jax.tree.map(jnp.add, g_acc, g)), z

        (s, g), zs = lax.scan(body, (jnp.float32(0), zero), (xs, ls, ms))
        logits = zs.reshape(b, -1)
        n = jnp.maximum((labels >= 0).sum(), 1).astype(jnp.float32)
        grads = jax.tree.map(lambda a: a / n, g)
        new_p, new_v = _update(params, vel, grads, hyper, net)
        out = {"loss": s / n, "logits": logits, "grads": grads,
               "pred": jnp.argmax(logits, axis=1).astype(jnp.int32)}
        return new_p, new_v, out

    return step


def leaf_norms(tree):
    """{"<layer index>.<w|b>": l2 norm} of a list-of-dicts parameter tree."""
    return {"%d.%s" % (i, k): float(jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32))))) for i, d in enumerate(tree)
        for k, v in d.items()}
