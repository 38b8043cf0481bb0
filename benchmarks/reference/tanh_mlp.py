"""Plain reference for a stack of veles.znicz ``all2all_tanh`` layers trained
on per-row targets by mean squared error (the Approximator sample).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
forward through the scaled tanh ``1.7159 tanh(0.6666 x)``, the loss
``sum((y - t)^2) / (2 n)`` over the ``n`` rows of the minibatch that count,
``jax.grad``, and the momentum-SGD update with weight decay (veles.znicz
gd.py).  It reads the layer list from the configuration's JSON file and
imports nothing of ``znicz_tpu``; weights come from
``numpy.random.RandomState`` seeded the way the published workflow seeds its
stream (uniform weights, then uniform bias, layer by layer), so the reference
takes no array the program has made.
"""

import functools

import numpy

import jax
import jax.numpy as jnp
from jax import lax

TANH_A, TANH_B = 1.7159, 0.6666
DEFAULTS = dict(lr=0.01, wd=0.00005, moment=0.0, factor_ortho=0.0)


def _hyper(layer):
    kw = dict(layer.get("<-", {}))
    w = dict(lr=kw.get("learning_rate", DEFAULTS["lr"]),
             wd=kw.get("weights_decay", DEFAULTS["wd"]),
             moment=kw.get("gradient_moment", DEFAULTS["moment"]),
             factor_ortho=kw.get("factor_ortho", DEFAULTS["factor_ortho"]))
    if w["factor_ortho"] or kw.get("l1_vs_l2") or kw.get("solvers"):
        raise ValueError("reference knows plain momentum SGD only")
    b = dict(lr=kw.get("learning_rate_bias", w["lr"]),
             wd=kw.get("weights_decay_bias", 0.0),
             moment=kw.get("gradient_moment_bias", w["moment"]),
             factor_ortho=0.0)
    return {"w": w, "b": b}


def plan(layers, input_sample_shape, target_shape):
    """Per-layer static description: kind, shapes, hypers, fillings.  The
    last layer's width is the targets' (the published workflow sets it from
    its loader)."""
    n_in = int(numpy.prod(input_sample_shape))
    out = []
    for i, layer in enumerate(layers):
        if layer["type"] != "all2all_tanh":
            raise ValueError("reference does not know layer type %r"
                             % layer["type"])
        fwd = layer.get("->", {})
        shape = fwd.get("output_sample_shape")
        if shape is None:
            if i != len(layers) - 1:
                raise ValueError("only the last layer takes its width "
                                 "from the targets")
            shape = target_shape
        n_out = int(numpy.prod(shape))
        for which in ("weights", "bias"):
            if fwd.get(which + "_filling") != "uniform" \
                    or fwd.get(which + "_stddev") is None:
                raise ValueError("reference initialises uniform weights "
                                 "and biases with stated ranges only")
        out.append({"type": layer["type"], "name": layer.get("name"),
                    "kind": "fc", "activation": "tanh",
                    "in_shape": (n_in,), "out_shape": (n_out,),
                    "w_shape": (n_out, n_in), "is_softmax": False,
                    "mask": None, "hyper": _hyper(layer),
                    "init": dict(weights_stddev=fwd["weights_stddev"],
                                 bias_stddev=fwd["bias_stddev"])})
        n_in = n_out
    return out


def init_params(net, seed):
    """Weights then bias, layer by layer, from one legacy numpy stream
    seeded with ``[seed]`` as uint32 words (the published harness
    contract); each uniform in [-stddev, stddev]."""
    rs = numpy.random.RandomState(numpy.asarray([seed], dtype=numpy.uint32))
    params = []
    for ent in net:
        ini = ent["init"]
        w = rs.uniform(-ini["weights_stddev"], ini["weights_stddev"],
                       size=ent["w_shape"]).astype(numpy.float32)
        b = rs.uniform(-ini["bias_stddev"], ini["bias_stddev"],
                       size=ent["w_shape"][0]).astype(numpy.float32)
        params.append({"w": w, "b": b})
    return params


def output_fn(params, x):
    y = x.reshape(x.shape[0], -1).astype(jnp.float32)
    for p in params:
        y = jnp.dot(y, p["w"].T, precision=lax.Precision.HIGHEST) + p["b"]
        y = TANH_A * jnp.tanh(TANH_B * y)
    return y


def make_step(net, root):
    """``step(params, vel, x, targets, valid, hyper) -> (params, vel, out)``
    over one minibatch; ``valid`` marks the rows that count.  ``root``: a
    row's error is the root of its mean square (the evaluator's default),
    not the mean square."""

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, vel, x, targets, valid, hyper):
        n = jnp.maximum(valid.sum(), 1).astype(jnp.float32)

        def loss_fn(p):
            y = output_fn(p, x)
            diff = jnp.where(valid[:, None], y - targets, 0.0)
            return 0.5 * (diff * diff).sum() / n, (y, diff)

        (loss, (y, diff)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_p, new_v = [], []
        for p, v, g, hy in zip(params, vel, grads, hyper):
            q, u = {}, {}
            for name in p:
                h = hy[name]
                u[name] = -h["lr"] * (g[name] + h["wd"] * p[name]) \
                    + h["moment"] * v[name]
                q[name] = p[name] + u[name]
            new_p.append(q)
            new_v.append(u)
        mse_per = (diff * diff).sum(axis=1) / diff.shape[1]
        out = {"loss": loss, "output": y, "grads": grads,
               "mse_per": jnp.sqrt(mse_per) if root else mse_per}
        return new_p, new_v, out

    return step


def leaf_norms(tree):
    """{"<layer index>.<w|b>": l2 norm} of a list-of-dicts parameter tree."""
    return {"%d.%s" % (i, k): float(jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32))))) for i, d in enumerate(tree)
        for k, v in d.items()}
