"""Plain reference for a looped language model's ``layers`` configuration.

The equations, in straightforward ``jax.numpy``, float32, ``highest``
matmul precision, ``jax.grad``: whole attention matrices, whole logits of a
pass, no kernel, no block, python loops over passes (the weights of a pass
are the same arrays, so ``jax.grad`` sums a shared weight's gradient over
its applications by itself).  It reads the layer list from the
configuration's JSON file and imports nothing of ``znicz_tpu``; weights come
from ``numpy.random.RandomState`` seeded the way the published harness
seeds its stream, leaf by leaf in the order of the configuration, so the
reference takes no array the program has made.

One departure from "no recomputation": every layer application and every
pass's head stands under ``jax.checkpoint``.  It changes no number (the
same operations are run again in the backward pass) and is what lets a
4,096-token row of 24 applications with whole 16 x 4096 x 4096 float32
attention matrices fit beside nothing on a 16 GB chip.

    RMS(x; g) = g * x / sqrt(mean(x^2) + eps)
    h = E[ids]
    for pass t = 1..T, for layer l = 1..L (the same weights every pass):
        a = RMS(h; g1); q, k, v = a Wq, a Wk, a Wv; rotary on q, k by the
        position in the row (rotate-half); scores q k^T / sqrt(hd), masked
        to j <= i and segment_j = segment_i; u = softmax(scores) v;
        h += RMS(u Wo; g2); b = RMS(h; g3);
        h += RMS((silu(b Wg) * (b Wu)) Wd; g4)
      h = RMS(h; gf)   (read by the heads AND by pass t+1)
      z_t = h W^T;  l_t = sigmoid(h . we + be)
    p_1 = l_1;  p_t = l_t prod_{j<t} (1 - l_j);  p_T = prod_{j<T} (1 - l_j)
    loss(position) = sum_t p_t CE(z_t, y) - beta H(p)
    AdamW with bias correction, decoupled decay.

``mode`` selects the arithmetic of every matrix product (``f32`` the
reference proper; ``bf16`` / ``fp8`` operands rounded, float32 accumulation:
the witness and the control).  ``passes`` and ``doc_cut`` plant the
mechanism's own faults: a pass left out, attention across documents.
"""

import functools

import numpy

import jax
import jax.numpy as jnp
from jax import lax

FP8_MAX = 448.0
KINDS = ("embedding", "rmsnorm", "attention", "gated_mlp", "lm_head")
#: a kind's cost module where it is not named after the kind: the accepted
#: ``tests/test_costs.py`` holds that ``layer_costs`` has no ``attention``
COST_KIND = {"attention": "causal_attention"}
ADAM_DEFAULTS = {"adam_beta1": 0.9, "adam_beta2": 0.999, "adam_eps": 1e-8}


# -- the configuration --------------------------------------------------------

def _fwd(layer):
    kw = {k: v for k, v in layer.items()
          if k not in ("type", "name", "->", "<-", "layers")}
    kw.update(layer.get("->", {}))
    return kw


def flatten(layers):
    """(leaf layers in the order of the configuration, topology): a node is
    a leaf's index, ("residual", [nodes]) or ("loop", times, [nodes])."""
    flat = []

    def walk(entries):
        nodes = []
        for layer in entries:
            if layer["type"] == "residual":
                nodes.append(("residual", walk(layer["layers"])))
            elif layer["type"] == "loop":
                nodes.append(("loop", int(layer["times"]),
                              walk(layer["layers"])))
            elif layer["type"] in KINDS:
                nodes.append(len(flat))
                flat.append(layer)
            else:
                raise ValueError("reference knows %s only, not %r"
                                 % (", ".join(KINDS), layer["type"]))
        return nodes

    return flat, walk(layers)


def leaf_table(layers):
    """[{leaf: (shape, stddev or None for a constant, constant, decays)}]
    for every leaf layer, leaves in the order they are drawn."""
    flat, _ = flatten(layers)
    out, dim = [], None
    for layer in flat:
        a, tpe = _fwd(layer), layer["type"]
        s = float(a.get("weights_stddev", 0.02))
        if tpe == "embedding":
            dim = int(a["dim"])
            t = {"w": ((int(a["vocab"]), dim), s, None, True)}
        elif tpe == "rmsnorm":
            t = {"g": ((dim,), None, 1.0, False)}
        elif tpe == "attention":
            hd, h, kv = (int(a[k]) for k in ("head_dim", "heads",
                                             "kv_heads"))
            t = {"wq": ((dim, h * hd), s, None, True),
                 "wk": ((dim, kv * hd), s, None, True),
                 "wv": ((dim, kv * hd), s, None, True),
                 "wo": ((h * hd, dim), s, None, True)}
        elif tpe == "gated_mlp":
            f = int(a["hidden"])
            t = {"wg": ((dim, f), s, None, True),
                 "wu": ((dim, f), s, None, True),
                 "wd": ((f, dim), s, None, True)}
        else:
            t = {"g": ((dim,), None, 1.0, False),
                 "w": ((int(a["vocab"]), dim), s, None, True),
                 "we": ((dim,), s, None, False),
                 "be": ((1,), None, 0.0, False)}
        out.append(t)
    return out


def init_params(layers, seed):
    """One legacy numpy stream seeded with ``[seed]`` as uint32 words."""
    rs = numpy.random.RandomState(numpy.asarray([seed], dtype=numpy.uint32))
    params = []
    for table in leaf_table(layers):
        p = {}
        for name, (shape, std, const, _) in table.items():
            if std is None:
                p[name] = numpy.full(shape, const, numpy.float32)
            else:
                p[name] = rs.normal(0, std, size=shape).astype(numpy.float32)
        params.append(p)
    return params


def hypers(layers):
    """[{leaf: {lr, wd, adam_beta1, adam_beta2, adam_eps}}]: what the
    configuration states for every leaf (gains and the gate take the
    layer's bias learning rate and decay)."""
    flat, _ = flatten(layers)
    out = []
    for layer, table in zip(flat, leaf_table(layers)):
        kw = {k: v for k, v in layer.items()
              if k not in ("type", "name", "->", "<-", "layers")}
        kw.update(layer.get("<-", {}))
        if list(kw.get("solvers", ())) != ["adamw"]:
            raise ValueError("reference knows AdamW only")
        adam = {k: float(kw.get(k, d)) for k, d in ADAM_DEFAULTS.items()}
        lr = float(kw["learning_rate"])
        w = dict(adam, lr=lr, wd=float(kw.get("weights_decay", 0.00005)))
        b = dict(adam, lr=float(kw.get("learning_rate_bias", lr)),
                 wd=float(kw.get("weights_decay_bias", 0.0)))
        out.append({name: dict(w if decays else b)
                    for name, (_, _, _, decays) in table.items()})
    return out


def plan(layers, seq, pairs_per_row=None):
    """The planned net for ``layer_costs``: one entry for every
    APPLICATION of a leaf layer in a step (a loop's sub-chain is listed
    ``times`` over; only an entry's first application, ``"update": True``,
    carries the optimizer's pass over its leaves).  An entry holds its
    ``kind`` (its cost module's name), ``spec`` (the leaf's index, as in the scopes ``L%02d.<kind>``),
    ``seq``, its widths, and for attention the attended (query, key) pairs
    of a row (``pairs_per_row``; causal over the whole row where None)."""
    flat, nodes = flatten(layers)
    tables = leaf_table(layers)
    out, seen = [], set()

    def walk(nodes):
        for node in nodes:
            if isinstance(node, int):
                layer, a = flat[node], _fwd(flat[node])
                ent = {"kind": COST_KIND.get(layer["type"], layer["type"]),
                       "spec": node, "seq": int(seq),
                       "leaves": {k: v[0] for k, v in tables[node].items()},
                       "update": node not in seen}
                seen.add(node)
                if layer["type"] == "attention":
                    ent.update(heads=int(a["heads"]),
                               kv_heads=int(a["kv_heads"]),
                               head_dim=int(a["head_dim"]),
                               pairs=float(seq * (seq + 1) / 2
                                           if pairs_per_row is None
                                           else pairs_per_row))
                out.append(ent)
            elif node[0] == "residual":
                walk(node[1])
            else:
                for _ in range(node[1]):
                    walk(node[2])

    walk(nodes)
    return out


# -- arithmetic modes ---------------------------------------------------------

def _quant(x, mode):
    """Round a product's operand as ``mode`` says; the gradient passes
    straight through the rounding."""
    if mode == "f32":
        return x
    if mode == "bf16":
        # not a cast there and back: the TPU compiler takes such a pair
        # out (excess precision is allowed) and the reading is float32's
        q = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    elif mode == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(mode)
    return x + lax.stop_gradient(q - x)


def _mm(a, b, mode):
    return jnp.matmul(_quant(a, mode), _quant(b, mode))


# -- the model, one row at a time ---------------------------------------------

def _rms(x, g, eps):
    return g * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, base):
    """x (S, H, hd): rotate-half rotary by the position in the row."""
    s, _, hd = x.shape
    inv = 1.0 / (base ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attention(p, x, seg, a, mode):
    s = x.shape[0]
    hd, h, kv = (int(a[k]) for k in ("head_dim", "heads", "kv_heads"))
    base = float(a.get("rope_base", 10000.0))
    q = _rope(_mm(x, p["wq"], mode).reshape(s, h, hd), base)
    k = _rope(_mm(x, p["wk"], mode).reshape(s, kv, hd), base)
    v = _mm(x, p["wv"], mode).reshape(s, kv, hd)
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("ihd,jhd->hij", _quant(q, mode),
                        _quant(k, mode)) / float(numpy.sqrt(hd))
    i = jnp.arange(s)
    ok = (i[None, :] <= i[:, None]) & (seg[None, :] == seg[:, None])
    w = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf), axis=-1)
    u = jnp.einsum("hij,jhd->ihd", _quant(w, mode), _quant(v, mode))
    return _mm(u.reshape(s, h * hd), p["wo"], mode)


def _apply(layer, p, x, seg, mode):
    a, tpe = _fwd(layer), layer["type"]
    if tpe == "embedding":
        return p["w"][x]
    if tpe == "rmsnorm":
        return _rms(x, p["g"], float(a.get("eps", 1e-6)))
    if tpe == "attention":
        return _attention(p, x, seg, a, mode)
    if tpe == "gated_mlp":
        return _mm(jax.nn.silu(_mm(x, p["wg"], mode))
                   * _mm(x, p["wu"], mode), p["wd"], mode)
    raise ValueError(tpe)


def _head(p, h, labels, sample, mode):
    """(cross-entropy (S,), argmax (S,), gate pre-activation (S,), logits
    at ``sample``) of one pass's normed state."""
    z = _mm(h, p["w"].T, mode)
    logp = jax.nn.log_softmax(z, axis=-1)
    ce = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None],
                              axis=1)[:, 0]
    gate = h @ p["we"] + p["be"][0]
    return ce, jnp.argmax(z, axis=-1), gate, z[sample]


def forward_row(params, ids, seg, labels, sample, layers, mode="f32",
                passes=None, doc_cut=True):
    """Per pass: cross-entropy, argmax, gate pre-activation, sampled
    logits, each stacked (T, ...), of one row ``ids (S,)``."""
    flat, nodes = flatten(layers)
    if not doc_cut:
        seg = jnp.zeros_like(seg)
    heads = []

    def run(nodes, x):
        for node in nodes:
            if isinstance(node, int):
                layer = flat[node]
                if layer["type"] == "lm_head":
                    a = _fwd(layer)
                    x = _rms(x, params[node]["g"], float(a.get("eps", 1e-6)))
                    heads.append(jax.checkpoint(
                        functools.partial(_head, mode=mode))(
                            params[node], x, labels, sample))
                else:
                    x = _apply(layer, params[node], x, seg, mode)
            elif node[0] == "residual":
                # one layer application: recomputed, not kept (see above)
                x = x + jax.checkpoint(
                    lambda x, body=node[1]: run(body, x))(x)
            else:
                for _ in range(node[1] if passes is None else passes):
                    x = run(node[2], x)
        return x

    run(nodes, ids)
    return tuple(jnp.stack(part) for part in zip(*heads))


def exit_distribution(gate):
    """p (T, S) from the gates' pre-activations: ``p_1 = l_1``, ``p_t = l_t
    prod_{j<t}(1 - l_j)``, ``p_T = prod_{j<T}(1 - l_j)``."""
    lam = jax.nn.sigmoid(gate)
    t = gate.shape[0]
    stay = jnp.concatenate([jnp.ones_like(lam[:1]),
                            jnp.cumprod(1.0 - lam, axis=0)[:-1]], axis=0)
    return jnp.concatenate([stay[:t - 1] * lam[:t - 1], stay[t - 1:]],
                           axis=0)


def beta_of(layers):
    flat, _ = flatten(layers)
    head = next(layer for layer in flat if layer["type"] == "lm_head")
    return float(_fwd(head).get("exit_entropy_weight", 0.0))


def make_row(layers, mode="f32", passes=None, doc_cut=True):
    """Jitted ``(params, total, ids, seg, labels, sample) -> (total + the
    gradient of the row's loss SUM over its graded positions, aux)``: the
    caller divides by the minibatch's graded count (``total`` is given up
    to the call, so that a minibatch's sum takes one buffer).  ``aux``:
    ``loss_sum``, ``graded``, ``errors``, and at ``sample`` (positions of
    the row) every pass's ``logits (T, n, V)`` and the ``exit (T, n)``
    distribution."""
    beta = beta_of(layers)

    def loss_sum(params, ids, seg, labels, sample):
        ce, pred, gate, z = forward_row(params, ids, seg, labels, sample,
                                        layers, mode, passes, doc_cut)
        p = exit_distribution(gate)
        entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(p), 0.0), axis=0)
        per_tok = jnp.sum(p * ce, axis=0) - beta * entropy
        valid = labels >= 0
        total = jnp.sum(jnp.where(valid, per_tok, 0.0))
        aux = {"loss_sum": total, "graded": valid.sum(),
               "errors": (valid & (pred[-1] != labels)).sum(),
               "logits": z, "exit": p[:, sample]}
        return total, aux

    def row(params, total, ids, seg, labels, sample):
        with jax.default_matmul_precision("highest"):
            (_, aux), grads = jax.value_and_grad(loss_sum, has_aux=True)(
                params, ids, seg, labels, sample)
        return jax.tree.map(jnp.add, total, grads), aux

    return jax.jit(row, donate_argnums=(1,))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def adamw(p, m, v, grads, count, t, hyper):
    """One AdamW step over one layer's leaves (dicts by leaf name):
    ``grads`` is the sum over the minibatch's graded positions and ``count``
    their number, ``t`` the step's number from 1.  Returns (p, m, v)."""
    def leaf(w, m, v, g, hy):
        g = g / count
        m = hy["adam_beta1"] * m + (1 - hy["adam_beta1"]) * g
        v = hy["adam_beta2"] * v + (1 - hy["adam_beta2"]) * g * g
        m_hat = m / (1 - hy["adam_beta1"] ** t)
        v_hat = v / (1 - hy["adam_beta2"] ** t)
        w = w - hy["lr"] * (m_hat / (jnp.sqrt(v_hat) + hy["adam_eps"])
                            + hy["wd"] * w)
        return w, m, v

    out = {k: leaf(p[k], m[k], v[k], grads[k], hyper[k]) for k in p}
    return tuple({k: t3[i] for k, t3 in out.items()} for i in range(3))


def leaf_norms(tree):
    """{"<i>.<leaf>": l2 norm} of a list-of-dicts tree of device arrays
    (reduced there) or of host arrays (summed in float64)."""
    if any(isinstance(v, numpy.ndarray) for d in tree for v in d.values()):
        return {"%d.%s" % (i, k): float(numpy.sqrt(numpy.einsum(
            "i,i->", v.ravel(), v.ravel(), dtype=numpy.float64)))
            for i, d in enumerate(tree) for k, v in d.items()}
    norms = jax.device_get(_norms(tree))
    return {"%d.%s" % (i, k): float(v)
            for i, d in enumerate(norms) for k, v in d.items()}


@jax.jit
def _norms(tree):
    return [{k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in d.items()}
            for d in tree]


@jax.jit
def difference(a, b):
    return jax.tree.map(jnp.subtract, a, b)
