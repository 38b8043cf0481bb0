"""Plain reference for a routed language model's ``layers`` configuration: a
mixture of experts under window and global attention, one chip's share.

The equations, in straightforward ``jax.numpy``, float32, ``highest`` matmul
precision, ``jax.grad``: python loops over layers, key-value head groups and
experts, no kernel, no sort, no grouped product (an expert is applied to
every token and weighed by the choice, 0 where it was not chosen).  It reads
the layer list from the configuration's JSON file and imports nothing of
``znicz_tpu`` (the arithmetic modes, the norm, rotary, AdamW and the leaves'
norms it takes from the looped reference beside it); weights come from ``numpy.random.RandomState`` seeded the way
the published harness seeds its stream, leaf by leaf in the order of the
configuration (an expert layer draws one number from that stream and every
expert it holds from a stream of its own named by that number and the
expert's index, so a share draws what the uncut layer draws), so the
reference takes no array the program has made.

Departures from "no recomputation" and "whole matrices", none of which
changes a float32 number beyond the order of a sum: every residual entry
stands under ``jax.checkpoint``; attention's scores are made a key-value
head group and a block of ``SCORE_BLOCK`` queries at a time and the head's
logits a block of ``HEAD_BLOCK`` positions at a time, each block under
``jax.checkpoint`` and the blocks one after another (``jax.lax.map``: left
to itself the compiler keeps twenty blocks of scores alive at once).  28
heads' whole 16,384 x 16,384 float32 scores are 30 GB, a row's whole logits
with their log-softmax and gradient 7.5 GB.  (In the ``fp8`` mode an
operand's scale is its block's.)

    RMS(x; g) = g * x / sqrt(mean(x^2) + eps)
    x = E[ids]
    for layer l:
        r = x Wr                        (the router reads the layer's input)
        a = RMS(x; g1); q, k, v = a Wq, a Wk, a Wv; rotary on q, k by the
        position in the row where the layer has it (rotate-half); scores
        q k^T / sqrt(hd), masked to j <= i, segment_j = segment_i and, where
        the layer has a window, i - j < window; u = softmax(scores) v;
        x += u Wo
        b = RMS(x; g2); S = the top_k largest of r (ties to the lower
        index); w_e = exp(r_e) / sum_{e' in S} exp(r_e') for e in S;
        x += sum_{e in S and held here} w_e (act(b Wg_e) * (b Wu_e)) Wd_e
    h = RMS(x; gf); z = h W^T
    loss = mean over graded positions of CE(z, label)
    AdamW with bias correction, decoupled decay.

Two ways to route.  **Free**: ``S`` is the reference's own, as above.
**Forced** (``route`` given, ``(entries, S, top_k)``): ``S`` is the choice
handed in, the weights are the softmax of the reference's OWN logits at the
chosen experts, and for every token and entry the reference says whether
its own choice differs and by how much of its own logits (``margin``: its
best expert not taken less the least expert taken in its place).  A top-k
over near-equal logits is a choice that rounding moves; everything after
it is compared under one choice.

``mode`` selects the arithmetic of every matrix product but the router's,
which the configuration states as float32 (``f32`` the reference proper;
``bf16`` / ``fp8`` operands rounded, float32 accumulation: the witness and
the control).  Planted faults: ``window_left_out`` (every layer attends its
whole document), ``rope_on_global`` (every layer turns its queries and
keys), ``weights_over_held`` (the softmax runs over the chosen experts held
here only).
"""

import numpy

import jax
import jax.numpy as jnp
from jax import lax

# the arithmetic modes, the norm, rotary, AdamW and the leaves' norms are the
# looped reference's own equations: one copy of them
from benchmarks.reference.looped_lm import (  # noqa: F401
    ADAM_DEFAULTS, _mm, _quant, _rms, _rope, adamw, difference, leaf_norms)

KINDS = ("embedding", "rmsnorm", "attention", "router", "moe", "lm_head")
#: a kind's cost module where it is not named after the kind
COST_KIND = {"attention": "local_attention", "lm_head": "ce_head"}
FAULTS = ("window_left_out", "rope_on_global", "weights_over_held")
#: queries a block of scores; positions a block of logits (a row that
#: they do not divide is taken whole)
SCORE_BLOCK = 2048
HEAD_BLOCK = 4096


# -- the configuration --------------------------------------------------------

def _fwd(layer):
    kw = {k: v for k, v in layer.items()
          if k not in ("type", "name", "->", "<-", "layers")}
    kw.update(layer.get("->", {}))
    return kw


def flatten(layers):
    """(leaf layers in the order of the configuration, topology): a node is
    a leaf's index or ("residual", [nodes])."""
    flat = []

    def walk(entries):
        nodes = []
        for layer in entries:
            if layer["type"] == "residual":
                nodes.append(("residual", walk(layer["layers"])))
            elif layer["type"] in KINDS:
                nodes.append(len(flat))
                flat.append(layer)
            else:
                raise ValueError("reference knows %s and residual entries "
                                 "only, not %r"
                                 % (", ".join(KINDS), layer["type"]))
        return nodes

    return flat, walk(layers)


def held(a):
    """(first, count) of the experts a ``moe`` layer holds."""
    first, count = a.get("held") or (0, int(a["experts"]))
    return int(first), int(count)


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
        elif tpe == "router":
            t = {"wr": ((dim, int(a["experts"])), s, None, True)}
        elif tpe == "moe":
            f, count = int(a["hidden"]), held(a)[1]
            t = {"wg": ((count, dim, f), s, None, True),
                 "wu": ((count, dim, f), s, None, True),
                 "wd": ((count, f, dim), s, None, True)}
        else:
            t = {"g": ((dim,), None, 1.0, False),
                 "w": ((int(a["vocab"]), dim), s, None, True)}
        out.append(t)
    return out


def init_params(layers, seed):
    """One legacy numpy stream seeded with ``[seed]`` as uint32 words; an
    expert layer takes one integer of it and draws expert ``e`` whole (its
    three matrices in the table's order) from the stream ``[that integer,
    e]``."""
    rs = numpy.random.RandomState(numpy.asarray([seed], dtype=numpy.uint32))
    flat, _ = flatten(layers)
    params = []
    for layer, table in zip(flat, leaf_table(layers)):
        p = {}
        if layer["type"] == "moe":
            base = int(rs.randint(0, 2 ** 31 - 1, size=1)[0])
            first, count = held(_fwd(layer))
            p = {name: numpy.empty(shape, numpy.float32)
                 for name, (shape, _, _, _) in table.items()}
            for j in range(count):
                own = numpy.random.RandomState([base, first + j])
                for name, (shape, std, _, _) in table.items():
                    p[name][j] = own.normal(0, std, size=shape[1:])
            params.append(p)
            continue
        for name, (shape, std, const, _) in table.items():
            if std is None:
                p[name] = numpy.full(shape, const, numpy.float32)
            else:
                p[name] = rs.normal(0, std, size=shape).astype(numpy.float32)
        params.append(p)
    return params


def hypers(layers):
    """[{leaf: {lr, wd, adam_beta1, adam_beta2, adam_eps}}]: what the
    configuration states for every leaf (gains take the layer's bias
    learning rate and decay)."""
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


def moe_entries(layers):
    """The leaf indices of the ``moe`` layers, in the chain's order."""
    flat, _ = flatten(layers)
    return [i for i, layer in enumerate(flat) if layer["type"] == "moe"]


def plan(layers, seq, pairs_per_row=None):
    """The planned net for ``layer_costs``: one entry a leaf layer, with
    its ``kind`` (its cost module's name), ``spec`` (the leaf's index, as
    in the scopes ``L%02d.<kind>``), ``seq`` and its widths; for attention
    the attended (query, key) pairs of a row (``pairs_per_row(window)``;
    causal, and inside the window, over the whole row where None); for an
    expert layer the pairs (token, expert) held here at an even load."""
    flat, _ = flatten(layers)
    out = []
    for node, (layer, table) in enumerate(zip(flat, leaf_table(layers))):
        a = _fwd(layer)
        ent = {"kind": COST_KIND.get(layer["type"], layer["type"]),
               "spec": node, "seq": int(seq),
               "leaves": {k: v[0] for k, v in table.items()},
               "update": True}
        if layer["type"] == "attention":
            window = a.get("window")
            if pairs_per_row is None:
                w = seq if window is None else min(int(window), seq)
                pairs = float(w * (w + 1) / 2 + (seq - w) * w)
            else:
                pairs = float(pairs_per_row(window))
            ent.update(heads=int(a["heads"]), kv_heads=int(a["kv_heads"]),
                       head_dim=int(a["head_dim"]), window=window,
                       pairs=pairs)
        elif layer["type"] == "moe":
            n_exp, k = int(a["experts"]), int(a["top_k"])
            ent.update(experts=n_exp, top_k=k, held=held(a)[1],
                       hidden=int(a["hidden"]),
                       pairs=float(seq) * k * held(a)[1] / n_exp)
        out.append(ent)
    return out


# -- the model, one row at a time ---------------------------------------------

def _scores_block(q, k, v, seg_q, seg_k, q0, window, mode):
    """Queries ``q (bq, G, hd)`` at positions ``q0 ...`` of one key-value
    head's group against all the row's keys ``k, v (S, hd)``."""
    hd = q.shape[-1]
    scores = jnp.einsum("ighd,jhd->gij", _quant(q, mode)[:, :, None],
                        _quant(k, mode)[:, None]) / float(numpy.sqrt(hd))
    i = q0 + jnp.arange(q.shape[0])
    j = jnp.arange(k.shape[0])
    ok = (j[None, :] <= i[:, None]) & (seg_k[None, :] == seg_q[:, None])
    if window is not None:
        ok = ok & (i[:, None] - j[None, :] < window)
    w = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("gij,jd->igd", _quant(w, mode), _quant(v, mode))


def _attention(p, x, seg, a, mode, fault):
    s = x.shape[0]
    hd, h, kv = (int(a[k]) for k in ("head_dim", "heads", "kv_heads"))
    q = _mm(x, p["wq"], mode).reshape(s, h, hd)
    k = _mm(x, p["wk"], mode).reshape(s, kv, hd)
    v = _mm(x, p["wv"], mode).reshape(s, kv, hd)
    if a.get("rope", True) or fault == "rope_on_global":
        base = float(a.get("rope_base", 10000.0))
        q, k = _rope(q, base), _rope(k, base)
    window = None if fault == "window_left_out" else a.get("window")
    group = h // kv
    block = jax.checkpoint(_scores_block, static_argnums=(6, 7))
    bq = SCORE_BLOCK if s % SCORE_BLOCK == 0 else s
    starts = jnp.arange(0, s, bq)
    heads = []
    for g in range(kv):
        qg = q[:, g * group:(g + 1) * group]
        out = lax.map(
            lambda blk, g=g: block(blk[0], k[:, g], v[:, g], blk[1], seg,
                                   blk[2], window, mode),
            (qg.reshape(s // bq, bq, group, hd), seg.reshape(s // bq, bq),
             starts))
        heads.append(out.reshape(s, group, hd))
    u = jnp.concatenate(heads, axis=1)
    return _mm(u.reshape(s, h * hd), p["wo"], mode)


def own_choice(r, k):
    """The ``k`` largest of every row of ``r``, ties to the lower index:
    (S, k) int32, largest first."""
    return lax.top_k(r, k)[1].astype(jnp.int32)


def _members(chosen, n_exp):
    """(S, experts) bool: the experts a token's choice names."""
    return (chosen[:, :, None] == jnp.arange(n_exp)[None, None, :]).any(
        axis=1)


def _moe(p, b, r, a, forced, mode, fault):
    """(the held experts' weighed sum (S, d), report) of one expert layer:
    ``r (S, experts)`` the router's logits, ``forced (S, k)`` a choice to
    take in place of the reference's own, or None."""
    n_exp, k = int(a["experts"]), int(a["top_k"])
    first, count = held(a)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[
        a.get("activation", "silu")]
    own = own_choice(r, k)
    chosen = own if forced is None else forced.astype(jnp.int32)
    taken, mine = _members(chosen, n_exp), _members(own, n_exp)
    e = jnp.arange(n_exp)
    counted = taken
    if fault == "weights_over_held":
        counted = taken & ((e >= first) & (e < first + count))[None, :]
    # the softmax over the chosen logits, laid out over all the experts
    z = jnp.where(counted, r, -jnp.inf)
    z = z - lax.stop_gradient(jnp.max(jnp.where(taken, r, -jnp.inf),
                                      axis=1, keepdims=True))
    ez = jnp.where(counted, jnp.exp(z), 0.0)
    w = ez / jnp.maximum(ez.sum(axis=1, keepdims=True), 1e-30)
    out = jnp.zeros_like(b)
    for j in range(count):
        y = _mm(act(_mm(b, p["wg"][j], mode)) * _mm(b, p["wu"][j], mode),
                p["wd"][j], mode)
        out = out + w[:, first + j, None] * y
    # what the reference would have chosen and the choice did not take,
    # against what the choice took in its place
    lost = jnp.max(jnp.where(mine & ~taken, r, -jnp.inf), axis=1)
    got = jnp.min(jnp.where(taken & ~mine, r, jnp.inf), axis=1)
    flipped = (mine != taken).any(axis=1)
    rms = jnp.sqrt(jnp.mean(r * r))
    sound = (taken.sum(axis=1) == k) & (chosen >= 0).all(axis=1) \
        & (chosen < n_exp).all(axis=1)
    margin = jnp.where(sound, jnp.where(flipped, (lost - got) / rms, 0.0),
                       jnp.inf)
    report = {"route": chosen.astype(jnp.int8 if n_exp <= 128
                                     else jnp.int16),
              "load": taken.sum(axis=0).astype(jnp.int32),
              "flipped": flipped, "margin": margin}
    return out, report


def forward_row(params, ids, seg, labels, sample, layers, route=None,
                mode="f32", fault=None):
    """(cross-entropy (S,), argmax (S,), logits at ``sample``, the expert
    layers' reports stacked over entries) of one row ``ids (S,)``;
    ``route (entries, S, k)`` forces the choice."""
    flat, nodes = flatten(layers)
    entries = moe_entries(layers)
    side, reports = {}, {}

    def leaf(node, x, side):
        layer, p = flat[node], params[node]
        a, tpe = _fwd(layer), layer["type"]
        if tpe == "embedding":
            return p["w"][x], None
        if tpe == "rmsnorm":
            return _rms(x, p["g"], float(a.get("eps", 1e-6))), None
        if tpe == "attention":
            return _attention(p, x, seg, a, mode, fault), None
        if tpe == "moe":
            forced = None if route is None else route[entries.index(node)]
            return _moe(p, x, side[a["router"]], a, forced, mode, fault)
        raise ValueError(tpe)

    def run(nodes, x, side):
        found = {}
        for node in nodes:
            if isinstance(node, int):
                layer = flat[node]
                if layer["type"] == "router":
                    # float32 whatever the mode: the configuration's own
                    side = dict(side, **{layer["name"]: jnp.matmul(
                        x, params[node]["wr"])})
                elif layer["type"] == "lm_head":
                    raise ValueError("the head ends the chain")
                else:
                    x, report = leaf(node, x, side)
                    if report is not None:
                        found[node] = report
            else:
                # one residual entry: recomputed, not kept (see above)
                out, inner = jax.checkpoint(
                    lambda x, side, body=node[1]: run(body, x, side)[:2])(
                        x, side)
                x = x + out
                found.update(inner)
        return x, found, side

    if flat[-1]["type"] != "lm_head" or not isinstance(nodes[-1], int):
        raise ValueError("the chain ends in its lm_head")
    x, reports, _ = run(nodes[:-1], ids, side)
    a = _fwd(flat[-1])
    ce, pred, z = _head(params[-1], _rms(x, params[-1]["g"],
                                         float(a.get("eps", 1e-6))),
                        labels, sample, mode)
    stacked = {k: jnp.stack([reports[n][k] for n in entries])
               for k in ("route", "load", "flipped", "margin")}
    return ce, pred, z, stacked


def _head_block(w, h, labels, mode):
    z = _mm(h, w.T, mode)
    logp = jax.nn.log_softmax(z, axis=-1)
    ce = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None],
                              axis=1)[:, 0]
    return ce, jnp.argmax(z, axis=-1)


def _head(p, h, labels, sample, mode):
    """(cross-entropy (S,), argmax (S,), logits at ``sample``) of the
    normed final state."""
    s = h.shape[0]
    tb = HEAD_BLOCK if s % HEAD_BLOCK == 0 else s
    block = jax.checkpoint(_head_block, static_argnums=(3,))
    ce, pred = lax.map(
        lambda blk: block(p["w"], blk[0], blk[1], mode),
        (h.reshape(s // tb, tb, -1), labels.reshape(s // tb, tb)))
    return ce.reshape(s), pred.reshape(s), _mm(h[sample], p["w"].T, mode)


def make_row(layers, mode="f32", fault=None, forced=False):
    """Jitted ``(params, total, ids, seg, labels, sample[, route]) -> (total
    + the gradient of the row's loss SUM over its graded positions, aux)``:
    the caller divides by the minibatch's graded count (``total`` is given
    up to the call, so that a minibatch's sum takes one buffer).  ``aux``:
    ``loss_sum``, ``graded``, ``errors``, the ``logits (n, V)`` at
    ``sample`` (positions of the row), and of every expert layer ``route
    (entries, S, k)`` as it was taken, ``load (entries, experts)`` under
    that choice, ``flipped (entries, S)`` and ``margin (entries, S)``.
    With ``forced`` the function takes the choice as its last argument."""
    if fault not in (None,) + FAULTS:
        raise ValueError(fault)

    def loss_sum(params, ids, seg, labels, sample, route):
        ce, pred, z, reports = forward_row(params, ids, seg, labels, sample,
                                           layers, route, mode, fault)
        valid = labels >= 0
        total = jnp.sum(jnp.where(valid, ce, 0.0))
        aux = dict(reports, loss_sum=total, graded=valid.sum(),
                   errors=(valid & (pred != labels)).sum(), logits=z)
        return total, aux

    def row(params, total, ids, seg, labels, sample, route=None):
        with jax.default_matmul_precision("highest"):
            (_, aux), grads = jax.value_and_grad(loss_sum, has_aux=True)(
                params, ids, seg, labels, sample, route)
        return jax.tree.map(jnp.add, total, grads), aux

    if forced:
        return jax.jit(row, donate_argnums=(1,))
    return jax.jit(lambda params, total, ids, seg, labels, sample: row(
        params, total, ids, seg, labels, sample), donate_argnums=(1,))
