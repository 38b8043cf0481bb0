"""Plain reference for a language model of shared and routed experts,
balanced by a selection bias, under gated, normed attention: one chip's
share.

The equations, in straightforward ``jax.numpy``, float32, ``highest`` matmul
precision, ``jax.grad``: python loops over layers, key-value head groups and
experts, no kernel, no sort, no grouped product (an expert is applied to
every token and weighed by the choice, 0 where it was not chosen).  It reads
the layer list from the configuration's JSON file and imports nothing of
``znicz_tpu``.  The arithmetic modes, the norm, rotary, AdamW and the
leaves' norms are the looped reference's, the blocked scores, the blocked
head and the choice's bookkeeping the routed reference's: one copy of each
equation.  Weights come from ``numpy.random.RandomState`` seeded the way the
published harness seeds its stream, leaf by leaf in the order of the
configuration; an expert layer draws one number from that stream, every
expert it holds from a stream of its own named by that number and the
expert's index, and the shared expert from the stream named by that number
and the count of ALL the experts, so every share draws the same shared
expert and its own experts as the uncut layer draws them.

The departures from "no recomputation" and "whole matrices" are the routed
reference's (``reference/routed_lm.py``): residual entries, blocks of
scores and blocks of logits stand under ``jax.checkpoint`` and run one
after another.

    RMS(x; g) = g * x / sqrt(mean(x^2) + eps)
    x = E[ids] * scale
    for layer l:
        a = RMS(x; g1); q = RMS_head(a Wq; gq); k = RMS_head(a Wk; gk)
        (per head over its own elements); v = a Wv; on a windowed layer
        rotary on q, k by the position in the row (rotate-half), on a full
        layer no position at all; scores q k^T / sqrt(hd), masked to
        j <= i, segment_j = segment_i and, on a windowed layer,
        i - j < window; o = softmax(scores) v; o = o * sigmoid(a Wgate);
        x += RMS(o Wo; g2)
        m = RMS(x; g3); x += RMS(f(m); g4), where in a dense layer
        f(m) = (silu(m Wg) * (m Wu)) Wd and in an expert layer
            s = sigmoid(m Wr)                          (float32)
            S = the top_k largest of s + b (ties to the lower index)
            w_e = scale * s_e / (sum_{e' in S} s_e' + 1e-20) for e in S
            f(m) = shared(m) + sum_{e in S and held here} w_e expert_e(m)
    h = RMS(x; gf); z = h W^T
    loss = mean over graded positions of CE(z, label)
    AdamW with bias correction, decoupled decay, on every leaf but b.
    After the step, with c_e the tokens the step sent to expert e:
        d_e = rate * sign(mean(c) - c_e); b += d - mean(d)

Two ways to route, as in the routed reference.  **Free**: ``S`` is the
reference's own.  **Forced** (``route`` given, ``(entries, S, top_k)``):
``S`` is the choice handed in, the weights are of the reference's OWN
scores at the chosen experts, and for every token and entry the reference
says whether its own choice from its own ``s + b`` differs and by how much
(``margin``: its best ``s + b`` not taken less the least taken in its
place, over the standard deviation of all its scores; ``tilt``: of a pair
that differs, the sign of the bias of the expert it would have taken less
that of the expert taken in its place).

``mode`` selects the arithmetic of every matrix product but the router's,
which the configuration states as float32 (``f32`` the reference proper;
``bf16`` / ``fp8`` operands rounded, float32 accumulation: the witness and
the control).  Planted faults: ``gate_left_out`` (the heads' output is not
gated), ``qk_norm_left_out`` (queries and keys are not normed),
``shared_left_out`` (no token passes the shared expert),
``bias_left_out_of_choice`` (the choice is of ``s`` alone),
``bias_in_weights`` (the weights are of ``s + b``), ``rope_on_full`` (every
layer turns its queries and keys), ``centring_left_out`` (the rule's moves
keep their mean: every bias of a layer drifts alike, which moves no choice
and no weight and is told by the biases alone).
"""

import numpy

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.looped_lm import (  # noqa: F401
    ADAM_DEFAULTS, _mm, _rms, _rope, adamw, difference, leaf_norms)
from benchmarks.reference.routed_lm import (
    SCORE_BLOCK, _fwd, _head, _members, _scores_block, held, own_choice)

KINDS = ("embedding", "rmsnorm", "attention", "gated_mlp", "router", "moe",
         "lm_head")
#: a kind's cost module where it is not named after the kind
COST_KIND = {"attention": "gated_attention", "moe": "shared_moe",
             "lm_head": "ce_head"}
FAULTS = ("gate_left_out", "qk_norm_left_out", "shared_left_out",
          "bias_left_out_of_choice", "bias_in_weights", "rope_on_full",
          "centring_left_out")
#: the leaf no gradient moves
BIAS = "sb"


# -- the configuration --------------------------------------------------------

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


def leaf_table(layers):
    """[{leaf: (shape, stddev or None for a constant, constant, decays)}]
    for every leaf layer, leaves in the order they are drawn; ``decays``
    None marks the selection bias, which the optimizer does not know."""
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
            if a.get("qk_norm"):
                t.update(gq=((hd,), None, 1.0, False),
                         gk=((hd,), None, 1.0, False))
            if a.get("gate"):
                t["wgate"] = ((dim, h * hd), s, None, True)
        elif tpe == "gated_mlp":
            f = int(a["hidden"])
            t = {"wg": ((dim, f), s, None, True),
                 "wu": ((dim, f), s, None, True),
                 "wd": ((f, dim), s, None, True)}
        elif tpe == "router":
            t = {"wr": ((dim, int(a["experts"])), s, None, True)}
        elif tpe == "moe":
            f, count = int(a["hidden"]), held(a)[1]
            t = {"wg": ((count, dim, f), s, None, True),
                 "wu": ((count, dim, f), s, None, True),
                 "wd": ((count, f, dim), s, None, True)}
            if a.get("shared_hidden"):
                fs = int(a["shared_hidden"])
                t.update(sg=((dim, fs), s, None, True),
                         su=((dim, fs), s, None, True),
                         sd=((fs, dim), s, None, True))
            if a.get("balance_rate") is not None:
                t[BIAS] = ((int(a["experts"]),), None, 0.0, None)
        else:
            t = {"g": ((dim,), None, 1.0, False),
                 "w": ((int(a["vocab"]), dim), s, None, True)}
        out.append(t)
    return out


def init_params(layers, seed):
    """One legacy numpy stream seeded with ``[seed]`` as uint32 words; an
    expert layer takes one integer of it, draws expert ``e`` whole (its
    three matrices in the table's order) from the stream ``[that integer,
    e]`` and the shared expert from ``[that integer, experts]``."""
    rs = numpy.random.RandomState(numpy.asarray([seed], dtype=numpy.uint32))
    flat, _ = flatten(layers)
    params = []
    for layer, table in zip(flat, leaf_table(layers)):
        p = {}
        if layer["type"] == "moe":
            a = _fwd(layer)
            base = int(rs.randint(0, 2 ** 31 - 1, size=1)[0])
            first, count = held(a)
            p = {name: numpy.full(shape, const or 0.0, numpy.float32)
                 for name, (shape, _, const, _) in table.items()}
            for j in range(count):
                own = numpy.random.RandomState([base, first + j])
                for name in ("wg", "wu", "wd"):
                    shape, std = table[name][:2]
                    p[name][j] = own.normal(0, std, size=shape[1:])
            own = numpy.random.RandomState([base, int(a["experts"])])
            for name in ("sg", "su", "sd"):
                if name in table:
                    shape, std = table[name][:2]
                    p[name][...] = own.normal(0, std, size=shape)
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
    configuration states for every leaf AdamW steps (gains take the
    layer's bias learning rate and decay; the selection bias has none)."""
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
                    for name, (_, _, _, decays) in table.items()
                    if decays is not None})
    return out


def moe_entries(layers):
    """The leaf indices of the ``moe`` layers, in the chain's order."""
    flat, _ = flatten(layers)
    return [i for i, layer in enumerate(flat) if layer["type"] == "moe"]


def balance(biases, load, layers, fault=None):
    """The expert layers' selection biases ``(entries, experts)`` float32
    after a step that sent ``load (entries, experts)`` tokens: every
    expert's bias moves by the layer's ``balance_rate`` toward the mean
    load, the moves centred (not under the fault ``centring_left_out``)."""
    flat, _ = flatten(layers)
    out = numpy.array(biases, numpy.float32)
    for n, node in enumerate(moe_entries(layers)):
        rate = _fwd(flat[node]).get("balance_rate")
        if rate is None:
            continue
        c = numpy.asarray(load[n], numpy.float32)
        d = numpy.float32(rate) * numpy.sign(c.mean(dtype=numpy.float32) - c)
        if fault != "centring_left_out":
            d = d - d.mean(dtype=numpy.float32)
        out[n] = out[n] + d
    return out


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

def _attention(p, x, seg, a, mode, fault):
    s = x.shape[0]
    hd, h, kv = (int(a[k]) for k in ("head_dim", "heads", "kv_heads"))
    q = _mm(x, p["wq"], mode).reshape(s, h, hd)
    k = _mm(x, p["wk"], mode).reshape(s, kv, hd)
    v = _mm(x, p["wv"], mode).reshape(s, kv, hd)
    if a.get("qk_norm") and fault != "qk_norm_left_out":
        eps = float(a.get("eps", 1e-6))
        q, k = _rms(q, p["gq"], eps), _rms(k, p["gk"], eps)
    if a.get("rope", True) or fault == "rope_on_full":
        base = float(a.get("rope_base", 10000.0))
        q, k = _rope(q, base), _rope(k, base)
    window = a.get("window")
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
    u = jnp.concatenate(heads, axis=1).reshape(s, h * hd)
    if a.get("gate") and fault != "gate_left_out":
        u = u * jax.nn.sigmoid(_mm(x, p["wgate"], mode))
    return _mm(u, p["wo"], mode)


def _gated(x, wg, wu, wd, act, mode):
    return _mm(act(_mm(x, wg, mode)) * _mm(x, wu, mode), wd, mode)


def _moe(p, m, r, a, forced, mode, fault):
    """(the shared expert's output plus the held experts' weighed sum
    (S, d), report) of one expert layer: ``r (S, experts)`` the router's
    logits, ``forced (S, k)`` a choice to take in place of the reference's
    own, or None."""
    n_exp, k = int(a["experts"]), int(a["top_k"])
    first, count = held(a)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[
        a.get("activation", "silu")]
    if a.get("score") != "sigmoid" or not a.get("route_norm", True):
        raise ValueError("this reference routes by sigmoid scores, "
                         "normalised over the chosen")
    s = jax.nn.sigmoid(r)
    b = lax.stop_gradient(p[BIAS]) if BIAS in p else jnp.zeros(n_exp)
    pick = s if fault == "bias_left_out_of_choice" else s + b[None, :]
    own = own_choice(pick, k)
    chosen = own if forced is None else forced.astype(jnp.int32)
    taken, mine = _members(chosen, n_exp), _members(own, n_exp)
    w = jnp.where(taken, s + b[None, :] if fault == "bias_in_weights"
                  else s, 0.0)
    w = w / (w.sum(axis=1, keepdims=True) + 1e-20) \
        * float(a.get("route_scale", 1.0))
    out = jnp.zeros_like(m)
    for j in range(count):
        out = out + w[:, first + j, None] * _gated(
            m, p["wg"][j], p["wu"][j], p["wd"][j], act, mode)
    if "sg" in p and fault != "shared_left_out":
        out = out + _gated(m, p["sg"], p["su"], p["sd"], act, mode)
    # what the reference would have chosen and the choice did not take,
    # against what the choice took in its place
    lost = jnp.max(jnp.where(mine & ~taken, pick, -jnp.inf), axis=1)
    got = jnp.min(jnp.where(taken & ~mine, pick, jnp.inf), axis=1)
    flipped = (mine != taken).any(axis=1)
    sound = (taken.sum(axis=1) == k) & (chosen >= 0).all(axis=1) \
        & (chosen < n_exp).all(axis=1)
    margin = jnp.where(sound, jnp.where(flipped, (lost - got) / jnp.std(s),
                                        0.0), jnp.inf)
    # which way the bias leans across a flipped pair: the sign of the
    # lost expert's bias less the taken one's.  Rounding flips pairs both
    # ways alike; a choice made without the bias loses the experts the
    # bias favours
    lost_at = jnp.argmax(jnp.where(mine & ~taken, pick, -jnp.inf), axis=1)
    got_at = jnp.argmin(jnp.where(taken & ~mine, pick, jnp.inf), axis=1)
    tilt = jnp.where(flipped & sound, jnp.sign(b[lost_at] - b[got_at]), 0.0)
    report = {"route": chosen.astype(jnp.int8 if n_exp <= 128
                                     else jnp.int16),
              "load": taken.sum(axis=0).astype(jnp.int32),
              "weight": lax.stop_gradient(w).sum(axis=0),
              "flipped": flipped, "margin": margin, "tilt": tilt}
    return out, report


REPORTS = ("route", "load", "weight", "flipped", "margin", "tilt")


def forward_row(params, ids, seg, labels, sample, layers, route=None,
                mode="f32", fault=None):
    """(cross-entropy (S,), argmax (S,), logits at ``sample``, the expert
    layers' reports stacked over entries) of one row ``ids (S,)``;
    ``route (entries, S, k)`` forces the choice."""
    flat, nodes = flatten(layers)
    entries = moe_entries(layers)

    def leaf(node, x, side):
        layer, p = flat[node], params[node]
        a, tpe = _fwd(layer), layer["type"]
        if tpe == "embedding":
            return p["w"][x] * float(a.get("scale") or 1.0), None
        if tpe == "rmsnorm":
            return _rms(x, p["g"], float(a.get("eps", 1e-6))), None
        if tpe == "attention":
            return _attention(p, x, seg, a, mode, fault), None
        if tpe == "gated_mlp":
            return _gated(x, p["wg"], p["wu"], p["wd"], jax.nn.silu,
                          mode), None
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
                # one residual entry: recomputed, not kept
                out, inner = jax.checkpoint(
                    lambda x, side, body=node[1]: run(body, x, side)[:2])(
                        x, side)
                x = x + out
                found.update(inner)
        return x, found, side

    if flat[-1]["type"] != "lm_head" or not isinstance(nodes[-1], int):
        raise ValueError("the chain ends in its lm_head")
    x, reports, _ = run(nodes[:-1], ids, {})
    a = _fwd(flat[-1])
    ce, pred, z = _head(params[-1], _rms(x, params[-1]["g"],
                                         float(a.get("eps", 1e-6))),
                        labels, sample, mode)
    stacked = {k: jnp.stack([reports[n][k] for n in entries])
               for k in REPORTS}
    return ce, pred, z, stacked


def make_row(layers, mode="f32", fault=None, forced=False):
    """Jitted ``(params, total, ids, seg, labels, sample[, route]) -> (total
    + the gradient of the row's loss SUM over its graded positions, aux)``,
    as the routed reference's; ``aux`` also holds ``weight (entries,
    experts)``, the routing weight every expert took, and ``tilt (entries,
    S)``.  The selection bias
    is a leaf of ``params`` whose gradient is nought."""
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
