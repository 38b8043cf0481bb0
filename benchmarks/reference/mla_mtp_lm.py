"""Plain reference for a language model of latent attention and shared and
routed experts, balanced by a selection bias, with a multi-token prediction
module: one chip's share.

The equations, in straightforward ``jax.numpy``, float32, ``highest`` matmul
precision, ``jax.grad``: python loops over layers and experts, no kernel, no
sort, no grouped product (an expert is applied to every token and weighed by
the choice, 0 where it was not chosen).  It reads the layer list from the
configuration's JSON file and imports nothing of ``znicz_tpu``.  The
arithmetic modes, the norm, rotary, AdamW and the leaves' norms are the
looped reference's; the expert layer (sigmoid scores, the selection bias in
the choice and not in the weights, the shared expert, the forced choice and
its bookkeeping) and the blocked head are those of the references beside
it: one copy of each equation.  Weights come from
``numpy.random.RandomState`` seeded the way the published harness seeds its
stream, leaf by leaf in the order of the configuration; an expert layer
draws one number from that stream, every expert it holds from a stream of
its own named by that number and the expert's index, and the shared expert
from the stream named by that number and the count of ALL the experts, so a
share draws what the uncut layer draws.  An entry that names another under
``tied`` has no leaf of that weight: it reads the other's.

Departures from "no recomputation" and "whole matrices", none of which
changes a float32 number beyond the order of a sum: every residual entry
stands under ``jax.checkpoint``; attention's scores are made a block of
``SCORE_BLOCK`` queries at a time over all the heads, the heads' logits a
block of positions at a time, each block under ``jax.checkpoint`` and the
blocks one after another (20 heads' whole 8,192 x 8,192 float32 scores are
5.4 GB, and their softmax and gradient as much again each).

    RMS(x; g) = g * x / sqrt(mean(x^2) + eps)
    x = E[ids]
    for layer l:
        a = RMS(x; g1)
        cq = RMS(a Wqa; gqa); [qn_h | qr_h] = (cq Wqb)_h     (nope | rope)
        [c | kr] = a Wkva; c = RMS(c; gkv)                   (kv_rank | rope)
        [kn_h | v_h] = (c Wkvb)_h                            (nope | v)
        q_h = [qn_h | rot(qr_h)], k_h = [kn_h | rot(kr)]: rotary (rotate-
        half) by the position in the row over the rope part alone, ONE
        rope key for all the heads; scores q k^T / sqrt(nope + rope),
        masked to j <= i and segment_j = segment_i; u = softmax(scores) v
        x += [u_1 .. u_H] Wo
        m = RMS(x; g2); x += f(m), where in a dense layer
        f(m) = (silu(m Wg) * (m Wu)) Wd and in an expert layer
            s = sigmoid(m Wr)                          (float32)
            S = the top_k largest of s + b (ties to the lower index)
            w_e = scale * s_e / (sum_{e' in S} s_e' + 1e-20) for e in S
            f(m) = shared(m) + sum_{e in S and held here} w_e expert_e(m)
    h = RMS(x; gf); z = h W^T                     (the main head)
    t'_i = ids_{i+1} (0 past the row's end)
    y = [RMS(E[t']; ge) | RMS(h; gh)] Weh          (E the embedding's table)
    y = one layer of the expert-layer form above
    z2 = RMS(y; gm) W^T                            (W the main head's weight)
    loss = mean over graded positions of CE(z, t_{i+1})
         + mtp_weight * mean over positions i with i, i+1, i+2 in one
           document of CE(z2, t_{i+2})
    AdamW with bias correction, decoupled decay, on every leaf but b.
    After the step, with c_e the tokens the step sent to expert e:
        d_e = rate * sign(mean(c) - c_e); b += d - mean(d)

The bias rule: DeepSeek-V3 moves each bias by ``rate * sign(mean - c_e)``
with no centring; the centring here moves every bias of a layer by one
constant, which changes no choice and no weight, so it is that rule as far
as routing sees it (it keeps the biases' sum at nought, as the program
does).

Two ways to route, as in the routed reference.  **Free**: ``S`` is the
reference's own.  **Forced** (``route`` given, ``(entries, S, top_k)``,
the multi-token prediction module's expert layer last): ``S`` is the choice
handed in, weighed by the reference's own scores, and for every token and
entry the reference says whether its own choice differs and by how much.

``mode`` selects the arithmetic of every matrix product but the router's,
which the configuration states as float32 (``f32`` the reference proper;
``bf16`` / ``fp8`` operands rounded, float32 accumulation: the witness and
the control).  Planted faults: ``rope_whole_head`` (rotary over every
head's whole width, the keys' latent part included), ``kv_norm_left_out``
(the key-value latent is not normed), ``mtp_shift_one`` (the second head
graded against the next id, as the first), ``mtp_head_apart`` (no gradient
of the second head's loss reaches the embedding and the output weight that
the module shares), ``mtp_weight_one`` (the second head's mean loss weighs
1).
"""

import numpy

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.afmoe_lm import BIAS, _gated, _moe
from benchmarks.reference.looped_lm import (  # noqa: F401
    ADAM_DEFAULTS, _mm, _quant, _rms, _rope, adamw, difference, leaf_norms)
from benchmarks.reference.routed_lm import HEAD_BLOCK, _fwd, held

KINDS = ("embedding", "rmsnorm", "latent_attention", "gated_mlp", "router",
         "moe", "lm_head", "mtp_input")
#: a kind's cost module where it is not named after the kind
COST_KIND = {"moe": "shared_moe", "lm_head": "ce_head"}
FAULTS = ("rope_whole_head", "kv_norm_left_out", "mtp_shift_one",
          "mtp_head_apart", "mtp_weight_one")
#: queries a block of scores (all the heads at once)
SCORE_BLOCK = 1024


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


def widths(a):
    """(heads, q_rank, kv_rank, nope_dim, rope_dim, v_dim) of a latent
    attention layer."""
    return tuple(int(a[k]) for k in ("heads", "q_rank", "kv_rank",
                                     "nope_dim", "rope_dim", "v_dim"))


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
        elif tpe == "latent_attention":
            h, qr, kvr, nope, rope, vd = widths(a)
            t = {"wqa": ((dim, qr), s, None, True),
                 "gqa": ((qr,), None, 1.0, False),
                 "wqb": ((qr, h * (nope + rope)), s, None, True),
                 "wkva": ((dim, kvr + rope), s, None, True),
                 "gkv": ((kvr,), None, 1.0, False),
                 "wkvb": ((kvr, h * (nope + vd)), s, None, True),
                 "wo": ((h * vd, dim), s, None, True)}
        elif tpe == "gated_mlp":
            f = int(a["hidden"])
            t = {"wg": ((dim, f), s, None, True),
                 "wu": ((dim, f), s, None, True),
                 "wd": ((f, dim), s, None, True)}
        elif tpe == "router":
            t = {"wr": ((dim, int(a["experts"])), s, None, True)}
        elif tpe == "moe":
            f, count = int(a["hidden"]), held(a)[1]
            fs = int(a["shared_hidden"])
            t = {"wg": ((count, dim, f), s, None, True),
                 "wu": ((count, dim, f), s, None, True),
                 "wd": ((count, f, dim), s, None, True),
                 "sg": ((dim, fs), s, None, True),
                 "su": ((dim, fs), s, None, True),
                 "sd": ((fs, dim), s, None, True),
                 BIAS: ((int(a["experts"]),), None, 0.0, None)}
        elif tpe == "mtp_input":
            t = {"ge": ((dim,), None, 1.0, False),
                 "gh": ((dim,), None, 1.0, False),
                 "w": ((2 * dim, dim), s, None, True)}
        else:
            t = {"g": ((dim,), None, 1.0, False)}
            if not a.get("tied"):
                t["w"] = ((int(a["vocab"]), dim), s, None, True)
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


def heads(layers):
    """(main head's index, the module's head's index, its weight, its
    shift)."""
    flat, _ = flatten(layers)
    at = [i for i, layer in enumerate(flat) if layer["type"] == "lm_head"]
    if len(at) != 2 \
            or _fwd(flat[at[1]]).get("tied") != flat[at[0]].get("name"):
        raise ValueError("this reference runs a head and a second head "
                         "that reads its weight")
    a = _fwd(flat[at[1]])
    return at[0], at[1], float(a.get("loss_weight", 1.0)), \
        int(a.get("shift", 1))


def balance(biases, load, layers, fault=None):
    """The expert layers' selection biases ``(entries, experts)`` float32
    after a step that sent ``load (entries, experts)`` tokens: every
    expert's bias moves by the layer's ``balance_rate`` toward the mean
    load, the moves centred."""
    flat, _ = flatten(layers)
    out = numpy.array(biases, numpy.float32)
    for n, node in enumerate(moe_entries(layers)):
        rate = _fwd(flat[node])["balance_rate"]
        c = numpy.asarray(load[n], numpy.float32)
        d = numpy.float32(rate) * numpy.sign(c.mean(dtype=numpy.float32) - c)
        out[n] = out[n] + d - d.mean(dtype=numpy.float32)
    return out


def plan(layers, seq, pairs_per_row=None):
    """The planned net for ``layer_costs``: one entry a leaf layer, with
    its ``kind`` (its cost module's name), ``spec`` (the leaf's index, as
    in the scopes ``L%02d.<kind>``), ``seq``, its widths and ``mtp`` (True
    from the module's way in to its head); for latent attention the
    attended (query, key) pairs of a row (``pairs_per_row(None)``: causal
    inside its documents; over the whole row where None); for an expert
    layer the pairs (token, expert) held here at an even load.  The
    second head is planned with the weight it reads, whose update is
    booked to the head that holds it."""
    flat, _ = flatten(layers)
    table = leaf_table(layers)
    main, second, _, _ = heads(layers)
    out = []
    for node, layer in enumerate(flat):
        a = _fwd(layer)
        leaves = {k: v[0] for k, v in table[node].items()}
        ent = {"kind": COST_KIND.get(layer["type"], layer["type"]),
               "spec": node, "seq": int(seq), "leaves": leaves,
               "update": True, "mtp": node > main}
        if node == second:
            ent.update(leaves=dict(leaves, w=table[main]["w"][0]),
                       update=False)
        if layer["type"] == "latent_attention":
            h, qr, kvr, nope, rope, vd = widths(a)
            pairs = float(seq * (seq + 1) / 2) if pairs_per_row is None \
                else float(pairs_per_row(None))
            ent.update(heads=h, q_rank=qr, kv_rank=kvr, nope_dim=nope,
                       rope_dim=rope, v_dim=vd, pairs=pairs)
        elif layer["type"] == "moe":
            n_exp, k = int(a["experts"]), int(a["top_k"])
            ent.update(experts=n_exp, top_k=k, held=held(a)[1],
                       hidden=int(a["hidden"]),
                       pairs=float(seq) * k * held(a)[1] / n_exp)
        out.append(ent)
    return out


def shifted(labels, shift):
    """Labels ``shift`` ids ahead: position i is graded where the next-id
    labels of i .. i + shift - 1 all are."""
    out = labels
    for _ in range(shift - 1):
        out = jnp.where(labels >= 0, jnp.concatenate(
            [out[1:], jnp.full((1,), -1, out.dtype)]), -1)
    return out


# -- the model, one row at a time ---------------------------------------------

def _scores_block(q, k, v, seg_q, seg, q0, mode):
    """Queries ``q (bq, H, hd)`` at positions ``q0 ...`` against all the
    row's keys ``k (S, H, hd)`` and values ``v (S, H, vd)``."""
    hd = q.shape[-1]
    scores = jnp.einsum("ihd,jhd->hij", _quant(q, mode),
                        _quant(k, mode)) / float(numpy.sqrt(hd))
    i = q0 + jnp.arange(q.shape[0])
    j = jnp.arange(k.shape[0])
    ok = (j[None, :] <= i[:, None]) & (seg[None, :] == seg_q[:, None])
    w = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hij,jhd->ihd", _quant(w, mode), _quant(v, mode))


def _latent_attention(p, x, seg, a, mode, fault):
    s = x.shape[0]
    h, _, kvr, nope, rope, vd = widths(a)
    eps = float(a.get("eps", 1e-6))
    base = float(a.get("rope_base", 10000.0))
    q = _mm(_rms(_mm(x, p["wqa"], mode), p["gqa"], eps), p["wqb"],
            mode).reshape(s, h, nope + rope)
    ckr = _mm(x, p["wkva"], mode)
    c = ckr[:, :kvr]
    if fault != "kv_norm_left_out":
        c = _rms(c, p["gkv"], eps)
    kv = _mm(c, p["wkvb"], mode).reshape(s, h, nope + vd)
    kr = jnp.broadcast_to(ckr[:, None, kvr:], (s, h, rope))
    if fault == "rope_whole_head":
        q = _rope(q, base)
        k = _rope(jnp.concatenate([kv[..., :nope], kr], axis=-1), base)
    else:
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], base)],
                            axis=-1)
        k = jnp.concatenate([kv[..., :nope], _rope(kr, base)], axis=-1)
    v = kv[..., nope:]
    block = jax.checkpoint(_scores_block, static_argnums=(6,))
    bq = SCORE_BLOCK if s % SCORE_BLOCK == 0 else s
    u = lax.map(lambda blk: block(blk[0], k, v, blk[1], seg, blk[2], mode),
                (q.reshape(s // bq, bq, h, nope + rope),
                 seg.reshape(s // bq, bq), jnp.arange(0, s, bq)))
    return _mm(u.reshape(s, h * vd), p["wo"], mode)


def _head_block(w, h, labels, mode):
    z = _mm(h, w.T, mode)
    logp = jax.nn.log_softmax(z, axis=-1)
    ce = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None],
                              axis=1)[:, 0]
    return ce, jnp.argmax(z, axis=-1)


def _head(w, h, labels, sample, mode):
    """(cross-entropy (S,), argmax (S,), logits at ``sample``) of a normed
    state against a weight."""
    s = h.shape[0]
    tb = HEAD_BLOCK if s % HEAD_BLOCK == 0 else s
    block = jax.checkpoint(_head_block, static_argnums=(3,))
    ce, pred = lax.map(
        lambda blk: block(w, blk[0], blk[1], mode),
        (h.reshape(s // tb, tb, -1), labels.reshape(s // tb, tb)))
    return ce.reshape(s), pred.reshape(s), _mm(h[sample], w.T, mode)


REPORTS = ("route", "load", "weight", "flipped", "margin", "tilt")


def forward_row(params, ids, seg, labels, sample, layers, route=None,
                mode="f32", fault=None):
    """Both heads' (cross-entropy (S,), argmax (S,), labels (S,), logits at
    ``sample``) and the expert layers' reports stacked over entries, of one
    row ``ids (S,)``; ``route (entries, S, k)`` forces the choice."""
    flat, nodes = flatten(layers)
    entries = moe_entries(layers)
    main, second, _, shift = heads(layers)
    emb = 0
    if flat[emb]["type"] != "embedding" or not isinstance(nodes[0], int):
        raise ValueError("the chain starts with its embedding")

    def leaf(node, x, side):
        layer, p = flat[node], params[node]
        a, tpe = _fwd(layer), layer["type"]
        if tpe == "rmsnorm":
            return _rms(x, p["g"], float(a.get("eps", 1e-6))), None
        if tpe == "latent_attention":
            return _latent_attention(p, x, seg, a, mode, fault), None
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

    at = nodes.index(main)
    if nodes[at + 1] != main + 1 or flat[main + 1]["type"] != "mtp_input" \
            or nodes[-1] != second:
        raise ValueError("the chain is embedding, trunk, head, the module's "
                         "way in, its layers, its head")
    table = params[emb]["w"]
    w_head = params[main]["w"]
    x, reports, _ = run(nodes[1:at], table[ids], {})
    a = _fwd(flat[main])
    h = _rms(x, params[main]["g"], float(a.get("eps", 1e-6)))
    ce, pred, z = _head(w_head, h, labels, sample, mode)
    # the module's way in: the embedding of every position's next id
    a = _fwd(flat[main + 1])
    eps = float(a.get("eps", 1e-6))
    if fault == "mtp_head_apart":
        table, w_head = lax.stop_gradient(table), lax.stop_gradient(w_head)
    nxt = jnp.concatenate([ids[1:], jnp.zeros((1,), ids.dtype)])
    p = params[main + 1]
    y = _mm(jnp.concatenate([_rms(table[nxt], p["ge"], eps),
                             _rms(h, p["gh"], eps)], axis=-1), p["w"], mode)
    y, more, _ = run(nodes[at + 2:-1], y, {})
    reports.update(more)
    a = _fwd(flat[second])
    ahead = shifted(labels, 1 if fault == "mtp_shift_one" else shift)
    ce2, pred2, z2 = _head(w_head, _rms(y, params[second]["g"],
                                        float(a.get("eps", 1e-6))),
                           ahead, sample, mode)
    stacked = {k: jnp.stack([reports[n][k] for n in entries])
               for k in REPORTS}
    return (ce, pred, labels, z), (ce2, pred2, ahead, z2), stacked


def make_row(layers, mode="f32", fault=None, forced=False):
    """Jitted ``(params, total, ids, seg, labels, sample, scale[, route]) ->
    (total + the gradient of the row's weighed loss, aux)``: ``scale (2,)``
    weighs the main head's loss SUM over its graded positions and the
    second head's (the caller hands in one over the minibatch's graded
    count of each, the second times the module's weight), so that a
    minibatch's rows add up to the objective's gradient.  ``aux``: of the
    main head ``loss_sum``, ``graded``, ``errors``, ``logits (n, V)`` at
    ``sample``; of the second ``mtp_loss_sum``, ``mtp_graded``,
    ``mtp_errors``, ``mtp_logits``; of every expert layer ``route``,
    ``load``, ``weight``, ``flipped``, ``margin`` and ``tilt``.  With
    ``forced`` the function takes the choice as its last argument.  The
    selection bias is a leaf of ``params`` whose gradient is nought."""
    if fault not in (None,) + FAULTS:
        raise ValueError(fault)

    def weighed(params, ids, seg, labels, sample, scale, route):
        one, two, reports = forward_row(params, ids, seg, labels, sample,
                                        layers, route, mode, fault)
        aux = dict(reports)
        sums = []
        for tag, (ce, pred, lbl, z) in (("", one), ("mtp_", two)):
            valid = lbl >= 0
            sums.append(jnp.sum(jnp.where(valid, ce, 0.0)))
            aux.update({tag + "loss_sum": sums[-1],
                        tag + "graded": valid.sum(),
                        tag + "errors": (valid & (pred != lbl)).sum(),
                        tag + "logits": z})
        return scale[0] * sums[0] + scale[1] * sums[1], aux

    def row(params, total, ids, seg, labels, sample, scale, route=None):
        with jax.default_matmul_precision("highest"):
            (_, aux), grads = jax.value_and_grad(weighed, has_aux=True)(
                params, ids, seg, labels, sample, scale, route)
        return jax.tree.map(jnp.add, total, grads), aux

    if forced:
        return jax.jit(row, donate_argnums=(1,))
    return jax.jit(lambda params, total, ids, seg, labels, sample, scale: row(
        params, total, ids, seg, labels, sample, scale), donate_argnums=(1,))
