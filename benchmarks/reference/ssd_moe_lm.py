"""Plain reference for a language model of state-space (Mamba-2) mixers,
attention with no position, and shared and routed relu^2 experts balanced
by a selection bias: one chip's share.

The equations, in straightforward ``jax.numpy``, float32, ``highest`` matmul
precision, ``jax.grad``: python loops over layers, key-value head groups and
experts, no kernel, no sort, no grouped product (an expert is applied to
every token and weighed by the choice, 0 where it was not chosen).  **The
state-space recurrence runs token by token**, as written below, a
``lax.scan`` over the positions; it shares nothing with the program's
chunked form.  It reads the layer list from the configuration's JSON file
and imports nothing of ``znicz_tpu``.  The arithmetic modes, the norm,
AdamW and the leaves' norms are the looped reference's, the blocked scores,
the blocked head and the choice's bookkeeping the routed reference's: one
copy of each equation.  Weights come from ``numpy.random.RandomState``
seeded the way the published harness seeds its stream, leaf by leaf in the
order of the configuration; an expert layer draws one number from that
stream, every expert it holds from a stream of its own named by that number
and the expert's index, and the shared expert from the stream named by that
number and the count of ALL the experts.

The departures from "no recomputation" and "whole matrices" are the routed
reference's (residual entries, blocks of scores and blocks of logits stand
under ``jax.checkpoint`` and run one after another) and one of its own: the
scan runs in blocks of :data:`SCAN_BLOCK` positions, each under
``jax.checkpoint``, so that its backward keeps a state a block and not one
a position.

    RMS(x; g) = g * x / sqrt(mean(x^2) + eps)
    x = E[ids]
    for layer l of the pattern, m = RMS(x; g_l), x += f(m):
      M (ssd): [z | xBC | dt] = m W_in      (z: H*P, xBC: H*P + 2*G*N, dt: H)
        xBC_t = silu(b_c + sum_{i<K} w_c[i] xBC_{t-K+1+i} [same document])
        [x | B | C] = xBC; x (S, H, P), B, C (S, G, N), head h reads group
        h // (H / G)
        dt = softplus(dt + dt_bias); A = -exp(A_log)
        r_t = 1 where position t starts a document
        S_t = (1 - r_t) exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,   S_{-1} = 0
        y_t = S_t C_t + D x_t
        y = RMS_G(y * silu(z); g_n): over each of G groups of H*P/G
        f(m) = y W_out
      *: attention, H_q heads on H_kv, no position, q k^T / sqrt(hd),
        masked to j <= i inside one document; f(m) = softmax(.) v W_o
      E: s = sigmoid(m W_r) (float32); S = top_k of s + b (ties to the
        lower index); w_e = scale * s_e / (sum_{e' in S} s_e' + 1e-20)
        f(m) = relu(m U_s)^2 D_s + sum_{e in S, held here} w_e relu(m U_e)^2
        D_e
    h = RMS(x; g_f); z = h W^T
    loss = mean over graded positions of CE(z, label)
    AdamW with bias correction, decoupled decay on the matrices (W_in,
    w_c, W_out, attention's, the router's, the experts', the embedding,
    the head), none on the gains, b_c, dt_bias, A_log and D; not on b.
    After the step, with c_e the tokens the step sent to expert e:
        d_e = rate * sign(mean(c) - c_e); b += d - mean(d)

Two ways to route, as in the routed reference: **free** (``S`` the
reference's own) and **forced** (``route (entries, S, top_k)`` handed in;
the weights are of the reference's own scores at the chosen experts, and
``flipped``, ``margin`` and ``tilt`` say where and how its own choice
differs).

Departures from the published model, each where the configuration says so
under ``assumed``: attention applies no position encoding (NemotronH's
attention has none; the configuration's ``rope_theta`` and
``partial_rotary_factor`` are carried and unused); the inner width is
``mamba_num_heads * mamba_head_dim`` (``expand`` is not read); the gate
comes before the group norm (``norm_before_gate`` false).

``mode`` selects the arithmetic of every matrix product but the router's,
and of the scan's ``x``, ``B`` and ``C`` (``f32`` the reference proper;
``bf16`` / ``fp8`` operands rounded, float32 accumulation and state: the
witness and the control).  Planted faults: ``reset_left_out`` (the state
is carried across a document start), ``conv_across_documents`` (the conv
reads the previous document's last positions), ``norm_whole_width`` (one
RMS over all ``H * P`` in place of ``G`` groups), ``d_skip_left_out`` (no
``D x``), ``relu_not_squared`` (experts of ``relu``, not ``relu^2``),
``shared_left_out`` (no token passes the shared expert),
``bias_left_out_of_choice`` (the choice is of ``s`` alone),
``centring_left_out`` (the rule's moves keep their mean).  A fault is
named at trace time, or, in the programs :func:`make_row` builds for the
readings (every mode but the reference proper), chosen at run time by its
index in :data:`FAULTS`, so that all the readings of one mode share one
compiled program (a compile at the cell's size is over a minute).
"""

import json

import numpy

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.looped_lm import (  # noqa: F401
    ADAM_DEFAULTS, _mm, _quant, _rms, adamw, difference, leaf_norms)
from benchmarks.reference.routed_lm import (
    _attention, _fwd, _head, _members, held, own_choice)

KINDS = ("embedding", "rmsnorm", "ssd", "attention", "router", "moe",
         "lm_head")
#: a kind's cost module where it is not named after the kind
COST_KIND = {"attention": "causal_attention", "ssd": "ssd_mixer",
             "moe": "relu2_shared_moe", "lm_head": "ce_head"}
FAULTS = ("reset_left_out", "conv_across_documents", "norm_whole_width",
          "d_skip_left_out", "relu_not_squared", "shared_left_out",
          "bias_left_out_of_choice", "centring_left_out")
#: the leaf no gradient moves
BIAS = "sb"
#: positions of the scan under one ``jax.checkpoint``
SCAN_BLOCK = 256


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


def ssd_widths(a):
    """(heads, head_dim, state, groups, conv, chunk) of an ``ssd`` layer."""
    return tuple(int(a[k]) for k in ("heads", "head_dim", "state", "groups",
                                     "conv", "chunk"))


def leaf_table(layers):
    """[{leaf: (shape, filling, value, decays)}] for every leaf layer,
    leaves in the order they are drawn: filling ``normal`` (stddev
    ``value``), ``constant``, ``uniform`` (over +-``value``),
    ``log_uniform`` (the log of U(``value``)) or ``dt_bias`` (the inverse
    softplus of ``max(exp U(log lo, log hi), floor)``); ``decays`` None
    marks the selection bias, which the optimizer does not know."""
    flat, _ = flatten(layers)
    out, dim = [], None
    for layer in flat:
        a, tpe = _fwd(layer), layer["type"]
        s = float(a.get("weights_stddev", 0.02))
        if tpe == "embedding":
            dim = int(a["dim"])
            t = {"w": ((int(a["vocab"]), dim), "normal", s, True)}
        elif tpe == "rmsnorm":
            t = {"g": ((dim,), "constant", 1.0, False)}
        elif tpe == "ssd":
            h, hd, n, g, k, _ = ssd_widths(a)
            inner, conv = h * hd, h * hd + 2 * g * n
            bound = 1.0 / float(numpy.sqrt(k))
            steps = (float(a.get("dt_min", 0.001)),
                     float(a.get("dt_max", 0.1)),
                     float(a.get("dt_floor", 1e-4)))
            t = {"w_in": ((dim, inner + conv + h), "normal", s, True),
                 "w_conv": ((k, conv), "uniform", bound, True),
                 "b_conv": ((conv,), "uniform", bound, False),
                 "dt_bias": ((h,), "dt_bias", steps, False),
                 "a_log": ((h,), "log_uniform", (1.0, 16.0), False),
                 "d_skip": ((h,), "constant", 1.0, False),
                 "g_norm": ((inner,), "constant", 1.0, False),
                 "w_out": ((inner, dim), "normal",
                           float(a.get("out_stddev", s)), True)}
        elif tpe == "attention":
            hd, h, kv = (int(a[k]) for k in ("head_dim", "heads",
                                             "kv_heads"))
            t = {"wq": ((dim, h * hd), "normal", s, True),
                 "wk": ((dim, kv * hd), "normal", s, True),
                 "wv": ((dim, kv * hd), "normal", s, True),
                 "wo": ((h * hd, dim), "normal", s, True)}
        elif tpe == "router":
            t = {"wr": ((dim, int(a["experts"])), "normal", s, True)}
        elif tpe == "moe":
            if a.get("gated", True):
                raise ValueError("this reference's experts are not gated")
            f, count = int(a["hidden"]), held(a)[1]
            t = {"wu": ((count, dim, f), "normal", s, True),
                 "wd": ((count, f, dim), "normal", s, True)}
            if a.get("shared_hidden"):
                fs = int(a["shared_hidden"])
                t.update(su=((dim, fs), "normal", s, True),
                         sd=((fs, dim), "normal", s, True))
            if a.get("balance_rate") is not None:
                t[BIAS] = ((int(a["experts"]),), "constant", 0.0, None)
        else:
            t = {"g": ((dim,), "constant", 1.0, False),
                 "w": ((int(a["vocab"]), dim), "normal", s, True)}
        out.append(t)
    return out


def _planted(fault, name):
    """Whether the fault ``name`` is planted: a python bool where ``fault``
    is a name or None, a traced one where it is an index into
    :data:`FAULTS` chosen at run time (-1: none)."""
    if fault is None or isinstance(fault, str):
        return fault == name
    return fault == FAULTS.index(name)


def _either(flag, yes, no):
    """``yes()`` where ``flag``, else ``no()``: a python bool picks one at
    trace time, a traced one selects between both at run time."""
    if isinstance(flag, bool):
        return yes() if flag else no()
    return jnp.where(flag, yes(), no())


def _drawn(rs, shape, filling, value):
    """One leaf from the stream ``rs``, float32."""
    if filling == "normal":
        return rs.normal(0, value, size=shape).astype(numpy.float32)
    if filling == "constant":
        return numpy.full(shape, value, numpy.float32)
    if filling == "uniform":
        return rs.uniform(-value, value, size=shape).astype(numpy.float32)
    if filling == "log_uniform":
        return numpy.log(rs.uniform(*value, size=shape).astype(
            numpy.float32))
    low, high, floor = value
    step = numpy.maximum(numpy.exp(rs.uniform(
        numpy.log(low), numpy.log(high), size=shape).astype(numpy.float32)),
        numpy.float32(floor))
    return step + numpy.log(-numpy.expm1(-step))


def _key(layers):
    return json.dumps(layers, sort_keys=True, default=repr)


#: the last draw, (key, seed) -> its leaves (read-only): a reading's steps
#: draw the initial parameters twice, and a calibration takes ten readings
_DRAWN = {}


def init_params(layers, seed):
    """One legacy numpy stream seeded with ``[seed]`` as uint32 words; an
    expert layer takes one integer of it, draws expert ``e`` whole (its
    two matrices in the table's order) from the stream ``[that integer,
    e]`` and the shared expert from ``[that integer, experts]``.  The last
    draw is kept; every call returns lists and dicts of its own."""
    key = (_key(layers), int(seed))
    if key not in _DRAWN:
        _DRAWN.clear()
        _DRAWN[key] = _draw(layers, seed)
    return [dict(p) for p in _DRAWN[key]]


def _draw(layers, seed):
    rs = numpy.random.RandomState(numpy.asarray([seed], dtype=numpy.uint32))
    flat, _ = flatten(layers)
    params = []
    for layer, table in zip(flat, leaf_table(layers)):
        if layer["type"] != "moe":
            params.append({name: _drawn(rs, *leaf[:3])
                           for name, leaf in table.items()})
            continue
        a = _fwd(layer)
        base = int(rs.randint(0, 2 ** 31 - 1, size=1)[0])
        first, count = held(a)
        p = {name: numpy.full(shape, value if filling == "constant" else 0.0,
                              numpy.float32)
             for name, (shape, filling, value, _) in table.items()}
        for j in range(count):
            own = numpy.random.RandomState([base, first + j])
            for name in ("wu", "wd"):
                shape, _, std = table[name][:3]
                p[name][j] = own.normal(0, std, size=shape[1:])
        own = numpy.random.RandomState([base, int(a["experts"])])
        for name in ("su", "sd"):
            if name in table:
                shape, _, std = table[name][:3]
                p[name][...] = own.normal(0, std, size=shape)
        params.append(p)
    for p in params:
        for a in p.values():
            a.flags.writeable = False
    return params


def hypers(layers):
    """[{leaf: {lr, wd, adam_beta1, adam_beta2, adam_eps}}]: what the
    configuration states for every leaf AdamW steps (a leaf that takes no
    decay takes the layer's bias learning rate and decay; the selection
    bias has none)."""
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
    the attended (query, key) pairs of a row (``pairs_per_row(None)``,
    causal over the whole row where None); for a mixer its heads, state,
    groups, conv and chunk; for an expert layer the pairs (token, expert)
    held here at an even load."""
    flat, _ = flatten(layers)
    out = []
    for node, (layer, table) in enumerate(zip(flat, leaf_table(layers))):
        a = _fwd(layer)
        ent = {"kind": COST_KIND.get(layer["type"], layer["type"]),
               "spec": node, "seq": int(seq),
               "leaves": {k: v[0] for k, v in table.items()},
               "update": True}
        if layer["type"] == "attention":
            pairs = float(seq * (seq + 1) / 2) if pairs_per_row is None \
                else float(pairs_per_row(None))
            ent.update(heads=int(a["heads"]), kv_heads=int(a["kv_heads"]),
                       head_dim=int(a["head_dim"]), window=None,
                       pairs=pairs)
        elif layer["type"] == "ssd":
            ent.update(zip(("heads", "head_dim", "state", "groups", "conv",
                            "chunk"), ssd_widths(a)))
        elif layer["type"] == "moe":
            n_exp, k = int(a["experts"]), int(a["top_k"])
            ent.update(experts=n_exp, top_k=k, held=held(a)[1],
                       hidden=int(a["hidden"]),
                       pairs=float(seq) * k * held(a)[1] / n_exp)
        out.append(ent)
    return out


# -- the model, one row at a time ---------------------------------------------

def _ssd(p, x, seg, a, mode, fault):
    """Mamba-2's mixer over one row ``x (S, d)``, the recurrence token by
    token."""
    s = x.shape[0]
    h, hd, n, g, k, _ = ssd_widths(a)
    inner, width = h * hd, h * hd + 2 * g * n
    eps = float(a.get("eps", 1e-5))
    zxd = _mm(x, p["w_in"], mode)
    starts = jnp.concatenate([jnp.ones(1, bool), seg[1:] != seg[:-1]])
    doc = jnp.cumsum(starts)
    xbc = zxd[:, inner:inner + width]
    conv = p["b_conv"] + p["w_conv"][k - 1] * xbc
    for lag in range(1, k):
        back = jnp.concatenate([jnp.zeros((lag, width)), xbc[:s - lag]])
        same = jnp.concatenate([jnp.zeros(lag, bool),
                                doc[lag:] == doc[:s - lag]])
        back = _either(_planted(fault, "conv_across_documents"),
                       lambda: back,
                       lambda: jnp.where(same[:, None], back, 0.0))
        conv = conv + p["w_conv"][k - 1 - lag] * back
    xbc = jax.nn.silu(conv)
    xs = _quant(xbc[:, :inner].reshape(s, h, hd), mode)
    bm = _quant(jnp.repeat(xbc[:, inner:inner + g * n].reshape(s, g, n),
                           h // g, axis=1), mode)
    cm = _quant(jnp.repeat(xbc[:, inner + g * n:].reshape(s, g, n),
                           h // g, axis=1), mode)
    dt = jax.nn.softplus(zxd[:, inner + width:] + p["dt_bias"])
    decay = jnp.exp(dt * -jnp.exp(p["a_log"]))
    keep = _either(_planted(fault, "reset_left_out"), lambda: jnp.ones(s),
                   lambda: 1.0 - starts.astype(jnp.float32))

    def step(state, at):
        x_t, b_t, c_t, dt_t, decay_t, keep_t = at
        state = keep_t * decay_t[:, None, None] * state \
            + dt_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    block = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s
    _, ys = lax.scan(
        jax.checkpoint(lambda state, blk: lax.scan(step, state, blk)),
        jnp.zeros((h, hd, n)),
        jax.tree.map(lambda v: v.reshape((s // block, block) + v.shape[1:]),
                     (xs, bm, cm, dt, decay, keep)))
    y = ys.reshape(s, h, hd)
    y = _either(_planted(fault, "d_skip_left_out"), lambda: y,
                lambda: y + p["d_skip"][:, None] * xs)
    y = y.reshape(s, inner) * jax.nn.silu(zxd[:, :inner])

    def normed(groups):
        return _rms(y.reshape(s, groups, inner // groups),
                    p["g_norm"].reshape(groups, inner // groups),
                    eps).reshape(s, inner)

    y = _either(_planted(fault, "norm_whole_width"), lambda: normed(1),
                lambda: normed(g))
    return _mm(y.reshape(s, inner), p["w_out"], mode)


def _expert(x, up, down, squared, mode):
    """``relu(x U)^2 D`` (``relu(x U) D`` where not ``squared``, a python
    or a traced bool)."""
    hidden = jax.nn.relu(_mm(x, up, mode))
    return _mm(_either(squared, lambda: hidden * hidden, lambda: hidden),
               down, mode)


def _moe(p, m, r, a, forced, mode, fault):
    """(the shared expert's output plus the held experts' weighed sum
    (S, d), report) of one expert layer: ``r (S, experts)`` the router's
    logits, ``forced (S, k)`` a choice to take in place of the reference's
    own, or None."""
    n_exp, k = int(a["experts"]), int(a["top_k"])
    first, count = held(a)
    if a.get("score") != "sigmoid" or a.get("activation") != "relu2":
        raise ValueError("this reference routes by sigmoid scores to relu2 "
                         "experts")
    squared = _either(_planted(fault, "relu_not_squared"), lambda: False,
                      lambda: True)
    s = jax.nn.sigmoid(r)
    b = lax.stop_gradient(p[BIAS]) if BIAS in p else jnp.zeros(n_exp)
    pick = _either(_planted(fault, "bias_left_out_of_choice"), lambda: s,
                   lambda: s + b[None, :])
    own = own_choice(pick, k)
    chosen = own if forced is None else forced.astype(jnp.int32)
    taken, mine = _members(chosen, n_exp), _members(own, n_exp)
    w = jnp.where(taken, s, 0.0)
    w = w / (w.sum(axis=1, keepdims=True) + 1e-20) \
        * float(a.get("route_scale", 1.0))
    out = jnp.zeros_like(m)
    for j in range(count):
        out = out + w[:, first + j, None] * _expert(
            m, p["wu"][j], p["wd"][j], squared, mode)
    if "su" in p:
        out = _either(_planted(fault, "shared_left_out"), lambda: out,
                      lambda: out + _expert(m, p["su"], p["sd"], squared,
                                            mode))
    # what the reference would have chosen and the choice did not take,
    # against what the choice took in its place
    lost = jnp.max(jnp.where(mine & ~taken, pick, -jnp.inf), axis=1)
    got = jnp.min(jnp.where(taken & ~mine, pick, jnp.inf), axis=1)
    flipped = (mine != taken).any(axis=1)
    sound = (taken.sum(axis=1) == k) & (chosen >= 0).all(axis=1) \
        & (chosen < n_exp).all(axis=1)
    margin = jnp.where(sound, jnp.where(flipped, (lost - got) / jnp.std(s),
                                        0.0), jnp.inf)
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
            return p["w"][x], None
        if tpe == "rmsnorm":
            return _rms(x, p["g"], float(a.get("eps", 1e-6))), None
        if tpe == "ssd":
            return _ssd(p, x, seg, a, mode, fault), None
        if tpe == "attention":
            if a.get("rope", True) or a.get("window"):
                raise ValueError("this reference's attention has no "
                                 "position and no window")
            return _attention(p, x, seg, a, mode, None), None
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
    as the balanced reference's (``reference/afmoe_lm.py``).  The reference
    proper (``f32``, no fault) names no fault at trace time; every other
    mode is one program with all the faults in it, the one planted chosen
    by its index at run time.  Programs are kept by configuration and
    mode, so that the readings of one mode compile once."""
    if fault not in (None,) + FAULTS:
        raise ValueError(fault)
    planted = mode != "f32" or fault is not None
    key = (_key(layers), mode, planted)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = _program(layers, mode)
    program = _PROGRAMS[key]
    which = jnp.int32(FAULTS.index(fault) if fault else -1) if planted \
        else None
    if forced:
        return lambda params, total, ids, seg, labels, sample, route: \
            program(params, total, ids, seg, labels, sample, route, which)
    return lambda params, total, ids, seg, labels, sample: program(
        params, total, ids, seg, labels, sample, None, which)


#: (configuration, mode, planted) -> the jitted row
_PROGRAMS = {}


def _program(layers, mode):
    """The jitted row of :func:`make_row`: its ``fault`` None, or an index
    into :data:`FAULTS` (-1: none) traced; ``route`` None or the choice."""

    def loss_sum(params, ids, seg, labels, sample, route, fault):
        ce, pred, z, reports = forward_row(params, ids, seg, labels, sample,
                                           layers, route, mode, fault)
        valid = labels >= 0
        total = jnp.sum(jnp.where(valid, ce, 0.0))
        aux = dict(reports, loss_sum=total, graded=valid.sum(),
                   errors=(valid & (pred != labels)).sum(), logits=z)
        return total, aux

    def row(params, total, ids, seg, labels, sample, route, fault):
        with jax.default_matmul_precision("highest"):
            (_, aux), grads = jax.value_and_grad(loss_sum, has_aux=True)(
                params, ids, seg, labels, sample, route, fault)
        return jax.tree.map(jnp.add, total, grads), aux

    return jax.jit(row, donate_argnums=(1,))
