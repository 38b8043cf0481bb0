"""Family ``balanced_token_rows``: ``routed_token_rows``' job (packed rows,
a routed language model's plain next-token cross-entropy with AdamW,
compared **under one choice of experts**) for a model whose expert layers
carry a **selection bias that the load moves**: a leaf of the parameters
with no gradient, no optimizer state and no hyperparameters.

Data, loader, packing, the captured entry, the counts of the first epoch
and the unit of work are ``token_rows``' and ``routed_token_rows``' own, by
import.  What differs, and why files alone could not give it:

* the capture's copies: the bias has no optimizer state, so the state's
  copy is cut to the leaves that have one before ``token_rows`` reads
  their first moment, and the parameters' copies to the same leaves before
  ``lib/job.py`` takes their norms; the biases after the checked steps are
  kept whole (``program["bias"] (entries, experts)``);
* the reference's steps: after AdamW the reference moves the biases by the
  step's load (``reference/<name>.py:balance``), and they ride in its
  parameters;
* the comparison: beside ``routed_token_rows``' numbers, the biases after
  the checked steps against the reference's under the same choice
  (``bias_gap``, limit: float32 rounding), the routing weight every expert
  took in every step against the reference's (``weight_share_gap``: the
  program hands ``moe_weight (K, entries, experts)`` back beside the load),
  and which way the bias leans across the pairs whose choice is not the
  reference's own (``choice_bias_tilt``: over those pairs, the mean sign
  of the bias of the expert the reference would have taken less that of
  the expert taken in its place, in magnitude).  The last two are what
  tell the bias's two faults from rounding at the checked steps, where no
  bias is past 0.005 and rounding moves a score as far: rounding flips
  pairs both ways alike, whichever expert the bias favours (the tilt reads
  nought to the root of the flips), where a choice made without the bias
  loses exactly the experts that the bias favours; a bias that leaks into
  the weights shifts an expert's weight for ALL its tokens one way, where
  rounding shifts them both ways.  (The flip share and the flipped
  margins, which ``routed_token_rows`` grades, do not tell the first fault
  from the program: 0.090 and 0.038 against 0.075 and 0.031, my chip runs,
  PR 35);
* the readings: the mechanism's own faults (``READINGS``).
"""

import time

import numpy

from benchmarks import families
from benchmarks.families import routed_token_rows as routed
from benchmarks.families import token_rows
from benchmarks.lib import job

# -- data, capture: token_rows' and routed_token_rows' own --------------------

make_data = routed.make_data
loader = routed.loader
ENTRY = routed.ENTRY
STATE_LEAVES = routed.STATE_LEAVES
first_epoch = routed.first_epoch
release = routed.release
rows_trained = routed.rows_trained
row_tokens = routed.row_tokens
plan = routed.plan
attended_pairs_per_row = routed.attended_pairs_per_row


def _stateful(layer_state):
    """A layer's leaves that have optimizer state."""
    return {name: st for name, st in layer_state.items() if st}


def feed(trainer, idx_s, batch_sizes, hypers_s):
    """``routed_token_rows``' feed, once the capture's copy of the
    optimizer state is cut to the leaves that have any (``token_rows``
    reads every leaf's first moment as it moves the copy to the host)."""
    held = getattr(getattr(trainer.net, ENTRY), "__self__", None)
    if getattr(held, "state1", None) is not None:
        held.state1 = [_stateful(layer) for layer in held.state1]
    return routed.feed(trainer, idx_s, batch_sizes, hypers_s)


def keep(stats, rec):
    out = routed.keep(stats, rec)
    out["moe_weight"] = stats["moe_weight"]
    return out


def fetch(st):
    return dict(routed.fetch(st), weight=numpy.asarray(
        st["moe_weight"], numpy.float64))


def leaf_numbers(cfg, mix, p0, state1, params_end):
    """``lib/job.py``'s norms over the leaves the optimizer steps, and the
    selection biases as the checked steps left them."""
    bias = families.reference(cfg).BIAS
    state1 = [_stateful(layer) for layer in state1]
    cut = [[{name: a for name, a in layer.items() if name in st}
            for layer, st in zip(tree, state1)] for tree in (p0, params_end)]
    out = job.leaf_norms(cut[0], state1, cut[1], [None] * len(p0),
                         STATE_LEAVES)
    out["bias"] = numpy.stack([numpy.asarray(layer[bias], numpy.float32)
                               for layer in params_end if bias in layer])
    return out


# -- comparison ---------------------------------------------------------------

GRADED = routed.GRADED + ("choice_bias_tilt", "weight_share_gap",
                          "bias_gap")

#: (reading, mode, fault, least chips): the bf16 witness, the fp8 control,
#: and faults planted in the bf16 reference put in the program's place
READINGS = (("bf16", "bf16", None, 1), ("fp8", "fp8", None, 1)) + tuple(
    (fault, "bf16", fault, 1) for fault in (
        "gate_left_out", "qk_norm_left_out", "shared_left_out",
        "bias_left_out_of_choice", "bias_in_weights", "rope_on_full",
        "centring_left_out"))

#: the bias's limit: float32 rounding of the checked steps' additions.  A
#: bias is a sum of moves of ``rate`` less their mean (under twice the rate
#: each), so after the four checked steps none is past 8 * 0.001 < 2^-6.9:
#: the largest read is 0.0052, and an ulp of it is 2^-31 = 4.7e-10; the
#: two sides may round an addition, or sum the mean of the 128 moves, in
#: another order, so they may part by an ulp or two and by no more than a
#: few: three ulp are 1.4e-9.  Every fault of the arithmetic
#: reads 0 here (under one choice the load, and so the rule, are the
#: reference's); the rule's own fault, ``centring_left_out``, is what reads
#: over it.


def _steps(ref, cfg, mix, run, mode, fault, routes, log):
    """The reference's own steps over the captured feed, as
    ``routed_token_rows._steps``, with the biases moved after every step
    (by the rule as ``fault`` leaves it);
    ``routes``, one ``(K, entries, tokens, top_k)`` a window, forces the
    choice."""
    import jax
    import jax.numpy as jnp
    layers = cfg["layers"]
    row_fn = ref.make_row(layers, mode, fault, forced=routes is not None)
    hyper = ref.hypers(layers)
    entries = ref.moe_entries(layers)
    made = run["data"]
    seq = made["ids"].shape[1]
    batch = run["batch"]
    n_sample = int(mix["logit_samples"])
    out = {"loss": [], "windows": [], "grad1": None, "m1": None,
           "flips": 0, "pairs": 0, "margins": [numpy.zeros(0)],
           "tilt": 0.0}
    step_no = 0
    tag = "%s%s%s" % (mode, " " + fault if fault else "",
                      " forced" if routes is not None else "")

    def stepped(tree):
        """The leaves AdamW steps: every one but the bias."""
        return [{k: a for k, a in p.items() if k != ref.BIAS} for p in tree]

    with jax.default_device(jax.devices()[0]):
        init = ref.init_params(layers, run["weight_seed"])
        params = jax.tree.map(jnp.asarray, init)
        biases = numpy.stack([init[node][ref.BIAS] for node in entries])
        m = [{k: numpy.zeros_like(a) for k, a in p.items()}
             for p in stepped(init)]
        v = [{k: numpy.zeros_like(a) for k, a in p.items()}
             for p in stepped(init)]
        del init
        for w, win in enumerate(run["windows"]):
            sample = win["sample"] if "sample" in win else (
                token_rows.sample_positions(win["idx"], seq, n_sample)
                if w == len(run["windows"]) - 1 else None)
            per_row = max(n_sample // batch, 1)
            counts = numpy.zeros(3, numpy.int64)
            loads, weights, taken, logits = [], [], [], None
            for k, (idx, size) in enumerate(zip(win["idx"], win["sizes"])):
                t0 = time.perf_counter()
                last = sample is not None and k == len(win["sizes"]) - 1
                rows = [int(r) for r in idx[:size] if r >= 0]
                graded = int(sum((made["labels"][r] >= 0).sum()
                                 for r in rows))
                total = jax.tree.map(jnp.zeros_like, params)
                loss_sum, errors = 0.0, 0
                load, weight, route = 0, 0.0, []
                for slot, r in enumerate(rows):
                    pos = (sample[slot * per_row:(slot + 1) * per_row]
                           - slot * seq) if last \
                        else numpy.zeros(per_row, numpy.int32)
                    args = [params, total, jnp.asarray(made["ids"][r]),
                            jnp.asarray(made["segments"][r]),
                            jnp.asarray(made["labels"][r]),
                            jnp.asarray(pos)]
                    if routes is not None:
                        args.append(jnp.asarray(
                            routes[w][k][:, slot * seq:(slot + 1) * seq]))
                    total, aux = row_fn(*args)
                    loss_sum += float(aux["loss_sum"])
                    errors += int(aux["errors"])
                    load = load + numpy.asarray(aux["load"], numpy.int64)
                    weight = weight + numpy.asarray(aux["weight"],
                                                    numpy.float64)
                    route.append(numpy.asarray(aux["route"]))
                    flipped = numpy.asarray(aux["flipped"])
                    out["flips"] += int(flipped.sum())
                    out["pairs"] += int(flipped.size)
                    margin = numpy.asarray(aux["margin"], numpy.float64)
                    out["tilt"] += float(numpy.asarray(aux["tilt"]).sum())
                    out["margins"].append(
                        margin[flipped | ~numpy.isfinite(margin)])
                    if last:
                        z = aux["logits"]
                        if logits is None:
                            logits = numpy.empty(
                                (1, len(rows) * per_row, z.shape[1]),
                                numpy.float32)
                        at = slice(slot * per_row, (slot + 1) * per_row)
                        logits[0, at] = jax.device_get(z)
                        del z
                    del aux
                out["loss"].append(loss_sum / max(graded, 1))
                counts += (errors, graded, len(rows))
                loads.append(load)
                weights.append(weight)
                taken.append(numpy.concatenate(route, axis=1))
                if out["grad1"] is None:
                    out["grad1"] = {k2: v2 / max(graded, 1) for k2, v2
                                    in ref.leaf_norms(stepped(total)).items()}
                step_no += 1
                biases = ref.balance(biases, load, layers, fault)
                for i, leaves in enumerate(stepped(params)):
                    params[i], m_i, v_i = ref.adamw(
                        leaves, jax.tree.map(jnp.asarray, m[i]),
                        jax.tree.map(jnp.asarray, v[i]),
                        {name: total[i][name] for name in leaves},
                        numpy.float32(max(graded, 1)),
                        numpy.float32(step_no), hyper[i])
                    m[i], v[i] = jax.device_get((m_i, v_i))
                    del m_i, v_i
                for n, node in enumerate(entries):
                    params[node][ref.BIAS] = jnp.asarray(biases[n])
                del total
                if log is not None:
                    log("reference %s step %d: %.1f s; host %.1f GB now, "
                        "%.1f GB at most"
                        % ((tag, step_no, time.perf_counter() - t0)
                           + token_rows._host_gb()))
            out["windows"].append({"n_err": counts, "logits": logits,
                                   "load": numpy.stack(loads),
                                   "weight": numpy.stack(weights),
                                   "route": numpy.stack(taken)})
            if out["m1"] is None:
                out["m1"] = ref.leaf_norms(m)
        del m, v
        out["dparam"] = ref.leaf_norms(ref.difference(
            stepped(params), jax.tree.map(jnp.asarray, stepped(
                ref.init_params(layers, run["weight_seed"])))))
    out["bias"] = biases
    out["margins"] = numpy.concatenate(out["margins"])
    return out


def follow(cfg, mix, run, mode="f32", fault=None, chips=1, log=None):
    """As ``routed_token_rows.follow``: ``f32`` with no fault is the
    reference proper, forced to the choice the run's windows hold; ``free``
    routes by itself; any other mode or a fault is a reading, which routes
    by itself and brings the float32 reference forced to ITS choice as
    ``forced_ref``."""
    ref = families.reference(cfg)
    if mode == "f32" and fault is None:
        routes = [win["stats"]["route"] for win in run["windows"]
                  if "stats" in win and win["stats"].get("route") is not None]
        return _steps(ref, cfg, mix, run, "f32", None,
                      routes if len(routes) == len(run["windows"]) else None,
                      log)
    if mode == "free":
        return _steps(ref, cfg, mix, run, "f32", fault, None, log)
    out = _steps(ref, cfg, mix, run, mode, fault, None, log)
    out["forced_ref"] = _steps(ref, cfg, mix, run, "f32", None,
                               [win["route"] for win in out["windows"]], log)
    return out


def graded(run, refout, limits):
    out, where = routed.graded(run, refout, limits)
    refout = run.get("forced_ref") or refout
    out.append(("choice_bias_tilt",
                abs(refout["tilt"]) / max(refout["flips"], 1),
                limits["choice_bias_tilt"]))
    # an expert layer's weights of a step add up to the same on both sides
    # (``route_scale`` a token); how far they lie with other experts
    gap = max(float(numpy.abs(got - want).sum(axis=-1).max()
                    / want.sum(axis=-1).min())
              for got, want in (
                  (win["stats"]["weight"], rw["weight"])
                  for win, rw in zip(run["windows"], refout["windows"])))
    out.append(("weight_share_gap", gap, limits["weight_share_gap"]))
    out.append(("bias_gap", float(numpy.abs(
        numpy.asarray(run["program"]["bias"], numpy.float64)
        - refout["bias"]).max()), limits["bias_gap"]))
    where["bias_abs_max"] = float(numpy.abs(refout["bias"]).max())
    return out, where


def numbers(run, refout, cfg, limits, net):
    """This family's graded numbers, then ``routed_token_rows``' exact
    counts."""
    out, _ = routed.numbers(run, refout, cfg, limits, net)
    mine, where = graded(run, refout, limits)
    return mine + out[len(routed.GRADED):], where


def in_place(run, refout):
    out = routed.in_place(run, refout)
    for win, rw in zip(out["windows"], refout["windows"]):
        win["stats"]["weight"] = rw["weight"]
    out["program"]["bias"] = refout["bias"]
    return out
