"""Family ``mtp_token_rows``: ``balanced_token_rows``' job (packed rows, a
routed language model whose expert layers carry a selection bias that the
load moves, AdamW, compared **under one choice of experts**) for a model
with a **second head that predicts the id two positions ahead** (a
multi-token prediction module: the main head's normed state and the
embedding of the next id, joined, one more decoder layer, and a head with
the main head's weight), trained on the main head's mean loss plus a
weight times the second's.

Data, loader, packing, the captured entry, the optimizer's leaves, the
capture's copies and the unit of work are the balanced family's own, by
import.  What differs, and why files alone could not give it:

* the capture: beside the balanced family's, every step's mean loss of the
  second head (``mtp_loss``), the window's ``[errors, graded]`` of it
  (``mtp_n_err``) and, of the last checked step, its logits at the sampled
  positions (``mtp_logits_sample``, made from the weight the main head's
  logits are made from);
* the reference's steps: a row's gradient is of both heads' loss sums,
  each weighed by one over its own graded count of the minibatch (the
  second's times the module's weight), so AdamW takes it whole;
* the comparison: beside the balanced family's numbers (whose
  ``loss_worst_step`` is of the objective, both heads), the second head's
  loss at every step (``mtp_loss_worst_step``) and its logits at the
  sampled positions (``mtp_logit_rel_diff``), and exactly, its graded
  tokens a window and in the first epoch, train and validation, against
  the count the data gives;
* the readings: the mechanisms' own faults (``READINGS``).
"""

import time

import numpy

from benchmarks import families
from benchmarks.families import balanced_token_rows as balanced
from benchmarks.families import routed_token_rows as routed
from benchmarks.families import token_rows

# -- data, capture: the balanced family's own ---------------------------------

make_data = balanced.make_data
loader = balanced.loader
ENTRY = balanced.ENTRY
STATE_LEAVES = balanced.STATE_LEAVES
leaf_numbers = balanced.leaf_numbers
release = balanced.release
rows_trained = balanced.rows_trained
row_tokens = balanced.row_tokens
plan = balanced.plan
feed = balanced.feed


def first_epoch(decision):
    return dict(balanced.first_epoch(decision),
                mtp_tokens=list(decision.epoch_mtp_graded))


def keep(stats, rec):
    import jax
    out = balanced.keep(stats, rec)
    out.update(mtp_loss=stats["mtp_loss"], mtp_n_err=stats["mtp_n_err"],
               mtp_logits=None)
    if rec["sample"] is not None:
        out["mtp_logits"] = numpy.asarray(jax.device_get(
            stats.pop("mtp_logits_sample")))
    return out


def fetch(st):
    return dict(balanced.fetch(st),
                mtp_loss=numpy.asarray(st["mtp_loss"],
                                       numpy.float64).reshape(-1),
                mtp_n_err=numpy.asarray(st["mtp_n_err"]).reshape(2),
                mtp_logits=st["mtp_logits"])


def mtp_labels(labels, shift):
    """The second head's labels of rows ``labels (n, S)``: position i is
    graded where the next-id labels of i .. i + shift - 1 all are."""
    out = labels
    for _ in range(shift - 1):
        ahead = numpy.full_like(out, -1)
        ahead[:, :-1] = out[:, 1:]
        out = numpy.where(labels >= 0, ahead, -1)
    return out


# -- comparison ---------------------------------------------------------------

GRADED = balanced.GRADED + ("mtp_loss_worst_step", "mtp_logit_rel_diff")

#: (reading, mode, fault, least chips): the bf16 witness, the fp8 control,
#: and faults planted in the bf16 reference put in the program's place
READINGS = (("bf16", "bf16", None, 1), ("fp8", "fp8", None, 1)) + tuple(
    (fault, "bf16", fault, 1) for fault in (
        "rope_whole_head", "kv_norm_left_out", "mtp_shift_one",
        "mtp_head_apart", "mtp_weight_one"))


def _steps(ref, cfg, mix, run, mode, fault, routes, log):
    """The reference's own steps over the captured feed, as
    ``balanced_token_rows._steps``, each row weighed by both heads'
    graded counts of its minibatch; ``routes``, one ``(K, entries,
    tokens, top_k)`` a window, forces the choice."""
    import jax
    import jax.numpy as jnp
    layers = cfg["layers"]
    row_fn = ref.make_row(layers, mode, fault, forced=routes is not None)
    hyper = ref.hypers(layers)
    entries = ref.moe_entries(layers)
    _, _, weight, shift = ref.heads(layers)
    if fault == "mtp_weight_one":
        weight = 1.0
    made = run["data"]
    ahead = mtp_labels(made["labels"], 1 if fault == "mtp_shift_one"
                       else shift)
    seq = made["ids"].shape[1]
    batch = run["batch"]
    n_sample = int(mix["logit_samples"])
    out = {"loss": [], "mtp_loss": [], "windows": [], "grad1": None,
           "m1": None, "flips": 0, "pairs": 0, "margins": [numpy.zeros(0)],
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
            mtp_counts = numpy.zeros(2, numpy.int64)
            loads, weights, taken = [], [], []
            logits = mtp_logits = None
            for k, (idx, size) in enumerate(zip(win["idx"], win["sizes"])):
                t0 = time.perf_counter()
                last = sample is not None and k == len(win["sizes"]) - 1
                rows = [int(r) for r in idx[:size] if r >= 0]
                graded = int(sum((made["labels"][r] >= 0).sum()
                                 for r in rows))
                graded2 = int(sum((ahead[r] >= 0).sum() for r in rows))
                scale = jnp.asarray([1.0 / max(graded, 1),
                                     weight / max(graded2, 1)], jnp.float32)
                total = jax.tree.map(jnp.zeros_like, params)
                sums = numpy.zeros(2)
                errors = numpy.zeros(2, numpy.int64)
                load, weight_took, route = 0, 0.0, []
                for slot, r in enumerate(rows):
                    pos = (sample[slot * per_row:(slot + 1) * per_row]
                           - slot * seq) if last \
                        else numpy.zeros(per_row, numpy.int32)
                    args = [params, total, jnp.asarray(made["ids"][r]),
                            jnp.asarray(made["segments"][r]),
                            jnp.asarray(made["labels"][r]),
                            jnp.asarray(pos), scale]
                    if routes is not None:
                        args.append(jnp.asarray(
                            routes[w][k][:, slot * seq:(slot + 1) * seq]))
                    total, aux = row_fn(*args)
                    sums += (float(aux["loss_sum"]),
                             float(aux["mtp_loss_sum"]))
                    errors += (int(aux["errors"]), int(aux["mtp_errors"]))
                    load = load + numpy.asarray(aux["load"], numpy.int64)
                    weight_took = weight_took + numpy.asarray(
                        aux["weight"], numpy.float64)
                    route.append(numpy.asarray(aux["route"]))
                    flipped = numpy.asarray(aux["flipped"])
                    out["flips"] += int(flipped.sum())
                    out["pairs"] += int(flipped.size)
                    margin = numpy.asarray(aux["margin"], numpy.float64)
                    out["tilt"] += float(numpy.asarray(aux["tilt"]).sum())
                    out["margins"].append(
                        margin[flipped | ~numpy.isfinite(margin)])
                    if last:
                        if logits is None:
                            shape = (1, len(rows) * per_row,
                                     aux["logits"].shape[1])
                            logits = numpy.empty(shape, numpy.float32)
                            mtp_logits = numpy.empty(shape, numpy.float32)
                        at = slice(slot * per_row, (slot + 1) * per_row)
                        logits[0, at] = jax.device_get(aux["logits"])
                        mtp_logits[0, at] = jax.device_get(
                            aux["mtp_logits"])
                    del aux
                main = sums[0] / max(graded, 1)
                second = sums[1] / max(graded2, 1)
                out["loss"].append(main + weight * second)
                out["mtp_loss"].append(second)
                counts += (errors[0], graded, len(rows))
                mtp_counts += (errors[1], graded2)
                loads.append(load)
                weights.append(weight_took)
                taken.append(numpy.concatenate(route, axis=1))
                if out["grad1"] is None:
                    out["grad1"] = ref.leaf_norms(stepped(total))
                step_no += 1
                biases = ref.balance(biases, load, layers)
                for i, leaves in enumerate(stepped(params)):
                    params[i], m_i, v_i = ref.adamw(
                        leaves, jax.tree.map(jnp.asarray, m[i]),
                        jax.tree.map(jnp.asarray, v[i]),
                        {name: total[i][name] for name in leaves},
                        numpy.float32(1.0), numpy.float32(step_no),
                        hyper[i])
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
            out["windows"].append({"n_err": counts, "mtp_n_err": mtp_counts,
                                   "logits": logits,
                                   "mtp_logits": mtp_logits,
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
    """As ``balanced_token_rows.follow``: ``f32`` with no fault is the
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
    out, where = balanced.graded(run, refout, limits)
    refout = run.get("forced_ref") or refout
    stats = [w["stats"] for w in run["windows"]]
    losses = numpy.concatenate([st["mtp_loss"] for st in stats])
    out.append(("mtp_loss_worst_step", max(
        abs(lp - lr_) / abs(lr_)
        for lp, lr_ in zip(losses, refout["mtp_loss"])),
        limits["mtp_loss_worst_step"]))
    out.append(("mtp_logit_rel_diff", max(
        token_rows._rel_diff(st["mtp_logits"], rw["mtp_logits"])
        for st, rw in zip(stats, refout["windows"])
        if rw["mtp_logits"] is not None), limits["mtp_logit_rel_diff"]))
    return out, where


def numbers(run, refout, cfg, limits, net):
    """This family's graded numbers, then ``routed_token_rows``' exact
    counts and the second head's graded tokens a window (against the
    reference's count) and in the first epoch, train and validation
    (against the count the data gives)."""
    out, _ = routed.numbers(run, refout, cfg, limits, net)
    mine, where = graded(run, refout, limits)
    exact = out[len(routed.GRADED):]
    ref_out = run.get("forced_ref") or refout
    gap = max(abs(int(win["stats"]["mtp_n_err"][1]) - int(rw["mtp_n_err"][1]))
              for win, rw in zip(run["windows"], ref_out["windows"]))
    exact.append(("window_mtp_tokens_gap", float(gap), 0.0))
    _, _, _, shift = families.reference(cfg).heads(cfg["layers"])
    ahead = mtp_labels(run["data"]["labels"], shift) >= 0
    nv = run["n_valid"]
    first = run["first_epoch"]["mtp_tokens"]
    for clazz, name, rows in ((2, "train", slice(nv, None)),
                              (1, "valid", slice(0, nv))):
        exact.append(("epoch_%s_mtp_tokens_gap" % name, float(abs(
            int(first[clazz]) - int(ahead[rows].sum()))), 0.0))
    return mine + exact, where


def in_place(run, refout):
    out = balanced.in_place(run, refout)
    at = 0
    for win, rw in zip(out["windows"], refout["windows"]):
        k = len(win["sizes"])
        win["stats"].update(
            mtp_loss=numpy.asarray(refout["mtp_loss"][at:at + k]),
            mtp_n_err=numpy.asarray(rw["mtp_n_err"]),
            mtp_logits=rw["mtp_logits"])
        at += k
    return out
