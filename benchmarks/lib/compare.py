"""The comparison that decides ``correct``.

``follow`` drives the plain reference over the very rows, sizes and dropout
keys of the windows that the timed path's own call was given during set-up,
from weights it draws itself.  ``numbers`` then sets what the program did
beside what the reference did, each number with its limit from the cell's
limits file.  Nothing here imports the program.
"""

import importlib
import time

import numpy

#: leaves whose reference gradient is under this share of the median
#: leaf's are nought to rounding and are left out of the norm gaps
NEGLIGIBLE = 1e-3


def lr_multiplier(policy, iteration):
    if not policy:
        return 1.0
    if policy["name"] != "arbitrary_step":
        raise ValueError("unknown lr policy %r" % policy["name"])
    bound = 0
    for coeff, length in policy["lrs_with_lengths"]:
        bound += length
        if iteration < bound:
            return float(coeff)
    return 0.0


def expected_hypers(net, policy, iteration):
    mult = lr_multiplier(policy, iteration)
    out = []
    for ent in net:
        hy = ent.get("hyper")
        out.append({} if hy is None else
                   {k: dict(v, lr=v["lr"] * mult) for k, v in hy.items()})
    return out


def follow(cfg, mix, run, mode="f32", fault=None, chips=1, log=None):
    """The reference's own first steps.  ``run`` holds the captured
    ``windows`` (idx, sizes), the seeds and the host data set.  ``fault``
    plants a fault in the reference put in the program's place:
    ``half_batch`` leaves the second half of every minibatch out and takes
    the mean over the rest; ``no_exchange`` keeps only the first chip's
    rows, what one chip would apply without the gradient all-reduce."""
    import jax
    import jax.numpy as jnp
    ref = importlib.import_module("benchmarks.reference." + cfg["reference"])

    net = ref.plan(cfg["layers"], cfg["input_sample_shape"])
    params = ref.init_params(net, run["weight_seed"])
    init = [{k: v.copy() for k, v in p.items()} for p in params]
    batch = run["batch"]
    block = min(int(mix["reference_block_rows"]), batch)
    step = ref.make_step(net, mode, block)
    with jax.default_device(jax.devices()[0]):
        params = jax.tree.map(jnp.asarray, params)
        vel = jax.tree.map(jnp.zeros_like, params)
        key = jax.random.PRNGKey(run["dropout_seed"])
        drop = ref.has_dropout(net)
        images, labels = run["images_host"], run["labels_host"]
        out = {"loss": [], "windows": [], "grad1": None, "vel1": None}
        iteration = 0
        for win in run["windows"]:
            n_err = total = 0
            hist = numpy.zeros(int(cfg["n_classes"]), numpy.int64)
            for idx, size in zip(win["idx"], win["sizes"]):
                rows = numpy.maximum(idx, 0)
                lbl = numpy.where(idx >= 0, labels[rows], -1).astype(
                    numpy.int32)
                lbl[size:] = -1
                if fault == "half_batch":
                    lbl[batch // 2:] = -1
                elif fault == "no_exchange":
                    lbl[batch // chips:] = -1
                elif fault is not None:
                    raise ValueError(fault)
                x = images[rows]
                if drop:
                    key, sub = jax.random.split(key)
                else:
                    sub = key
                hy = expected_hypers(net, cfg.get("lr_policy"), iteration)
                t0 = time.perf_counter()
                params, vel, res = step(params, vel, jnp.asarray(x),
                                        jnp.asarray(lbl), sub, hy)
                jax.block_until_ready(res["loss"])
                if log is not None:
                    log("reference %s step %d: %.1f s"
                        % (mode, iteration + 1, time.perf_counter() - t0))
                if out["grad1"] is None:
                    out["grad1"] = ref.leaf_norms(res["grads"])
                res.pop("grads")
                pred = numpy.asarray(res["pred"])
                ok = lbl >= 0
                n_err += int((ok & (pred != lbl)).sum())
                total += int(ok.sum())
                hist += numpy.bincount(lbl[ok], minlength=len(hist))
                out["loss"].append(float(res["loss"]))
                iteration += 1
            out["windows"].append({
                "n_err": n_err, "total": total, "label_hist": hist,
                # the window's last step, as the program hands it back
                "logits": numpy.asarray(res["logits"], numpy.float64),
                "valid": ok})
            if out["vel1"] is None:
                out["vel1"] = ref.leaf_norms(vel)
        delta = [{k: params[i][k] - _masked(init[i][k], net[i], k)
                  for k in init[i]} for i in range(len(init))]
        out["dparam"] = ref.leaf_norms(delta)
    return out


def _masked(w, ent, name):
    mask = ent.get("mask")
    if name == "w" and mask is not None:
        return w * mask.astype(w.dtype)
    return w


def worst_leaf(prog, refn, grad_ref):
    """Largest gap between the program's and the reference's norm of a
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger; leaves whose reference gradient is nought to
    rounding are left out."""
    med_g = float(numpy.median(list(grad_ref.values())))
    keep = [k for k in refn if grad_ref[k] >= NEGLIGIBLE * med_g]
    med = float(numpy.median([refn[k] for k in keep]))
    worst, at = 0.0, None
    for k in keep:
        gap = abs(prog[k] - refn[k]) / max(refn[k], med)
        if gap > worst:
            worst, at = gap, k
    return worst, at


def centred(logits):
    return logits - logits.mean(axis=1, keepdims=True)


def logit_rel_diff(probs, logits, valid):
    """Norm of the difference between the program's and the reference's
    logits of one step, each row centred over the classes (the program
    hands back softmax outputs, whose logarithm is the logits up to a
    constant per row), over the reference's norm; labelled rows only."""
    prog = centred(numpy.log(numpy.maximum(probs[valid], 1e-300)))
    want = centred(logits[valid])
    return float(numpy.linalg.norm(prog - want) / numpy.linalg.norm(want))


def hyper_feed_gap(windows, net, policy):
    """Largest difference between a hyperparameter the trainer fed to a
    step and what the configuration and its learning-rate policy state."""
    worst = 0.0
    iteration = 0
    for win in windows:
        for k in range(len(win["sizes"])):
            want = expected_hypers(net, policy, iteration)
            for got_l, want_l in zip(win["hypers"], want):
                for name, fields in want_l.items():
                    for f, v in fields.items():
                        got = float(numpy.asarray(got_l[name][f])[k])
                        worst = max(worst, abs(got - float(numpy.float32(v))))
                    if float(numpy.asarray(got_l[name]["l1_vs_l2"])[k]):
                        worst = max(worst, 1.0)
            iteration += 1
    return worst


def graded(prog, stats, refout, limits):
    """The numbers that carry a limit of their own: ``prog`` holds the
    per-leaf norms (``vel1``, ``dparam``) and ``stats`` each window's
    ``loss``, ``output`` and ``n_err`` of whatever stands in the program's
    place.  Returns ([(name, value, limit)], worst leaves)."""
    out = []
    losses = numpy.concatenate([st["loss"] for st in stats])
    out.append(("loss_worst_step", max(
        abs(lp - lr_) / abs(lr_) for lp, lr_ in zip(losses, refout["loss"])),
        limits["loss_worst_step"]))
    out.append(("logit_rel_diff", max(
        logit_rel_diff(st["output"], rw["logits"], rw["valid"])
        for st, rw in zip(stats, refout["windows"])),
        limits["logit_rel_diff"]))
    g, g_at = worst_leaf(prog["vel1"], refout["vel1"], refout["grad1"])
    d, d_at = worst_leaf(prog["dparam"], refout["dparam"], refout["grad1"])
    out.append(("vel1_worst_leaf", g, limits["vel1_worst_leaf"]))
    out.append(("dparam_worst_leaf", d, limits["dparam_worst_leaf"]))
    out.append(("n_err_gap", max(
        abs(int(st["n_err"][0]) - rw["n_err"]) / max(rw["total"], 1)
        for st, rw in zip(stats, refout["windows"])),
        limits["n_err_gap"]))
    return out, {"vel1_at": g_at, "dparam_at": d_at}


def numbers(run, refout, cfg, limits, net):
    """[(name, value, limit)] in a fixed order: the graded numbers, then
    the exact counts (limit 0)."""
    out, where = graded(run["program"],
                        [w["stats"] for w in run["windows"]], refout, limits)
    rows_gap = hist_gap = 0.0
    for win, rw in zip(run["windows"], refout["windows"]):
        total = int(win["stats"]["n_err"][1])
        rows_gap = max(rows_gap, abs(total - rw["total"]))
        hist = win["stats"]["confusion"].sum(axis=0)
        hist_gap = max(hist_gap, int(numpy.abs(hist - rw["label_hist"]).sum()))
    out.append(("window_rows_gap", float(rows_gap), 0.0))
    out.append(("window_label_hist_gap", float(hist_gap), 0.0))
    out.append(("hyper_feed_gap",
                hyper_feed_gap(run["windows"], net, cfg.get("lr_policy")),
                0.0))
    first = run["first_epoch"]
    labels = run["labels_host"]
    train_hist = numpy.bincount(labels[run["n_valid"]:],
                                minlength=int(cfg["n_classes"]))
    out.append(("epoch_train_rows_gap",
                float(abs(first["evaluated"][2] - run["n_train"])), 0.0))
    out.append(("epoch_valid_rows_gap",
                float(abs(first["evaluated"][1] - run["n_valid"])), 0.0))
    out.append(("epoch_label_hist_gap", float(numpy.abs(
        first["confusion_train"].sum(axis=0) - train_hist).sum()), 0.0))
    return out, where


def decide(nums):
    return all(numpy.isfinite(v) and v <= lim for _, v, lim in nums)
