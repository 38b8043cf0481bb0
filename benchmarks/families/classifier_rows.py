"""Family ``classifier_rows``: rows with one class label each, trained by
softmax cross-entropy over the device-resident data set (cifar-caffe,
AlexNet).

Data: seeded synthetic labelled images, and the loader that serves them.
The data set is made once per run from ``--seed``: windows into a pool of
random bytes scaled to [-1, 1] float32, so every row differs, and labels
that cover every class.  Layout on the sample axis is the loader contract's
[TEST | VALID | TRAIN].  The harness keeps the same arrays for the
reference, which therefore gathers its rows from data the benchmark made.

Capture: ``FusedNet.run_window_indexed``; its per-step losses, the window's
own counts, its confusion matrix and the last step's softmax output; the
momentum after the first window and the parameters' change over all of them.

Comparison: ``follow`` drives the plain reference over the very rows, sizes
and dropout keys of the windows that the timed path's own call was given
during set-up, from weights it draws itself.  ``numbers`` then sets what the
program did beside what the reference did, each number with its limit from
the cell's limits file.
"""

import time

import numpy

from benchmarks import families
from benchmarks.lib import compare, data, job

# -- data ---------------------------------------------------------------------


def make_images(seed, n, sample_shape, n_classes):
    """(data float32 (n, *sample_shape) in [-1, 1], labels int32 (n,))."""
    if n < n_classes:
        raise ValueError("need at least one image per class (%d < %d)"
                         % (n, n_classes))
    images = data.seeded_rows(seed, n, sample_shape)
    lrng = numpy.random.Generator(
        numpy.random.PCG64(data.sub_seed(seed, data.TAG_LABELS)))
    labels = lrng.permutation(numpy.arange(n) % n_classes).astype(numpy.int32)
    return images, labels


def make_data(seed, cfg, mix):
    """The run's host arrays: what the loader serves and the reference
    gathers from."""
    images, labels = make_images(
        seed, int(mix["n_valid"]) + int(mix["n_train"]),
        tuple(cfg["input_sample_shape"]), int(cfg["n_classes"]))
    return {"images": images, "labels": labels}


def loader(made, mix):
    """(loader class, its ``loader_config``).  The class needs the program's
    base classes, so this is called only where the program is imported
    anyway."""
    from znicz_tpu.loader.base import (FullBatchLoader, IFullBatchLoader,
                                       TEST, VALID, TRAIN)

    class BenchSeededImages(FullBatchLoader, IFullBatchLoader):
        """Stock full-batch loader (no ``fill_minibatch`` override, so the
        fused trainer's device-resident path still engages) over arrays
        the benchmark hands in through ``loader_config``."""

        MAPPING = "bench_seeded_images"

        def __init__(self, workflow, **kwargs):
            kwargs.setdefault("normalization_type", "none")
            super(BenchSeededImages, self).__init__(workflow, **kwargs)
            self._bench_data = kwargs["bench_data"]
            self._bench_labels = kwargs["bench_labels"]
            self._n_valid = int(kwargs["n_valid"])

        def load_data(self):
            self.original_data.reset(self._bench_data)
            del self._original_labels[:]
            self._original_labels.extend(self._bench_labels.tolist())
            self.class_lengths[TEST] = 0
            self.class_lengths[VALID] = self._n_valid
            self.class_lengths[TRAIN] = len(self._bench_data) - self._n_valid

    return BenchSeededImages, {"bench_data": made["images"],
                               "bench_labels": made["labels"],
                               "n_valid": int(mix["n_valid"])}


# -- capture ------------------------------------------------------------------

#: the entry of the fused net that the trainer dispatches a train window to
ENTRY = "run_window_indexed"

#: the optimizer's leaves of state beside each parameter (momentum SGD)
STATE_LEAVES = ("vel",)


def _logical_idx(idx_s):
    """(K, B) numpy row indices from what the trainer staged (batch-major,
    or shard-major ``(S, K, B // S)`` under a data mesh)."""
    if isinstance(idx_s, numpy.ndarray):
        return numpy.array(idx_s, dtype=numpy.int64)
    base = idx_s.base
    s, k, b = base.shape
    return numpy.array(base, dtype=numpy.int64).transpose(1, 0, 2).reshape(
        k, s * b)


def feed(trainer, idx_s, batch_sizes, hypers_s):
    """What one dispatch was given: row indices, sizes, hyperparameters
    (the entry's own arguments say it all; the trainer is not asked)."""
    import jax
    return {"idx": _logical_idx(idx_s),
            "sizes": [int(s) for s in batch_sizes],
            "hypers": jax.tree.map(numpy.array, hypers_s)}


def keep(stats, rec):
    """Never donated: per-step losses, the window's own counts and the
    last step's softmax output (whole: ``rec``, the feed, picks no sample)."""
    return {k: stats[k] for k in ("loss", "n_err", "confusion", "output")}


def fetch(st):
    """The kept outputs of one window, once on the host."""
    conf = numpy.asarray(st["confusion"])
    if conf.ndim == 3:      # per-shard partials under a data mesh
        conf = conf.sum(axis=0)
    return {"loss": numpy.asarray(st["loss"], numpy.float64).reshape(-1),
            "n_err": numpy.asarray(st["n_err"]).reshape(-1, 2).sum(axis=0),
            "confusion": conf,
            "output": numpy.asarray(st["output"], numpy.float64)}


def leaf_numbers(cfg, mix, p0, state1, params_end):
    """Per-leaf norms of what the program did: the momentum after the first
    captured window, which is the first gradients as the optimizer got them
    (``v = -lr*(g + wd*w + ortho)``, folded with ``moment`` over the
    window's steps), and the parameters' change over all captured windows,
    from ``w0`` under its zero_filter mask, as the program updates it."""
    masks = [ent.get("mask") for ent in plan(cfg, mix)]
    return job.leaf_norms(p0, state1, params_end, masks, STATE_LEAVES)


def first_epoch(decision):
    return {"evaluated": list(decision.epoch_n_evaluated_samples),
            "confusion_train": numpy.array(decision.confusion_matrixes[2])}


def release(net):
    """Let the program's device state go before the reference runs."""
    net.run_window_indexed = None
    net.params = net.state = net._data_d = net._labels_d = None
    net._win_acc = None
    net._window_fns.clear()


# -- comparison ---------------------------------------------------------------

#: the numbers that carry a limit of their own: the keys of a cell's limits
GRADED = ("loss_worst_step", "logit_rel_diff", "vel1_worst_leaf",
          "dparam_worst_leaf", "n_err_gap")

#: (reading, mode, fault, least chips) of the reference put in the program's
#: place: the bf16 witness, the fp8 control and the planted faults
READINGS = (("bf16", "bf16", None, 1), ("fp8", "fp8", None, 1),
            ("half_batch", "bf16", "half_batch", 1),
            ("no_exchange", "bf16", "no_exchange", 2))


def plan(cfg, mix):
    """The planned net, as the configuration's reference gives it."""
    return families.reference(cfg).plan(cfg["layers"],
                                        cfg["input_sample_shape"])


def follow(cfg, mix, run, mode="f32", fault=None, chips=1, log=None):
    """The reference's own first steps.  ``run`` holds the captured
    ``windows`` (idx, sizes), the seeds and the host data set.  ``fault``
    plants a fault in the reference put in the program's place:
    ``half_batch`` leaves the second half of every minibatch out and takes
    the mean over the rest; ``no_exchange`` keeps only the first chip's
    rows, what one chip would apply without the gradient all-reduce."""
    import jax
    import jax.numpy as jnp
    ref = families.reference(cfg)

    net = plan(cfg, mix)
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
        images, labels = run["data"]["images"], run["data"]["labels"]
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
                hy = compare.expected_hypers(net, cfg.get("lr_policy"),
                                             iteration)
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


def graded(run, refout, limits):
    """The numbers that carry a limit of their own: ``run["program"]`` holds
    the per-leaf norms (``vel1``, ``dparam``) and each window's ``stats`` the
    ``loss``, ``output`` and ``n_err`` of whatever stands in the program's
    place.  Returns ([(name, value, limit)], worst leaves)."""
    prog = run["program"]
    stats = [w["stats"] for w in run["windows"]]
    out = []
    losses = numpy.concatenate([st["loss"] for st in stats])
    out.append(("loss_worst_step", max(
        abs(lp - lr_) / abs(lr_) for lp, lr_ in zip(losses, refout["loss"])),
        limits["loss_worst_step"]))
    out.append(("logit_rel_diff", max(
        logit_rel_diff(st["output"], rw["logits"], rw["valid"])
        for st, rw in zip(stats, refout["windows"])),
        limits["logit_rel_diff"]))
    g, g_at = compare.worst_leaf(prog["vel1"], refout["vel1"],
                                 refout["grad1"])
    d, d_at = compare.worst_leaf(prog["dparam"], refout["dparam"],
                                 refout["grad1"])
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
    out, where = graded(run, refout, limits)
    rows_gap = hist_gap = 0.0
    for win, rw in zip(run["windows"], refout["windows"]):
        total = int(win["stats"]["n_err"][1])
        rows_gap = max(rows_gap, abs(total - rw["total"]))
        hist = win["stats"]["confusion"].sum(axis=0)
        hist_gap = max(hist_gap, int(numpy.abs(hist - rw["label_hist"]).sum()))
    out.append(("window_rows_gap", float(rows_gap), 0.0))
    out.append(("window_label_hist_gap", float(hist_gap), 0.0))
    out.append(("hyper_feed_gap", compare.hyper_feed_gap(
        run["windows"], net, cfg.get("lr_policy")), 0.0))
    first = run["first_epoch"]
    labels = run["data"]["labels"]
    train_hist = numpy.bincount(labels[run["n_valid"]:],
                                minlength=int(cfg["n_classes"]))
    out.append(("epoch_train_rows_gap",
                float(abs(first["evaluated"][2] - run["n_train"])), 0.0))
    out.append(("epoch_valid_rows_gap",
                float(abs(first["evaluated"][1] - run["n_valid"])), 0.0))
    out.append(("epoch_label_hist_gap", float(numpy.abs(
        first["confusion_train"].sum(axis=0) - train_hist).sum()), 0.0))
    return out, where


def in_place(run, refout):
    """``run`` (a program's, or ``calibrate.py``'s seeded feed) with a reference's outputs
    standing where the program's were: per-leaf norms, and per window the
    steps' losses, the counts, a confusion matrix that holds the label
    histogram, and the last step's softmax output."""
    wins, at = [], 0
    for win, rw in zip(run["windows"], refout["windows"]):
        k = len(win["sizes"])
        z = rw["logits"] - rw["logits"].max(axis=1, keepdims=True)
        conf = numpy.zeros((len(rw["label_hist"]),) * 2, numpy.int64)
        conf[0] = rw["label_hist"]
        wins.append(dict(win, stats={
            "loss": numpy.asarray(refout["loss"][at:at + k]),
            "n_err": numpy.asarray([rw["n_err"], rw["total"]]),
            "confusion": conf,
            "output": numpy.exp(z) / numpy.exp(z).sum(axis=1,
                                                      keepdims=True)}))
        at += k
    return dict(run, windows=wins, program={"vel1": refout["vel1"],
                                            "dparam": refout["dparam"]})


# -- the rate's unit of work --------------------------------------------------

def rows_trained(mix, epochs):
    """Rows given a training step in a window of ``epochs`` whole epochs:
    the "images" of ``train_images_per_s``."""
    return int(epochs) * int(mix["n_train"])


def row_tokens(cfg, mix):
    """Tokens in a row, where a row is a sequence; an image has none."""
    return None
