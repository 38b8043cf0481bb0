"""Family ``regression_rows``: float rows with a float target each, trained
by mean squared error over the device-resident data set (the MSE objective's
sliced window entry, ``FusedNet.run_window_mse_sliced``).

It is the witness that the harness takes a second family by new files alone
(``benchmarks/tests/test_witness_family.py``, on the CPU at a tiny size); no
cell uses it and nothing here has run on the chip.  Unlike
``classifier_rows`` there is no label, no error count and no confusion
matrix: the window hands back per-step losses, the sum of its rows' errors
and the last step's output, and its feed is offsets into the epoch's order,
which only the trainer's loader knows.
"""

import numpy

from benchmarks import families
from benchmarks.lib import compare, data, job

# -- data ---------------------------------------------------------------------


def make_data(seed, cfg, mix):
    n = int(mix["n_valid"]) + int(mix["n_train"])
    return {"rows": data.seeded_rows(seed, n,
                                     tuple(cfg["input_sample_shape"])),
            "targets": data.seeded_rows(seed, n, tuple(cfg["target_shape"]),
                                        tag=data.TAG_LABELS)}


def loader(made, mix):
    from znicz_tpu.loader.base import (FullBatchLoaderMSE, IFullBatchLoader,
                                       TEST, VALID, TRAIN)

    class BenchSeededPairs(FullBatchLoaderMSE, IFullBatchLoader):
        """Stock full-batch MSE loader (both fills stock, so the sliced
        resident path engages) over arrays the benchmark hands in."""

        MAPPING = "bench_seeded_pairs"

        def __init__(self, workflow, **kwargs):
            kwargs.setdefault("normalization_type", "none")
            kwargs.setdefault("targets_normalization_type", "none")
            super(BenchSeededPairs, self).__init__(workflow, **kwargs)
            self._bench_rows = kwargs["bench_rows"]
            self._bench_targets = kwargs["bench_targets"]
            self._n_valid = int(kwargs["n_valid"])

        def load_data(self):
            self.original_data.mem = self._bench_rows
            self.original_targets.mem = self._bench_targets
            self.class_lengths[TEST] = 0
            self.class_lengths[VALID] = self._n_valid
            self.class_lengths[TRAIN] = len(self._bench_rows) - self._n_valid

    return BenchSeededPairs, {"bench_rows": made["rows"],
                              "bench_targets": made["targets"],
                              "n_valid": int(mix["n_valid"])}


# -- capture ------------------------------------------------------------------

ENTRY = "run_window_mse_sliced"
STATE_LEAVES = ("vel",)


def feed(trainer, starts, batch, batch_sizes, hypers_s):
    """The rows of each step: the entry is given offsets into the epoch's
    shuffled order, which the trainer's loader holds."""
    import jax
    order = numpy.array(trainer.loader_unit.train_indices, dtype=numpy.int64)
    idx = numpy.full((len(starts), int(batch)), -1, numpy.int64)
    for i, (start, size) in enumerate(zip(starts, batch_sizes)):
        idx[i, :int(size)] = order[int(start):int(start) + int(size)]
    return {"idx": idx, "sizes": [int(s) for s in batch_sizes],
            "hypers": jax.tree.map(numpy.array, hypers_s)}


def keep(stats, rec):
    return {k: stats[k] for k in ("loss", "metrics", "output")}


def fetch(st):
    return {"loss": numpy.asarray(st["loss"], numpy.float64).reshape(-1),
            # [sum, max, min] of the window's per-row errors
            "mse_sum": float(numpy.asarray(st["metrics"]).reshape(-1, 3)[
                :, 0].sum()),
            "output": numpy.asarray(st["output"], numpy.float64)}


def leaf_numbers(cfg, mix, p0, state1, params_end):
    masks = [ent.get("mask") for ent in plan(cfg, mix)]
    return job.leaf_norms(p0, state1, params_end, masks, STATE_LEAVES)


def first_epoch(decision):
    """The MSE decision counts no rows: it keeps (mean, max, min) of the
    rows' errors for each class of rows that the epoch served."""
    return {"metrics": list(decision.epoch_metrics)}


def release(net):
    net.run_window_mse_sliced = None
    net.params = net.state = None
    net._data_d = net._targets_d = net._labels_d = None
    net._data_p = net._targets_p = net._labels_p = None
    net._win_acc = None
    net._window_fns.clear()


# -- comparison ---------------------------------------------------------------

GRADED = ("loss_worst_step", "output_rel_diff", "vel1_worst_leaf",
          "dparam_worst_leaf", "mse_sum_gap")

READINGS = (("half_batch", "f32", "half_batch", 1),)


def plan(cfg, mix):
    return families.reference(cfg).plan(
        cfg["layers"], cfg["input_sample_shape"], cfg["target_shape"])


def follow(cfg, mix, run, mode="f32", fault=None, chips=1, log=None):
    """The reference's own first steps over the captured rows and sizes.
    ``half_batch`` leaves the second half of every minibatch out and takes
    the mean over the rest."""
    import jax
    import jax.numpy as jnp
    if mode != "f32" or fault not in (None, "half_batch"):
        raise ValueError((mode, fault))
    ref = families.reference(cfg)
    net = plan(cfg, mix)
    params = ref.init_params(net, run["weight_seed"])
    init = [{k: v.copy() for k, v in p.items()} for p in params]
    step = ref.make_step(net, bool(cfg["mse_root"]))
    params = jax.tree.map(jnp.asarray, params)
    vel = jax.tree.map(jnp.zeros_like, params)
    rows, targets = run["data"]["rows"], run["data"]["targets"]
    out = {"loss": [], "windows": [], "grad1": None, "vel1": None}
    iteration = 0
    for win in run["windows"]:
        mse_sum = 0.0
        for idx, size in zip(win["idx"], win["sizes"]):
            valid = idx >= 0
            valid[size:] = False
            if fault == "half_batch":
                valid[run["batch"] // 2:] = False
            at = numpy.maximum(idx, 0)
            hy = compare.expected_hypers(net, cfg.get("lr_policy"),
                                         iteration)
            params, vel, res = step(params, vel, jnp.asarray(rows[at]),
                                    jnp.asarray(targets[at]),
                                    jnp.asarray(valid), hy)
            if out["grad1"] is None:
                out["grad1"] = ref.leaf_norms(res["grads"])
            mse_sum += float(numpy.asarray(res["mse_per"])[valid].sum())
            out["loss"].append(float(res["loss"]))
            iteration += 1
        out["windows"].append({
            "mse_sum": mse_sum, "valid": valid,
            "output": numpy.asarray(res["output"], numpy.float64)})
        if out["vel1"] is None:
            out["vel1"] = ref.leaf_norms(vel)
    out["dparam"] = ref.leaf_norms(
        [{k: p[k] - p0[k] for k in p0} for p, p0 in zip(params, init)])
    return out


def graded(run, refout, limits):
    prog = run["program"]
    stats = [w["stats"] for w in run["windows"]]
    losses = numpy.concatenate([st["loss"] for st in stats])
    out = [("loss_worst_step", max(
        abs(lp - lr_) / abs(lr_) for lp, lr_ in zip(losses, refout["loss"])),
        limits["loss_worst_step"])]
    out.append(("output_rel_diff", max(
        float(numpy.linalg.norm((st["output"] - rw["output"])[rw["valid"]])
              / numpy.linalg.norm(rw["output"][rw["valid"]]))
        for st, rw in zip(stats, refout["windows"])),
        limits["output_rel_diff"]))
    g, g_at = compare.worst_leaf(prog["vel1"], refout["vel1"],
                                 refout["grad1"])
    d, d_at = compare.worst_leaf(prog["dparam"], refout["dparam"],
                                 refout["grad1"])
    out.append(("vel1_worst_leaf", g, limits["vel1_worst_leaf"]))
    out.append(("dparam_worst_leaf", d, limits["dparam_worst_leaf"]))
    out.append(("mse_sum_gap", max(
        abs(st["mse_sum"] - rw["mse_sum"]) / rw["mse_sum"]
        for st, rw in zip(stats, refout["windows"])),
        limits["mse_sum_gap"]))
    return out, {"vel1_at": g_at, "dparam_at": d_at}


def numbers(run, refout, cfg, limits, net):
    out, where = graded(run, refout, limits)
    out.append(("hyper_feed_gap", compare.hyper_feed_gap(
        run["windows"], net, cfg.get("lr_policy")), 0.0))
    # [TEST | VALID | TRAIN]: the first epoch served both classes it has
    out.append(("epoch_classes_missing", float(sum(
        m is None for m in run["first_epoch"]["metrics"][1:])), 0.0))
    return out, where


def in_place(run, refout):
    wins, at = [], 0
    for win, rw in zip(run["windows"], refout["windows"]):
        k = len(win["sizes"])
        wins.append(dict(win, stats={
            "loss": numpy.asarray(refout["loss"][at:at + k]),
            "mse_sum": rw["mse_sum"], "output": rw["output"]}))
        at += k
    return dict(run, windows=wins, program={"vel1": refout["vel1"],
                                            "dparam": refout["dparam"]})


# -- the rate's unit of work --------------------------------------------------

def rows_trained(mix, epochs):
    return int(epochs) * int(mix["n_train"])


def row_tokens(cfg, mix):
    return None
