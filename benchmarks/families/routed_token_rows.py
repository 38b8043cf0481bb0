"""Family ``routed_token_rows``: ``token_rows``' packed rows, trained by a
routed language model's plain next-token cross-entropy with AdamW, and
compared **under one choice of experts**.

Data, loader, packing, the captured entry, the optimizer's leaves and the
counts of the first epoch are ``token_rows``' own, by import; so is the
feed, after the capture's copy of the parameters has gone to the host.

Capture: beside the per-step loss and the window's ``[errors, graded tokens,
rows]``, of every checked step the load of every expert of every ``moe``
application (``moe_load (K, entries, experts)``) and the choice made
(``moe_route (K, entries, tokens, top_k)`` int8, which the window hands back
on the device and only this capture reads); of the last checked window's
last step the logits at ``logit_samples`` seeded positions.

Comparison: a top-k over near-equal logits is a choice that rounding moves,
and which experts a token goes to changes everything after it.  So the
reference follows the captured rows **forced to the program's choice**
(``reference/<name>.py``: it takes the choice, weighs it by its own logits,
and says for every token and entry whether its own choice differs and by
what margin of its own logits), and loss, logits, gradients and the
parameters' change are compared under that one choice.  The choice itself is
graded by two numbers: the share of (token, entry) pairs whose choice is not
the reference's own (``route_flip_share``) and the margin by which the
flipped ones differ, at their 99.9th percentile (``flip_margin_p999``;
infinite where any choice is no ``top_k`` different experts).  The largest
margin, which ISSUE 33 named first, is a maximum over some 8,500 flipped
pairs and read 0.060 to 0.122 over five seeds of the sound program, more
than two times apart, where the percentile read 0.0355 to 0.0414 (my chip
runs, PR 33); it is logged beside the verdict (``flip_margin_max``).  Each
step's load of every expert is held exactly to the reference's count under
the same choice.  The gap between the two sides' token errors, which
``token_rows`` grades, is logged too and not graded: ids uniform over the
vocabulary leave every argmax wrong on both sides at the checked steps, the
sound program reads 0 and the control and every fault at most one token
(3.1e-5), so no reading says where a limit would lie (``tok_err_gap``).

A reading (``READINGS``: the reference in a lower precision or with a fault
planted, put in the program's place) routes by itself; the float32
reference is then forced to THAT reading's choice (``forced_ref``, which
``in_place`` carries along and ``graded`` takes in place of the reference
it is handed), as it is forced to the program's.
"""

import time

import numpy

from benchmarks import families
from benchmarks.families import token_rows
from benchmarks.lib import compare

# -- data, capture: token_rows' own -------------------------------------------

make_data = token_rows.make_data
loader = token_rows.loader
ENTRY = token_rows.ENTRY
STATE_LEAVES = token_rows.STATE_LEAVES
leaf_numbers = token_rows.leaf_numbers
first_epoch = token_rows.first_epoch
release = token_rows.release
rows_trained = token_rows.rows_trained
row_tokens = token_rows.row_tokens


def attended_pairs_per_row(mix, window=None):
    """Mean over the mix's rows of the (query, key) pairs a row attends:
    over its segments, ``j <= i`` and, under a ``window``, ``i - j <
    window``."""
    n = int(mix["n_valid"]) + int(mix["n_train"])
    seq = int(mix["seq_len"])
    _, _, seg = token_rows.pack(token_rows.doc_lengths(mix, n * seq),
                                numpy.zeros(n * seq), n, seq)
    pairs = 0.0
    for row in seg:
        lens = numpy.bincount(row).astype(numpy.int64)
        w = lens if window is None else numpy.minimum(lens, int(window))
        pairs += float((w * (w + 1) // 2 + (lens - w) * w).sum())
    return pairs / n


def feed(trainer, idx_s, batch_sizes, hypers_s):
    """``token_rows``' feed, after the job's copy of the parameters from
    before the first checked window has gone to the host: beside 7.9 GB of
    live AdamW state and the window program's 6.1 GB, 2.6 GB more of copy
    leave a 16.9 GB chip no room (``token_rows._copies_to_host`` moves it
    only once the copy of the optimizer state is held too, which is after
    that window; ``leaf_numbers`` takes both from the host)."""
    import jax
    held = getattr(getattr(trainer.net, ENTRY), "__self__", None)
    if getattr(held, "p0", None) is not None:
        held.p0 = jax.device_get(held.p0)
    return token_rows.feed(trainer, idx_s, batch_sizes, hypers_s)


def keep(stats, rec):
    """Small numbers and the choice stay on the device; the sampled logits
    come to the host at once."""
    import jax
    rec.pop("_net").sample_positions = None
    out = {k: stats[k] for k in ("loss", "n_err", "loss_sum", "moe_load",
                                 "moe_route")}
    out["logits"] = None
    if rec["sample"] is not None:
        out["logits"] = numpy.asarray(jax.device_get(
            stats.pop("logits_sample")))
    return out


def fetch(st):
    return {"loss": numpy.asarray(st["loss"], numpy.float64).reshape(-1),
            "n_err": numpy.asarray(st["n_err"]).reshape(3),
            "loss_sum": float(st["loss_sum"]),
            "load": numpy.asarray(st["moe_load"], numpy.int64),
            "route": numpy.asarray(st["moe_route"]),
            "logits": st["logits"]}


# -- comparison ---------------------------------------------------------------

GRADED = ("loss_worst_step", "logit_rel_diff", "m1_worst_leaf",
          "dparam_worst_leaf", "route_flip_share", "flip_margin_p999")

#: (reading, mode, fault, least chips): the bf16 witness, the fp8 control,
#: and faults planted in the bf16 reference put in the program's place
READINGS = (("bf16", "bf16", None, 1), ("fp8", "fp8", None, 1),
            ("window_left_out", "bf16", "window_left_out", 1),
            ("rope_on_global", "bf16", "rope_on_global", 1),
            ("weights_over_held", "bf16", "weights_over_held", 1))


def plan(cfg, mix):
    """The planned net: every leaf layer once, attention with the pairs its
    own window leaves inside the mix's documents."""
    return families.reference(cfg).plan(
        cfg["layers"], int(mix["seq_len"]),
        lambda window: attended_pairs_per_row(mix, window))


def _steps(ref, cfg, mix, run, mode, fault, routes, log):
    """The reference's own steps over the captured feed; ``routes``, one
    ``(K, entries, tokens, top_k)`` a window, forces the choice."""
    import jax
    import jax.numpy as jnp
    layers = cfg["layers"]
    row_fn = ref.make_row(layers, mode, fault, forced=routes is not None)
    hyper = ref.hypers(layers)
    made = run["data"]
    seq = made["ids"].shape[1]
    batch = run["batch"]
    n_sample = int(mix["logit_samples"])
    out = {"loss": [], "windows": [], "grad1": None, "m1": None,
           "flips": 0, "pairs": 0, "margins": [numpy.zeros(0)]}
    step_no = 0
    tag = "%s%s%s" % (mode, " " + fault if fault else "",
                      " forced" if routes is not None else "")
    with jax.default_device(jax.devices()[0]):
        # the weights and a minibatch's gradient sum live on the device
        # beside the row's work; the two moments wait on the host and visit
        # the device a layer at a time
        init = ref.init_params(layers, run["weight_seed"])
        params = jax.tree.map(jnp.asarray, init)
        m = [{k: numpy.zeros_like(a) for k, a in p.items()} for p in init]
        v = [{k: numpy.zeros_like(a) for k, a in p.items()} for p in init]
        del init
        for w, win in enumerate(run["windows"]):
            # the logits are read where the program was asked for them: in
            # the last window (a seeded feed has no record of the asking)
            sample = win["sample"] if "sample" in win else (
                token_rows.sample_positions(win["idx"], seq, n_sample)
                if w == len(run["windows"]) - 1 else None)
            # as many positions every step as the sampled one asks for, so
            # that the row's program compiles once (the other steps' logits
            # are not read)
            per_row = max(n_sample // batch, 1)
            counts = numpy.zeros(3, numpy.int64)
            loads, taken, logits = [], [], None
            for k, (idx, size) in enumerate(zip(win["idx"], win["sizes"])):
                t0 = time.perf_counter()
                last = sample is not None and k == len(win["sizes"]) - 1
                rows = [int(r) for r in idx[:size] if r >= 0]
                graded = int(sum((made["labels"][r] >= 0).sum()
                                 for r in rows))
                total = jax.tree.map(jnp.zeros_like, params)
                loss_sum, errors = 0.0, 0
                load, route = 0, []
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
                    route.append(numpy.asarray(aux["route"]))
                    flipped = numpy.asarray(aux["flipped"])
                    out["flips"] += int(flipped.sum())
                    out["pairs"] += int(flipped.size)
                    margin = numpy.asarray(aux["margin"], numpy.float64)
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
                taken.append(numpy.concatenate(route, axis=1))
                if out["grad1"] is None:
                    out["grad1"] = {k2: v2 / max(graded, 1) for k2, v2
                                    in ref.leaf_norms(total).items()}
                step_no += 1
                for i in range(len(params)):
                    params[i], m_i, v_i = ref.adamw(
                        params[i], jax.tree.map(jnp.asarray, m[i]),
                        jax.tree.map(jnp.asarray, v[i]), total[i],
                        numpy.float32(max(graded, 1)),
                        numpy.float32(step_no), hyper[i])
                    m[i], v[i] = jax.device_get((m_i, v_i))
                    del m_i, v_i
                del total
                if log is not None:
                    log("reference %s step %d: %.1f s; host %.1f GB now, "
                        "%.1f GB at most"
                        % ((tag, step_no, time.perf_counter() - t0)
                           + token_rows._host_gb()))
            out["windows"].append({"n_err": counts, "logits": logits,
                                   "load": numpy.stack(loads),
                                   "route": numpy.stack(taken)})
            if out["m1"] is None:
                out["m1"] = ref.leaf_norms(m)
        del m, v
        out["dparam"] = ref.leaf_norms(ref.difference(
            params, jax.tree.map(jnp.asarray, ref.init_params(
                layers, run["weight_seed"]))))
    out["margins"] = numpy.concatenate(out["margins"])
    return out


def follow(cfg, mix, run, mode="f32", fault=None, chips=1, log=None):
    """The reference over the captured feed.  ``f32`` with no fault is the
    reference proper: forced to the choice the run's windows hold (the
    program's), free where they hold none (a seeded feed).  ``free`` is the
    float32 reference routing by itself whatever the run holds.  Any other
    mode, or a fault (``window_left_out``, ``rope_on_global``,
    ``weights_over_held``), is a reading: it routes by itself, and the
    float32 reference forced to ITS choice rides along as ``forced_ref``."""
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
    prog = run["program"]
    refout = run.get("forced_ref") or refout
    stats = [w["stats"] for w in run["windows"]]
    out = []
    losses = numpy.concatenate([st["loss"] for st in stats])
    out.append(("loss_worst_step", max(
        abs(lp - lr_) / abs(lr_) for lp, lr_ in zip(losses, refout["loss"])),
        limits["loss_worst_step"]))
    sampled = [(st, rw) for st, rw in zip(stats, refout["windows"])
               if rw["logits"] is not None]
    out.append(("logit_rel_diff", max(
        token_rows._rel_diff(st["logits"], rw["logits"])
        for st, rw in sampled), limits["logit_rel_diff"]))
    g, g_at = compare.worst_leaf(prog["m1"], refout["m1"], refout["grad1"])
    d, d_at = compare.worst_leaf(prog["dparam"], refout["dparam"],
                                 refout["grad1"])
    out.append(("m1_worst_leaf", g, limits["m1_worst_leaf"]))
    out.append(("dparam_worst_leaf", d, limits["dparam_worst_leaf"]))
    # logged, not graded: no reading of it says where a limit would lie
    tok_err_gap = max(
        abs(int(st["n_err"][0]) - int(rw["n_err"][0]))
        / max(int(rw["n_err"][1]), 1)
        for st, rw in zip(stats, refout["windows"]))
    margins = refout["margins"]
    out.append(("route_flip_share",
                refout["flips"] / max(refout["pairs"], 1),
                limits["route_flip_share"]))
    p999 = 0.0
    if len(margins):
        p999 = float(numpy.percentile(margins, 99.9)) \
            if numpy.isfinite(margins).all() else float("inf")
    out.append(("flip_margin_p999", p999, limits["flip_margin_p999"]))
    where = {"m1_at": g_at, "dparam_at": d_at,
             "flips": "%d of %d" % (refout["flips"], refout["pairs"]),
             "flip_margin_max": float(margins.max()) if len(margins)
             else 0.0, "tok_err_gap": tok_err_gap}
    return out, where


def numbers(run, refout, cfg, limits, net):
    """The graded numbers, then the exact counts (limit 0): rows and graded
    tokens per window and per epoch, train and validation, the
    hyperparameter feed, and every step's load of every expert against the
    reference's count under the same choice."""
    out, where = graded(run, refout, limits)
    refout = run.get("forced_ref") or refout
    rows_gap = tok_gap = load_gap = 0
    for win, rw in zip(run["windows"], refout["windows"]):
        got = win["stats"]["n_err"]
        tok_gap = max(tok_gap, abs(int(got[1]) - int(rw["n_err"][1])))
        rows_gap = max(rows_gap, abs(int(got[2]) - int(rw["n_err"][2])))
        load_gap = max(load_gap, int(numpy.abs(
            win["stats"]["load"] - rw["load"]).max()))
    out.append(("window_rows_gap", float(rows_gap), 0.0))
    out.append(("window_tokens_gap", float(tok_gap), 0.0))
    out.append(("window_load_gap", float(load_gap), 0.0))
    per_spec = [{"hyper": h} for h in
                families.reference(cfg).hypers(cfg["layers"])]
    out.append(("hyper_feed_gap", compare.hyper_feed_gap(
        run["windows"], per_spec, cfg.get("lr_policy")), 0.0))
    first = run["first_epoch"]
    labels, nv = run["data"]["labels"], run["n_valid"]
    want = {"rows": (nv, run["n_train"]),
            "tokens": (int((labels[:nv] >= 0).sum()),
                       int((labels[nv:] >= 0).sum()))}
    for what in ("rows", "tokens"):
        for clazz, name in ((2, "train"), (1, "valid")):
            out.append(("epoch_%s_%s_gap" % (name, what), float(abs(
                int(first[what][clazz]) - want[what][clazz - 1])), 0.0))
    return out, where


def in_place(run, refout):
    """``run`` with a reference's outputs standing where the program's
    were, and with the float32 reference forced to that reference's choice
    where it brings one."""
    wins, at = [], 0
    for win, rw in zip(run["windows"], refout["windows"]):
        k = len(win["sizes"])
        wins.append(dict(win, stats={
            "loss": numpy.asarray(refout["loss"][at:at + k]),
            "n_err": numpy.asarray(rw["n_err"]),
            "logits": rw["logits"], "load": rw["load"],
            "route": rw["route"]}))
        at += k
    return dict(run, windows=wins, forced_ref=refout.get("forced_ref"),
                program={"m1": refout["m1"], "dparam": refout["dparam"]})
