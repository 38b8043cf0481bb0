"""Family ``token_rows``: rows of token ids with a label and a segment id at
every position, trained by a looped language model's mixture loss with
AdamW over the device-resident rows.

Data: ``n_valid + n_train`` rows of ``seq_len`` ids.  Document lengths come
from the mix's own ``doc_seed`` (lognormal, clipped), so every run of a cell
packs the same documents into the same rows and does the same attention
work, which ``plan`` counts exactly; ``--seed`` draws the ids (uniform over
the vocabulary) and the weights.  Documents are laid end to end and cut
into rows; a label is the next id inside the same document and row, -1
where none is graded; a segment id numbers a row's documents.

Capture: ``FusedNet.run_window_indexed``; per step the mean loss over the
minibatch's graded tokens, per window ``[errors, graded tokens, rows]``; of
the last checked window's last step every pass's logits and the exit
distribution at ``logit_samples`` seeded positions (a step's logits are
gigabytes: the program is asked for these positions only, through
``sample_positions``, and they are pulled to the host at once; the windows
before it run the plain program that is timed, so their losses, the first
moment and the parameters' change are that executable's); AdamW's first
moment after the first window (the summed gradient of each shared weight
as the optimizer got it) and the parameters' change over all captured
windows.  The job's own copies of the parameters and of that state wait on
the host for the last checked window (``_copies_to_host``).

Comparison: ``follow`` drives the plain reference (``reference/<name>.py``)
over the very rows of the captured windows, one row at a time with the
minibatch's graded count as the normaliser, from weights it draws itself.
"""

import time

import numpy

from benchmarks import families
from benchmarks.lib import compare, data, job

# -- data ---------------------------------------------------------------------


def doc_lengths(mix, total):
    """Lengths of documents laid end to end that cover ``total`` tokens,
    from the mix's ``doc_seed`` alone."""
    d = mix["documents"]
    rng = numpy.random.Generator(numpy.random.PCG64(int(mix["doc_seed"])))
    out, have = [], 0
    while have < total:
        part = numpy.clip(numpy.rint(rng.lognormal(
            numpy.log(float(d["median"])), float(d["sigma"]), 4096)),
            int(d["min"]), int(d["max"])).astype(numpy.int64)
        out.append(part)
        have += int(part.sum())
    return numpy.concatenate(out)


def pack(lengths, ids, n_rows, seq):
    """(ids, labels, segments), each (n_rows, seq) int32."""
    total = n_rows * seq
    doc = numpy.searchsorted(numpy.cumsum(lengths), numpy.arange(total),
                             side="right").reshape(n_rows, seq)
    ids = numpy.asarray(ids, numpy.int32).reshape(n_rows, seq)
    labels = numpy.full((n_rows, seq), -1, numpy.int32)
    labels[:, :-1] = numpy.where(doc[:, 1:] == doc[:, :-1], ids[:, 1:], -1)
    return ids, labels, (doc - doc[:, :1] + 1).astype(numpy.int32)


def make_data(seed, cfg, mix):
    n = int(mix["n_valid"]) + int(mix["n_train"])
    seq = int(mix["seq_len"])
    rng = numpy.random.Generator(numpy.random.PCG64(
        data.sub_seed(seed, data.TAG_IMAGES)))
    stream = rng.integers(0, int(cfg["vocab_size"]), n * seq,
                          dtype=numpy.int32)
    ids, labels, segments = pack(doc_lengths(mix, n * seq), stream, n, seq)
    return {"ids": ids, "labels": labels, "segments": segments}


def attended_pairs_per_row(mix):
    """Mean over the mix's rows of the (query, key) pairs a row attends:
    ``len (len + 1) / 2`` summed over its segments."""
    n = int(mix["n_valid"]) + int(mix["n_train"])
    seq = int(mix["seq_len"])
    _, _, seg = pack(doc_lengths(mix, n * seq), numpy.zeros(n * seq), n,
                     seq)
    pairs = 0.0
    for row in seg:
        lens = numpy.bincount(row)
        pairs += float((lens * (lens + 1) // 2).sum())
    return pairs / n


def loader(made, mix):
    from znicz_tpu.loader.tokens import TokenRowsLoader

    class BenchTokenRows(TokenRowsLoader):
        """The stock token-rows loader over arrays the benchmark hands in."""

        MAPPING = "bench_token_rows"

        def __init__(self, workflow, **kwargs):
            super(BenchTokenRows, self).__init__(workflow, **kwargs)
            self._bench = kwargs["bench_rows"]
            self._n_valid = int(kwargs["n_valid"])
            #: how many positions ``feed`` asks the last checked window's
            #: logits at, which window that is, and how many it has seen
            self.bench_logit_samples = int(kwargs["logit_samples"])
            self.bench_check_windows = int(kwargs["check_windows"])
            self.bench_windows_fed = 0

        def load_data(self):
            self.set_rows(self._bench["ids"], self._bench["labels"],
                          self._bench["segments"], n_valid=self._n_valid)

    return BenchTokenRows, {"bench_rows": made,
                            "n_valid": int(mix["n_valid"]),
                            "logit_samples": int(mix["logit_samples"]),
                            "check_windows": int(mix["check_windows"])}


# -- capture ------------------------------------------------------------------

ENTRY = "run_window_indexed"

#: AdamW's leaves beside each parameter that are read (the first moment)
STATE_LEAVES = ("m",)


def sample_positions(idx, seq, n):
    """``n`` positions into the flattened ``B * seq`` tokens of a window's
    last minibatch, ``n / B`` a row and sorted inside it, seeded by the
    window's own row indices."""
    idx = numpy.asarray(idx)
    batch = idx.shape[1]
    per_row = max(int(n) // batch, 1)
    rng = numpy.random.Generator(numpy.random.PCG64(
        [int(v) & 0x7FFFFFFF for v in idx.ravel()]))
    return numpy.concatenate([
        r * seq + numpy.sort(rng.choice(seq, per_row, replace=False))
        for r in range(batch)]).astype(numpy.int32)


def _copies_to_host(net):
    """``lib/job.py:WindowCapture`` keeps its copy of the parameters from
    before the first checked window and of the optimizer state from after
    it on the device until the last checked window has run.  With AdamW's
    two moments that is as much again as the live state (6.1 GB beside
    6.1 GB at 510 M parameters), and a train window's program then finds no
    room on 16 GB.  So once both are held (before a second checked window)
    they go to the host, the state cut to the leaves that are read; ``leaf_numbers`` takes them
    from there.  (The capture is the object whose method stands in for
    the net's entry; PERF.md section 7 names the edit with which
    ``lib/job.py`` does this itself.)"""
    import jax
    held = getattr(getattr(net, ENTRY), "__self__", None)
    if getattr(held, "state1", None) is None:
        return
    held.p0 = jax.device_get(held.p0)
    held.state1 = [{name: {leaf: jax.device_get(st[leaf])
                           for leaf in STATE_LEAVES}
                    for name, st in layer.items()} for layer in held.state1]


def feed(trainer, idx_s, batch_sizes, hypers_s):
    """What one dispatch was given; asks the net for the last checked
    window's logits at the sampled positions (``keep`` withdraws the
    request)."""
    import jax
    net, loader_unit = trainer.net, trainer.loader_unit
    idx = numpy.array(idx_s, dtype=numpy.int64)
    sample = None
    loader_unit.bench_windows_fed += 1
    _copies_to_host(net)
    if loader_unit.bench_windows_fed == loader_unit.bench_check_windows:
        sample = sample_positions(idx, int(net.input_sample_shape[0]),
                                  loader_unit.bench_logit_samples)
    net.sample_positions = sample
    return {"idx": idx, "sizes": [int(s) for s in batch_sizes],
            "hypers": jax.tree.map(numpy.array, hypers_s),
            "sample": sample, "_net": net}


def keep(stats, rec):
    """Small numbers stay on the device; the sampled logits (gigabytes at
    a real size) come to the host at once."""
    import jax
    rec.pop("_net").sample_positions = None
    out = {k: stats[k] for k in ("loss", "n_err", "loss_sum")}
    out["logits"] = out["exit"] = None
    if rec["sample"] is not None:
        # taken out of what the trainer still holds, so that the device's
        # copy goes before the job works out its norms beside it
        out["logits"] = numpy.asarray(jax.device_get(
            stats.pop("logits_sample")))
        out["exit"] = numpy.asarray(jax.device_get(
            stats.pop("exit_sample")))
    return out


def fetch(st):
    return {"loss": numpy.asarray(st["loss"], numpy.float64).reshape(-1),
            "n_err": numpy.asarray(st["n_err"]).reshape(3),
            "loss_sum": float(st["loss_sum"]),
            "logits": st["logits"], "exit": st["exit"]}


def leaf_numbers(cfg, mix, p0, state1, params_end):
    return job.leaf_norms(p0, state1, params_end, [None] * len(p0),
                          STATE_LEAVES)


def first_epoch(decision):
    return {"tokens": list(decision.epoch_n_evaluated_samples),
            "rows": list(decision.epoch_rows)}


def release(net):
    net.run_window_indexed = None
    net.params = net.state = None
    net._data_d = net._labels_d = net._segments_d = None
    net._win_acc = None
    net._window_fns.clear()
    net._fwd_tokens_at = None


# -- comparison ---------------------------------------------------------------

GRADED = ("loss_worst_step", "logit_rel_diff", "exit_prob_diff",
          "m1_worst_leaf", "dparam_worst_leaf", "tok_err_gap")

#: (reading, mode, fault, least chips): the bf16 witness, the fp8 control,
#: and faults planted in the bf16 reference put in the program's place
READINGS = (("bf16", "bf16", None, 1), ("fp8", "fp8", None, 1),
            ("half_batch", "bf16", "half_batch", 1),
            ("state_unchanged", "bf16", "state_unchanged", 1),
            ("pass_left_out", "bf16", "pass_left_out", 1),
            ("no_doc_cut", "bf16", "no_doc_cut", 1))


def plan(cfg, mix):
    """The planned net: every application of every layer in a step."""
    return families.reference(cfg).plan(
        cfg["layers"], int(mix["seq_len"]), attended_pairs_per_row(mix))


def _passes(layers):
    for layer in layers:
        if layer["type"] == "loop":
            return int(layer["times"])
    return 1


def _host_gb():
    """(resident now, resident at most) of this process, in GB."""
    import resource
    with open("/proc/self/statm") as f:
        now = int(f.read().split()[1]) * resource.getpagesize()
    return now / 1e9, resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


def follow(cfg, mix, run, mode="f32", fault=None, chips=1, log=None):
    """The reference's own steps over the captured feed.  Faults:
    ``half_batch`` leaves the second half of every minibatch out (mean over
    the rest); ``state_unchanged`` takes no step; ``pass_left_out`` runs one
    pass fewer; ``no_doc_cut`` attends across document boundaries."""
    import jax
    import jax.numpy as jnp
    ref = families.reference(cfg)
    layers = cfg["layers"]
    if fault not in (None, "half_batch", "state_unchanged", "pass_left_out",
                     "no_doc_cut"):
        raise ValueError(fault)
    passes = _passes(layers)
    row_fn = ref.make_row(
        layers, mode,
        passes=passes - 1 if fault == "pass_left_out" else None,
        doc_cut=fault != "no_doc_cut")
    hyper = ref.hypers(layers)
    made = run["data"]
    seq = made["ids"].shape[1]
    batch = run["batch"]
    n_sample = int(mix["logit_samples"])
    none = numpy.full(seq, -1, numpy.int32)
    out = {"loss": [], "windows": [], "grad1": None, "m1": None}
    step_no = 0
    with jax.default_device(jax.devices()[0]):
        # the weights and a minibatch's gradient sum live on the device
        # beside the row's work (7 GB of whole float32 attention); the two
        # moments wait on the host and visit the device a layer at a time
        init = ref.init_params(layers, run["weight_seed"])
        params = jax.tree.map(jnp.asarray, init)
        m = [{k: numpy.zeros_like(a) for k, a in p.items()} for p in init]
        v = [{k: numpy.zeros_like(a) for k, a in p.items()} for p in init]
        del init    # 2 GB of host at a real size: drawn again at the end
        for w, win in enumerate(run["windows"]):
            # the logits are read where the program was asked for them: in
            # the last window (a seeded feed has no record of the asking)
            sample = win["sample"] if "sample" in win else (
                sample_positions(win["idx"], seq, n_sample)
                if w == len(run["windows"]) - 1 else None)
            per_row = 0 if sample is None else len(sample) // batch
            counts = numpy.zeros(3, numpy.int64)
            for k, (idx, size) in enumerate(zip(win["idx"], win["sizes"])):
                t0 = time.perf_counter()
                last = sample is not None and k == len(win["sizes"]) - 1
                rows = [int(r) for r in idx[:size] if r >= 0]
                # a row left out grades nothing (its logits are still read)
                kept = max(batch // 2, 1) if fault == "half_batch" \
                    else len(rows)
                graded = int(sum((made["labels"][r] >= 0).sum()
                                 for r in rows[:kept]))
                total = jax.tree.map(jnp.zeros_like, params)
                loss_sum, errors = 0.0, 0
                logits = exits = None
                for slot, r in enumerate(rows):
                    pos = (sample[slot * per_row:(slot + 1) * per_row]
                           - slot * seq) if last \
                        else numpy.zeros(1, numpy.int32)
                    total, aux = row_fn(
                        params, total, jnp.asarray(made["ids"][r]),
                        jnp.asarray(made["segments"][r]),
                        jnp.asarray(made["labels"][r] if slot < kept
                                    else none), jnp.asarray(pos))
                    loss_sum += float(aux["loss_sum"])
                    errors += int(aux["errors"])
                    if last:
                        # (T, n, V) and (T, n) of the window's last step,
                        # a row's share written where it stays (a second
                        # copy of 3 GB is what a concatenation would cost)
                        z = aux["logits"]
                        if logits is None:
                            logits = numpy.empty(
                                (z.shape[0], len(rows) * per_row,
                                 z.shape[2]),
                                numpy.float32)
                            exits = numpy.empty(logits.shape[:2],
                                                numpy.float32)
                        at = slice(slot * per_row, (slot + 1) * per_row)
                        logits[:, at] = jax.device_get(z)
                        exits[:, at] = jax.device_get(aux["exit"])
                        del z
                    del aux
                out["loss"].append(loss_sum / max(graded, 1))
                counts += (errors, graded, kept)
                if out["grad1"] is None:
                    out["grad1"] = {k2: v2 / max(graded, 1) for k2, v2
                                    in ref.leaf_norms(total).items()}
                step_no += 1
                if fault != "state_unchanged":
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
                    log("reference %s%s step %d: %.1f s; host %.1f GB now, "
                        "%.1f GB at most"
                        % ((mode, " " + fault if fault else "", step_no,
                            time.perf_counter() - t0) + _host_gb()))
            out["windows"].append({"n_err": counts, "logits": logits,
                                   "exit": exits})
            if out["m1"] is None:
                out["m1"] = ref.leaf_norms(m)
        del m, v
        out["dparam"] = ref.leaf_norms(ref.difference(
            params, jax.tree.map(jnp.asarray, ref.init_params(
                layers, run["weight_seed"]))))
    return out


def _rel_diff(prog, want):
    """Norm of the difference of two (T, n, V) logit samples over the
    reference's norm, each row centred over the vocabulary; a pass that
    one side lacks counts as that side repeating its last."""
    num = den = 0.0
    for t in range(want.shape[0]):
        # by blocks of positions: a pass's sample in float64 is 1.6 GB a
        # side at a real vocabulary
        for at in range(0, want.shape[1], 256):
            a = numpy.asarray(prog[min(t, prog.shape[0] - 1), at:at + 256],
                              numpy.float64)
            b = numpy.asarray(want[t, at:at + 256], numpy.float64)
            a -= a.mean(axis=1, keepdims=True)
            b -= b.mean(axis=1, keepdims=True)
            num += float(numpy.square(a - b).sum())
            den += float(numpy.square(b).sum())
    return float(numpy.sqrt(num / den))


def _exit_diff(prog, want):
    return max(float(numpy.abs(
        numpy.asarray(prog[min(t, prog.shape[0] - 1)], numpy.float64)
        - want[t]).max()) for t in range(want.shape[0]))


def graded(run, refout, limits):
    prog = run["program"]
    stats = [w["stats"] for w in run["windows"]]
    out = []
    losses = numpy.concatenate([st["loss"] for st in stats])
    out.append(("loss_worst_step", max(
        abs(lp - lr_) / abs(lr_) for lp, lr_ in zip(losses, refout["loss"])),
        limits["loss_worst_step"]))
    sampled = [(st, rw) for st, rw in zip(stats, refout["windows"])
               if rw["logits"] is not None]
    out.append(("logit_rel_diff", max(
        _rel_diff(st["logits"], rw["logits"]) for st, rw in sampled),
        limits["logit_rel_diff"]))
    out.append(("exit_prob_diff", max(
        _exit_diff(st["exit"], rw["exit"]) for st, rw in sampled),
        limits["exit_prob_diff"]))
    g, g_at = compare.worst_leaf(prog["m1"], refout["m1"], refout["grad1"])
    d, d_at = compare.worst_leaf(prog["dparam"], refout["dparam"],
                                 refout["grad1"])
    out.append(("m1_worst_leaf", g, limits["m1_worst_leaf"]))
    out.append(("dparam_worst_leaf", d, limits["dparam_worst_leaf"]))
    out.append(("tok_err_gap", max(
        abs(int(st["n_err"][0]) - int(rw["n_err"][0]))
        / max(int(rw["n_err"][1]), 1)
        for st, rw in zip(stats, refout["windows"])),
        limits["tok_err_gap"]))
    return out, {"m1_at": g_at, "dparam_at": d_at}


def numbers(run, refout, cfg, limits, net):
    """The graded numbers, then the exact counts (limit 0): rows and graded
    tokens per window and per epoch, train and validation, and the
    hyperparameter feed."""
    out, where = graded(run, refout, limits)
    rows_gap = tok_gap = 0
    for win, rw in zip(run["windows"], refout["windows"]):
        got = win["stats"]["n_err"]
        tok_gap = max(tok_gap, abs(int(got[1]) - int(rw["n_err"][1])))
        rows_gap = max(rows_gap, abs(int(got[2]) - int(rw["n_err"][2])))
    out.append(("window_rows_gap", float(rows_gap), 0.0))
    out.append(("window_tokens_gap", float(tok_gap), 0.0))
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
    were."""
    wins, at = [], 0
    for win, rw in zip(run["windows"], refout["windows"]):
        k = len(win["sizes"])
        wins.append(dict(win, stats={
            "loss": numpy.asarray(refout["loss"][at:at + k]),
            "n_err": numpy.asarray(rw["n_err"]),
            "logits": rw["logits"], "exit": rw["exit"]}))
        at += k
    return dict(run, windows=wins, program={"m1": refout["m1"],
                                            "dparam": refout["dparam"]})


# -- the rate's unit of work --------------------------------------------------

def rows_trained(mix, epochs):
    return int(epochs) * int(mix["n_train"])


def row_tokens(cfg, mix):
    return int(mix["seq_len"])
