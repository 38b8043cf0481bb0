"""What every family's comparison shares.

A family (``benchmarks/families/<family>.py``) drives its plain reference
over the very feed the timed path's own call was given during set-up
(``follow``) and sets what the program did beside what the reference did,
each number with its limit from the cell's limits file (``numbers``).  Here
are the measures those share: the worst leaf's gap of norms, the
hyperparameters a step should have been fed, and the verdict.  Nothing here
imports the program.
"""

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


def decide(nums):
    return all(numpy.isfinite(v) and v <= lim for _, v, lim in nums)
