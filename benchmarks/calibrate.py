"""Read the two ends every limit is set between, on the chip, at the cell's
own size, over many seeds in one process (set-up is long; no measured
window is needed for a training cell's readings).

    python3 benchmarks/calibrate.py <cell> --seeds 12 --control-seeds 3

For each seed the cell is built and warmed exactly as ``run.py`` does it
(the window is cut to one epoch), and the numbers of the configuration's
family are read for the program and for each of the family's ``READINGS``
(for ``classifier_rows``):

* ``program``  — the timed path against the float32 reference (lower end);
* ``bf16``     — the reference itself in bfloat16 against float32 (a second
  witness of what sound bf16 arithmetic reads);
* ``fp8``      — the control: the reference in float8_e4m3fn put in the
  program's place (upper end);
* ``half_batch`` / ``no_exchange`` — faults planted in the bf16 reference put
  in the program's place.

One JSON line per seed and reading goes to ``chiprun_out/`` and a summary of
minima and maxima to standard output.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as run_mod   # noqa: E402


def log(msg):
    print("[calibrate] %s" % msg, file=sys.stderr, flush=True)


def seeded_feed(fam, cfg, mix, seed):
    """What a family's ``follow`` needs of a run, with no program behind it:
    the seeded data set and the first ``check_windows`` windows of a seeded
    shuffle of the training rows.  A fault planted in the reference is read
    against the reference, so this is all its reading takes (and one chip,
    whatever the cell asks for)."""
    import numpy
    from benchmarks.lib import data
    n_train, n_valid = int(mix["n_train"]), int(mix["n_valid"])
    k, batch = int(mix["window"]), int(mix["minibatch"])
    rng = numpy.random.Generator(numpy.random.PCG64(
        data.sub_seed(seed, data.TAG_SHUFFLE)))
    n_win = int(mix["check_windows"])
    # a fresh shuffle for every epoch the windows reach into
    order = n_valid + numpy.concatenate([
        rng.permutation(n_train)
        for _ in range(-(-n_win * k * batch // n_train))])
    windows = [{"idx": order[w * k * batch:(w + 1) * k * batch].reshape(
        k, batch), "sizes": [batch] * k} for w in range(n_win)]
    return {"windows": windows, "batch": batch,
            "data": fam.make_data(seed, cfg, mix),
            "weight_seed": data.sub_seed(seed, data.TAG_WEIGHTS),
            "dropout_seed": data.sub_seed(seed, data.TAG_DROPOUT)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147484000)
    ap.add_argument("--readings", default=None,
                    help="which of the control seeds' readings to take, "
                    "comma-separated (default: all the family's; a four-chip "
                    "cell's reference is its one-chip twin's, so there only "
                    "no_exchange is new)")
    ap.add_argument("--reference-only", action="store_true",
                    help="no program run: the control seeds' readings of "
                    "the reference put in the program's place, on a seeded "
                    "feed (graded numbers only)")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at a tiny size")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"),
                    help="directory for the JSON lines")
    args = ap.parse_args(argv)

    run_mod.place_cache()
    cell, cfg, mix, limits, _ = run_mod.resolve(args.cell)
    if args.tiny:
        from benchmarks import rehearse
        mix = rehearse.tiny_mix(mix)
    elif args.reference_only:
        run_mod.device_check(dict(cell, chips=1))
    else:
        run_mod.device_check(cell)
    from benchmarks import families
    from benchmarks.lib import job
    fam = families.load(cfg)
    net = fam.plan(cfg, mix)
    chips = int(cell["chips"])
    wanted = [r[0] for r in fam.READINGS] if args.readings is None \
        else args.readings.split(",")
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "calibrate_%s.jsonl" % cell["name"])
    readings = {}
    with open(path, "a") as sink:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            t0 = time.perf_counter()
            if args.reference_only:
                run = seeded_feed(fam, cfg, mix, seed)
                cases = []
            else:
                run = job.run_cell(cell, cfg, mix, seed, 0.0, False, ROOT,
                                   time.perf_counter(), log)
                cases = [("program", run)]
            f32 = fam.follow(cfg, mix, run, chips=chips)
            if i < args.control_seeds:
                for name, mode, fault, least_chips in fam.READINGS:
                    if name not in wanted or chips < least_chips:
                        continue
                    other = fam.follow(cfg, mix, run, mode=mode,
                                       fault=fault, chips=chips)
                    cases.append((name, fam.in_place(run, other)))
            for name, case in cases:
                if args.reference_only:
                    nums, where = fam.graded(case, f32, limits)
                else:
                    nums, where = fam.numbers(case, f32, cfg, limits, net)
                row = {"cell": cell["name"], "seed": seed, "reading": name,
                       "numbers": {n: v for n, v, _ in nums},
                       "failed": [n for n, v, lim in nums if not v <= lim],
                       "where": where}
                sink.write(json.dumps(row) + "\n")
                sink.flush()
                for n, v, _ in nums:
                    readings.setdefault(name, {}).setdefault(n, []).append(v)
            log("seed %d done in %.0f s" % (seed, time.perf_counter() - t0))
    for name, nums in readings.items():
        for n, vals in nums.items():
            print("%-12s %-24s n=%2d min %.6g max %.6g"
                  % (name, n, len(vals), min(vals), max(vals)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
