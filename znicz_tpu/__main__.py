"""``python -m znicz_tpu`` — the workflow CLI (the veles launcher's
user-facing contract: run a workflow module with config overrides).

Examples::

    python -m znicz_tpu wine
    python -m znicz_tpu znicz_tpu.samples.mnist \
        --config mnistr.decision.max_epochs=3
    python -m znicz_tpu samples/mnist.py --snapshot snap.pickle
    python -m znicz_tpu mnist --testing
    python -m znicz_tpu --list
    python -m znicz_tpu serve --latest wine --port 8899
    python -m znicz_tpu profile wine --out /tmp/trace
    python -m znicz_tpu profile http://127.0.0.1:8899 --seconds 5

The ``serve`` subcommand hands off to the online inference tier
(:mod:`znicz_tpu.serving`): a snapshot or deployment package served
over HTTP with dynamic micro-batching — see ``serve --help`` and
docs/serving.md.  The ``profile`` subcommand drives the performance
introspection layer (:mod:`znicz_tpu.core.profiler`): run a workflow
under the profiler, or hit a running server's
``GET /debug/profile?seconds=N`` — see docs/observability.md.
"""

import argparse
import ast
import sys

from znicz_tpu.core.config import root
from znicz_tpu.launcher import list_samples, run_workflow


def apply_override(root_cfg, assignment):
    """Apply one ``dotted.path=value`` override onto the config root
    (delegates to the ONE shared parser in core/config.py — the serve
    CLI's ``--config`` uses the same rule)."""
    from znicz_tpu.core.config import apply_override as _apply
    _apply(assignment, root_cfg=root_cfg)


def _generic_population_evaluator(sites):
    """DEFAULT fused GA path: find the
    top-level config namespace whose subtree holds every Range site
    (a StandardWorkflow sample's root.<ns> with layers + loader_name)
    and build the generic vmapped evaluator for it — no sample-file
    opt-in needed.  Returns None (with a printed reason) when the
    sample/sites are not fusable; the serial path remains the general
    fallback."""
    from znicz_tpu.parallel.population import workflow_population_evaluator
    from znicz_tpu.core.genetics import enumerate_ranges
    want = {(id(c), k) for c, k, _ in sites}
    try:
        for name, node in root.items():
            if not isinstance(node, type(root)):
                continue
            if "layers" not in node or "loader_name" not in node:
                continue
            found = {(id(c), k) for c, k, _ in enumerate_ranges(node)}
            if found and found == want:
                ev = workflow_population_evaluator(node, sites,
                                                   verbose=True)
                if ev is not None:
                    print("fused GA: vmapping each generation over "
                          "root.%s (generic Range-site mapping)" % name)
                return ev
    except Exception as e:  # the serial path is the promised fallback
        print("fused GA unavailable (%s); evaluating serially" % e)
        return None
    print("fused GA unavailable: no single sample namespace holds all "
          "Range sites; evaluating serially")
    return None


def run_genetics(module, spec, fused=None):
    """--optimize GENSxPOP: evolve the Range values found anywhere under
    the config root (the reference's GA tier, SURVEY.md §3.5 —
    samples/MNIST/mnist_config.py:62 declares Range sites the same way).
    The whole generation trains as ONE vmapped XLA computation whenever
    the sites map onto fused hyper slots (any registered sample —
    generic path); otherwise each fitness evaluation is a full training
    run of the workflow (fused when ``--fused`` is given)."""
    from znicz_tpu.core.genetics import GeneticsOptimizer, enumerate_ranges
    from znicz_tpu.launcher import run_workflow
    gens_s, _, pop_s = spec.partition("x")
    try:
        gens = int(gens_s or 4)
        pop = int(pop_s or 8)
    except ValueError:
        raise SystemExit("--optimize wants GENSxPOP (e.g. 4x8), got %r"
                         % spec)
    if gens < 1 or pop < 1:
        raise SystemExit("--optimize needs at least 1 generation and 1 "
                         "individual, got %r" % spec)
    if not enumerate_ranges(root):
        raise SystemExit(
            "--optimize needs Range(...) values in the config; e.g. "
            'root.myns.learning_rate = Range(0.01, 0.001, 0.1)')

    # fused population path: a sample-level population_evaluator factory
    # takes precedence (it may carry sample-specific epochs/seeds); the
    # generic Range-site mapping is the default for everything else
    evaluate_population = None
    factory = getattr(module, "population_evaluator", None)
    if factory is not None:
        # a factory that returns None already probed (and logged) its
        # namespace — do not re-initialize the dataset loader generically
        try:
            evaluate_population = factory(enumerate_ranges(root))
        except Exception as e:
            print("sample population evaluator unavailable (%s); "
                  "evaluating serially" % e)
    else:
        evaluate_population = _generic_population_evaluator(
            enumerate_ranges(root))
    if evaluate_population is not None and fused:
        print("note: --fused K=V settings do not apply to the vmapped "
              "population path (it is already fused; pass a "
              "population_evaluator for custom control)")

    metric = {"label": "-err%"}  # the vmapped path scores -err% always

    def evaluate(_cfg):
        wf = run_workflow(module, fused=fused)
        decision = getattr(wf, "decision", None)
        err = None
        if decision is not None:
            pts = getattr(decision, "best_n_err_pt", None)
            if pts is not None:
                err = pts[1] if pts[1] is not None else pts[2]
            if err is None:
                # MSE decisions track [avg, max, min] mse instead of
                # error percent — fitness is the best (VALID, else
                # TRAIN) average mse
                bm = getattr(decision, "best_metrics", None)
                if bm is not None:
                    for clazz in (1, 2):
                        if bm[clazz] is not None:
                            err = bm[clazz][0]
                            metric["label"] = "-avg_mse"
                            break
        if err is None:
            raise SystemExit("workflow exposes no error metric to "
                             "optimize against")
        return -float(err)

    opt = GeneticsOptimizer(evaluate, root, generations=gens,
                            population_size=pop,
                            evaluate_population=evaluate_population)
    values, fitness = opt.run()
    print("best fitness (%s): %.4f" % (metric["label"], fitness))
    for (container, key, rng), value in zip(opt.sites, values):
        print("  %s = %s  (range %s..%s)" % (key, value, rng.min_value,
                                             rng.max_value))
    return 0


def main(argv=None):
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "serve":
        # the serving tier has its own flag set — dispatch before the
        # training parser can reject them
        from znicz_tpu.serving.server import main as serve_main
        return serve_main(argv[1:])
    if argv and argv[0] == "profile":
        # performance introspection: capture a device trace from a
        # running server (URL target) or run a workflow under the full
        # profiler stack (core/profiler.py)
        from znicz_tpu.core.profiler import cli_main as profile_main
        return profile_main(argv[1:])
    if argv and argv[0] == "obs":
        # durable blackbox queries: merged cross-process timeline,
        # --rid request reconstruction, cross-restart --rate, and
        # --postmortem bundles (core/blackbox.py)
        from znicz_tpu.core.blackbox import cli_main as obs_main
        return obs_main(argv[1:])
    parser = argparse.ArgumentParser(
        prog="python -m znicz_tpu",
        description="Run a znicz_tpu workflow (module path, file, or "
                    "sample name); 'python -m znicz_tpu serve ...' "
                    "starts the inference server instead.")
    parser.add_argument("workflow", nargs="?",
                        help="dotted module, .py file, or sample name")
    parser.add_argument("--config", action="append", default=[],
                        metavar="KEY=VALUE",
                        help="config-root override, e.g. "
                             "wine.decision.max_epochs=5")
    parser.add_argument("--snapshot", help="snapshot file to resume from")
    parser.add_argument("--auto-resume", action="store_true",
                        help="elastic recovery: restore the newest "
                             "matching snapshot (if any) and continue "
                             "training — safe to use as the default "
                             "launch mode of a supervised job")
    parser.add_argument("--max-restarts", type=int, default=0,
                        metavar="N",
                        help="supervised mode: catch a crashed run, "
                             "back off, and re-enter with auto-resume "
                             "up to N times (mid-epoch snapshots — "
                             "snapshotter window_interval — make the "
                             "re-entry resume mid-epoch)")
    parser.add_argument("--restart-backoff-ms", type=float,
                        default=1000.0, metavar="MS",
                        help="supervised-restart backoff base (doubles "
                             "per attempt, capped at 30 s)")
    parser.add_argument("--testing", action="store_true",
                        help="forward-only run (reference --test)")
    parser.add_argument("--dry-run", action="store_true",
                        help="build + initialize only")
    parser.add_argument("--dump-graph", metavar="FILE.dot",
                        help="write the workflow control graph as DOT; "
                             "skips training unless combined with "
                             "--testing")
    parser.add_argument("--optimize", metavar="GENSxPOP",
                        help="genetic hyperparameter search over Range "
                             "values in the config (e.g. 4x8 = 4 "
                             "generations, population 8); fitness is "
                             "-validation error")
    parser.add_argument("--parity", action="store_true",
                        help="real-data accuracy parity run: provision "
                             "the dataset (network required), train the "
                             "published config, print the BASELINE.md "
                             "comparison row")
    parser.add_argument("--fused", nargs="?", const=True, default=None,
                        metavar="K=V[,K=V...]",
                        help="fused execution mode: compile the whole "
                             "per-minibatch train step to one SPMD XLA "
                             "computation (e.g. --fused "
                             "mesh=8,model_parallel=2,pool_impl=gather)")
    parser.add_argument("--list", action="store_true",
                        help="list bundled samples and exit")
    args = parser.parse_args(argv)

    if args.list:
        from znicz_tpu.samples import MANIFESTS
        for name in list_samples():
            meta = MANIFESTS.get(name)
            if meta:
                print("%-24s %-22s baseline: %s"
                      % (name, meta["workflow"],
                         meta["baseline"] or "-"))
            else:
                print(name)
        return 0
    if not args.workflow:
        parser.error("workflow required (or --list)")
    # import FIRST: sample modules install their root.<ns> defaults at
    # import time, which would clobber any override applied before it
    from znicz_tpu.launcher import resolve_workflow_module
    module = resolve_workflow_module(args.workflow)
    for assignment in args.config:
        apply_override(root, assignment)
    fused = args.fused
    if isinstance(fused, str):
        cfg = {}
        for pair in fused.split(","):
            key, sep, raw = pair.partition("=")
            if not sep:
                parser.error("--fused wants K=V pairs, got %r" % pair)
            try:
                cfg[key.strip()] = ast.literal_eval(raw)
            except (ValueError, SyntaxError):
                cfg[key.strip()] = raw
        fused = cfg
    if args.parity:
        if args.optimize or args.snapshot or args.testing or \
                args.dry_run or args.dump_graph:
            parser.error("--parity runs the published training config "
                         "standalone")
        from znicz_tpu import parity
        # the module is already resolved — accept any spelling the CLI
        # accepts ('mnist', 'znicz_tpu.samples.mnist', 'samples/mnist.py').
        # Parity trains on the fused path by default; --fused K=V
        # overrides its config (e.g. --fused window=1).
        parity.run_parity(module.__name__.rsplit(".", 1)[-1],
                          fused=fused if fused is not None else "auto")
        return 0
    if args.optimize:
        if args.snapshot or args.testing or args.dry_run or \
                args.dump_graph:
            parser.error("--optimize cannot be combined with --snapshot/"
                         "--testing/--dry-run/--dump-graph")
        if args.max_restarts > 0:
            # loud, not silently inert: the genetics sweep is not
            # supervised
            parser.error("--optimize cannot be combined with "
                         "--max-restarts")
        return run_genetics(module, args.optimize, fused=fused)
    dry_run = args.dry_run or (bool(args.dump_graph) and not args.testing)
    if args.max_restarts > 0:
        from znicz_tpu.launcher import run_supervised
        wf = run_supervised(module, max_restarts=args.max_restarts,
                            restart_backoff_ms=args.restart_backoff_ms,
                            snapshot=args.snapshot, testing=args.testing,
                            dry_run=dry_run, fused=fused,
                            auto_resume=args.auto_resume)
    else:
        wf = run_workflow(module, snapshot=args.snapshot,
                          testing=args.testing, dry_run=dry_run,
                          fused=fused, auto_resume=args.auto_resume)
    if args.dump_graph:
        wf.dump_graph(args.dump_graph)
    decision = getattr(wf, "decision", None)
    if decision is not None and hasattr(decision, "best_n_err_pt"):
        print("best val/train err%%: %s" % (decision.best_n_err_pt,))
    return 0


if __name__ == "__main__":
    sys.exit(main())
