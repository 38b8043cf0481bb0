"""Job families: what one kind of training job needs of the harness.

A configuration names its family (``"family"`` in ``configs/<name>.json``)
and the harness finds ``families/<family>.py`` by that name, the way it
finds references, cost modules and metric readers.  ``lib/job.py`` drives
every family alike (build, seed, warm, window, trace, memory); what the rows
are, what is kept of a train window, and what is compared with the plain
reference is the family's.  ``CONTRACT`` lists the names a family module
supplies; ``benchmarks/README.md`` says what each has to answer.
"""

import importlib

#: the names every family module supplies, in the README's order
CONTRACT = (
    # data
    "make_data", "loader",
    # capture
    "ENTRY", "feed", "keep", "fetch", "STATE_LEAVES", "leaf_numbers",
    "first_epoch", "release",
    # comparison
    "plan", "follow", "graded", "numbers", "GRADED", "READINGS", "in_place",
    # the rate's unit of work
    "rows_trained", "row_tokens",
)


def load(cfg):
    """The family module a configuration names."""
    name = cfg.get("family")
    if not name:
        raise SystemExit("%s: the configuration names no family"
                         % cfg.get("name"))
    try:
        mod = importlib.import_module("%s.%s" % (__name__, name))
    except ModuleNotFoundError as exc:
        if exc.name != "%s.%s" % (__name__, name):
            raise
        raise SystemExit("%s: no family module benchmarks/families/%s.py"
                         % (cfg.get("name"), name)) from exc
    missing = [n for n in CONTRACT if not hasattr(mod, n)]
    if missing:
        raise SystemExit("family %s lacks %s" % (name, ", ".join(missing)))
    return mod


def reference(cfg):
    """The plain reference a configuration names (``reference/<name>.py``)."""
    return importlib.import_module(
        "benchmarks.reference." + cfg["reference"])
