"""Share of the busiest device's busy time in ops that carry no scope
(copies the layout pass adds, fusions across scopes, every program's ops
counted): how complete the attribution is."""
from benchmarks.lib import scoped_trace


def read(ctx):
    red = scoped_trace.of_run(ctx)
    if red is None or red["busy_s"] <= 0:
        return None
    unscoped = sum(s for (_, scope, _), s in red["by_scope"].items()
                   if scope is None)
    return 100.0 * unscoped / red["busy_s"]
