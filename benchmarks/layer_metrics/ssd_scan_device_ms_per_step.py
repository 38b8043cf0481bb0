"""Device time under the ``L*.ssd_scan`` scopes alone (everything of a
mixer from its steps ``dt`` to the scan's output ``y``: the decays, the
intra-chunk products, the chunks' states and their recurrence, the carried
state's part, ``D x``), forward, recomputed and backward, per train step.
None of a program that has no such scope."""
from benchmarks.lib import scoped_trace


def read(ctx):
    return scoped_trace.train_ms_per_step(
        ctx, lambda scope: scoped_trace.kind_of(scope) == "ssd_scan")
