"""Device time under the ``L*.moe_shared`` scopes (the three products of the
expert every token passes, inside the ``moe`` entry), forward, recomputed
and backward, per train step.  None of a program that has no such scope."""
from benchmarks.lib import scoped_trace


def read(ctx):
    return scoped_trace.train_ms_per_step(
        ctx, lambda scope: scoped_trace.kind_of(scope) == "moe_shared")
