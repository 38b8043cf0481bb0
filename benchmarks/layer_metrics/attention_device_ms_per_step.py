"""Device time under the ``L*.attention`` scopes, forward, recomputed and
backward, per train step (the train window programs' ops)."""
from benchmarks.lib import scoped_trace


def read(ctx):
    return scoped_trace.train_ms_per_step(
        ctx, lambda scope: scoped_trace.kind_of(scope) == "attention")
