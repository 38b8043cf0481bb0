"""Device time under the ``L*.moe_dispatch`` and ``L*.moe_combine`` scopes:
what an expert layer spends outside its products (the top-k, the sort by
expert, the gather into that order and the weighted sum back), forward,
recomputed and backward, per train step."""
from benchmarks.lib import scoped_trace


def read(ctx):
    return scoped_trace.train_ms_per_step(
        ctx, lambda scope: scoped_trace.kind_of(scope) in (
            "moe_dispatch", "moe_combine"))
