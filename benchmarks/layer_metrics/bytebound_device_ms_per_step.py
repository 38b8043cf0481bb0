"""Device time, per train step, under the scopes of the layer kinds whose
least time the byte bound sets (``benchmarks/layer_costs``)."""
from benchmarks.lib import scoped_trace

KINDS = ("lrn", "pool", "activation", "dropout", "zerofill")


def read(ctx):
    return scoped_trace.train_ms_per_step(
        ctx, lambda scope: scoped_trace.kind_of(scope) in KINDS)
