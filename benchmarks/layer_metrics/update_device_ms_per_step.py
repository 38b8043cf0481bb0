"""Device time, per train step, of what a step does beside its layers: the
optimizer (``update.*``), the gradient exchange, the row gather, the epoch
accumulators, the loss and the in-scan evaluator statistics."""
from benchmarks.lib import scoped_trace

SCOPES = ("grad_exchange", "gather", "acc", "loss", "eval_stats")


def read(ctx):
    return scoped_trace.train_ms_per_step(
        ctx, lambda scope: scope in SCOPES or scope.startswith("update."))
