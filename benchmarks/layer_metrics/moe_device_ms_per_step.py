"""Device time under the ``L*.router`` and ``L*.moe*`` scopes (the router's
product; choosing, sorting and gathering; the grouped products; the
weighted sum back), forward, recomputed and backward, per train step."""
from benchmarks.lib import scoped_trace

KINDS = ("router", "moe", "moe_dispatch", "moe_experts", "moe_combine")


def read(ctx):
    return scoped_trace.train_ms_per_step(
        ctx, lambda scope: scoped_trace.kind_of(scope) in KINDS)
