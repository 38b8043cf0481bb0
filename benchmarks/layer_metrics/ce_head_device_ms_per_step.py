"""Device time under the ``L*.lm_head`` scope of a plain cross-entropy head
(final norm, the blocked output product, the loss's pieces), forward,
recomputed and backward, per train step."""
from benchmarks.lib import scoped_trace


def read(ctx):
    if not any(ent["kind"] == "ce_head" for ent in ctx["net"]):
        return None
    return scoped_trace.train_ms_per_step(
        ctx, lambda scope: scoped_trace.kind_of(scope) == "lm_head")
