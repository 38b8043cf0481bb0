"""Device time under the ``L*.latent_attention`` scopes (the five products,
the two norms, the rotary and the attention kernel's calls), forward,
recomputed and backward, per train step.  None of a program that has no
such scope."""
from benchmarks.lib import scoped_trace


def read(ctx):
    return scoped_trace.train_ms_per_step(
        ctx, lambda scope: scoped_trace.kind_of(scope) == "latent_attention")
