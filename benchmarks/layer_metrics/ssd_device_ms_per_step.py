"""Device time under the ``L*.ssd`` scopes and their sub-scopes
(``ssd_conv``, ``ssd_scan``, ``ssd_norm``: the two projections, the conv,
the chunked scan, the gate and the group norm), forward, recomputed and
backward, per train step.  None of a program that has no such scope."""
from benchmarks.lib import scoped_trace

KINDS = ("ssd", "ssd_conv", "ssd_scan", "ssd_norm")


def read(ctx):
    return scoped_trace.train_ms_per_step(
        ctx, lambda scope: scoped_trace.kind_of(scope) in KINDS)
