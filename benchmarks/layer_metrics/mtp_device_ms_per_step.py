"""Device time under the scopes of the multi-token prediction module's
leaves (``L<i>.<kind>`` of every leaf the plan marks ``mtp``: the way in,
the module's decoder layer and its head), forward, recomputed and backward,
per train step.  None of a program that has no such leaf."""
import re

from benchmarks.lib import scoped_trace

INDEX = re.compile(r"^L(\d+)\.")


def read(ctx):
    mine = {ent["spec"] for ent in ctx["net"] if ent.get("mtp")}
    if not mine:
        return None

    def wanted(scope):
        m = INDEX.match(scope)
        return bool(m) and int(m.group(1)) in mine

    return scoped_trace.train_ms_per_step(ctx, wanted)
