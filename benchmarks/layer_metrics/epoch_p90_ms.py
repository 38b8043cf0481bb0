"""90th percentile of the window's epoch times (host clock, taken where
the decision ends an epoch)."""
import numpy


def read(ctx):
    times = ctx["epoch_times"]
    if len(times) < 3:
        return None
    return 1e3 * float(numpy.percentile(times, 90))
