"""One reader per per-layer metric, found by the metric's name.

``read(ctx)`` takes the run's context (see ``run.py``: the job's counters
and clocks, the reduced trace, the planned net, the peaks) and returns the
value, or None where it finds nothing to read; it never returns 0 for a
share of a roofline or of a peak.
"""
