"""Set-up's time inside ``trainer.dispatch`` and ``trainer.valid.dispatch``:
the calls of the step programs before the timed epochs.  A program's first
call traces it and compiles it or loads it from the cache, on the host and
blocking; a later call takes milliseconds (``window_dispatch_ms_per_epoch``).
"""
from benchmarks.lib import program_spans as ps


def read(ctx):
    run = ps.of_run(ctx)
    if run is None:
        return None
    calls = [s[2] for s in run["ring"] if s[1] < run["lo"]
             and s[0] in ("trainer.dispatch", "trainer.valid.dispatch")]
    if not calls:
        return None
    return sum(calls) / 1e9
