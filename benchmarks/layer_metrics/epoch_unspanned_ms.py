"""Wall time of an epoch that no leaf span covers: what the measurement
still cannot name."""
from benchmarks.lib import program_spans as ps


def read(ctx):
    run = ps.of_run(ctx)
    if run is None or not any(s[0] == "trainer.dispatch"
                              for s in run["spans"]):
        return None
    return ps.unspanned_ns(run["ring"], run["lo"], run["hi"]) / 1e6 / run["n"]
