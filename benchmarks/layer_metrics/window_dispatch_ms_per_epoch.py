"""``trainer.dispatch`` per epoch: the host's time inside the compiled
window's call (argument handling, enqueue on every device)."""
from benchmarks.lib import program_spans as ps


def read(ctx):
    run = ps.of_run(ctx)
    if run is None:
        return None
    return ps.total_ms(run["spans"], ps.named("trainer.dispatch"), run["n"])
