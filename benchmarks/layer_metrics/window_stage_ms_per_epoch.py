"""Self time of ``trainer.collect`` and ``trainer.place`` per epoch: staging
a train window and putting it on the device (the loader's fills inside the
collection are ``loader.fill``'s, and are left out)."""
from benchmarks.lib import program_spans as ps


def read(ctx):
    run = ps.of_run(ctx)
    if run is None:
        return None
    return ps.total_ms(run["spans"],
                       ps.named("trainer.collect", "trainer.place"),
                       run["n"], own=run["own"])
