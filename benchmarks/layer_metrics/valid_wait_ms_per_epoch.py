"""The host blocked on a validation minibatch, per epoch: the
``trainer.readback`` that stands under ``trainer.valid``.  It waits for the
padded minibatch's host to device copy (``trainer.valid.place`` only
enqueues it) and for the inference forward, then fetches the output: the
metric a change to the validation copy should move."""
from benchmarks.lib import program_spans as ps


def read(ctx):
    run = ps.of_run(ctx)
    if run is None:
        return None
    return ps.total_ms(
        run["spans"],
        ps.under(run["ring"], "trainer.readback", "trainer.valid"),
        run["n"])
