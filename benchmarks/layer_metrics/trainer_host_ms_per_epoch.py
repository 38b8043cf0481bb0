"""The host's own work inside the fused trainer, per epoch: the
``unit.<trainer>`` spans less the time the host was blocked on the device in
``trainer.wait`` and ``trainer.readback``."""
from benchmarks.lib import program_spans as ps


def read(ctx):
    run = ps.of_run(ctx)
    if run is None:
        return None
    unit = ps.total_ms(run["spans"], ps.named("unit." + ctx["trainer_name"]),
                       run["n"])
    blocked = ps.total_ms(run["spans"],
                          ps.named("trainer.wait", "trainer.readback"),
                          run["n"])
    if unit is None or blocked is None:
        return None
    return unit - blocked
