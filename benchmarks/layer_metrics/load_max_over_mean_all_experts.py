"""The most tokens any one expert of any expert layer took in one step, of
ALL the layer's experts, held here or not (the program's gauge
``moe.load_max_all``: of the steps since the last train readback, so at
the run's end of the timed window's last epoch), over the mean an expert
takes (``top_k`` times the step's tokens over the experts): what the
selection bias acts on, after it has acted for the whole run.  From the
program's counters; None of a program that keeps no such gauge."""


def read(ctx):
    from znicz_tpu.core import telemetry
    most = telemetry.gauge("moe.load_max_all").value
    net = [ent for ent in ctx["net"] if ent["kind"] == "shared_moe"]
    if not most or not net:
        return None
    ent = net[0]
    mean = float(ent["top_k"]) * ctx["batch"] * ent["seq"] / ent["experts"]
    return float(most) / mean
