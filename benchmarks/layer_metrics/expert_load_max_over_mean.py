"""The most pairs an expert held here took in one step (``moe.load_max``)
over the mean a held expert took a step (``moe.pairs_held`` over the steps
counted and the experts held): what the busiest expert's group of rows is
against an even load's.  From the program's counters, over the whole run."""
from benchmarks.layer_metrics import moe_experts_roofline_pct


def read(ctx):
    from znicz_tpu.core import telemetry
    pairs = moe_experts_roofline_pct.pairs_per_step(ctx)
    held = sum(ent["held"] for ent in ctx["net"] if ent["kind"] == "moe")
    most = telemetry.gauge("moe.load_max").value
    if pairs is None or not held or not most:
        return None
    return float(most) * held / pairs
