"""The state-space mixers' least time for a train step
(``layer_costs/ssd_mixer.py``: the two projections and the chunked scan's
products over the causal pairs of every chunk, forward and backward, the
larger of FLOPs over the peak and bytes over the bandwidth, entry by
entry) over the device time under the ``L*.ssd*`` scopes.  The recomputed
forward is in the time and not in the required work."""
from benchmarks import layer_costs
from benchmarks.layer_metrics import ssd_device_ms_per_step


def read(ctx):
    have = ssd_device_ms_per_step.read(ctx)
    net = [ent for ent in ctx["net"] if ent["kind"] == "ssd_mixer"]
    if not have or not net:
        return None
    per_chip = ctx["batch"] // int(ctx["cell"]["chips"])
    least, _ = layer_costs.least_seconds(
        [dict(ent, update=False) for ent in net], per_chip, ctx["peaks"])
    return 100.0 * 1e3 * least / have
