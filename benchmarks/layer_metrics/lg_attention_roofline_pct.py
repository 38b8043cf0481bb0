"""The local and global attention entries' least time for a train step
(required FLOPs over the peak or least bytes over the bandwidth, whichever
is longer, forward and backward; the (query, key) pairs inside the window
and the document only) over the device time under the ``L*.attention``
scopes: the lowering's share of its roofline."""
from benchmarks import layer_costs
from benchmarks.layer_metrics import lg_attention_device_ms_per_step


def read(ctx):
    have = lg_attention_device_ms_per_step.read(ctx)
    net = [ent for ent in ctx["net"] if ent["kind"] == "local_attention"]
    if not have or not net:
        return None
    per_chip = ctx["batch"] // int(ctx["cell"]["chips"])
    least, _ = layer_costs.least_seconds(
        [dict(ent, update=False) for ent in net], per_chip, ctx["peaks"])
    return 100.0 * 1e3 * least / have
