"""The shared experts' least time for a train step (``layer_costs/
shared_moe.py:shared_products`` over every token of the step, forward and
backward, the larger of FLOPs over the peak and bytes over the bandwidth)
over the device time under the ``L*.moe_shared`` scopes.  The recomputed
forward is in the time and not in the required work."""
from benchmarks import layer_costs
from benchmarks.layer_metrics import shared_expert_device_ms_per_step


def read(ctx):
    have = shared_expert_device_ms_per_step.read(ctx)
    net = [ent for ent in ctx["net"] if ent["kind"] == "shared_moe"]
    if not have or not net:
        return None
    module = layer_costs.module_for("shared_moe")
    peaks = ctx["peaks"]
    per_chip = ctx["batch"] // int(ctx["cell"]["chips"])
    least = 0.0
    for ent in net:
        flops, nbytes = module.shared_products(ent, per_chip * ent["seq"])
        for times in (1.0, 2.0):    # forward, backward
            least += max(times * flops / peaks["flops_per_s"],
                         times * nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least / have
