"""The grouped products' least time for a train step (``layer_costs/moe.py:
products``, forward and backward, the larger of FLOPs over the peak and
bytes over the bandwidth) over the device time under the ``L*.moe_experts``
scopes.  The pairs are the run's own count, not the even load's: the
program's counters ``moe.pairs_held`` over the train rows it counted
(``trainer.rows``), a mean over the whole run's steps (the counters do not
know where the window starts; the weights move little in a run's steps)."""
from benchmarks import layer_costs
from benchmarks.lib import scoped_trace


def pairs_per_step(ctx):
    """Pairs (token, expert held here) a train step, summed over the expert
    layers, as the program counted them; None where it counts none."""
    from znicz_tpu.core import telemetry
    pairs = telemetry.counter("moe.pairs_held").value
    rows = telemetry.counter("trainer.rows").value
    if not pairs or not rows:
        return None
    return float(pairs) * ctx["batch"] / float(rows)


def read(ctx):
    have = scoped_trace.train_ms_per_step(
        ctx, lambda scope: scoped_trace.kind_of(scope) == "moe_experts")
    net = [ent for ent in ctx["net"] if ent["kind"] == "moe"]
    pairs = pairs_per_step(ctx)
    if not have or not net or pairs is None:
        return None
    module = layer_costs.module_for("moe")
    peaks = ctx["peaks"]
    least = 0.0
    for ent in net:
        flops, nbytes = module.products(ent, pairs / len(net))
        for times in (1.0, 2.0):    # forward, backward
            least += max(times * flops / peaks["flops_per_s"],
                         times * nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least / have
