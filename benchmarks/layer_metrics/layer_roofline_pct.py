"""Sum over layers of the least time the chip could take for the traced
window's steps and validation passes, over the device's busy time."""
from benchmarks import layer_costs


def read(ctx):
    chips = int(ctx["cell"]["chips"])
    busy = ctx["trace"]["busy_s_mean"]
    if busy <= 0:
        return None
    per_chip = ctx["batch"] // chips
    steps = ctx["images"] // ctx["batch"]
    t_train, _ = layer_costs.least_seconds(
        ctx["net"], per_chip, ctx["peaks"], train=True)
    # validation rows are not split over the mesh's chips by the cost
    # model: each epoch one forward over the validation rows, per chip
    t_valid, _ = layer_costs.least_seconds(
        ctx["net"], max(ctx["n_valid"] // chips, 1), ctx["peaks"],
        train=False)
    least = steps * t_train + ctx["epochs"] * t_valid
    return 100.0 * least / busy
