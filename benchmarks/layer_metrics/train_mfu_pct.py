"""Images per second of this run times the matrix FLOPs one trained image
requires, over chips times the peak."""
from benchmarks import layer_costs


def read(ctx):
    need = layer_costs.train_flops_per_image(ctx["net"])
    have = ctx["peaks"]["flops_per_s"] * int(ctx["cell"]["chips"])
    return 100.0 * ctx["rate"] * need / have
