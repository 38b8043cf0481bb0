"""Standalone elementwise activation."""
from benchmarks.layer_costs import ACT_BYTES, elems

MXU = False


def cost(ent, batch, first):
    x = elems(ent["in_shape"]) * batch
    return {
        "flops_fwd": float(x),
        "flops_bwd": 0.0 if first else float(x),
        "bytes_fwd": 2 * x * ACT_BYTES,
        "bytes_bwd": 0 if first else 3 * x * ACT_BYTES,
        "bytes_update": 0,
    }
