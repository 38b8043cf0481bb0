"""Cross-channel LRN: a window of n squares, a power and a divide."""
from benchmarks.layer_costs import ACT_BYTES, elems

MXU = False


def cost(ent, batch, first):
    x = elems(ent["in_shape"]) * batch
    per = 2 * ent["n"] + 4
    return {
        "flops_fwd": float(x * per),
        "flops_bwd": 0.0 if first else float(x * (2 * per + 4)),
        "bytes_fwd": 2 * x * ACT_BYTES,
        "bytes_bwd": 0 if first else 3 * x * ACT_BYTES,
        "bytes_update": 0,
    }
