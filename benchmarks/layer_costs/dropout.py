"""Inverted dropout: a keep-mask multiply each way (training only)."""
from benchmarks.layer_costs import ACT_BYTES, elems

MXU = False


def cost(ent, batch, first):
    x = elems(ent["in_shape"]) * batch
    return {
        "flops_fwd": float(x),
        "flops_bwd": 0.0 if first else float(x),
        # the mask is remade from the key, so it need not cross HBM
        "bytes_fwd": 2 * x * ACT_BYTES,
        "bytes_bwd": 0 if first else 2 * x * ACT_BYTES,
        "bytes_update": 0,
    }
