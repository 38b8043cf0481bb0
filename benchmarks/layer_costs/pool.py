"""Max / average pooling: one compare or add per window cell."""
from benchmarks.layer_costs import ACT_BYTES, elems

MXU = False


def cost(ent, batch, first):
    x = elems(ent["in_shape"]) * batch
    y = elems(ent["out_shape"]) * batch
    cells = ent["kx"] * ent["ky"]
    return {
        "flops_fwd": float(y * cells),
        "flops_bwd": 0.0 if first else float(y * cells),
        "bytes_fwd": (x + y) * ACT_BYTES,
        # max needs the input again to find the winner; avg does not
        "bytes_bwd": 0 if first else (
            (y + x + (x if ent["mode"] == "max" else 0)) * ACT_BYTES),
        "bytes_update": 0,
    }
