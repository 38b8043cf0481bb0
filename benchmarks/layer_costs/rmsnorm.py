"""RMS norm in float32 over the model width: one application."""
from benchmarks.layer_costs import ACT_BYTES
from benchmarks.layer_costs.embedding import update_bytes

MXU = False


def cost(ent, batch, first):
    (dim,) = ent["leaves"]["g"]
    n = batch * ent["seq"] * dim
    return {
        "flops_fwd": 4.0 * n,
        "flops_bwd": 0.0 if first else 8.0 * n,
        "bytes_fwd": 2 * n * ACT_BYTES,
        "bytes_bwd": 0 if first else 3 * n * ACT_BYTES,
        "bytes_update": update_bytes(ent),
    }
