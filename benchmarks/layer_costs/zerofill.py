"""zero_filter: identity in the chain (its mask is the next layer's)."""

MXU = False


def cost(ent, batch, first):
    return {"flops_fwd": 0.0, "flops_bwd": 0.0, "bytes_fwd": 0,
            "bytes_bwd": 0, "bytes_update": 0}
