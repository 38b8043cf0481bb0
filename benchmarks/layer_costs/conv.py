"""Convolution: 2 FLOPs per multiply-add over unmasked weight positions."""
from benchmarks.layer_costs import (ACT_BYTES, PARAM_BYTES, elems,
                                    mask_share)

MXU = True


def cost(ent, batch, first):
    ny, nx, k = ent["out_shape"]
    n_w = elems(ent["w_shape"])
    fwd = 2.0 * ny * nx * n_w * mask_share(ent) * batch
    x = elems(ent["in_shape"]) * batch * ACT_BYTES
    y = elems(ent["out_shape"]) * batch * ACT_BYTES
    w = n_w * ACT_BYTES
    return {
        "flops_fwd": fwd,
        # dW always; dX only where a layer upstream consumes it
        "flops_bwd": fwd * (1 if first else 2),
        "bytes_fwd": x + w + y,
        "bytes_bwd": y + x + w + n_w * PARAM_BYTES + (0 if first else x),
        "bytes_update": (n_w + k) * PARAM_BYTES * 5,
    }
