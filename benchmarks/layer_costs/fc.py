"""Fully connected (all2all, softmax head): one matrix product."""
from benchmarks.layer_costs import ACT_BYTES, PARAM_BYTES, mask_share

MXU = True


def cost(ent, batch, first):
    n_out, n_in = ent["w_shape"]
    n_w = n_out * n_in
    fwd = 2.0 * n_w * mask_share(ent) * batch
    x = n_in * batch * ACT_BYTES
    y = n_out * batch * ACT_BYTES
    w = n_w * ACT_BYTES
    return {
        "flops_fwd": fwd,
        "flops_bwd": fwd * (1 if first else 2),
        "bytes_fwd": x + w + y,
        "bytes_bwd": y + x + w + n_w * PARAM_BYTES + (0 if first else x),
        "bytes_update": (n_w + n_out) * PARAM_BYTES * 5,
    }
