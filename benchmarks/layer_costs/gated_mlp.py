"""Gated MLP ``(silu(x Wg) * (x Wu)) Wd``: three products an application."""
from benchmarks.layer_costs import ACT_BYTES, PARAM_BYTES
from benchmarks.layer_costs.embedding import update_bytes

MXU = True


def cost(ent, batch, first):
    dim, hidden = ent["leaves"]["wg"]
    n = batch * ent["seq"]
    n_w = 3 * dim * hidden
    fwd = 2.0 * n * n_w
    acts = (2 * n * dim + 2 * n * hidden) * ACT_BYTES
    return {
        "flops_fwd": fwd,
        "flops_bwd": 2.0 * fwd,
        "bytes_fwd": acts + n_w * ACT_BYTES,
        "bytes_bwd": 2 * acts + n_w * (ACT_BYTES + PARAM_BYTES),
        "bytes_update": update_bytes(ent),
    }
