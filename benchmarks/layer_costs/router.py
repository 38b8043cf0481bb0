"""A routed layer's router: one float32 product of the stream with ``(dim,
experts)``; the logits (float32) are written once and read once by the
expert layer.  Too narrow to be the matrix unit's work in any count that
matters (64 outputs), but a product all the same."""
from benchmarks.layer_costs import ACT_BYTES, PARAM_BYTES
from benchmarks.layer_costs.embedding import update_bytes

MXU = True


def cost(ent, batch, first):
    dim, experts = ent["leaves"]["wr"]
    n = batch * ent["seq"]
    fwd = 2.0 * n * dim * experts
    acts = n * dim * ACT_BYTES + n * experts * PARAM_BYTES
    return {
        "flops_fwd": fwd,
        "flops_bwd": 2.0 * fwd,
        "bytes_fwd": acts + dim * experts * PARAM_BYTES,
        "bytes_bwd": 2 * acts + 2 * dim * experts * PARAM_BYTES,
        "bytes_update": update_bytes(ent),
    }
