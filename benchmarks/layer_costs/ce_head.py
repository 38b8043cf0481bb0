"""A plain head: final norm, the output product over the vocabulary rows
held here, cross-entropy; no exit gate.  The logits need not cross HBM (the
loss is taken block by block); the weight is read once each way and its
float32 gradient written once."""
from benchmarks.layer_costs import ACT_BYTES, PARAM_BYTES
from benchmarks.layer_costs.embedding import update_bytes

MXU = True


def cost(ent, batch, first):
    vocab, dim = ent["leaves"]["w"]
    n = batch * ent["seq"]
    fwd = 2.0 * n * dim * vocab
    acts = 2 * n * dim * ACT_BYTES + 2 * n * PARAM_BYTES
    return {
        "flops_fwd": fwd,
        "flops_bwd": 2.0 * fwd,
        "bytes_fwd": acts + vocab * dim * ACT_BYTES,
        "bytes_bwd": acts + vocab * dim * (ACT_BYTES + PARAM_BYTES),
        "bytes_update": update_bytes(ent),
    }
