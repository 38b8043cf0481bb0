"""Rotary causal attention cut at document boundaries: one application.

Required FLOPs: the four projections, and for the scores and the weighted
sum only the (query, key) pairs a row attends (``ent["pairs"]``: ``j <= i``
inside one document, counted exactly from the mix's documents), 2 products
of ``head_dim`` a pair and head.  No recomputation is required work.  Least
bytes: the input and output, q, k and v once, the weights once; the scores
need not cross HBM."""
from benchmarks.layer_costs import ACT_BYTES, PARAM_BYTES, elems
from benchmarks.layer_costs.embedding import update_bytes

MXU = True


def parts(ent, batch):
    """(projection FLOPs, score FLOPs) of one forward application."""
    n = batch * ent["seq"]
    proj = 2.0 * n * sum(elems(s) for s in ent["leaves"].values())
    score = 4.0 * ent["head_dim"] * ent["heads"] * ent["pairs"] * batch
    return proj, score


def cost(ent, batch, first):
    proj, score = parts(ent, batch)
    fwd = proj + score
    dim = ent["leaves"]["wq"][0]
    n = batch * ent["seq"]
    n_w = sum(elems(s) for s in ent["leaves"].values())
    qkv = n * (ent["heads"] + 2 * ent["kv_heads"]) * ent["head_dim"]
    acts = (2 * n * dim + 2 * qkv) * ACT_BYTES
    return {
        "flops_fwd": fwd,
        "flops_bwd": 2.0 * fwd,
        "bytes_fwd": acts + n_w * ACT_BYTES,
        "bytes_bwd": 2 * acts + n_w * (ACT_BYTES + PARAM_BYTES),
        "bytes_update": update_bytes(ent),
    }
