"""A chip's share of a routed mixture of experts: one application.

Required FLOPs: the three products of every (token, expert) pair whose
expert is held here (``ent["pairs"]`` a row: ``seq * top_k * held /
experts`` at an even load; a reader that has the pairs a run counted hands
them in), ``2 * dim * hidden`` each.  Choosing, sorting and summing back are
no matrix work.  Least bytes: the stream in and out once, every pair's
input row, hidden row and output row once in the compute type, the held
experts' weights once; the router's logits are booked to the router."""
from benchmarks.layer_costs import ACT_BYTES, PARAM_BYTES, elems
from benchmarks.layer_costs.embedding import update_bytes

MXU = True


def products(ent, pairs):
    """(FLOPs, bytes) of the three grouped products' forward over ``pairs``
    (token, expert) pairs: the required work of the ``experts`` scope."""
    _, dim, hidden = ent["leaves"]["wg"]
    n_w = sum(elems(s) for s in ent["leaves"].values())
    flops = 6.0 * pairs * dim * hidden
    nbytes = pairs * (2 * dim + 3 * hidden) * ACT_BYTES + n_w * ACT_BYTES
    return flops, nbytes


def cost(ent, batch, first):
    _, dim, _ = ent["leaves"]["wg"]
    n = batch * ent["seq"]
    pairs = batch * ent["pairs"]
    n_w = sum(elems(s) for s in ent["leaves"].values())
    fwd, grouped = products(ent, pairs)
    acts = 2 * n * dim * ACT_BYTES + grouped - n_w * ACT_BYTES
    return {
        "flops_fwd": fwd,
        "flops_bwd": 2.0 * fwd,
        "bytes_fwd": acts + n_w * ACT_BYTES,
        "bytes_bwd": 2 * acts + n_w * (ACT_BYTES + PARAM_BYTES),
        "bytes_update": update_bytes(ent),
    }
