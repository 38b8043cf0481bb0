"""A multi-token prediction module's way in: the embedding of every
position's next id (a gather of the main embedding's float32 rows forward,
a scatter-add of as many gradient rows backward), two norms, and one
product of the joined ``2 * dim`` wide rows with ``(2 * dim, dim)``: one
application.  Least bytes: the state in, the gathered rows in float32, the
joined rows written and read once, the output once, the weight once."""
from benchmarks.layer_costs import ACT_BYTES, PARAM_BYTES, elems
from benchmarks.layer_costs.embedding import update_bytes

MXU = True


def cost(ent, batch, first):
    two, dim = ent["leaves"]["w"]
    n = batch * ent["seq"]
    fwd = 2.0 * n * two * dim
    n_w = elems(ent["leaves"]["w"])
    acts = (2 * n * dim + 2 * n * two) * ACT_BYTES + n * dim * ACT_BYTES
    rows = n * dim * PARAM_BYTES
    return {
        "flops_fwd": fwd,
        "flops_bwd": 2.0 * fwd,
        "bytes_fwd": acts + rows + n_w * ACT_BYTES,
        "bytes_bwd": 2 * acts + rows + n_w * (ACT_BYTES + PARAM_BYTES),
        "bytes_update": update_bytes(ent),
    }
