"""Token embedding: a gather of ``batch * seq`` rows of the float32 table
forward, a scatter-add of as many gradient rows backward; no matrix work.
(``cost`` takes rows for ``batch``; a row's length rides on the entry.)"""
from benchmarks.layer_costs import ACT_BYTES, PARAM_BYTES, elems

MXU = False


def update_bytes(ent):
    """AdamW's seven leaf passes (read weight, two moments and gradient;
    write weight and two moments), float32, once a step: booked to the
    entry's first application of the step."""
    if not ent.get("update", True):
        return 0
    return 7 * PARAM_BYTES * sum(elems(s) for s in ent["leaves"].values())


def cost(ent, batch, first):
    _, dim = ent["leaves"]["w"]
    n = batch * ent["seq"] * dim
    return {
        "flops_fwd": 0.0,
        "flops_bwd": float(n),
        "bytes_fwd": n * (PARAM_BYTES + ACT_BYTES),
        "bytes_bwd": n * (ACT_BYTES + PARAM_BYTES),
        "bytes_update": update_bytes(ent),
    }
