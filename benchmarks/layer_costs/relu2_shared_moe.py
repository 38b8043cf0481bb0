"""A chip's share of a routed mixture of relu^2 experts that are not gated
(``relu(x U)^2 D``) beside a shared expert of the same form that every
token passes: one application.

Required FLOPs: TWO products of every (token, expert) pair whose expert is
held here (``ent["pairs"]`` a row at an even load), ``2 * dim * hidden``
each, and the shared expert's two over EVERY token of the step, ``2 * dim
* shared_hidden`` each.  Choosing, sorting and summing back are no matrix
work.  Least bytes: the stream in and out once; every pair's input row and
output row once and its hidden row written and read once, in the compute
type; the shared expert's rows likewise; the weights once.  The selection
bias is no matrix work; it is 4 bytes an expert in no pass of the
optimizer and is not counted."""
from benchmarks.layer_costs import ACT_BYTES, PARAM_BYTES, elems
from benchmarks.layer_costs.embedding import update_bytes

MXU = True
ROUTED = ("wu", "wd")
SHARED = ("su", "sd")


def products(ent, pairs):
    """(FLOPs, bytes) of the routed experts' two grouped products' forward
    over ``pairs`` (token, expert) pairs: the required work of the
    ``experts`` scope."""
    _, dim, hidden = ent["leaves"]["wu"]
    n_w = sum(elems(ent["leaves"][k]) for k in ROUTED)
    flops = 4.0 * pairs * dim * hidden
    nbytes = pairs * (2 * dim + 2 * hidden) * ACT_BYTES + n_w * ACT_BYTES
    return flops, nbytes


def shared_products(ent, tokens):
    """(FLOPs, bytes) of the shared expert's two products' forward over
    ``tokens`` tokens: the required work of the ``shared`` scope."""
    dim, hidden = ent["leaves"]["su"]
    flops = 4.0 * tokens * dim * hidden
    nbytes = tokens * (2 * dim + 2 * hidden) * ACT_BYTES \
        + 2 * dim * hidden * ACT_BYTES
    return flops, nbytes


def cost(ent, batch, first):
    _, dim, _ = ent["leaves"]["wu"]
    n = batch * ent["seq"]
    n_w = sum(elems(ent["leaves"][k]) for k in ROUTED + SHARED)
    flops, routed = products(ent, batch * ent["pairs"])
    shared_flops, shared = shared_products(ent, n)
    fwd = flops + shared_flops
    acts = 2 * n * dim * ACT_BYTES + routed + shared - n_w * ACT_BYTES
    return {
        "flops_fwd": fwd,
        "flops_bwd": 2.0 * fwd,
        "bytes_fwd": acts + n_w * ACT_BYTES,
        "bytes_bwd": 2 * acts + n_w * (ACT_BYTES + PARAM_BYTES),
        "bytes_update": update_bytes(dict(ent, leaves={
            k: ent["leaves"][k] for k in ROUTED + SHARED})),
    }
