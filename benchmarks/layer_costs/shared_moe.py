"""A chip's share of a routed mixture of experts beside a shared expert
that every token passes: one application.

The routed part is ``moe``'s count over the experts held here (its three
grouped products over the pairs held).  Added: the shared expert's three
products over EVERY token of the step, ``2 * dim * shared_hidden`` each, a
gated MLP's bytes (the stream in and out, the hidden rows once each, the
weights once).  The selection bias is no matrix work; it is 4 bytes an
expert in no pass of the optimizer and is not counted."""
from benchmarks.layer_costs import ACT_BYTES, PARAM_BYTES, elems, moe
from benchmarks.layer_costs.embedding import update_bytes

MXU = True
ROUTED = ("wg", "wu", "wd")
SHARED = ("sg", "su", "sd")


def _routed(ent):
    return dict(ent, leaves={k: ent["leaves"][k] for k in ROUTED})


def products(ent, pairs):
    """``moe.products`` of the experts held: the ``experts`` scope."""
    return moe.products(_routed(ent), pairs)


def shared_products(ent, tokens):
    """(FLOPs, bytes) of the shared expert's three products' forward over
    ``tokens`` tokens: the required work of the ``shared`` scope."""
    dim, hidden = ent["leaves"]["sg"]
    flops = 6.0 * tokens * dim * hidden
    nbytes = tokens * (2 * dim + 2 * hidden) * ACT_BYTES \
        + 3 * dim * hidden * ACT_BYTES
    return flops, nbytes


def cost(ent, batch, first):
    out = dict(moe.cost(_routed(ent), batch, first))
    n_w = sum(elems(ent["leaves"][k]) for k in SHARED)
    flops, nbytes = shared_products(ent, batch * ent["seq"])
    acts = nbytes - n_w * ACT_BYTES
    out["flops_fwd"] += flops
    out["flops_bwd"] += 2.0 * flops
    out["bytes_fwd"] += nbytes
    out["bytes_bwd"] += 2 * acts + n_w * (ACT_BYTES + PARAM_BYTES)
    out["bytes_update"] += update_bytes(dict(ent, leaves={
        k: ent["leaves"][k] for k in SHARED}))
    return out
