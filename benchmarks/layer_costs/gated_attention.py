"""Attention as ``local_attention`` counts it (causal, cut at document
boundaries and at the layer's window, grouped key-value heads) with a norm
of every query and key head and an output gate: one application.

Added to ``local_attention``'s count of the four projections and the
attended pairs: the gate's projection, ``2 * dim * heads * head_dim`` a
token, which no key of a published configuration names.  The norms' gains
(``gq``, ``gk``) are no matrix work and are not counted among the
projections; they and the gate's weight are in the optimizer's pass.  Least
bytes: the gate's pre-activation written once and read once beside what
``local_attention`` counts, its weight once each way."""
from benchmarks.layer_costs import (ACT_BYTES, PARAM_BYTES, elems,
                                    local_attention)
from benchmarks.layer_costs.embedding import update_bytes

MXU = True
PLAIN = ("wq", "wk", "wv", "wo")


def gate_flops(ent, batch):
    """FLOPs of one forward application of the gate's projection."""
    return 2.0 * batch * ent["seq"] * elems(ent["leaves"]["wgate"])


def cost(ent, batch, first):
    leaves = ent["leaves"]
    out = dict(local_attention.cost(
        dict(ent, leaves={k: leaves[k] for k in PLAIN}), batch, first))
    gate = gate_flops(ent, batch)
    n_g = elems(leaves["wgate"])
    acts = 2 * batch * ent["seq"] * leaves["wgate"][1] * ACT_BYTES
    out["flops_fwd"] += gate
    out["flops_bwd"] += 2.0 * gate
    out["bytes_fwd"] += acts + n_g * ACT_BYTES
    out["bytes_bwd"] += 2 * acts + n_g * (ACT_BYTES + PARAM_BYTES)
    out["bytes_update"] += update_bytes(dict(ent, leaves={
        k: s for k, s in leaves.items() if k not in PLAIN}))
    return out
