"""Latent attention (queries and keys read back from two normed narrow
projections, a rotary part of every head, one rotary key for all the heads),
causal and cut at document boundaries: one application.

Required FLOPs: the five products (``wqa``, ``wqb``, ``wkva``, ``wkvb``,
``wo``), and for the scores and the weighted sum only the (query, key)
pairs a row attends (``ent["pairs"]``: ``j <= i`` inside one document,
counted exactly from the mix's documents), ``2 * (nope_dim + rope_dim)``
a pair and head for the scores and ``2 * v_dim`` for the sum.  No
recomputation is required work.  Least bytes: the input and output once;
the two latents (``q_rank``, and ``kv_rank`` with the rope key) written and
read once, and their norms' pass over them once more; q, k (every head's
key at its whole width, as the heads read it) and v written and read once;
the rotary's pass over the rope parts (every query head's and the one
key); the weights once; the scores need not cross HBM."""
from benchmarks.layer_costs import ACT_BYTES, PARAM_BYTES, elems
from benchmarks.layer_costs.embedding import update_bytes

MXU = True
PRODUCTS = ("wqa", "wqb", "wkva", "wkvb", "wo")


def parts(ent, batch):
    """(projection FLOPs, core FLOPs) of one forward application."""
    n = batch * ent["seq"]
    proj = 2.0 * n * sum(elems(ent["leaves"][k]) for k in PRODUCTS)
    width = ent["nope_dim"] + ent["rope_dim"] + ent["v_dim"]
    core = 2.0 * width * ent["heads"] * ent["pairs"] * batch
    return proj, core


def activation_bytes(ent, batch):
    """Least bytes of one forward application's activations."""
    n = batch * ent["seq"]
    dim = ent["leaves"]["wqa"][0]
    h, rope = ent["heads"], ent["rope_dim"]
    qk = ent["nope_dim"] + rope
    latents = n * (ent["q_rank"] + ent["kv_rank"] + rope)
    qkv = n * h * (2 * qk + ent["v_dim"])
    norms = 2 * n * (ent["q_rank"] + ent["kv_rank"])
    rotary = 2 * n * (h + 1) * rope
    return (2 * n * dim + 2 * latents + 2 * qkv + norms + rotary) \
        * ACT_BYTES


def cost(ent, batch, first):
    proj, core = parts(ent, batch)
    fwd = proj + core
    n_w = sum(elems(s) for s in ent["leaves"].values())
    acts = activation_bytes(ent, batch)
    return {
        "flops_fwd": fwd,
        "flops_bwd": 2.0 * fwd,
        "bytes_fwd": acts + n_w * ACT_BYTES,
        "bytes_bwd": 2 * acts + n_w * (ACT_BYTES + PARAM_BYTES),
        "bytes_update": update_bytes(ent),
    }
