"""Mamba-2's state-space mixer (``ssd``), its scan in the chunked form at
the entry's ``chunk``: one application.

Required FLOPs, the matrix work alone: the two projections (``w_in`` to
``z``, ``xBC`` and ``dt``; ``w_out``) over every token, and the chunked
scan's four products over every chunk of a row, the intra-chunk ones over
the (t, s) pairs with ``s <= t`` only: ``C B^T`` per group (``2 * state``
a pair), ``(L o C B^T) X`` per head (``2 * head_dim`` a pair), the chunk's
own state ``B^T X`` and the carried state's part ``C S`` per head (``2 *
head_dim * state`` a position each).  The conv, the steps and their decays,
the gate and the group norm are no matrix work; they are in the bytes.  No
recomputation is required work.  Least bytes: the stream in and out once;
the input projection's output written and read once, the conv's output
(``x``, ``B``, ``C``) likewise, in the compute type; the steps, the scan's
output and the chunks' states in float32, each written and read once; the
gated, normed output written and read once; the weights once."""
from benchmarks.layer_costs import ACT_BYTES, PARAM_BYTES, elems
from benchmarks.layer_costs.embedding import update_bytes

MXU = True
PRODUCTS = ("w_in", "w_out")


def chunks(ent, batch):
    """Chunks the scan runs over ``batch`` rows."""
    return batch * -(-ent["seq"] // ent["chunk"])


def parts(ent, batch):
    """(projection FLOPs, scan FLOPs) of one forward application."""
    n = batch * ent["seq"]
    proj = 2.0 * n * sum(elems(ent["leaves"][k]) for k in PRODUCTS)
    h, hd, state, q = (ent[k] for k in ("heads", "head_dim", "state",
                                        "chunk"))
    pairs = q * (q + 1) / 2.0
    per_chunk = 2.0 * pairs * (state * ent["groups"] + hd * h) \
        + 2 * 2.0 * q * hd * state * h
    return proj, per_chunk * chunks(ent, batch)


def activation_bytes(ent, batch):
    """Least bytes of one forward application's activations."""
    n = batch * ent["seq"]
    dim = ent["leaves"]["w_in"][0]
    h, hd, state, g = (ent[k] for k in ("heads", "head_dim", "state",
                                        "groups"))
    inner, conv = h * hd, h * hd + 2 * g * state
    in_proj = n * (inner + conv + h)
    half = (2 * n * dim + 2 * in_proj + 2 * n * conv + 2 * n * inner) \
        * ACT_BYTES
    wide = (2 * n * h + 2 * n * inner
            + 2 * chunks(ent, batch) * h * hd * state) * PARAM_BYTES
    return half + wide


def cost(ent, batch, first):
    proj, scan = parts(ent, batch)
    fwd = proj + scan
    n_w = sum(elems(s) for s in ent["leaves"].values())
    acts = activation_bytes(ent, batch)
    return {
        "flops_fwd": fwd,
        "flops_bwd": 2.0 * fwd,
        "bytes_fwd": acts + n_w * ACT_BYTES,
        "bytes_bwd": 2 * acts + n_w * (ACT_BYTES + PARAM_BYTES),
        "bytes_update": update_bytes(ent),
    }
