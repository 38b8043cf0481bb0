"""Required operations and least bytes of one layer, by layer kind.

One module per kind, named after the kind as ``reference/layers_net.plan``
gives it (``conv.py``, ``fc.py``, ...); a new kind adds a file.  Each has

    cost(ent, batch, first) -> {"flops_fwd", "flops_bwd",
                                "bytes_fwd", "bytes_bwd", "bytes_update"}

for ONE step of ``batch`` images: the FLOPs the mathematics requires (a
position that ``zero_filter`` masks to nought is not required, nor is
padding, nor a gradient nobody consumes: ``first`` marks the first layer,
whose input gradient is not needed) and the fewest bytes that must cross
HBM (activations in the 2-byte compute type, each read or written once;
float32 master weights, momentum and gradient in the update).  ``MXU`` says
whether the kind's FLOPs are matrix work, which is what ``mfu`` counts.
"""

import importlib

ACT_BYTES = 2      # bfloat16 activations, as the configurations state
PARAM_BYTES = 4    # float32 master weights, momentum, gradient


def module_for(kind):
    try:
        return importlib.import_module("%s.%s" % (__name__, kind))
    except ImportError as exc:
        raise KeyError("no cost module for layer kind %r (add "
                       "benchmarks/layer_costs/%s.py)" % (kind, kind)) from exc


def net_costs(net, batch):
    """[(ent, cost dict, is_mxu)] for every layer of a planned net."""
    out = []
    first = True
    for ent in net:
        mod = module_for(ent["kind"])
        out.append((ent, mod.cost(ent, batch, first), mod.MXU))
        first = False
    return out


def train_flops_per_image(net):
    """Matrix FLOPs one trained image requires, forward and backward."""
    return sum(c["flops_fwd"] + c["flops_bwd"]
               for _, c, mxu in net_costs(net, 1) if mxu)


def forward_macs_per_image(net, masked=True):
    """Multiply-adds of one forward pass (``masked=False`` counts the
    positions zero_filter removes too: the dense count)."""
    total = 0
    for ent, c, mxu in net_costs(net, 1):
        if mxu:
            share = 1.0 if masked else 1.0 / mask_share(ent)
            total += c["flops_fwd"] * share / 2
    return total


def mask_share(ent):
    mask = ent.get("mask")
    return 1.0 if mask is None else float(mask.mean())


def elems(shape):
    n = 1
    for s in shape:
        n *= int(s)
    return n


def least_seconds(net, batch, peaks, train=True):
    """Sum over layers of max(FLOPs / peak FLOP/s, bytes / peak bytes/s)
    for one step (forward only where ``train`` is false), with the share
    of that sum which the byte bound sets."""
    total = by_bytes = 0.0
    for _, c, _ in net_costs(net, batch):
        parts = [(c["flops_fwd"], c["bytes_fwd"])]
        if train:
            parts += [(c["flops_bwd"], c["bytes_bwd"]),
                      (0.0, c["bytes_update"])]
        for flops, nbytes in parts:
            tf = flops / peaks["flops_per_s"]
            tb = nbytes / peaks["hbm_bytes_per_s"]
            total += max(tf, tb)
            if tb >= tf:
                by_bytes += tb
    return total, (by_bytes / total if total else 0.0)
