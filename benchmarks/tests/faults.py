"""Faults planted under the timed path: each takes the train-window call
that ``lib/job.WindowCapture`` wraps (the entry, the net, the entry's own
arguments, ``final``) and breaks it the way a wrong program would."""

import numpy


def _rows_view(idx_s):
    """(array to edit, axis of the batch rows) of a staged index window."""
    if isinstance(idx_s, numpy.ndarray):
        return idx_s, None
    return idx_s.base, idx_s


def state_unchanged(orig, net, *args):
    """The step returns its state as it got it (whatever the entry)."""
    import jax
    import jax.numpy as jnp
    params = jax.tree.map(jnp.copy, net.params)
    state = jax.tree.map(jnp.copy, net.state)
    stats = orig(*args[:-1], final=args[-1])
    net.params, net.state = params, state
    return stats


def half_batch(orig, net, idx_s, batch_sizes, hypers_s, final):
    """The second half of every minibatch is left out; the program's own
    masking then takes the mean over the rest."""
    if isinstance(idx_s, numpy.ndarray):
        cut = idx_s.copy()
        cut[:, cut.shape[1] // 2:] = -1
    else:   # shard-major (S, K, B // S): the later shards are the later rows
        cut = type(idx_s)(idx_s.base.copy())
        cut.base[cut.base.shape[0] // 2:] = -1
    return orig(cut, batch_sizes, hypers_s, final=final)


def no_exchange(orig, net, idx_s, batch_sizes, hypers_s, final):
    """What the first chip would apply had the gradient all-reduce been left
    out: the update from its own rows alone."""
    cut = type(idx_s)(idx_s.base.copy())
    cut.base[1:] = -1
    return orig(cut, batch_sizes, hypers_s, final=final)
