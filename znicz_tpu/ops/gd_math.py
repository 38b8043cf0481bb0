"""The gradient-descent update algebra — exact parity with the reference.

This is the per-layer optimizer the whole framework shares
(nn_units.py:696-719, gd.py:314-419, cuda/gradient_descent_common.cu
``gradient_step_l12``):

1. ``step = grad + wd * ((1 - l1_vs_l2) * w + 0.5 * l1_vs_l2 * sign(w))
            [+ ortho]``;  ``gradient = -lr * step``
2. accumulate (nn_units.py:419-428):
   ``acc = acc_alpha * gradient + acc_beta * acc``
   ``gradient = gd_beta * gradient + gd_alpha * acc``
3. moment (gd.py:314-326, variant_moment_gradient=True):
   ``vel = gradient + moment * vel``; applied gradient is ``vel``
4. ``w += gradient`` when apply_gradient.

The ortho regularizer (nn_units.py:713-717): each gradient row i gains
``(col_sums - w[i]) * factor_ortho / n_rows`` where col_sums = w.sum(axis=0).

Solvers adagrad/adadelta/fast (gd.py:395-419) transform the velocity before
application; they compose with the above exactly as the reference's
``numpy_update`` does.

Solver ``adamw`` stands alone (it composes with none of the above): two
moments and a step count per tensor, bias correction, decoupled weight
decay (:func:`adamw`); its hyperparameters ``lr``, ``wd``, ``adam_beta1``,
``adam_beta2``, ``adam_eps`` are traced like the rest.

State per parameter tensor is a dict pytree: ``acc`` (accumulated gradient),
``vel`` (gradient with moment), plus solver slots.  The same function runs
under jit (jax arrays) and eagerly (numpy) — pure jnp/numpy-agnostic algebra
via the ``xp`` module argument.
"""

from functools import partial

import numpy
import jax
import jax.numpy as jnp


def _gradient_step(xp, w, grad, lr, wd, l1_vs_l2, factor_ortho, use_ortho):
    step = grad + wd * ((1.0 - l1_vs_l2) * w +
                        0.5 * l1_vs_l2 * xp.sign(w))
    if use_ortho:
        col_sums = w.sum(axis=0)
        step = step + (col_sums[None, :] - w) * (factor_ortho / w.shape[0])
    return lr * step


#: AdamW's own hyperparameters and their defaults (a layer's "<-" may give
#: them; they ride the traced hyper pytree of layers that ask for adamw)
ADAMW_HYPER = {"adam_beta1": 0.9, "adam_beta2": 0.999, "adam_eps": 1e-8}


def adamw(xp, w, grad, state, hyper):
    """``m <- b1 m + (1-b1) g``; ``v <- b2 v + (1-b2) g^2``;
    ``w <- w - lr (m^ / (sqrt(v^) + eps) + wd w)`` with ``m^ = m / (1 -
    b1^t)``, ``v^ = v / (1 - b2^t)``.  Returns (new_w, new_state, applied
    gradient); ``state["t"]`` counts the steps taken."""
    b1, b2 = hyper["adam_beta1"], hyper["adam_beta2"]
    t = state["t"] + 1
    m = b1 * state["m"] + (1.0 - b1) * grad
    v = b2 * state["v"] + (1.0 - b2) * grad * grad
    m_hat = m / (1.0 - b1 ** t)
    v_hat = v / (1.0 - b2 ** t)
    gradient = -hyper["lr"] * (m_hat / (xp.sqrt(v_hat) + hyper["adam_eps"])
                               + hyper["wd"] * w)
    return w + gradient, dict(state, m=m, v=v, t=t), gradient


def update(xp, w, grad, state, hyper, flags):
    """One parameter update.  Returns (new_w, new_state, applied_gradient).

    hyper: dict(lr, wd, l1_vs_l2, moment, acc_alpha, acc_beta, gd_alpha,
                gd_beta, factor_ortho)
    flags: dict(accumulate, apply, solvers=frozenset, variant_moment=True)
    state: dict(acc, vel, [adagrad], [adadelta_v, adadelta_gv], [fast])
    """
    if "adamw" in (flags.get("solvers") or ()):
        new_w, new_state, gradient = adamw(xp, w, grad, state, hyper)
        return (new_w if flags.get("apply", True) else w), new_state, \
            gradient
    gradient = -_gradient_step(
        xp, w, grad, hyper["lr"], hyper["wd"], hyper["l1_vs_l2"],
        hyper.get("factor_ortho", 0.0), flags.get("ortho", False))
    new_state = dict(state)

    if flags.get("accumulate") and state.get("acc") is not None:
        acc = hyper["acc_alpha"] * gradient + hyper["acc_beta"] * state["acc"]
        gradient = hyper["gd_beta"] * gradient + hyper["gd_alpha"] * acc
        new_state["acc"] = acc

    if state.get("vel") is not None:
        if flags.get("variant_moment", True):
            vel = gradient + hyper["moment"] * state["vel"]
        else:
            vel = ((1.0 - hyper["moment"]) * gradient +
                   hyper["moment"] * state["vel"])
        new_state["vel"] = vel
        gradient = vel
    solvers = flags.get("solvers") or frozenset()
    if "adagrad" in solvers:
        ada = state["adagrad"] + new_state["vel"] ** 2
        gradient = gradient * xp.sqrt(ada + hyper.get("adagrad_eps", 1e-8))
        new_state["adagrad"] = ada
    if "adadelta" in solvers:
        eps = hyper.get("adadelta_eps", 1e-8)
        adom = hyper.get("adadelta_adom", 0.3)
        gv = (adom * state["adadelta_gv"] +
              (1.0 - adom) * new_state["vel"] ** 2)
        s1 = xp.sqrt(state["adadelta_v"] + eps)
        s2 = xp.sqrt(gv + eps)
        gradient = gradient * (s1 / s2)
        v = adom * state["adadelta_v"] + (1.0 - adom) * gradient ** 2
        new_state["adadelta_gv"] = gv
        new_state["adadelta_v"] = v
    if "fast" in solvers:
        fast = (state["fast"] * 0.95 +
                hyper.get("fast_lr", 0.02) * new_state["vel"])
        new_state["fast"] = fast

    new_w = w
    if flags.get("apply", True):
        new_w = w + gradient
        if "fast" in solvers:
            new_w = new_w - new_state["fast"]
    return new_w, new_state, gradient


# jit-compiled entry for the jax path; hyper values become traced scalars so
# learning-rate schedules don't retrigger compilation.
@partial(jax.jit, static_argnames=("flags_key",))
def _update_jax(w, grad, state, hyper, flags_key):
    flags = dict(flags_key)
    flags["solvers"] = frozenset(flags.get("solvers") or ())
    return update(jnp, w, grad, state, hyper, flags)[:2] + (None,)


def _flags_key(flags):
    """Hashable static-arg form of a flags dict (jit cache key)."""
    return tuple(sorted(
        (k, tuple(sorted(v)) if isinstance(v, (set, frozenset)) else v)
        for k, v in flags.items()))


def update_jax(w, grad, state, hyper, flags):
    new_w, new_state, _ = _update_jax(w, grad, state, hyper,
                                      _flags_key(flags))
    return new_w, new_state


def register_update_cost(name, w, grad, state, hyper, flags):
    """Executable cost-registry hook for the jitted GD update kernel
    (core/profiler.py): lower ``_update_jax`` with the exact dispatch
    arguments BEFORE the first call, recording XLA's FLOPs and bytes
    accessed.  Call sites guard with ``profiler.enabled()``; the
    registered-name check FIRST keeps the armed steady state at one
    dict lookup per update."""
    from znicz_tpu.core import profiler
    entry = profiler.cost_entry(name)
    if entry is not None:
        return entry
    return profiler.register_jit_cost(
        name, _update_jax, (w, grad, state, hyper),
        kwargs={"flags_key": _flags_key(flags)},
        param_elements=int(getattr(w, "size", 0) or 0))


def update_numpy(w, grad, state, hyper, flags):
    return update(numpy, w, grad, state, hyper, flags)[:2]


def init_state(w, flags, like=numpy):
    """Allocate the optimizer-state pytree for one parameter tensor."""
    z = (lambda: like.zeros_like(w))
    state = {}
    solvers = flags.get("solvers") or frozenset()
    if "adamw" in solvers:
        # w's own type: a float64 count would promote the float32 moments
        return {"m": z(), "v": z(), "t": like.zeros((), dtype=w.dtype)}
    if flags.get("accumulate"):
        state["acc"] = z()
    if flags.get("need_vel", True):
        state["vel"] = z()
    if "adagrad" in solvers:
        state["adagrad"] = z()
    if "adadelta" in solvers:
        state["adadelta_v"] = z()
        state["adadelta_gv"] = z()
    if "fast" in solvers:
        state["fast"] = z()
    return state
