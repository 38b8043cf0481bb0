"""Local response normalization (AlexNet/Caffe cross-channel LRN).

Reference normalization.py:49-287: with ``s_i = k + alpha *
sum_{j in window(i)} x_j^2`` over the channel window ``[i-n//2, i+n//2]``,

* forward:  ``y_i = x_i / s_i^beta``  (normalization.py:143-154)
* backward: ``dL/dx_i = sum_{j in window(i)} (delta_ij * s_j
  - 2 beta alpha x_i x_j) * err_j / s_j^(beta+1)``
  (normalization.py:223-262)

Defaults alpha=1e-4, beta=0.75, k=2, n=5.
"""

from functools import partial

import numpy
import jax
import jax.numpy as jnp


def _band_matrix(c, n):
    """(c, c) 0/1 band: M[i, j] = 1 iff j is inside i's channel window.
    Trace-time constant (channel counts are static)."""
    idx = numpy.arange(c)
    return (numpy.abs(idx[:, None] - idx[None, :]) <= n // 2)


def _subsums_jax(x2, n):
    """Windowed channel sums (reference _subsums, normalization.py:64-78)
    as ONE band-matrix matmul on the channel (lane) axis.

    A cumsum/fancy-index formulation makes odd-width channel tensors
    (C+2·half, C+2·half+1) and a lane-axis gather, which force
    relayouts between every stage (its cost: not measured on this
    machine; this form runs at 1.5-2.1 times its least time in
    AlexNet, PERF.md section 5).
    ``x2 @ M`` (M symmetric banded, a trace-time constant) keeps the
    NHWC layout bit-for-bit — lanes contract to lanes on the MXU, no
    pads, no gathers — and its autodiff VJP is the same matmul with
    M^T = M.  In bf16 the MXU accumulates in f32, strictly better
    than the bf16 cumsum it replaces."""
    c = x2.shape[3]
    m = jnp.asarray(_band_matrix(c, n), x2.dtype)
    return x2 @ m


@partial(jax.jit, static_argnames=("alpha", "beta", "k", "n"))
def lrn_forward_jax(x, alpha=1e-4, beta=0.75, k=2, n=5):
    s = k + alpha * _subsums_jax(jnp.square(x), n)
    return x / jnp.power(s, beta)


@partial(jax.jit, static_argnames=("alpha", "beta", "k", "n"))
def lrn_backward_jax(x, err_output, alpha=1e-4, beta=0.75, k=2, n=5):
    s = k + alpha * _subsums_jax(jnp.square(x), n)
    sp = jnp.power(s, beta + 1)
    t = err_output / sp  # (B, H, W, C)
    # err_i = s_i * t_i - 2 beta alpha x_i * window_sum_j(x_j t_j)
    xt = _subsums_jax(x * t, n)
    return s * t - 2.0 * beta * alpha * x * xt


def _subsums_numpy(src, n):
    c = src.shape[3]
    out = numpy.empty_like(src)
    half = n // 2
    for i in range(c):
        lo = max(0, i - half)
        hi = min(i + half, c - 1)
        out[:, :, :, i] = src[:, :, :, lo:hi + 1].sum(axis=3)
    return out


def lrn_forward_numpy(x, alpha=1e-4, beta=0.75, k=2, n=5):
    s = k + alpha * _subsums_numpy(numpy.square(x), n)
    return x / numpy.power(s, beta)


def lrn_backward_numpy(x, err_output, alpha=1e-4, beta=0.75, k=2, n=5):
    """Direct port of the reference double loop (normalization.py:223-262),
    vectorized over the window offset."""
    s = k + alpha * _subsums_numpy(numpy.square(x), n)
    sp = numpy.power(s, beta + 1)
    t = err_output / sp
    xt = _subsums_numpy(x * t, n)
    return s * t - 2.0 * beta * alpha * x * xt
