"""Pooling ops — max / maxabs / avg / stochastic, forward + backward.

Reference semantics (pooling.py:67-548, gd_pooling.py:58-287):
* layout NHWC; ``sliding`` (x, y); ceil-mode output size
  ``out = ceil((s - k) / stride) + 1`` — windows may overhang the
  right/bottom edge and are then truncated (pooling.py:96-105);
* max/maxabs record ``input_offset``: the FLAT index into the input array
  of the winning element (pooling.py:303-312); backward scatters
  ``err_output`` additively to those offsets (gd_pooling.py:233-247);
* avg divides by the TRUNCATED window size (pooling.py:548) and backward
  spreads err/(window size) over the truncated window (gd_pooling.py:272);
* stochastic pooling samples an element with probability proportional to
  its (abs) value using a uint16 random stream (pooling.py:368-480);
  samples uniformly when the window sums to zero.

The jax paths build strided window views via advanced indexing (the
patches are fused away by XLA) and use masked argmax/segment-sum —
one jitted computation per op, no host round-trips.
"""

from functools import lru_cache, partial
import logging

import numpy
import jax
import jax.numpy as jnp
from jax import lax


def output_spatial(sy, sx, ky, kx, sliding):
    """Ceil-mode output geometry (reference pooling.py:96-105)."""
    outs = []
    for last, stride in ((sx - kx, sliding[0]), (sy - ky, sliding[1])):
        o = last // stride + 1
        if last % stride != 0:
            o += 1
        outs.append(o)
    return outs[1], outs[0]  # ny, nx


def _ceil_mode_pads(sy, sx, ky, kx, sliding):
    """Right/bottom padding that makes every ceil-mode window in range."""
    ny, nx = output_spatial(sy, sx, ky, kx, sliding)
    pad_y = (ny - 1) * sliding[1] + ky - sy
    pad_x = (nx - 1) * sliding[0] + kx - sx
    return ny, nx, ((0, 0), (0, pad_y), (0, pad_x), (0, 0))


def _window_view_jax(x, ky, kx, sliding, fill):
    """(B, ny, nx, ky*kx, C) window view + validity mask (ky*kx,) grids.

    Overhanging cells are filled with ``fill`` and masked invalid.
    """
    b, sy, sx, c = x.shape
    ny, nx, pads = _ceil_mode_pads(sy, sx, ky, kx, sliding)
    xp = jnp.pad(x, pads, constant_values=fill)
    rows = (jnp.arange(ny) * sliding[1])[:, None] + jnp.arange(ky)[None, :]
    cols = (jnp.arange(nx) * sliding[0])[:, None] + jnp.arange(kx)[None, :]
    # (B, ny, ky, nx, kx, C) -> (B, ny, nx, ky, kx, C)
    win = xp[:, rows[:, None, :, None], cols[None, :, None, :], :]
    valid = ((rows < sy)[:, None, :, None] &
             (cols < sx)[None, :, None, :])  # (ny, nx, ky, kx)
    return (win.reshape(b, ny, nx, ky * kx, c),
            valid.reshape(ny, nx, ky * kx), ny, nx)


def _flat_offsets_jax(shape, ny, nx, ky, kx, sliding, q):
    """Flat input index for window cell q (B, ny, nx, C) of each output."""
    b, sy, sx, c = shape
    dy, dx = q // kx, q % kx  # (B, ny, nx, C)
    y = jnp.arange(ny).reshape(1, ny, 1, 1) * sliding[1] + dy
    x = jnp.arange(nx).reshape(1, 1, nx, 1) * sliding[0] + dx
    bi = jnp.arange(b).reshape(b, 1, 1, 1)
    ci = jnp.arange(c).reshape(1, 1, 1, c)
    return ((bi * sy + y) * sx + x) * c + ci


def max_pooling_jax(x, ky, kx, sliding, use_abs=False):
    """Returns (output, input_offset) — offsets are flat input indices.

    On a TPU, float inputs whose per-row working set fits VMEM run the
    fused Pallas kernel (ops/pallas_pooling.py — one VMEM pass);
    everything else — other dtypes, oversized feature maps, other
    backends — runs the window-view gather lowering.  Both reproduce
    the numpy twin bit-exactly, offsets included, with first-winner
    ties.  The choice is made from shapes and the backend alone, never
    from a compile that failed: a kernel the compiler refuses raises.
    The kernel's documented shape limit (``pallas_pooling.supported``:
    float dtypes up to f32, one batch row within the VMEM budget) sends
    the rest to the gather lowering, logged once per shape; off-TPU
    only tests run the kernel, interpreted.

    NOT differentiable through the Pallas path — this is the
    unit-graph op whose backward is the offset scatter
    (max_pooling_backward_jax); autodiff users take pooling_fwd_jax
    or max_pooling_gather_jax."""
    from znicz_tpu.ops import pallas_pooling
    sliding = tuple(int(s) for s in sliding)
    if jax.default_backend() == "tpu":
        key = (tuple(x.shape), str(x.dtype), ky, kx, sliding)
        if pallas_pooling.supported(x, ky, kx, sliding, use_abs):
            _log_lowering("pallas", *key)
            return pallas_pooling.max_pooling_offsets_pallas(
                x, ky, kx, sliding, use_abs=use_abs)
        _log_lowering("gather", *key)
    return max_pooling_gather_jax(x, ky, kx, sliding, use_abs)


@partial(jax.jit, static_argnames=("ky", "kx", "sliding", "use_abs"))
def max_pooling_gather_jax(x, ky, kx, sliding, use_abs=False):
    win, valid, ny, nx = _window_view_jax(x, ky, kx, sliding, 0.0)
    key = jnp.abs(win) if use_abs else win
    key = jnp.where(valid[None, :, :, :, None], key, -jnp.inf)
    q = jnp.argmax(key, axis=3)  # (B, ny, nx, C) in (dy, dx) C-order
    val = jnp.take_along_axis(win, q[:, :, :, None, :], axis=3)[:, :, :, 0, :]
    offs = _flat_offsets_jax(x.shape, ny, nx, ky, kx, sliding, q)
    return val, offs.astype(jnp.int32)


@lru_cache(maxsize=None)
def _log_lowering(lowering, shape, dtype, ky, kx, sliding):
    """One log line per (shape, kernel) — which max-pool lowering the
    TPU path picked ("gather" there means the shape is outside the
    Pallas kernel's dtype/VMEM limit)."""
    logging.getLogger("pooling").info(
        "max pooling %s %s k%dx%d s%s -> %s", shape, dtype, ky, kx,
        sliding, lowering)


def _trunc_divisor(sy, sx, ky, kx, sliding, ny, nx):
    """Truncated-window element counts (ny, nx) — the reference's avg
    divisor (pooling.py:548); pure geometry, a trace-time constant."""
    t_y = numpy.minimum(ky, sy - numpy.arange(ny) * sliding[1])
    t_x = numpy.minimum(kx, sx - numpy.arange(nx) * sliding[0])
    return (t_y[:, None] * t_x[None, :]).astype(numpy.float32)


@partial(jax.jit, static_argnames=("ky", "kx", "sliding", "mode"))
def pooling_fwd_jax(x, ky, kx, sliding, mode="max"):
    """Offset-free pooling via ``lax.reduce_window`` — the TPU-native
    formulation (no gathers; the max VJP lowers to select-and-scatter).

    Used by the fused path, where the backward comes from ``jax.grad``
    and the reference's flat ``input_offset`` bookkeeping is not needed.
    NOTE maxabs breaks exact-|tie| windows toward the positive value; the
    reference (and ``max_pooling_jax``) take the first occurrence — use
    the offset path where that parity matters.
    Ceil-mode overhang is realized as right/bottom window padding: padded
    cells contribute the reduction identity, which reproduces the
    reference's truncated-window semantics for max and (with the
    geometry-constant divisor below) for avg.
    """
    b, sy, sx, c = x.shape
    dims = (1, ky, kx, 1)
    strides = (1, sliding[1], sliding[0], 1)
    ny, nx, pads = _ceil_mode_pads(sy, sx, ky, kx, sliding)
    # init values must be CONCRETE numpy scalars so jax recognizes the
    # monoid (max/min/add) and uses the differentiable specialized
    # reduce-window primitives; traced inits fall back to the generic,
    # non-differentiable form
    ninf = numpy.asarray(-numpy.inf, x.dtype)
    pinf = numpy.asarray(numpy.inf, x.dtype)
    if mode == "max":
        return lax.reduce_window(x, ninf, lax.max, dims, strides, pads)
    if mode == "maxabs":
        # the max-|x| element is either the window max or the window min;
        # max/min reductions keep the op differentiable (custom reducers
        # have no VJP)
        mx = lax.reduce_window(x, ninf, lax.max, dims, strides, pads)
        mn = lax.reduce_window(x, pinf, lax.min, dims, strides, pads)
        return jnp.where(jnp.abs(mx) >= jnp.abs(mn), mx, mn)
    if mode == "avg":
        s = lax.reduce_window(x, numpy.asarray(0, x.dtype), lax.add,
                              dims, strides, pads)
        cnt = _trunc_divisor(sy, sx, ky, kx, sliding, ny, nx)
        return s / jnp.asarray(cnt, x.dtype)[None, :, :, None]
    raise ValueError(mode)


@partial(jax.jit, static_argnames=("ky", "kx", "sliding"))
def avg_pooling_jax(x, ky, kx, sliding):
    return pooling_fwd_jax(x, ky, kx, sliding, mode="avg")


@partial(jax.jit, static_argnames=("ky", "kx", "sliding", "use_abs"))
def stochastic_pooling_jax(x, rand_u16, ky, kx, sliding, use_abs=False):
    """rand_u16: uint16 stream of size >= output size (row-major order).

    Reference pooling.py:434-480: position = rnd * vsum / 65536 over the
    running prefix of positive (abs) values; uniform window index when the
    window sum is zero.
    """
    b, sy, sx, c = x.shape
    win, valid, ny, nx = _window_view_jax(x, ky, kx, sliding, 0.0)
    key = jnp.abs(win) if use_abs else jnp.maximum(win, 0.0)
    key = key * valid[None, :, :, :, None]
    vsum = key.sum(axis=3)  # (B, ny, nx, C)
    rnd = rand_u16[:b * ny * nx * c].reshape(b, ny, nx, c).astype(x.dtype)
    position = rnd * vsum / 65536.0
    csum = jnp.cumsum(key, axis=3)
    # first q with position <= csum[q] (and a positive contribution)
    hit = position[:, :, :, None, :] <= csum
    q_prop = jnp.argmax(hit, axis=3)
    # zero-sum window: uniform index into the TRUNCATED window
    # (reference indexes the truncated cut, pooling.py:437-440)
    ty = jnp.minimum(ky, sy - jnp.arange(ny) * sliding[1]).reshape(
        1, ny, 1, 1)
    tx = jnp.minimum(kx, sx - jnp.arange(nx) * sliding[0]).reshape(
        1, 1, nx, 1)
    rnd32 = rand_u16[:b * ny * nx * c].reshape(b, ny, nx, c).astype(
        jnp.uint32)
    k_trunc = (rnd32 * (ty * tx).astype(jnp.uint32) >> 16).astype(jnp.int32)
    q_unif = (k_trunc // tx) * kx + k_trunc % tx
    q = jnp.where(vsum > 0, q_prop, q_unif)
    val = jnp.take_along_axis(win, q[:, :, :, None, :], axis=3)[:, :, :, 0, :]
    offs = _flat_offsets_jax(x.shape, ny, nx, ky, kx, sliding, q)
    return val, offs.astype(jnp.int32)


@partial(jax.jit, static_argnames=("ky", "kx", "use_abs"))
def stochastic_pool_depool_jax(x, rand_u16, ky, kx, use_abs=False):
    """Stochastic pooling + depooling in place (reference ocl/pooling.cl
    ``stochastic_pooling_depooling``): one winner per non-overlapping
    window, sampled with probability proportional to max(x, 0) (or |x|);
    the output has the INPUT shape — the winner keeps its original signed
    value, every other cell becomes 0.  Zero-sum windows sample uniformly
    over the truncated window via the kernel's pos_add=1 cumsum walk.

    Returns (y, offs): y is input-shaped, offs the winners' flat input
    indices (window-grid shaped, for IDistributable/export parity).
    """
    sliding = (kx, ky)
    b, sy, sx, c = x.shape
    win, valid, ny, nx = _window_view_jax(x, ky, kx, sliding, 0.0)
    vmask = valid[None, :, :, :, None]
    key = jnp.abs(win) if use_abs else jnp.maximum(win, 0.0)
    key = key * vmask
    vsum = key.sum(axis=3)                      # (B, ny, nx, C)
    cnt = valid.sum(axis=2).astype(x.dtype)     # (ny, nx)
    rnd = rand_u16[:b * ny * nx * c].reshape(b, ny, nx, c).astype(x.dtype)
    nonzero = vsum > 0
    total = jnp.where(nonzero, vsum, cnt[None, :, :, None])
    pos = rnd * total / 65536.0
    # zero-sum windows walk a cumsum of ones over the valid cells
    keyz = jnp.where(nonzero[:, :, :, None, :], key,
                     vmask.astype(x.dtype) * jnp.ones_like(win))
    csum = jnp.cumsum(keyz, axis=3)
    hit = pos[:, :, :, None, :] <= csum
    q = jnp.argmax(hit, axis=3)
    offs = _flat_offsets_jax(x.shape, ny, nx, ky, kx, sliding, q)
    vals = jnp.take_along_axis(win, q[:, :, :, None, :], axis=3)[:, :, :, 0, :]
    y = jnp.zeros((x.size,), x.dtype).at[offs.reshape(-1)].set(
        vals.reshape(-1))
    return y.reshape(x.shape), offs.astype(jnp.int32)


@partial(jax.jit, static_argnames=("input_size", "input_shape"))
def max_pooling_backward_jax(err_output, input_offset, input_size,
                             input_shape):
    """Scatter-add err to the winning offsets (gd_pooling.py:233-247)."""
    flat = jnp.zeros((input_size,), dtype=err_output.dtype)
    flat = flat.at[input_offset.reshape(-1)].add(err_output.reshape(-1))
    return flat.reshape(input_shape)


@partial(jax.jit, static_argnames=("ky", "kx", "sliding", "input_shape"))
def avg_pooling_backward_jax(err_output, ky, kx, sliding, input_shape):
    """Spread err/(truncated window size) over each window
    (gd_pooling.py:272-287) — via VJP of the forward average."""
    zeros = jnp.zeros(input_shape, dtype=err_output.dtype)
    _, vjp = jax.vjp(
        lambda x: avg_pooling_jax(x, ky, kx, sliding), zeros)
    return vjp(err_output)[0]


# -- numpy twins (the executable spec) --------------------------------------

def max_pooling_numpy(x, ky, kx, sliding, use_abs=False):
    b, sy, sx, c = x.shape
    ny, nx = output_spatial(sy, sx, ky, kx, sliding)
    out = numpy.empty((b, ny, nx, c), dtype=x.dtype)
    offs = numpy.empty((b, ny, nx, c), dtype=numpy.int32)
    for bi in range(b):
        for ci in range(c):
            for i in range(ny):
                y1 = i * sliding[1]
                y2 = min(y1 + ky, sy)
                for j in range(nx):
                    x1 = j * sliding[0]
                    x2 = min(x1 + kx, sx)
                    cut = x[bi, y1:y2, x1:x2, ci]
                    k = numpy.abs(cut).argmax() if use_abs else cut.argmax()
                    di, dj = numpy.unravel_index(k, cut.shape)
                    out[bi, i, j, ci] = cut[di, dj]
                    offs[bi, i, j, ci] = numpy.ravel_multi_index(
                        (bi, y1 + di, x1 + dj, ci), x.shape)
    return out, offs


def avg_pooling_numpy(x, ky, kx, sliding):
    b, sy, sx, c = x.shape
    ny, nx = output_spatial(sy, sx, ky, kx, sliding)
    out = numpy.empty((b, ny, nx, c), dtype=x.dtype)
    for i in range(ny):
        y1 = i * sliding[1]
        y2 = min(y1 + ky, sy)
        for j in range(nx):
            x1 = j * sliding[0]
            x2 = min(x1 + kx, sx)
            cut = x[:, y1:y2, x1:x2, :]
            out[:, i, j, :] = cut.sum(axis=(1, 2)) / (
                (y2 - y1) * (x2 - x1))
    return out


def stochastic_pooling_numpy(x, rand_u16, ky, kx, sliding, use_abs=False):
    """Bit-exact port of the reference selection loop
    (pooling.py:434-480)."""
    b, sy, sx, c = x.shape
    ny, nx = output_spatial(sy, sx, ky, kx, sliding)
    out = numpy.empty((b, ny, nx, c), dtype=x.dtype)
    offs = numpy.empty((b, ny, nx, c), dtype=numpy.int32)
    oshape = (b, ny, nx, c)
    for bi in range(b):
        for i in range(ny):
            y1 = i * sliding[1]
            y2 = min(y1 + ky, sy)
            for j in range(nx):
                x1 = j * sliding[0]
                x2 = min(x1 + kx, sx)
                for ci in range(c):
                    cut = x[bi, y1:y2, x1:x2, ci]
                    index = numpy.ravel_multi_index((bi, i, j, ci), oshape)
                    rnd = int(rand_u16[index])
                    vals = cut.ravel()
                    key = numpy.abs(vals) if use_abs else \
                        numpy.where(vals > 0, vals, 0)
                    vsum = key.sum()
                    if vsum == 0:
                        k = int(rnd * vals.size) >> 16
                    else:
                        position = rnd * vsum / 65536.0
                        acc = 0.0
                        k = vals.size - 1
                        for t in range(vals.size):
                            acc += key[t]
                            if position <= acc:
                                k = t
                                break
                    di, dj = numpy.unravel_index(k, cut.shape)
                    out[bi, i, j, ci] = cut[di, dj]
                    offs[bi, i, j, ci] = numpy.ravel_multi_index(
                        (bi, y1 + di, x1 + dj, ci), x.shape)
    return out, offs


def stochastic_pool_depool_numpy(x, rand_u16, ky, kx, use_abs=False):
    """Numpy twin of :func:`stochastic_pool_depool_jax` — a direct port of
    the OpenCL kernel's three-pass walk (sum, select, zero-fill)."""
    sliding = (kx, ky)
    b, sy, sx, c = x.shape
    ny, nx = output_spatial(sy, sx, ky, kx, sliding)
    y = numpy.zeros_like(x)
    offs = numpy.empty((b, ny, nx, c), dtype=numpy.int32)
    oshape = (b, ny, nx, c)
    for bi in range(b):
        for i in range(ny):
            y1 = i * sliding[1]
            y2 = min(y1 + ky, sy)
            for j in range(nx):
                x1 = j * sliding[0]
                x2 = min(x1 + kx, sx)
                for ci in range(c):
                    cut = x[bi, y1:y2, x1:x2, ci]
                    vals = cut.ravel()
                    key = numpy.abs(vals) if use_abs else \
                        numpy.maximum(vals, 0)
                    vsum = key.sum()
                    index = numpy.ravel_multi_index((bi, i, j, ci), oshape)
                    rnd = int(rand_u16[index])
                    pos_add = 1.0 if vsum == 0 else 0.0
                    pos_factor = vals.size if vsum == 0 else vsum
                    pos = pos_factor * rnd / 65536.0
                    acc = 0.0
                    k = 0
                    for t in range(vals.size):
                        acc += key[t] + pos_add
                        if pos <= acc:
                            k = t
                            break
                    di, dj = numpy.unravel_index(k, cut.shape)
                    off = numpy.ravel_multi_index(
                        (bi, y1 + di, x1 + dj, ci), x.shape)
                    y[bi, y1 + di, x1 + dj, ci] = cut[di, dj]
                    offs[bi, i, j, ci] = off
    return y, offs


def max_pooling_backward_numpy(err_output, input_offset, input_shape):
    err_input = numpy.zeros(input_shape, dtype=err_output.dtype)
    flat = err_input.reshape(-1)
    for err, off in numpy.nditer([err_output, input_offset]):
        flat[off] += err
    return err_input


def avg_pooling_backward_numpy(err_output, ky, kx, sliding, input_shape):
    b, sy, sx, c = input_shape
    err_input = numpy.zeros(input_shape, dtype=err_output.dtype)
    ny, nx = err_output.shape[1], err_output.shape[2]
    for i in range(ny):
        y1 = i * sliding[1]
        y2 = min(y1 + ky, sy)
        for j in range(nx):
            x1 = j * sliding[0]
            x2 = min(x1 + kx, sx)
            err_input[:, y1:y2, x1:x2, :] += (
                err_output[:, i:i + 1, j:j + 1, :] /
                ((y2 - y1) * (x2 - x1)))
    return err_input
