"""Pallas TPU kernels: the Mamba-2 mixer's conv stage (``ops/transformer.py:
causal_conv``, a depthwise causal conv cut at document starts, its bias and
its silu), forward and backward under one ``jax.custom_vjp``.

The ``jax.numpy`` form builds each lag of the conv with a pad along the
positions (the sublanes of a float32 tile) and a broadcast mask, and its
backward is those pads' and masks' transposes and a reduction a weight;
XLA runs them as several passes over the ``(B, S, C)`` float32 arrays.
Here one grid step is one row's block of positions and channels, worked a
strip of :data:`HALO` positions at a time so that a strip's values stay in
registers (a kernel that works its whole block as one value stores every
step's values to VMEM and is bound by that): the forward reads the block of
``x`` (bf16 column blocks of the mixer's input projection itself, no slice
copied out) with the :data:`HALO` positions before it, and writes ``silu(b
+ w[K-1] x_t + ... + w[0] x_{t-K+1})`` in float32, each term summed in the
``jax.numpy`` form's order and read only where its position lies in
``t``'s document; the backward reads the float32 cotangent and ``x`` with a
halo on either side, recomputes the pre-activation in registers and writes
``dx`` in ``x``'s dtype beside float32 partial sums of ``dw`` and ``db`` a
grid step, summed outside.

The document cut comes in as one int32 word a position, made outside from
the documents exactly as ``causal_conv`` compares them: bit ``l - 1`` says
that the term at lag ``l`` counts at ``t`` (``doc[t - l] == doc[t]``, false
before the row's start), bit ``K - 2 + l`` that it counts at ``t + l``
(false past the row's end), which the backward's ``dx`` reads.  A halo
block that lies before the row's start or past its end is clamped to the
row's first or last; the words mask everything it brings.

Where it runs: ``transformer.conv_kernel_suits``.  The kernels are built for
the TPU's compiler; off the chip only tests run them, inside
``pltpu.force_tpu_interpret_mode()`` (``tests/unit/test_pallas_conv.py``),
and the off-chip compile for a described v5e is
``tests/unit/test_tpu_compile.py``.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops.pallas_ssd import _block
from znicz_tpu.ops.transformer import CONV_HALO as HALO
from znicz_tpu.ops.transformer import CONV_LAGS as _TAIL

#: a grid step's positions and channels at most, both kernels (on a v5e at
#: the Nemotron cell's widths a larger block is no faster forward and
#: slower backward: PERF.md section 6)
BLOCK = (512, 512)

_F32 = jnp.float32


def _words(doc, k):
    """``(B, S, 1)`` int32: the conv's document cut a position, as the
    module says, for a conv of width ``k`` over rows numbered ``doc``."""
    s = doc.shape[1]
    word = jnp.zeros(doc.shape, jnp.int32)
    for lag in range(1, k):
        same = jnp.pad(doc, ((0, 0), (lag, 0)), constant_values=-1)[:, :s] \
            == doc
        ahead = jnp.pad(same[:, lag:], ((0, 0), (0, lag)))
        word = word | (same.astype(jnp.int32) << (lag - 1)) \
            | (ahead.astype(jnp.int32) << (k - 2 + lag))
    return word[..., None]


def _bit(word, k):
    return (word >> k) & 1 == 1


def _lags(x, tail, word, k):
    """``x (HALO, ch)`` float32 at each lag ``1 .. k - 1``, the rows before
    the strip read from ``tail``, each 0 where the word's bit says the term
    does not count."""
    ext = jnp.concatenate([tail, x], axis=0)
    return [jnp.where(_bit(word, lag - 1),
                      pltpu.roll(ext, lag, 0)[_TAIL:], 0.0)
            for lag in range(1, k)]


def _pre(x, backs, w, b, k):
    """The pre-activation, summed in ``causal_conv``'s order."""
    out = b + w[k - 1:k] * x
    for lag, back in enumerate(backs, 1):
        out = out + w[k - 1 - lag:k - lag] * back
    return out


def _forward_kernel(x_ref, before_ref, word_ref, w_ref, b_ref, out_ref, *,
                    k):
    """One block, a strip of :data:`HALO` positions at a time (its values
    stay in registers): the conv, the halo before the block giving the
    first strip its lags and each strip the next one's."""
    w, b = w_ref[...], b_ref[...]

    def strip(r, tail):
        t = pl.multiple_of(r * HALO, HALO)
        x = x_ref[0, pl.ds(t, HALO)].astype(_F32)
        word = word_ref[0, pl.ds(t, HALO)]
        out_ref[0, pl.ds(t, HALO)] = jax.nn.silu(
            _pre(x, _lags(x, tail, word, k), w, b, k))
        return x[HALO - _TAIL:]

    jax.lax.fori_loop(0, x_ref.shape[1] // HALO, strip,
                      before_ref[0].astype(_F32)[HALO - _TAIL:])


def _backward_kernel(x_ref, before_ref, after_ref, word_ref, word_after_ref,
                     dy_ref, dy_after_ref, w_ref, b_ref, dx_ref, dwb_ref,
                     acc, *, k):
    """One block, a strip of :data:`HALO` positions at a time: the
    pre-activation recomputed and the silu's cotangent ``g`` at the strip,
    its terms of ``dw`` and ``db`` summed into ``acc``, and ``dx`` of the
    strip before it, which ``g`` at the strip's first ``k - 1`` positions
    reaches; the halo after the block gives the last strip its ``g``."""
    w, b = w_ref[...], b_ref[...]
    p = x_ref.shape[1]
    acc[...] = jnp.zeros_like(acc)

    def cotangent(x, tail, word, dy, sums):
        backs = _lags(x, tail, word, k)
        pre = _pre(x, backs, w, b, k)
        sig = jax.nn.sigmoid(pre)
        g = dy * (sig * (1.0 + pre * (1.0 - sig)))
        if sums:
            acc[k] = acc[k] + g
            acc[k - 1] = acc[k - 1] + g * x
            for lag, back in enumerate(backs, 1):
                acc[k - 1 - lag] = acc[k - 1 - lag] + g * back
        return g

    def dx_of(g_prev, g, word_prev):
        ext = jnp.concatenate([g_prev, g[:_TAIL]], axis=0)
        rows = ext.shape[0]
        dx = w[k - 1:k] * g_prev
        for lag in range(1, k):
            dx = dx + w[k - 1 - lag:k - lag] * jnp.where(
                _bit(word_prev, k - 2 + lag),
                pltpu.roll(ext, rows - lag, 0)[:HALO], 0.0)
        return dx.astype(dx_ref.dtype)

    def strip(r, carry):
        g_prev, tail = carry
        t = pl.multiple_of(r * HALO, HALO)
        x = x_ref[0, pl.ds(t, HALO)].astype(_F32)
        g = cotangent(x, tail, word_ref[0, pl.ds(t, HALO)],
                      dy_ref[0, pl.ds(t, HALO)], True)
        before = pl.multiple_of(t - HALO, HALO)
        dx_ref[0, pl.ds(before, HALO)] = dx_of(
            g_prev, g, word_ref[0, pl.ds(before, HALO)])
        return g, x[HALO - _TAIL:]

    x = x_ref[0, :HALO].astype(_F32)
    g = cotangent(x, before_ref[0].astype(_F32)[HALO - _TAIL:],
                  word_ref[0, :HALO], dy_ref[0, :HALO], True)
    g, tail = jax.lax.fori_loop(1, p // HALO, strip, (g, x[HALO - _TAIL:]))
    after = cotangent(after_ref[0].astype(_F32), tail, word_after_ref[0],
                      dy_after_ref[0], False)
    dx_ref[0, p - HALO:] = dx_of(g, after, word_ref[0, p - HALO:])
    for i in range(k + 1):
        dwb_ref[0, 0, i:i + 1] = jnp.sum(acc[i], axis=0, keepdims=True)


def _specs(s, first, width, k):
    """The block specs both kernels share and the grid over ``(row,
    position block, channel block)``: the block of ``x`` (the largest that
    divides the row and the channels, up to :data:`BLOCK`) and the halos
    before and after it (clamped to the row), the words of the block and
    of the halo after it, ``w`` and ``b``."""
    pos, ch = math.gcd(s, BLOCK[0]), math.gcd(first, width, BLOCK[1])
    n, last, col = pos // HALO, s // HALO - 1, first // ch
    specs = {
        "x": _block((1, pos, ch), lambda i, p, j: (i, p, col + j)),
        "before": _block((1, HALO, ch), lambda i, p, j: (
            i, jnp.maximum(p * n - 1, 0), col + j)),
        "after": _block((1, HALO, ch), lambda i, p, j: (
            i, jnp.minimum((p + 1) * n, last), col + j)),
        "word": _block((1, pos, 1), lambda i, p, j: (i, p, 0)),
        "word_after": _block((1, HALO, 1), lambda i, p, j: (
            i, jnp.minimum((p + 1) * n, last), 0)),
        "out": _block((1, pos, ch), lambda i, p, j: (i, p, j)),
        "out_after": _block((1, HALO, ch), lambda i, p, j: (
            i, jnp.minimum((p + 1) * n, last), j)),
        "w": _block((k, ch), lambda i, p, j: (0, j)),
        "b": _block((1, ch), lambda i, p, j: (0, j)),
        "dwb": _block((1, 1, k + 1, ch), lambda i, p, j: (i, p, 0, j))}
    return specs, (s // pos, width // ch)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


def _forward(zxd, w, b, word, first, width):
    bsz, s, _ = zxd.shape
    k = w.shape[0]
    sp, grid = _specs(s, first, width, k)
    return pl.pallas_call(
        functools.partial(_forward_kernel, k=k),
        grid=(bsz,) + grid,
        in_specs=[sp["x"], sp["before"], sp["word"], sp["w"], sp["b"]],
        out_specs=sp["out"],
        out_shape=jax.ShapeDtypeStruct((bsz, s, width), _F32),
        compiler_params=_PARAMS,
        name="ssd_conv_forward",
    )(zxd, zxd, word, w, b)


def _backward(zxd, w, b, word, dy, first, width):
    bsz, s, _ = zxd.shape
    k = w.shape[0]
    sp, grid = _specs(s, first, width, k)
    ab = jax.ShapeDtypeStruct
    return pl.pallas_call(
        functools.partial(_backward_kernel, k=k),
        grid=(bsz,) + grid,
        in_specs=[sp["x"], sp["before"], sp["after"], sp["word"],
                  sp["word_after"], sp["out"], sp["out_after"], sp["w"],
                  sp["b"]],
        out_specs=[sp["out"], sp["dwb"]],
        out_shape=[ab((bsz, s, width), zxd.dtype),
                   ab((bsz, grid[0], k + 1, width), _F32)],
        scratch_shapes=[pltpu.VMEM((k + 1, HALO, sp["w"].block_shape[1]),
                                   _F32)],
        compiler_params=_PARAMS,
        name="ssd_conv_backward",
    )(zxd, zxd, zxd, word, word, dy, dy, w, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _conv(zxd, w, b, word, first, width):
    return _forward(zxd, w, b, word, first, width)


def _conv_fwd(zxd, w, b, word, first, width):
    return _forward(zxd, w, b, word, first, width), (zxd, w, b, word)


def _conv_bwd(first, width, res, dy):
    zxd, w, b, word = res
    k = w.shape[0]
    dx, dwb = _backward(zxd, w, b, word, dy, first, width)
    dwb = dwb.sum(axis=(0, 1))
    right = zxd.shape[-1] - first - width
    return (jnp.pad(dx, ((0, 0), (0, 0), (first, right))), dwb[:k],
            dwb[k:], None)


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv(zxd, w, b, doc, first, width):
    """``transformer.causal_conv(zxd[..., first:first + width], w, b,
    doc)``, ``(B, S, width)`` float32, with ``x`` read as column blocks of
    ``zxd`` itself; ``dzxd`` is ``dx`` placed at those columns.  The caller
    has checked ``transformer.conv_kernel_suits``."""
    return _conv(zxd, w.astype(_F32), b.astype(_F32).reshape(1, -1),
                 _words(doc, w.shape[0]), first, width)
