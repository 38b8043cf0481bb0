"""Pallas TPU kernel: max/maxabs pooling WITH winner offsets, one pass.

The unit-graph path needs the reference's flat ``input_offset``
bookkeeping (pooling.py:303-312) so GD pooling can scatter gradients to
the winners.  The XLA formulation materializes a (B, ny, nx, ky*kx, C)
window view and gathers through argmax indices — several HBM round
trips.  This kernel keeps one batch row in VMEM and computes value +
winner offset in a single fused pass: a running strict-greater max over
the ky*kx window cells (unrolled — kernels are small), which also
reproduces the argmax first-winner tie rule.

The kernel is always built for the TPU's compiler.  Off the chip only
tests run it, inside ``pltpu.force_tpu_interpret_mode()`` (the test's
choice, tests/unit/test_pooling.py), and ``ops/pooling.py`` sends
other backends to the gather lowering; the off-chip compile for a
described v5e is tests/unit/test_tpu_compile.py (guide:
/opt/skills/guides/pallas_guide.md).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, out_ref, off_ref, *, ky, kx, sy, sx, ny, nx,
            h, w, c, use_abs):
    b = pl.program_id(0)
    # compute in f32: sub-32-bit dtypes tile (2,128)/(4,128) and their
    # i1 comparison masks cannot relayout against the (8,128) int32
    # winner-index selects (Mosaic rejects the mixed layouts); the
    # bf16->f32->bf16 round trip is value-exact.  supported() rejects
    # dtypes wider than f32 (f64 would round).
    x = x_ref[0].astype(jnp.float32)  # (h, w, c) in VMEM
    # pad so every strided window position exists; Mosaic has no
    # stride>1 vector slices, so striding is done by reshape-and-select
    # enough slack that every (dy, dx) shift has ny*sy / nx*sx rows/cols.
    # Overhang cells carry a KEY of -inf: under the strict-> update an
    # overhang cell can NEVER replace the incumbent (even a real -inf
    # cell, since -inf > -inf is false, and the (0,0) init cell is
    # always real) — no boolean validity masks (Mosaic's i1 relayouts
    # reject the (ny, nx, c) broadcast shapes).  NaN windows remain
    # undefined behavior (select semantics, not numpy argmax).
    ph = ny * sy + ky - 1 - h
    pw = nx * sx + kx - 1 - w
    # constants carry explicit 32-bit types: a bare Python number is a
    # 64-bit constant under jax_enable_x64, which Mosaic cannot convert
    neg = jnp.float32(-jnp.inf)
    xv = jnp.pad(x, ((0, ph), (0, pw), (0, 0)),
                 constant_values=jnp.float32(0))
    xk = jnp.pad(jnp.abs(x) if use_abs else x,
                 ((0, ph), (0, pw), (0, 0)), constant_values=neg)
    hp, wp = h + ph, w + pw

    def row_strip(src, dy):
        rows = jax.lax.slice(src, (dy, 0, 0), (dy + ny * sy, wp, c))
        return rows.reshape(ny, sy, wp, c)[:, 0]  # stride sy

    def cell(rows, dx):
        cols = jax.lax.slice(rows, (0, dx, 0), (ny, dx + nx * sx, c))
        return cols.reshape(ny, nx, sx, c)[:, :, 0]  # stride sx

    best_key = best_val = best_q = None
    for dy in range(ky):
        # hoist the row strips: one slice pair per dy, not per cell
        rows_k = row_strip(xk, dy)
        rows_v = row_strip(xv, dy)
        for dx in range(kx):
            key = cell(rows_k, dx)
            val = cell(rows_v, dx)
            if best_key is None:
                # cell (0, 0) — the window origin is always in-bounds
                best_key, best_val = key, val
                best_q = jnp.zeros((ny, nx, c), jnp.int32)
                continue
            # strict > keeps the FIRST window cell on ties (the unit
            # path's argmax rule)
            better = key > best_key
            best_key = jnp.where(better, key, best_key)
            best_val = jnp.where(better, val, best_val)
            best_q = jnp.where(better, jnp.int32(dy * kx + dx), best_q)
    out_ref[0] = best_val.astype(out_ref.dtype)
    ii = jax.lax.broadcasted_iota(jnp.int32, (ny, nx, c), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (ny, nx, c), 1)
    cc = jax.lax.broadcasted_iota(jnp.int32, (ny, nx, c), 2)
    wy = ii * sy + best_q // jnp.int32(kx)
    wx = jj * sx + best_q % jnp.int32(kx)
    off_ref[0] = ((b * h + wy) * w + wx) * c + cc


@functools.partial(jax.jit,
                   static_argnames=("ky", "kx", "sliding", "use_abs"))
def max_pooling_offsets_pallas(x, ky, kx, sliding, use_abs=False):
    """(output, flat winner offsets) — drop-in for the window-view
    formulation of ops/pooling.max_pooling_jax."""
    from znicz_tpu.ops.pooling import output_spatial
    b, h, w, c = x.shape
    ny, nx = output_spatial(h, w, ky, kx, sliding)
    kernel = functools.partial(
        _kernel, ky=ky, kx=kx, sx=int(sliding[0]), sy=int(sliding[1]),
        ny=ny, nx=nx, h=h, w=w, c=c, use_abs=use_abs)
    def row(i):
        zero = jnp.int32(0)  # not a 64-bit Python 0 under x64
        return (i, zero, zero, zero)

    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, w, c), row)],
        out_specs=[pl.BlockSpec((1, ny, nx, c), row),
                   pl.BlockSpec((1, ny, nx, c), row)],
        out_shape=[jax.ShapeDtypeStruct((b, ny, nx, c), x.dtype),
                   jax.ShapeDtypeStruct((b, ny, nx, c), jnp.int32)],
    )(x)


#: VMEM budget for one batch row; Mosaic's scoped stack is 16MB/core —
#: stay well under (the estimate below is approximate)
_VMEM_BYTES_LIMIT = 8 * 1024 * 1024


def supported(x, ky, kx, sliding, use_abs):
    """Whether the kernel covers this case: float dtypes (the sentinel
    needs a float lattice bottom) whose per-row working set fits the
    Mosaic VMEM stack.  The estimate accounts for LANE padding (the
    minor dim tiles to 128) and the per-unrolled-cell temporaries —
    measured against real Mosaic scoped-vmem failures, not just the
    input bytes.  Shape/dtype inspection only — works on tracers."""
    if not jnp.issubdtype(x.dtype, jnp.floating):
        # jnp (not numpy) so bfloat16 qualifies
        return False
    if x.dtype.itemsize > 4:
        # the kernel computes in f32 — f64 would silently round values
        # and could flip winners; wide dtypes take the window-view path
        return False
    from znicz_tpu.ops.pooling import output_spatial
    h, w, c = int(x.shape[1]), int(x.shape[2]), int(x.shape[3])
    ny, nx = output_spatial(h, w, ky, kx, sliding)
    c_pad = -(-c // 128) * 128
    hp = ny * sliding[1] + ky - 1
    wp = nx * sliding[0] + kx - 1
    # two padded copies + per-dy hoisted row strips (2*ky) + per-cell
    # strided views + bests; the kernel computes in f32 regardless of
    # the input dtype.  Calibrated against Mosaic's scoped-vmem
    # accounting (it rejected ~17.6M for the 33x33x32 k=3 case).
    est = 4 * c_pad * (hp * wp * (2 + 2 * ky) +
                       ny * nx * (2 * ky * kx + 8))
    return est <= _VMEM_BYTES_LIMIT
