"""Pallas TPU kernels: Mamba-2's chunked scan (``ops/transformer.py:
ssd_scan``) with ``D x``, the mixer's scan stage, forward and backward under
one ``jax.custom_vjp``.

The ``jax.numpy`` form lowers the scan into HBM round trips: every chunk's
``(heads, chunk, chunk)`` float32 decays, its scores, its own state and the
carried states are written and read back, and a ``lax.scan`` over the
chunks carries the state.  Here one grid step is one row's chunk of one
group of heads (the ``heads / groups`` heads that share ``B`` and ``C``):
the chunk's ``C B^T``, each head's decays and scores, the carried state's
part and the chunk's own state live in VMEM, and the group's carried state
(``(state, heads of the group x head_dim)`` float32, kept transposed so
that every product is a plain one) stays in VMEM from one chunk to the next
(the chunk axis of the grid runs in order).  ``x``, ``B`` and ``C`` are
read as blocks of the conv's float32 output itself and cast in VMEM; HBM
sees that output, the per-position factors below and the row's document
ids go in and ``y + D x`` come out.

The per-position factors are made outside, in ``jax.numpy``, exactly as
``ssd_scan`` makes them (they are ``(B, S, H)`` float32, a 64th of ``x``):
the chunk's cumulative decay ``cum``, the steps ``dt``, ``to_end * dt``
(the weight of ``x`` in the chunk's own state), ``inflow * exp(cum)`` (the
carried state's weight at each position) and the carry factor ``keep`` of
every chunk; their gradients are the kernels' outputs, and ``jax``
differentiates the rest (the exponentials, the masks, the softplus).

Arithmetic as the ``jax.numpy`` form's: ``x``, ``B``, ``C``, the scores,
``x * to_end * dt`` and the carried state enter the products in ``cd``
with float32 accumulation; ``cum``, the decays and their exponentials and
the carried state are float32.  The backward accumulates every gradient in
float32 and cuts each float32 cotangent that enters a product into two
parts in ``cd`` (:data:`COTANGENT_PARTS`): its gradients lie closer to
the float32 form's than the ``jax.numpy`` form's, whose cotangents of the
``cd`` operands are themselves ``cd``.

A head's ``head_dim`` lanes of ``x`` lie in one 128-lane tile with its
neighbours' (64 wide: two heads a tile): the heads of a tile have their
score blocks stacked into one product with the tile, whose rows each head
takes at its own lanes, which costs the MXU nothing (it multiplies 128
columns at a time).

The backward runs the chunks in reverse, carrying the state's cotangent in
VMEM, and works on the scores transposed, which it makes as the forward
makes the scores, so that no product needs an operand transposed but
``C`` and the summed ``C B^T`` cotangent, once a step.  It reads the state
every chunk took in, which a pass of the forward kernel that writes only
those states gives it (``(B, chunks, groups, state, rep * head_dim)``
float32, alive during the mixer's backward alone).

Where it runs: ``transformer.ssd_kernel_suits``.  The kernels are built for
the TPU's compiler; off the chip only tests run them, inside
``pltpu.force_tpu_interpret_mode()`` (``tests/unit/test_pallas_ssd.py``),
and the off-chip compile for a described v5e is
``tests/unit/test_tpu_compile.py``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lanes of a vector register and of the MXU's operands
LANES = 128

_F32 = jnp.float32
#: contract the last dimension of both operands (``a @ b.T``)
_NT = (((1,), (1,)), ((), ()))


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=_F32)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _cast(v, cd):
    return v if cd is None else v.astype(cd)


#: the parts in ``cd`` that a float32 cotangent is cut into where it enters
#: a product with an operand in ``cd``: their sum holds the cotangent to
#: about 16 bits, at one more pass of the MXU a part
COTANGENT_PARTS = 2


def _parts(v, cd):
    """``v`` float32 as :data:`COTANGENT_PARTS` values in ``cd`` that sum
    to it (``v`` itself where ``cd`` is None)."""
    if cd is None:
        return [v]
    out = []
    for _ in range(COTANGENT_PARTS):
        out.append(v.astype(cd))
        v = v - out[-1].astype(_F32)
    return out


def _cot_dot(parts, other, dims=None, first=True):
    """The product of a cotangent's :func:`_parts` with ``other``, the
    cotangent the first operand (or the second, where not ``first``)."""
    out = None
    for part in parts:
        d = _dot(part, other, dims) if first else _dot(other, part, dims)
        out = d if out is None else out + d
    return out


def _heads_of(j, rep, hp):
    """``(head, first lane, end lane)`` of every head of a group that lies
    in lane tile ``j`` of the group's ``rep * hp`` lanes, lanes counted
    inside the tile."""
    lo, hi = j * LANES, (j + 1) * LANES
    return [(r, max(r * hp - lo, 0), min((r + 1) * hp - lo, LANES))
            for r in range(rep) if r * hp < hi and (r + 1) * hp > lo]


def _lanes(cols, rep, hp):
    """``(rows, rep * hp)`` float32: each lane holding its head's column of
    ``cols (rows, rep)``, built a tile of 128 lanes at a time."""
    rows = cols.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    tiles = []
    for j in range(rep * hp // LANES):
        out = jnp.zeros((rows, LANES), _F32)
        for r, a, b in _heads_of(j, rep, hp):
            out = jnp.where((lane >= a) & (lane < b), cols[:, r:r + 1], out)
        tiles.append(out)
    return _join(tiles)


def _head_sums(v, rep, hp):
    """``(rows, rep)`` float32: each head's lanes of ``v (rows, rep *
    hp)`` summed."""
    rows = v.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (rows, rep), 1)
    out = jnp.zeros((rows, rep), _F32)
    for j in range(rep * hp // LANES):
        tile = v[:, j * LANES:(j + 1) * LANES]
        for r, a, b in _heads_of(j, rep, hp):
            out = out + jnp.where(head == r, jnp.sum(
                jnp.where((lane >= a) & (lane < b), tile, 0.0), axis=1,
                keepdims=True), 0.0)
    return out


def _join(tiles):
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _by_head(stacked, heads, q):
    """``(q, 128)``: of ``stacked (len(heads) * q, 128)``, each head's block
    of rows at the head's lanes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 1)
    out = stacked[:q]
    for i, (_, a, b) in enumerate(heads[1:], 1):
        out = jnp.where((lane >= a) & (lane < b),
                        stacked[i * q:(i + 1) * q], out)
    return out


def _stack(blocks):
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=0)


def _forward_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, cumc_ref, w_ref,
                    f_ref, keep_ref, d_ref, doc_ref, docc_ref, out_ref,
                    state, *, q, rep, hp, cd, states):
    """One chunk of one group: ``y + D x`` (or, under ``states``, the
    state the chunk takes in), then the carried state moved past the
    chunk.  The state is kept transposed, ``(N, rep * P)``, so that every
    product is a plain one."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    xf = x_ref[0]
    x = _cast(xf, cd)
    bt = _cast(b_ref[0].T, cd)
    if states:
        out_ref[0, 0, 0] = state[...]
    else:
        cm = _cast(c_ref[0], cd)
        dt, cum, cumc = dt_ref[0, 0, 0], cum_ref[0, 0, 0], cumc_ref[0, 0, 0]
        t = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        s = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
        pair = (docc_ref[0, 0] == doc_ref[0, 0]) & (s <= t)
        cb = _dot(cm, bt)
        tiles = []
        for j in range(rep * hp // LANES):
            heads = _heads_of(j, rep, hp)
            scores = [_cast(jnp.exp(jnp.where(
                pair, cumc[:, r:r + 1] - cum[r:r + 1, :], -jnp.inf)) * cb
                * dt[r:r + 1, :], cd) for r, _, _ in heads]
            tiles.append(_by_head(_dot(
                _stack(scores), x[:, j * LANES:(j + 1) * LANES]), heads, q))
        y = _join(tiles) + _dot(cm, _cast(state[...], cd)) \
            * _lanes(f_ref[0, 0, 0], rep, hp)
        out_ref[0] = y + d_ref[...] * xf
    xw = _cast(x * _lanes(w_ref[0, 0, 0], rep, hp), cd)
    state[...] = _lanes(keep_ref[0, 0, 0], rep, hp) * state[...] \
        + _dot(bt, xw)


def _backward_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, cumc_ref, w_ref,
                     f_ref, keep_ref, d_ref, doc_ref, docc_ref, dtc_ref,
                     st_ref, dy_ref, dx_ref, db_ref, dc_ref, ddt_ref,
                     dcum_ref, dcumc_ref, dw_ref, df_ref, dkeep_ref, dd_ref,
                     dstate, *, q, rep, hp, cd):
    """One chunk of one group, the chunks in reverse: the gradients of the
    chunk's inputs from ``dy`` and the carried state's cotangent, which
    moves on to the chunk before.  The intra-chunk part works on the
    scores transposed, ``(s, t)``, which it makes as the forward makes
    them, so that no product needs an operand transposed."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    xf = x_ref[0]
    x = _cast(xf, cd)
    bm, cm = _cast(b_ref[0], cd), _cast(c_ref[0], cd)
    ct = _cast(c_ref[0].T, cd)
    cbt = _dot(bm, ct)                                  # (s, t)
    cum, cumc, dtc = cum_ref[0, 0, 0], cumc_ref[0, 0, 0], dtc_ref[0, 0, 0]
    dy = dy_ref[0]
    held, ds = st_ref[0, 0, 0], dstate[...]
    # u = y + D x
    dd_ref[0, 0] = jnp.sum(dy * xf, axis=0, keepdims=True)
    dx = d_ref[...] * dy
    # S' = keep S + own
    dkeep_ref[0, 0, 0] = _head_sums(jnp.sum(ds * held, axis=0,
                                            keepdims=True), rep, hp)
    ds_in = _lanes(keep_ref[0, 0, 0], rep, hp) * ds
    # own^T = B^T (x * w)
    w = _lanes(w_ref[0, 0, 0], rep, hp)
    xw = _cast(x * w, cd)
    ds_parts = _parts(ds, cd)
    dxw = _cot_dot(ds_parts, bm, first=False)
    db = _cot_dot(ds_parts, xw, _NT, first=False)
    dx = dx + dxw * w
    dw_ref[0, 0, 0] = _head_sums(dxw * x, rep, hp)
    # the carried state's part f * (C S)
    held_cd = _cast(held, cd)
    df_ref[0, 0, 0] = _head_sums(dy * _dot(cm, held_cd), rep, hp)
    g = _parts(_lanes(f_ref[0, 0, 0], rep, hp) * dy, cd)
    dc = _cot_dot(g, held_cd, _NT)
    ds_in = ds_in + _cot_dot(g, ct, first=False)
    # inside the chunk: y = (L o C B^T o dt) x, worked on transposed
    s = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    t = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    pair = (docc_ref[0, 0] == doc_ref[0, 0]) & (s <= t)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 1)
    head_row = jax.lax.broadcasted_iota(jnp.int32, (rep, q), 0)
    head_col = jax.lax.broadcasted_iota(jnp.int32, (q, rep), 1)
    dy_parts = _parts(dy, cd)
    dcbt = jnp.zeros((q, q), _F32)
    ddt = jnp.zeros((q, rep), _F32)
    dcum = jnp.zeros((rep, q), _F32)
    dcumc = jnp.zeros((q, rep), _F32)
    tiles = []
    for j in range(rep * hp // LANES):
        heads = _heads_of(j, rep, hp)
        cols = slice(j * LANES, (j + 1) * LANES)
        decay = [jnp.exp(jnp.where(pair, cum[r:r + 1, :] - cumc[:, r:r + 1],
                                   -jnp.inf)) for r, _, _ in heads]
        scores = [_cast(dk * cbt * dtc[:, r:r + 1], cd)
                  for dk, (r, _, _) in zip(decay, heads)]
        dx_j = _by_head(_cot_dot([p[:, cols] for p in dy_parts],
                                 _stack(scores), first=False), heads, q)
        tiles.append(dx[:, cols] + dx_j)
        d_scores = _cot_dot(
            [_stack([jnp.where((lane >= a) & (lane < b), p[:, cols], 0.0)
                     .astype(p.dtype) for _, a, b in heads])
             for p in dy_parts], x[:, cols], _NT, first=False)
        for i, (r, _, _) in enumerate(heads):
            qd = d_scores[:, i * q:(i + 1) * q] * decay[i]
            p = qd * dtc[:, r:r + 1]
            dcbt = dcbt + p
            h = p * cbt
            ddt = jnp.where(head_col == r, ddt + jnp.sum(
                qd * cbt, axis=1, keepdims=True), ddt)
            dcum = jnp.where(head_row == r, dcum + jnp.sum(
                h, axis=0, keepdims=True), dcum)
            dcumc = jnp.where(head_col == r, dcumc - jnp.sum(
                h, axis=1, keepdims=True), dcumc)
    dx_ref[0] = _join(tiles)
    dcb = _parts(dcbt.T, cd)
    dc_ref[0] = dc + _cot_dot(dcb, bm)
    db_ref[0] = db + _cot_dot(_parts(dcbt, cd), cm)
    dstate[...] = ds_in
    ddt_ref[0, 0, 0] = ddt
    dcum_ref[0, 0, 0] = dcum
    dcumc_ref[0, 0, 0] = dcumc


def _block(shape, index):
    """A block spec whose ``index (row, group, chunk)`` gives the blocks
    that ``jax.numpy`` arguments name, made int32 (not a 64-bit Python 0
    under x64)."""
    def at(i, k, c):
        return tuple(jnp.int32(v) if isinstance(v, int) else v
                     for v in index(i, k, c))

    return pl.BlockSpec(shape, at)


def _specs(nc, q, n, rep, hp, inner, reverse):
    """Block specs of the kernels' inputs: ``x``, ``B`` and ``C`` as
    column blocks of the conv's output ``(B, S, inner + 2 G N)``, the
    steps and ``cum`` as rows, ``cum``'s, ``to_end * dt``'s and ``inflow
    * exp(cum)``'s columns, ``keep`` as a row, ``D`` on every lane and the
    documents as a row and as a column; the chunks in reverse under
    ``reverse``."""
    def at(c):
        return nc - 1 - c if reverse else c

    groups = inner // (rep * hp)
    row = _block((1, 1, 1, rep, q), lambda i, k, c: (i, at(c), k, 0, 0))
    col = _block((1, 1, 1, q, rep), lambda i, k, c: (i, at(c), k, 0, 0))
    return [
        _block((1, q, rep * hp), lambda i, k, c: (i, at(c), k)),
        _block((1, q, n), lambda i, k, c: (i, at(c), inner // n + k)),
        _block((1, q, n),
               lambda i, k, c: (i, at(c), inner // n + groups + k)),
        row, row, col, col, col,
        _block((1, 1, 1, 1, rep), lambda i, k, c: (i, at(c), k, 0, 0)),
        _block((1, rep * hp), lambda i, k, c: (0, k)),
        _block((1, 1, 1, q), lambda i, k, c: (i, at(c), 0, 0)),
        _block((1, 1, q, 1), lambda i, k, c: (i, at(c), 0, 0))]


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _shapes(ins):
    xbc, dt = ins[0], ins[3]
    b, s, _ = xbc.shape
    _, nc, g, _, q = dt.shape
    return b, s, nc, g, q


def _forward(ins, rep, hp, n, cd, states):
    """The forward kernel over ``ins``; ``y + D x (B, S, H * P)`` float32,
    or under ``states`` the state each chunk takes in, transposed, ``(B,
    chunks, groups, N, rep * P)`` float32."""
    b, s, nc, g, q = _shapes(ins)
    width = rep * hp
    if states:
        out_shape = jax.ShapeDtypeStruct((b, nc, g, n, width), _F32)
        out_spec = _block((1, 1, 1, n, width),
                          lambda i, k, c: (i, c, k, 0, 0))
    else:
        out_shape = jax.ShapeDtypeStruct((b, s, g * width), _F32)
        out_spec = _block((1, q, width), lambda i, k, c: (i, c, k))
    return pl.pallas_call(
        functools.partial(_forward_kernel, q=q, rep=rep, hp=hp, cd=cd,
                          states=states),
        grid=(b, g, nc),
        in_specs=_specs(nc, q, n, rep, hp, g * width, False),
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, width), _F32)],
        compiler_params=_PARAMS,
        name="ssd_scan_states" if states else "ssd_scan_forward",
    )(*ins)


def _backward(ins, dtc, held, dy, rep, hp, n, cd):
    """The backward kernel over ``ins``, the steps as columns ``dtc``, the
    states ``held`` each chunk took in and the cotangent ``dy``: the
    cotangents of ``x``, ``B``, ``C``, the steps (as columns), ``cum`` (as
    rows and as columns), the columns of ``to_end * dt`` and ``inflow *
    exp(cum)``, ``keep`` and ``D``'s lanes (a row a chunk)."""
    b, s, nc, g, q = _shapes(ins)
    width = rep * hp
    specs = _specs(nc, q, n, rep, hp, g * width, True)

    def at(c):
        return nc - 1 - c

    ab = jax.ShapeDtypeStruct
    cols = ab(dtc.shape, _F32)
    return pl.pallas_call(
        functools.partial(_backward_kernel, q=q, rep=rep, hp=hp, cd=cd),
        grid=(b, g, nc),
        in_specs=specs + [
            specs[5],
            _block((1, 1, 1, n, width), lambda i, k, c: (i, at(c), k, 0, 0)),
            _block((1, q, width), lambda i, k, c: (i, at(c), k))],
        out_specs=[
            specs[0],
            _block((1, q, n), lambda i, k, c: (i, at(c), k)),
            _block((1, q, n), lambda i, k, c: (i, at(c), k)),
            specs[5], specs[3], specs[5], specs[5], specs[5], specs[8],
            _block((1, 1, 1, width), lambda i, k, c: (i, at(c), 0, k))],
        out_shape=[ab((b, s, g * width), _F32), ab((b, s, g * n), _F32),
                   ab((b, s, g * n), _F32), cols, ab(ins[4].shape, _F32),
                   cols, cols, cols, ab(ins[8].shape, _F32),
                   ab((b, nc, 1, g * width), _F32)],
        scratch_shapes=[pltpu.VMEM((n, width), _F32)],
        compiler_params=_PARAMS,
        name="ssd_scan_backward",
    )(*ins, dtc, held, dy)


def _columns(v):
    return jnp.swapaxes(v, -1, -2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _scan(xbc, dt, cum, w, f, keep, d, doc, rep, hp, n, cd):
    return _forward(_inputs(xbc, dt, cum, w, f, keep, d, doc), rep, hp, n,
                    cd, False)


def _inputs(xbc, dt, cum, w, f, keep, d, doc):
    """The kernels' inputs: the conv's output three times (``x``, ``B``
    and ``C`` are blocks of it), ``cum`` as rows and as columns and the
    documents as a row and as a column beside the rest."""
    return (xbc, xbc, xbc, dt, cum, _columns(cum), w, f, keep, d,
            doc[:, :, None, :], doc[..., None])


def _scan_fwd(xbc, dt, cum, w, f, keep, d, doc, rep, hp, n, cd):
    ins = _inputs(xbc, dt, cum, w, f, keep, d, doc)
    return _forward(ins, rep, hp, n, cd, False), (ins, dt)


def _scan_bwd(rep, hp, n, cd, res, dy):
    ins, dt = res
    held = _forward(ins, rep, hp, n, cd, True)
    dx, db, dc, ddt, dcum, dcumc, dw, df, dkeep, dd = _backward(
        ins, _columns(dt), held, dy, rep, hp, n, cd)
    g = dt.shape[2]
    width = rep * hp
    dxbc = jnp.concatenate([dx, db, dc], axis=-1)
    return (dxbc, _columns(ddt), dcum + _columns(dcumc), dw, df, dkeep,
            dd.sum(axis=(0, 1)).reshape(1, g * width), None)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_heads(xbc, dt, a, d_skip, doc, widths, chunk, cd=None):
    """``transformer._ssd_heads``'s result, ``ssd_scan(x, dt, a, B, C) + D
    x`` of ``x``, ``B`` and ``C`` read from the conv's output ``xbc (B, S,
    H P + 2 G N)`` (float32), with ``dt (B, S, H)`` the steps after their
    softplus and ``a (H,)``; ``(B, S, H P)`` float32.  The caller has
    checked ``transformer.ssd_kernel_suits``."""
    h, hp, n, g = widths
    b, s, _ = xbc.shape
    rep = h // g
    q = int(chunk)
    pad = (-s) % q
    if pad:
        # no input and no decay past the row's end
        xbc, dt = (jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in (xbc, dt))
        doc = jnp.pad(doc, ((0, 0), (0, pad)), mode="edge")
    nc = (s + pad) // q
    # the factors as ``ssd_scan`` makes them
    dt = dt.reshape(b, nc, q, h).transpose(0, 1, 3, 2)          # (b,c,h,q)
    doc = doc.reshape(b, nc, q)
    cum = jnp.cumsum(dt * a[:, None], axis=-1)                   # (b,c,h,q)
    last = doc[..., -1:]
    came = jnp.concatenate([jnp.zeros_like(last[:, :1]), last[:, :-1]],
                           axis=1)                                # (b,c,1)
    to_end = jnp.where((doc == last)[:, :, None],
                       jnp.exp(cum[..., -1:] - cum), 0.0) * dt   # (b,c,h,q)
    inflow = jnp.where((doc == came)[:, :, None], jnp.exp(cum), 0.0)
    keep = jnp.where(last == came, jnp.exp(cum[..., -1]), 0.0)    # (b,c,h)

    def rows(v):                                              # (b,c,g,r,q)
        return v.reshape(b, nc, g, rep, -1)

    u = _scan(xbc.astype(_F32), rows(dt), rows(cum), _columns(rows(to_end)),
              _columns(rows(inflow)), _columns(rows(keep)),
              jnp.repeat(d_skip.astype(_F32), hp)[None], doc, rep, hp, n,
              cd)
    return u[:, :s]
