"""Layer kinds of a token-sequence model, each defined once.

``embedding`` (times ``scale`` where the layer names one), ``rmsnorm``,
``attention`` (causal, cut at document boundaries; rotary unless the layer
says ``rope: False``, over the whole row unless it names a ``window`` of
keys; under ``qk_norm`` every head of the queries and of the keys is
normed with a learned gain, under ``gate`` the heads' output is multiplied
by the sigmoid of a fifth projection of the layer's input), ``gated_mlp``,
``router`` (a routed layer's float32 logits over all its experts, read from
the stream where the entry stands and left for the ``moe`` entry that names
it), ``moe`` (a chip's share of a routed mixture of experts: it chooses
``top_k`` of all ``experts`` and computes the part of the result that the
experts it holds give, dropping no token at any imbalance; ``score`` says
how logits become weights, ``shared_hidden`` adds an expert that every
token passes, ``balance_rate`` a selection bias that the load moves) and
``lm_head`` (final norm and untied output product; inside a loop also the
exit gate of a looped model).  A kind is a row of :data:`KINDS`:
its parameter leaves (shape, filling, whether weight decay applies, or
None for a leaf that no gradient moves: :func:`balance`), the sample shape
it gives, and its ``jax.numpy`` forward.  The fused path reads
nothing else of a kind: ``parallel/fused.py`` builds specs, draws and
places parameters, makes optimizer state and hyperparameters and applies
updates leaf by leaf from these rows, and names each spec's device ops
``L%02d.<kind>`` (kinds are ``[a-z_]+``).

Arithmetic: products run in the compute type (``cd``, bfloat16 on the
chip) with float32 master weights cast where they are used, so that a
shared weight's gradient is summed over its applications in float32;
norms, rotary, softmaxes, the router (logits, choice and weights), the
exit gate and the loss are float32.

Memory: attention works by blocks of queries and the head by blocks of
tokens, each block under ``jax.checkpoint``, so neither the scores
(batch x heads x S x S) nor a pass's logits (tokens x vocabulary) exist
whole, forward or backward.

Which lowering runs where.  Attention is the blocked ``jax.numpy`` one
(:func:`attend`) on every platform but a TPU and for rows or heads that do
not fill the kernel's tiles of 128; where the program is lowered for a TPU
and they do (:func:`kernel_suits`), every layer, with or without a window,
grouped heads or not, is the TPU's splash-attention kernel
(:func:`attend_splash`).  Its block map follows the rows' segment ids
(:func:`follow_segments`): beside the blocks that the causal mask and the
window empty, which are known as the program is traced, it skips those
that a document boundary empties, which are data, so each row brings its
own map, made once a step (:func:`block_maps`) and shared by the layers,
a loop's passes and a recomputed forward.  The experts' grouped products
are the TPU's grouped-matmul kernel there and ``jax.lax.ragged_dot``
elsewhere, and the two row movements around them (:func:`spread`,
:func:`collect`) run on every platform over the pairs of the experts held,
a count that is data, and over no others.  The code chooses by what it
observes (the platform, the tiles, the segment ids, the experts' load); no
option does.

Serving, ``export`` and the C++ runtime do not know these kinds and refuse
them by name (:func:`refuse`).
"""

import contextlib
from dataclasses import dataclass, field

import numpy

import jax
import jax.numpy as jnp


@dataclass
class TokenSpec:
    """One leaf layer of a token-sequence kind in the fused stack."""
    type: str
    in_shape: tuple
    out_shape: tuple
    name: str = ""      # the entry's, by which a ``moe`` finds its router
    attrs: dict = field(default_factory=dict)
    hyper: dict = field(default_factory=dict)        # decayed leaves
    hyper_bias: dict = field(default_factory=dict)   # gains and the gate
    flags: dict = field(default_factory=dict)

    is_softmax = False

    @property
    def kind(self):
        return self.type


#: init of every matrix and of the gate unless the layer says otherwise
DEFAULT_STDDEV = 0.02


def _std(attrs):
    return float(attrs.get("weights_stddev", DEFAULT_STDDEV))


# -- the kinds ----------------------------------------------------------------
# leaves(attrs, in_shape) -> {name: (shape, filling, value, decays)}, in draw
# order; filling is "gaussian" (stddev ``value``) or "constant"; ``decays``
# None marks a leaf the optimizer does not know (no gradient, no state, no
# hyperparameters: the selection bias, which :func:`balance` moves).

def _embedding_leaves(a, in_shape):
    return {"w": ((int(a["vocab"]), int(a["dim"])), "gaussian", _std(a),
                  True)}


def _rmsnorm_leaves(a, in_shape):
    return {"g": ((int(in_shape[-1]),), "constant", 1.0, False)}


def _attention_leaves(a, in_shape):
    d = int(in_shape[-1])
    hd, h, kv = int(a["head_dim"]), int(a["heads"]), int(a["kv_heads"])
    s = _std(a)
    out = {"wq": ((d, h * hd), "gaussian", s, True),
           "wk": ((d, kv * hd), "gaussian", s, True),
           "wv": ((d, kv * hd), "gaussian", s, True),
           "wo": ((h * hd, d), "gaussian", s, True)}
    if a.get("qk_norm"):
        # one gain over a head's elements for the queries, one for the keys
        out.update(gq=((hd,), "constant", 1.0, False),
                   gk=((hd,), "constant", 1.0, False))
    if a.get("gate"):
        out["wgate"] = ((d, h * hd), "gaussian", s, True)
    return out


def _gated_mlp_leaves(a, in_shape):
    d, f = int(in_shape[-1]), int(a["hidden"])
    s = _std(a)
    return {"wg": ((d, f), "gaussian", s, True),
            "wu": ((d, f), "gaussian", s, True),
            "wd": ((f, d), "gaussian", s, True)}


def _router_leaves(a, in_shape):
    return {"wr": ((int(in_shape[-1]), int(a["experts"])), "gaussian",
                   _std(a), True)}


def _held(a):
    """(first, count) of the experts a ``moe`` entry holds: all of them
    unless the layer says which."""
    first, count = a.get("held") or (0, int(a["experts"]))
    return int(first), int(count)


def holds(a, expert):
    """Whether a ``moe`` entry with attributes ``a`` holds ``expert``."""
    first, count = _held(a)
    return first <= expert < first + count


def _moe_leaves(a, in_shape):
    d, f = int(in_shape[-1]), int(a["hidden"])
    first, count = _held(a)
    s = _std(a)
    # stacked over the experts held; :func:`init` draws them expert by
    # expert, so that a share holds what the uncut layer draws
    out = {"wg": ((count, d, f), "gaussian", s, True),
           "wu": ((count, d, f), "gaussian", s, True),
           "wd": ((count, f, d), "gaussian", s, True)}
    if a.get("shared_hidden"):
        # the expert every token passes, whole on every chip
        fs = int(a["shared_hidden"])
        out.update(sg=((d, fs), "gaussian", s, True),
                   su=((d, fs), "gaussian", s, True),
                   sd=((fs, d), "gaussian", s, True))
    if a.get("balance_rate") is not None:
        # the selection bias over ALL the experts (:func:`balance`)
        out["sb"] = ((int(a["experts"]),), "constant", 0.0, None)
    return out


def _lm_head_leaves(a, in_shape):
    d = int(in_shape[-1])
    s = _std(a)
    out = {"g": ((d,), "constant", 1.0, False),
           "w": ((int(a["vocab"]), d), "gaussian", s, True)}
    if a.get("exit_gate", True):
        # a looped model's: read where a loop runs the head every pass
        out.update(we=((d,), "gaussian", s, False),
                   be=((1,), "constant", 0.0, False))
    return out


def _cast(w, cd):
    return w if cd is None else w.astype(cd)


def rms(x, g, eps):
    """``g * x / sqrt(mean(x^2) + eps)`` in float32, handed back in the
    type of ``x``."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def rope_tables(seq, head_dim, base):
    """(cos, sin), each (seq, head_dim) float32, rotate-half convention."""
    inv = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                          / head_dim))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _rotary(x, cos, sin):
    """x (B, S, H, hd) -> rotated, float32 arithmetic, type of x."""
    x32 = x.astype(jnp.float32)
    half = x32.shape[-1] // 2
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[None, :, None, :]
            + rot * sin[None, :, None, :]).astype(x.dtype)


def _attend_block(q, k, v, seg_q, seg_k, q0, scale, k0=0, window=None):
    """One block of queries against the keys up to its end: q (B, bq, H,
    hd) at row positions ``q0 ...``, k and v (B, n, KV, hd) at ``k0 ...
    k0 + n - 1``.  Masked to ``j <= i``, the same document and, under a
    ``window``, ``i - j < window``; softmax in float32."""
    b, bq, h, hd = q.shape
    n, kv = k.shape[1], k.shape[2]
    rep = h // kv
    qg = q.reshape(b, bq, kv, rep, hd)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    qi = q0 + jnp.arange(bq)
    kj = jnp.arange(n) if not k0 else k0 + jnp.arange(n)
    near = kj[None, :] <= qi[:, None]
    if window is not None:
        near = near & (qi[:, None] - kj[None, :] < window)
    ok = near[None] & (seg_q[:, :, None] == seg_k[:, None, :])
    s = jnp.where(ok[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    u = jnp.einsum("bgrqk,bkgd->bqgrd", p, v)
    return u.reshape(b, bq, h * hd)


def attend(q, k, v, segments, q_block, remat, window=None):
    """Causal attention inside documents, by blocks of ``q_block`` queries
    (each block sees the keys up to its own end only, and under a
    ``window`` from ``window - 1`` before its start, so the masked upper
    triangle and what lies before the window are skipped block-wise);
    ``remat`` recomputes a block's scores in the backward pass instead of
    keeping them."""
    b, s, h, hd = q.shape
    bq = int(q_block) if q_block else s
    if s % bq:
        bq = s
    scale = 1.0 / float(numpy.sqrt(hd))
    fn = jax.checkpoint(_attend_block, static_argnums=(5, 6, 7, 8)) \
        if remat else _attend_block
    outs = []
    for q0 in range(0, s, bq):
        end = q0 + bq
        k0 = 0 if window is None else max(0, q0 - int(window) + 1)
        outs.append(fn(q[:, q0:end], k[:, k0:end], v[:, k0:end],
                       segments[:, q0:end], segments[:, k0:end], q0, scale,
                       k0, window))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


#: the TPU kernels' tiles: a row's tokens and a head's size in multiples of
#: this
KERNEL_TILE = 128

_lowered_for = []


@contextlib.contextmanager
def lowering_for(platform):
    """Names the platform a program is lowered for where that is not the
    backend that traces it: an ahead-of-time compile for a described chip
    (``tests/unit/test_tpu_compile.py``)."""
    _lowered_for.append(platform)
    try:
        yield
    finally:
        _lowered_for.pop()


def kernel_suits(seq, head_dim):
    """Whether attention runs as the TPU's kernel (:func:`attend_splash`):
    the program is lowered for a TPU (Mosaic compiles for nothing else) and
    the row and the head fill the kernel's tiles; the blocked ``jax.numpy``
    lowering (:func:`attend`) serves everything else."""
    platform = _lowered_for[-1] if _lowered_for else jax.default_backend()
    return platform == "tpu" and seq % KERNEL_TILE == 0 \
        and head_dim % KERNEL_TILE == 0


#: every block size of the splash-attention kernel's three kernels (the
#: probe on a v5e, PERF.md section 6, PR 33)
SPLASH_BLOCK = 1024


def _splash_mask(s, window, rep):
    """The mask known as the program is traced, for rows of ``s`` tokens
    and ``rep`` query heads on each key-value head: ``j <= i`` and, under
    a ``window``, ``i - j < window``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm)
    one = sm.CausalMask((s, s)) if window is None else sm.LocalMask(
        (s, s), (int(window) - 1, 0), 0)
    return sm.MultiHeadMask([one] * rep)


def _splash_kernel(s, window, rep, interpret=False):
    """The library's kernel under :func:`_splash_mask`; its three
    ``MaskInfo`` (forward, dq, dkv) hold the block map of that mask."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    n = min(SPLASH_BLOCK, s)
    return sk.make_splash_mqa_single_device(
        _splash_mask(s, window, rep),
        block_sizes=sk.BlockSizes(
            block_q=n, block_kv=n, block_kv_compute=n, block_q_dkv=n,
            block_kv_dkv=n, block_kv_dkv_compute=n, block_q_dq=n,
            block_kv_dq=n),
        interpret=interpret)


def _static_maps(s, window, rep):
    """The ``MaskInfo`` of :func:`_splash_kernel`'s forward, dq and dkv
    programs as the library works them out, numpy arrays: the very calls it
    makes as it builds the kernel (so its cache answers whichever comes
    second), where the kernel itself holds them as device arrays, which a
    program being traced could read only by waiting for the device."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask_info as mi)
    n = min(SPLASH_BLOCK, s)
    mask, shards = _splash_mask(s, window, rep), {
        "downcast_smem_data": True, "head_shards": 1, "q_seq_shards": 1}
    fwd, _ = mi.process_mask(mask, (n, n), **shards)
    dkv, _ = mi.process_mask_dkv(mask, (n, n), **shards, shrink_grid=True)
    return fwd, fwd, dkv


def follow_segments(info, segments, block, dkv=False):
    """One static ``MaskInfo`` of the library with its block map made to
    follow one row's ``segments (S,)``: ``(block_mask, data_next)``, traced,
    in the shapes and types the library gave them.  A grid step whose query
    block and key block of ``block`` tokens hold no common document
    (their segment-id ranges are disjoint: right for any ids, exact for
    non-decreasing ones) reads ``block_mask`` 0, so the kernel does not
    run it, and every step's ``data_next`` names the block of the next step
    that is run, in the order the grid is walked, so that nothing is
    fetched for a step that is not.  The block of a step is looked up in
    the library's own ``data_next``: a forward or dq step ``(h, i, j)`` is
    query block ``i`` against key block ``data_next[h, i, j]``, a ``dkv``
    step key block ``j`` against query block ``data_next[h, i, j]``, whether
    or not the library shrank the grid.  Where no step is turned off (a
    row of one document) the library's map comes back value for value."""
    static_mask = info.block_mask
    own = info.data_next.astype(numpy.int32)
    rows, cols = numpy.indices(own.shape)[1:]
    qb, kb = (own, cols) if dkv else (rows, own)
    seg = segments.reshape(-1, int(block))
    lo, hi = seg.min(axis=1), seg.max(axis=1)
    shared = (lo[qb] <= hi[kb]) & (lo[kb] <= hi[qb])
    block_mask = jnp.where(shared, static_mask, 0).astype(static_mask.dtype)
    # the grid walks (head, query step, key step), dkv's (key step, head,
    # query step): the next step that runs, the first again after the last
    walk = (lambda a: a.transpose(2, 0, 1)) if dkv else (lambda a: a)
    runs, walked = walk(block_mask > 0).reshape(-1), walk(own)
    steps = jnp.arange(runs.size, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(runs, steps, runs.size), reverse=True)
    nxt = jnp.where(nxt == runs.size, nxt[0], nxt)
    data_next = jnp.asarray(walked.reshape(-1))[nxt].reshape(walked.shape)
    if dkv:
        data_next = data_next.transpose(1, 2, 0)
    off = (static_mask > 0) & ~shared
    data_next = jnp.where(off.any(), data_next, own).astype(
        info.data_next.dtype)
    return block_mask, data_next


def block_maps(segments, window=None, rep=1):
    """``(maps, blocks)`` of rows ``segments (B, S)``.  ``maps`` is what
    :func:`attend_splash` takes: the three block maps of every row
    (:func:`follow_segments` of the kernel's forward, dq and dkv
    ``MaskInfo``), each ``(block_mask, data_next)`` with the rows in front.
    They hang on the rows and the mask alone, so one set serves every layer
    of a step under that ``window``, every pass of a loop and a ``remat``'s
    second forward.  ``blocks`` is ``[steps the rows' forward maps run,
    steps the static map runs]`` int32: how far the documents emptied it.
    The maps are the same for any ``rep`` (every head has the one mask);
    naming the layers' own reads the static maps the library has already
    worked out for them (seconds at rows of 16,384)."""
    s = segments.shape[1]
    infos = _static_maps(s, window, rep)
    seg = segments.astype(jnp.int32)
    maps = tuple(
        jax.vmap(lambda row, info=info, dkv=dkv: follow_segments(
            info, row, min(SPLASH_BLOCK, s), dkv))(seg)
        for info, dkv in zip(infos, (False, False, True)))
    static = int((infos[0].block_mask > 0).sum())
    return maps, jnp.stack([(maps[0][0] > 0).sum(dtype=jnp.int32),
                            jnp.int32(segments.shape[0] * static)])


def attend_splash(q, k, v, segments, window=None, maps=None,
                  interpret=False):
    """The same attention as :func:`attend` by the TPU's splash-attention
    kernel (``jax.experimental.pallas.ops.tpu.splash_attention``), which
    takes a local mask and grouped heads.  The mask (``j <= i`` and, under
    a ``window``, ``i - j < window``) is known as the program is traced, so
    a block that it empties is never visited, forward or backward; the
    documents are data, and the kernel reads its block map as it runs, so
    each row brings its own (``maps``, :func:`block_maps`: made here where
    none is handed in): a block that only a document boundary empties is
    not visited either and its keys are not fetched, and segment ids mask
    across documents inside the blocks that are.  A group of query heads
    reads its one key-value head where it lies (the kernel's multi-query
    form, mapped over the key-value heads), so no key is repeated.  Online
    softmax in float32; it compiles for a TPU only (``interpret`` runs it
    anywhere: ``tests/unit/test_block_maps.py`` holds it to :func:`attend`
    on the CPU)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    b, s, h, hd = q.shape
    kv = k.shape[2]
    rep = h // kv
    kernel = _splash_kernel(s, window, rep, interpret)
    infos = (kernel.fwd_mask_info, kernel.dq_mask_info, kernel.dkv_mask_info)
    if maps is None:
        maps, _ = block_maps(segments, window, rep)
    scale = 1.0 / float(numpy.sqrt(hd))
    # (B, KV, rep, S, hd) queries against (B, KV, S, hd) keys and values
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(b, s, kv, rep, hd) \
        .transpose(0, 2, 3, 1, 4)
    seg = segments.astype(jnp.int32)

    kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)

    def row(i):
        # the static kernel with this row's maps.  A call a row, not a
        # ``vmap`` over rows: the maps are scalar-prefetch operands, which
        # ``pallas_call`` batches by a loop of slices and updates that reads
        # 9-19 % slower than the calls laid out (PERF.md section 6, PR 34)
        mine = sk.SplashAttentionKernel(
            *(info._replace(block_mask=block_mask[i], data_next=data_next[i])
              for info, (block_mask, data_next) in zip(infos, maps)),
            **kernel.kwargs)
        ids = sk.SegmentIds(q=seg[i], kv=seg[i])
        return jax.vmap(lambda q1, k1, v1: mine(
            q1, k1, v1, segment_ids=ids))(qg[i], kt[i], vt[i])

    out = jnp.stack([row(i) for i in range(b)])
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h * hd)


def _embedding_apply(spec, p, ids, ctx):
    rows = jnp.take(p["w"], ids, axis=0)
    if spec.attrs.get("scale"):
        # in the master weights' type, before the cast
        rows = rows * float(spec.attrs["scale"])
    return _cast(rows, ctx["cd"])


def _rmsnorm_apply(spec, p, y, ctx):
    return rms(y, p["g"], float(spec.attrs.get("eps", 1e-6)))


def _attention_apply(spec, p, y, ctx):
    a, cd = spec.attrs, ctx["cd"]
    b, s, _ = y.shape
    hd, h, kv = int(a["head_dim"]), int(a["heads"]), int(a["kv_heads"])
    window = a.get("window")
    rot = lambda x: x   # noqa: E731
    if a.get("rope", True):
        cos, sin = ctx["rope"][(hd, float(a.get("rope_base", 10000.0)))]
        rot = lambda x: _rotary(x, cos, sin)    # noqa: E731
    normed = lambda x, g: x     # noqa: E731
    if a.get("qk_norm"):
        # every head over its own elements, before it is turned
        normed = lambda x, g: rms(  # noqa: E731
            x, p[g], float(a.get("eps", 1e-6)))
    q = rot(normed((y @ _cast(p["wq"], cd)).reshape(b, s, h, hd), "gq"))
    k = rot(normed((y @ _cast(p["wk"], cd)).reshape(b, s, kv, hd), "gk"))
    v = (y @ _cast(p["wv"], cd)).reshape(b, s, kv, hd)
    if not kernel_suits(s, hd):
        u = attend(q, k, v, ctx["segments"], a.get("q_block"), ctx["train"],
                   window)
    else:
        maps, blocks = ctx["block_maps"][window]
        u = attend_splash(q, k, v, ctx["segments"], window, maps)
        ctx["routed"][ctx["node"]] = {"blocks": blocks}
    if a.get("gate"):
        # the sigmoid in float32, of a product in the compute type
        gate = jax.nn.sigmoid((y @ _cast(p["wgate"], cd))
                              .astype(jnp.float32))
        u = (u.astype(jnp.float32) * gate).astype(u.dtype)
    return u @ _cast(p["wo"], cd)


def _gated_mlp_apply(spec, p, y, ctx):
    cd = ctx["cd"]
    gate = jax.nn.silu(y @ _cast(p["wg"], cd))
    return (gate * (y @ _cast(p["wu"], cd))) @ _cast(p["wd"], cd)


def _router_apply(spec, p, y, ctx):
    """Passes its input on and leaves its float32 logits over all the
    experts, ``(tokens, experts)``, under the entry's name in
    ``ctx["side"]``: the ``moe`` entry that names it reads them there,
    however many entries lie between."""
    x32 = y.reshape(-1, y.shape[-1]).astype(jnp.float32)
    ctx["side"][spec.name] = jnp.matmul(
        x32, p["wr"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    return y


#: rows a turn of the expert layer's two row movements fetches, at most
#: (the probe on a v5e, PERF.md section 6, PR 36)
MOVE_ROWS = 1024


def _spread_rows(x, order, k, held, weights=None, dot=None, dtype=None):
    """Tokens to sorted pairs, over the pairs held and no others: row ``i``
    of the result is ``x[order[i] // k]`` for ``i < held`` and zero beyond;
    with ``weights (tokens, k)`` it is that times the pair's own weight,
    and with ``dot (pairs, width)`` the second result is the row products
    ``<dot[i], x[order[i] // k]>`` in float32 (zero beyond ``held``); the
    third is the rows it fetched.
    A loop of ``ceil(held / MOVE_ROWS)`` turns, each a gather of
    ``MOVE_ROWS`` rows; ``held`` is data."""
    pairs, width = order.shape[0], x.shape[1]
    c = min(MOVE_ROWS, pairs)
    flat = None if weights is None else weights.reshape(-1)
    zero = jnp.zeros((), jnp.int32)

    def turn(i, carry):
        xs, dots = carry
        # (the last turn of a buffer that MOVE_ROWS does not divide writes
        # some rows again, the same)
        at = jnp.minimum(i * c, pairs - c).astype(jnp.int32)
        live = at + jnp.arange(c) < held
        pair = jax.lax.dynamic_slice(order, (at,), (c,))
        rows = jnp.take(x, pair // k, axis=0, mode="clip")
        if dots is not None:
            mine = jax.lax.dynamic_slice(dot, (at, zero), (c, width))
            part = (mine.astype(jnp.float32)
                    * rows.astype(jnp.float32)).sum(axis=1)
            dots = jax.lax.dynamic_update_slice(
                dots, jnp.where(live, part, 0.0), (at,))
        if flat is not None:
            rows = rows * jnp.take(flat, pair, mode="clip")[:, None]
        rows = jnp.where(live[:, None], rows, 0).astype(xs.dtype)
        return jax.lax.dynamic_update_slice(xs, rows, (at, zero)), dots

    turns = (held + c - 1) // c
    # zero, of the data: a constant's fill the compiler makes anew outside
    # every scope, and the device's time under ``moe_dispatch`` loses it
    nought = jnp.minimum(held, 0)
    xs, dots = jax.lax.fori_loop(
        0, turns, turn,
        (jnp.full((pairs, width), nought, dtype or x.dtype),
         None if dot is None else jnp.zeros((pairs,), jnp.float32)))
    return xs, dots, turns * c


def _collect_rows(rows, back, held, weights):
    """Sorted pairs to tokens, over the pairs held and no others:
    ``out[t] = sum over j with back[t, j] < held of weights[t, j] *
    float32(rows[back[t, j]])``, float32, and the rows it fetched.

    Gathers alone, no scatter: every token's held pairs go first among its
    ``k`` (in their own order, so the sum's order is the slots'), the tokens
    with the most held pairs go first, so that those with more than ``r``
    are a prefix for every ``r``; a turn takes ``MOVE_ROWS`` tokens of
    that order and adds their ``r``-th rows for ``r`` up to its first
    token's count; one last gather puts the sums back in token order."""
    n, k = back.shape
    width = rows.shape[1]
    c = min(MOVE_ROWS, n)
    zero = jnp.zeros((), jnp.int32)
    mine = (back < held).T                                  # (k, n)
    count = mine.sum(axis=0, dtype=jnp.int32)
    rank = jnp.cumsum(mine, axis=0, dtype=jnp.int32) - 1
    # (r, j, n): the token's j-th pair is its r-th held one
    nth = mine[None] & (rank[None] == jnp.arange(k)[:, None, None])
    at = jnp.where(nth, back.T[None], 0).sum(axis=1)
    w = jnp.where(nth, weights.T[None].astype(jnp.float32), 0.0).sum(axis=1)
    tokens = jnp.arange(n, dtype=jnp.int32)
    key, by_count, at, w = jax.lax.sort(
        (jnp.broadcast_to(-count, (k, n)), jnp.broadcast_to(tokens, (k, n)),
         at, w), dimension=1, is_stable=True, num_keys=1)
    count, by_count = -key[0], by_count[0]

    def turn(i, carry):
        acc, fetched = carry
        at0 = jnp.minimum(i * c, n - c).astype(jnp.int32)
        counts = jax.lax.dynamic_slice(count, (at0,), (c,))

        def nth_rows(r):
            r = jnp.asarray(r, jnp.int32)
            got = jnp.take(
                rows, jax.lax.dynamic_slice(at, (r, at0), (1, c))[0],
                axis=0, mode="clip").astype(jnp.float32)
            scale = jax.lax.dynamic_slice(w, (r, at0), (1, c))[0]
            return jnp.where((r < counts)[:, None], got * scale[:, None],
                             0.0)

        # (a turn's first token holds a pair: the turns end with the
        # tokens that do)
        part = jax.lax.fori_loop(
            1, counts[0], lambda r, part: part + nth_rows(r), nth_rows(0))
        return (jax.lax.dynamic_update_slice(acc, part, (at0, zero)),
                fetched + c * counts[0])

    served = (count > 0).sum(dtype=jnp.int32)
    # (zero of the data, as in :func:`_spread_rows`)
    nought = jnp.minimum(held, 0)
    acc, fetched = jax.lax.fori_loop(
        0, (served + c - 1) // c, turn,
        (jnp.full((n, width), nought, jnp.float32), zero))
    out = jnp.take(acc, jnp.argsort(by_count), axis=0, mode="clip")
    return out, fetched + n


@jax.custom_vjp
def spread(x, order, back, held):
    """The dispatch: ``x[order // k]`` over the sorted positions below
    ``held``, zero beyond, and the rows fetched (:func:`_spread_rows`);
    its transpose is :func:`_collect_rows` under weights of one."""
    return _spread_fwd(x, order, back, held)[0]


def _spread_fwd(x, order, back, held):
    xs, _, fetched = _spread_rows(x, order, back.shape[1], held)
    return (xs, fetched), (order, back, held)


def _spread_bwd(res, g):
    order, back, held = res
    dx, _ = _collect_rows(g[0], back, held,
                          jnp.ones(back.shape, jnp.float32))
    return dx.astype(g[0].dtype), None, None, None


spread.defvjp(_spread_fwd, _spread_bwd)


@jax.custom_vjp
def collect(rows, weights, order, back, held):
    """The combine: every token's weighted float32 sum of its held pairs'
    rows and the rows fetched (:func:`_collect_rows`); its transpose is
    :func:`_spread_rows` under the pairs' weights, with the row products
    for the weights' own gradient."""
    return _collect_fwd(rows, weights, order, back, held)[0]


def _collect_fwd(rows, weights, order, back, held):
    return _collect_rows(rows, back, held, weights), \
        (rows, weights, order, back, held)


def _collect_bwd(res, g):
    rows, weights, order, back, held = res
    d_rows, dots, _ = _spread_rows(g[0], order, back.shape[1], held,
                                   weights, rows, rows.dtype)
    # the products (zero beyond ``held``) back in the pairs' own order: a
    # sort by the pair, a quarter of what a gather of as many scalars takes
    # on the chip
    _, d_weights = jax.lax.sort((order, dots), num_keys=1)
    return d_rows, d_weights.reshape(weights.shape).astype(weights.dtype), \
        None, None, None


collect.defvjp(_collect_fwd, _collect_bwd)

#: the grouped-matmul kernel's tiles over (pairs, contraction, output), at
#: most (the probe on a v5e, PERF.md section 6, PR 33)
GMM_TILES = (512, 1280, 1280)


def _tile(n, most):
    """The largest multiple of the kernel's 128 up to ``most`` that divides
    ``n``; ``n`` where there is none."""
    for t in range(min(most, n) // KERNEL_TILE * KERNEL_TILE, 0,
                   -KERNEL_TILE):
        if n % t == 0:
            return t
    return n


def grouped_dot(x, w, sizes):
    """``x[rows of group g] @ w[g]`` for the groups ``w`` holds: ``x
    (pairs, a)`` sorted by group, ``w (held, a, b)``, ``sizes`` the rows of
    EVERY group in that order (the held ones first); rows of a group that
    ``w`` does not hold come out zero and cost nothing.  Where the program
    is lowered for a TPU this is the grouped-matmul kernel
    (``jax.experimental.pallas.ops.tpu.megablox``: it visits the tiles of
    the rows held and no others, so its time follows the pairs held, not
    the buffer and not a capacity), elsewhere ``jax.lax.ragged_dot``."""
    platform = _lowered_for[-1] if _lowered_for else jax.default_backend()
    if platform == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import ops as mb
        tiles = tuple(_tile(n, t) for n, t in zip(
            (x.shape[0], x.shape[1], w.shape[2]), GMM_TILES))
        return mb.gmm(x, w, sizes, x.dtype, tiles)
    return jax.lax.ragged_dot(x, w, sizes[:w.shape[0]])


def route(logits, top_k, score="softmax", bias=None, scale=1.0):
    """(chosen experts (tokens, k) int32, their weights float32), all in
    float32.  A token's scores are its logits (``score`` "softmax") or
    their sigmoid ("sigmoid"); it goes to the ``top_k`` largest of score
    plus ``bias`` (the selection bias, (experts,); ties to the lower
    index).  The weights are of the scores alone, never of the bias: the
    softmax over the chosen logits, or the chosen sigmoids divided by
    their sum; times ``scale``."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError("score %r: softmax or sigmoid" % (score,))
    scores = jax.nn.sigmoid(logits) if score == "sigmoid" else logits
    if bias is None:
        vals, chosen = jax.lax.top_k(scores, int(top_k))
    else:
        chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(
            bias.astype(scores.dtype))[None, :], int(top_k))[1]
        vals = jnp.take_along_axis(scores, chosen, axis=1)
    if score == "softmax":
        weights = jax.nn.softmax(vals, axis=-1)
    else:
        weights = vals / (vals.sum(axis=-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        weights = weights * float(scale)
    return chosen.astype(jnp.int32), weights


def balance(bias, load, rate):
    """The selection bias after a step that sent ``load[e]`` tokens to
    expert ``e``: every expert moves by ``rate`` toward the mean load (up
    where it took fewer, down where more, not at all at the mean), and the
    moves are centred so that the bias keeps its sum.  No gradient, no
    decay, no moment."""
    load = load.astype(jnp.float32)
    delta = float(rate) * jnp.sign(load.mean() - load)
    return bias + (delta - delta.mean()).astype(bias.dtype)


def _moe_apply(spec, p, y, ctx):
    """A chip's share of a routed mixture of experts: ``sum over the chosen
    experts held here of weight * expert(y)``, the weights normalised over
    all ``top_k`` chosen of all ``experts``.  Pairs (token, expert) are
    sorted by expert with the held ones first, the three products run
    grouped over the pairs held (:func:`grouped_dot`), and every token
    takes back the weighted sum of its pairs in float32; no pair of a held
    expert is dropped at any imbalance.  Where the layer has a shared
    expert every token passes it, held experts or none, and its output
    joins the sum in float32.  Leaves ``load`` (experts,), the
    count of tokens none of whose experts is held here (``unserved``),
    ``route`` (tokens, k) and, of a layer with a selection bias, ``weight``
    (experts,) float32 in ``ctx["routed"]``."""
    a, cd = spec.attrs, ctx["cd"]
    n_exp, k = int(a["experts"]), int(a["top_k"])
    first, count = _held(a)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[
        a.get("activation", "silu")]
    shape = y.shape
    x = y.reshape(-1, shape[-1])
    n = x.shape[0]
    name = "L%02d.moe" % ctx["node"]
    with jax.named_scope(name + "_dispatch"):
        chosen, weights = route(
            ctx["side"][a["router"]], k, a.get("score", "softmax"),
            p.get("sb"), float(a.get("route_scale", 1.0)))
        flat = chosen.reshape(-1)
        member = flat[:, None] == jnp.arange(n_exp)[None, :]
        load = member.sum(axis=0, dtype=jnp.int32)
        # held experts first, in their own order: the pairs of the others
        # follow and are never read
        order = jnp.argsort((flat - first) % n_exp, stable=True)
        back = jnp.argsort(order).reshape(n, k)
        sizes = jnp.roll(load, -first)
        # the sorted positions below ``held`` are the pairs of the experts
        # held here: both row movements run over those and no others
        held = sizes[:count].sum(dtype=jnp.int32)
        xs, spread_rows = spread(x, order, back, held)
    with jax.named_scope(name + "_experts"):
        hidden = act(grouped_dot(xs, _cast(p["wg"], cd), sizes)) \
            * grouped_dot(xs, _cast(p["wu"], cd), sizes)
        ys = grouped_dot(hidden, _cast(p["wd"], cd), sizes)
    with jax.named_scope(name + "_combine"):
        mine = (chosen >= first) & (chosen < first + count)
        out, collect_rows = collect(ys, weights, order, back, held)
    if "sg" in p:
        with jax.named_scope(name + "_shared"):
            out = out + ((act(x @ _cast(p["sg"], cd))
                          * (x @ _cast(p["su"], cd)))
                         @ _cast(p["sd"], cd)).astype(jnp.float32)
    # the choice in the smallest type that names every expert
    report = {
        "load": load,
        "route": chosen.astype(jnp.int8 if n_exp <= 128 else jnp.int16),
        "unserved": (~mine.any(axis=1)).sum(dtype=jnp.int32),
        # the rows the two movements fetched, and what movements over all
        # the pairs would have
        "rows": jnp.stack([spread_rows + collect_rows, 2 * n * k]).astype(
            jnp.int32)}
    if "sb" in p:
        # the routing weight every expert took, held here or not: what
        # tells a bias that leaked into the weights from one that did not
        report["weight"] = jax.lax.stop_gradient(
            member * weights.reshape(-1)[:, None]).sum(axis=0)
    ctx["routed"][ctx["node"]] = report
    return out.astype(y.dtype).reshape(shape)


def head_logits(h, w, cd):
    """``h W^T`` in the compute type, accumulated and kept in float32."""
    return jnp.einsum("nd,vd->nv", _cast(h, cd), _cast(w, cd),
                      preferred_element_type=jnp.float32)


def _head_block(h, labels, w, we, be, cd):
    """One block of tokens: per-token cross-entropy of the pass's logits
    against the label (0 where none is graded), the logits' argmax and the
    exit gate's pre-activation (None of a head that has no gate)."""
    z = head_logits(h, w, cd)
    lse = jax.nn.logsumexp(z, axis=-1)
    lbl = jnp.maximum(labels, 0)
    ce = lse - jnp.take_along_axis(z, lbl[:, None], axis=1)[:, 0]
    ce = jnp.where(labels >= 0, ce, 0.0)
    pred = jnp.argmax(z, axis=-1).astype(jnp.int32)
    if we is None:
        return ce, pred, None
    gate = h.astype(jnp.float32) @ we.astype(jnp.float32) \
        + be.astype(jnp.float32)[0]
    return ce, pred, gate


def _lm_head_apply(spec, p, y, ctx):
    """Final norm; the normed state goes on down the chain (a looped
    model's next pass starts from it) and the head's per-token numbers go
    to ``ctx["emit"]``: ``ce``, ``pred`` and, of a gated head, ``gate``
    (N,), and the normed state at ``ctx["sample"]`` where positions are
    asked for."""
    a, cd = spec.attrs, ctx["cd"]
    h = rms(y, p["g"], float(a.get("eps", 1e-6)))
    b, s, d = h.shape
    n = b * s
    flat = h.reshape(n, d)
    labels = ctx["labels"].reshape(n)
    tb = int(a.get("token_block") or n)
    if n % tb:
        tb = n
    block = _head_block
    if ctx["train"]:
        block = jax.checkpoint(_head_block, static_argnums=(5,))
    we, be = p.get("we"), p.get("be")
    if tb == n:
        ce, pred, gate = block(flat, labels, p["w"], we, be, cd)
    else:
        ce, pred, gate = jax.lax.map(
            lambda blk: block(blk[0], blk[1], p["w"], we, be, cd),
            (flat.reshape(n // tb, tb, d), labels.reshape(n // tb, tb)))
        ce, pred, gate = jax.tree.map(lambda part: part.reshape(n),
                                      (ce, pred, gate))
    emit = {"ce": ce, "pred": pred}
    if gate is not None:
        emit["gate"] = gate
    if ctx.get("sample") is not None:
        emit["hidden"] = jnp.take(flat, ctx["sample"], axis=0)
    if ctx["emit"]:
        raise ValueError("one lm_head a chain (inside a loop it reads "
                         "every pass)")
    ctx["emit"].update(emit)
    return h


def _seq_dim(in_shape, a):
    return (int(in_shape[0]), int(a["dim"]))


#: kind -> (leaves, apply, output sample shape)
KINDS = {
    "embedding": (_embedding_leaves, _embedding_apply, _seq_dim),
    "rmsnorm": (_rmsnorm_leaves, _rmsnorm_apply, lambda s, a: tuple(s)),
    "attention": (_attention_leaves, _attention_apply,
                  lambda s, a: tuple(s)),
    "gated_mlp": (_gated_mlp_leaves, _gated_mlp_apply,
                  lambda s, a: tuple(s)),
    "router": (_router_leaves, _router_apply, lambda s, a: tuple(s)),
    "moe": (_moe_leaves, _moe_apply, lambda s, a: tuple(s)),
    "lm_head": (_lm_head_leaves, _lm_head_apply, lambda s, a: tuple(s)),
}


def refuse(tpe, who):
    """The one error of everything that does not run these kinds."""
    raise ValueError(
        "%s does not support layer type %r: the token-sequence kinds (%s) "
        "and the residual / loop entries train through the fused path "
        "only (serving them is ROADMAP R6)"
        % (who, tpe, ", ".join(sorted(KINDS))))


STRUCTURAL = ("residual", "loop")


def build(tpe, fwd, in_shape, hyper, hyper_bias, flags, name=""):
    """The spec of one layer of kind ``tpe`` over ``in_shape`` samples."""
    attrs = dict(fwd)
    out_shape = KINDS[tpe][2](in_shape, attrs)
    if tpe != "embedding" and len(in_shape) != 2:
        raise ValueError("%s needs (seq, dim) samples, have %r"
                         % (tpe, tuple(in_shape)))
    return TokenSpec(type=tpe, in_shape=tuple(in_shape),
                     out_shape=tuple(out_shape), name=name, attrs=attrs,
                     hyper=hyper,
                     hyper_bias=hyper_bias, flags=flags)


def leaves(spec):
    return KINDS[spec.type][0](spec.attrs, spec.in_shape)


def init(spec, rand, dtype, fill):
    """The spec's parameters on the host, drawn leaf by leaf in the order
    :func:`leaves` lists them (``fill`` is the fused path's own filler)."""
    out = {}
    if spec.type == "moe":
        # one draw of the layer's stream names the streams of its experts,
        # each drawn whole from its own: a share draws for the experts it
        # holds what the uncut layer draws for them, and nothing for the
        # rest; the shared expert's stream is named by the count of ALL
        # the experts, which no expert's is, so every share draws the same
        base = int(rand.randint(0, 2 ** 31 - 1, size=1)[0])
        first, count = _held(spec.attrs)
        table = leaves(spec)
        out = {name: numpy.full(shape, 0.0 if filling == "gaussian"
                                else value, dtype=dtype)
               for name, (shape, filling, value, _) in table.items()}
        for j in range(count):
            own = numpy.random.RandomState([base, first + j])
            for name in ("wg", "wu", "wd"):
                out[name][j] = own.normal(0, table[name][2],
                                          out[name].shape[1:])
        own = numpy.random.RandomState([base, int(spec.attrs["experts"])])
        for name in ("sg", "su", "sd"):
            if name in out:
                out[name][...] = own.normal(0, table[name][2],
                                            out[name].shape)
        return out
    for name, (shape, filling, value, _) in leaves(spec).items():
        arr = numpy.zeros(shape, dtype=dtype)
        fill(rand, filling, arr, value)
        out[name] = arr
    return out


def leaf_hypers(spec, hyper=None, hyper_bias=None):
    """{leaf: hyperparameters}: decayed leaves take the layer's weight
    hyperparameters, gains and the gate its bias ones; a leaf that no
    gradient moves has none."""
    hyper = spec.hyper if hyper is None else hyper
    hyper_bias = spec.hyper_bias if hyper_bias is None else hyper_bias
    return {name: dict(hyper if decays else hyper_bias)
            for name, (_, _, _, decays) in leaves(spec).items()
            if decays is not None}


def apply(spec, p, y, ctx):
    return KINDS[spec.type][1](spec, p, y, ctx)


# -- the objective of a looped model ------------------------------------------

def exit_log_probs(gate):
    """log of the exit distribution from the gates' pre-activations
    ``gate (T, N)``: ``p_1 = l_1``, ``p_t = l_t prod_{j<t}(1 - l_j)``,
    ``p_T = prod_{j<T}(1 - l_j)`` with ``l = sigmoid(gate)``."""
    t = gate.shape[0]
    log_l = jax.nn.log_sigmoid(gate)
    log_n = jax.nn.log_sigmoid(-gate)
    stay = jnp.concatenate(
        [jnp.zeros_like(log_n[:1]), jnp.cumsum(log_n, axis=0)[:-1]], axis=0)
    last = jnp.arange(t)[:, None] == t - 1
    return stay + jnp.where(last, 0.0, log_l)


def token_loss(emit, labels, beta):
    """(mean loss over graded tokens, [errors, graded], loss sum, exit
    distribution (T, N) or None) from a chain's head outputs, stacked over
    passes where a loop ran the head more than once.  A head with no gate
    gives the plain cross-entropy of its one pass."""
    ce, pred = emit["ce"], emit["pred"]
    if ce.ndim == 1:
        ce, pred = ce[None], pred[None]
    if "gate" in emit:
        gate = emit["gate"]
        logp = exit_log_probs(gate[None] if gate.ndim == 1 else gate)
        p = jnp.exp(logp)
        per_tok = (p * ce).sum(axis=0) + beta * (p * logp).sum(axis=0)
    else:
        p, per_tok = None, ce[-1]
    lbl = labels.reshape(-1)
    valid = lbl >= 0
    graded = valid.sum()
    loss_sum = jnp.where(valid, per_tok, 0.0).sum()
    errors = (valid & (pred[-1] != lbl)).sum()
    loss = loss_sum / jnp.maximum(graded, 1)
    return loss, jnp.stack([errors, graded]).astype(jnp.int32), loss_sum, p
