"""Layer kinds of a token-sequence model, each defined once.

``embedding`` (times ``scale`` where the layer names one), ``rmsnorm``,
``attention`` (causal, cut at document boundaries; rotary unless the layer
says ``rope: False``, over the whole row unless it names a ``window`` of
keys; under ``qk_norm`` every head of the queries and of the keys is
normed with a learned gain, under ``gate`` the heads' output is multiplied
by the sigmoid of a fifth projection of the layer's input),
``latent_attention`` (the same attention over heads whose queries and keys
are read back from two narrow projections, each normed, with rotary on a
``rope_dim`` part of every head alone and that part of the keys one for all
heads), ``gated_mlp``, ``router`` (a routed layer's float32 logits over all
its experts, read from the stream where the entry stands and left for the
``moe`` entry that names it), ``moe`` (a chip's share of a routed mixture of
experts: it chooses ``top_k`` of all ``experts`` and computes the part of
the result that the experts it holds give, dropping no token at any
imbalance; ``score`` says how logits become weights, ``shared_hidden`` adds
an expert that every token passes, ``balance_rate`` a selection bias that
the load moves, ``gated: False`` experts of two products), ``ssd``
(Mamba-2's state-space mixer: a depthwise causal conv, the scan in its
chunked form and a gated group norm, none of them across a document start),
``lm_head`` (final norm and untied output product; inside a loop also the
exit gate of a looped model; graded at a ``shift`` of the
labels, with the output weight of another head where it names one under
``tied``) and ``mtp_input`` (a multi-token prediction module's way in: the
normed state the main head passes on beside the normed embedding of every
position's next id, joined and projected back to the model's width).  A
kind is a row of :data:`KINDS`:
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
grouped heads or not, latent or not, is the TPU's splash-attention kernel
(:func:`attend_splash`).  Its block map follows the rows' segment ids
(:func:`follow_segments`): beside the blocks that the causal mask and the
window empty, which are known as the program is traced, it skips those
that a document boundary empties, which are data, so each row brings its
own map, made once a step (:func:`block_maps`) and shared by the layers,
a loop's passes and a recomputed forward.  The experts' grouped products
are the TPU's grouped-matmul kernel there and ``jax.lax.ragged_dot``
elsewhere, and the two row movements around them (:func:`spread`,
:func:`collect`) run on every platform over the pairs of the experts held,
a count that is data, and over no others.  An ``ssd`` entry's chunked
scan is the ``jax.numpy`` form of :func:`ssd_scan` but where the program is
lowered for a TPU and its chunk, state and groups of heads fill the tiles
of 128 (:func:`ssd_kernel_suits`): there it is a pair of Pallas kernels
(``ops/pallas_ssd.py``) that keep every chunk's decays, scores and the
carried state in VMEM; its conv is :func:`causal_conv` but where the
program is lowered for a TPU and its channels and the row fill the tiles
(:func:`conv_kernel_suits`): there it is a pair of Pallas kernels
(``ops/pallas_conv.py``) that read the input projection's columns and
recompute the conv in the backward in VMEM.  The code chooses by what it
observes (the platform, the tiles, the segment ids, the experts' load); no
option does.

An entry that names another under ``tied`` reads that entry's weight
(the embedding's table, a head's output weight) where it stands, as
``ctx["tied"]`` hands it down the chain's top level: one leaf, which both
applications' gradients reach, and which the optimizer steps once.

Serving, ``export`` and the C++ runtime do not know these kinds and refuse
them by name (:func:`refuse`).
"""

import contextlib
import functools
from dataclasses import dataclass, field

import numpy

import jax
import jax.numpy as jnp

from znicz_tpu.core import prng


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
# order; filling is "gaussian" (stddev ``value``), "constant", "uniform"
# (over +-``value``) or one of an ``ssd`` entry's own (:func:`_draw`);
# ``decays`` None marks a leaf the optimizer does not know (no gradient, no
# state, no hyperparameters: the selection bias, which :func:`balance`
# moves).

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


def _latent_widths(a):
    """(heads, q_rank, kv_rank, nope_dim, rope_dim, v_dim) of a
    ``latent_attention`` entry; refuses a value width other than the
    query's and key's (the kernel's output is laid out at that width)."""
    out = tuple(int(a[k]) for k in ("heads", "q_rank", "kv_rank",
                                    "nope_dim", "rope_dim", "v_dim"))
    if out[5] != out[3] + out[4]:
        raise ValueError(
            "latent_attention: v_dim %d != nope_dim %d + rope_dim %d (the "
            "heads' output is laid out at the queries' width)"
            % (out[5], out[3], out[4]))
    return out


def _latent_attention_leaves(a, in_shape):
    d = int(in_shape[-1])
    h, qr, kvr, nope, rope, vd = _latent_widths(a)
    s = _std(a)
    return {"wqa": ((d, qr), "gaussian", s, True),
            "gqa": ((qr,), "constant", 1.0, False),
            "wqb": ((qr, h * (nope + rope)), "gaussian", s, True),
            # the latent and the one rotary key of every head
            "wkva": ((d, kvr + rope), "gaussian", s, True),
            "gkv": ((kvr,), "constant", 1.0, False),
            "wkvb": ((kvr, h * (nope + vd)), "gaussian", s, True),
            "wo": ((h * vd, d), "gaussian", s, True)}


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
    gated = a.get("gated", True)
    # stacked over the experts held; :func:`init` draws them expert by
    # expert, so that a share holds what the uncut layer draws
    out = {"wg": ((count, d, f), "gaussian", s, True)} if gated else {}
    out.update(wu=((count, d, f), "gaussian", s, True),
               wd=((count, f, d), "gaussian", s, True))
    if a.get("shared_hidden"):
        # the expert every token passes, whole on every chip
        fs = int(a["shared_hidden"])
        if gated:
            out["sg"] = ((d, fs), "gaussian", s, True)
        out.update(su=((d, fs), "gaussian", s, True),
                   sd=((fs, d), "gaussian", s, True))
    if a.get("balance_rate") is not None:
        # the selection bias over ALL the experts (:func:`balance`)
        out["sb"] = ((int(a["experts"]),), "constant", 0.0, None)
    return out


def _lm_head_leaves(a, in_shape):
    d = int(in_shape[-1])
    s = _std(a)
    out = {"g": ((d,), "constant", 1.0, False)}
    if not a.get("tied"):
        # a head that names another reads that head's output weight
        out["w"] = ((int(a["vocab"]), d), "gaussian", s, True)
    if a.get("exit_gate", True):
        # a looped model's: read where a loop runs the head every pass
        out.update(we=((d,), "gaussian", s, False),
                   be=((1,), "constant", 0.0, False))
    return out


def _mtp_input_leaves(a, in_shape):
    d = int(in_shape[-1])
    return {"ge": ((d,), "constant", 1.0, False),
            "gh": ((d,), "constant", 1.0, False),
            "w": ((2 * d, d), "gaussian", _std(a), True)}


def _ssd_widths(a):
    """(heads, head_dim, state, groups, conv, chunk) of an ``ssd`` entry;
    refuses heads that the groups do not divide."""
    out = tuple(int(a[k]) for k in ("heads", "head_dim", "state", "groups",
                                    "conv", "chunk"))
    if out[0] % out[3]:
        raise ValueError("ssd: %d heads over %d groups" % (out[0], out[3]))
    return out


def conv_columns(a):
    """``(first, width, conv)`` of an ``ssd`` entry's conv: the column of
    the input projection where its channels start, their number and the
    conv's width."""
    h, hd, n, g, k, _ = _ssd_widths(a)
    return h * hd, h * hd + 2 * g * n, k


def _ssd_leaves(a, in_shape):
    d = int(in_shape[-1])
    h, hd, n, g, k, _ = _ssd_widths(a)
    inner, conv = h * hd, h * hd + 2 * g * n
    s = _std(a)
    # Mamba-2's own initialisation: a depthwise conv as PyTorch draws one
    # (uniform over 1/sqrt of its width), A = -exp(a_log) with a_log the
    # log of U(1, 16), dt_bias the inverse softplus of a step drawn
    # log-uniform between dt_min and dt_max and held above dt_floor
    bound = 1.0 / float(numpy.sqrt(k))
    steps = tuple(float(a.get(key, default)) for key, default in (
        ("dt_min", 0.001), ("dt_max", 0.1), ("dt_floor", 1e-4)))
    return {"w_in": ((d, inner + conv + h), "gaussian", s, True),
            "w_conv": ((k, conv), "uniform", bound, True),
            "b_conv": ((conv,), "uniform", bound, False),
            "dt_bias": ((h,), "dt_bias", steps, False),
            "a_log": ((h,), "log_uniform", (1.0, 16.0), False),
            "d_skip": ((h,), "constant", 1.0, False),
            "g_norm": ((inner,), "constant", 1.0, False),
            "w_out": ((inner, d), "gaussian",
                      float(a.get("out_stddev", s)), True)}


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


def ssd_kernel_suits(heads, head_dim, state, groups, chunk):
    """Whether an ``ssd`` entry's scan runs as the TPU's kernels
    (``ops/pallas_ssd.py``): the program is lowered for a TPU and the
    chunk, the state and a group's heads (``heads / groups`` of
    ``head_dim``, each within one tile of 128 lanes or whole tiles of its
    own) fill the kernels' tiles, and the heads' lanes are a multiple of
    the state (the kernels read ``B`` and ``C`` as blocks of the conv's
    output beside ``x``); the ``jax.numpy`` form of :func:`ssd_scan`
    serves everything else."""
    platform = _lowered_for[-1] if _lowered_for else jax.default_backend()
    width = heads // groups * head_dim
    return platform == "tpu" and chunk % KERNEL_TILE == 0 \
        and state % KERNEL_TILE == 0 and width % KERNEL_TILE == 0 \
        and heads * head_dim % state == 0 \
        and (KERNEL_TILE % head_dim == 0 or head_dim % KERNEL_TILE == 0)


#: positions of the conv kernels' strips and halo blocks (a tile of
#: bfloat16 rows): a row's positions come in multiples of it
CONV_HALO = 16
#: lags the conv kernels read past a strip's edge at most (a tile of
#: float32 rows)
CONV_LAGS = 8


def conv_kernel_suits(seq, first, width, conv):
    """Whether an ``ssd`` entry's conv stage runs as the TPU's kernels
    (``ops/pallas_conv.py``): the program is lowered for a TPU, the conv's
    ``width`` channels and the column ``first`` they start at in the input
    projection fill tiles of 128 lanes, the row's ``seq`` positions fill
    halo blocks of :data:`CONV_HALO`, and the conv's lags are at most
    :data:`CONV_LAGS`; :func:`causal_conv` serves everything else."""
    platform = _lowered_for[-1] if _lowered_for else jax.default_backend()
    return platform == "tpu" and first % KERNEL_TILE == 0 \
        and width % KERNEL_TILE == 0 and seq % CONV_HALO == 0 \
        and conv - 1 <= CONV_LAGS


#: every block size of the splash-attention kernel's three kernels at
#: heads of 128 (the probe on a v5e, PERF.md section 6)
SPLASH_BLOCK = 1024


def splash_block(s, head_dim):
    """The splash kernels' block of queries and of keys for rows of ``s``
    tokens and heads of ``head_dim``: :data:`SPLASH_BLOCK` at heads of 128
    or narrower, halved as the heads double (a kernel's fast memory holds
    blocks of block x head; the dkv kernel at 1024 x 256 asks 16.9 MB of the
    16 MB it may have, compiled for a v5e), no fewer than 128."""
    wide = max(int(head_dim), KERNEL_TILE)
    return min(max(KERNEL_TILE, SPLASH_BLOCK * KERNEL_TILE // wide), s)


#: the name the kernel's forward gives the two values its backward reads,
#: the output and the float32 log-sum of every query's scores: a recomputed
#: residual entry keeps them (``parallel/fused.py:_run_nodes``), so its
#: backward does not run the kernel's forward a second time
ATTENTION_KEPT = "attention_kept"


def _splash_mask(s, window, rep):
    """The mask known as the program is traced, for rows of ``s`` tokens
    and ``rep`` query heads on each key-value head: ``j <= i`` and, under
    a ``window``, ``i - j < window``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm)
    one = sm.CausalMask((s, s)) if window is None else sm.LocalMask(
        (s, s), (int(window) - 1, 0), 0)
    return sm.MultiHeadMask([one] * rep)


def _splash_kernel(s, window, rep, interpret=False, block=None):
    """The library's kernel under :func:`_splash_mask` at blocks of
    ``block`` (``min(SPLASH_BLOCK, s)`` where None); its three ``MaskInfo``
    (forward, dq, dkv) hold the block map of that mask, and its forward
    names what the backward reads :data:`ATTENTION_KEPT`."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    n = block or min(SPLASH_BLOCK, s)
    return sk.make_splash_mqa_single_device(
        _splash_mask(s, window, rep),
        block_sizes=sk.BlockSizes(
            block_q=n, block_kv=n, block_kv_compute=n, block_q_dkv=n,
            block_kv_dkv=n, block_kv_dkv_compute=n, block_q_dq=n,
            block_kv_dq=n),
        residual_checkpoint_name=ATTENTION_KEPT, interpret=interpret)


def _static_maps(s, window, rep, block=None):
    """The ``MaskInfo`` of :func:`_splash_kernel`'s forward, dq and dkv
    programs as the library works them out, numpy arrays: the very calls it
    makes as it builds the kernel (so its cache answers whichever comes
    second), where the kernel itself holds them as device arrays, which a
    program being traced could read only by waiting for the device."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask_info as mi)
    n = block or min(SPLASH_BLOCK, s)
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


def block_maps(segments, window=None, rep=1, block=None):
    """``(maps, blocks)`` of rows ``segments (B, S)`` at the kernel's
    ``block`` (:func:`_splash_kernel`'s).  ``maps`` is what
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
    infos = _static_maps(s, window, rep, block)
    seg = segments.astype(jnp.int32)
    maps = tuple(
        jax.vmap(lambda row, info=info, dkv=dkv: follow_segments(
            info, row, block or min(SPLASH_BLOCK, s), dkv))(seg)
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
    block = splash_block(s, hd)
    kernel = _splash_kernel(s, window, rep, interpret, block)
    infos = (kernel.fwd_mask_info, kernel.dq_mask_info, kernel.dkv_mask_info)
    if maps is None:
        maps, _ = block_maps(segments, window, rep, block)
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


def _lend(spec, w, ctx):
    """Hands ``w`` on to the entries that name this one under ``tied``."""
    if spec.name in ctx.get("lends", ()):
        ctx["tied"][spec.name] = w


def _embedding_apply(spec, p, ids, ctx):
    _lend(spec, p["w"], ctx)
    rows = jnp.take(p["w"], ids, axis=0)
    if spec.attrs.get("scale"):
        # in the master weights' type, before the cast
        rows = rows * float(spec.attrs["scale"])
    return _cast(rows, ctx["cd"])


def _rmsnorm_apply(spec, p, y, ctx):
    return rms(y, p["g"], float(spec.attrs.get("eps", 1e-6)))


def turns(spec):
    """The key of the rotary tables (``ctx["rope"]``) an entry reads, its
    width and base, or None: an ``attention`` entry's head size (given its
    tables whether or not it turns), a ``latent_attention`` entry's
    ``rope_dim``."""
    if spec.kind not in ("attention", "latent_attention"):
        return None
    a = spec.attrs
    width = a["head_dim"] if spec.kind == "attention" else a["rope_dim"]
    return int(width), float(a.get("rope_base", 10000.0))


def attends(spec):
    """(a head's width, query heads on a key-value head) of an entry that
    attends (and runs :func:`attend_splash` where :func:`kernel_suits`),
    or None."""
    if spec.kind == "attention":
        a = spec.attrs
        return int(a["head_dim"]), int(a["heads"]) // int(a["kv_heads"])
    if spec.kind == "latent_attention":
        _, _, _, nope, rope, _ = _latent_widths(spec.attrs)
        return nope + rope, 1
    return None


def _attend_heads(q, k, v, window, q_block, ctx):
    """The heads' output ``(B, S, H * hd)`` of queries ``q (B, S, H, hd)``
    on keys and values ``(B, S, KV, hd)``: the TPU's kernel where it suits
    (which reports ``blocks`` and ``kept`` in ``ctx["routed"]``), else the
    blocked lowering."""
    s, hd = q.shape[1], q.shape[-1]
    if not kernel_suits(s, hd):
        return attend(q, k, v, ctx["segments"], q_block, ctx["train"],
                      window)
    maps, blocks = ctx["block_maps"][(window, splash_block(s, hd))]
    u = attend_splash(q, k, v, ctx["segments"], window, maps)
    # ``kept``: 1 where a recomputed residual entry keeps this
    # application's output and log-sum for the backward
    ctx["routed"][ctx["node"]] = {
        "blocks": blocks, "kept": jnp.int32(bool(ctx.get("kept")))}
    return u


def _attention_apply(spec, p, y, ctx):
    a, cd = spec.attrs, ctx["cd"]
    b, s, _ = y.shape
    hd, h, kv = int(a["head_dim"]), int(a["heads"]), int(a["kv_heads"])
    window = a.get("window")
    rot = lambda x: x   # noqa: E731
    if a.get("rope", True):
        cos, sin = ctx["rope"][turns(spec)]
        rot = lambda x: _rotary(x, cos, sin)    # noqa: E731
    normed = lambda x, g: x     # noqa: E731
    if a.get("qk_norm"):
        # every head over its own elements, before it is turned
        normed = lambda x, g: rms(  # noqa: E731
            x, p[g], float(a.get("eps", 1e-6)))
    q = rot(normed((y @ _cast(p["wq"], cd)).reshape(b, s, h, hd), "gq"))
    k = rot(normed((y @ _cast(p["wk"], cd)).reshape(b, s, kv, hd), "gk"))
    v = (y @ _cast(p["wv"], cd)).reshape(b, s, kv, hd)
    u = _attend_heads(q, k, v, window, a.get("q_block"), ctx)
    if a.get("gate"):
        # the sigmoid in float32, of a product in the compute type
        gate = jax.nn.sigmoid((y @ _cast(p["wgate"], cd))
                              .astype(jnp.float32))
        u = (u.astype(jnp.float32) * gate).astype(u.dtype)
    return u @ _cast(p["wo"], cd)


def _latent_attention_apply(spec, p, y, ctx):
    """Queries from a normed rank-``q_rank`` projection; keys and values
    from a normed rank-``kv_rank`` latent beside ONE rotary key of
    ``rope_dim`` that every head shares; a head's query and key are its
    ``nope_dim`` part and its turned ``rope_dim`` part, its value
    ``v_dim`` wide (equal to theirs), scaled by ``1/sqrt(nope_dim +
    rope_dim)``; causal, cut at document boundaries.  Products in the
    compute type, the two norms and the rotary in float32."""
    a, cd = spec.attrs, ctx["cd"]
    b, s, _ = y.shape
    h, _, kvr, nope, rope, vd = _latent_widths(a)
    eps = float(a.get("eps", 1e-6))
    cos, sin = ctx["rope"][turns(spec)]
    cq = rms(y @ _cast(p["wqa"], cd), p["gqa"], eps)
    q = (cq @ _cast(p["wqb"], cd)).reshape(b, s, h, nope + rope)
    ckr = y @ _cast(p["wkva"], cd)
    kv = (rms(ckr[..., :kvr], p["gkv"], eps) @ _cast(p["wkvb"], cd)) \
        .reshape(b, s, h, nope + vd)
    kr = _rotary(ckr[..., kvr:].reshape(b, s, 1, rope), cos, sin)
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], cos, sin)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(kr, (b, s, h, rope))], axis=-1)
    u = _attend_heads(q, k, kv[..., nope:], None, a.get("q_block"), ctx)
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
    ``n``, or, of an ``n`` that is no multiple of 128, ``n`` rounded up to
    one: the kernel masks the last tile's overhang, and its backward takes
    the same tiles over the other operand's sides, where a tile of ``n``
    itself would not be one of 128 (experts 1,856 wide)."""
    if n % KERNEL_TILE:
        return _tile(-(-n // KERNEL_TILE) * KERNEL_TILE, most)
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


#: an expert's activation by the name a ``moe`` entry gives it
ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu,
               "relu2": lambda v: jnp.square(jax.nn.relu(v))}


def _moe_apply(spec, p, y, ctx):
    """A chip's share of a routed mixture of experts: ``sum over the chosen
    experts held here of weight * expert(y)``, the weights normalised over
    all ``top_k`` chosen of all ``experts``; an expert is ``(act(y Wg) * (y
    Wu)) Wd``, or ``act(y Wu) Wd`` where the entry says ``gated: False``.
    Pairs (token, expert) are
    sorted by expert with the held ones first, the products run
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
    act = ACTIVATIONS[a.get("activation", "silu")]
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
        if "wg" in p:
            hidden = act(grouped_dot(xs, _cast(p["wg"], cd), sizes)) \
                * grouped_dot(xs, _cast(p["wu"], cd), sizes)
        else:
            hidden = act(grouped_dot(xs, _cast(p["wu"], cd), sizes))
        ys = grouped_dot(hidden, _cast(p["wd"], cd), sizes)
    with jax.named_scope(name + "_combine"):
        mine = (chosen >= first) & (chosen < first + count)
        out, collect_rows = collect(ys, weights, order, back, held)
    if "sg" in p:
        with jax.named_scope(name + "_shared"):
            out = out + ((act(x @ _cast(p["sg"], cd))
                          * (x @ _cast(p["su"], cd)))
                         @ _cast(p["sd"], cd)).astype(jnp.float32)
    elif "su" in p:
        with jax.named_scope(name + "_shared"):
            out = out + (act(x @ _cast(p["su"], cd))
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


def shifted_labels(labels, shift):
    """The labels of a head that predicts the id ``shift`` positions
    ahead, from the next-id labels ``labels (B, S)`` (-1 where none is
    graded): position ``i`` is graded where positions ``i .. i + shift``
    lie in one document and row, which is where the next-id labels of
    ``i .. i + shift - 1`` all are."""
    out = labels
    for _ in range(int(shift) - 1):
        ahead = jnp.concatenate(
            [out[:, 1:], jnp.full_like(out[:, :1], -1)], axis=1)
        out = jnp.where(labels >= 0, ahead, -1)
    return out


def _lm_head_apply(spec, p, y, ctx):
    """Final norm; the normed state goes on down the chain (a looped
    model's next pass starts from it, a multi-token prediction module reads
    it) and the head's per-token numbers go to ``ctx["emit"]``: ``ce``,
    ``pred`` and, of a gated head, ``gate`` (N,), and the normed state at
    ``ctx["sample"]`` where positions are asked for.  A head graded at a
    ``shift`` past 1 puts them, with its ``label``, under
    ``ctx["emit"]["mtp"]``."""
    a, cd = spec.attrs, ctx["cd"]
    shift = int(a.get("shift", 1))
    w = ctx["tied"][a["tied"]] if a.get("tied") else p["w"]
    _lend(spec, w, ctx)
    h = rms(y, p["g"], float(a.get("eps", 1e-6)))
    b, s, d = h.shape
    n = b * s
    flat = h.reshape(n, d)
    labels = ctx["labels"] if shift == 1 else shifted_labels(
        ctx["labels"], shift)
    labels = labels.reshape(n)
    tb = int(a.get("token_block") or n)
    if n % tb:
        tb = n
    block = _head_block
    if ctx["train"]:
        block = jax.checkpoint(_head_block, static_argnums=(5,))
    we, be = p.get("we"), p.get("be")
    if tb == n:
        ce, pred, gate = block(flat, labels, w, we, be, cd)
    else:
        ce, pred, gate = jax.lax.map(
            lambda blk: block(blk[0], blk[1], w, we, be, cd),
            (flat.reshape(n // tb, tb, d), labels.reshape(n // tb, tb)))
        ce, pred, gate = jax.tree.map(lambda part: part.reshape(n),
                                      (ce, pred, gate))
    emit = {"ce": ce, "pred": pred}
    if gate is not None:
        emit["gate"] = gate
    if ctx.get("sample") is not None:
        emit["hidden"] = jnp.take(flat, ctx["sample"], axis=0)
    out = ctx["emit"]
    if shift != 1:
        emit["label"] = labels
        out = out.setdefault("mtp", {})
    if "ce" in out:
        raise ValueError("one lm_head a chain at each shift (inside a loop "
                         "it reads every pass)")
    out.update(emit)
    return h


def _mtp_input_apply(spec, p, y, ctx):
    """``[RMS(E[t']; ge) | RMS(y; gh)] W``: ``y`` the normed state the main
    head passed on, ``t'`` every position's next id in its row (0 past the
    row's end), ``E`` the table of the embedding the entry names under
    ``tied``, read as it stands."""
    a, cd = spec.attrs, ctx["cd"]
    eps = float(a.get("eps", 1e-6))
    ids = ctx["ids"]
    nxt = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
    e = _cast(jnp.take(ctx["tied"][a["tied"]], nxt, axis=0), cd)
    joined = jnp.concatenate([rms(e, p["ge"], eps), rms(y, p["gh"], eps)],
                             axis=-1)
    return joined @ _cast(p["w"], cd)


def document_starts(segments):
    """``(B, S)`` bool: where a position starts a document (the first of a
    row, and every one whose segment id is not its predecessor's)."""
    seg = segments.astype(jnp.int32)
    return jnp.concatenate([jnp.ones_like(seg[:, :1], bool),
                            seg[:, 1:] != seg[:, :-1]], axis=1)


def causal_conv(x, w, b, doc):
    """``silu(b + sum_i w[i] * x[t - K + 1 + i])`` over ``x (B, S, C)``,
    depthwise and causal, a term read only where its position lies in
    ``t``'s document (``doc (B, S)``: a document's number in its row);
    float32.  Where :func:`conv_kernel_suits` (a program lowered for a
    TPU, tiles filled), the mixer's conv stage runs the Pallas kernels of
    ``ops/pallas_conv.py`` in its place, forward and backward under one
    custom VJP, with the same terms summed in the same order."""
    s, k = x.shape[1], w.shape[0]
    x32, w32 = x.astype(jnp.float32), w.astype(jnp.float32)
    out = b.astype(jnp.float32) + w32[k - 1] * x32
    for lag in range(1, min(k, s + 1)):
        back = jnp.pad(x32, ((0, 0), (lag, 0), (0, 0)))[:, :s]
        same = jnp.pad(doc, ((0, 0), (lag, 0)), constant_values=-1)[:, :s] \
            == doc
        out = out + w32[k - 1 - lag] * jnp.where(same[..., None], back, 0.0)
    return jax.nn.silu(out)


def ssd_scan(x, dt, a, bm, cm, doc, chunk, cd=None):
    """Mamba-2's state-space recurrence in its chunked form:
    ``S_t = (1 - r_t) exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t
    C_t`` for every head, ``r_t`` 1 where ``doc (B, S)`` changes (a
    document starts) and ``S_{-1} = 0``; ``x (B, S, H, P)``, ``dt (B, S,
    H)`` float32 steps, ``a (H,)`` float32 (negative), ``bm, cm (B, S, G,
    N)`` read by the ``H / G`` heads of their group.  Returns ``y (B, S, H,
    P)`` float32.

    Chunks of ``chunk`` positions: inside one, ``y = (L o C B^T) X`` with
    ``L[t, s] = exp(sum_{s < j <= t} dt_j a)`` where ``s <= t`` lie in one
    document and 0 elsewhere; every chunk's state ``B^T X`` decayed to its
    end (from its last document's positions alone); a recurrence over the
    chunks' states that a document start cuts; and the carried state's
    part ``exp(cum_t) C_t S`` at the positions of the document it came in
    with.  ``x``, ``B``, ``C`` and the intra-chunk scores enter the products
    in ``cd`` with float32 accumulation; the steps, the decays and their
    exponentials and the carried states are float32.

    Which form runs where: this is the ``jax.numpy`` form, whose decays,
    scores and states are arrays of the whole row; it runs on the CPU and
    wherever the widths do not tile.  Where :func:`ssd_kernel_suits` (a
    program lowered for a TPU, tiles of 128 filled), the mixer's scan stage
    (:func:`_ssd_heads`) runs the Pallas kernels of ``ops/pallas_ssd.py``
    in its place, forward and backward under one custom VJP, with the same
    arithmetic: a grid step holds one chunk of one group of heads, its
    ``C B^T``, each head's decays and scores and the group's carried state
    in VMEM, and HBM sees the conv's output, ``(B, S, H)`` float32 factors
    of the steps and the document ids go in and ``y + D x`` come out."""
    b, s, h, hp = x.shape
    g, n = bm.shape[2:]
    rep = h // g
    q = int(chunk)
    pad = (-s) % q
    if pad:
        # no input and no decay past the row's end
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (
            v.ndim - 2)) for v in (x, dt, bm, cm))
        doc = jnp.pad(doc, ((0, 0), (0, pad)), mode="edge")
    nc = (s + pad) // q
    f32 = jnp.float32
    x = _cast(x.reshape(b, nc, q, g, rep, hp), cd)
    bm = _cast(bm.reshape(b, nc, q, g, n), cd)
    cm = _cast(cm.reshape(b, nc, q, g, n), cd)
    dt = dt.reshape(b, nc, q, h).transpose(0, 1, 3, 2)          # (b,c,h,q)
    doc = doc.reshape(b, nc, q)
    cum = jnp.cumsum(dt * a[:, None], axis=-1)                   # (b,c,h,q)
    last = doc[..., -1:]
    # inside a chunk: (t, s) pairs of one document, s <= t
    pair = (doc[..., :, None] == doc[..., None, :]) \
        & jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(pair[:, :, None],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                          # (b,c,h,t,s)
    cb = jnp.einsum("bctgn,bcsgn->bcgts", cm, bm, preferred_element_type=f32)
    scores = decay.reshape(b, nc, g, rep, q, q) * cb[:, :, :, None] \
        * dt.reshape(b, nc, g, rep, 1, q)
    y = jnp.einsum("bcgrts,bcsgrp->bctgrp", _cast(scores, cd), x,
                   preferred_element_type=f32)
    # every chunk's own state at its end, from its last document alone
    to_end = jnp.where((doc == last)[:, :, None],
                       jnp.exp(cum[..., -1:] - cum), 0.0) * dt    # (b,c,h,s)
    xw = _cast(x * to_end.reshape(b, nc, g, rep, q).transpose(
        0, 1, 4, 2, 3)[..., None], cd)
    own = jnp.einsum("bcsgrp,bcsgn->bcgrpn", xw, bm,
                     preferred_element_type=f32)
    # the state a chunk takes in: the previous one's, carried on where the
    # chunk's last document is the one it came in with
    came = jnp.concatenate([jnp.zeros_like(last[:, :1]), last[:, :-1]],
                           axis=1)                                # (b,c,1)
    keep = jnp.where(last == came, jnp.exp(cum[..., -1]), 0.0)    # (b,c,h)

    def carry(state, chunk_in):
        kept, mine = chunk_in
        return kept.reshape(b, g, rep, 1, 1) * state + mine, state

    _, taken = jax.lax.scan(
        carry, jnp.zeros((b, g, rep, hp, n), f32),
        (keep.transpose(1, 0, 2), own.transpose(1, 0, 2, 3, 4, 5)))
    taken = taken.transpose(1, 0, 2, 3, 4, 5)                     # (b,c,...)
    inflow = jnp.where((doc == came)[:, :, None], jnp.exp(cum), 0.0)
    y = y + jnp.einsum("bctgn,bcgrpn->bctgrp", cm, _cast(taken, cd),
                       preferred_element_type=f32) \
        * inflow.reshape(b, nc, g, rep, q).transpose(0, 1, 4, 2, 3)[..., None]
    return y.reshape(b, nc * q, h, hp)[:, :s]


def ssd_counts(starts, chunk):
    """``[chunks, resets, chunks_split]`` int32 of rows whose ``starts (B,
    S)`` mark the document starts: the chunks a scan at ``chunk`` runs, the
    starts past a row's first position (where a state is cut), and the
    chunks that hold such a start past their own first position (where the
    mask and the carry both cut)."""
    b, s = starts.shape
    q = int(chunk)
    pad = (-s) % q
    cut = jnp.pad(starts, ((0, 0), (0, pad))).reshape(b, -1, q)
    return jnp.stack([
        jnp.int32(cut.shape[0] * cut.shape[1]),
        starts[:, 1:].sum(dtype=jnp.int32),
        cut[..., 1:].any(axis=-1).sum(dtype=jnp.int32)])


def _ssd_heads(xbc, dt, dt_bias, a_log, d_skip, doc, widths, chunk, cd):
    """The scan's output plus ``D x``, ``(B, S, H * P)`` float32, from the
    conv's output ``xbc`` and the steps' projection ``dt``: the TPU's
    kernels where :func:`ssd_kernel_suits`, which read ``x``, ``B`` and
    ``C`` as blocks of ``xbc`` itself, else :func:`ssd_scan`."""
    h, hd, n, g = widths
    b, s, _ = xbc.shape
    inner = h * hd
    x = xbc[..., :inner].reshape(b, s, h, hd)
    bm = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
    cm = xbc[..., inner + g * n:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + dt_bias.astype(jnp.float32))
    if ssd_kernel_suits(h, hd, n, g, chunk):
        from znicz_tpu.ops import pallas_ssd
        return pallas_ssd.ssd_heads(
            xbc, dt, -jnp.exp(a_log.astype(jnp.float32)), d_skip, doc,
            widths, chunk, cd)
    u = ssd_scan(x, dt, -jnp.exp(a_log.astype(jnp.float32)), bm, cm, doc,
                 chunk, cd) + d_skip.astype(jnp.float32)[:, None] * x
    return u.reshape(b, s, inner)


def _gated_norm(u, z, gain, groups, eps):
    """``u * silu(z)`` normed over each of ``groups`` groups with
    ``gain``, float32."""
    b, s, inner = u.shape
    width = inner // groups
    gated = u.reshape(b, s, groups, width) * jax.nn.silu(
        z.astype(jnp.float32)).reshape(b, s, groups, width)
    return rms(gated, gain.reshape(groups, width), eps).reshape(b, s, inner)


def _ssd_apply(spec, p, y, ctx):
    """Mamba-2's mixer: ``[z | xBC | dt] = y W_in``; ``xBC`` through the
    depthwise causal conv (:func:`causal_conv`) and split into ``x`` (heads
    of ``head_dim``) and ``B``, ``C`` (``groups`` of ``state``); ``dt =
    softplus(dt + dt_bias)``, ``A = -exp(a_log)``; the scan
    (:func:`ssd_scan`) plus ``D x``; ``y * silu(z)`` normed over each group
    of ``heads * head_dim / groups`` with the gain ``g_norm``; ``W_out``.
    Neither the conv nor the state crosses a document start.  The
    projections in the compute type, the rest in float32 but for the
    scan's products.  The scan is the TPU's kernels where the program is
    lowered for a TPU and the widths tile (:func:`ssd_kernel_suits`: the
    chunks' decays, scores and the carried state stay in VMEM), and so is
    the conv (:func:`conv_kernel_suits`: it reads ``xBC`` as columns of
    the projection itself), each the ``jax.numpy`` form elsewhere; each
    stage is recomputed in the backward either way, and the kernels' ops,
    the backward's too, carry the ``L%02d.ssd_conv`` and ``L%02d.ssd_scan``
    scopes.  Leaves ``ssd`` (:func:`ssd_counts`) in
    ``ctx["routed"]``."""
    a, cd = spec.attrs, ctx["cd"]
    h, hd, n, g, _, chunk = _ssd_widths(a)
    inner, width, k = conv_columns(a)
    b, s, _ = y.shape
    name = "L%02d.ssd" % ctx["node"]
    zxd = y @ _cast(p["w_in"], cd)
    starts = document_starts(ctx["segments"])
    doc = jnp.cumsum(starts, axis=1, dtype=jnp.int32)
    # each stage recomputed in the backward from its inputs, so that one
    # stage's values are kept at a time
    with jax.named_scope(name + "_conv"):
        if conv_kernel_suits(s, inner, width, k):
            from znicz_tpu.ops import pallas_conv
            xbc = jax.checkpoint(pallas_conv.causal_conv,
                                 static_argnums=(4, 5))(
                zxd, p["w_conv"], p["b_conv"], doc, inner, width)
        else:
            xbc = jax.checkpoint(causal_conv)(
                zxd[..., inner:inner + width], p["w_conv"], p["b_conv"], doc)
    with jax.named_scope(name + "_scan"):
        u = jax.checkpoint(_ssd_heads, static_argnums=(6, 7, 8))(
            xbc, zxd[..., inner + width:], p["dt_bias"], p["a_log"],
            p["d_skip"], doc, (h, hd, n, g), chunk, cd)
    with jax.named_scope(name + "_norm"):
        u = jax.checkpoint(_gated_norm, static_argnums=(3, 4))(
            u, zxd[..., :inner], p["g_norm"], g, float(a.get("eps", 1e-5)))
    ctx["routed"][ctx["node"]] = {"ssd": ssd_counts(starts, chunk)}
    return _cast(u, cd) @ _cast(p["w_out"], cd)


def _seq_dim(in_shape, a):
    return (int(in_shape[0]), int(a["dim"]))


#: kind -> (leaves, apply, output sample shape)
KINDS = {
    "embedding": (_embedding_leaves, _embedding_apply, _seq_dim),
    "rmsnorm": (_rmsnorm_leaves, _rmsnorm_apply, lambda s, a: tuple(s)),
    "attention": (_attention_leaves, _attention_apply,
                  lambda s, a: tuple(s)),
    "latent_attention": (_latent_attention_leaves, _latent_attention_apply,
                         lambda s, a: tuple(s)),
    "gated_mlp": (_gated_mlp_leaves, _gated_mlp_apply,
                  lambda s, a: tuple(s)),
    "router": (_router_leaves, _router_apply, lambda s, a: tuple(s)),
    "moe": (_moe_leaves, _moe_apply, lambda s, a: tuple(s)),
    "lm_head": (_lm_head_leaves, _lm_head_apply, lambda s, a: tuple(s)),
    "mtp_input": (_mtp_input_leaves, _mtp_input_apply,
                  lambda s, a: tuple(s)),
    "ssd": (_ssd_leaves, _ssd_apply, lambda s, a: tuple(s)),
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
    :func:`leaves` lists them (``fill`` is the fused path's own filler).
    A ``moe`` entry draws one value of ``rand`` and returns, in place of
    its leaves, a function that draws them from the streams that value
    names and no other: a caller may run it on another thread."""
    out = {}
    if spec.type == "moe":
        # one draw of the layer's stream names the streams of its experts,
        # each drawn whole from its own: a share draws for the experts it
        # holds what the uncut layer draws for them, and nothing for the
        # rest; the shared expert's stream is named by the count of ALL
        # the experts, which no expert's is, so every share draws the same
        base = int(rand.randint(0, 2 ** 31 - 1, size=1)[0])
        return functools.partial(_init_experts, spec, base, dtype)
    for name, (shape, filling, value, _) in leaves(spec).items():
        arr = numpy.zeros(shape, dtype=dtype)
        _draw(rand, filling, arr, value, fill)
        out[name] = arr
    return out


def _draw(rand, filling, arr, value, fill):
    """Fills ``arr`` from ``rand``: ``log_uniform`` the log of U(``value``),
    ``dt_bias`` the inverse softplus of ``max(exp U(log lo, log hi),
    floor)`` of ``value = (lo, hi, floor)``, in the array's type; any
    other ``filling`` is ``fill``'s."""
    if filling == "log_uniform":
        rand.fill(arr, *value)
        numpy.log(arr, out=arr)
    elif filling == "dt_bias":
        low, high, floor = value
        rand.fill(arr, numpy.log(low), numpy.log(high))
        step = numpy.maximum(numpy.exp(arr), arr.dtype.type(floor))
        arr[...] = step + numpy.log(-numpy.expm1(-step))
    else:
        fill(rand, filling, arr, value)


def _init_experts(spec, base, dtype):
    """A ``moe`` entry's leaves (:func:`init`): each expert held from the
    stream ``[base, expert]``, the shared one from ``[base, experts]``."""
    first, count = _held(spec.attrs)
    table = leaves(spec)
    out = {name: numpy.empty(shape, dtype) if filling == "gaussian"
           else numpy.full(shape, value, dtype)
           for name, (shape, filling, value, _) in table.items()}
    routed = [name for name in ("wg", "wu", "wd") if name in table]
    for j in range(count):
        own = numpy.random.RandomState([base, first + j])
        for name in routed:
            prng.normal_into(own, out[name][j], 0, table[name][2])
    own = numpy.random.RandomState([base, int(spec.attrs["experts"])])
    for name in ("sg", "su", "sd"):
        if name in out:
            prng.normal_into(own, out[name], 0, table[name][2])
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


def shifted_loss(emit):
    """(mean loss over its graded tokens, [errors, graded], loss sum) of a
    head graded at a shift, from what it emits (``ce``, ``pred`` and its
    own ``label``, each (N,))."""
    lbl = emit["label"]
    valid = lbl >= 0
    graded = valid.sum()
    loss_sum = jnp.where(valid, emit["ce"], 0.0).sum()
    errors = (valid & (emit["pred"] != lbl)).sum()
    return loss_sum / jnp.maximum(graded, 1), \
        jnp.stack([errors, graded]).astype(jnp.int32), loss_sum
