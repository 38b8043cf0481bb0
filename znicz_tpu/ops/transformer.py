"""Layer kinds of a token-sequence model, each defined once.

``embedding``, ``rmsnorm``, ``attention`` (rotary, causal, cut at document
boundaries), ``gated_mlp`` and ``lm_head`` (a looped model's head: final
norm, exit gate, untied output product).  A kind is a row of :data:`KINDS`:
its parameter leaves (shape, filling, whether weight decay applies), the
sample shape it gives, and its ``jax.numpy`` forward.  The fused path reads
nothing else of a kind: ``parallel/fused.py`` builds specs, draws and
places parameters, makes optimizer state and hyperparameters and applies
updates leaf by leaf from these rows, and names each spec's device ops
``L%02d.<kind>`` (kinds are ``[a-z_]+``).

Arithmetic: products run in the compute type (``cd``, bfloat16 on the
chip) with float32 master weights cast where they are used, so that a
shared weight's gradient is summed over its applications in float32;
norms, rotary, softmaxes, the exit gate and the loss are float32.

Memory: attention works by blocks of queries and the head by blocks of
tokens, each block under ``jax.checkpoint``, so neither the scores
(batch x heads x S x S) nor a pass's logits (tokens x vocabulary) exist
whole, forward or backward.  Where the program is lowered for a TPU and
the shapes suit it, attention is the TPU's flash-attention kernel instead
(:func:`kernel_suits`): the code chooses, no option does.

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
# order; filling is "gaussian" (stddev ``value``) or "constant".

def _embedding_leaves(a, in_shape):
    return {"w": ((int(a["vocab"]), int(a["dim"])), "gaussian", _std(a),
                  True)}


def _rmsnorm_leaves(a, in_shape):
    return {"g": ((int(in_shape[-1]),), "constant", 1.0, False)}


def _attention_leaves(a, in_shape):
    d = int(in_shape[-1])
    hd, h, kv = int(a["head_dim"]), int(a["heads"]), int(a["kv_heads"])
    s = _std(a)
    return {"wq": ((d, h * hd), "gaussian", s, True),
            "wk": ((d, kv * hd), "gaussian", s, True),
            "wv": ((d, kv * hd), "gaussian", s, True),
            "wo": ((h * hd, d), "gaussian", s, True)}


def _gated_mlp_leaves(a, in_shape):
    d, f = int(in_shape[-1]), int(a["hidden"])
    s = _std(a)
    return {"wg": ((d, f), "gaussian", s, True),
            "wu": ((d, f), "gaussian", s, True),
            "wd": ((f, d), "gaussian", s, True)}


def _lm_head_leaves(a, in_shape):
    d = int(in_shape[-1])
    s = _std(a)
    return {"g": ((d,), "constant", 1.0, False),
            "w": ((int(a["vocab"]), d), "gaussian", s, True),
            "we": ((d,), "gaussian", s, False),
            "be": ((1,), "constant", 0.0, False)}


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


def _attend_block(q, k, v, seg_q, seg_k, q0, scale):
    """One block of queries against the keys up to its end: q (B, bq, H,
    hd) at row positions ``q0 ...``, k and v (B, n, KV, hd) at 0 ... n-1.
    Masked to ``j <= i`` and the same document; softmax in float32."""
    b, bq, h, hd = q.shape
    n, kv = k.shape[1], k.shape[2]
    rep = h // kv
    qg = q.reshape(b, bq, kv, rep, hd)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    qi = q0 + jnp.arange(bq)
    ok = (jnp.arange(n)[None, :] <= qi[:, None])[None] \
        & (seg_q[:, :, None] == seg_k[:, None, :])
    s = jnp.where(ok[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    u = jnp.einsum("bgrqk,bkgd->bqgrd", p, v)
    return u.reshape(b, bq, h * hd)


def attend(q, k, v, segments, q_block, remat):
    """Causal attention inside documents, by blocks of ``q_block`` queries
    (each block sees the keys up to its own end only, so the masked upper
    triangle is skipped block-wise); ``remat`` recomputes a block's scores
    in the backward pass instead of keeping them."""
    b, s, h, hd = q.shape
    bq = int(q_block) if q_block else s
    if s % bq:
        bq = s
    scale = 1.0 / float(numpy.sqrt(hd))
    fn = jax.checkpoint(_attend_block, static_argnums=(5, 6)) if remat \
        else _attend_block
    outs = []
    for q0 in range(0, s, bq):
        end = q0 + bq
        outs.append(fn(q[:, q0:end], k[:, :end], v[:, :end],
                       segments[:, q0:end], segments[:, :end], q0, scale))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


#: every block size of the flash-attention kernel's three kernels: what
#: 4,096-token rows of 16 heads of 128 were measured with on a v5e (PERF.md
#: section 6, PR 28)
FLASH_BLOCK = 1024

#: the kernel's tiles: a row's tokens and a head's size in multiples of this
FLASH_TILE = 128

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
    """Whether attention runs as the flash-attention kernel: the program
    is lowered for a TPU (Mosaic compiles for nothing else) and the row
    and the head fill the kernel's tiles; the blocked ``jax.numpy``
    lowering serves everything else."""
    platform = _lowered_for[-1] if _lowered_for else jax.default_backend()
    return platform == "tpu" and seq % FLASH_TILE == 0 \
        and head_dim % FLASH_TILE == 0


def attend_flash(q, k, v, segments, block=FLASH_BLOCK):
    """The same attention as :func:`attend` by the TPU's flash-attention
    kernel (``jax.experimental.pallas.ops.tpu.flash_attention``: online
    softmax in float32 over blocks held in VMEM, causal blocks above the
    diagonal skipped, segment ids masking across documents; its backward
    pass is two more kernels, so no score crosses HBM either way).  It
    compiles for a TPU only.  ``block`` is every block size of its three
    kernels."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    b, s, h, hd = q.shape
    n = min(int(block), s)
    sizes = fa.BlockSizes(
        block_q=n, block_k_major=n, block_k=n, block_b=1,
        block_q_major_dkv=n, block_k_major_dkv=n, block_k_dkv=n,
        block_q_dkv=n, block_k_major_dq=n, block_k_dq=n, block_q_dq=n)
    rep = h // k.shape[2]
    if rep > 1:
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    seg = segments.astype(jnp.int32)
    out = fa.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), segment_ids=fa.SegmentIds(q=seg, kv=seg),
        causal=True, sm_scale=1.0 / float(numpy.sqrt(hd)),
        block_sizes=sizes)
    return out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)


def _embedding_apply(spec, p, ids, ctx):
    return _cast(jnp.take(p["w"], ids, axis=0), ctx["cd"])


def _rmsnorm_apply(spec, p, y, ctx):
    return rms(y, p["g"], float(spec.attrs.get("eps", 1e-6)))


def _attention_apply(spec, p, y, ctx):
    a, cd = spec.attrs, ctx["cd"]
    b, s, _ = y.shape
    hd, h, kv = int(a["head_dim"]), int(a["heads"]), int(a["kv_heads"])
    cos, sin = ctx["rope"][(hd, float(a.get("rope_base", 10000.0)))]
    q = _rotary((y @ _cast(p["wq"], cd)).reshape(b, s, h, hd), cos, sin)
    k = _rotary((y @ _cast(p["wk"], cd)).reshape(b, s, kv, hd), cos, sin)
    v = (y @ _cast(p["wv"], cd)).reshape(b, s, kv, hd)
    if kernel_suits(s, hd):
        u = attend_flash(q, k, v, ctx["segments"])
    else:
        u = attend(q, k, v, ctx["segments"], a.get("q_block"), ctx["train"])
    return u @ _cast(p["wo"], cd)


def _gated_mlp_apply(spec, p, y, ctx):
    cd = ctx["cd"]
    gate = jax.nn.silu(y @ _cast(p["wg"], cd))
    return (gate * (y @ _cast(p["wu"], cd))) @ _cast(p["wd"], cd)


def head_logits(h, w, cd):
    """``h W^T`` in the compute type, accumulated and kept in float32."""
    return jnp.einsum("nd,vd->nv", _cast(h, cd), _cast(w, cd),
                      preferred_element_type=jnp.float32)


def _head_block(h, labels, w, we, be, cd):
    """One block of tokens: per-token cross-entropy of the pass's logits
    against the label (0 where none is graded), the logits' argmax and the
    exit gate's pre-activation."""
    z = head_logits(h, w, cd)
    lse = jax.nn.logsumexp(z, axis=-1)
    lbl = jnp.maximum(labels, 0)
    ce = lse - jnp.take_along_axis(z, lbl[:, None], axis=1)[:, 0]
    ce = jnp.where(labels >= 0, ce, 0.0)
    pred = jnp.argmax(z, axis=-1).astype(jnp.int32)
    gate = h.astype(jnp.float32) @ we.astype(jnp.float32) \
        + be.astype(jnp.float32)[0]
    return ce, pred, gate


def _lm_head_apply(spec, p, y, ctx):
    """Final norm; the normed state goes on down the chain (a looped
    model's next pass starts from it) and the head's per-token numbers go
    to ``ctx["emit"]``: ``ce``, ``pred``, ``gate`` (N,), and the normed
    state at ``ctx["sample"]`` where positions are asked for."""
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
    if tb == n:
        ce, pred, gate = block(flat, labels, p["w"], p["we"], p["be"], cd)
    else:
        ce, pred, gate = jax.lax.map(
            lambda blk: block(blk[0], blk[1], p["w"], p["we"], p["be"],
                              cd),
            (flat.reshape(n // tb, tb, d), labels.reshape(n // tb, tb)))
        ce, pred, gate = ce.reshape(n), pred.reshape(n), gate.reshape(n)
    emit = {"ce": ce, "pred": pred, "gate": gate}
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


def build(tpe, fwd, in_shape, hyper, hyper_bias, flags):
    """The spec of one layer of kind ``tpe`` over ``in_shape`` samples."""
    attrs = dict(fwd)
    out_shape = KINDS[tpe][2](in_shape, attrs)
    if tpe != "embedding" and len(in_shape) != 2:
        raise ValueError("%s needs (seq, dim) samples, have %r"
                         % (tpe, tuple(in_shape)))
    return TokenSpec(type=tpe, in_shape=tuple(in_shape),
                     out_shape=tuple(out_shape), attrs=attrs, hyper=hyper,
                     hyper_bias=hyper_bias, flags=flags)


def leaves(spec):
    return KINDS[spec.type][0](spec.attrs, spec.in_shape)


def init(spec, rand, dtype, fill):
    """The spec's parameters on the host, drawn leaf by leaf in the order
    :func:`leaves` lists them (``fill`` is the fused path's own filler)."""
    out = {}
    for name, (shape, filling, value, _) in leaves(spec).items():
        arr = numpy.zeros(shape, dtype=dtype)
        fill(rand, filling, arr, value)
        out[name] = arr
    return out


def leaf_hypers(spec, hyper=None, hyper_bias=None):
    """{leaf: hyperparameters}: decayed leaves take the layer's weight
    hyperparameters, gains and the gate its bias ones."""
    hyper = spec.hyper if hyper is None else hyper
    hyper_bias = spec.hyper_bias if hyper_bias is None else hyper_bias
    return {name: dict(hyper if decays else hyper_bias)
            for name, (_, _, _, decays) in leaves(spec).items()}


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
    distribution (T, N)) from a chain's head outputs, stacked over passes
    where a loop ran the head more than once."""
    ce, gate, pred = emit["ce"], emit["gate"], emit["pred"]
    if ce.ndim == 1:
        ce, gate, pred = ce[None], gate[None], pred[None]
    logp = exit_log_probs(gate)
    p = jnp.exp(logp)
    per_tok = (p * ce).sum(axis=0) + beta * (p * logp).sum(axis=0)
    lbl = labels.reshape(-1)
    valid = lbl >= 0
    graded = valid.sum()
    loss_sum = jnp.where(valid, per_tok, 0.0).sum()
    errors = (valid & (pred[-1] != lbl)).sum()
    loss = loss_sum / jnp.maximum(graded, 1)
    return loss, jnp.stack([errors, graded]).astype(jnp.int32), loss_sum, p
