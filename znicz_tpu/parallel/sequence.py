"""Sequence/context parallelism — ring attention over the device mesh.

Long-context support the TPU way: the sequence axis is sharded across
devices, each device holds one block of Q/K/V, and K/V blocks rotate
around the ring (``lax.ppermute`` — neighbor exchanges ride ICI) while
every device accumulates its queries' attention with a flash-style
streaming softmax (running max / normalizer), so the full T x T score
matrix never materializes and context length scales linearly with the
number of devices.

This is the long-sequence counterpart of the reference's LSTM tier: the
reference (2013-2015) predates attention, but its "long sequence"
ambition maps to exactly this primitive on TPU (the scaling-book
recipe: pick a mesh, shard the sequence, let collectives do the rest).

API:

* :func:`attention_reference` — single-device attention, the executable
  spec (numpy-style jnp math);
* :func:`ring_attention` — the same math over a mesh axis, exact to
  float tolerance, causal or full.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P


def attention_reference(q, k, v, causal=False):
    """Plain softmax attention, (B, T, H, D) -> (B, T, H, D).

    The single-device spec ring_attention must reproduce."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(tk)[None, :] > jnp.arange(tq)[:, None]
        s = jnp.where(mask, -jnp.inf, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _ring_body(q, kb, vb, m, l, acc, q_pos, k_pos, scale, causal):
    """One ring step: fold the visiting K/V block into the running
    flash-softmax state."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kb) * scale
    if causal:
        mask = k_pos[None, :] > q_pos[:, None]      # (T_q, T_k)
        s = jnp.where(mask[None, None], -jnp.inf, s)
    blk_max = jnp.max(s, axis=-1)                   # (B, H, T_q)
    m_new = jnp.maximum(m, blk_max)
    # fully-masked rows keep m = -inf; guard the exp against inf - inf
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - safe_m[..., None])  # masked cells: exp(-inf) == 0
    corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + \
        jnp.einsum("bhqk,bkhd->bhqd", p, vb)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, mesh, axis="data", causal=False):
    """Attention with the SEQUENCE axis sharded over ``mesh[axis]``.

    q/k/v: (B, T, H, D) global arrays (host or device); T must divide
    evenly by the axis size.  Returns the (B, T, H, D) result sharded
    the same way.  K/V blocks rotate around the ring; with ``causal``
    each device masks by GLOBAL positions, so the result matches
    :func:`attention_reference` on the gathered arrays.
    """
    n = mesh.shape[axis]
    t = q.shape[1]
    if tuple(k.shape) != tuple(q.shape) or \
            tuple(v.shape) != tuple(q.shape):
        raise ValueError(
            "ring attention is self-attention: q/k/v must share one "
            "(B, T, H, D) shape, got %s / %s / %s"
            % (q.shape, k.shape, v.shape))
    if t % n:
        raise ValueError("sequence length %d not divisible by %d shards"
                         % (t, n))
    t_local = t // n
    spec = P(None, axis, None, None)
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(a, sharding) for a in (q, k, v))
    return _compiled_ring(mesh, axis, n, t_local, int(q.shape[-1]),
                          causal)(q, k, v)


@functools.lru_cache(maxsize=64)
def _compiled_ring(mesh, axis, n, t_local, d, causal):
    """Cache the jitted shard_map per geometry — rebuilding it per call
    would re-trace and re-compile every step."""
    spec = P(None, axis, None, None)
    fwd = functools.partial(_ring_attention_local, axis=axis, n=n,
                            t_local=t_local,
                            scale=1.0 / math.sqrt(d), causal=causal)
    return jax.jit(shard_map(fwd, mesh=mesh,
                             in_specs=(spec, spec, spec),
                             out_specs=spec))


def _ring_attention_local(q, k, v, *, axis, n, t_local, scale, causal):
    """Per-device body: q is MY block; k/v blocks visit via ppermute."""
    my = jax.lax.axis_index(axis)
    b, _, h, d = q.shape
    q_pos = my * t_local + jnp.arange(t_local)
    # pvary: the carry becomes axis-varying on the first iteration (it
    # mixes in axis_index-dependent masks), so the init must be marked
    # varying too or the fori_loop carry types mismatch
    def vary(a):
        return jax.lax.pcast(a, axis, to="varying")

    m = vary(jnp.full((b, h, t_local), -jnp.inf, q.dtype))
    l = vary(jnp.zeros((b, h, t_local), q.dtype))
    acc = vary(jnp.zeros((b, h, t_local, d), q.dtype))
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(i, carry):
        m, l, acc, kb, vb = carry
        # after i rotations each device holds the block that STARTED at
        # device (my - i) mod n
        src = (my - i) % n
        k_pos = src * t_local + jnp.arange(t_local)
        m, l, acc = _ring_body(q, kb, vb, m, l, acc, q_pos, k_pos,
                               scale, causal)
        kb = jax.lax.ppermute(kb, axis, perm)
        vb = jax.lax.ppermute(vb, axis, perm)
        return m, l, acc, kb, vb

    m, l, acc, _, _ = jax.lax.fori_loop(0, n, body, (m, l, acc, k, v))
    # fully-masked rows (l == 0) normalize to 0 rather than NaN
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3))  # (B, T_local, H, D)
