"""A recomputed residual entry keeps what the attention kernel's backward
reads, so the kernel's forward runs once a train step.

The splash kernel names its output and log-sum ``transformer.ATTENTION_KEPT``
and a residual entry with ``remat`` saves only values of that name.  Off the
chip: a tiny token net's train window lowered for a TPU holds one forward
kernel per attention entry, beside its dq and dkv kernels; on the CPU, the
kernel interpreted, the gradients under the policy equal those of a
recomputation that keeps nothing, and the step counts the applications whose
values were kept (none where the blocked lowering runs, none in a forward
that does not train).
"""
import collections
import re

import numpy
import pytest

import jax
import jax.numpy as jnp

from znicz_tpu.ops import transformer
from znicz_tpu.parallel import fused
from znicz_tpu.samples.research import latent_moe_lm, looped_lm, routed_lm

#: the kernel's three programs, by the name it gives each
FWD, DQ, DKV = ("splash_mqa_fwd_segmented_residuals",
                "splash_mqa_dq_segmented_no_residuals",
                "splash_mqa_dkv_segmented_no_residuals")


def _routed(**kw):
    """Two layers: one global, one under a window; heads of 128."""
    return routed_lm.make_layers(**dict(dict(
        vocab=256, dim=256, heads=2, kv_heads=1, head_dim=128, experts=4,
        top_k=2, held_count=4, hidden=256, n_layers=2, window=256), **kw))


def _looped():
    """Two layers run twice over one set of weights; heads of 128."""
    return looped_lm.make_layers(
        vocab=256, dim=256, heads=2, kv_heads=2, head_dim=128, hidden=256,
        n_layers=2, passes=2)


def _latent(**kw):
    """A dense and an expert layer of latent attention, then a multi-token
    prediction module's layer: three entries, heads of 192 + 64."""
    return latent_moe_lm.make_layers(**dict(dict(
        vocab=256, dim=256, heads=2, q_rank=128, kv_rank=128, nope_dim=192,
        rope_dim=64, v_dim=256, dense_hidden=256, experts=4, top_k=2,
        held_count=4, hidden=256, shared_hidden=256, n_layers=2), **kw))


def _shapes(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype),
        tree)


def kernel_calls(text):
    """The kernels a lowered module calls, by name, each call site once
    as the calls are followed from ``main`` (the text holds a function
    that two sites call once; a loop's body is one site)."""
    bodies = dict(re.findall(r"func\.func \w+ @([\w.$-]+)\((.*?)\n  }",
                             text, re.S))

    def calls(name):
        body = bodies[name]
        out = collections.Counter(re.findall(
            r'kernel_name = "(splash_mqa_\w+)"', body))
        for callee in re.findall(r"\bcall @([\w.$-]+)\(", body):
            out.update(calls(callee))
        return out
    return calls("main")


@pytest.mark.parametrize("make, leaves",
                         [(_routed, 2), (_looped, 2), (_latent, 3)],
                         ids=["routed", "looped", "latent"])
def test_a_window_lowered_for_a_tpu_runs_each_forward_once(make, leaves):
    """The window's text holds one forward kernel per attention entry (a
    loop's body once), as many as its dq and its dkv kernels: the backward
    takes the forward's output and log-sum and does not call the forward
    kernel again.  Lowering for a TPU needs no chip and no TPU compiler."""
    seq, k, batch, rows = 1024, 1, 1, 3
    with jax.enable_x64(False), jax.default_matmul_precision("default"), \
            transformer.lowering_for("tpu"):
        net = fused.FusedNet(make(), (seq,), compute_dtype=jnp.bfloat16,
                             objective="tokens")
        data = jax.ShapeDtypeStruct((rows, seq), jnp.int32)
        hy = jax.tree.map(lambda v: jax.ShapeDtypeStruct((k,), jnp.float32),
                          fused.default_hypers(net.specs))
        text = net._get_window_fn(k, "indexed").trace(
            _shapes(net.params), _shapes(net.state),
            jax.ShapeDtypeStruct((2,), jnp.uint32), data, (data, data),
            jax.ShapeDtypeStruct((k, batch), jnp.int32), None,
            jax.ShapeDtypeStruct((k,), jnp.int32), hy,
            _shapes(net.window_acc_zeros())).lower(
                lowering_platforms=("tpu",)).as_text()
    assert kernel_calls(text) == {FWD: leaves, DQ: leaves, DKV: leaves}


@pytest.fixture
def interpreted(monkeypatch):
    """Attention as the splash kernel, interpreted, at blocks of 128: the
    test steers the choice the platform makes on the chip."""
    real = transformer._splash_kernel
    monkeypatch.setattr(transformer, "SPLASH_BLOCK", 128)
    monkeypatch.setattr(transformer, "kernel_suits",
                        lambda seq, head_dim: True)
    monkeypatch.setattr(transformer, "_splash_kernel",
                        lambda s, window, rep, interpret=False,
                        block=None: real(s, window, rep, True, block))
    with jax.enable_x64(False):
        yield


def _segments(seq, cuts):
    seg = numpy.ones(seq, numpy.int32)
    for cut in cuts:
        seg[cut:] += 1
    return seg


def test_the_kept_values_give_the_recomputed_gradients(interpreted):
    """The kernel's gradients of queries, keys and values with its output
    and log-sum kept equal those of a recomputation that keeps nothing."""
    rs = numpy.random.RandomState(38)
    q, k, v = (jnp.asarray(rs.normal(0, 1, (1, 512, n, 128)), jnp.float32)
               for n in (2, 1, 1))
    seg = jnp.asarray(_segments(512, [150, 300])[None])

    def grads(policy):
        def loss(q, k, v):
            return (transformer.attend_splash(q, k, v, seg, 200) ** 2).sum()
        return jax.grad(jax.checkpoint(loss, policy=policy),
                        argnums=(0, 1, 2))(q, k, v)

    kept = jax.checkpoint_policies.save_only_these_names(
        transformer.ATTENTION_KEPT)
    for got, want in zip(grads(kept), grads(None)):
        numpy.testing.assert_array_equal(got, want)


def _step(net, train):
    """One step's weight gradients and what the forward counted."""
    seq = net.specs[0].in_shape[0]
    rs = numpy.random.RandomState(3)
    ids = jnp.asarray(rs.randint(0, 256, (2, seq)), jnp.int32)
    seg = jnp.asarray(numpy.stack([_segments(seq, [100, 290]),
                                   _segments(seq, [256])]))

    def loss(p):
        emit = fused.forward_tokens(p, ids, seg, ids, net.specs,
                                    net.topology, train=train)
        return fused._token_stats(emit, ids, net.specs)[0], emit

    return jax.grad(loss, has_aux=True)(net.params)


def test_a_step_keeping_the_values_gives_the_same_gradients(
        interpreted, monkeypatch):
    """Every weight's gradient of a routed step, the whole net under the
    policy against the same net recomputing everything."""
    net = fused.FusedNet(_routed(stddev=0.2), (512,), objective="tokens")
    kept, emit = _step(net, True)
    monkeypatch.setattr(fused, "_KEEP_ATTENTION", None)
    whole, _ = _step(net, True)
    for got, want in zip(jax.tree.leaves(kept), jax.tree.leaves(whole)):
        numpy.testing.assert_array_equal(got, want)
    assert int(emit["attention_kept"]) == 2


@pytest.mark.parametrize("make, train, applications", [
    (_routed, True, 2), (_looped, True, 4), (_routed, False, 0),
    (lambda: _latent(nope_dim=64, v_dim=128), True, 3)],
    ids=["routed", "looped", "not_training", "latent"])
def test_a_step_counts_the_applications_kept(interpreted, make, train,
                                             applications):
    """Every application of an ``attention`` or ``latent_attention`` entry
    under a recomputed residual entry, a loop's passes each; none in a
    forward that does not train, where nothing is recomputed.  The net's
    accumulator carries the count."""
    net = fused.FusedNet(make(), (256,), objective="tokens")
    _, emit = _step(net, train)
    assert int(emit["attention_kept"]) == applications
    assert net.window_acc_zeros()["attention_kept"].shape == ()


def test_where_no_kernel_runs_nothing_is_kept_or_counted():
    """On this platform attention is the blocked lowering: no report, and
    no count in the accumulator."""
    net = fused.FusedNet(routed_lm.make_layers(n_layers=1), (32,),
                         objective="tokens")
    assert "attention_kept" not in net.window_acc_zeros()
    ids = jnp.zeros((2, 32), jnp.int32)
    emit = fused.forward_tokens(net.params, ids, jnp.ones_like(ids), ids,
                                net.specs, net.topology, train=True)
    assert "attention_kept" not in emit
