"""The chunked scan's Pallas kernels (``ops/pallas_ssd.py``) against the
``jax.numpy`` form of ``transformer.ssd_scan`` (plus ``D x``: the mixer's
scan stage, ``transformer._ssd_heads``), which
``tests/unit/test_ssd_moe_lm.py`` holds to the token-by-token recurrence.

The kernels are built for the TPU's compiler; here they run interpreted
(``pltpu.force_tpu_interpret_mode()``, the test's choice), at the
smallest widths that fill their tiles: chunks of 128, a state of 128 and
groups of two heads of 64.  Their compile for a described v5e is
``tests/unit/test_tpu_compile.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy
import pytest
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops import pallas_ssd, transformer

HEADS, HEAD_DIM, GROUPS, STATE, CHUNK = 4, 64, 2, 128, 128
INNER = HEADS * HEAD_DIM
WIDTHS = (HEADS, HEAD_DIM, STATE, GROUPS)

#: (row length, document starts past the row's first position)
LAYOUTS = {
    "one_document": (256, ()),
    "start_at_a_chunk": (256, (128,)),
    "start_mid_chunk": (256, (77,)),
    "two_starts_in_a_chunk": (256, (150, 201)),
    "row_not_a_multiple": (300, (50, 260)),
}
#: the gradients compared: ``xbc``'s three parts, then the other arguments
GRADS = ("x", "B", "C", "dt", "dt_bias", "a_log", "d_skip")


def _inputs(length, starts):
    """The scan stage's arguments (the conv's output ``xbc``, the steps'
    projection, ``dt_bias``, ``a_log``, ``D``), the documents and a
    cotangent."""
    r = numpy.random.RandomState(length + sum(starts))
    xbc = numpy.concatenate([r.randn(1, length, INNER),
                             0.3 * r.randn(1, length, 2 * GROUPS * STATE)],
                            axis=-1)
    args = [jnp.asarray(v, jnp.float32) for v in (
        xbc, r.randn(1, length, HEADS) - 2.0, 0.5 * r.randn(HEADS),
        numpy.log(r.uniform(1.0, 16.0, HEADS)), r.randn(HEADS))]
    first = numpy.zeros((1, length), bool)
    first[:, (0,) + tuple(starts)] = True
    doc = jnp.cumsum(jnp.asarray(first), axis=1, dtype=jnp.int32)
    return args, doc, jnp.asarray(r.randn(1, length, INNER), jnp.float32)


def _stage(platform, args, doc, dy, cd):
    """``transformer._ssd_heads`` lowered for ``platform`` (the kernels,
    interpreted, for ``tpu``; the ``jax.numpy`` form for ``cpu``): its
    output and the gradients :data:`GRADS` of ``sum(u * dy)``."""
    def loss(xbc, dt, dt_bias, a_log, d_skip):
        u = transformer._ssd_heads(xbc, dt, dt_bias, a_log, d_skip, doc,
                                   WIDTHS, CHUNK, cd)
        return jnp.sum(u * dy), u

    with transformer.lowering_for(platform), \
            pltpu.force_tpu_interpret_mode():
        (_, u), grads = jax.value_and_grad(loss, argnums=range(5),
                                           has_aux=True)(*args)
    xbc = grads[0]
    parts = (xbc[..., :INNER], xbc[..., INNER:INNER + GROUPS * STATE],
             xbc[..., INNER + GROUPS * STATE:])
    return u, dict(zip(GRADS, parts + tuple(grads[1:])))


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("cd", ["float32", "bfloat16"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_kernels_compute_the_jnp_form(layout, cd):
    """The scan stage's output (``ssd_scan`` plus ``D x``) and its
    gradients in x, B, C, the steps, ``a`` (through ``a_log``) and ``D``.
    In float32 the two forms agree to rounding; in bf16 the output still
    does (the same operands enter the same products), and each gradient
    lies about as close to the float32 form's as the ``jax.numpy`` form's
    own does (its cotangents are bf16 where the kernels accumulate theirs
    in float32 and cut them into two bf16 parts for the products)."""
    args, doc, dy = _inputs(*LAYOUTS[layout])
    dtype = None if cd == "float32" else jnp.dtype(cd)
    u_ref, g_ref = _stage("cpu", args, doc, dy, dtype)
    u, grads = _stage("tpu", args, doc, dy, dtype)
    assert u.shape == u_ref.shape and u.dtype == jnp.float32
    assert _rel(u, u_ref) < 1e-6, layout
    if dtype is None:
        for name in GRADS:
            # float32 sums in another order: ``a``'s gradient is a sum
            # over every position that cancels to a few parts in 1e-5
            assert _rel(grads[name], g_ref[name]) < 1e-4, name
        return
    # the distance of each from the float32 form's gradient: ``a``'s, four
    # sums over every position that cancel, scatters from one draw to the
    # next by a factor of two or three in either form
    _, exact = _stage("cpu", args, doc, dy, None)
    for name in GRADS:
        assert _rel(grads[name], exact[name]) \
            < 3 * _rel(g_ref[name], exact[name]) + 1e-3, name


def test_a_document_start_cuts_the_carried_state():
    """The kernels' output before a start does not read what follows it,
    and after it does not read what came before: a row of two documents
    scans as the two rows of one document each."""
    (xbc, dt, dt_bias, a_log, d_skip), doc, _ = _inputs(256, (150,))
    dt, a = jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log)

    def scan(part, documents):
        with pltpu.force_tpu_interpret_mode():
            return pallas_ssd.ssd_heads(xbc[:, part], dt[:, part], a, d_skip,
                                        documents, WIDTHS, CHUNK)

    # float32 throughout; the second document's chunks fall elsewhere
    # alone, so its sums run in another order
    whole = scan(slice(0, 256), doc)
    for part in (slice(0, 150), slice(150, 256)):
        numpy.testing.assert_allclose(
            whole[:, part], scan(part, jnp.ones_like(doc[:, part])),
            rtol=1e-4, atol=1e-4)


#: (heads, head_dim, state, groups, chunk) -> whether the kernels take it
#: where the program is lowered for a TPU
SHAPES = [
    ((64, 64, 128, 8, 128), True),      # the Nemotron cell's mixers
    ((4, 64, 128, 2, 128), True),       # two heads of 64 a group
    ((8, 32, 128, 2, 128), True),       # four heads of 32 a group
    ((4, 128, 256, 4, 256), True),      # a head of 128 a group
    ((4, 8, 8, 2, 8), False),           # the tiny cell's
    ((4, 64, 64, 2, 128), False),       # a state of 64
    ((4, 64, 128, 2, 64), False),       # chunks of 64
    ((4, 32, 128, 2, 128), False),      # a group of 64 lanes
    ((4, 96, 128, 1, 128), False),      # heads of 96 straddle tiles
    ((2, 128, 512, 1, 128), False),     # x's lanes no multiple of the state
]


@pytest.mark.parametrize("shape,suits", SHAPES)
def test_the_kernels_suit_tiled_widths_lowered_for_a_tpu(shape, suits):
    with transformer.lowering_for("tpu"):
        assert transformer.ssd_kernel_suits(*shape) is suits
    assert not transformer.ssd_kernel_suits(*shape)      # the CPU
