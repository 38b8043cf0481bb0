"""The conv stage's Pallas kernels (``ops/pallas_conv.py``) against the
``jax.numpy`` form of ``transformer.causal_conv``, which
``tests/unit/test_ssd_moe_lm.py`` holds to the plain reference.

The kernels are built for the TPU's compiler; here they run interpreted
(``pltpu.force_tpu_interpret_mode()``, the test's choice), over blocks of
64 positions and 128 channels (the module's :data:`BLOCK`, cut in the test so
that a row of 256 spans four blocks of positions and the conv's 384
channels three of channels), reading the conv's channels at column 128 of
a wider projection.  Their compile for a described v5e is
``tests/unit/test_tpu_compile.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy
import pytest
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops import pallas_conv, transformer

LENGTH, FIRST, WIDTH, RIGHT, CONV = 256, 128, 384, 68, 4

#: (rows' document starts past the row's first position, one tuple a row)
LAYOUTS = {
    "one_document": ((),),
    "start_at_a_block": ((64,),),
    "starts_near_block_edges": ((61, 63, 66, 127, 130),),
    "starts_in_a_row": ((1, 2, 3, 5, 200),),
    "start_at_the_row_end": ((253, 255),),
    "several_rows": ((64,), (), (2, 190, 192)),
}


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(pallas_conv, "BLOCK", (64, 128))


def _inputs(starts, dtype, conv=CONV):
    """The projection ``zxd`` (the conv's channels at :data:`FIRST`), the
    conv's weight and bias, the documents and a cotangent."""
    r = numpy.random.RandomState(len(starts) + sum(map(sum, starts)))
    rows = len(starts)
    zxd = jnp.asarray(r.randn(rows, LENGTH, FIRST + WIDTH + RIGHT), dtype)
    w = jnp.asarray(r.uniform(-0.5, 0.5, (conv, WIDTH)), jnp.float32)
    b = jnp.asarray(r.uniform(-0.5, 0.5, WIDTH), jnp.float32)
    first = numpy.zeros((rows, LENGTH), bool)
    first[:, 0] = True
    for row, at in enumerate(starts):
        first[row, list(at)] = True
    doc = jnp.cumsum(jnp.asarray(first), axis=1, dtype=jnp.int32)
    dy = jnp.asarray(r.randn(rows, LENGTH, WIDTH), jnp.float32)
    return (zxd, w, b), doc, dy


def _stage(kernel, args, doc, dy):
    """The conv of ``zxd``'s channels as the kernels (interpreted) or the
    ``jax.numpy`` form, and the cotangents of ``zxd``, ``w`` and ``b``."""
    def conv(zxd, w, b):
        if kernel:
            return pallas_conv.causal_conv(zxd, w, b, doc, FIRST, WIDTH)
        return transformer.causal_conv(zxd[..., FIRST:FIRST + WIDTH], w, b,
                                       doc)

    with pltpu.force_tpu_interpret_mode():
        out, vjp = jax.vjp(conv, *args)
        return (out,) + vjp(dy)


def _rel(a, b):
    a, b = (numpy.asarray(v, numpy.float64) for v in (a, b))
    return float(numpy.linalg.norm(a - b) / numpy.linalg.norm(b))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_kernels_compute_the_jnp_form(layout):
    """In float32 the output, ``dx``, ``dw`` and ``db`` agree with the
    ``jax.numpy`` form's to rounding (the same terms in the same order
    forward; the backward's sums run in another), and ``dzxd`` is ``dx``
    at the conv's columns and 0 elsewhere."""
    args, doc, dy = _inputs(LAYOUTS[layout], jnp.float32)
    got = _stage(True, args, doc, dy)
    want = _stage(False, args, doc, dy)
    for name, a, b in zip(("out", "dzxd", "dw", "db"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < 1e-6, name
    dzxd = numpy.asarray(got[1])
    assert not dzxd[..., :FIRST].any() and not dzxd[..., FIRST + WIDTH:].any()


def test_a_conv_of_eight_lags():
    """The widest conv the kernels take (:data:`transformer.CONV_LAGS`
    lags, past a strip's edge and a block's), starts near the edges."""
    args, doc, dy = _inputs(LAYOUTS["starts_near_block_edges"], jnp.float32,
                            conv=transformer.CONV_LAGS + 1)
    got = _stage(True, args, doc, dy)
    want = _stage(False, args, doc, dy)
    for name, a, b in zip(("out", "dzxd", "dw", "db"), got, want):
        assert _rel(a, b) < 1e-6, name


def test_a_bf16_projection_gives_a_bf16_dx():
    """The projection in bf16, as on the chip: the output is still the
    float32 form's to rounding, and ``dx`` comes back in bf16 within a
    rounding of it."""
    args, doc, dy = _inputs(LAYOUTS["starts_near_block_edges"],
                            jnp.bfloat16)
    got = _stage(True, args, doc, dy)
    want = _stage(False, args, doc, dy)
    assert got[0].dtype == jnp.float32 and got[1].dtype == jnp.bfloat16
    assert _rel(got[0], want[0]) < 1e-6
    assert _rel(got[1], want[1]) < 4e-3
    for a, b in zip(got[2:], want[2:]):
        assert _rel(a, b) < 1e-6


#: (seq, first, width, conv) -> whether the kernels take it where the
#: program is lowered for a TPU
SHAPES = [
    ((8192, 4096, 6144, 4), True),      # the Nemotron cell's mixers
    ((1024, 256, 768, 4), True),        # the compiled window's
    ((256, 128, 384, 4), True),         # this file's
    ((16, 0, 128, 9), True),            # a conv of eight lags
    ((32, 32, 64, 4), False),           # the tiny cell's
    ((256, 64, 384, 4), False),         # channels off a tile's edge
    ((256, 128, 320, 4), False),        # channels no multiple of 128
    ((300, 128, 384, 4), False),        # a row no multiple of a halo
    ((256, 128, 384, 10), False),       # lags past a float32 tile
]


@pytest.mark.parametrize("shape,suits", SHAPES)
def test_the_kernels_suit_tiled_widths_lowered_for_a_tpu(shape, suits):
    with transformer.lowering_for("tpu"):
        assert transformer.conv_kernel_suits(*shape) is suits
    assert not transformer.conv_kernel_suits(*shape)      # the CPU
