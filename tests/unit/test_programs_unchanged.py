"""The window programs of the nets that a change was not to touch, held to
the text they lowered to before it.

A ``perf_opt`` change to one layer kind says of the cells without that kind
that "nothing may move"; on the chip their rates say so, within a bound.
Off the chip the statement is exact: the StableHLO text that a net's train
window lowers to on the CPU (shapes only, no weights, no source locations)
is the same character for character, so its sha-256 is.  Stamped by PR 36
from the commit before it (``2e9fc09``), where the ``moe`` entry's row
movements changed: the tiny looped token cell of ``benchmarks/tests/
tiny_lm`` (no ``moe`` entry) and ``alexnet``'s own layers over 67 x 67
images.  The tiny routed and balanced cells (``benchmarks/tests/
tiny_routed``, ``tiny_balanced``: the expert layer, its shared expert and
selection bias, gated and normed attention) were stamped from ``5181db8``,
the commit before latent attention and a second head arrived, and the tiny
state-space cell (``benchmarks/tests/tiny_ssd``: Mamba-2 mixers, attention
with no position, non-gated relu^2 experts) by the change that brought
it.  A PR that
changes one of these programs on purpose stamps it again and says in
``CHANGES.md`` what moved it; jax itself moving does too.
"""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

import benchmarks
from znicz_tpu.parallel import fused

BENCH = os.path.dirname(os.path.abspath(benchmarks.__file__))

#: program -> (characters, sha-256) of its lowered text at ``2e9fc09``
#: (``5181db8`` for the routed and balanced cells; the state-space cell's as
#: it came in)
STAMPS = {
    "tiny_looped_lm-float32": (427159, "701ebae8afea8769f7becdf82ee51a18"
                               "e794c25aef0754f41d70680277328a33"),
    "tiny_looped_lm-bfloat16": (443486, "68c1af3e57b7013c2a90eb98fd775454"
                                "c13aba670691563d2ae6fef0160b9a3a"),
    "alexnet_67-bfloat16": (41937088, "9e571feb18114a22bdc4df2f2706b667"
                            "1f7904fc38a61a03f1df7fbfa79f0f7d"),
    "tiny_routed_lm-float32": (911956, "40e9dd005d8b4ce900610658e190d1e6"
                               "903ffc78319c7e3f922950d26358990d"),
    "tiny_routed_lm-bfloat16": (940374, "de2f681867c9bee2da994f88145f37b4"
                                "53c30a2bc53281dab63d36395ca1de15"),
    "tiny_balanced_lm-float32": (1466191, "e2dcc496e70be19ba935f1b565fa906d"
                                 "4ff2f5bbd82c568551cb9c4aecca2ffd"),
    "tiny_balanced_lm-bfloat16": (1520289, "1c9c25ad40ddcdc8698800a7d8f345f9"
                                  "08b6aed0ed33206240095087fd04118a"),
    "tiny_ssd_lm-float32": (767126, "571b2e3985a2b35cfdaf63a2b6ba82b8"
                            "c935d5516e920a8f3c0858a3a74dbb85"),
    "tiny_ssd_lm-bfloat16": (792026, "e52876459693396d50ba62358abcdb71"
                             "76ea1611055710a40dceae49407bac05"),
}


def _layers(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)["layers"]


def _sds(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype),
        tree)


#: a tiny token cell's net -> (its fixture under ``benchmarks/tests``, its
#: rows' length)
TOKEN_NETS = {"tiny_looped_lm": ("tiny_lm", 24),
              "tiny_routed_lm": ("tiny_routed", 32),
              "tiny_balanced_lm": ("tiny_balanced", 32),
              "tiny_ssd_lm": ("tiny_ssd", 32)}


def window_text(which, dtype):
    """The lowered text of ``which`` net's train window of two steps over
    a resident set, its compute type ``dtype``."""
    k, batch, rows = 2, 2, 10
    cd = None if dtype == "float32" else jnp.dtype(dtype)
    if which in TOKEN_NETS:
        fixture, seq = TOKEN_NETS[which]
        net = fused.FusedNet(
            _layers("tests", fixture, "configs", which + ".json"),
            (seq,), compute_dtype=cd, objective="tokens")
        data = jax.ShapeDtypeStruct((rows, seq), jnp.int32)
        labels = (data, data)
    else:
        net = fused.FusedNet(_layers("configs", "alexnet.json"),
                             (67, 67, 3), compute_dtype=cd)
        data = jax.ShapeDtypeStruct((rows, 67, 67, 3), jnp.bfloat16)
        labels = jax.ShapeDtypeStruct((rows,), jnp.int32)
    hy = jax.tree.map(lambda v: jax.ShapeDtypeStruct((k,), jnp.float32),
                      fused.default_hypers(net.specs))
    return net._get_window_fn(k, "indexed").lower(
        _sds(net.params), _sds(net.state),
        jax.ShapeDtypeStruct((2,), jnp.uint32), data, labels,
        jax.ShapeDtypeStruct((k, batch), jnp.int32), None,
        jax.ShapeDtypeStruct((k,), jnp.int32), hy,
        _sds(net.window_acc_zeros())).as_text()


def stamp(name):
    which, dtype = name.rsplit("-", 1)
    text = window_text(which, dtype)
    return len(text), hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("name", sorted(STAMPS))
def test_a_net_without_the_changed_kind_lowers_to_the_text_it_did(name):
    assert stamp(name) == STAMPS[name]
