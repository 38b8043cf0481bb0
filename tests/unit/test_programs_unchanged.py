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
images.  A PR that changes one of these programs on purpose stamps it
again and says in ``CHANGES.md`` what moved it; jax itself moving does too.
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
STAMPS = {
    "tiny_looped_lm-float32": (427159, "701ebae8afea8769f7becdf82ee51a18"
                               "e794c25aef0754f41d70680277328a33"),
    "tiny_looped_lm-bfloat16": (443486, "68c1af3e57b7013c2a90eb98fd775454"
                                "c13aba670691563d2ae6fef0160b9a3a"),
    "alexnet_67-bfloat16": (41937088, "9e571feb18114a22bdc4df2f2706b667"
                            "1f7904fc38a61a03f1df7fbfa79f0f7d"),
}


def _layers(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)["layers"]


def _sds(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype),
        tree)


def window_text(which, dtype):
    """The lowered text of ``which`` net's train window of two steps over
    a resident set, its compute type ``dtype``."""
    k, batch, rows = 2, 2, 10
    cd = None if dtype == "float32" else jnp.dtype(dtype)
    if which == "tiny_looped_lm":
        seq = 24
        net = fused.FusedNet(
            _layers("tests", "tiny_lm", "configs", "tiny_looped_lm.json"),
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
