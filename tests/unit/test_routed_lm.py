"""A routed language model (a mixture of experts under window and global
attention, one chip's share) through the fused path, at toy widths.

The fused window against the plain reference ``benchmarks/reference/
routed_lm.py`` on seeded weights, for the whole model and for a share; the
shares adding up to the uncut layer; the window and the layer with no
position encoding; no pair dropped at the worst imbalance; the load and the
choice handed back; the forced reference against the free one; and the
looped model's tiny cell against the numbers of the commit before.

Tolerances: the program and the reference are float32 on the CPU at
``highest`` matmul precision (``tests/conftest.py``) and differ by the order
of float32 sums alone, so relative gaps sit at 1e-7..1e-6; the fixture's
limits leave two to three decades of room.
"""
import copy
import json
import os
import time

import numpy
import pytest

import jax
import jax.numpy as jnp

from benchmarks import rehearse
from benchmarks.reference import routed_lm as ref
from znicz_tpu.ops import transformer
from znicz_tpu.parallel import fused
from znicz_tpu.samples.research import routed_lm

TESTS = os.path.join(os.path.dirname(os.path.abspath(rehearse.__file__)),
                     "tests")
CELL = {"name": "tiny_routed_lm.train_s32_b2", "config": "tiny_routed_lm",
        "traffic": "train_s32_b2", "chips": 1}
NUMBERS = ("loss_worst_step", "logit_rel_diff", "m1_worst_leaf",
           "dparam_worst_leaf", "route_flip_share", "flip_margin_p999",
           "window_rows_gap", "window_tokens_gap",
           "window_load_gap", "hyper_feed_gap", "epoch_train_rows_gap",
           "epoch_valid_rows_gap", "epoch_train_tokens_gap",
           "epoch_valid_tokens_gap")


def _load(*parts):
    with open(os.path.join(TESTS, *parts)) as f:
        return json.load(f)


def _parts(held=None):
    """The fixture cell (a share: experts 2..5 of 8); with ``held`` the
    same model holding those experts."""
    cfg = _load("tiny_routed", "configs", CELL["config"] + ".json")
    if held is not None:
        cfg = copy.deepcopy(cfg)
        args = cfg["layers_from"]["args"]
        args[7], args[8] = held
        cfg["layers"] = json.loads(json.dumps(routed_lm.make_layers(*args)))
    return (CELL, cfg,
            _load("tiny_routed", "traffic", CELL["traffic"] + ".json"),
            _load("tiny_routed", "limits", CELL["name"] + ".json"))


#: the experts a run of the fixture cell holds: its own half, all eight,
#: a quarter
HELD = {"share": None, "whole": (0, 8), "quarter": (2, 2)}


@pytest.fixture(scope="module", params=list(HELD))
def tiny_run(request):
    """The fixture cell end to end: StandardWorkflow, fused trainer,
    evaluator, decision; the reference follows the first epoch's four
    steps under the program's choice of experts.  The ``moe`` entry's row
    movements take eight rows a turn, so that the step's 64 tokens and 128
    pairs are several turns."""
    from znicz_tpu.core import telemetry
    from znicz_tpu.core.config import root
    was = root.common.telemetry.get("enabled", False)
    loader_was = root.routed_lm.loader_name
    rows_was = transformer.MOVE_ROWS
    transformer.MOVE_ROWS = 8
    telemetry.enable()
    telemetry.reset()
    try:
        correct, nums = rehearse.tiny_cell(*_parts(HELD[request.param]))
        counters = {n: telemetry.counter(n).value for n in (
            "moe.pairs_held", "moe.tokens_unserved", "moe.rows_moved",
            "moe.rows_static", "trainer.rows", "trainer.readbacks")}
        counters["moe.load_max"] = telemetry.gauge("moe.load_max").value
    finally:
        transformer.MOVE_ROWS = rows_was
        root.common.telemetry.enabled = was
        root.routed_lm.loader_name = loader_was
    return request.param, correct, {n: (v, lim) for n, v, lim in nums}, \
        counters


@pytest.mark.parametrize("name", NUMBERS)
def test_fused_window_equals_the_plain_reference(tiny_run, name):
    """(a), (e): loss of every step, sampled logits, every leaf's first
    moment and parameter change over four AdamW steps, under the
    program's choice; the choice is the reference's own, and every step's
    load of every expert its count; exact counts."""
    _, _, nums, _ = tiny_run
    value, limit = nums[name]
    assert numpy.isfinite(value) and value <= limit, (name, value, limit)


def test_cell_is_correct_and_counters_count_pairs(tiny_run):
    which, correct, _, counters = tiny_run
    assert correct
    _, cfg, mix, _ = _parts()
    tokens = counters["trainer.rows"] * mix["seq_len"]
    entries, top_k = 4, 2
    assert counters["trainer.readbacks"] >= 2
    if which == "whole":
        # every pair of every token lies with an expert held here
        assert counters["moe.pairs_held"] == tokens * entries * top_k
        assert counters["moe.tokens_unserved"] == 0
    else:
        assert 0 < counters["moe.pairs_held"] < tokens * entries * top_k
        assert 0 < counters["moe.tokens_unserved"] < tokens * entries
    assert 0 < counters["moe.load_max"] <= \
        mix["minibatch"] * mix["seq_len"]


def test_the_rows_moved_follow_the_pairs_held(tiny_run):
    """``moe.rows_moved`` over ``moe.rows_static``: the rows the entries'
    two movements fetched forward over what movements over all ``tokens x
    top_k`` pairs fetch.  The pairs held each way, whole turns of eight
    rows, and every token's sum put back in token order: where every
    expert is held that is every pair once each way and the tokens' sums,
    ``1 + 1 / (2 top_k)``; under a share it follows the share."""
    which, _, _, counters = tiny_run
    _, _, mix, _ = _parts()
    tokens = counters["trainer.rows"] * mix["seq_len"]
    entries, top_k = 4, 2
    moved, static = counters["moe.rows_moved"], counters["moe.rows_static"]
    held = counters["moe.pairs_held"]
    assert static == 2 * tokens * entries * top_k
    if which == "whole":
        assert moved == static + tokens * entries
        return
    steps = counters["trainer.rows"] // mix["minibatch"]
    assert 2 * held + tokens * entries <= moved \
        <= 2 * held + tokens * entries + steps * entries * 8 * (1 + top_k)
    assert moved < static
    if which == "quarter":
        assert moved < 0.7 * static


# -- the mechanism, on the spec stack itself ----------------------------------

DIM, EXPERTS, TOP_K, HIDDEN = 64, 8, 2, 32


def _moe_spec(held):
    return transformer.build(
        "moe", {"router": "r", "experts": EXPERTS, "top_k": TOP_K,
                "held": list(held), "hidden": HIDDEN, "activation": "relu"},
        (32, DIM), {}, {}, {})


def _moe_layers(held):
    """A chain whose reference draws the same expert layer."""
    bwd = {"learning_rate": 1e-3, "solvers": ["adamw"]}
    return [{"type": "embedding", "->": {"vocab": 8, "dim": DIM},
             "<-": bwd},
            {"type": "moe", "->": dict(_moe_spec(held).attrs), "<-": bwd},
            {"type": "lm_head", "->": {"vocab": 8}, "<-": bwd}]


def _moe_out(spec, p, y, logits):
    ctx = {"cd": None, "side": {"r": logits}, "routed": {}, "node": 1}
    out = transformer.apply(spec, jax.tree.map(jnp.asarray, p), y, ctx)
    return out, ctx["routed"][1]


def _drawn(held, seed=11):
    """The program's own draw of a share's experts."""
    from znicz_tpu.core import prng
    rand = prng.RandomGenerator().seed(seed)
    specs = fused.build_specs(_moe_layers(held), (32,))
    return fused.init_params(specs, rand, numpy.float32)[1]


def test_the_shares_add_up_to_the_uncut_layer():
    """(b): the four shares' ``moe`` outputs, each from its own draw of the
    experts it holds, sum to what the uncut reference's layer gives from
    ITS draw of all eight; nothing is computed alike on every chip, so
    nothing is counted twice."""
    rs = numpy.random.RandomState(4)
    y = jnp.asarray(rs.normal(0, 1, (2, 32, DIM)), jnp.float32)
    logits = jnp.asarray(rs.normal(0, 1, (64, EXPERTS)), jnp.float32)
    whole = ref.init_params(_moe_layers((0, EXPERTS)), 11)[1]
    want, report = ref._moe(jax.tree.map(jnp.asarray, whole),
                            y.reshape(64, DIM), logits,
                            _moe_spec((0, EXPERTS)).attrs, None, "f32", None)
    total = 0.0
    for first in range(0, EXPERTS, 2):
        p = _drawn((first, 2))
        for name in p:      # a share holds the uncut layer's own experts
            numpy.testing.assert_array_equal(p[name],
                                             whole[name][first:first + 2])
        out, routed = _moe_out(_moe_spec((first, 2)), p, y, logits)
        total = total + out
        numpy.testing.assert_array_equal(routed["load"], report["load"])
        numpy.testing.assert_array_equal(routed["route"], report["route"])
    numpy.testing.assert_allclose(total.reshape(64, DIM), want, rtol=2e-5,
                                  atol=2e-7)
    assert int(report["load"].sum()) == 64 * TOP_K


def test_no_pair_is_dropped_at_the_worst_imbalance():
    """(d): a router that sends every token to the same two held experts:
    all 128 pairs fall into two groups, and the output is the
    reference's."""
    rs = numpy.random.RandomState(5)
    y = jnp.asarray(rs.normal(0, 1, (2, 32, DIM)), jnp.float32)
    logits = numpy.tile(rs.normal(0, 0.1, (1, EXPERTS)), (64, 1))
    logits[:, 3] += 5.0
    logits[:, 4] += 4.0
    logits = jnp.asarray(logits, jnp.float32)
    held = (2, 4)
    p = _drawn(held)
    out, routed = _moe_out(_moe_spec(held), p, y, logits)
    want, report = ref._moe(jax.tree.map(jnp.asarray, p), y.reshape(64, DIM),
                            logits, _moe_spec(held).attrs, None, "f32", None)
    assert [int(v) for v in routed["load"]] == [0, 0, 0, 64, 64, 0, 0, 0]
    assert int(routed["unserved"]) == 0
    numpy.testing.assert_allclose(out.reshape(64, DIM), want, rtol=2e-5,
                                  atol=2e-7)
    assert float(jnp.abs(want).max()) > 0


@pytest.mark.parametrize("experts, dtype", [(128, "int8"), (256, "int16")])
def test_the_choice_handed_back_names_every_expert(experts, dtype):
    """A router wider than a signed byte holds: the choice comes back in a
    type that names its last expert, from the program and the reference
    alike, and the reference reads it as a sound choice."""
    attrs = {"router": "r", "experts": experts, "top_k": TOP_K,
             "held": [experts - 2, 2], "hidden": HIDDEN,
             "activation": "relu"}
    spec = transformer.build("moe", attrs, (32, DIM), {}, {}, {})
    rs = numpy.random.RandomState(13)
    p = {k: jnp.asarray(rs.normal(0, 0.1, v[0]), jnp.float32)
         for k, v in transformer.leaves(spec).items()}
    y = jnp.asarray(rs.normal(0, 1, (1, 32, DIM)), jnp.float32)
    logits = rs.normal(0, 0.1, (32, experts))
    logits[:, experts - 1] += 5.0
    logits = jnp.asarray(logits, jnp.float32)
    out, routed = _moe_out(spec, p, y, logits)
    assert routed["route"].dtype == dtype
    assert int(routed["route"].max()) == experts - 1
    want, report = ref._moe(p, y.reshape(32, DIM), logits, attrs,
                            routed["route"], "f32", None)
    assert report["route"].dtype == dtype
    assert not bool(report["flipped"].any())
    assert bool(jnp.isfinite(report["margin"]).all())
    numpy.testing.assert_allclose(out.reshape(32, DIM), want, rtol=2e-5,
                                  atol=2e-7)


def test_gradients_of_the_expert_layer_equal_the_references():
    """(a): every leaf's gradient, the stream's and the router logits',
    through the sort, the grouped products and the sum back."""
    rs = numpy.random.RandomState(6)
    y = jnp.asarray(rs.normal(0, 1, (1, 32, DIM)), jnp.float32)
    logits = jnp.asarray(rs.normal(0, 1, (32, EXPERTS)), jnp.float32)
    held = (2, 4)
    p = jax.tree.map(jnp.asarray, _drawn(held))
    weight = jnp.asarray(rs.normal(0, 1, (32, DIM)), jnp.float32)

    def mine(p, y, logits):
        return (_moe_out(_moe_spec(held), p, y, logits)[0].reshape(32, DIM)
                * weight).sum()

    def plain(p, y, logits):
        return (ref._moe(p, y.reshape(32, DIM), logits,
                         _moe_spec(held).attrs, None, "f32", None)[0]
                * weight).sum()

    got = jax.grad(mine, argnums=(0, 1, 2))(p, y, logits)
    want = jax.grad(plain, argnums=(0, 1, 2))(p, y, logits)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        numpy.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)


def _attention(rope, window, seed=7, seq=32):
    spec = transformer.build(
        "attention", {"heads": 4, "kv_heads": 2, "head_dim": 16,
                      "rope": rope, "rope_base": 1.5e6, "window": window,
                      "q_block": 8}, (seq, DIM), {}, {}, {})
    rs = numpy.random.RandomState(seed)
    p = {k: jnp.asarray(rs.normal(0, 0.2, v[0]), jnp.float32)
         for k, v in transformer.leaves(spec).items()}

    def run(y, seg):
        ctx = {"cd": None, "segments": jnp.asarray(seg), "train": True,
               "rope": {(16, 1.5e6): transformer.rope_tables(seq, 16,
                                                              1.5e6)}}
        return transformer.apply(spec, p, jnp.asarray(y), ctx)

    def plain(y, seg, fault=None):
        return ref._attention(p, jnp.asarray(y[0]), jnp.asarray(seg[0]),
                              spec.attrs, "f32", fault)[None]

    return run, plain


def test_a_window_cuts_a_document_longer_than_itself():
    """(c): one document of 20 tokens under a window of 8: the blocked
    lowering equals the reference, the first 8 positions equal the
    unwindowed layer's and the later ones differ."""
    rs = numpy.random.RandomState(8)
    y = rs.normal(0, 1, (1, 32, DIM)).astype(numpy.float32)
    seg = numpy.ones((1, 32), numpy.int32)
    seg[:, 20:] = 2
    windowed, plain = _attention(True, 8)
    whole, _ = _attention(True, None)
    a, b = windowed(y, seg), whole(y, seg)
    numpy.testing.assert_allclose(a, plain(y, seg), rtol=2e-5, atol=2e-6)
    numpy.testing.assert_allclose(a[:, :8], b[:, :8], rtol=2e-5, atol=2e-6)
    numpy.testing.assert_allclose(a[:, 20:28], b[:, 20:28], rtol=2e-5,
                                  atol=2e-6)
    assert float(jnp.abs(a[:, 8:20] - b[:, 8:20]).max()) > 1e-3
    # the planted fault is the layer with its window left out
    numpy.testing.assert_allclose(b, plain(y, seg, "window_left_out"),
                                  rtol=2e-5, atol=2e-6)


def test_the_global_layer_sees_no_position():
    """(c): with no position encoding a document gives the same outputs
    wherever it lies in the row, and a token's output does not change when
    the tokens before it change places; a rotary layer's does."""
    rs = numpy.random.RandomState(9)
    doc = rs.normal(0, 1, (12, DIM)).astype(numpy.float32)
    other = rs.normal(0, 1, (20, DIM)).astype(numpy.float32)
    first = numpy.concatenate([doc, other])[None]
    later = numpy.concatenate([other[:5], doc, other[5:]])[None]
    seg_first = numpy.array([[1] * 12 + [2] * 20], numpy.int32)
    seg_later = numpy.array([[1] * 5 + [2] * 12 + [3] * 15], numpy.int32)
    nope, plain = _attention(False, None)
    numpy.testing.assert_allclose(nope(first, seg_first)[:, :12],
                                  nope(later, seg_later)[:, 5:17],
                                  rtol=2e-5, atol=2e-6)
    numpy.testing.assert_allclose(nope(first, seg_first),
                                  plain(first, seg_first), rtol=2e-5,
                                  atol=2e-6)
    swapped = first.copy()
    swapped[0, [2, 7]] = swapped[0, [7, 2]]
    rotary, _ = _attention(True, None)
    numpy.testing.assert_allclose(nope(first, seg_first)[:, 11],
                                  nope(swapped, seg_first)[:, 11],
                                  rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(rotary(first, seg_first)[:, 11]
                         - rotary(swapped, seg_first)[:, 11]).max()) > 1e-4
    # the planted fault turns the global layer's queries and keys
    numpy.testing.assert_allclose(rotary(first, seg_first),
                                  plain(first, seg_first, "rope_on_global"),
                                  rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("window", [100, None])
def test_the_tpu_kernels_lowering_equals_the_blocked_one(monkeypatch, window):
    """The splash-attention lowering, interpreted on the CPU, against the
    blocked ``jax.numpy`` one: rows of three of the kernel's blocks (at the
    chip's block size a toy row is one), seven query heads on each of two
    key-value heads, two documents a row, both longer than the window, so
    the local mask empties a block and cuts inside the others; outputs
    and the gradients of queries, keys and values."""
    monkeypatch.setattr(transformer, "SPLASH_BLOCK", 128)
    rs = numpy.random.RandomState(12)
    b, s, h, kv, hd = 2, 384, 14, 2, 128
    q, k, v = (jnp.asarray(rs.normal(0, 1, (b, s, n, hd)), jnp.float32)
               for n in (h, kv, kv))
    seg = numpy.ones((b, s), numpy.int32)
    seg[0, 150:], seg[1, 290:] = 2, 2
    seg = jnp.asarray(seg)

    def blocked(q, k, v):
        return transformer.attend(q, k, v, seg, 64, True, window)

    def splash(q, k, v):
        return transformer.attend_splash(q, k, v, seg, window,
                                         interpret=True)

    def grads(fn):
        return jax.grad(lambda *a: (fn(*a) ** 2).sum(), argnums=(0, 1, 2))(
            q, k, v)

    numpy.testing.assert_allclose(splash(q, k, v), blocked(q, k, v),
                                  rtol=2e-5, atol=2e-5)
    for got, want in zip(grads(splash), grads(blocked)):
        numpy.testing.assert_allclose(
            got, want, rtol=2e-5, atol=2e-5 * float(jnp.abs(want).max()))
    if window is not None:
        whole = transformer.attend(q, k, v, seg, 64, True, None)
        assert float(jnp.abs(splash(q, k, v) - whole).max()) > 1e-3


def test_forced_reference_equals_the_free_one_where_no_choice_differs():
    """(f): handed its own choice the reference gives the loss, the logits
    and every gradient it gives by itself, and reports no flip; handed
    another it reports the pairs and a margin, and a choice that is no
    ``top_k`` different experts reads infinite."""
    _, cfg, _, _ = _parts()
    layers = cfg["layers"]
    params = jax.tree.map(jnp.asarray, ref.init_params(layers, 3))
    rs = numpy.random.RandomState(10)
    ids = jnp.asarray(rs.randint(0, 96, 32), jnp.int32)
    seg = jnp.asarray([1] * 20 + [2] * 12, jnp.int32)
    lbl = jnp.asarray(numpy.where(numpy.arange(32) % 20 == 19, -1,
                                  numpy.roll(numpy.asarray(ids), -1)),
                      jnp.int32)
    pos = jnp.arange(0, 32, 4)

    def zeros():
        return jax.tree.map(jnp.zeros_like, params)

    free_g, free = ref.make_row(layers)(params, zeros(), ids, seg, lbl, pos)
    forced = ref.make_row(layers, forced=True)
    same_g, same = forced(params, zeros(), ids, seg, lbl, pos, free["route"])
    assert not bool(same["flipped"].any())
    assert float(same["margin"].max()) == 0.0
    for a, b in zip(jax.tree.leaves((free_g, free["loss_sum"],
                                     free["logits"], free["load"])),
                    jax.tree.leaves((same_g, same["loss_sum"],
                                     same["logits"], same["load"]))):
        numpy.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)
    # the first token of the first entry takes its third choice for its
    # second
    third = jax.lax.top_k(_router_logits(params, ids, layers), 3)[1][2]
    moved = numpy.array(free["route"])
    moved[0, 0, 1] = int(third)
    _, other = forced(params, zeros(), ids, seg, lbl, pos,
                      jnp.asarray(moved))
    assert int(other["flipped"].sum()) == 1 and bool(other["flipped"][0, 0])
    assert 0.0 < float(other["margin"][0, 0]) < 10.0
    assert float(other["loss_sum"]) != float(free["loss_sum"])
    twice = numpy.array(free["route"])
    twice[0, 0, 1] = twice[0, 0, 0]
    _, bad = forced(params, zeros(), ids, seg, lbl, pos, jnp.asarray(twice))
    assert numpy.isinf(float(bad["margin"][0, 0]))


def test_reference_by_blocks_equals_the_reference_whole(monkeypatch):
    """The reference's blocks of queries and of positions (what lets a row
    of 16,384 fit a chip) change no number beyond a sum's order."""
    _, cfg, _, _ = _parts()
    layers = cfg["layers"]
    params = jax.tree.map(jnp.asarray, ref.init_params(layers, 3))
    rs = numpy.random.RandomState(12)
    ids = jnp.asarray(rs.randint(0, 96, 32), jnp.int32)
    seg = jnp.asarray([1] * 20 + [2] * 12, jnp.int32)
    lbl = jnp.asarray(numpy.roll(numpy.asarray(ids), -1), jnp.int32)
    pos = jnp.arange(0, 32, 4)
    zeros = jax.tree.map(jnp.zeros_like, params)
    whole_g, whole = ref.make_row(layers)(params, zeros, ids, seg, lbl, pos)
    monkeypatch.setattr(ref, "SCORE_BLOCK", 8)
    monkeypatch.setattr(ref, "HEAD_BLOCK", 8)
    zeros = jax.tree.map(jnp.zeros_like, params)
    block_g, block = ref.make_row(layers)(params, zeros, ids, seg, lbl, pos)
    numpy.testing.assert_array_equal(whole["route"], block["route"])
    for a, b in zip(jax.tree.leaves((whole_g, whole["loss_sum"],
                                     whole["logits"])),
                    jax.tree.leaves((block_g, block["loss_sum"],
                                     block["logits"]))):
        numpy.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def _router_logits(params, ids, layers):
    """The first router's logits of the first token."""
    flat, _ = ref.flatten(layers)
    node = next(i for i, layer in enumerate(flat)
                if layer["type"] == "router")
    return params[0]["w"][ids[0]] @ params[node]["wr"]


def test_head_outside_a_loop_has_no_gate_and_a_looped_one_keeps_it():
    from znicz_tpu.samples.research import looped_lm
    plain = fused.build_specs(routed_lm.make_layers(), (32,))
    looped = fused.build_specs(looped_lm.make_layers(), (32,))
    assert sorted(transformer.leaves(plain[-1])) == ["g", "w"]
    assert sorted(transformer.leaves(looped[-1])) == ["be", "g", "w", "we"]


#: the tiny looped cell's first four losses, its windows' counts and four
#: leaves' norms as the commit before this kinds' arrival gave them
#: (0299137, float32 on the CPU under this harness's jax settings)
LOOPED_BEFORE = {
    "loss": [4.50830078125, 4.504592418670654, 4.499108791351318,
             4.493185043334961],
    "n_err": [[77, 77, 4], [80, 80, 4]],
    "m1": {"0.w": 0.1365555077791214, "2.wq": 0.0008319556945934892,
           "5.wg": 0.08163923025131226, "13.w": 0.09542452543973923},
    "dparam": {"0.w": 0.029391750693321228, "2.wq": 0.021781273186206818,
               "5.wg": 0.027538558468222618, "13.w": 0.040753915905952454}}


#: the tiny routed cell's, with the first step's load of the first entry's
#: experts, as the commit before the shared expert, the selection bias and
#: the gated, normed attention arrived gave them (cac7557, the same
#: settings)
ROUTED_BEFORE = {
    "loss": [4.574373722076416, 4.5342278480529785, 4.60994291305542,
             4.561324119567871],
    "n_err": [[111, 112, 4], [111, 112, 4]],
    "load": [10, 12, 15, 14, 16, 14, 17, 30],
    "m1": {"0.w": 0.14842073619365692, "1.wr": 1.1177894521097187e-05,
           "3.wq": 0.0011032650945708156, "5.wg": 0.004808289464563131,
           "21.w": 0.14340035617351532},
    "dparam": {"0.w": 0.04497426375746727, "1.wr": 0.01683368720114231,
               "3.wq": 0.0446460098028183, "5.wg": 0.06253328919410706,
               "21.w": 0.0551171712577343}}


@pytest.mark.parametrize("what, fixture, config, traffic, before", [
    ("looped_lm", "tiny_lm", "tiny_looped_lm", "train_s24_b2",
     LOOPED_BEFORE),
    ("routed_lm", "tiny_routed", "tiny_routed_lm", "train_s32_b2",
     ROUTED_BEFORE)], ids=["looped", "routed"])
def test_accepted_models_tiny_cells_give_the_numbers_they_gave_before(
        what, fixture, config, traffic, before):
    """(g): the looped and the routed model share the attention, the
    expert layer, the head, the walk of the chain, the optimizer's pass and
    the window with every model that came after them; their tiny cells'
    losses, counts, norms and load (the plain window, the sampled one and
    the validation pass all run) are those of the commit before."""
    from benchmarks.lib import job
    from znicz_tpu.core.config import root
    cell = {"name": "%s.%s" % (config, traffic), "config": config,
            "traffic": traffic, "chips": 1}
    cfg = _load(fixture, "configs", config + ".json")
    mix = rehearse.tiny_mix(_load(fixture, "traffic", traffic + ".json"))
    node = getattr(root, what)
    loader_was = node.loader_name
    try:
        run = job.run_cell(cell, cfg, mix, 2147483659, 0.5, False,
                           rehearse.ROOT, time.perf_counter(),
                           lambda msg: None)
    finally:
        node.loader_name = loader_was
    numpy.testing.assert_allclose(
        [v for w in run["windows"] for v in w["stats"]["loss"]],
        before["loss"], rtol=1e-6)
    assert [[int(v) for v in w["stats"]["n_err"]]
            for w in run["windows"]] == before["n_err"]
    if "load" in before:
        assert [int(v) for v in run["windows"][0]["stats"]["load"][0, 0]] \
            == before["load"]
    for leaves in ("m1", "dparam"):
        for leaf, want in before[leaves].items():
            assert run["program"][leaves][leaf] == pytest.approx(
                want, rel=1e-5), (leaves, leaf)
