"""A language model of shared and routed experts, balanced by a selection
bias, under gated, normed attention (one chip's share) through the fused
path, at toy widths.

The fused window against the plain reference ``benchmarks/reference/
afmoe_lm.py`` on seeded weights, for a share and for the whole model (loss,
logits, every leaf's first moment and change, the bias after the steps and
every step's load); eight shares adding up to the uncut layer with the
shared expert counted once; the bias in the choice and not in the weights;
the full layer with no position and the sliding layer's window; the norm of
queries and keys and the gate in the gradient; the bias through a snapshot
and a resumed window.

Tolerances: float32 on the CPU at ``highest`` matmul precision on both
sides (``tests/conftest.py``), so gaps are the order of float32 sums.
"""
import copy
import json
import os

import numpy
import pytest

import jax
import jax.numpy as jnp

from benchmarks import rehearse
from benchmarks.reference import afmoe_lm as ref
from znicz_tpu.ops import transformer
from znicz_tpu.parallel import fused
from znicz_tpu.samples.research import shared_moe_lm

TESTS = os.path.join(os.path.dirname(os.path.abspath(rehearse.__file__)),
                     "tests", "tiny_balanced")
CELL = {"name": "tiny_balanced_lm.train_s32_b2", "config": "tiny_balanced_lm",
        "traffic": "train_s32_b2", "chips": 1}
NUMBERS = ("loss_worst_step", "logit_rel_diff", "m1_worst_leaf",
           "dparam_worst_leaf", "route_flip_share", "flip_margin_p999",
           "choice_bias_tilt", "weight_share_gap", "bias_gap",
           "window_rows_gap", "window_tokens_gap", "window_load_gap",
           "hyper_feed_gap", "epoch_train_rows_gap", "epoch_valid_rows_gap",
           "epoch_train_tokens_gap", "epoch_valid_tokens_gap")


def _load(*parts):
    with open(os.path.join(TESTS, *parts)) as f:
        return json.load(f)


def _parts(held=None):
    """The fixture cell (a share: experts 2..5 of 8); with ``held`` the
    same model holding those experts."""
    cfg = _load("configs", CELL["config"] + ".json")
    if held is not None:
        cfg = copy.deepcopy(cfg)
        args = cfg["layers_from"]["args"]
        args[8], args[9] = held
        cfg["layers"] = json.loads(json.dumps(
            shared_moe_lm.make_layers(*args)))
    return (CELL, cfg, _load("traffic", CELL["traffic"] + ".json"),
            _load("limits", CELL["name"] + ".json"))


@pytest.fixture(scope="module", params=["share", "whole"])
def tiny_run(request):
    """The fixture cell end to end: StandardWorkflow, fused trainer,
    evaluator, decision; the reference follows the first epoch's four
    steps under the program's choice of experts."""
    from znicz_tpu.core import telemetry
    from znicz_tpu.core.config import root
    was = root.common.telemetry.get("enabled", False)
    loader_was = root.shared_moe_lm.loader_name
    telemetry.enable()
    telemetry.reset()
    try:
        correct, nums = rehearse.tiny_cell(
            *_parts(None if request.param == "share" else (0, 8)))
        gauges = {n: telemetry.gauge(n).value for n in (
            "moe.load_max", "moe.load_max_all", "moe.bias_abs_max")}
        gauges["rows"] = telemetry.counter("trainer.rows").value
    finally:
        root.common.telemetry.enabled = was
        root.shared_moe_lm.loader_name = loader_was
    return request.param, correct, {n: (v, lim) for n, v, lim in nums}, \
        gauges


@pytest.mark.parametrize("name", NUMBERS)
def test_fused_window_equals_the_plain_reference(tiny_run, name):
    """Loss of every step, sampled logits, every leaf's first moment and
    parameter change over four AdamW steps, the bias after them, under the
    program's choice; the choice is the reference's own from its own
    ``s + b``, every step's load of every expert its count and every
    expert's weight its sum; exact counts."""
    _, _, nums, _ = tiny_run
    value, limit = nums[name]
    assert numpy.isfinite(value) and value <= limit, (name, value, limit)


def test_cell_is_correct_and_the_gauges_read_the_bias_and_the_load(tiny_run):
    which, correct, _, gauges = tiny_run
    assert correct
    _, _, mix, _ = _parts()
    tokens = mix["minibatch"] * mix["seq_len"]
    # the most a held expert took in any step of the run, and the most
    # any of ALL the experts took in a step of the last epoch: no more
    # than every token of a step, and of the whole model the first is no
    # less than the second
    assert 0 < gauges["moe.load_max"] <= tokens
    assert 0 < gauges["moe.load_max_all"] <= tokens
    if which == "whole":
        assert gauges["moe.load_max"] >= gauges["moe.load_max_all"]
    # a bias is a sum of moves of the rate less their mean: after n steps
    # it lies within n rates, and it has moved
    steps = gauges["rows"] // mix["minibatch"]
    assert 0 < gauges["moe.bias_abs_max"] <= steps * 0.02 * (1 + 1e-6)


# -- the mechanism, on the spec stack itself ----------------------------------

DIM, EXPERTS, TOP_K, HIDDEN, SHARED = 64, 16, 4, 32, 48


def _moe_attrs(held, **more):
    return dict({"router": "r", "experts": EXPERTS, "top_k": TOP_K,
                 "held": list(held), "hidden": HIDDEN,
                 "shared_hidden": SHARED, "activation": "silu",
                 "score": "sigmoid", "route_scale": 2.5,
                 "balance_rate": 1e-3}, **more)


def _moe_spec(held, **more):
    return transformer.build("moe", _moe_attrs(held, **more), (32, DIM), {},
                             {}, {})


def _moe_layers(held):
    """A chain whose reference draws the same expert layer."""
    bwd = {"learning_rate": 1e-3, "solvers": ["adamw"]}
    return [{"type": "embedding", "->": {"vocab": 8, "dim": DIM},
             "<-": bwd},
            {"type": "moe", "->": _moe_attrs(held), "<-": bwd},
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


def _shared(p, x):
    return (jax.nn.silu(x @ p["sg"]) * (x @ p["su"])) @ p["sd"]


def test_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts of shares 0-7, each from its own draw of the two
    experts it holds, plus the shared expert counted ONCE, equal what the
    uncut reference's layer gives from ITS draw of all sixteen: every share
    draws the same shared expert and computes it alike, so seven of the
    eight copies are taken out."""
    rs = numpy.random.RandomState(4)
    y = jnp.asarray(rs.normal(0, 1, (2, 32, DIM)), jnp.float32)
    logits = jnp.asarray(rs.normal(0, 1, (64, EXPERTS)), jnp.float32)
    bias = rs.normal(0, 0.3, EXPERTS).astype(numpy.float32)
    whole = ref.init_params(_moe_layers((0, EXPERTS)), 11)[1]
    whole["sb"] = bias
    want, report = ref._moe(jax.tree.map(jnp.asarray, whole),
                            y.reshape(64, DIM), logits,
                            _moe_attrs((0, EXPERTS)), None, "f32", None)
    routed = 0.0
    for first in range(0, EXPERTS, 2):
        p = _drawn((first, 2))
        for name in ("wg", "wu", "wd"):  # the uncut layer's own experts
            numpy.testing.assert_array_equal(p[name],
                                             whole[name][first:first + 2])
        for name in ("sg", "su", "sd"):  # and its one shared expert
            numpy.testing.assert_array_equal(p[name], whole[name])
        assert not p["sb"].any() and p["sb"].shape == (EXPERTS,)
        p["sb"] = bias
        out, said = _moe_out(_moe_spec((first, 2)), p, y, logits)
        once = _shared(jax.tree.map(jnp.asarray, p), y.reshape(64, DIM))
        routed = routed + (out.reshape(64, DIM) - once)
        numpy.testing.assert_array_equal(said["load"], report["load"])
        numpy.testing.assert_array_equal(said["route"], report["route"])
        numpy.testing.assert_allclose(said["weight"], report["weight"],
                                      rtol=1e-5)
    numpy.testing.assert_allclose(routed + once, want, rtol=2e-5, atol=2e-6)
    assert int(report["load"].sum()) == 64 * TOP_K
    # a token none of whose experts is held still passes the shared expert
    far = _drawn((0, 2))
    far["sb"] = numpy.where(numpy.arange(EXPERTS) < 2, -10.0, 0.0).astype(
        numpy.float32)
    out, said = _moe_out(_moe_spec((0, 2)), far, y, logits)
    assert int(said["unserved"]) == 64
    numpy.testing.assert_allclose(out.reshape(64, DIM), _shared(
        jax.tree.map(jnp.asarray, far), y.reshape(64, DIM)), rtol=2e-5,
        atol=2e-6)


def test_the_bias_moves_the_choice_and_not_the_weights():
    rs = numpy.random.RandomState(5)
    logits = jnp.asarray(rs.normal(0, 1, (64, EXPERTS)), jnp.float32)
    scores = numpy.asarray(jax.nn.sigmoid(logits), numpy.float64)
    free, w_free = transformer.route(logits, TOP_K, "sigmoid", None, 2.5)
    bias = numpy.zeros(EXPERTS, numpy.float32)
    bias[[3, 9]] = 5.0      # past any sigmoid: every token takes 3 and 9
    chosen, w = transformer.route(logits, TOP_K, "sigmoid",
                                  jnp.asarray(bias), 2.5)
    chosen = numpy.asarray(chosen)
    assert (numpy.sort(chosen[:, :2], axis=1) == [3, 9]).all()
    assert not (numpy.asarray(free)[:, :2] == chosen[:, :2]).all()
    # the weights are the chosen scores over their sum, times the scale:
    # of the scores alone
    picked = numpy.take_along_axis(scores, chosen, axis=1)
    numpy.testing.assert_allclose(
        w, 2.5 * picked / picked.sum(axis=1, keepdims=True), rtol=1e-6)
    numpy.testing.assert_allclose(numpy.asarray(w).sum(axis=1), 2.5,
                                  rtol=1e-6)
    # no gradient reaches the bias, and the scores' gradient is the
    # reference's under the same choice
    g = jax.grad(lambda b: transformer.route(
        logits, TOP_K, "sigmoid", b, 2.5)[1].sum())(jnp.asarray(bias))
    assert not numpy.asarray(g).any()
    # ties go to the lower index
    tied, _ = transformer.route(jnp.zeros((3, EXPERTS)), TOP_K, "sigmoid",
                                jnp.zeros(EXPERTS), 1.0)
    assert (numpy.asarray(tied) == numpy.arange(TOP_K)).all()


def test_the_reference_refuses_weights_that_are_not_normalised():
    """The program divides the chosen sigmoids by their sum and has no
    switch for it; the published key ``route_norm`` stands in the
    configuration's file, and a layer list that says false is refused."""
    attrs = {"experts": EXPERTS, "top_k": TOP_K, "score": "sigmoid"}
    x = jnp.zeros((4, DIM), jnp.float32)
    logits = jnp.zeros((4, EXPERTS), jnp.float32)
    with pytest.raises(ValueError, match="normalised"):
        ref._moe({}, x, logits, dict(attrs, route_norm=False), None, "f32",
                 None)
    with pytest.raises(ValueError, match="sigmoid"):
        ref._moe({}, x, logits, dict(attrs, score="softmax"), None, "f32",
                 None)


def test_the_rule_moves_every_bias_toward_the_mean_load_and_keeps_the_sum():
    load = jnp.asarray([0, 10, 4, 4, 4, 2], jnp.int32)    # mean 4
    after = numpy.asarray(transformer.balance(
        jnp.zeros(6, jnp.float32), load, 0.5))
    # raw moves +.5 -.5 0 0 0 +.5, their mean 1/12 taken off
    numpy.testing.assert_allclose(
        after, numpy.array([.5, -.5, 0, 0, 0, .5]) - 0.5 / 6, rtol=1e-6)
    assert abs(after.sum()) < 1e-6
    want = ref.balance(numpy.zeros((1, 6), numpy.float32),
                       numpy.asarray(load)[None],
                       [{"type": "moe", "->": {"balance_rate": 0.5}}])
    numpy.testing.assert_allclose(after, want[0], rtol=1e-6)


def test_gradients_of_the_expert_layer_equal_the_references():
    """Every leaf's gradient, the stream's and the router logits', through
    the sigmoid, the normalised weights, the sort, the grouped products,
    the sum back and the shared expert; the bias's is nought."""
    rs = numpy.random.RandomState(6)
    y = jnp.asarray(rs.normal(0, 1, (1, 32, DIM)), jnp.float32)
    logits = jnp.asarray(rs.normal(0, 1, (32, EXPERTS)), jnp.float32)
    held = (2, 4)
    p = jax.tree.map(jnp.asarray, _drawn(held))
    p["sb"] = jnp.asarray(rs.normal(0, 0.3, EXPERTS), jnp.float32)
    weight = jnp.asarray(rs.normal(0, 1, (32, DIM)), jnp.float32)

    def mine(p, y, logits):
        return (_moe_out(_moe_spec(held), p, y, logits)[0].reshape(32, DIM)
                * weight).sum()

    def plain(p, y, logits):
        return (ref._moe(p, y.reshape(32, DIM), logits, _moe_attrs(held),
                         None, "f32", None)[0] * weight).sum()

    got = jax.grad(mine, argnums=(0, 1, 2))(p, y, logits)
    want = jax.grad(plain, argnums=(0, 1, 2))(p, y, logits)
    assert not numpy.asarray(got[0]["sb"]).any()
    assert float(jnp.abs(got[0]["sg"]).max()) > 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        numpy.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)


def _attention(rope, window, seed=7, seq=32, qk_norm=True, gate=True):
    spec = transformer.build(
        "attention", {"heads": 4, "kv_heads": 2, "head_dim": 16,
                      "rope": rope, "rope_base": 1e4, "window": window,
                      "qk_norm": qk_norm, "gate": gate, "eps": 1e-5,
                      "q_block": 8}, (seq, DIM), {}, {}, {})
    rs = numpy.random.RandomState(seed)
    p = {k: jnp.asarray(rs.normal(1.0 if k in ("gq", "gk") else 0.0, 0.2,
                                  v[0]), jnp.float32)
         for k, v in transformer.leaves(spec).items()}

    def run(y, seg, p=p):
        ctx = {"cd": None, "segments": jnp.asarray(seg), "train": True,
               "rope": {(16, 1e4): transformer.rope_tables(seq, 16, 1e4)}}
        return transformer.apply(spec, p, jnp.asarray(y), ctx)

    def plain(y, seg, fault=None, p=p):
        return ref._attention(p, jnp.asarray(y[0]), jnp.asarray(seg[0]),
                              spec.attrs, "f32", fault)[None]

    return run, plain, p


def test_full_layer_sees_no_position_and_sliding_one_no_key_past_its_window():
    rs = numpy.random.RandomState(9)
    doc = rs.normal(0, 1, (12, DIM)).astype(numpy.float32)
    other = rs.normal(0, 1, (20, DIM)).astype(numpy.float32)
    first = numpy.concatenate([doc, other])[None]
    later = numpy.concatenate([other[:5], doc, other[5:]])[None]
    seg_first = numpy.array([[1] * 12 + [2] * 20], numpy.int32)
    seg_later = numpy.array([[1] * 5 + [2] * 12 + [3] * 15], numpy.int32)
    full, plain, _ = _attention(False, None)
    # a document's outputs are the same wherever it lies in the row
    numpy.testing.assert_allclose(full(first, seg_first)[:, :12],
                                  full(later, seg_later)[:, 5:17],
                                  rtol=2e-5, atol=2e-6)
    numpy.testing.assert_allclose(full(first, seg_first),
                                  plain(first, seg_first), rtol=2e-5,
                                  atol=2e-6)
    # the planted fault turns the full layer's queries and keys
    rotary, _, _ = _attention(True, None)
    numpy.testing.assert_allclose(rotary(first, seg_first),
                                  plain(first, seg_first, "rope_on_full"),
                                  rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(rotary(first, seg_first)
                         - full(first, seg_first)).max()) > 1e-4
    # one document of 32 tokens under a window of 8: query i sees keys
    # i-7 .. i, so a change to key 10 reaches queries 10..17 and not 18
    sliding, plain_w, _ = _attention(True, 8)
    one = numpy.ones((1, 32), numpy.int32)
    y = rs.normal(0, 1, (1, 32, DIM)).astype(numpy.float32)
    moved = y.copy()
    moved[0, 10] += 1.0
    a, b = sliding(y, one), sliding(moved, one)
    numpy.testing.assert_allclose(a, plain_w(y, one), rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(a[0, 17] - b[0, 17]).max()) > 1e-4
    numpy.testing.assert_array_equal(a[0, 18:], b[0, 18:])
    numpy.testing.assert_array_equal(a[0, :10], b[0, :10])


@pytest.mark.parametrize("fault, leaves", [
    ("qk_norm_left_out", ("gq", "gk")), ("gate_left_out", ("wgate",))])
def test_the_norm_of_queries_and_keys_and_the_gate_are_in_the_gradient(
        fault, leaves):
    """Every leaf's gradient equals the reference's; the fault's leaves'
    gradients are not nought, the fault changes the output, and the layer
    built without the mechanism is the reference with the fault planted."""
    rs = numpy.random.RandomState(10)
    y = rs.normal(0, 1, (1, 32, DIM)).astype(numpy.float32)
    seg = numpy.array([[1] * 20 + [2] * 12], numpy.int32)
    run, plain, p = _attention(True, 8)
    weight = jnp.asarray(rs.normal(0, 1, (1, 32, DIM)), jnp.float32)
    got = jax.grad(lambda p: (run(y, seg, p) * weight).sum())(p)
    want = jax.grad(lambda p: (plain(y, seg, None, p) * weight).sum())(p)
    for name in p:
        numpy.testing.assert_allclose(got[name], want[name], rtol=2e-4,
                                      atol=2e-6, err_msg=name)
    for name in leaves:
        assert float(jnp.abs(got[name]).max()) > 1e-4, name
    without, _, _ = _attention(True, 8, qk_norm=fault != "qk_norm_left_out",
                               gate=fault != "gate_left_out")
    numpy.testing.assert_allclose(
        without(y, seg, {k: v for k, v in p.items() if k not in leaves}),
        plain(y, seg, fault), rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(run(y, seg) - plain(y, seg, fault)).max()) > 1e-3


def test_the_embeddings_multiplier_acts_on_its_output_alone():
    layers = shared_moe_lm.make_layers(n_layers=1, dense_layers=1)
    specs = fused.build_specs(layers, (32,))
    assert specs[0].attrs["scale"] == pytest.approx(32 ** 0.5)
    w = jnp.asarray(numpy.random.RandomState(1).normal(0, 1, (64, 32)),
                    jnp.float32)
    ids = jnp.asarray([[3, 5, 3]])
    out = transformer.apply(specs[0], {"w": w}, ids, {"cd": None})
    numpy.testing.assert_allclose(out, w[ids] * 32 ** 0.5, rtol=1e-6)
    plain = dict(specs[0].attrs, scale=None)
    spec = transformer.build("embedding", plain, (32,), {}, {}, {})
    numpy.testing.assert_array_equal(
        transformer.apply(spec, {"w": w}, ids, {"cd": None}), w[ids])


# -- the bias as a part of the training state ---------------------------------

def _net(seed=3, data_seed=3):
    from znicz_tpu.core import prng
    layers = shared_moe_lm.make_layers(n_layers=2, dense_layers=1,
                                       balance_rate=0.004)
    net = fused.FusedNet(layers, (32,), objective="tokens",
                         rand=prng.RandomGenerator().seed(seed))
    rs = numpy.random.RandomState(data_seed)
    ids = rs.randint(0, 64, (8, 32)).astype(numpy.int32)
    labels = numpy.roll(ids, -1, axis=1)
    labels[:, -1] = -1
    net.set_dataset(ids, labels, segments=numpy.ones((8, 32), numpy.int32))
    return net


def _window(net, rows):
    idx = numpy.asarray(rows, numpy.int32).reshape(2, 2)
    hy = jax.tree.map(lambda v: numpy.full((2,), v, numpy.float32),
                      net.hypers)
    return net.run_window_indexed(idx, numpy.array([2, 2], numpy.int32), hy)


def test_the_load_gauge_reads_the_newest_readback_not_the_runs_most():
    """``moe.load_max_all`` is what the bias rule acts on: it has to fall
    as the rule evens the load, so a readback sets it and the run's first
    steps (the initialisation's load) do not stay in it; ``moe.load_max``
    stays the run's most."""
    import types
    from znicz_tpu.core import telemetry
    from znicz_tpu.core.config import root
    from znicz_tpu.units.fused_trainer import FusedForwardBackward
    held = numpy.ones((1, 4), bool)
    unit = types.SimpleNamespace(net=types.SimpleNamespace(moe_held=held))

    def host(most):
        return {"n_err": numpy.asarray([0, 8, 1]), "loss_sum": 1.0,
                "moe_load": numpy.asarray([[most, 8 - most, 0, 0]]),
                "moe_unserved": numpy.zeros(1, numpy.int32),
                "moe_load_max": most, "moe_load_max_all": most,
                "moe_bias_abs_max": 0.001 * most}

    was = root.common.telemetry.get("enabled", False)
    telemetry.enable()
    telemetry.reset()
    try:
        for most in (7, 5):
            FusedForwardBackward._set_token_stats(unit, host(most), True)
        assert telemetry.gauge("moe.load_max_all").value == 5
        assert telemetry.gauge("moe.load_max").value == 7
        assert telemetry.gauge("moe.bias_abs_max").value == \
            pytest.approx(0.005)
    finally:
        root.common.telemetry.enabled = was


def test_the_bias_has_no_optimizer_state_and_survives_a_snapshot():
    """The bias rides in the parameters a window hands on: it has no state
    and no hyperparameters, moves by the rule alone, is in ``state_dict``,
    and a net resumed from that state runs the next window as the first
    net does, bias and load alike."""
    net = _net()
    moe = next(i for i, s in enumerate(net.specs) if s.kind == "moe")
    assert net.state[moe]["sb"] == {} and "sb" not in net.hypers[moe]
    assert set(net.state[moe]["wg"]) == {"m", "v", "t"}
    assert not numpy.asarray(net.params[moe]["sb"]).any()
    stats = _window(net, [0, 1, 2, 3])
    load = numpy.asarray(stats["moe_load"])         # (steps, entries, experts)
    assert load.shape == (2, 1, 8) and (load.sum(axis=2) == 128).all()
    # the reference's rule over the window's two loads, exactly
    want = numpy.zeros((1, 8), numpy.float32)
    layers = [{"type": "moe", "->": {"balance_rate": 0.004}}]
    for step in load:
        want = ref.balance(want, step, layers)
    numpy.testing.assert_allclose(net.params[moe]["sb"], want[0], rtol=1e-6,
                                  atol=1e-9)
    assert numpy.abs(want).max() > 0
    acc = net.window_acc_host()
    assert int(acc["moe_load_max_all"]) == int(load.max())
    assert float(acc["moe_bias_abs_max"]) == pytest.approx(
        numpy.abs(want).max(), rel=1e-6)
    saved = copy.deepcopy(net.state_dict())
    numpy.testing.assert_array_equal(saved["params"][moe]["sb"],
                                     numpy.asarray(net.params[moe]["sb"]))
    after = _window(net, [4, 5, 6, 7])
    resumed = _net(seed=4)      # other weights, until the state is loaded
    resumed.load_state_dict(saved)
    again = _window(resumed, [4, 5, 6, 7])
    numpy.testing.assert_array_equal(again["moe_load"], after["moe_load"])
    numpy.testing.assert_array_equal(again["loss"], after["loss"])
    numpy.testing.assert_array_equal(resumed.params[moe]["sb"],
                                     net.params[moe]["sb"])
    assert float(jnp.abs(resumed.params[moe]["sb"]
                         - saved["params"][moe]["sb"]).max()) > 0
