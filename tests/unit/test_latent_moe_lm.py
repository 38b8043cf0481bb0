"""A language model of latent attention and shared and routed experts with a
multi-token prediction module (one chip's share) through the fused path, at
toy widths.

The fused window against the plain reference ``benchmarks/reference/
mla_mtp_lm.py`` on seeded weights (both heads' losses and logits, every
leaf's first moment and change, the bias after the steps, every step's load,
exact counts), and one step's gradient of every leaf, the embedding and the
output weight taking both heads'; eight shares adding up to the uncut
expert layer with the shared expert counted once; rotary on the rope part
alone; the second head graded two ids ahead inside documents; the value
width refused; the kernel's lowering against the blocked one; a snapshot
and a resumed window.

Tolerances: float32 on the CPU at ``highest`` matmul precision on both
sides (``tests/conftest.py``), so gaps are the order of float32 sums over
the widths (a few 1e-7 relative; the limits below give them two orders of
room); a product in bf16 moves a logit by 1e-3 relative and fails every
one of them.
"""
import copy
import json
import os

import numpy
import pytest

import jax
import jax.numpy as jnp

from benchmarks import rehearse
from benchmarks.reference import mla_mtp_lm as ref
from znicz_tpu.ops import transformer
from znicz_tpu.parallel import fused
from znicz_tpu.samples.research import latent_moe_lm

TESTS = os.path.join(os.path.dirname(os.path.abspath(rehearse.__file__)),
                     "tests", "tiny_latent")
CELL = {"name": "tiny_latent_lm.train_s32_b2", "config": "tiny_latent_lm",
        "traffic": "train_s32_b2", "chips": 1}
NUMBERS = ("loss_worst_step", "logit_rel_diff", "m1_worst_leaf",
           "dparam_worst_leaf", "route_flip_share", "flip_margin_p999",
           "choice_bias_tilt", "weight_share_gap", "bias_gap",
           "mtp_loss_worst_step", "mtp_logit_rel_diff", "window_rows_gap",
           "window_tokens_gap", "window_load_gap", "hyper_feed_gap",
           "epoch_train_rows_gap", "epoch_valid_rows_gap",
           "epoch_train_tokens_gap", "epoch_valid_tokens_gap",
           "window_mtp_tokens_gap", "epoch_train_mtp_tokens_gap",
           "epoch_valid_mtp_tokens_gap")


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
        names = cfg["layers_from"]["arg_names"]
        args[names.index("held_first")], args[names.index("held_count")] = \
            held
        cfg["layers"] = json.loads(json.dumps(
            latent_moe_lm.make_layers(*args)))
    return (CELL, cfg, _load("traffic", CELL["traffic"] + ".json"),
            _load("limits", CELL["name"] + ".json"))


@pytest.fixture(scope="module", params=["share", "whole"])
def latent_run(request):
    """The fixture cell end to end: StandardWorkflow, fused trainer,
    evaluator, decision; the reference follows the first epoch's four
    steps under the program's choice of experts."""
    from znicz_tpu.core import telemetry
    from znicz_tpu.core.config import root
    was = root.common.telemetry.get("enabled", False)
    loader_was = root.latent_moe_lm.loader_name
    telemetry.enable()
    telemetry.reset()
    try:
        correct, nums = rehearse.tiny_cell(
            *_parts(None if request.param == "share" else (0, 8)))
        counters = {n: telemetry.counter(n).value for n in (
            "mtp.graded_tokens", "mtp.errors", "trainer.graded_tokens",
            "attention.forwards_kept")}
    finally:
        root.common.telemetry.enabled = was
        root.latent_moe_lm.loader_name = loader_was
    return correct, {n: (v, lim) for n, v, lim in nums}, counters


@pytest.mark.parametrize("name", NUMBERS)
def test_latent_window_equals_the_plain_reference(latent_run, name):
    """Both heads' loss of every step and logits at the sampled positions,
    every leaf's first moment and parameter change over four AdamW steps,
    the bias after them, under the program's choice; every step's load and
    the graded tokens of both heads exactly."""
    _, nums, _ = latent_run
    value, limit = nums[name]
    assert numpy.isfinite(value) and value <= limit, (name, value, limit)


def test_latent_cell_is_correct_and_counts_the_second_head(latent_run):
    correct, _, counters = latent_run
    assert correct
    # the second head grades fewer tokens than the first (two ids ahead
    # inside a document), and no attention runs the kernel on the CPU
    assert 0 < counters["mtp.graded_tokens"] < \
        counters["trainer.graded_tokens"]
    assert 0 <= counters["mtp.errors"] <= counters["mtp.graded_tokens"]
    assert counters["attention.forwards_kept"] == 0


# -- one step's gradient, leaf by leaf ----------------------------------------

def _small(**more):
    kw = dict(vocab=48, dim=32, heads=2, q_rank=16, kv_rank=12, nope_dim=8,
              rope_dim=8, v_dim=16, dense_hidden=40, experts=8, top_k=2,
              held_first=0, held_count=8, hidden=16, shared_hidden=16,
              n_layers=2, dense_layers=1, route_scale=1.8,
              balance_rate=1e-3, mtp_weight=0.3, q_block=8, token_block=16)
    kw.update(more)
    return latent_moe_lm.make_layers(**kw)


def _rows(seq=32, rows=2, seed=5):
    rs = numpy.random.RandomState(seed)
    ids = rs.randint(0, 48, (rows, seq)).astype(numpy.int32)
    seg = numpy.ones((rows, seq), numpy.int32)
    seg[0, 11:], seg[1, 20:] = 2, 2
    seg[0, 25:] = 3
    labels = numpy.full((rows, seq), -1, numpy.int32)
    labels[:, :-1] = numpy.where(seg[:, 1:] == seg[:, :-1], ids[:, 1:], -1)
    return ids, seg, labels


def _program_grads(layers, ids, seg, labels, seed=3):
    specs = fused.build_specs(layers, (ids.shape[1],))
    _, topology = fused.flatten_layers(layers)
    params = jax.tree.map(jnp.asarray, fused.init_params(
        specs, _rand(seed), numpy.float32))

    def objective(p):
        emit = fused.forward_tokens(p, jnp.asarray(ids), jnp.asarray(seg),
                                    jnp.asarray(labels), specs, topology,
                                    train=True)
        loss, stats, _ = fused._token_stats(emit, jnp.asarray(labels), specs)
        return loss, stats

    return specs, params, jax.value_and_grad(objective, has_aux=True)(params)


def _rand(seed):
    from znicz_tpu.core import prng
    return prng.RandomGenerator().seed(seed)


def _reference_grads(layers, ids, seg, labels, params, fault=None):
    """The reference's gradient of the objective over the rows, from the
    program's own initial weights (the reference draws the same)."""
    _, _, weight, shift = ref.heads(layers)
    graded = int((labels >= 0).sum())
    graded2 = int(sum((numpy.asarray(ref.shifted(jnp.asarray(r), shift))
                       >= 0).sum() for r in labels))
    row = ref.make_row(layers, "f32", fault)
    total = jax.tree.map(jnp.zeros_like, params)
    scale = jnp.asarray([1.0 / graded, weight / graded2], jnp.float32)
    sums = numpy.zeros(2)
    for r in range(ids.shape[0]):
        total, aux = row(params, total, jnp.asarray(ids[r]),
                         jnp.asarray(seg[r]), jnp.asarray(labels[r]),
                         jnp.zeros(1, jnp.int32), scale)
        sums += (float(aux["loss_sum"]), float(aux["mtp_loss_sum"]))
    return total, sums[0] / graded + weight * sums[1] / graded2, graded2


def test_every_leafs_gradient_equals_the_references_and_ties_take_both():
    """One step of the objective over two rows of three documents: every
    leaf's gradient (the bias's is nought), the loss, and the second head's
    graded count; the embedding and the output weight, each one leaf, take
    both heads' gradients (without the second head's they differ)."""
    layers = _small()
    ids, seg, labels = _rows()
    specs, params, ((loss, stats), got) = _program_grads(
        layers, ids, seg, labels)
    init = ref.init_params(layers, 0)
    assert [sorted(p) for p in init] == [sorted(p) for p in params]
    want, want_loss, graded2 = _reference_grads(layers, ids, seg, labels,
                                                params)
    assert int(stats["mtp_n_err"][1]) == graded2
    assert float(loss) == pytest.approx(want_loss, rel=1e-5)
    for i, (a, b) in enumerate(zip(got, want)):
        for name in a:
            numpy.testing.assert_allclose(a[name], b[name], rtol=2e-4,
                                          atol=1e-7, err_msg="%d.%s"
                                          % (i, name))
    main, second, _, _ = ref.heads(layers)
    assert "w" not in params[second] and "w" in params[main]
    apart, _, _ = _reference_grads(layers, ids, seg, labels, params,
                                   "mtp_head_apart")
    for i, name in ((0, "w"), (main, "w")):
        gap = float(jnp.abs(want[i][name] - apart[i][name]).max())
        assert gap > 1e-3 * float(jnp.abs(want[i][name]).max()), (i, name)
    moe = [i for i, s in enumerate(specs) if s.kind == "moe"]
    assert len(moe) == 2 and not any(
        numpy.asarray(got[i]["sb"]).any() for i in moe)


# -- the expert layer's shares ------------------------------------------------

DIM, EXPERTS, TOP_K, HIDDEN, SHARED = 32, 16, 4, 24, 40


def _moe_attrs(held):
    return {"router": "r", "experts": EXPERTS, "top_k": TOP_K,
            "held": list(held), "hidden": HIDDEN, "shared_hidden": SHARED,
            "activation": "silu", "score": "sigmoid", "route_scale": 1.8,
            "balance_rate": 1e-3}


def _moe_layers(held):
    bwd = {"learning_rate": 1e-3, "solvers": ["adamw"]}
    return [{"type": "embedding", "name": "embed",
             "->": {"vocab": 8, "dim": DIM}, "<-": bwd},
            {"type": "moe", "->": _moe_attrs(held), "<-": bwd},
            {"type": "lm_head", "name": "head", "->": {"vocab": 8},
             "<-": bwd},
            {"type": "mtp_input", "->": {"tied": "embed"}, "<-": bwd},
            {"type": "lm_head", "->": {"vocab": 8, "tied": "head",
                                       "shift": 2}, "<-": bwd}]


def test_eight_shares_of_the_expert_layer_add_up_to_the_uncut_one():
    """Top-4 of 16 by sigmoid scores plus a bias, scale 1.8: the routed
    parts of shares of two experts each, every one drawn by the program as
    it draws its share, plus the shared expert counted ONCE, equal what the
    uncut reference's layer gives from its own draw of all sixteen."""
    rs = numpy.random.RandomState(4)
    y = jnp.asarray(rs.normal(0, 1, (64, DIM)), jnp.float32)
    logits = jnp.asarray(rs.normal(0, 1, (64, EXPERTS)), jnp.float32)
    bias = rs.normal(0, 0.3, EXPERTS).astype(numpy.float32)
    whole = ref.init_params(_moe_layers((0, EXPERTS)), 11)[1]
    whole["sb"] = bias
    want, report = ref._moe(jax.tree.map(jnp.asarray, whole), y, logits,
                            _moe_attrs((0, EXPERTS)), None, "f32", None)
    routed, once = 0.0, None
    for first in range(0, EXPERTS, 2):
        specs = fused.build_specs(_moe_layers((first, 2)), (64,))
        p = fused.init_params(specs, _rand(11), numpy.float32)[1]
        for name in ("wg", "wu", "wd"):
            numpy.testing.assert_array_equal(p[name],
                                             whole[name][first:first + 2])
        for name in ("sg", "su", "sd"):
            numpy.testing.assert_array_equal(p[name], whole[name])
        p = dict(jax.tree.map(jnp.asarray, p), sb=jnp.asarray(bias))
        ctx = {"cd": None, "side": {"r": logits}, "routed": {}, "node": 1}
        out = transformer.apply(specs[1], p, y[None], ctx)[0]
        once = ref._gated(y, p["sg"], p["su"], p["sd"], jax.nn.silu, "f32")
        routed = routed + (out - once)
        numpy.testing.assert_array_equal(ctx["routed"][1]["load"],
                                         report["load"])
    numpy.testing.assert_allclose(routed + once, want, rtol=2e-5, atol=2e-6)
    assert int(report["load"].sum()) == 64 * TOP_K


# -- latent attention ---------------------------------------------------------

def _latent(seq=32, seed=7, **more):
    a = dict({"heads": 2, "q_rank": 16, "kv_rank": 12, "nope_dim": 8,
              "rope_dim": 8, "v_dim": 16, "rope_base": 1e4, "eps": 1e-5,
              "q_block": 8}, **more)
    spec = transformer.build("latent_attention", a, (seq, DIM), {}, {}, {})
    rs = numpy.random.RandomState(seed)
    p = {k: jnp.asarray(rs.normal(1.0 if k.startswith("g") else 0.0, 0.3,
                                  v[0]), jnp.float32)
         for k, v in transformer.leaves(spec).items()}
    key = transformer.turns(spec)

    def run(y, seg, p=p, still=False):
        # ``still``: tables that turn nothing (cos 1, sin 0)
        cos, sin = transformer.rope_tables(seq, *key)
        if still:
            cos, sin = jnp.ones_like(cos), jnp.zeros_like(sin)
        ctx = {"cd": None, "segments": jnp.asarray(seg), "train": True,
               "rope": {key: (cos, sin)}, "routed": {}, "node": 2}
        return transformer.apply(spec, p, jnp.asarray(y), ctx)

    def plain(y, seg, fault=None, p=p):
        return ref._latent_attention(p, jnp.asarray(y[0]),
                                     jnp.asarray(seg[0]), spec.attrs, "f32",
                                     fault)[None]

    return spec, p, run, plain


def test_rotary_turns_the_rope_part_alone():
    """The program equals the reference and not the reference that turns
    every head whole; with the rope parts' weights at nought (the queries'
    last 8 of every head's 16, the keys' last 8 of the down projection) the
    tables change nothing, so nothing else is turned, and with them they
    do; a document's outputs are the same wherever it lies in the row."""
    assert transformer.turns(_latent()[0]) == (8, 1e4)
    rs = numpy.random.RandomState(9)
    doc = rs.normal(0, 1, (12, DIM)).astype(numpy.float32)
    other = rs.normal(0, 1, (20, DIM)).astype(numpy.float32)
    first = numpy.concatenate([doc, other])[None]
    later = numpy.concatenate([other[:5], doc, other[5:]])[None]
    seg_first = numpy.array([[1] * 12 + [2] * 20], numpy.int32)
    seg_later = numpy.array([[1] * 5 + [2] * 12 + [3] * 15], numpy.int32)
    _, p, run, plain = _latent()
    numpy.testing.assert_allclose(run(first, seg_first),
                                  plain(first, seg_first), rtol=2e-5,
                                  atol=2e-6)
    assert float(jnp.abs(run(first, seg_first) - plain(
        first, seg_first, "rope_whole_head")).max()) > 1e-3
    numpy.testing.assert_allclose(run(first, seg_first)[:, :12],
                                  run(later, seg_later)[:, 5:17],
                                  rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(run(first, seg_first) - run(
        first, seg_first, still=True)).max()) > 1e-3
    flat = dict(p)
    cols = numpy.zeros((2, 16), bool)
    cols[:, 8:] = True
    flat["wqb"] = p["wqb"] * jnp.asarray(~cols.reshape(-1))
    flat["wkva"] = p["wkva"].at[:, 12:].set(0.0)
    numpy.testing.assert_allclose(run(first, seg_first, flat),
                                  run(first, seg_first, flat, still=True),
                                  rtol=2e-5, atol=2e-6)


def test_the_kv_norm_is_in_the_output_and_the_gradient():
    rs = numpy.random.RandomState(10)
    y = rs.normal(0, 1, (1, 32, DIM)).astype(numpy.float32)
    seg = numpy.array([[1] * 20 + [2] * 12], numpy.int32)
    _, p, run, plain = _latent()
    weight = jnp.asarray(rs.normal(0, 1, (1, 32, DIM)), jnp.float32)
    got = jax.grad(lambda p: (run(y, seg, p) * weight).sum())(p)
    want = jax.grad(lambda p: (plain(y, seg, None, p) * weight).sum())(p)
    for name in p:
        numpy.testing.assert_allclose(got[name], want[name], rtol=2e-4,
                                      atol=2e-6, err_msg=name)
    assert float(jnp.abs(got["gkv"]).max()) > 1e-4
    assert float(jnp.abs(run(y, seg) - plain(
        y, seg, "kv_norm_left_out")).max()) > 1e-3


def test_a_value_width_other_than_the_heads_is_refused():
    with pytest.raises(ValueError, match="v_dim 24 != nope_dim 8 \\+ "
                       "rope_dim 8"):
        _latent(v_dim=24)


def test_latent_attention_through_the_kernel_equals_the_blocked_lowering(
        monkeypatch):
    """Heads of 256 (192 + 64, values 256) over rows of three of the
    kernel's blocks, two documents a row: the splash kernel, interpreted,
    against the blocked lowering, outputs and every leaf's gradient; the
    application reports its blocks and whether it is kept."""
    monkeypatch.setattr(transformer, "SPLASH_BLOCK", 256)
    assert transformer.splash_block(384, 256) == 128
    seq = 384
    spec, p, run, _ = _latent(seq=seq, nope_dim=192, rope_dim=64, v_dim=256,
                              heads=2)
    rs = numpy.random.RandomState(12)
    y = jnp.asarray(rs.normal(0, 1, (2, seq, DIM)), jnp.float32)
    seg = numpy.ones((2, seq), numpy.int32)
    seg[0, 150:], seg[1, 290:] = 2, 2
    key = transformer.turns(spec)
    block = transformer.splash_block(seq, 256)

    def kernel(p):
        ctx = {"cd": None, "segments": jnp.asarray(seg), "train": True,
               "rope": {key: transformer.rope_tables(seq, *key)},
               "routed": {}, "node": 2,
               "block_maps": {(None, block): transformer.block_maps(
                   jnp.asarray(seg), None, 1, block)}}
        return transformer.apply(spec, p, y, ctx), ctx["routed"]

    real = transformer._splash_kernel
    monkeypatch.setattr(transformer, "_splash_kernel",
                        lambda s, window, rep, interpret=False, block=None:
                        real(s, window, rep, True, block))
    with jax.enable_x64(False):
        monkeypatch.setattr(transformer, "kernel_suits", lambda s, hd: True)
        out, routed = kernel(p)
        g_kernel = jax.grad(lambda p: (kernel(p)[0] ** 2).sum())(p)
        monkeypatch.setattr(transformer, "kernel_suits",
                            lambda s, hd: False)
        want = run(y, seg)
        g_want = jax.grad(lambda p: (run(y, seg, p) ** 2).sum())(p)
    numpy.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    for name in p:
        numpy.testing.assert_allclose(g_kernel[name], g_want[name],
                                      rtol=2e-3, atol=2e-4, err_msg=name)
    visited, static = (int(n) for n in routed[2]["blocks"])
    assert 0 < visited < static and int(routed[2]["kept"]) == 0


# -- the second head ----------------------------------------------------------

def test_the_second_head_grades_two_ids_ahead_inside_documents():
    ids = jnp.arange(10, dtype=jnp.int32)[None] + 100
    seg = jnp.asarray([[1, 1, 1, 1, 2, 2, 3, 3, 3, 3]])
    labels = jnp.where(seg[:, 1:] == seg[:, :-1], ids[:, 1:], -1)
    labels = jnp.concatenate([labels, jnp.full((1, 1), -1)], axis=1)
    got = numpy.asarray(transformer.shifted_labels(labels, 2))[0]
    # 0..3 one document, 4..5 a second, 6..9 a third
    assert got.tolist() == [102, 103, -1, -1, -1, -1, 108, 109, -1, -1]
    assert (numpy.asarray(transformer.shifted_labels(labels, 1))
            == numpy.asarray(labels)).all()
    numpy.testing.assert_array_equal(
        numpy.asarray(ref.shifted(labels[0], 2)), got)


def test_a_chain_takes_one_head_at_each_shift():
    with pytest.raises(ValueError, match="at most one more"):
        fused.FusedNet(_small()[:-1] + [_small()[-1], _small()[-1]], (16,),
                       objective="tokens")
    layers = _small()
    specs = fused.build_specs(layers, (16,))
    heads = [s for s in specs if s.kind == "lm_head"]
    assert [int(s.attrs.get("shift", 1)) for s in heads] == [1, 2]
    assert set(transformer.leaves(heads[1])) == {"g"}


# -- the training state -------------------------------------------------------

def _net(seed=3):
    net = fused.FusedNet(_small(held_first=2, held_count=4,
                                balance_rate=0.004), (32,),
                         objective="tokens", rand=_rand(seed))
    ids, seg, labels = _rows(rows=2)
    rs = numpy.random.RandomState(8)
    more = rs.randint(0, 48, (6, 32)).astype(numpy.int32)
    ids = numpy.concatenate([ids, more])
    seg = numpy.concatenate([seg, numpy.ones((6, 32), numpy.int32)])
    labels = numpy.concatenate([labels, numpy.roll(more, -1, axis=1)])
    labels[2:, -1] = -1
    net.set_dataset(ids, labels, segments=seg)
    return net


def _window(net, rows):
    idx = numpy.asarray(rows, numpy.int32).reshape(2, 2)
    hy = jax.tree.map(lambda v: numpy.full((2,), v, numpy.float32),
                      net.hypers)
    return net.run_window_indexed(idx, numpy.array([2, 2], numpy.int32), hy)


def test_a_resumed_window_keeps_the_bias_and_the_tied_leaves():
    """The window carries the second head's counts and loss; the bias,
    the embedding and the output weight ride in ``state_dict``; a net
    resumed from it runs the next window as the first net does."""
    net = _net()
    main = next(i for i, s in enumerate(net.specs) if s.kind == "lm_head")
    w0 = numpy.asarray(net.params[main]["w"]).copy()
    e0 = numpy.asarray(net.params[0]["w"]).copy()
    stats = _window(net, [0, 1, 2, 3])
    assert numpy.asarray(stats["mtp_loss"]).shape == (2,)
    acc = net.window_acc_host()
    assert int(acc["mtp_n_err"][1]) == int(stats["mtp_n_err"][1]) > 0
    assert float(acc["mtp_loss_sum"]) > 0
    assert float(numpy.abs(numpy.asarray(net.params[main]["w"]) - w0).max(
    )) > 0 and float(numpy.abs(numpy.asarray(net.params[0]["w"]) - e0).max(
    )) > 0
    saved = copy.deepcopy(net.state_dict())
    after = _window(net, [4, 5, 6, 7])
    resumed = _net(seed=4)
    resumed.load_state_dict(saved)
    again = _window(resumed, [4, 5, 6, 7])
    for name in ("loss", "mtp_loss", "moe_load"):
        numpy.testing.assert_array_equal(again[name], after[name])
    for i in (0, main):
        numpy.testing.assert_array_equal(resumed.params[i]["w"],
                                         net.params[i]["w"])
    moe = [i for i, s in enumerate(net.specs) if s.kind == "moe"]
    for i in moe:
        numpy.testing.assert_array_equal(resumed.params[i]["sb"],
                                         net.params[i]["sb"])
        assert float(jnp.abs(net.params[i]["sb"]).max()) > 0
