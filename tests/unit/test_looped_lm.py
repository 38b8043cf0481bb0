"""A looped language model through the fused path, at toy widths.

The fused window (recomputation and blocks on) against the plain reference
``benchmarks/reference/looped_lm.py`` on seeded weights; the loop's shared
gradient; blocked attention and head against the whole ones; the document
mask; the refusals of everything that does not run the new kinds.

Tolerances: the program and the reference are float32 on the CPU at
``highest`` matmul precision (``tests/conftest.py``) and differ by the order
of float32 sums alone (blocks, scan transposes), so relative gaps sit at
1e-7..1e-6; the fixture's limits leave two to three decades of room.
"""
import json
import os

import numpy
import pytest

import jax
import jax.numpy as jnp

from benchmarks import families, rehearse
from znicz_tpu.ops import transformer
from znicz_tpu.parallel import fused
from znicz_tpu.samples.research import looped_lm

HERE = os.path.join(os.path.dirname(os.path.abspath(rehearse.__file__)),
                    "tests", "tiny_lm")
CELL = {"name": "tiny_looped_lm.train_s24_b2", "config": "tiny_looped_lm",
        "traffic": "train_s24_b2", "chips": 1}
NUMBERS = ("loss_worst_step", "logit_rel_diff", "exit_prob_diff",
           "m1_worst_leaf", "dparam_worst_leaf", "tok_err_gap",
           "window_rows_gap", "window_tokens_gap", "hyper_feed_gap",
           "epoch_train_rows_gap", "epoch_valid_rows_gap",
           "epoch_train_tokens_gap", "epoch_valid_tokens_gap")


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def parts():
    return (CELL, _load("configs", CELL["config"] + ".json"),
            _load("traffic", CELL["traffic"] + ".json"),
            _load("limits", CELL["name"] + ".json"))


@pytest.fixture(scope="module")
def tiny_run(parts):
    """The fixture cell end to end: StandardWorkflow, fused trainer,
    evaluator, decision; the reference follows the first epoch's steps."""
    from znicz_tpu.core import telemetry
    from znicz_tpu.core.config import root
    was = root.common.telemetry.get("enabled", False)
    # the benchmark's job points the sample's config at its own loader
    loader_was = root.looped_lm.loader_name
    telemetry.enable()
    telemetry.reset()
    try:
        correct, nums = rehearse.tiny_cell(*parts)
        counters = {n: telemetry.counter(n).value for n in (
            "trainer.graded_tokens", "trainer.rows", "trainer.readbacks")}
    finally:
        root.common.telemetry.enabled = was
        root.looped_lm.loader_name = loader_was
    return correct, {n: (v, lim) for n, v, lim in nums}, counters


@pytest.mark.parametrize("name", NUMBERS)
def test_fused_window_equals_the_plain_reference(tiny_run, name):
    """Loss of every step, sampled logits of every pass, exit distribution,
    every leaf's first moment and parameter change; exact counts."""
    _, nums, _ = tiny_run
    value, limit = nums[name]
    assert numpy.isfinite(value) and value <= limit, (name, value, limit)


def test_cell_is_correct_and_counters_count_tokens(tiny_run, parts):
    correct, _, counters = tiny_run
    assert correct
    _, cfg, mix, _ = parts
    made = families.load(cfg).make_data(2147483659, cfg, mix)
    nv = mix["n_valid"]
    epochs = counters["trainer.readbacks"]      # one train readback each
    assert epochs >= 2
    assert counters["trainer.rows"] == epochs * mix["n_train"]
    assert counters["trainer.graded_tokens"] == \
        epochs * int((made["labels"][nv:] >= 0).sum())


# -- the mechanism, on the spec stack itself ----------------------------------

def _net(passes=2, q_block=4, token_block=8, kv_heads=2, seq=16, seed=3):
    layers = looped_lm.make_layers(
        vocab=40, dim=16, heads=4, kv_heads=kv_heads, head_dim=4, hidden=24,
        n_layers=2, passes=passes, q_block=q_block, token_block=token_block)
    specs = fused.build_specs(layers, (seq,))
    _, topology = fused.flatten_layers(layers)
    rs = numpy.random.RandomState(seed)
    params = [{k: jnp.asarray(rs.normal(0, 0.3, v.shape), jnp.float32)
               for k, v in p.items()}
              for p in fused.init_params(specs, dtype=numpy.float32)]
    return layers, specs, topology, params


def _rows(seq=16, batch=2, vocab=40, seed=5):
    rs = numpy.random.RandomState(seed)
    ids = rs.randint(0, vocab, (batch, seq)).astype(numpy.int32)
    seg = numpy.ones((batch, seq), numpy.int32)
    seg[:, seq // 2:] = 2           # two documents a row
    seg[1, 3:] += 1                 # three in the second
    lbl = numpy.full((batch, seq), -1, numpy.int32)
    same = seg[:, 1:] == seg[:, :-1]
    lbl[:, :-1] = numpy.where(same, ids[:, 1:], -1)
    return jnp.asarray(ids), jnp.asarray(seg), jnp.asarray(lbl)


def _loss(params, specs, topology, ids, seg, lbl, train=True):
    emit = fused.forward_tokens(params, ids, seg, lbl, specs, topology,
                                train=train)
    return fused._token_stats(emit, lbl, specs)[0]


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_shared_weight_gradient_is_the_sum_over_its_applications(passes):
    """d loss / d w of the looped net (one set of weights, a scan) equals
    the sum over passes of d loss / d w_t of the same net run pass by pass
    with a copy of the weights for each."""
    _, specs, topology, params = _net(passes=passes)
    ids, seg, lbl = _rows()
    shared = jax.grad(_loss)(params, specs, topology, ids, seg, lbl)
    (_, _, body), = [n for n in topology if not isinstance(n, int)]

    def unrolled(copies):
        ctx = {"cd": None, "segments": seg, "labels": lbl, "train": False,
               "sample": None, "emit": {},
               "rope": {(4, 1e6): transformer.rope_tables(16, 4, 1e6)}}
        y = fused._run_nodes([0], copies[0], specs, ids, ctx)
        emits = []
        for p in copies:
            sub = dict(ctx, emit={})
            y = fused._run_nodes(body, p, specs, y, sub)
            emits.append(sub["emit"])
        emit = jax.tree.map(lambda *a: jnp.stack(a), *emits)
        return fused._token_stats(emit, lbl, specs)[0]

    per_pass = jax.grad(unrolled)([params] * passes)
    for i, leaf in enumerate(shared):
        for name, g in leaf.items():
            # the embedding (spec 0) is applied once, by the first copy
            want = per_pass[0][i][name] if i == 0 else sum(
                c[i][name] for c in per_pass)
            # float32 sums in another order: 1e-7 of the leaf's largest
            # element; a pass left out would be of the order of the leaf
            numpy.testing.assert_allclose(
                g, want, rtol=1e-4, atol=1e-4 * float(jnp.abs(want).max()),
                err_msg="%d.%s" % (i, name))


@pytest.mark.parametrize("q_block,remat", [(4, False), (4, True), (8, True)])
def test_blocked_attention_equals_whole(q_block, remat):
    rs = numpy.random.RandomState(1)
    q = jnp.asarray(rs.normal(size=(2, 16, 4, 8)), jnp.float32)
    k = jnp.asarray(rs.normal(size=(2, 16, 2, 8)), jnp.float32)
    v = jnp.asarray(rs.normal(size=(2, 16, 2, 8)), jnp.float32)
    _, seg, _ = _rows()

    def total(q, k, v, block, remat):
        out = transformer.attend(q, k, v, seg, block, remat)
        return (out * jnp.cos(jnp.arange(out.size).reshape(out.shape))).sum()

    whole = jax.value_and_grad(total, argnums=(0, 1, 2))(q, k, v, None,
                                                         False)
    blocked = jax.value_and_grad(total, argnums=(0, 1, 2))(q, k, v, q_block,
                                                           remat)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(blocked)):
        numpy.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("platform,seq,head_dim,kernel", [
    (None, 4096, 128, False),       # the backend here is the CPU
    ("tpu", 4096, 128, True), ("tpu", 32, 8, False),
    ("tpu", 4096, 64, False), ("cpu", 4096, 128, False)])
def test_attention_chooses_its_lowering_by_platform_and_shape(
        platform, seq, head_dim, kernel):
    """No option selects the kernel: the platform the program is lowered
    for and the tiles the shapes fill do."""
    if platform is None:
        assert transformer.kernel_suits(seq, head_dim) is kernel
        return
    with transformer.lowering_for(platform):
        assert transformer.kernel_suits(seq, head_dim) is kernel
    assert not transformer.kernel_suits(seq, head_dim)


@pytest.mark.parametrize("train", [False, True])
def test_blocked_head_equals_whole(train):
    ids, seg, lbl = _rows()
    _, specs, topology, params = _net(token_block=8)
    _, specs_w, _, _ = _net(token_block=None)
    a = jax.value_and_grad(_loss)(params, specs, topology, ids, seg, lbl,
                                  train)
    b = jax.value_and_grad(_loss)(params, specs_w, topology, ids, seg, lbl,
                                  train)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        numpy.testing.assert_allclose(x, y, rtol=2e-5, atol=1e-7)


def test_a_token_of_another_document_changes_nothing():
    """The second document of a row reads nothing of the first: another id
    there leaves its losses, gates and predictions bit for bit alone."""
    _, specs, topology, params = _net()
    ids, seg, lbl = _rows()
    one = fused.forward_tokens(params, ids, seg, lbl, specs, topology)
    other = fused.forward_tokens(params, ids.at[0, 2].add(1), seg, lbl,
                                 specs, topology)
    second = numpy.asarray(seg[0] == 2)
    for name in ("ce", "gate", "pred"):
        a = numpy.asarray(one[name]).reshape(-1, 2, 16)[:, 0]
        b = numpy.asarray(other[name]).reshape(-1, 2, 16)[:, 0]
        assert (a[:, second] == b[:, second]).all(), name
    assert (numpy.asarray(one["gate"]).reshape(-1, 2, 16)[:, 0, 2:8] !=
            numpy.asarray(other["gate"]).reshape(-1, 2, 16)[:, 0, 2:8]).any()


def test_exit_distribution_sums_to_one_and_matches_the_product_form():
    gate = jnp.asarray(numpy.random.RandomState(0).normal(size=(4, 7)))
    p = numpy.exp(numpy.asarray(transformer.exit_log_probs(gate)))
    lam = 1 / (1 + numpy.exp(-numpy.asarray(gate)))
    want = numpy.stack([lam[0], lam[1] * (1 - lam[0]),
                        lam[2] * (1 - lam[0]) * (1 - lam[1]),
                        (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])])
    numpy.testing.assert_allclose(p, want, rtol=1e-6)
    numpy.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)


@pytest.fixture
def stock_loader(monkeypatch):
    """The sample's own loader, whatever a benchmark job run earlier in
    this process left in its config (``lib/job.py`` points it at its
    own)."""
    from znicz_tpu.core.config import root
    monkeypatch.setattr(root.looped_lm, "loader_name",
                        "synthetic_token_rows")


def test_sample_trains_through_the_normal_path(stock_loader):
    """``build()`` -> StandardWorkflow -> fused trainer -> token evaluator
    -> decision: the loss a token falls, on both classes of minibatch."""
    from znicz_tpu.core.backends import JaxDevice
    wf = looped_lm.build(fused={"window": 2},
                         decision_config={"max_epochs": 4})
    wf.initialize(device=JaxDevice())
    losses = []
    stop = wf.decision.stop_condition

    def note():
        losses.append(list(wf.decision.epoch_loss))
        return stop()
    wf.decision.stop_condition = note
    wf.run()
    assert type(wf.evaluator).__name__ == "EvaluatorTokens"
    assert type(wf.decision).__name__ == "DecisionTokens"
    assert losses[-1][2] < losses[0][2] and losses[-1][1] < losses[0][1]
    assert wf.decision.epoch_rows[1:] == [16, 64]
    assert wf.decision.confusion_matrixes == [None] * 3


def test_sample_takes_the_fused_path_with_or_without_the_flag(stock_loader):
    """The unit graph has no token-sequence kind (it refuses the first one
    it meets), so ``build()`` turns the fused path on itself."""
    from znicz_tpu.core.config import root
    assert looped_lm.build().fused_trainer is not None
    cfg = root.looped_lm
    with pytest.raises(ValueError, match="Unknown layer type 'embedding'"):
        looped_lm.LoopedLMWorkflow(
            layers=looped_lm.make_layers(), loader_name=cfg.loader_name,
            loader_config=cfg.loader.as_dict(), loss_function="tokens")


# -- what does not run the new kinds says so ----------------------------------

@pytest.mark.parametrize("tpe", sorted(transformer.KINDS)
                         + list(transformer.STRUCTURAL))
def test_serving_engine_refuses_the_kind_by_name(tpe):
    from znicz_tpu.serving import engine
    with pytest.raises(ValueError) as err:
        engine._validate_layers([{"type": tpe, "arrays": []}])
    assert "serving engine does not support layer type %r" % tpe \
        in str(err.value)
    assert "ROADMAP R6" in str(err.value)


def test_export_refuses_the_kinds_by_name():
    from znicz_tpu import export

    class Workflow(object):
        layers = looped_lm.make_layers()
        forwards = []

    with pytest.raises(ValueError, match="export does not support layer "
                       "type 'embedding'"):
        export.export_package(Workflow(), "/nonexistent/package.zip")


def test_the_other_objectives_refuse_the_kinds():
    with pytest.raises(ValueError, match="objective='tokens'"):
        fused.FusedNet(looped_lm.make_layers() + [
            {"type": "softmax", "->": {"output_sample_shape": 4}}], (8,))
    with pytest.raises(ValueError, match="one lm_head"):
        fused.FusedNet(looped_lm.make_layers()[:1], (8,), objective="tokens")
