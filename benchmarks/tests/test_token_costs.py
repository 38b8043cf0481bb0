"""Hand counts of the five token-sequence cost modules (in ``test_costs.py``'s
manner), at the published widths of ``configs/ouro_2_6b.json``."""
import json
import os

import pytest

from benchmarks import families, layer_costs
from benchmarks.layer_costs import (causal_attention, embedding, gated_mlp,
                                    lm_head, rmsnorm)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
D, HEADS, HD, F, V, S, L, T = 2048, 16, 128, 5632, 49152, 4096, 6, 4
PAIRS = 1000000.0       # attended pairs of a row, for the hand count


@pytest.fixture(scope="module")
def net():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ouro_2_6b.json")) as f:
        cfg = json.load(f)
    return families.reference(cfg).plan(cfg["layers"], S, PAIRS)


def _of(net, kind):
    return [e for e in net if e["kind"] == kind]


def test_the_plan_lists_every_application():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ouro_2_6b.json")) as f:
        cfg = json.load(f)
    plan = families.reference(cfg).plan(cfg["layers"], S)
    assert len(_of(plan, "causal_attention")) == L * T == 24
    assert len(_of(plan, "gated_mlp")) == 24
    assert len(_of(plan, "lm_head")) == T
    assert len(_of(plan, "embedding")) == 1
    assert len(_of(plan, "rmsnorm")) == 4 * L * T
    # only a leaf's first application of the step carries its update
    assert sum(e["update"] for e in _of(plan, "causal_attention")) == L
    assert sum(e["update"] for e in _of(plan, "lm_head")) == 1
    # causal over the whole row where no documents are given
    assert _of(plan, "causal_attention")[0]["pairs"] == S * (S + 1) / 2
    assert sorted({e["spec"] for e in plan}) == list(range(38))


def test_attention_counts_projections_and_attended_pairs_only(net):
    ent = _of(net, "causal_attention")[0]
    proj, score = causal_attention.parts(ent, 1)
    assert proj == 2.0 * S * 4 * D * D == 137438953472.0
    # two products of head_dim a pair and head
    assert score == 2 * 2 * HD * HEADS * PAIRS == 8192000000.0
    c = causal_attention.cost(ent, 1, False)
    assert c["flops_fwd"] == proj + score
    assert c["flops_bwd"] == 2 * c["flops_fwd"]
    assert c["bytes_update"] == 7 * 4 * 4 * D * D
    assert causal_attention.cost(_of(net, "causal_attention")[L], 1,
                          False)["bytes_update"] == 0


def test_gated_mlp_is_three_products(net):
    c = gated_mlp.cost(_of(net, "gated_mlp")[0], 2, False)
    assert c["flops_fwd"] == 2.0 * (2 * S) * 3 * D * F == 566935683072.0
    assert c["bytes_update"] == 7 * 4 * 3 * D * F


def test_lm_head_is_the_whole_vocabulary_once_a_pass(net):
    c = lm_head.cost(_of(net, "lm_head")[0], 1, False)
    assert c["flops_fwd"] == 2.0 * S * D * (V + 1)
    # the logits do not cross HBM; the weight and its gradient do
    assert c["bytes_bwd"] - c["bytes_fwd"] == V * D * 4
    assert c["bytes_update"] == 7 * 4 * (V * D + 2 * D + 1)


def test_embedding_and_norm_are_no_matrix_work(net):
    e = embedding.cost(_of(net, "embedding")[0], 1, True)
    assert e["flops_fwd"] == 0 and not embedding.MXU
    assert e["bytes_fwd"] == S * D * (4 + 2)
    assert e["bytes_update"] == 7 * 4 * V * D
    n = rmsnorm.cost(_of(net, "rmsnorm")[0], 1, False)
    assert n["flops_fwd"] == 4.0 * S * D and not rmsnorm.MXU
    assert n["bytes_update"] == 7 * 4 * D


def test_a_trained_token_is_about_ten_gigaflop(net):
    """Layers 4 x 6 x 102.8 MFLOP forward, heads 4 x 201.3, attended pairs
    on top; three times that trained."""
    per_token = layer_costs.train_flops_per_image(net) / S
    linear = 3 * (T * L * 2 * (4 * D * D + 3 * D * F)
                  + T * 2 * D * (V + 1))
    scores = 3 * T * L * 4 * HD * HEADS * PAIRS / S
    assert per_token == pytest.approx(linear + scores)
    assert 9.8e9 < linear < 10.0e9
