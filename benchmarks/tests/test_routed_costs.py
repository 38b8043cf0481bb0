"""Required FLOPs of the routed configuration against hand counts."""
import json
import os

import pytest

from benchmarks import families, layer_costs
from benchmarks import run as run_mod

CELL = "smallthinker_21b_a3b.train_s16384_b1"


@pytest.fixture(scope="module")
def planned():
    _, cfg, mix, _, _ = run_mod.resolve(CELL)
    return families.load(cfg).plan(cfg, mix), mix


def test_plan_has_one_entry_a_leaf_in_the_scopes_order(planned):
    net, _ = planned
    kinds = [ent["kind"] for ent in net]
    assert kinds == ["embedding"] + ["router", "rmsnorm", "local_attention",
                                     "rmsnorm", "moe"] * 4 + ["ce_head"]
    assert [ent["spec"] for ent in net] == list(range(22))
    windows = [ent["window"] for ent in net
               if ent["kind"] == "local_attention"]
    assert windows == [None, 4096, 4096, 4096]


def test_attended_pairs_are_counted_from_the_mixs_documents(planned):
    net, mix = planned
    att = [ent for ent in net if ent["kind"] == "local_attention"]
    seq = mix["seq_len"]
    causal = seq * (seq + 1) / 2
    assert att[0]["pairs"] == pytest.approx(57.29e6, rel=1e-3)
    assert att[1]["pairs"] == pytest.approx(42.83e6, rel=1e-3)
    assert att[1]["pairs"] < att[0]["pairs"] < causal
    # a window that no document reaches changes nothing; a window of one
    # leaves every query its own key
    fam = families.load({"name": "t", "family": "routed_token_rows"})
    assert fam.attended_pairs_per_row(mix, seq) == att[0]["pairs"]
    assert fam.attended_pairs_per_row(mix, 1) == seq


def test_required_matrix_flops_a_step_are_the_hand_count(planned):
    net, _ = planned
    n, d, hd, h, kv = 16384, 2560, 128, 28, 4
    proj = 2.0 * n * (2 * d * h * hd + 2 * d * kv * hd)
    scores = [4.0 * hd * h * p for p in (57292714.8, 42833175.6)]
    experts = 6.0 * (n * 6 * 16 / 64) * d * 768
    head = 2.0 * n * d * 37984
    router = 2.0 * n * d * 64
    want = 3 * (4 * proj + scores[0] + 3 * scores[1] + 4 * experts
                + head + 4 * router)
    assert layer_costs.train_flops_per_image(net) == pytest.approx(
        want, rel=1e-6)
    assert want == pytest.approx(29.4e12, rel=0.01)
    by_kind = {}
    for ent, c, _ in layer_costs.net_costs(net, 1):
        by_kind[ent["kind"]] = by_kind.get(ent["kind"], 0.0) \
            + c["flops_fwd"] + c["flops_bwd"]
    assert by_kind["moe"] == pytest.approx(3 * 4 * experts)
    assert by_kind["ce_head"] / want == pytest.approx(0.326, abs=0.005)


def test_the_products_cost_follows_the_pairs_handed_in(planned):
    net, _ = planned
    moe = next(ent for ent in net if ent["kind"] == "moe")
    module = layer_costs.module_for("moe")
    flops, nbytes = module.products(moe, 1000.0)
    assert flops == 6.0 * 1000 * 2560 * 768
    assert module.products(moe, 2000.0)[0] == 2 * flops
    weights = 16 * 3 * 2560 * 768 * 2
    assert nbytes == 1000 * (2 * 2560 + 3 * 768) * 2 + weights


def test_configuration_keeps_every_published_number():
    with open(os.path.join(run_mod.ROOT, "benchmarks", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        cfg = json.load(f)
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "num_attention_heads": 28,
        "num_hidden_layers": 52, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_theta": 1500000,
        "sliding_window_size": 4096}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["rope_layout"] == [0, 1, 1, 1] * 13
    assert cfg["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert cfg["moe_num_primary_experts"] == 16 and cfg["vocab_size"] == 37984
    assert cfg["published"] == {"moe_num_primary_experts": 64,
                                "vocab_size": 151936,
                                "num_hidden_layers": 52}
    assert cfg["reduced"] == ["layers", "moe_num_primary_experts",
                              "vocab_size", "epoch_rows"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
