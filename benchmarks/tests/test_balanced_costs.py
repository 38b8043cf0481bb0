"""Required FLOPs and least bytes of the configuration with a shared expert
and gated attention against hand counts."""
import json
import os

import pytest

from benchmarks import families, layer_costs
from benchmarks import run as run_mod

CELL = "trinity_mini.train_s8192_b1"
N, D, HD, H, KV = 8192, 2048, 128, 32, 4


@pytest.fixture(scope="module")
def planned():
    _, cfg, mix, _, _ = run_mod.resolve(CELL)
    return families.load(cfg).plan(cfg, mix), mix


def test_the_cell_resolves_and_plans_one_entry_a_leaf(planned):
    net, mix = planned
    cell, cfg, _, limits, manifest = run_mod.resolve(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train_s8192_b1"
    assert cfg["family"] == "balanced_token_rows"
    assert set(limits) - {"readings"} == set(families.load(cfg).GRADED)
    block = ["rmsnorm", "gated_attention", "rmsnorm", "rmsnorm"]
    assert [ent["kind"] for ent in net] == (
        ["embedding"] + block + ["gated_mlp", "rmsnorm"]
        + (block + ["router", "shared_moe", "rmsnorm"]) * 4 + ["ce_head"])
    assert [ent["spec"] for ent in net] == list(range(36))
    assert [ent["window"] for ent in net
            if ent["kind"] == "gated_attention"] == [2048, 2048, None, 2048,
                                                     2048]
    new = [m["name"] for m in manifest["per_layer"]
           if m.get("workloads") == [CELL]]
    assert new == ["shared_expert_device_ms_per_step",
                   "shared_expert_roofline_pct",
                   "load_max_over_mean_all_experts"]


def test_a_window_of_2048_leaves_a_third_fewer_pairs(
        planned):
    net, mix = planned
    att = [ent for ent in net if ent["kind"] == "gated_attention"]
    causal = N * (N + 1) / 2
    assert att[2]["pairs"] == pytest.approx(17.92e6, rel=1e-3)
    assert att[0]["pairs"] == pytest.approx(12.07e6, rel=1e-3)
    assert att[0]["pairs"] < att[2]["pairs"] < causal / 1.8


def test_required_matrix_flops_a_step_are_the_hand_count(planned):
    net, _ = planned
    proj = 2.0 * N * (2 * D * H * HD + 2 * D * KV * HD)
    gate = 2.0 * N * D * H * HD
    scores = [4.0 * HD * H * p for p in (17922003.6, 12074983.0)]
    dense = 2.0 * N * 3 * D * 6144
    shared = 6.0 * N * D * 1024
    experts = 6.0 * (N * 8 * 16 / 128) * D * 1024
    head = 2.0 * N * D * 25024
    router = 2.0 * N * D * 128
    want = 3 * (5 * (proj + gate) + scores[0] + 4 * scores[1] + dense
                + 4 * (shared + experts + router) + head)
    assert layer_costs.train_flops_per_image(net) == pytest.approx(
        want, rel=1e-6)
    assert want == pytest.approx(16.85e12, rel=0.005)
    by_kind = {}
    for ent, c, _ in layer_costs.net_costs(net, 1):
        by_kind[ent["kind"]] = by_kind.get(ent["kind"], 0.0) \
            + c["flops_fwd"] + c["flops_bwd"]
    assert by_kind["shared_moe"] == pytest.approx(3 * 4 * (shared + experts))
    assert by_kind["gated_attention"] == pytest.approx(
        3 * (5 * (proj + gate) + scores[0] + 4 * scores[1]))
    # the gate is 8.4 M parameters a layer that no published key names
    assert 3 * 5 * gate / want == pytest.approx(0.122, abs=0.003)


def test_the_gates_and_the_shared_experts_bytes_are_the_hand_count(planned):
    net, _ = planned
    att = next(ent for ent in net if ent["kind"] == "gated_attention")
    plain = dict(att, kind="local_attention", leaves={
        k: att["leaves"][k] for k in ("wq", "wk", "wv", "wo")})
    base = layer_costs.module_for("local_attention").cost(plain, 1, False)
    got = layer_costs.module_for("gated_attention").cost(att, 1, False)
    wgate = D * H * HD
    acts = 2 * N * H * HD * 2       # written once and read once, 2 bytes
    assert got["bytes_fwd"] - base["bytes_fwd"] == acts + wgate * 2
    assert got["bytes_bwd"] - base["bytes_bwd"] == 2 * acts + wgate * 6
    # AdamW's seven float32 passes over the gate's weight and the two gains
    assert got["bytes_update"] - base["bytes_update"] == \
        7 * 4 * (wgate + 2 * HD)
    moe = next(ent for ent in net if ent["kind"] == "shared_moe")
    module = layer_costs.module_for("shared_moe")
    flops, nbytes = module.shared_products(moe, N)
    assert flops == 6.0 * N * D * 1024
    assert nbytes == N * (2 * D + 2 * 1024) * 2 + 3 * D * 1024 * 2
    # the experts held: moe's own count over the routed leaves alone
    routed = dict(moe, leaves={k: moe["leaves"][k]
                               for k in ("wg", "wu", "wd")})
    assert module.products(moe, 1000.0) == layer_costs.module_for(
        "moe").products(routed, 1000.0)
    assert module.products(moe, 1000.0)[1] == \
        1000 * (2 * D + 3 * 1024) * 2 + 16 * 3 * D * 1024 * 2
    whole = module.cost(moe, 1, False)
    part = layer_costs.module_for("moe").cost(routed, 1, False)
    assert whole["flops_fwd"] - part["flops_fwd"] == flops
    assert whole["bytes_update"] - part["bytes_update"] == \
        7 * 4 * 3 * D * 1024


def test_configuration_keeps_every_published_number():
    with open(os.path.join(run_mod.ROOT, "benchmarks", "configs",
                           "trinity_mini.json")) as f:
        cfg = json.load(f)
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts_per_tok": 8,
        "num_hidden_layers": 32, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 8
    assert cfg["num_experts"] == 16 and cfg["vocab_size"] == 25024
    assert cfg["published"]["num_experts"] == 128
    assert cfg["published"]["vocab_size"] == 200192 == 8 * 25024
    assert cfg["reduced"] == ["layers", "num_experts", "vocab_size",
                              "epoch_rows"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    # the layers that run are published layers 1-5: their kinds of attention
    ran = [layer["layers"][1]["->"]["window"] is None
           for layer in cfg["layers"][1:-1:2]]
    assert ran == [t == "full_attention" for t in cfg["layer_types"][1:6]]
