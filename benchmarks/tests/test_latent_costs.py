"""Required FLOPs and least bytes of the configuration with latent
attention and a multi-token prediction module against hand counts."""
import json
import os

import pytest

from benchmarks import families, layer_costs
from benchmarks import run as run_mod

CELL = "glm_4_7_flash.train_s8192_b1"
N, D, H, QR, KVR, NOPE, ROPE, V = 8192, 2048, 20, 768, 512, 192, 64, 256
#: (query, key) pairs a row of the mix attends, causal inside documents
PAIRS = 17922003.6


@pytest.fixture(scope="module")
def latent_planned():
    _, cfg, mix, _, _ = run_mod.resolve(CELL)
    return families.load(cfg).plan(cfg, mix), mix


def test_the_latent_cell_resolves_and_plans_one_entry_a_leaf(latent_planned):
    net, _ = latent_planned
    cell, cfg, _, limits, manifest = run_mod.resolve(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train_s8192_b1"
    assert cfg["family"] == "mtp_token_rows"
    assert set(limits) - {"readings"} == set(families.load(cfg).GRADED)
    attn = ["rmsnorm", "latent_attention", "rmsnorm"]
    expert = attn + ["router", "shared_moe"]
    assert [ent["kind"] for ent in net] == (
        ["embedding"] + attn + ["gated_mlp"] + expert * 4 + ["ce_head",
                                                             "mtp_input"]
        + expert + ["ce_head"])
    assert [ent["spec"] for ent in net] == list(range(33))
    assert [ent["spec"] for ent in net if ent["mtp"]] == list(range(26, 33))
    # the second head is planned with the weight it reads, whose update
    # is booked to the head that holds it
    assert net[32]["leaves"]["w"] == (19360, D) and not net[32]["update"]
    assert all(ent["pairs"] == pytest.approx(PAIRS, rel=1e-6)
               for ent in net if ent["kind"] == "latent_attention")
    new = [m["name"] for m in manifest["per_layer"]
           if m.get("workloads") == [CELL]]
    assert new == ["mla_attention_device_ms_per_step",
                   "mla_attention_roofline_pct", "mtp_device_ms_per_step"]


def test_latent_required_matrix_flops_a_step_are_the_hand_count(
        latent_planned):
    net, _ = latent_planned
    proj = 2.0 * N * (D * QR + QR * H * (NOPE + ROPE) + D * (KVR + ROPE)
                      + KVR * H * (NOPE + V) + H * V * D)
    core = 2.0 * H * PAIRS * (NOPE + ROPE + V)
    dense = 2.0 * N * 3 * D * 10240
    shared = 6.0 * N * D * 1536
    experts = 6.0 * (N * 4 * 8 / 64) * D * 1536
    router = 2.0 * N * D * 64
    head = 2.0 * N * D * 19360
    way_in = 2.0 * N * 2 * D * D
    want = 3 * (6 * (proj + core) + dense + 5 * (shared + experts + router)
                + 2 * head + way_in)
    assert layer_costs.train_flops_per_image(net) == pytest.approx(
        want, rel=1e-6)
    assert want == pytest.approx(23.94e12, rel=0.005)
    attention = sum(c["flops_fwd"] for ent, c, _ in
                    layer_costs.net_costs(net, 1)
                    if ent["kind"] == "latent_attention")
    assert attention == pytest.approx(6 * (proj + core))
    # latent attention, the module's included, is over half the step
    assert 3 * attention / want == pytest.approx(0.544, abs=0.001)
    module = sum(c["flops_fwd"] + c["flops_bwd"] for ent, c, mxu in
                 layer_costs.net_costs(net, 1) if ent["mtp"] and mxu)
    assert module == pytest.approx(3 * (proj + core + shared + experts
                                        + router + head + way_in))


def test_latent_attention_and_the_way_in_bytes_are_the_hand_count(
        latent_planned):
    net, _ = latent_planned
    ent = next(e for e in net if e["kind"] == "latent_attention")
    got = layer_costs.module_for("latent_attention").cost(ent, 1, False)
    acts = (2 * N * D + 2 * N * (QR + KVR + ROPE)
            + 2 * N * H * (2 * (NOPE + ROPE) + V)
            + 2 * N * (QR + KVR) + 2 * N * (H + 1) * ROPE) * 2
    weights = (D * QR + QR * H * (NOPE + ROPE) + D * (KVR + ROPE)
               + KVR * H * (NOPE + V) + H * V * D + QR + KVR)
    assert got["bytes_fwd"] == acts + weights * 2
    assert got["bytes_bwd"] == 2 * acts + weights * 6
    assert got["bytes_update"] == 7 * 4 * weights
    way = next(e for e in net if e["kind"] == "mtp_input")
    got = layer_costs.module_for("mtp_input").cost(way, 1, False)
    assert got["flops_fwd"] == 2.0 * N * 2 * D * D
    acts = (2 * N * D + 2 * N * 2 * D) * 2 + N * D * 2
    assert got["bytes_fwd"] == acts + N * D * 4 + 2 * D * D * 2
    assert got["bytes_update"] == 7 * 4 * (2 * D * D + 2 * D)


def test_latent_configuration_keeps_every_published_number():
    with open(os.path.join(run_mod.ROOT, "benchmarks", "configs",
                           "glm_4_7_flash.json")) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_shared_experts": 1, "routed_scaling_factor": 1.8,
        "num_experts_per_tok": 4, "first_k_dense_replace": 1,
        "num_hidden_layers": 47, "num_key_value_heads": 20,
        "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
        "v_head_dim": 256}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["n_routed_experts"] == 8 and cfg["vocab_size"] == 19360
    assert cfg["published"]["n_routed_experts"] == 64
    assert cfg["published"]["vocab_size"] == 154880 == 8 * 19360
    assert cfg["reduced"] == ["layers", "n_routed_experts", "vocab_size",
                              "epoch_rows"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    kinds = [layer["layers"][-1]["type"] for layer in cfg["layers"]
             if layer["type"] == "residual"]
    # published layers 0-4 (one dense) and the module's layer
    assert kinds == ["latent_attention", "gated_mlp"] + [
        "latent_attention", "moe"] * 5
    assert cfg["compile_readings"]["parameters"] == 706518848
