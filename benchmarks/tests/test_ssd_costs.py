"""Required FLOPs and least bytes of the configuration of state-space
mixers and relu^2 experts against hand counts."""
import json
import os

import pytest

from benchmarks import families, layer_costs
from benchmarks import run as run_mod

CELL = "nemotron_3_nano_30b_a3b.train_s8192_b1"
N, D = 8192, 2688
H, P, S, G, K, Q = 64, 64, 128, 8, 4, 128
I, C = H * P, H * P + 2 * G * S
#: (query, key) pairs a row of the mix attends, causal inside documents
PAIRS = 17922003.6


@pytest.fixture(scope="module")
def ssd_planned():
    _, cfg, mix, _, _ = run_mod.resolve(CELL)
    return families.load(cfg).plan(cfg, mix), mix


def test_the_ssd_cell_resolves_and_plans_one_entry_a_leaf(ssd_planned):
    net, _ = ssd_planned
    cell, cfg, _, limits, manifest = run_mod.resolve(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train_s8192_b1"
    assert cfg["family"] == "ssd_token_rows"
    assert set(limits) - {"readings"} == set(families.load(cfg).GRADED)
    mixer = ["rmsnorm", "ssd_mixer"]
    expert = ["rmsnorm", "router", "relu2_shared_moe"]
    attention = ["rmsnorm", "causal_attention"]
    # M E M E M * E M E, then the head
    assert [ent["kind"] for ent in net] == (
        ["embedding"] + (mixer + expert) * 2 + mixer + attention + expert
        + mixer + expert + ["ce_head"])
    assert [ent["spec"] for ent in net] == list(range(24))
    assert all(ent["pairs"] == pytest.approx(PAIRS, rel=1e-6)
               for ent in net if ent["kind"] == "causal_attention")
    assert all(ent["pairs"] == pytest.approx(N * 6 * 8 / 128)
               for ent in net if ent["kind"] == "relu2_shared_moe")
    new = [m["name"] for m in manifest["per_layer"]
           if m.get("workloads") == [CELL]]
    assert new == ["ssd_device_ms_per_step", "ssd_scan_device_ms_per_step",
                   "ssd_roofline_pct"]


def _scan_flops():
    """The chunked scan's products of one application: C B^T per group and
    (L o C B^T) X per head over a chunk's causal pairs, B^T X and C S per
    head over its positions."""
    pairs = Q * (Q + 1) / 2
    per_chunk = 2 * pairs * S * G + 2 * pairs * P * H + 2 * 2 * Q * P * S * H
    return per_chunk * (N // Q)


def test_ssd_required_matrix_flops_a_step_are_the_hand_count(ssd_planned):
    net, _ = ssd_planned
    mixer = 2.0 * N * (D * (I + C + H) + I * D) + _scan_flops()
    attention = 2.0 * N * (D * 4096 + 2 * D * 256 + 4096 * D) \
        + 4.0 * 128 * 32 * PAIRS
    router = 2.0 * N * D * 128
    experts = 4.0 * (N * 6 * 8 / 128) * D * 1856
    shared = 4.0 * N * D * 3712
    head = 2.0 * N * D * 16384
    want = 3 * (4 * mixer + attention + 4 * (router + experts + shared)
                + head)
    assert layer_costs.train_flops_per_image(net) == pytest.approx(
        want, rel=1e-9)
    assert want == pytest.approx(16.80e12, rel=0.005)
    mixers = sum(c["flops_fwd"] for ent, c, _ in
                 layer_costs.net_costs(net, 1) if ent["kind"] == "ssd_mixer")
    assert mixers == pytest.approx(4 * mixer)
    # the four mixers are the largest share of the step's matrix work
    assert 3 * mixers / want == pytest.approx(0.47, abs=0.005)
    assert _scan_flops() / N == pytest.approx(2.76e6, rel=0.01)


def test_the_mixers_and_the_experts_bytes_are_the_hand_count(ssd_planned):
    net, _ = ssd_planned
    ent = next(e for e in net if e["kind"] == "ssd_mixer")
    got = layer_costs.module_for("ssd_mixer").cost(ent, 1, False)
    acts = (2 * N * D + 2 * N * (I + C + H) + 2 * N * C + 2 * N * I) * 2 \
        + (2 * N * H + 2 * N * I + 2 * (N // Q) * H * P * S) * 4
    weights = D * (I + C + H) + K * C + C + 3 * H + I + I * D
    assert got["bytes_fwd"] == acts + weights * 2
    assert got["bytes_bwd"] == 2 * acts + weights * 6
    assert got["bytes_update"] == 7 * 4 * weights
    ent = next(e for e in net if e["kind"] == "relu2_shared_moe")
    got = layer_costs.module_for("relu2_shared_moe").cost(ent, 1, False)
    pairs = N * 6 * 8 / 128
    assert got["flops_fwd"] == 4.0 * pairs * D * 1856 + 4.0 * N * D * 3712
    routed, shared = 8 * 2 * D * 1856, 2 * D * 3712
    acts = 2 * N * D * 2 + pairs * (2 * D + 2 * 1856) * 2 \
        + N * (2 * D + 2 * 3712) * 2
    assert got["bytes_fwd"] == acts + (routed + shared) * 2
    # the selection bias is in no pass of the optimizer
    assert got["bytes_update"] == 7 * 4 * (routed + shared)


def test_ssd_configuration_keeps_every_published_number():
    with open(os.path.join(run_mod.ROOT, "benchmarks", "configs",
                           "nemotron_3_nano_30b_a3b.json")) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 52,
        "num_key_value_heads": 2, "rescale_prenorm_residual": True,
        "routed_scaling_factor": 2.5, "ssm_state_size": 128,
        "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["n_routed_experts"] == 8 and cfg["vocab_size"] == 16384
    assert cfg["published"]["n_routed_experts"] == 128
    assert cfg["published"]["vocab_size"] == 131072 == 8 * 16384
    assert cfg["reduced"] == ["layers", "n_routed_experts", "vocab_size",
                              "epoch_rows"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    kinds = [layer["layers"][-1]["type"] for layer in cfg["layers"]
             if layer["type"] == "residual"]
    # published layers 0-8, one whole period: MEMEM*EME
    assert kinds == ["ssd", "moe", "ssd", "moe", "ssd", "attention", "moe",
                     "ssd", "moe"]
    assert cfg["pattern_run"] == cfg["hybrid_override_pattern"][:9]
    assert cfg["compile_readings"]["parameters"] == 666963456
    mixer = cfg["layers"][1]["layers"][1]["->"]
    assert (mixer["heads"], mixer["head_dim"], mixer["state"],
            mixer["groups"], mixer["conv"], mixer["chunk"]) == (
        64, 64, 128, 8, 4, 128)
    assert mixer["out_stddev"] == pytest.approx(0.02 / 52 ** 0.5)
