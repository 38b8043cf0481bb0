"""Every configuration names a job family that keeps the contract, and every
cell's limits are its family's graded names.  No job runs and no device is
touched: modules are imported and files read.

The tier-1 copy of ``benchmarks/tests/test_families.py`` (which is not part
of tier-1), so that tier-1 guards ``token_rows``, ``classifier_rows`` and the
witness ``regression_rows`` against ``families.CONTRACT``.  The cases of
``benchmarks/tests/test_balanced_costs.py`` (hand counts of the cost modules
that PR 35 added, the manifest resolving its cell) and of
``benchmarks/tests/test_balanced_token_rows.py`` (``correct`` false under
the control and each planted fault at the mix's tiny size) run here too, by
import: one copy of each; so do those of ``test_latent_costs.py`` and
``test_mtp_token_rows.py``, and those of ``test_ssd_costs.py`` and
``test_ssd_token_rows.py``."""
import json
import os
import re

import pytest

from benchmarks import families
from benchmarks import run as run_mod
from benchmarks.tests.test_balanced_costs import (  # noqa: F401
    planned, test_a_window_of_2048_leaves_a_third_fewer_pairs,
    test_configuration_keeps_every_published_number,
    test_required_matrix_flops_a_step_are_the_hand_count,
    test_the_cell_resolves_and_plans_one_entry_a_leaf,
    test_the_gates_and_the_shared_experts_bytes_are_the_hand_count)
from benchmarks.tests.test_balanced_token_rows import (  # noqa: F401
    feed_and_reference, parts, test_each_reading_fails_a_limit,
    test_sound_run_is_correct_and_counts_exactly,
    test_state_left_unchanged_under_the_timed_path_is_not_correct,
    test_the_reference_in_its_own_place_reads_nought)
from benchmarks.tests.test_latent_costs import (  # noqa: F401
    latent_planned,
    test_latent_attention_and_the_way_in_bytes_are_the_hand_count,
    test_latent_configuration_keeps_every_published_number,
    test_latent_required_matrix_flops_a_step_are_the_hand_count,
    test_the_latent_cell_resolves_and_plans_one_entry_a_leaf)
from benchmarks.tests.test_mtp_token_rows import (  # noqa: F401
    latent_feed_and_reference, latent_parts,
    test_each_latent_reading_fails_a_limit,
    test_latent_state_left_unchanged_is_not_correct,
    test_sound_latent_run_is_correct_and_counts_exactly,
    test_the_latent_reference_in_its_own_place_reads_nought)
from benchmarks.tests.test_ssd_costs import (  # noqa: F401
    ssd_planned, test_ssd_configuration_keeps_every_published_number,
    test_ssd_required_matrix_flops_a_step_are_the_hand_count,
    test_the_mixers_and_the_experts_bytes_are_the_hand_count,
    test_the_ssd_cell_resolves_and_plans_one_entry_a_leaf)
from benchmarks.tests.test_ssd_token_rows import (  # noqa: F401
    ssd_feed_and_reference, ssd_parts, test_each_ssd_reading_fails_a_limit,
    test_sound_ssd_run_is_correct_and_counts_exactly,
    test_ssd_state_left_unchanged_is_not_correct,
    test_the_ssd_reference_in_its_own_place_reads_nought)

ROOT = run_mod.ROOT
BENCH = os.path.join(ROOT, "benchmarks")


def _manifests():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        yield json.load(f)
    with open(os.path.join(BENCH, "pending_cells.json")) as f:
        yield json.load(f)


CONFIGS = sorted({c["name"]: c["file"] for m in _manifests()
                  for c in m["configs"]}.items())
CELLS = sorted({w["name"] for m in _manifests() for w in m["workloads"]})


@pytest.mark.parametrize("name,path", CONFIGS)
def test_configuration_names_a_family_that_keeps_the_contract(name, path):
    with open(os.path.join(ROOT, path)) as f:
        cfg = json.load(f)
    fam = families.load(cfg)    # refuses a module that lacks a name
    assert fam.__name__ == "benchmarks.families." + cfg["family"]
    for n in families.CONTRACT:
        if n.isupper():
            assert getattr(fam, n)
        else:
            assert callable(getattr(fam, n)), n
    assert isinstance(fam.ENTRY, str)
    assert all(len(r) == 4 for r in fam.READINGS)
    # the configuration's own reference plans the net, not one by import
    assert families.reference(cfg).__name__.endswith("." + cfg["reference"])


@pytest.mark.parametrize("cell", CELLS)
def test_limits_hold_exactly_the_familys_graded_names(cell):
    _, cfg, _, limits, _ = run_mod.resolve(cell)
    fam = families.load(cfg)
    assert set(limits) - {"readings"} == set(fam.GRADED)
    assert all(limits[k] > 0 for k in fam.GRADED)


@pytest.mark.parametrize("family", ["classifier_rows", "regression_rows",
                                    "token_rows", "routed_token_rows",
                                    "balanced_token_rows",
                                    "mtp_token_rows", "ssd_token_rows"])
def test_family_module_keeps_the_contract(family):
    fam = families.load({"name": family, "family": family})
    assert [n for n in families.CONTRACT if not hasattr(fam, n)] == []
    assert isinstance(fam.STATE_LEAVES, tuple) and fam.GRADED


def test_token_rows_has_the_mechanisms_own_readings():
    fam = families.load({"name": "t", "family": "token_rows"})
    names = [r[0] for r in fam.READINGS]
    for want in ("bf16", "fp8", "half_batch", "state_unchanged",
                 "pass_left_out", "no_doc_cut"):
        assert want in names
    assert fam.ENTRY == "run_window_indexed"
    assert fam.row_tokens({}, {"seq_len": 4096}) == 4096


def test_routed_token_rows_compares_under_one_choice():
    fam = families.load({"name": "t", "family": "routed_token_rows"})
    names = [r[0] for r in fam.READINGS]
    for want in ("bf16", "fp8", "window_left_out", "rope_on_global",
                 "weights_over_held"):
        assert want in names
    for want in ("route_flip_share", "flip_margin_p999"):
        assert want in fam.GRADED
    assert fam.ENTRY == "run_window_indexed"
    # what token_rows gives by import is token_rows' own
    token_rows = families.load({"name": "t", "family": "token_rows"})
    assert fam.make_data is token_rows.make_data
    assert fam.loader is token_rows.loader


def test_balanced_token_rows_grades_the_bias_beside_the_routed_numbers():
    fam = families.load({"name": "t", "family": "balanced_token_rows"})
    names = [r[0] for r in fam.READINGS]
    assert names == ["bf16", "fp8", "gate_left_out", "qk_norm_left_out",
                     "shared_left_out", "bias_left_out_of_choice",
                     "bias_in_weights", "rope_on_full", "centring_left_out"]
    routed = families.load({"name": "t", "family": "routed_token_rows"})
    assert fam.GRADED[:len(routed.GRADED)] == routed.GRADED
    for want in ("choice_bias_tilt", "weight_share_gap", "bias_gap"):
        assert want in fam.GRADED
    # what the routed family gives by import is its own
    assert fam.make_data is routed.make_data and fam.plan is routed.plan
    assert fam.loader is routed.loader and fam.release is routed.release


def test_ssd_token_rows_is_the_balanced_job_with_its_own_readings():
    fam = families.load({"name": "t", "family": "ssd_token_rows"})
    names = [r[0] for r in fam.READINGS]
    assert names == ["bf16", "fp8", "reset_left_out",
                     "conv_across_documents", "norm_whole_width",
                     "d_skip_left_out", "relu_not_squared",
                     "shared_left_out", "bias_left_out_of_choice",
                     "centring_left_out"]
    balanced = families.load({"name": "t", "family": "balanced_token_rows"})
    assert fam.GRADED == balanced.GRADED
    # what the balanced family gives by import is its own
    for name in ("make_data", "loader", "feed", "follow", "numbers",
                 "in_place", "plan"):
        assert getattr(fam, name) is getattr(balanced, name), name


def test_readme_lists_every_name_of_the_contract():
    with open(os.path.join(BENCH, "README.md")) as f:
        text = f.read()
    section = text[text.index("## The family contract"):]
    named = set(re.findall(r"`([A-Za-z_]+)(?:\([^`]*\))?(?: -> [^`]*)?`",
                           section))
    assert not [n for n in families.CONTRACT if n not in named]


def test_a_family_that_is_not_there_is_an_error():
    with pytest.raises(SystemExit):
        families.load({"name": "x", "family": "no_such_family"})
    with pytest.raises(SystemExit):
        families.load({"name": "x"})


def test_the_harness_reaches_data_and_comparison_through_the_family_alone():
    for name in ("run.py", "rehearse.py", "calibrate.py", "tables.py",
                 os.path.join("lib", "job.py"),
                 os.path.join("lib", "compare.py")):
        with open(os.path.join(BENCH, name)) as f:
            text = f.read()
        for word in ("layers_net", "make_images", "register_loader",
                     "compare.follow", "compare.numbers", "n_classes",
                     "confusion"):
            assert word not in text, (name, word)
