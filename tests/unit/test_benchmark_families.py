"""Every configuration names a job family that keeps the contract, and every
cell's limits are its family's graded names.  No job runs and no device is
touched: modules are imported and files read.

The tier-1 copy of ``benchmarks/tests/test_families.py`` (which is not part
of tier-1), so that tier-1 guards ``token_rows``, ``classifier_rows`` and the
witness ``regression_rows`` against ``families.CONTRACT``."""
import json
import os
import re

import pytest

from benchmarks import families
from benchmarks import run as run_mod

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
                                    "token_rows", "routed_token_rows"])
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
