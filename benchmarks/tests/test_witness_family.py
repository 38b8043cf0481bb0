"""The seam takes a second family by new files alone: the MSE objective's
sliced window entry (``FusedNet.run_window_mse_sliced``), which has targets
in place of labels, no error count and no confusion matrix, driven through
``job.run_cell`` and its family's comparison on the CPU at a tiny size.
``correct`` comes out true, and false with the state left unchanged and with
half of every batch left out.  It is no cell and never runs on the chip."""
import json
import os

import pytest

from benchmarks import families, rehearse
from benchmarks.tests import faults

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "witness")
CELL = {"name": "approximator.train_b8", "config": "approximator",
        "traffic": "train_b8", "chips": 1}


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def parts():
    return (CELL, _load("configs", CELL["config"] + ".json"),
            _load("traffic", CELL["traffic"] + ".json"),
            _load("limits", CELL["name"] + ".json"))


def half_batch(orig, net, starts, batch, batch_sizes, hypers_s, final):
    """The second half of every minibatch is left out; the program's own
    masking then takes the mean over the rest."""
    return orig(starts, batch, [int(s) // 2 for s in batch_sizes], hypers_s,
                final=final)


def failed(nums):
    return sorted(n for n, v, lim in nums if not v <= lim)


def test_the_witness_keeps_the_contract_and_its_limits_are_its_graded(parts):
    _, cfg, _, limits = parts
    fam = families.load(cfg)
    assert fam.ENTRY == "run_window_mse_sliced"
    assert set(limits) - {"readings"} == set(fam.GRADED)


def test_sound_run_is_correct(parts):
    correct, nums = rehearse.tiny_cell(*parts)
    assert correct, failed(nums)
    exact = {n: v for n, v, lim in nums if lim == 0.0}
    assert exact and all(v == 0 for v in exact.values()), exact


def test_state_left_unchanged_is_not_correct(parts):
    correct, nums = rehearse.tiny_cell(*parts,
                                       sabotage=faults.state_unchanged)
    assert not correct
    assert dict((n, v) for n, v, _ in nums)["dparam_worst_leaf"] == \
        pytest.approx(1.0, abs=1e-3)


def test_half_of_the_batch_left_out_is_not_correct(parts):
    correct, nums = rehearse.tiny_cell(*parts, sabotage=half_batch)
    assert not correct
    assert "mse_sum_gap" in failed(nums)


def test_the_reference_in_the_programs_place_reads_nought(parts):
    """``in_place`` over ``calibrate.py``'s seeded feed: the reference
    against itself, and against itself with half a batch left out."""
    from benchmarks import calibrate
    _, cfg, mix, limits = parts
    fam = families.load(cfg)
    feed = calibrate.seeded_feed(fam, cfg, mix, 2147483659)
    f32 = fam.follow(cfg, mix, feed)
    nums, _ = fam.graded(fam.in_place(feed, f32), f32, limits)
    assert all(v == 0 for _, v, _ in nums), nums
    (name, mode, fault, _), = fam.READINGS
    other = fam.follow(cfg, mix, feed, mode=mode, fault=fault)
    nums, _ = fam.graded(fam.in_place(feed, other), f32, limits)
    assert failed(nums), nums
