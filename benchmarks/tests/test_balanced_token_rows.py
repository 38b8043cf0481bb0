"""``balanced_token_rows`` on the CPU at a toy width (``tests/tiny_balanced/``:
the ``shared_moe_lm`` sample with one dense and four expert layers, 8
experts of which the share holds four, top-2 by sigmoid scores plus a
selection bias, a shared expert, gated and normed attention, float32):
``correct`` true for a sound run, false under the control and under each
planted fault, each compared under its own choice of experts."""
import json
import os

import numpy
import pytest

from benchmarks import calibrate, families, rehearse
from benchmarks.lib import compare
from benchmarks.tests import faults

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "tiny_balanced")
CELL = {"name": "tiny_balanced_lm.train_s32_b2", "config": "tiny_balanced_lm",
        "traffic": "train_s32_b2", "chips": 1}
#: the number that has to catch each fault, whatever else does
CATCHES = {"gate_left_out": "logit_rel_diff",
           "qk_norm_left_out": "logit_rel_diff",
           "shared_left_out": "logit_rel_diff",
           "bias_left_out_of_choice": "choice_bias_tilt",
           "bias_in_weights": "weight_share_gap",
           "rope_on_full": "logit_rel_diff",
           "centring_left_out": "bias_gap"}


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def parts():
    return (CELL, _load("configs", CELL["config"] + ".json"),
            _load("traffic", CELL["traffic"] + ".json"),
            _load("limits", CELL["name"] + ".json"))


@pytest.fixture(scope="module")
def feed_and_reference(parts):
    _, cfg, mix, _ = parts
    fam = families.load(cfg)
    feed = calibrate.seeded_feed(fam, cfg, mix, 2147483659)
    return fam, feed, fam.follow(cfg, mix, feed)


def failed(nums):
    return sorted(n for n, v, lim in nums if not v <= lim)


def test_sound_run_is_correct_and_counts_exactly(parts):
    correct, nums = rehearse.tiny_cell(*parts)
    assert correct, failed(nums)
    exact = {n: v for n, v, lim in nums if lim == 0.0}
    assert len(exact) == 8 and all(v == 0 for v in exact.values()), exact
    assert dict((n, v) for n, v, _ in nums)["bias_gap"] <= 3e-8


def test_the_reference_in_its_own_place_reads_nought(parts,
                                                     feed_and_reference):
    fam, feed, f32 = feed_and_reference
    nums, where = fam.graded(fam.in_place(feed, f32), f32, parts[3])
    assert all(v == 0 for _, v, _ in nums), nums
    assert where["bias_abs_max"] > 0    # the rule has moved the biases


@pytest.mark.parametrize("reading", [r for r in families.load(
    {"name": "t", "family": "balanced_token_rows"}).READINGS
    if r[0] != "bf16"], ids=lambda r: r[0])
def test_each_reading_fails_a_limit(parts, feed_and_reference, reading):
    """The fp8 control and every planted fault in the reference's place
    (float32 arithmetic here, so that the fault alone speaks), each against
    the float32 reference forced to the reading's own choice."""
    _, cfg, mix, limits = parts
    fam, feed, f32 = feed_and_reference
    name, mode, fault, _ = reading
    other = fam.follow(cfg, mix, feed, mode=mode if fault is None else "f32",
                       fault=fault)
    assert other["forced_ref"]["pairs"] == 4 * 2 * 32 * 4
    nums, where = fam.graded(fam.in_place(feed, other), f32, limits)
    assert not compare.decide(nums), (name, nums)
    got = dict((n, v) for n, v, _ in nums)
    assert numpy.isfinite(got["flip_margin_p999"])
    if fault is not None:
        assert CATCHES[fault] in failed(nums), (where, nums)
    if fault == "centring_left_out":
        # every bias of a layer drifts alike: no choice and no weight
        # moves, so the rule's fault is told by the biases alone
        assert failed(nums) == ["bias_gap"]
    elif fault != "bias_left_out_of_choice":
        # under the reading's own choice the load and the rule are the
        # reference's: the bias reads as the forced reference's
        assert got["bias_gap"] == 0


def test_state_left_unchanged_under_the_timed_path_is_not_correct(parts):
    correct, nums = rehearse.tiny_cell(*parts,
                                       sabotage=faults.state_unchanged)
    assert not correct
    got = dict((n, v) for n, v, _ in nums)
    assert got["dparam_worst_leaf"] == pytest.approx(1.0, abs=1e-3)
    # the biases stayed at nought too
    assert got["bias_gap"] > 0.01
