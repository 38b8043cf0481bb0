"""``ssd_token_rows`` on the CPU at a toy width (``tests/tiny_ssd/``: the
``hybrid_moe_lm`` sample over the pattern ``MEM*E``, two Mamba-2 mixers at
chunks of 8 over rows of 32 packed documents, attention with no position,
8 relu^2 experts of which the share holds four, top-2 by sigmoid scores plus
a selection bias, a shared expert, float32): ``correct`` true for a sound
run, false under the control and under each planted fault, each compared
under its own choice of experts."""
import json
import os

import pytest

from benchmarks import calibrate, families, rehearse
from benchmarks.lib import compare
from benchmarks.tests import faults

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny_ssd")
CELL = {"name": "tiny_ssd_lm.train_s32_b2", "config": "tiny_ssd_lm",
        "traffic": "train_s32_b2", "chips": 1}
#: the number that has to catch each fault, whatever else does
CATCHES = {"reset_left_out": "dparam_worst_leaf",
           "conv_across_documents": "logit_rel_diff",
           "norm_whole_width": "logit_rel_diff",
           "d_skip_left_out": "logit_rel_diff",
           "relu_not_squared": "logit_rel_diff",
           "shared_left_out": "m1_worst_leaf",
           "bias_left_out_of_choice": "choice_bias_tilt",
           "centring_left_out": "bias_gap"}


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ssd_parts():
    return (CELL, _load("configs", CELL["config"] + ".json"),
            _load("traffic", CELL["traffic"] + ".json"),
            _load("limits", CELL["name"] + ".json"))


@pytest.fixture(scope="module")
def ssd_feed_and_reference(ssd_parts):
    _, cfg, mix, _ = ssd_parts
    fam = families.load(cfg)
    feed = calibrate.seeded_feed(fam, cfg, mix, 2147483659)
    return fam, feed, fam.follow(cfg, mix, feed)


def failed(nums):
    return sorted(n for n, v, lim in nums if not v <= lim)


def test_sound_ssd_run_is_correct_and_counts_exactly(ssd_parts):
    correct, nums = rehearse.tiny_cell(*ssd_parts)
    assert correct, failed(nums)
    exact = {n: v for n, v, lim in nums if lim == 0.0}
    assert len(exact) == 8 and all(v == 0 for v in exact.values()), exact


def test_the_ssd_reference_in_its_own_place_reads_nought(
        ssd_parts, ssd_feed_and_reference):
    fam, feed, f32 = ssd_feed_and_reference
    nums, _ = fam.graded(fam.in_place(feed, f32), f32, ssd_parts[3])
    assert all(v == 0 for _, v, _ in nums), nums


@pytest.mark.parametrize("reading", [r for r in families.load(
    {"name": "t", "family": "ssd_token_rows"}).READINGS
    if r[0] != "bf16"], ids=lambda r: r[0])
def test_each_ssd_reading_fails_a_limit(ssd_parts, ssd_feed_and_reference,
                                        reading):
    """The fp8 control and every planted fault in the reference's place
    (float32 arithmetic here, so that the fault alone speaks), each against
    the float32 reference forced to the reading's own choice."""
    _, cfg, mix, limits = ssd_parts
    fam, feed, f32 = ssd_feed_and_reference
    name, mode, fault, _ = reading
    other = fam.follow(cfg, mix, feed, mode=mode if fault is None else "f32",
                       fault=fault)
    nums, where = fam.graded(fam.in_place(feed, other), f32, limits)
    assert not compare.decide(nums), (name, nums)
    if fault is not None:
        assert CATCHES[fault] in failed(nums), (where, nums)


def test_ssd_state_left_unchanged_is_not_correct(ssd_parts):
    correct, nums = rehearse.tiny_cell(*ssd_parts,
                                       sabotage=faults.state_unchanged)
    assert not correct
    got = dict((n, v) for n, v, _ in nums)
    assert got["dparam_worst_leaf"] == pytest.approx(1.0, abs=1e-3)
