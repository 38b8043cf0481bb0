"""``routed_token_rows`` on the CPU at a toy width (``tests/tiny_routed/``:
the ``routed_lm`` sample with four layers, 8 experts of which the share
holds four, top-2, a window of 8, float32): ``correct`` true for a sound
run, false under the control and under each planted fault, each compared
under its own choice of experts."""
import json
import os

import numpy
import pytest

from benchmarks import calibrate, families, rehearse
from benchmarks.lib import compare
from benchmarks.tests import faults

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "tiny_routed")
CELL = {"name": "tiny_routed_lm.train_s32_b2", "config": "tiny_routed_lm",
        "traffic": "train_s32_b2", "chips": 1}


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


def test_the_reference_in_its_own_place_reads_nought(parts,
                                                     feed_and_reference):
    fam, feed, f32 = feed_and_reference
    nums, _ = fam.graded(fam.in_place(feed, f32), f32, parts[3])
    assert all(v == 0 for _, v, _ in nums), nums


@pytest.mark.parametrize("reading", [r for r in families.load(
    {"name": "t", "family": "routed_token_rows"}).READINGS
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
    if fault is not None:
        # a fault after the router moves no choice: the router reads the
        # stream, which the first step's fault has not yet changed much
        assert numpy.isfinite(got["flip_margin_p999"])
    if name in ("window_left_out", "rope_on_global", "weights_over_held"):
        assert "logit_rel_diff" in failed(nums), where


def test_a_choice_that_is_none_reads_infinite(parts, feed_and_reference):
    """A route with an expert named twice is no choice of ``top_k``
    different experts: the margin reads infinite and the run is not
    correct."""
    _, cfg, mix, limits = parts
    fam, feed, f32 = feed_and_reference
    broken = fam.in_place(feed, f32)
    wins = [dict(w, stats=dict(w["stats"], route=w["stats"]["route"].copy()))
            for w in broken["windows"]]
    wins[0]["stats"]["route"][0, 0, 0, 1] = wins[0]["stats"]["route"][
        0, 0, 0, 0]
    broken = dict(broken, windows=wins)
    forced = fam.follow(cfg, mix, broken)
    nums, _ = fam.graded(broken, forced, limits)
    got = dict((n, v) for n, v, _ in nums)
    assert numpy.isinf(got["flip_margin_p999"])
    assert not compare.decide(nums)


def test_state_left_unchanged_under_the_timed_path_is_not_correct(parts):
    correct, nums = rehearse.tiny_cell(*parts,
                                       sabotage=faults.state_unchanged)
    assert not correct
    assert dict((n, v) for n, v, _ in nums)["dparam_worst_leaf"] == \
        pytest.approx(1.0, abs=1e-3)
