"""``mtp_token_rows`` on the CPU at a toy width (``tests/tiny_latent/``:
the ``latent_moe_lm`` sample with one dense and two expert layers under
latent attention, 8 experts of which the share holds four, top-2 by sigmoid
scores plus a selection bias, a shared expert, and a multi-token prediction
module with its head, float32): ``correct`` true for a sound run, false
under the control and under each planted fault, each compared under its own
choice of experts."""
import json
import os

import numpy
import pytest

from benchmarks import calibrate, families, rehearse
from benchmarks.lib import compare
from benchmarks.tests import faults

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "tiny_latent")
CELL = {"name": "tiny_latent_lm.train_s32_b2", "config": "tiny_latent_lm",
        "traffic": "train_s32_b2", "chips": 1}
#: the number that has to catch each fault, whatever else does
CATCHES = {"rope_whole_head": "logit_rel_diff",
           "kv_norm_left_out": "logit_rel_diff",
           "mtp_shift_one": "mtp_loss_worst_step",
           "mtp_head_apart": "m1_worst_leaf",
           "mtp_weight_one": "loss_worst_step"}


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def latent_parts():
    return (CELL, _load("configs", CELL["config"] + ".json"),
            _load("traffic", CELL["traffic"] + ".json"),
            _load("limits", CELL["name"] + ".json"))


@pytest.fixture(scope="module")
def latent_feed_and_reference(latent_parts):
    _, cfg, mix, _ = latent_parts
    fam = families.load(cfg)
    feed = calibrate.seeded_feed(fam, cfg, mix, 2147483659)
    return fam, feed, fam.follow(cfg, mix, feed)


def failed(nums):
    return sorted(n for n, v, lim in nums if not v <= lim)


def test_sound_latent_run_is_correct_and_counts_exactly(latent_parts):
    correct, nums = rehearse.tiny_cell(*latent_parts)
    assert correct, failed(nums)
    exact = {n: v for n, v, lim in nums if lim == 0.0}
    assert len(exact) == 11 and all(v == 0 for v in exact.values()), exact


def test_the_latent_reference_in_its_own_place_reads_nought(
        latent_parts, latent_feed_and_reference):
    fam, feed, f32 = latent_feed_and_reference
    nums, where = fam.graded(fam.in_place(feed, f32), f32, latent_parts[3])
    assert all(v == 0 for _, v, _ in nums), nums
    # both heads graded something, the second fewer than the first
    win = f32["windows"][0]
    assert 0 < win["mtp_n_err"][1] < win["n_err"][1]


@pytest.mark.parametrize("reading", [r for r in families.load(
    {"name": "t", "family": "mtp_token_rows"}).READINGS
    if r[0] != "bf16"], ids=lambda r: r[0])
def test_each_latent_reading_fails_a_limit(latent_parts,
                                           latent_feed_and_reference,
                                           reading):
    """The fp8 control and every planted fault in the reference's place
    (float32 arithmetic here, so that the fault alone speaks), each against
    the float32 reference forced to the reading's own choice."""
    _, cfg, mix, limits = latent_parts
    fam, feed, f32 = latent_feed_and_reference
    name, mode, fault, _ = reading
    other = fam.follow(cfg, mix, feed, mode=mode if fault is None else "f32",
                       fault=fault)
    nums, where = fam.graded(fam.in_place(feed, other), f32, limits)
    assert not compare.decide(nums), (name, nums)
    if fault is not None:
        assert CATCHES[fault] in failed(nums), (where, nums)
    got = dict((n, v) for n, v, _ in nums)
    if fault in ("mtp_head_apart", "mtp_weight_one"):
        # the forward is the reference's: the first step reads alike
        assert numpy.isfinite(got["logit_rel_diff"])


def test_latent_state_left_unchanged_is_not_correct(latent_parts):
    correct, nums = rehearse.tiny_cell(*latent_parts,
                                       sabotage=faults.state_unchanged)
    assert not correct
    got = dict((n, v) for n, v, _ in nums)
    assert got["dparam_worst_leaf"] == pytest.approx(1.0, abs=1e-3)
