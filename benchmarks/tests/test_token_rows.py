"""``token_rows`` on the CPU at a toy width (``tests/tiny_lm/``: the
``looped_lm`` sample with two layers, four passes, grouped key-value heads,
float32): ``correct`` true for a sound run, and false under each of the
family's readings and under faults planted beneath the timed path."""
import json
import os

import pytest

from benchmarks import calibrate, families, rehearse
from benchmarks.lib import compare
from benchmarks.tests import faults

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny_lm")
CELL = {"name": "tiny_looped_lm.train_s24_b2", "config": "tiny_looped_lm",
        "traffic": "train_s24_b2", "chips": 1}


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
    assert len(exact) == 7 and all(v == 0 for v in exact.values()), exact


def test_only_the_last_checked_window_is_sampled_and_copies_wait_on_the_host(
        parts, monkeypatch):
    """The first checked window runs the plain program that is timed (no
    logit sample asked of it), and the job's copies of the parameters and
    of the optimizer state are host arrays by the time the second has
    run, the state cut to the first moment."""
    import numpy
    fam = families.load(parts[1])
    seen = {"kept": []}
    keep, leaf_numbers = fam.keep, fam.leaf_numbers

    def spy_keep(stats, rec):
        seen["kept"].append((rec["sample"] is not None,
                             "logits_sample" in stats))
        return keep(stats, rec)

    def spy_numbers(cfg, mix, p0, state1, params_end):
        seen["p0"] = {type(a) for layer in p0 for a in layer.values()}
        seen["state1"] = {(tuple(st), type(st["m"]))
                          for layer in state1 for st in layer.values()}
        return leaf_numbers(cfg, mix, p0, state1, params_end)

    monkeypatch.setattr(fam, "keep", spy_keep)
    monkeypatch.setattr(fam, "leaf_numbers", spy_numbers)
    correct, nums = rehearse.tiny_cell(*parts)
    assert correct, failed(nums)
    assert seen["kept"] == [(False, False), (True, True)]
    assert seen["p0"] == {numpy.ndarray}
    assert seen["state1"] == {(("m",), numpy.ndarray)}


def test_the_reference_in_its_own_place_reads_nought(parts,
                                                     feed_and_reference):
    fam, feed, f32 = feed_and_reference
    nums, _ = fam.graded(fam.in_place(feed, f32), f32, parts[3])
    assert all(v == 0 for _, v, _ in nums), nums


@pytest.mark.parametrize("reading", [r for r in families.load(
    {"name": "t", "family": "token_rows"}).READINGS if r[0] != "bf16"],
    ids=lambda r: r[0])
def test_each_reading_fails_a_limit(parts, feed_and_reference, reading):
    """The fp8 control and every planted fault in the reference's place
    (float32 arithmetic here, so that the fault alone speaks)."""
    _, cfg, mix, limits = parts
    fam, feed, f32 = feed_and_reference
    name, mode, fault, _ = reading
    other = fam.follow(cfg, mix, feed, mode=mode if fault is None else "f32",
                       fault=fault)
    nums, _ = fam.graded(fam.in_place(feed, other), f32, limits)
    assert not compare.decide(nums), name
    got = dict((n, v) for n, v, _ in nums)
    if name == "state_unchanged":
        assert got["dparam_worst_leaf"] == pytest.approx(1.0, abs=1e-6)
        assert got["m1_worst_leaf"] > 0.1
    if name in ("pass_left_out", "no_doc_cut"):
        assert "logit_rel_diff" in failed(nums)
        assert "loss_worst_step" in failed(nums)


def test_state_left_unchanged_under_the_timed_path_is_not_correct(parts):
    correct, nums = rehearse.tiny_cell(*parts,
                                       sabotage=faults.state_unchanged)
    assert not correct
    assert dict((n, v) for n, v, _ in nums)["dparam_worst_leaf"] == \
        pytest.approx(1.0, abs=1e-3)


def test_half_of_the_batch_left_out_is_not_correct(parts):
    correct, nums = rehearse.tiny_cell(*parts, sabotage=faults.half_batch)
    assert not correct
    assert "window_rows_gap" in failed(nums)
    assert "window_tokens_gap" in failed(nums)
