"""The trace reduction on a small recorded trace, every number by hand."""
import json
import os

import pytest

from benchmarks.lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(HERE, "small_trace.json")) as f:
        return json.load(f)


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]


def test_busy_is_the_union_not_the_sum(small):
    # device 0: [1000,1800] (two overlapping ops), [2000,2300],
    # [3000,3400], [5000,5100] -> 800 + 300 + 400 + 100
    assert trace.busy_ns(small["devices"]["/device:TPU:0"]) == 1600
    assert trace.busy_ns(small["devices"]["/device:TPU:1"]) == 500


def test_gaps_and_their_names(small):
    evs = small["devices"]["/device:TPU:0"]
    assert trace.gaps(evs) == [[1800, 2000], [2300, 3000], [3400, 5000]]
    # the loader covers 2300-3000 whole; the decision covers most of
    # 3400-5000 and the evaluator only a sliver; nothing covers 1800-2000
    names = [trace.attribute_gap(g, small["host"]) for g in trace.gaps(evs)]
    assert names == ["unattributed", "bench.unit.loader",
                     "bench.unit.decision"]
    assert trace.gaps(evs, lo=0, hi=6000)[0] == [0, 1000]
    assert trace.gaps(evs, lo=0, hi=6000)[-1] == [5100, 6000]


def test_reduce_trace(small):
    red = trace.reduce_trace(small, window_s=1e-5)
    assert red["busy_s_busiest"] == pytest.approx(1600e-9)
    assert red["busy_s_mean"] == pytest.approx((1600e-9 + 500e-9) / 2)
    # the busiest device's collective time on its serial ops line
    assert red["collective_exposed_s"] == pytest.approx(300e-9)
    ops = dict(red["device_ops"])
    assert ops["%fusion.1"] == pytest.approx(800e-9)
    assert red["device_ops"][0][0] == "%fusion.1"
    idle = dict(red["idle_gaps"])
    assert idle["bench.unit.decision"] == pytest.approx(1600e-9)
    assert idle["bench.unit.loader"] == pytest.approx(700e-9)
    idle_pct = 100.0 * (1 - red["busy_s_busiest"] / red["window_s"])
    assert idle_pct == pytest.approx(84.0)


def test_wrappers_and_collectives_by_name():
    assert trace.is_wrapper("%while.4 = (f32[2]) while(...)")
    assert not trace.is_wrapper("%fusion.7 = bf16[8] fusion(...)")
    assert trace.is_collective("%all-reduce-start.1 = f32[4]")
    assert not trace.is_collective("%fusion.3 = f32[4] fusion(%all-reduce.1)")


def test_no_device_events_is_nothing_to_read():
    assert trace.reduce_trace({"devices": {}, "host": []}, 1.0) is None
