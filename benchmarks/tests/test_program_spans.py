"""The per-epoch span arithmetic, on a hand-made nest (every number by
hand) and on the span list one chip run recorded."""
import json
import os

import pytest

from benchmarks.lib import program_spans as ps
from znicz_tpu.core import telemetry

HERE = os.path.dirname(os.path.abspath(__file__))

#: (name, start, dur, id, parent, attrs); markers at 100, 1100, 2100
MARKS = [("loader.epoch_end", t, 0, 0, 0, {"epoch": i})
         for i, t in enumerate((100, 1100, 2100))] \
    + [("profiler.leak_suspect", 1500, 0, 0, 0, {})]
HAND = [
    # straddles the first marker: it starts before, so it is not counted
    ("unit.fused_trainer", 50, 200, 1, 0, {}),
    ("trainer.dispatch", 120, 30, 2, 1, {}),
    ("unit.loader", 300, 200, 3, 0, {}),
    ("loader.fill", 320, 150, 4, 3, {"clazz": "validation"}),
    ("unit.fused_trainer", 600, 400, 5, 0, {}),
    ("fused.window", 610, 380, 6, 5, {"window": 1}),
    ("trainer.collect", 620, 100, 7, 6, {"window": 1}),
    ("loader.fill", 640, 50, 8, 7, {"clazz": "train", "window": 1}),
    ("trainer.dispatch", 730, 40, 9, 6, {"window": 1}),
    ("trainer.readback", 780, 200, 10, 6, {"window": 1}),
    # the second epoch: the same again, 1000 later, and a window that the
    # last marker cuts from its children
    ("unit.loader", 1300, 200, 13, 0, {}),
    ("loader.fill", 1320, 150, 14, 13, {"clazz": "validation"}),
    ("unit.fused_trainer", 1600, 400, 15, 0, {}),
    ("fused.window", 1610, 380, 16, 15, {"window": 2}),
    ("trainer.collect", 1620, 100, 17, 16, {"window": 2}),
    ("trainer.dispatch", 1730, 40, 19, 16, {"window": 2}),
    ("trainer.readback", 1780, 200, 20, 16, {"window": 2}),
    ("unit.fused_trainer", 2050, 300, 25, 0, {}),
    ("fused.window", 2060, 280, 26, 25, {"window": 3}),
    ("trainer.collect", 2070, 50, 27, 26, {"window": 3}),
    ("trainer.dispatch", 2130, 40, 29, 26, {"window": 3}),
    ("trainer.readback", 2180, 150, 30, 26, {"window": 3}),
]


def test_cut_keeps_the_last_whole_epochs():
    kept, lo, hi = ps.cut(HAND, MARKS, 2)
    assert (lo, hi) == (100, 2100)
    assert [s[3] for s in kept][:3] == [2, 3, 4]
    assert 1 not in [s[3] for s in kept]            # started before lo
    assert {25, 26, 27} <= {s[3] for s in kept}     # started before hi
    assert 29 not in [s[3] for s in kept]           # started after hi
    kept1, lo1, hi1 = ps.cut(HAND, MARKS, 1)
    assert (lo1, hi1) == (1100, 2100)
    assert ps.cut(HAND, MARKS, 3) is None           # three need four markers
    assert ps.cut(HAND, MARKS, 0) is None
    assert ps.cut(HAND, [], 1) is None


def test_self_times_are_taken_on_the_whole_ring():
    own = telemetry.self_times(HAND)
    assert own[6] == 380 - 100 - 40 - 200
    assert own[7] == 100 - 50
    assert own[3] == 50 and own[5] == 20
    # window 3 is cut from its dispatch and readback by the last marker,
    # and still does not count them as its own
    assert own[26] == 280 - 50 - 40 - 150


def test_per_epoch_totals_by_name():
    kept, lo, hi = ps.cut(HAND, MARKS, 2)
    own = telemetry.self_times(HAND)
    # dispatch: ids 2, 9, 19 start inside; 29 does not
    assert ps.total_ms(kept, ps.named("trainer.dispatch"), 2) == \
        pytest.approx((30 + 40 + 40) / 1e6 / 2)
    # collect's self time leaves the loader's fill inside it out
    assert ps.total_ms(kept, ps.named("trainer.collect"), 2, own=own) == \
        pytest.approx((50 + 100 + 50) / 1e6 / 2)
    fills = ps.total_ms(
        kept, lambda s: s[0] == "loader.fill"
        and s[5].get("clazz") != "train", 2)
    assert fills == pytest.approx(300 / 1e6 / 2)
    assert ps.total_ms(kept, ps.named("trainer.wait"), 2) is None


def test_unspanned_is_what_no_leaf_covers():
    # leaves that reach into [100, 2100]: dispatch 120-150, fill 320-470,
    # fill 640-690 (inside collect 620-720, a parent: not a leaf),
    # dispatch 730-770, readback 780-980, fill 1320-1470, collect
    # 1620-1720 (no child: a leaf), dispatch 1730-1770, readback
    # 1780-1980, collect 2070-2120 cut at 2100
    covered = 30 + 150 + 50 + 40 + 200 + 150 + 100 + 40 + 200 + 30
    assert ps.unspanned_ns(HAND, 100, 2100) == 2000 - covered
    assert ps.unspanned_ns([], 0, 10) == 10
    # overlapping leaves (two threads) are not counted twice
    two = [("a", 0, 60, 1, 0, {}), ("b", 40, 40, 2, 0, {})]
    assert ps.unspanned_ns(two, 0, 100) == 20


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "small_spans.json")) as f:
        rec = json.load(f)
    rec["spans"] = [tuple(s) for s in rec["spans"]]
    rec["instants"] = [tuple(s) for s in rec["instants"]]
    return rec


def test_recorded_run_adds_up(recorded):
    n = recorded["epochs"]
    kept, lo, hi = ps.cut(recorded["spans"], recorded["instants"], n)
    own = telemetry.self_times(recorded["spans"])
    epoch_ms = (hi - lo) / 1e6 / n
    assert 850 < epoch_ms < 950
    # self times of everything that started inside add up to the epoch
    # (one thread, so nothing overlaps), but for what no span covers
    total = sum(own[s[3]] for s in kept if s[0] != "workflow.run")
    top = ps.unspanned_ns(recorded["spans"], lo, hi) / 1e6 / n
    assert total / 1e6 / n == pytest.approx(epoch_ms, rel=0.02)
    assert top < 0.05 * epoch_ms
    # the trainer's spans against its run_time_, taken from outside
    unit = "unit." + recorded["trainer_name"]
    spans_ms = ps.total_ms(
        kept, lambda s: s[0].startswith(("trainer.", "fused."))
        or s[0] == unit, n, own=own)
    t0, t1 = recorded["unit_time0"], recorded["unit_time1"]
    grown = 1e3 * (t1[recorded["trainer_name"]][0]
                   - t0[recorded["trainer_name"]][0]) / n
    assert spans_ms == pytest.approx(grown, rel=0.10)
    # one validation minibatch an epoch, four windows
    assert len([s for s in kept if s[0] == "trainer.valid"]) == n
    assert len([s for s in kept if s[0] == "fused.window"]) == 4 * n


def test_readers_on_the_recorded_run(recorded, monkeypatch):
    import importlib
    from znicz_tpu.core import telemetry
    monkeypatch.setattr(telemetry, "spans",
                        lambda ph="X": recorded["spans"] if ph == "X"
                        else recorded["instants"])
    ps._MEMO.clear()
    ctx = {"epochs": recorded["epochs"], "epoch_times": [0.9] * 5,
           "trainer_name": recorded["trainer_name"]}
    values = {}
    for name in ("trainer_host_ms_per_epoch", "window_stage_ms_per_epoch",
                 "window_dispatch_ms_per_epoch", "valid_wait_ms_per_epoch",
                 "valid_fill_ms_per_epoch", "epoch_unspanned_ms",
                 "setup_dispatch_s"):
        reader = importlib.import_module("benchmarks.layer_metrics." + name)
        values[name] = reader.read(ctx)
    ps._MEMO.clear()
    assert all(v is not None and v > 0 for v in values.values()), values
    # the host's own work is a small part of the trainer's 690 ms: the
    # rest it spends blocked on the device
    assert 20 < values["trainer_host_ms_per_epoch"] < 80
    assert 150 < values["valid_fill_ms_per_epoch"] < 250
    assert values["window_dispatch_ms_per_epoch"] < 60
    # the wait for the 633 MB copy and the forward, not the 1 ms enqueue
    assert 100 < values["valid_wait_ms_per_epoch"] < 180
    # this run compiled: 52.6 s in the window program's first call and
    # 14.0 s in the validation forward's, milliseconds in the seven others
    assert values["setup_dispatch_s"] == pytest.approx(66.668, rel=1e-4)


def test_a_program_without_spans_reads_nothing(monkeypatch):
    import importlib
    from znicz_tpu.core import telemetry
    monkeypatch.setattr(telemetry, "spans", lambda ph="X": [])
    ps._MEMO.clear()
    ctx = {"epochs": 5, "epoch_times": [0.9] * 5,
           "trainer_name": "fused_trainer"}
    for name in ("trainer_host_ms_per_epoch", "window_stage_ms_per_epoch",
                 "window_dispatch_ms_per_epoch", "valid_wait_ms_per_epoch",
                 "valid_fill_ms_per_epoch", "epoch_unspanned_ms",
                 "setup_dispatch_s"):
        reader = importlib.import_module("benchmarks.layer_metrics." + name)
        assert reader.read(ctx) is None, name
    # and the parent commit's module, which has no spans() at all
    monkeypatch.delattr(telemetry, "spans")
    ps._MEMO.clear()
    for name in ("trainer_host_ms_per_epoch", "setup_dispatch_s"):
        reader = importlib.import_module("benchmarks.layer_metrics." + name)
        assert reader.read(ctx) is None, name
    ps._MEMO.clear()
