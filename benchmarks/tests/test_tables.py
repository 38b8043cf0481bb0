"""The rows of PERF.md's two tables, on hand-made inputs."""
import pytest

from benchmarks import tables
from benchmarks.tests.test_program_spans import HAND, MARKS


def test_host_rows_split_a_name_by_its_parents():
    ring = HAND + [("trainer.valid", 1000, 90, 40, 0, {}),
                   ("trainer.readback", 1010, 60, 41, 40, {})]
    rows = {r[0]: r for r in tables.host_rows(ring, MARKS, 2)}
    # readback stands under fused.window and under trainer.valid
    assert rows["trainer.readback (in fused.window)"][1:4] == \
        pytest.approx((1.0, 400 / 1e6 / 2, 400 / 1e6 / 2))
    assert rows["trainer.readback (in trainer.valid)"][1:4] == \
        pytest.approx((0.5, 60 / 1e6 / 2, 60 / 1e6 / 2))
    # collect's self time leaves its loader.fill child out
    assert rows["trainer.collect"][2:4] == \
        pytest.approx((250 / 1e6 / 2, 200 / 1e6 / 2))
    assert rows["trainer.valid"][3] == pytest.approx(30 / 1e6 / 2)
    # self times and the rest add up to the epoch
    assert sum(r[4] for r in rows.values()) == pytest.approx(100.0)
    assert rows["outside these spans"][3] > 0
    assert tables.host_rows(HAND, MARKS, 5) is None


def test_setup_rows_are_the_run_before_the_timed_epochs():
    # one timed epoch: it starts at the marker at 1100, the run at 50
    ring = [("workflow.run", 10, 5000, 99, 0, {})] + HAND
    rows = {r[0]: r for r in tables.setup_rows(ring, MARKS, 1)}
    # dispatch stands under a unit and under a window: ids 2 and 9
    assert rows["trainer.dispatch (in unit.fused_trainer)"][1:4] == \
        pytest.approx((1.0, 30 / 1e6, 30 / 1e6))
    assert rows["trainer.dispatch (in fused.window)"][1:4] == \
        pytest.approx((1.0, 40 / 1e6, 40 / 1e6))
    assert rows["loader.fill (in unit.loader)"][1:3] == \
        pytest.approx((1.0, 150 / 1e6))
    assert "workflow.run" not in rows
    # self times and the rest add up to the 1050 ns from 50 to 1100
    assert sum(r[3] for r in rows.values()) == pytest.approx(1050 / 1e6)
    assert sum(r[4] for r in rows.values()) == pytest.approx(100.0)
    assert tables.setup_rows(HAND, MARKS, 5) is None


def test_device_rows_set_scopes_beside_their_least_time():
    by_scope = {(True, "L00.conv", False): 0.020,
                (True, "L00.conv", True): 0.040,
                (False, "L00.conv", False): 0.006,
                (True, "update.L00", False): 0.002,
                (True, "gather", False): 0.010,
                (True, None, False): 0.008}
    least = {0: (1.0, 2.0, 0.5)}
    rows = {r[0]: r for r in tables.device_rows(by_scope, 10, 3, least)}
    assert rows["L00.conv"][1:] == pytest.approx((2.0, 4.0, 3.0, 2.0, 2.0))
    assert rows["update.L00"][1:5] == pytest.approx((0.2, 0.0, 0.5, 0.4))
    assert rows["gather"][3:5] == (None, None)
    assert rows["no scope"][1] == pytest.approx(0.8)
    assert list(rows)[-1] == "no scope"


def test_least_ms_follows_the_cost_model():
    from benchmarks import families, layer_costs, run as run_mod
    _, cfg, mix, _, _ = run_mod.resolve("alexnet.train_b1024")
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    net = families.load(cfg).plan(cfg, mix)
    least = tables.least_ms(net, 1024, peaks)
    total, _ = layer_costs.least_seconds(net, 1024, peaks, train=True)
    assert sum(sum(v) for v in least.values()) == pytest.approx(1e3 * total)
