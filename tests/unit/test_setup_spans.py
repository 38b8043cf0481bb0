"""Set-up's spans (ISSUE 37): jax's own trace, lowering and compile of each
program as spans of the ring, the data set's crossing timed by a thread of
its own, ``workflow.initialize`` with a child a unit, and the clock anchor
that lays the ring on a wall clock.

* with telemetry on, a tiny fused workflow records ``jax.trace`` /
  ``jax.lower`` / ``jax.compile`` under ``trainer.dispatch`` with ``fun``
  and ``cache`` set, a trace that holds another once;
* a second compile from a persistent cache reads ``cache`` ``hit``;
* ``trainer.set_dataset.crossing`` starts with ``trainer.set_dataset`` and
  ends after it starts;
* ``workflow.initialize`` holds one child per unit;
* with telemetry off no listener records, no thread starts and the ring
  stays empty.
"""

import threading
import time

import jax
import jax.numpy as jnp
import pytest

from znicz_tpu.core import compile_cache, telemetry
from tests.unit.test_spans import _run


@pytest.fixture
def tel():
    telemetry.enable()
    telemetry.reset()
    yield telemetry
    telemetry.reset()


def _joined(wf):
    """The run's spans once the crossing's thread has recorded its own."""
    thread = wf.fused_trainer.net._crossing
    thread.join(timeout=60)
    assert not thread.is_alive()
    return telemetry.spans()


def test_jax_spans_nest_under_the_dispatch_that_compiles(tmp_path, tel):
    spans = _joined(_run(tmp_path))
    by_id = {s[3]: s for s in spans}

    def parent(s):
        return by_id.get(s[4], ("",))[0]

    under = {}
    for s in spans:
        if s[0].startswith("jax."):
            assert s[5]["fun"], s
            under.setdefault(s[0], set()).add(parent(s))
    # the window program and the validation forward are traced, lowered
    # and compiled from shapes in the trainer's initialize, while the
    # parameters are drawn; their first dispatches lower and compile
    # nothing, and their trace there is jax's cache lookup, which holds
    # no other
    init = "unit.fused_trainer.initialize"
    for name in ("jax.trace", "jax.lower", "jax.compile"):
        assert init in under[name]
        assert name == "jax.trace" or not {
            "trainer.dispatch", "trainer.valid.dispatch"} & under[name]
    prepared = [s[5]["fun"] for s in spans if s[0] == "jax.compile"
                and parent(s) == init]
    assert {"jit(window_fn)", "jit(fwd_idx_at)"} <= set(prepared)
    looked_up = [s for s in spans if s[0] == "jax.trace" and parent(s) in (
        "trainer.dispatch", "trainer.valid.dispatch")]
    assert sorted(s[5]["fun"] for s in looked_up) == [
        "fwd_idx_at", "window_fn"]
    assert not [s for s in spans if s[4] in {t[3] for t in looked_up}]
    assert {s[5]["cache"] for s in spans if s[0] == "jax.compile"} == {"off"}
    # the window function's trace holds the traces of the jitted helpers
    # it calls, each inside it
    outer = next(s for s in spans if s[0] == "jax.trace"
                 and s[5]["fun"] == "window_fn")
    inner = [s for s in spans if s[4] == outer[3]]
    assert inner and all(s[0] == "jax.trace" for s in inner)
    for s in inner:
        assert outer[1] - 1000 <= s[1] and \
            s[1] + s[2] <= outer[1] + outer[2] + 1000
    # the series the listener feeds keep their meaning
    snap = tel.snapshot()
    assert snap["counters"]["jax.backend_compiles"] == sum(
        1 for s in spans if s[0] == "jax.compile")
    assert snap["counters"]["jax.traces"] == sum(
        1 for s in spans if s[0] == "jax.trace")
    assert snap["histograms"]["jax.compile_seconds"]["count"] == \
        snap["counters"]["jax.backend_compiles"]


def test_a_compile_from_the_persistent_cache_reads_hit(tmp_path, tel):
    compile_cache.enable(str(tmp_path / "xla_cache"))
    try:
        @jax.jit
        def scaled(x):
            return x * 2.5 + 0.125

        x = jnp.arange(12.0).reshape(3, 4)
        scaled(x).block_until_ready()
        jax.clear_caches()
        scaled(x).block_until_ready()
    finally:
        compile_cache.disable()
    caches = [s[5]["cache"] for s in tel.spans()
              if s[0] == "jax.compile" and s[5]["fun"] == "jit(scaled)"]
    assert caches == ["miss", "hit"]


def test_the_crossing_is_timed_from_set_dataset_on_its_own_thread(
        tmp_path, tel):
    spans = _joined(_run(tmp_path))
    placed = [s for s in spans if s[0] == "trainer.set_dataset"]
    crossing = [s for s in spans if s[0] == "trainer.set_dataset.crossing"]
    assert len(placed) == 1 and len(crossing) == 1
    (place,), (cross,) = placed, crossing
    assert cross[4] == place[3]
    assert cross[1] == place[1]
    assert cross[1] + cross[2] > place[1]
    assert cross[5]["bytes"] == place[5]["bytes"]
    assert cross[5]["thread"] == "trainer.set_dataset.crossing"


def test_set_dataset_keeps_its_self_time_beside_the_crossing(tmp_path, tel):
    spans = _joined(_run(tmp_path))
    (place,) = [s for s in spans if s[0] == "trainer.set_dataset"]
    inside = sum(s[2] for s in spans
                 if s[4] == place[3] and "thread" not in s[5])
    own = telemetry.self_times(spans)[place[3]]
    assert own == place[2] - inside > 0


def test_a_span_recorded_beside_its_parent_names_its_thread(tel):
    with telemetry.span("outer") as outer:
        telemetry.record_span("inside", 1, 2, outer.id)
        side = threading.Thread(
            target=telemetry.record_span, name="side",
            args=("beside", 1, 5, outer.id))
        side.start()
        side.join()
    spans = {s[0]: s for s in telemetry.spans()}
    assert "thread" not in spans["inside"][5]
    assert spans["beside"][5] == {"thread": "side"}
    assert spans["beside"][4] == spans["outer"][3]
    own = telemetry.self_times(list(spans.values()))
    assert own[spans["outer"][3]] == spans["outer"][2] - 1


def test_initialize_holds_one_child_a_unit(tmp_path, tel):
    wf = _run(tmp_path)
    spans = _joined(wf)
    (init,) = [s for s in spans if s[0] == "workflow.initialize"]
    kids = sorted(s[0] for s in spans if s[4] == init[3])
    assert kids == sorted("unit.%s.initialize" % u.name for u in wf.units)
    # set-up's first phase, over before the scheduler's first pass
    assert init[1] + init[2] <= min(
        s[1] for s in spans if s[0] == "workflow.run")


def test_off_records_nothing_and_starts_no_thread(tmp_path):
    telemetry.enable()      # the listeners are installed, then it is off
    telemetry.disable()
    telemetry.reset()
    before = set(threading.enumerate())
    wf = _run(tmp_path)
    assert wf.fused_trainer.net._crossing is None
    assert not [t for t in set(threading.enumerate()) - before
                if t.name == "trainer.set_dataset.crossing"]
    assert telemetry.spans() == []
    assert telemetry.snapshot()["counters"] == {}
    assert "jax_top" not in telemetry._open.__dict__


def test_the_clock_anchor_pairs_the_two_clocks_back_to_back():
    wall, perf = telemetry.clock_anchor()
    assert telemetry._anchor == (wall, perf)
    # read again now, the pair stands as far apart as the two clocks do
    gap = (time.time_ns() - wall) - (time.perf_counter_ns() - perf)
    assert abs(gap) < 5_000_000
