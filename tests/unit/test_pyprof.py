"""Continuous sampling profiler (znicz_tpu/core/pyprof.py,
ISSUE 18): fold math via injectable frames/names/clock — zero sleeps,
zero real threads for the math tests — plus the disabled-by-default
zero-overhead pin, the fixed phase vocabulary, the GIL-probe
calibration, the window diff, and the fleet merge."""

import os
import time

import pytest

from znicz_tpu.core.config import root
from znicz_tpu.core import pyprof, telemetry


@pytest.fixture
def pp():
    """Telemetry + pyprof ON with clean aggregates; knobs restored
    and everything wiped after (conftest restores telemetry)."""
    saved = {k: root.common.profiler.pyprof.get(k)
             for k in ("enabled", "hz", "capacity", "max_depth",
                       "gil_probe", "gil_interval_ms",
                       "gil_calib_probes", "capture_seconds_cap")}
    root.common.telemetry.enabled = True
    telemetry.reset()
    pyprof.reset()
    root.common.profiler.pyprof.enabled = True
    yield pyprof
    pyprof.reset()
    telemetry.reset()
    for k, v in saved.items():
        setattr(root.common.profiler.pyprof, k, v)


# -- synthetic stacks ---------------------------------------------------------

class _Code(object):
    def __init__(self, filename, name):
        self.co_filename = filename
        self.co_name = name


class _Frame(object):
    def __init__(self, code, back=None):
        self.f_code = code
        self.f_back = back


def chain(*pairs):
    """Root-first ``(filename, funcname)`` pairs -> the LEAF frame
    (``f_back`` walks back toward the root, like a real frame)."""
    f = None
    for filename, funcname in pairs:
        f = _Frame(_Code(filename, funcname), back=f)
    return f


# -- the disabled fast path ---------------------------------------------------

def test_disabled_profiler_touches_nothing(monkeypatch):
    """The zero-overhead-off pin: with the gate off, every hook
    returns after ONE config predicate — a booby-trapped state
    allocator proves none of them reach the armed path, and no state
    dict is ever allocated."""
    root.common.profiler.pyprof.enabled = False

    def boom(*a, **k):
        raise AssertionError("disabled profiler touched its state")

    monkeypatch.setattr(pyprof, "_ensure_state", boom)
    assert pyprof.sample_once() == 0
    assert pyprof.gil_probe_once(0.01) is None
    assert pyprof.maybe_start() is False
    assert pyprof.capture(0.1) == {"enabled": False}
    assert pyprof.running() is False
    assert pyprof._state is None
    snap = pyprof.snapshot()
    assert snap["enabled"] is False and snap["samples"] == 0


# -- the thread-name registry -------------------------------------------------

def test_thread_name_registry():
    assert pyprof.thread_name("continuous") == "znicz:continuous"
    assert pyprof.component_of("znicz:continuous") == "continuous"
    # one trailing -<index> pool suffix folds a pool into ONE
    # component; non-numeric tails (replica ids) stay distinct
    assert pyprof.component_of("znicz:continuous-3") == "continuous"
    assert pyprof.component_of("znicz:replica-out-r0") == \
        "replica-out-r0"
    # off-convention names land in the bucket the >=90%-attributed
    # acceptance criterion counts against
    assert pyprof.component_of("MainThread") == "unnamed"
    assert pyprof.component_of("Thread-12") == "unnamed"
    assert pyprof.component_of("") == "unnamed"
    assert pyprof.component_of(None) == "unnamed"
    assert pyprof.component_of("znicz:") == "unnamed"


def test_name_current_thread(pp):
    import threading
    saved = threading.current_thread().name
    try:
        pyprof.name_current_thread("test-main")
        assert threading.current_thread().name == "znicz:test-main"
    finally:
        threading.current_thread().name = saved


# -- phase classification -----------------------------------------------------

@pytest.mark.parametrize("filename,funcname,want", [
    ("/usr/lib/python3/threading.py", "wait", "lock_wait"),
    ("/usr/lib/python3/queue.py", "get", "lock_wait"),
    ("app.py", "acquire", "lock_wait"),
    # a thread parked in threading.wait is lock_wait even though the
    # json precedence would otherwise never see it
    ("/usr/lib/python3/json/decoder.py", "raw_decode",
     "json_decode"),
    ("/usr/lib/python3/json/scanner.py", "scan_once", "json_decode"),
    ("/usr/lib/python3/json/__init__.py", "loads", "json_decode"),
    ("/usr/lib/python3/json/encoder.py", "iterencode", "serialize"),
    ("app.py", "dumps", "serialize"),
    ("app.py", "tolist", "serialize"),
    ("/sp/numpy/lib/format.py", "read_array", "npy_decode"),
    ("/sp/numpy/core/multiarray.py", "frombuffer", "npy_decode"),
    ("/usr/lib/python3/socket.py", "recv_into", "socket_io"),
    ("/usr/lib/python3/http/client.py", "begin", "socket_io"),
    ("/usr/lib/python3/socketserver.py", "process_request",
     "socket_io"),
    ("app.py", "sendall", "socket_io"),
    ("/sp/jax/_src/api.py", "cache_miss", "device_dispatch"),
    ("/sp/jaxlib/xla_client.py", "execute", "device_dispatch"),
    ("app.py", "block_until_ready", "device_dispatch"),
    ("app.py", "train_epoch", "other"),
    (None, None, "other"),
])
def test_classify_table(filename, funcname, want):
    got = pyprof.classify(filename, funcname)
    assert got == want
    assert got in pyprof.PHASES  # the classifier is total


def test_dataplane_phases_are_a_subset():
    assert set(pyprof.DATAPLANE_PHASES) < set(pyprof.PHASES)
    assert "lock_wait" not in pyprof.DATAPLANE_PHASES


# -- the fold math ------------------------------------------------------------

def test_sample_once_folds_and_attributes(pp):
    frames = {
        1: chain(("server.py", "handle"),
                 ("/usr/lib/python3/json/decoder.py", "raw_decode")),
        2: chain(("app.py", "main"), ("model.py", "train_epoch")),
    }
    names = {1: "znicz:http-handler", 2: "Thread-5"}
    assert pyprof.sample_once(frames=frames, names=names) == 2
    snap = pyprof.snapshot()
    assert snap["samples"] == 2 and snap["sweeps"] == 1
    assert snap["components"] == {"http-handler": 1, "unnamed": 1}
    assert snap["phases"]["json_decode"] == 1
    assert snap["phases"]["other"] == 1
    # collapsed keys are component;root;...;leaf
    assert snap["stacks"] == {
        "http-handler;server:handle;decoder:raw_decode": 1,
        "unnamed;app:main;model:train_epoch": 1,
    }
    assert snap["attributed_pct"] == pytest.approx(50.0)
    # repeated sweeps accumulate into the SAME aggregates
    pyprof.sample_once(frames=frames, names=names)
    snap = pyprof.snapshot()
    assert snap["samples"] == 4 and snap["sweeps"] == 2
    assert snap["stacks"][
        "http-handler;server:handle;decoder:raw_decode"] == 2


def test_sampler_never_profiles_itself(pp):
    frames = {1: chain(("pyprof.py", "_run"))}
    names = {1: "znicz:pyprof-sampler"}
    assert pyprof.sample_once(frames=frames, names=names) == 0
    assert pyprof.snapshot()["samples"] == 0


def test_max_depth_keeps_the_leaf_side(pp):
    root.common.profiler.pyprof.max_depth = 2
    frames = {1: chain(("a.py", "fa"), ("b.py", "fb"),
                       ("c.py", "fc"), ("d.py", "fd"))}
    pyprof.sample_once(frames=frames, names={1: "znicz:x"})
    (key,) = pyprof.snapshot()["stacks"]
    # the walk starts at the leaf: depth trims the ROOT side
    assert key == "x;c:fc;d:fd"


def test_capacity_bounds_stacks_loudly(pp):
    root.common.profiler.pyprof.capacity = 2
    for i in range(4):
        frames = {1: chain(("m%d.py" % i, "f"))}
        pyprof.sample_once(frames=frames, names={1: "znicz:x"})
    snap = pyprof.snapshot()
    assert len(snap["stacks"]) == 2
    assert snap["truncated"] == 2     # overflow is counted, not lost
    assert snap["samples"] == 4       # totals still see every sample


def test_unknown_phase_is_a_loud_error(pp, monkeypatch):
    """A classifier change that invents a phase outside the fixed
    vocabulary must fail the sweep, never silently skew the ledger."""
    monkeypatch.setattr(pyprof, "classify",
                        lambda filename, funcname: "warp_drive")
    frames = {1: chain(("novel.py", "f"))}
    with pytest.raises(ValueError, match="warp_drive"):
        pyprof.sample_once(frames=frames, names={1: "znicz:x"})


def test_samples_counter_reaches_telemetry(pp):
    frames = {1: chain(("a.py", "f"))}
    pyprof.sample_once(frames=frames, names={1: "znicz:x"})
    pyprof.sample_once(frames=frames, names={1: "znicz:x"})
    snap = telemetry.snapshot()
    assert snap["counters"]["pyprof.samples"] == 2


def test_overhead_self_meter_uses_the_clock(pp):
    # t0, sweep end: 250 ms inside the sweep.  On the host's own clock,
    # which the snapshot's uptime is taken on: ticks from a made-up epoch
    # (100.0) read an uptime of the host's, and once that passed 14 hours
    # the share rounded to 0.000
    t0 = time.perf_counter()
    ticks = [t0, t0 + 0.25]
    pyprof.sample_once(frames={1: chain(("a.py", "f"))},
                       names={1: "znicz:x"},
                       clock=lambda: ticks.pop(0))
    ovh = pyprof.snapshot()["overhead"]
    assert ovh["busy_ms"] == pytest.approx(250.0)
    assert ovh["pct"] > 0.0


# -- the GIL probe ------------------------------------------------------------

def test_gil_probe_calibrates_then_counts_excess(pp):
    root.common.profiler.pyprof.gil_calib_probes = 3
    # calibration overshoots: attributed as 0, median becomes the
    # host baseline
    assert pyprof.gil_probe_once(0.001) == 0.0
    assert pyprof.gil_probe_once(0.003) == 0.0
    assert pyprof.gil_probe_once(0.002) == 0.0
    snap = pyprof.snapshot()["gil"]
    assert snap["baseline_ms"] == pytest.approx(2.0)
    assert snap["wait_ms"] == 0.0
    # after calibration only the EXCESS above baseline counts
    assert pyprof.gil_probe_once(0.005) == pytest.approx(0.003)
    assert pyprof.gil_probe_once(0.001) == 0.0
    snap = pyprof.snapshot()["gil"]
    assert snap["probes"] == 5
    assert snap["wait_ms"] == pytest.approx(3.0)
    counters = telemetry.snapshot()["counters"]
    assert counters["pyprof.gil_wait_ms"] == pytest.approx(3.0)


# -- windows, captures and the fleet merge ------------------------------------

def test_diff_snapshots_is_the_window(pp):
    a = {1: chain(("a.py", "f"))}
    b = {1: chain(("b.py", "dumps"))}
    pyprof.sample_once(frames=a, names={1: "znicz:x"})
    before = pyprof.snapshot()
    pyprof.sample_once(frames=a, names={1: "znicz:x"})
    pyprof.sample_once(frames=b, names={1: "znicz:y"})
    after = pyprof.snapshot()
    win = pyprof.diff_snapshots(before, after)
    assert win["samples"] == 2 and win["sweeps"] == 2
    assert win["components"] == {"x": 1, "y": 1}
    assert win["stacks"] == {"x;a:f": 1, "y;b:dumps": 1}
    assert win["phases"] == {"other": 1, "serialize": 1}
    assert win["attributed_pct"] == pytest.approx(100.0)
    # the cumulative aggregates were never reset under the reader
    assert after["samples"] == 3
    assert after["stacks"]["x;a:f"] == 2


def test_capture_clamps_and_injects_sleep(pp):
    root.common.profiler.pyprof.capture_seconds_cap = 5.0
    slept = []
    out = pyprof.capture(99.0, sleep=slept.append)
    assert slept == [5.0]          # clamped by the cap, no real sleep
    assert out["seconds"] == 5.0
    assert out["pid"] == os.getpid()
    assert out["enabled"] is True


def test_merge_profiles_sums_with_attribution():
    merged = pyprof.merge_profiles({
        "r0": {"enabled": True, "samples": 10,
               "components": {"http-handler": 8, "unnamed": 2},
               "phases": {"socket_io": 6, "other": 4},
               "stacks": {"http-handler;a:f": 8},
               "gil": {"probes": 5, "wait_ms": 1.5},
               "overhead": {"pct": 2.0}},
        "r1": {"enabled": True, "samples": 6,
               "components": {"http-handler": 6},
               "phases": {"socket_io": 6},
               "stacks": {"http-handler;a:f": 6},
               "gil": {"probes": 5, "wait_ms": 0.5},
               "overhead": {"pct": 3.0}},
        "router": {"enabled": False},
    })
    assert merged["merged"] is True and merged["enabled"] is True
    assert merged["sources"] == {"r0": 10, "r1": 6, "router": 0}
    assert merged["samples"] == 16
    assert merged["components"] == {"http-handler": 14, "unnamed": 2}
    assert merged["phases"] == {"socket_io": 12, "other": 4}
    assert merged["stacks"] == {"http-handler;a:f": 14}
    assert merged["gil"]["probes"] == 10
    assert merged["gil"]["wait_ms"] == pytest.approx(2.0)
    # the conservative "worst replica" tax view
    assert merged["overhead"]["pct"] == pytest.approx(3.0)
    assert merged["attributed_pct"] == pytest.approx(87.5)


# -- renderers ----------------------------------------------------------------

def test_collapsed_text():
    prof = {"stacks": {"x;a:f;b:g": 3, "x;a:f": 1}}
    assert pyprof.collapsed(prof) == "x;a:f 1\nx;a:f;b:g 3"


def test_speedscope_document():
    prof = {"stacks": {"x;a:f;b:g": 3, "x;a:f": 1}}
    doc = pyprof.speedscope(prof, name="t")
    assert doc["name"] == "t"
    names = [f["name"] for f in doc["shared"]["frames"]]
    assert sorted(names) == ["a:f", "b:g", "x"]
    (p,) = doc["profiles"]
    assert p["type"] == "sampled"
    assert sum(p["weights"]) == p["endValue"] == 4
    for sample in p["samples"]:
        assert all(0 <= i < len(names) for i in sample)
    # every sample's root frame is the component (the fleet view
    # groups by component)
    assert all(names[s[0]] == "x" for s in p["samples"])


# -- thread lifecycle ---------------------------------------------------------

def test_maybe_start_lifecycle(pp):
    import threading
    import time
    assert pyprof.maybe_start() is True
    assert pyprof.maybe_start() is True   # idempotent: same thread
    assert pyprof.running() is True
    mine = [t.name for t in threading.enumerate()
            if t.name.startswith("znicz:pyprof")]
    assert "znicz:pyprof-sampler" in mine
    assert "znicz:pyprof-gil" in mine
    # flipping the gate off retires the threads on their own
    root.common.profiler.pyprof.enabled = False
    deadline = time.monotonic() + 5.0
    while pyprof.running() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert pyprof.running() is False
    assert pyprof.maybe_start() is False


def test_stop_keeps_aggregates_reset_drops_them(pp):
    pyprof.sample_once(frames={1: chain(("a.py", "f"))},
                       names={1: "znicz:x"})
    assert pyprof.maybe_start() is True
    pyprof.stop()
    assert pyprof.running() is False
    assert pyprof.snapshot()["samples"] >= 1  # history outlives it
    pyprof.reset()
    assert pyprof.snapshot()["samples"] == 0
