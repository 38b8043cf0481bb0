"""Fleet-wide distributed tracing over REAL subprocesses (ISSUE 16):
the router head-samples an admission, propagates the decision via
``X-Trace-Sampled``, and ``GET /debug/trace/<rid>`` at the router
returns ONE stitched cross-process tree — router hop kinds
partitioning router wall time (parts-sum pinned within
[0.9, 1.05]x), the replica's serving tree nested inside the
``replica_wait`` window, a Chrome export with a track per process.
Retried requests show BOTH peers in one tree; an unsampled rid 404s;
the shipped default (sampling off) is booby-trap-pinned inert.

Every fleet spawns real ``python -m znicz_tpu serve`` replicas behind
an in-process :class:`~znicz_tpu.serving.router.FleetRouter` — the
router half of the tracing plane runs in THIS process (knobs via
monkeypatch), the replica half arms through forwarded ``--config``."""

import json
import os
import time
import urllib.error
import urllib.request

import numpy
import pytest

from znicz_tpu.core.config import root
from znicz_tpu.core import telemetry
from znicz_tpu.serving import reqtrace
from znicz_tpu.serving.router import FleetRouter

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ENV = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
MAX_BATCH = 8
N_IN = 6

#: the replica-side arming: the plane is ON (forced rids trace) but
#: the replica's OWN head-sampling cadence is ~never — so a replica
#: tree for our rid proves the ROUTER's decision propagated, not a
#: lucky hit of the replica's own cursor
REPLICA_ARGS = ["--max-batch", str(MAX_BATCH),
                "--config", "common.serving.trace_sample_n=1000000",
                "--config", "common.serving.slo_enabled=True"]


def _synth_zip(directory):
    from znicz_tpu.testing import build_fc_package_zip
    return build_fc_package_zip(os.path.join(directory, "synth.zip"),
                                [N_IN, 8, 3], seed=42)


def _predict(url, x, rid=None, timeout=60):
    headers = {"Content-Type": "application/json"}
    if rid:
        headers["X-Request-Id"] = rid
    req = urllib.request.Request(
        url + "/predict/m",
        json.dumps({"inputs": numpy.asarray(x).tolist()}).encode(),
        headers)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read()), dict(
            resp.headers)


def _get(url, path, timeout=30):
    with urllib.request.urlopen(url + path, timeout=timeout) as resp:
        return json.loads(resp.read())


def _x(seed, rows=2):
    return numpy.random.RandomState(seed).uniform(
        -1.0, 1.0, (rows, N_IN))


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """One shared 2-replica fleet with armed replicas; also warms the
    shared compile cache the per-test fleets below reuse."""
    tmp = tmp_path_factory.mktemp("fleet_tracing")
    zip_path = _synth_zip(str(tmp))
    router = FleetRouter(
        ["m=" + zip_path] + REPLICA_ARGS, replicas=2,
        compile_cache_dir=str(tmp / "cache"), env=ENV).start()
    url = "http://127.0.0.1:%d" % router.port
    yield router, url, str(tmp)
    router.stop()


@pytest.fixture
def armed(monkeypatch):
    """Router-side sampling ON (this process IS the router)."""
    monkeypatch.setattr(root.common.serving, "trace_sample_n", 1)
    monkeypatch.setattr(root.common.telemetry, "enabled", True)
    reqtrace.reset()
    yield
    reqtrace.reset()


def test_stitched_tree_partitions_router_wall(fleet, armed):
    """THE tentpole pin: one request, one stitched tree — router
    kinds partition router wall time within [0.9, 1.05]x, the
    replica's tree rides inside replica_wait, the Chrome export
    carries two process tracks, and the hop histograms observed."""
    router, url, _ = fleet
    code, doc, _ = _predict(url, _x(1), rid="stitch-1")
    assert code == 200 and doc["model"] == "m"
    tree = _get(url, "/debug/trace/stitch-1")
    assert tree["stitched"] is True
    assert tree["origin"] == "router"
    assert tree["complete"] is True, tree["span_kinds"]
    assert tree["model"] == "m"
    up_rids = {r.rid for r in router.replicas() if r.state == "up"}
    assert tree["replica"] in up_rids
    # the five hop phases plus the replica's six serving kinds (plus
    # the synthetic nested alignment anchor) in ONE payload
    kinds = set(tree["span_kinds"])
    assert set(reqtrace.ROUTER_REQUIRED_KINDS) <= kinds, kinds
    assert set(reqtrace.SPAN_KINDS) <= kinds, kinds
    assert "replica" in kinds
    # the partition pin: router top-level durations ~= router wall
    assert tree["wall_ms"] > 0
    ratio = tree["parts_ms"] / tree["wall_ms"]
    assert 0.9 <= ratio <= 1.05, \
        "router kinds cover %.3fx of router wall" % ratio
    # the replica's spans landed INSIDE the replica_wait window
    # (clock alignment): small tolerance for rounding at the left
    # edge and for the reply tail the router cannot see
    wait = [s for s in tree["spans"]
            if s["kind"] == "replica_wait"][-1]
    lo = wait["start_ms"] - 0.5
    hi = wait["start_ms"] + wait["duration_ms"] + 2.0
    replica_spans = [s for s in tree["spans"]
                     if s["process"] == "replica"]
    assert replica_spans
    for s in replica_spans:
        assert lo <= s["start_ms"], (s, wait)
        assert s["start_ms"] + s["duration_ms"] <= hi, (s, wait)
    # ONE Chrome trace, a track per process, named via metadata
    events = tree["traceEvents"]
    assert {e["pid"] for e in events if e["ph"] == "X"} == {0, 1}
    assert [e for e in events if e["ph"] == "M"]
    telemetry.validate_trace({"traceEvents": events})
    # the hop histograms fed from the sampled spans, labeled by model
    for kind in reqtrace.ROUTER_REQUIRED_KINDS:
        h = telemetry.histogram(telemetry.labeled(
            "fleet.hop_seconds.%s" % kind, model="m"))
        assert h.count >= 1, "no %s hop observation" % kind


def test_trace_index_fans_out_with_replica_attribution(fleet, armed):
    """The /debug/trace index no longer dead-ends at the router
    process: the payload carries the router's own rids AND every
    replica's, attributed by replica id."""
    router, url, _ = fleet
    assert _predict(url, _x(2), rid="index-1")[0] == 200
    index = _get(url, "/debug/trace")
    assert index["enabled"] is True and index["fleet"] is True
    assert "index-1" in index["rids"]
    up = {r.rid for r in router.replicas() if r.state == "up"}
    assert set(index["replicas"]) == up
    assert all(b["enabled"] for b in index["replicas"].values())
    # the propagated rid landed on exactly ONE replica's ring
    holders = [rid for rid, b in index["replicas"].items()
               if "index-1" in b["rids"]]
    assert len(holders) == 1, index["replicas"]


def test_unsampled_rid_404s_at_router(fleet, armed, monkeypatch):
    """Head-sampling at the router: with trace_sample_n=2 the second
    admission is unsampled — its rid 404s at the router exactly like
    a replica's endpoint (and the sampled sibling still stitches)."""
    _, url, _ = fleet
    monkeypatch.setattr(root.common.serving, "trace_sample_n", 2)
    reqtrace.reset()
    assert _predict(url, _x(3), rid="half-0")[0] == 200
    assert _predict(url, _x(4), rid="half-1")[0] == 200
    with pytest.raises(urllib.error.HTTPError) as err:
        _get(url, "/debug/trace/half-1")
    assert err.value.code == 404
    body = json.loads(err.value.read())
    assert "trace_sample_n" in body["error"]
    assert _get(url, "/debug/trace/half-0")["stitched"] is True


def test_router_overhead_summary_and_serving_ms_header(fleet, armed):
    """Every proxied 200 (sampled or not) feeds router_overhead_ms =
    router wall minus the replica-reported X-Serving-Ms; the summary
    rides in /slo and /statusz."""
    router, url, _ = fleet
    for i in range(4):
        assert _predict(url, _x(10 + i))[0] == 200
    # the replica stamps its serving time on every 200
    up = [r for r in router.replicas() if r.state == "up"]
    _, _, headers = _predict(up[0].url, _x(20))
    assert float(headers["X-Serving-Ms"]) > 0.0
    for surface in ("/slo", "/statusz"):
        block = _get(url, surface)["router_overhead_ms"]
        assert block["count"] >= 4, (surface, block)
        assert block["mean_ms"] > 0.0, (surface, block)
        assert block["p99_ms"] >= block["p50_ms"] >= 0.0
        assert block["max_ms"] >= block["p99_ms"]


def test_retried_request_tree_shows_both_peers(fleet, armed,
                                               monkeypatch):
    """A request whose first pick is a corpse: the failed attempt
    collapses into ONE retry span (attrs: peer + reason) and the
    winning attempt's replica_wait names the survivor — both peers
    in one tree, partition still exact."""
    _, _, tmp = fleet
    # a slow health monitor: the corpse must stay in rotation long
    # enough for a request to provably pick it first
    monkeypatch.setattr(root.common.serving.fleet,
                        "probe_interval_s", 60.0)
    router = FleetRouter(
        ["m=" + os.path.join(tmp, "synth.zip")] + REPLICA_ARGS,
        replicas=2, compile_cache_dir=os.path.join(tmp, "cache"),
        env=ENV).start()
    url = "http://127.0.0.1:%d" % router.port
    try:
        victim, survivor = router.replicas()
        victim.proc.kill()
        victim.proc.wait(timeout=30)
        # drop parked conns: the next pick is a plain connect-refused
        victim.close_conns()
        retried = None
        for i in range(8):
            rid = "retry-%d" % i
            assert _predict(url, _x(30 + i), rid=rid)[0] == 200
            tree = _get(url, "/debug/trace/" + rid)
            if "retry" in tree["span_kinds"]:
                retried = tree
                break
        assert retried is not None, \
            "no request picked the corpse within 8 tries"
        retry_spans = [s for s in retried["spans"]
                       if s["kind"] == "retry"]
        assert retry_spans[0]["attrs"]["peer"] == victim.rid
        assert retry_spans[0]["attrs"]["reason"] == "connect_failed"
        waits = [s for s in retried["spans"]
                 if s["kind"] == "replica_wait"]
        assert waits[-1]["attrs"]["replica"] == survivor.rid
        assert retried["replica"] == survivor.rid
        assert retried["stitched"] is True
        # retry is a top-level kind: the partition survives failure
        ratio = retried["parts_ms"] / retried["wall_ms"]
        assert 0.9 <= ratio <= 1.05, ratio
    finally:
        router.stop()


def test_disabled_default_fleet_plane_is_inert(fleet, monkeypatch):
    """The shipped default (trace_sample_n=0) on the fleet path costs
    nothing: booby-trapped reqtrace hooks never fire in the router
    process, every trace surface answers enabled:false, and the
    replicas warm with ZERO fresh compiles off the shared cache."""
    _, _, tmp = fleet
    monkeypatch.setattr(root.common.serving, "trace_sample_n", 0)

    def boom(*a, **k):
        raise AssertionError("disabled fleet tracing touched "
                             "reqtrace")

    monkeypatch.setattr(reqtrace, "begin", boom)
    monkeypatch.setattr(reqtrace, "add_span", boom)
    router = FleetRouter(
        ["m=" + os.path.join(tmp, "synth.zip"), "--max-batch",
         str(MAX_BATCH)],
        replicas=2, compile_cache_dir=os.path.join(tmp, "cache"),
        env=ENV).start()
    url = "http://127.0.0.1:%d" % router.port
    try:
        for i in range(3):
            assert _predict(url, _x(40 + i), rid="off-%d" % i)[0] \
                == 200
        index = _get(url, "/debug/trace")
        assert index["enabled"] is False
        assert index["rids"] == []
        assert not any(b["enabled"]
                       for b in index["replicas"].values())
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(url, "/debug/trace/off-0")
        assert err.value.code == 404
        # zero fresh compiles: every warmup executable deserialized
        # from the cache the module fleet populated
        def counter(text, name):
            for line in text.splitlines():
                if line.startswith(name + " "):
                    return float(line.split()[-1])
            return 0.0
        for r in router.replicas():
            with urllib.request.urlopen(r.url + "/metrics",
                                        timeout=30) as resp:
                text = resp.read().decode()
            compiles = counter(text, "znicz_jax_backend_compiles")
            hits = counter(text, "znicz_jax_persistent_cache_hits")
            assert compiles == hits > 0, (r.rid, compiles, hits)
    finally:
        router.stop()


def test_fleet_timeseries_merges_at_the_front_door(fleet, armed,
                                                   monkeypatch):
    """GET /debug/timeseries at the router is the fleet view: merged
    step-function counters with per-source attribution (the replicas
    sample on their own threads; the router's rings merge in)."""
    from znicz_tpu.core import timeseries
    _, _, tmp = fleet
    monkeypatch.setattr(root.common.telemetry.timeseries, "enabled",
                        True)
    # the router.* family is not in the default curated prefixes —
    # opt it in so the router's OWN rings have something to merge
    monkeypatch.setattr(root.common.telemetry.timeseries, "prefixes",
                        "serving,router")
    timeseries.reset()
    router = FleetRouter(
        ["m=" + os.path.join(tmp, "synth.zip")] + REPLICA_ARGS
        + ["--config", "common.telemetry.timeseries.enabled=True",
           "--config",
           "common.telemetry.timeseries.interval_ms=100.0"],
        replicas=2, compile_cache_dir=os.path.join(tmp, "cache"),
        env=ENV).start()
    url = "http://127.0.0.1:%d" % router.port
    try:
        for i in range(4):
            assert _predict(url, _x(50 + i))[0] == 200
        time.sleep(0.4)              # >= one 100 ms replica sweep
        timeseries.sample_once()     # the router's own rings
        merged = _get(url, "/debug/timeseries")
        assert merged["merged"] is True
        up = {r.rid for r in router.replicas() if r.state == "up"}
        assert set(merged["sources"]) == up | {"router"}
        batches = merged["series"]["serving.batches"]
        parts = [v for v in batches["sources"].values()
                 if v is not None]
        assert len(parts) == 2          # both replicas attributed
        assert batches["points"][-1][1] == sum(parts) > 0
        # the router's own series merged into the same payload
        assert "router.requests" in merged["series"]
    finally:
        router.stop()
        timeseries.reset()
