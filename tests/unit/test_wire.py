"""The binary framed relay's protocol layer (ISSUE 20): frame
packing/parsing, the incremental reader's early typed failures, the
zero-copy ``.npy`` codec, the listener's malformed-frame and
slowloris behavior over real sockets, and the mux's failure-class
classification."""

import socket
import struct
import threading
import time

import numpy
import pytest

from znicz_tpu.serving import wire


def _frame_of(kind, meta, body=b""):
    reader = wire.FrameReader()
    reader.feed(wire.pack_frame(kind, meta, body))
    return reader.next_frame()


# -- framing ----------------------------------------------------------------

def test_pack_roundtrip_meta_and_body():
    body = b"\x00\x01binary\xffpayload"
    kind, meta, got = _frame_of(
        wire.KIND_REQUEST, {"rid": "r-1", "model": "m"}, body)
    assert kind == wire.KIND_REQUEST
    assert meta == {"rid": "r-1", "model": "m"}
    assert bytes(got) == body


def test_pack_roundtrip_empty_meta_and_body():
    kind, meta, body = _frame_of(wire.KIND_RESPONSE, {})
    assert kind == wire.KIND_RESPONSE
    assert meta == {}
    assert bytes(body) == b""


def test_reader_byte_at_a_time_and_back_to_back_frames():
    f1 = wire.pack_frame(wire.KIND_REQUEST, {"rid": "a"}, b"one")
    f2 = wire.pack_frame(wire.KIND_REQUEST, {"rid": "b"}, b"two")
    reader = wire.FrameReader()
    for i in range(len(f1) - 1):
        reader.feed(f1[i:i + 1])
        assert reader.next_frame() is None, \
            "frame surfaced %d bytes early" % (len(f1) - 1 - i)
    # the last byte of frame 1 arrives glued to ALL of frame 2
    reader.feed(f1[-1:] + f2)
    kind, meta, body = reader.next_frame()
    assert (kind, meta, bytes(body)) == (
        wire.KIND_REQUEST, {"rid": "a"}, b"one")
    kind, meta, body = reader.next_frame()
    assert (kind, meta, bytes(body)) == (
        wire.KIND_REQUEST, {"rid": "b"}, b"two")
    assert reader.next_frame() is None
    assert reader.pending == 0


def test_reader_body_view_survives_next_frame():
    """The returned body is a memoryview DETACHED from the
    accumulation buffer — feeding the next frame must not invalidate
    or mutate it."""
    reader = wire.FrameReader()
    reader.feed(wire.pack_frame(wire.KIND_REQUEST, {"rid": "a"},
                                b"stable"))
    _, _, body = reader.next_frame()
    assert isinstance(body, memoryview)
    reader.feed(wire.pack_frame(wire.KIND_REQUEST, {"rid": "b"},
                                b"XXXXXX"))
    reader.next_frame()
    assert bytes(body) == b"stable"


@pytest.mark.parametrize("mutate,reason,early_at", [
    (lambda f: b"XY" + f[2:], "bad_magic", 2),
    (lambda f: f[:2] + b"\x63" + f[3:], "bad_version", 3),
    (lambda f: f[:3] + b"\x2a" + f[4:], "bad_kind", 4),
])
def test_reader_rejects_typed_and_early(mutate, reason, early_at):
    good = wire.pack_frame(wire.KIND_REQUEST, {"rid": "x"}, b"body")
    bad = mutate(good)
    # the full bad frame classifies
    reader = wire.FrameReader()
    reader.feed(bad)
    with pytest.raises(wire.WireProtocolError) as err:
        reader.next_frame()
    assert err.value.reason == reason
    # and the failure fires as soon as the offending byte is in —
    # no waiting for a length's worth of garbage
    reader = wire.FrameReader()
    reader.feed(bad[:early_at])
    with pytest.raises(wire.WireProtocolError) as err:
        reader.next_frame()
    assert err.value.reason == reason


def test_reader_rejects_oversize_body_before_buffering_it():
    hdr = struct.pack("!2sBBII", wire.MAGIC, wire.VERSION,
                      wire.KIND_REQUEST, 0, 1 << 30)
    reader = wire.FrameReader(max_body=1 << 16)
    reader.feed(hdr)  # header only — the body never has to arrive
    with pytest.raises(wire.WireProtocolError) as err:
        reader.next_frame()
    assert err.value.reason == "oversize"


def test_reader_rejects_oversize_meta():
    hdr = struct.pack("!2sBBII", wire.MAGIC, wire.VERSION,
                      wire.KIND_REQUEST, (1 << 20) + 1, 0)
    reader = wire.FrameReader()
    reader.feed(hdr)
    with pytest.raises(wire.WireProtocolError) as err:
        reader.next_frame()
    assert err.value.reason == "oversize"


def test_reader_rejects_undecodable_meta():
    garbage = b"not json"
    frame = struct.pack("!2sBBII", wire.MAGIC, wire.VERSION,
                        wire.KIND_REQUEST, len(garbage), 0) + garbage
    reader = wire.FrameReader()
    reader.feed(frame)
    with pytest.raises(wire.WireProtocolError) as err:
        reader.next_frame()
    assert err.value.reason == "bad_meta"


def test_error_frame_carries_http_equivalent_payload():
    frame = wire.error_frame(429, {"error": "queue full"}, rid="r9",
                             retry_after="1", fatal=False)
    reader = wire.FrameReader()
    reader.feed(frame)
    kind, meta, body = reader.next_frame()
    assert kind == wire.KIND_ERROR
    assert meta["status"] == 429
    assert meta["payload"] == {"error": "queue full"}
    assert meta["rid"] == "r9"
    assert meta["retry_after"] == "1"
    assert "fatal" not in meta


# -- the zero-copy .npy codec ----------------------------------------------

def test_parse_npy_roundtrip_and_zero_copy():
    x = numpy.arange(24, dtype=numpy.float64).reshape(4, 6) * 0.5
    payload = wire.npy_bytes(x)
    arr = wire.parse_npy(payload)
    numpy.testing.assert_array_equal(arr, x)
    # the array's storage IS the wire buffer — no copy happened
    assert numpy.shares_memory(
        arr, numpy.frombuffer(payload, dtype=numpy.uint8))


def test_parse_npy_over_memoryview_slice():
    x = numpy.random.RandomState(3).uniform(-1, 1, (3, 5))
    framed = b"prefix" + wire.npy_bytes(x)
    arr = wire.parse_npy(memoryview(framed)[6:])
    numpy.testing.assert_array_equal(arr, x)


def test_parse_npy_fortran_order():
    x = numpy.asfortranarray(
        numpy.arange(12, dtype=numpy.float32).reshape(3, 4))
    import io
    buf = io.BytesIO()
    numpy.save(buf, x)  # fortran_order: True in the header
    numpy.testing.assert_array_equal(
        wire.parse_npy(buf.getvalue()), x)


@pytest.mark.parametrize("payload", [
    b"",
    b"\x93NUMPY",                       # truncated before version
    b"not npy at all" * 3,
    wire.npy_bytes(numpy.zeros((4, 4)))[:-7],   # truncated data
])
def test_parse_npy_rejects_malformed(payload):
    with pytest.raises(ValueError):
        wire.parse_npy(payload)


# -- the listener over real sockets ----------------------------------------

def _echo_handler(group):
    for req in group:
        req.reply(wire.pack_frame(
            wire.KIND_RESPONSE,
            {"rid": req.meta.get("rid"), "status": 200},
            bytes(req.body)))


@pytest.fixture
def listener():
    lst = wire.WireListener(_echo_handler, name="test",
                            workers=2, max_body=1 << 16,
                            read_timeout_ms=300.0).start()
    yield lst
    lst.stop()


def test_listener_round_trip(listener):
    conn = wire.WireConn("127.0.0.1", listener.port, timeout=10)
    try:
        kind, meta, body = conn.request(
            {"rid": "t-1"}, b"payload", timeout=10)
    finally:
        conn.close()
    assert kind == wire.KIND_RESPONSE
    assert meta["rid"] == "t-1" and meta["status"] == 200
    assert bytes(body) == b"payload"


@pytest.mark.parametrize("raw,reason", [
    (b"XY" + b"\x00" * 20, "bad_magic"),
    (wire.MAGIC + b"\x63" + b"\x00" * 20, "bad_version"),
    (struct.pack("!2sBBII", wire.MAGIC, wire.VERSION,
                 wire.KIND_REQUEST, 0, 1 << 30), "oversize"),
    # a listener never accepts RESPONSE frames
    (wire.pack_frame(wire.KIND_RESPONSE, {"rid": "x"}), "bad_kind"),
])
def test_listener_answers_typed_error_then_closes(listener, raw,
                                                  reason):
    conn = wire.WireConn("127.0.0.1", listener.port, timeout=10)
    try:
        conn.sock.sendall(raw)
        kind, meta, _ = conn.recv_frame(timeout=10)
        assert kind == wire.KIND_ERROR
        assert meta["status"] == 400
        assert meta["fatal"] is True
        assert meta["payload"]["reason"] == reason
        # the connection is then CLOSED, not wedged
        with pytest.raises(wire.WireDeadError):
            conn.recv_frame(timeout=10)
    finally:
        conn.close()


def test_listener_sweeps_slowloris_without_wedging(listener):
    """A half-frame connection parked past read_timeout_ms gets a 408
    ERROR frame and the close; a healthy connection keeps round-
    tripping the whole time — the event loop never blocked."""
    half = wire.pack_frame(wire.KIND_REQUEST, {"rid": "slow"},
                           b"x" * 64)[:20]
    slow = wire.WireConn("127.0.0.1", listener.port, timeout=10)
    healthy = wire.WireConn("127.0.0.1", listener.port, timeout=10)
    try:
        slow.sock.sendall(half)
        deadline = time.monotonic() + 10.0
        swept = None
        while time.monotonic() < deadline and swept is None:
            kind, meta, _ = healthy.request(
                {"rid": "ok-%f" % time.monotonic()}, b"fine",
                timeout=10)
            assert kind == wire.KIND_RESPONSE \
                and meta["status"] == 200
            slow.sock.settimeout(0.05)
            try:
                data = slow.sock.recv(1 << 16)
            except socket.timeout:
                continue
            if data:
                slow._reader.feed(data)
                swept = slow._reader.next_frame()
        assert swept is not None, "slowloris was never swept"
        kind, meta, _ = swept
        assert kind == wire.KIND_ERROR
        assert meta["status"] == 408
        assert meta["payload"]["reason"] == "timeout"
    finally:
        slow.close()
        healthy.close()


def test_listener_coalesces_batched_frames(listener):
    """Frames that arrive in one burst reach the handler as ONE
    group (the coalesced decode)."""
    groups = []
    lst = wire.WireListener(lambda g: groups.append(len(g)) or
                            _echo_handler(g),
                            name="grp", workers=2).start()
    try:
        conn = wire.WireConn("127.0.0.1", lst.port, timeout=10)
        burst = b"".join(wire.pack_frame(
            wire.KIND_REQUEST, {"rid": "b-%d" % i}, b"x")
            for i in range(8))
        conn.sock.sendall(burst)
        seen = set()
        for _ in range(8):
            _, meta, _ = conn.recv_frame(timeout=10)
            seen.add(meta["rid"])
        conn.close()
        assert seen == {"b-%d" % i for i in range(8)}
        assert max(groups) > 1, \
            "a one-burst octet of frames never coalesced: %s" % groups
    finally:
        lst.stop()


# -- the mux's failure classes ---------------------------------------------

def test_mux_round_trip_and_stats(listener):
    mux = wire.WireMux(conns_per_target=2)
    try:
        kind, meta, body, t_frame = mux.round_trip(
            "r0", ("127.0.0.1", listener.port),
            {"rid": "m-1"}, b"abc", timeout=10)
        assert kind == wire.KIND_RESPONSE
        assert meta["rid"] == "m-1"
        assert bytes(body) == b"abc"
        assert t_frame <= time.monotonic()
        st = mux.stats()
        assert st["targets"] == 1 and st["round_trips"] == 1
        assert st["in_flight"] == 0
    finally:
        mux.stop()


def test_mux_connect_failure_is_never_sent_class():
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    dead_port = probe.getsockname()[1]
    probe.close()  # nothing listens here now
    mux = wire.WireMux()
    try:
        with pytest.raises(wire.WireConnectError):
            mux.round_trip("gone", ("127.0.0.1", dead_port),
                           {"rid": "m-2"}, b"", timeout=5)
    finally:
        mux.stop()


def test_mux_dead_connection_fails_parked_waiters(listener):
    """Dropping the target mid-wait fails the parked rid with the
    dead-connection class (the oracle-consulting path), not a hang."""
    sink = wire.WireListener(lambda group: None,  # never replies
                            name="sink", workers=1).start()
    mux = wire.WireMux(conns_per_target=1)
    errors = []

    def call():
        try:
            mux.round_trip("s0", ("127.0.0.1", sink.port),
                           {"rid": "m-3"}, b"", timeout=30)
        except Exception as e:  # noqa: BLE001 - asserted below
            errors.append(e)

    t = threading.Thread(target=call)
    t.start()
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and not mux.stats()["in_flight"]:
            time.sleep(0.02)
        assert mux.stats()["in_flight"] == 1
        mux.drop("s0")
        t.join(timeout=10)
        assert not t.is_alive()
        assert len(errors) == 1
        assert isinstance(errors[0], wire.WireDeadError)
    finally:
        mux.stop()
        sink.stop()


def test_mux_requires_a_rid():
    mux = wire.WireMux()
    try:
        with pytest.raises(ValueError):
            mux.round_trip("k", ("127.0.0.1", 1), {}, b"")
    finally:
        mux.stop()
