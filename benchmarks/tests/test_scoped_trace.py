"""The scoped reduction on a small xplane written out by hand in the
protobuf wire format, and ``scope_of`` on ``op_name``s the chip recorded."""
import struct

import pytest

from benchmarks.lib import scoped_trace as st


# -- a writer for the few fields the reader reads ------------------------------

def varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num, value):
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, float):
        return varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


STAT_IDS = {"tf_op": 7, "program_id": 8, "flops": 9, "Interned": 10}


def stat(name, value):
    if name == "ref":       # a string held in the stat metadata
        return field(1, STAT_IDS["tf_op"]) + field(7, value)
    body = field(1, STAT_IDS[name])
    if isinstance(value, str):
        return body + field(5, value)
    if isinstance(value, float):
        return body + field(2, value)
    return body + field(3, value)


def plane(name, metadata, lines):
    """metadata: {id: (name, [stats])}; lines: {name: [(id, off, dur)]}"""
    out = field(1, 1) + field(2, name)
    for mid, (mname, stats) in metadata.items():
        em = field(1, mid) + field(2, mname) + field(4, "display")
        for s in stats:
            em += field(5, s)
        out += field(4, field(1, mid) + field(2, em))
    for sname, sid in STAT_IDS.items():
        label = "jit(window_fn)/while/body/jvp(L04.conv)/dot_general:" \
            if sname == "Interned" else sname
        out += field(5, field(1, sid)
                     + field(2, field(1, sid) + field(2, label)))
    for lname, events in lines.items():
        line = field(1, 3) + field(2, lname) + field(3, 123456)
        for mid, off, dur in events:
            line += field(4, field(1, mid) + field(2, off) + field(3, dur)
                          + field(4, stat("flops", 1.5)))
        out += field(3, line)
    return out


WINDOW, PREDICT = 111, 222
META = {
    1: ("jit_window_fn(111)", []),
    2: ("jit_fwd_idx(222)", []),
    10: ("%fusion.1 = bf16[8] fusion(...)",
         [stat("program_id", WINDOW),
          stat("tf_op", "jit(window_fn)/while/body/closed_call/"
               "jvp(L00.conv)/conv_general_dilated:")]),
    11: ("%fusion.2 = bf16[8] fusion(...)",
         [stat("program_id", WINDOW),
          stat("tf_op", "jit(window_fn)/while/body/closed_call/"
               "transpose(jvp(L00.conv))/conv_general_dilated:")]),
    12: ("%copy.56 = bf16[8448,227,227,3] copy(%data.1)",
         [stat("program_id", WINDOW)]),
    13: ("%while.4 = (f32[2]) while(...)",
         [stat("program_id", WINDOW),
          stat("tf_op", "jit(window_fn)/while:")]),
    14: ("%all-reduce.41 = f32[4096] all-reduce(...)",
         [stat("program_id", WINDOW),
          stat("tf_op", "jit(window_fn)/while/body/closed_call/"
               "transpose(jvp(L14.fc))/dot_general:")]),
    15: ("%fusion.9 = f32[8] fusion(...)",
         [stat("program_id", WINDOW),
          stat("tf_op", "jit(window_fn)/while/body/closed_call/"
               "update.L14/mul:")]),
    16: ("%fusion.3 = bf16[8] fusion(...)",
         [stat("program_id", PREDICT),
          stat("tf_op", "jit(fwd_idx)/L00.conv/conv_general_dilated:")]),
    17: ("%gather.2 = bf16[8] gather(...)",
         [stat("program_id", WINDOW),
          stat("tf_op", "jit(window_fn)/while/body/closed_call/gather/"
               "gather:")]),
    18: ("%fusion.4 = bf16[8] fusion(...)",
         [stat("program_id", WINDOW), stat("ref", STAT_IDS["Interned"])]),
}
OPS = [(12, 0, 100), (13, 100, 900),        # the while spans its body
       (17, 100, 50), (10, 150, 200), (18, 350, 50), (11, 400, 300),
       (14, 700, 100), (15, 800, 100),
       (16, 2000, 80)]
#: the modules lines: the idler device's module event is the longer one (it
#: waits in a collective), and that is not what chooses the plane
BUSY = {"/device:TPU:0": [(1, 0, 1000), (2, 2000, 80)],
        "/device:TPU:1": [(1, 0, 5000)]}


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    space = field(1, plane("/host:metadata", {}, {}))
    # the idler device comes first, and has an ops line of its own
    space += field(1, plane("/device:TPU:1", META,
                            {"XLA Modules": BUSY["/device:TPU:1"],
                             "XLA Ops": [(10, 0, 400)]}))
    space += field(1, plane("/device:TPU:0", META,
                            {"Steps": [(1, 0, 1000)],
                             "XLA Modules": BUSY["/device:TPU:0"],
                             "XLA Ops": OPS,
                             "Async XLA Ops": [(12, 0, 5000)]}))
    space += field(4, "hostname")
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(space)
    return str(path)


def test_fields_round_trip():
    msg = field(1, 300) + field(2, "abc") + field(9, 2.5) + field(3, b"")
    got = [(n, v if isinstance(v, int) else bytes(v))
           for n, v in st.fields(memoryview(msg))]
    assert got == [(1, 300), (2, b"abc"), (9, struct.pack("<d", 2.5)),
                   (3, b"")]
    with pytest.raises(ValueError):
        list(st.fields(memoryview(bytes([3 << 3 | 3]))))    # a group


def test_reads_the_busiest_plane_only(xplane):
    loaded = st.read_xplane(xplane)
    assert loaded["plane"] == "/device:TPU:0"
    # the while wrapper is dropped, the async line is not read
    assert len(loaded["ops"]) == len(OPS) - 1
    by_op = {op[0].split(" = ")[0]: op for op in loaded["ops"]}
    assert by_op["%copy.56"][1:3] == ("", "jit_window_fn")
    assert by_op["%fusion.3"][2] == "jit_fwd_idx"
    assert by_op["%fusion.4"][1].endswith("jvp(L04.conv)/dot_general:")
    assert by_op["%fusion.1"][3:] == (150, 200)


def test_plane_is_chosen_as_lib_trace_chooses_it(tmp_path):
    from benchmarks.lib import trace as trace_mod
    # two overlapping ops on one device (busy 300), two apart on the other
    # (busy 400, the smaller sum of the two lines but for the while, which
    # is no leaf); then a third device level with the second
    lines = {"/device:TPU:0": [(10, 0, 300), (11, 100, 150)],
             "/device:TPU:1": [(13, 0, 900), (10, 0, 200), (11, 500, 200)],
             "/device:TPU:2": [(10, 0, 400)]}
    space = b"".join(field(1, plane(name, META, {"XLA Ops": events}))
                     for name, events in lines.items())
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    loaded = st.read_xplane(str(path))
    assert loaded["plane"] == "/device:TPU:1"
    assert [op[3:] for op in loaded["ops"]] == [(0, 200), (500, 200)]
    # the same choice from the same events by lib/trace.py's own rule
    devices = {name: [[META[mid][0], off, dur] for mid, off, dur in events
                      if not trace_mod.is_wrapper(META[mid][0])]
               for name, events in lines.items()}
    red = trace_mod.reduce_trace({"devices": devices, "host": []}, 1.0)
    assert red["busy_s_busiest"] == pytest.approx(400e-9)
    assert st.reduce_scopes(loaded["ops"])["busy_s"] == \
        pytest.approx(400e-12)


def test_reduce_scopes_by_hand(xplane):
    red = st.reduce_scopes(st.read_xplane(xplane)["ops"])
    ps = 1e-12
    assert red["by_scope"] == {
        (True, None, False): pytest.approx(100 * ps),          # copy.56
        (True, "gather", False): pytest.approx(50 * ps),
        (True, "L00.conv", False): pytest.approx(200 * ps),
        (True, "L04.conv", False): pytest.approx(50 * ps),
        (True, "L00.conv", True): pytest.approx(300 * ps),
        # the gradient all-reduce carries the backward product's name
        (True, "grad_exchange", False): pytest.approx(100 * ps),
        (True, "update.L14", False): pytest.approx(100 * ps),
        (False, "L00.conv", False): pytest.approx(80 * ps),
    }
    assert red["sum_s"] == pytest.approx(980 * ps)
    assert red["busy_s"] == pytest.approx(980 * ps)


def test_metric_readers_by_hand(xplane, monkeypatch):
    import importlib
    import os
    st._MEMO.clear()
    ctx = {"trace_dir": os.path.dirname(xplane), "images": 2048,
           "batch": 1024}
    want = {"conv_device_ms_per_step": (200 + 50 + 300) / 2,
            "update_device_ms_per_step": (50 + 100 + 100) / 2,
            "unscoped_device_pct": 100.0 * 100 / 980}
    for name, value in want.items():
        reader = importlib.import_module("benchmarks.layer_metrics." + name)
        got = reader.read(ctx)
        if name.endswith("_pct"):
            assert got == pytest.approx(value)
        else:
            assert got == pytest.approx(value * 1e-9)   # ps to ms
    # no op of this trace stands under an fc or a byte-bound layer's scope
    for name in ("fc_device_ms_per_step", "bytebound_device_ms_per_step"):
        reader = importlib.import_module("benchmarks.layer_metrics." + name)
        assert reader.read(ctx) is None
    st._MEMO.clear()


def test_a_program_without_scopes_reads_nothing(tmp_path):
    import importlib
    # ops that stand under no scope, and a collective, which is booked by
    # its opcode: that alone is not the program naming a scope
    meta = {1: ("jit_window_fn(111)", []),
            10: ("%fusion.1 = bf16[8] fusion(...)",
                 [stat("program_id", WINDOW),
                  stat("tf_op", "jit(window_fn)/while/body/closed_call/"
                       "jvp(jit(relu))/max:")]),
            11: ("%all-reduce.41 = f32[4096] all-reduce(...)",
                 [stat("program_id", WINDOW),
                  stat("tf_op", "jit(window_fn)/while/body/closed_call/"
                       "transpose(jvp(jit(relu)))/dot_general:")])}
    space = field(1, plane("/device:TPU:0", meta,
                           {"XLA Modules": [(1, 0, 10)],
                            "XLA Ops": [(10, 0, 10), (11, 10, 5)]}))
    (tmp_path / "t.xplane.pb").write_bytes(space)
    st._MEMO.clear()
    ctx = {"trace_dir": str(tmp_path), "images": 2048, "batch": 1024}
    assert st.of_run(ctx) is None
    for name in ("conv_device_ms_per_step", "update_device_ms_per_step",
                 "unscoped_device_pct"):
        reader = importlib.import_module("benchmarks.layer_metrics." + name)
        assert reader.read(ctx) is None
    assert st.of_run({"trace_dir": None}) is None
    st._MEMO.clear()
    (tmp_path / "t.xplane.pb").write_bytes(
        field(1, plane("/host:CPU", {}, {})))
    assert st.read_xplane(str(tmp_path / "t.xplane.pb")) is None
    st._MEMO.clear()


#: ``op_name``s as the v5e's runtime recorded them (my chip run, PR 25)
RECORDED = [
    ("jit(window_fn)/while/body/closed_call/jvp(L16.dropout)/"
     "jit(_threefry_split)/slice:", ("L16.dropout", False)),
    ("jit(window_fn)/while/body/closed_call/jit(_threefry_split)/"
     "FusedNet._get_window_fn.<locals>.body/add:", (None, False)),
    ("jit(window_fn)/while:", (None, False)),
    ("jit(fwd_idx)/convert_element_type:", (None, False)),
    ("", (None, False)),
    ("jit(fwd_idx)/L04.conv/conv_general_dilated:", ("L04.conv", False)),
    ("jit(window_fn)/while/body/closed_call/transpose(jvp(L01.pool))/"
     "select_and_scatter_add:", ("L01.pool", True)),
    ("jit(window_fn)/while/body/closed_call/gather/gather:",
     ("gather", False)),
    ("jit(window_fn)/while/body/closed_call/jvp(loss)/reduce_sum:",
     ("loss", False)),
    ("jit(window_fn)/while/body/closed_call/transpose(jvp(loss))/mul:",
     ("loss", True)),
    ("jit(window_fn)/while/body/closed_call/update.L14/sub:",
     ("update.L14", False)),
    ("jit(window_fn)/acc/add:", ("acc", False)),
    ("jit(window_fn)/while/body/closed_call/eval_stats/dot_general:",
     ("eval_stats", False)),
    # a primitive or a function that is merely called like a scope
    ("jit(window_fn)/while/body/closed_call/jit(gather)/mul:",
     (None, False)),
    ("jit(window_fn)/while/body/closed_call/L1.conv/mul:", (None, False)),
]


@pytest.mark.parametrize("op_name,expected", RECORDED)
def test_scope_of(op_name, expected):
    assert st.scope_of(op_name) == expected


def test_kind_of():
    assert st.kind_of("L00.conv") == "conv"
    assert st.kind_of("L03.zerofill") == "zerofill"
    assert st.kind_of("update.L00") is None and st.kind_of(None) is None
