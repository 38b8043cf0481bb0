"""The span tree (ISSUE 25): what a span records, the spans of the fused
trainer's host path, and the layer scopes on its device ops.

* a span carries integer ``perf_counter_ns`` stamps, an id, its parent's
  id and the ``window`` its unit of work shares; ``self_times`` takes the
  children out of a duration;
* with telemetry off a fused run leaves the ring empty, keeps no stack
  and enters no ``TraceAnnotation``;
* with it on, a tiny fused run (one device, and a data=2 mesh of virtual
  devices) yields every span of the table in docs/observability.md, each
  ``trainer.*`` under ``unit.<trainer>``;
* the scopes name the ops (``L00.`` forward, ``transpose(jvp(L00.``
  backward) and leave the program byte-identical but for metadata.
"""

import contextlib
import re
import time

import numpy
import pytest

import jax
import jax.numpy as jnp

from znicz_tpu.core.config import root
from znicz_tpu.core import prng, telemetry
from znicz_tpu.core.backends import JaxDevice
from znicz_tpu.loader.base import (FullBatchLoader, IFullBatchLoader,
                                   TEST, VALID, TRAIN)
from znicz_tpu.parallel import fused
from znicz_tpu.standard_workflow import StandardWorkflow

N_IN, N_VALID, N_TRAIN, BATCH = 96, 24, 256, 64
#: a net whose steps take milliseconds on the CPU, for the test that sets
#: the host's glue between the spans against the spans themselves
WIDE = {"n_in": 1024, "hidden": 4096, "batch": 256, "n_train": 1024}


def _layers(hidden=256):
    return [
        {"type": "all2all_tanh", "->": {"output_sample_shape": hidden},
         "<-": {"learning_rate": 0.05}},
        {"type": "softmax", "->": {"output_sample_shape": 10},
         "<-": {"learning_rate": 0.05}},
    ]


class SpanTestRows(FullBatchLoader, IFullBatchLoader):
    """Stock full-batch loader (the device-data window path engages)
    with a validation split, so every epoch has a ``trainer.valid``."""

    MAPPING = "span_test_rows"

    def __init__(self, workflow, **kwargs):
        kwargs.setdefault("normalization_type", "none")
        super(SpanTestRows, self).__init__(workflow, **kwargs)
        self._n_in = int(kwargs.get("n_in", N_IN))
        self._n_train = int(kwargs.get("n_train", N_TRAIN))

    def load_data(self):
        rng = numpy.random.RandomState(7)
        n = N_VALID + self._n_train
        self.original_data.reset(
            rng.uniform(-1, 1, (n, self._n_in)).astype(numpy.float32))
        del self._original_labels[:]
        self._original_labels.extend((numpy.arange(n) % 10).tolist())
        self.class_lengths[TEST] = 0
        self.class_lengths[VALID] = N_VALID
        self.class_lengths[TRAIN] = self._n_train


@pytest.fixture(autouse=True)
def _prng_streams_restored():
    """These tests seed the process-global streams; whatever runs after
    them in the same worker finds the streams as they were."""
    prng.get(1), prng.get(2)
    before = prng.states()
    yield
    prng.restore(before)


@pytest.fixture
def tel():
    root.common.telemetry.enabled = True
    telemetry.reset()
    yield telemetry
    telemetry.reset()


def _run(tmp_path, epochs=2, n_in=N_IN, hidden=256, batch=BATCH,
         n_train=N_TRAIN, **fused_cfg):
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    fused_cfg.setdefault("window", 2)
    wf = StandardWorkflow(
        None, layers=_layers(hidden),
        loader_name=SpanTestRows.MAPPING,
        loader_config={"minibatch_size": batch, "n_in": n_in,
                       "n_train": n_train},
        decision_config={"max_epochs": epochs, "fail_iterations": 100},
        snapshotter_config={"prefix": "spans", "interval": 10 ** 9,
                            "time_interval": 1e9, "compression": "",
                            "directory": str(tmp_path)},
        fused=fused_cfg)
    wf.initialize(device=JaxDevice())
    wf.run()
    return wf


# -- what a span records ------------------------------------------------------

def test_span_records_id_parent_and_integer_ns(tel):
    t_before = time.perf_counter_ns()
    with tel.span("outer", phase="train"):
        with tel.span("inner"):
            pass
        with tel.span("inner"):
            pass
    with tel.span("alone"):
        pass
    t_after = time.perf_counter_ns()
    spans = tel.spans()
    assert [s[0] for s in spans] == ["inner", "inner", "outer", "alone"]
    by_name = {s[0]: s for s in spans}
    outer, alone = by_name["outer"], by_name["alone"]
    for name, t0, dur, sid, parent, attrs in spans:
        assert type(t0) is int and type(dur) is int
        # absolute, on the clock any reader can take itself
        assert t_before <= t0 <= t0 + dur <= t_after
        assert sid > 0
    assert len({s[3] for s in spans}) == 4
    assert outer[4] == 0 and alone[4] == 0
    assert [s[4] for s in spans[:2]] == [outer[3], outer[3]]
    assert outer[5] == {"phase": "train"} and alone[5] == {}


def test_children_inherit_the_window_and_set_adds_attrs(tel):
    with tel.span("fused.window", step_num=7, window=7) as sp:
        with tel.span("trainer.collect"):
            with tel.span("loader.fill", clazz="train"):
                pass
        with tel.span("trainer.valid", window=8):
            pass
        sp.set(steps=2, final=False)
    attrs = {s[0]: s[5] for s in tel.spans()}
    assert attrs["fused.window"] == {"window": 7, "steps": 2,
                                     "final": False}
    assert attrs["trainer.collect"] == {"window": 7}
    assert attrs["loader.fill"] == {"clazz": "train", "window": 7}
    # a span's own identifier wins over the inherited one
    assert attrs["trainer.valid"] == {"window": 8}


def test_instant_carries_its_epoch_its_parent_and_an_integer_stamp(tel):
    with tel.span("unit.loader"):
        tel.instant("loader.epoch_end", epoch=3)
    tel.instant("loader.epoch_end", epoch=4)
    (loader,) = tel.spans()
    first, second = tel.spans("i")
    assert first[0] == "loader.epoch_end" and first[5] == {"epoch": 3}
    assert type(first[1]) is int and first[2] == 0 and first[3] == 0
    assert loader[1] <= first[1] <= loader[1] + loader[2]
    assert first[4] == loader[3] and second[4] == 0


def test_self_times_on_a_hand_made_nest():
    spans = [("leaf", 110, 20, 3, 2, {}),
             ("leaf", 140, 30, 4, 2, {}),
             ("mid", 100, 80, 2, 1, {}),
             ("other", 190, 5, 5, 1, {}),
             ("top", 90, 120, 1, 0, {}),
             ("orphan", 500, 10, 9, 77, {})]    # parent fell off the ring
    assert telemetry.self_times(spans) == {
        3: 20, 4: 30, 2: 80 - 20 - 30, 5: 5, 1: 120 - 80 - 5, 9: 10}


def test_chrome_export_keeps_its_own_origin(tel):
    with tel.span("outer"):
        with tel.span("inner"):
            pass
    inner, outer = tel.trace_events()
    # microseconds from the module's import, not from the clock's zero
    assert 0 <= outer["ts"] < (time.perf_counter_ns()
                               - telemetry._T0_NS) / 1e3
    assert inner["parent_id"] == outer["span_id"]
    assert "parent_id" not in outer


# -- off: nothing; on: annotations ---------------------------------------------

class _Counting(object):
    """Stands in for a ``jax.profiler`` annotation class."""

    def __init__(self, seen):
        self.seen = seen

    def __call__(self, name, **kwargs):
        self.seen.append((name, kwargs))
        return contextlib.nullcontext()


@pytest.fixture
def annotations(monkeypatch):
    seen = {"trace": [], "step": []}
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _Counting(seen["trace"]))
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation",
                        _Counting(seen["step"]))
    return seen


def test_off_leaves_no_event_no_stack_and_no_annotation(
        tmp_path, annotations):
    root.common.telemetry.enabled = False
    telemetry.reset()
    wf = _run(tmp_path)
    assert wf.fused_trainer._use_device_data
    assert telemetry.spans() == [] and telemetry.spans("i") == []
    assert "stack" not in telemetry._open.__dict__
    assert annotations == {"trace": [], "step": []}
    assert wf.fused_trainer._span_serial == 0
    assert telemetry.snapshot()["counters"] == {}


def test_on_enters_an_annotation_per_span_and_a_step_per_window(
        tmp_path, tel, annotations):
    _run(tmp_path)
    names = [s[0] for s in tel.spans()]
    assert sorted(n for n, _ in annotations["trace"]) == sorted(
        n for n in names if n != "fused.window")
    windows = [s[5]["window"] for s in tel.spans()
               if s[0] == "fused.window"]
    assert annotations["step"] == [
        ("fused.window", {"step_num": w}) for w in windows]
    # nothing is left open
    assert telemetry._open.__dict__.get("stack") == []


# -- the spans of a fused run -------------------------------------------------

ALWAYS = ("workflow.run", "loader.fill", "fused.window", "trainer.collect",
          "trainer.place", "trainer.dispatch", "trainer.readback",
          "trainer.valid", "trainer.valid.place", "trainer.valid.dispatch",
          "trainer.set_dataset")


def _children(spans):
    kids = {}
    for s in spans:
        kids.setdefault(s[4], []).append(s)
    return kids


@pytest.mark.parametrize("mesh", [None, 2], ids=["one_device", "mesh2"])
def test_fused_run_yields_every_span_nested_under_the_trainer(
        tmp_path, tel, mesh):
    cfg = {"mesh": mesh} if mesh else {}
    # no window in flight may be left behind: the host waits in
    # trainer.wait for every mid-epoch window
    wf = _run(tmp_path, epochs=3, pipeline_depth=0, **cfg)
    spans = tel.spans()
    by_id = {s[3]: s for s in spans}
    names = {s[0] for s in spans}
    unit = "unit." + wf.fused_trainer.name
    assert set(ALWAYS) | {unit, "trainer.wait"} <= names, \
        sorted(set(ALWAYS) | {"trainer.wait"} - names)

    def ancestors(s):
        while s[4] in by_id:
            s = by_id[s[4]]
            yield s[0]

    for s in spans:
        if s[0].startswith("trainer.") or s[0] == "fused.window":
            assert unit in list(ancestors(s)), s
    kids = _children(spans)
    parents = {"fused.window": {"trainer.collect", "trainer.place",
                                "trainer.dispatch", "trainer.wait",
                                "trainer.readback"},
               "trainer.valid": {"trainer.valid.place",
                                 "trainer.valid.dispatch",
                                 "trainer.readback"}}
    for s in spans:
        if s[0] in parents:
            mine = kids[s[3]]
            assert {k[0] for k in mine} <= parents[s[0]]
            # one identifier for the spans of one unit of work
            assert {k[5]["window"] for k in mine} == {s[5]["window"]}
    # windows and validation minibatches count on in one series
    serial = sorted(s[5]["window"] for s in spans if s[0] in parents)
    assert serial == list(range(1, len(serial) + 1))
    finals = [s[5]["final"] for s in spans if s[0] == "fused.window"]
    assert finals == [False, True] * 3
    assert {s[5]["steps"] for s in spans if s[0] == "fused.window"} == {2}
    for s in spans:
        if s[0] in ("trainer.place", "trainer.valid.place",
                    "trainer.readback", "trainer.set_dataset"):
            assert s[5]["bytes"] > 0, s
    # the epochs are cut by the loader's markers
    assert [m[5]["epoch"] for m in tel.spans("i")
            if m[0] == "loader.epoch_end"] == [1, 2, 3]


def test_children_cover_the_window_and_the_validation_minibatch(
        tmp_path, tel):
    # pipeline_depth=0: every window's device time lies in a child of
    # its own (trainer.wait or trainer.readback), as the validation
    # forward's lies in its trainer.readback
    _run(tmp_path, epochs=4, pipeline_depth=0, **WIDE)
    spans = tel.spans()
    self_ns = tel.self_times(spans)
    # past the first epoch (set-up: placing the data set, compiling)
    first = min(m[1] for m in tel.spans("i"))
    for name in ("fused.window", "trainer.valid"):
        mine = [s for s in spans if s[0] == name and s[1] > first]
        assert len(mine) >= 3
        short = [(s, self_ns[s[3]]) for s in mine
                 if self_ns[s[3]] > 0.05 * s[2]]
        # each of them, but for one that the scheduler may have cut into
        # (the workers of a test run share the cores)
        assert len(short) <= 1, short


@pytest.mark.parametrize("fused_cfg,nbytes", [
    # resident path (ISSUE 26): the minibatch crosses as its row indices
    ({}, BATCH * 4),
    # streaming: 24 validation rows are served as one minibatch padded to
    # 64, with the labels' stand-in (int32 zeros) beside it
    ({"device_data": False}, BATCH * N_IN * 4 + BATCH * 4),
], ids=["resident_indices", "streaming_padded_rows"])
def test_valid_place_bytes_are_what_crosses(tmp_path, tel, fused_cfg,
                                            nbytes):
    _run(tmp_path, epochs=3, **fused_cfg)
    placed = [s[5]["bytes"] for s in tel.spans()
              if s[0] == "trainer.valid.place"]
    assert placed == [nbytes] * 3
    counters = tel.snapshot()["counters"]
    assert counters["transfer.h2d_bytes"] >= sum(placed)


def test_a_leaf_on_the_device_is_no_host_to_device_byte():
    host = numpy.zeros((4, 8), numpy.float32)
    assert fused._nbytes(host, {"placed": jnp.zeros((4, 8))}, None) \
        == host.nbytes


# -- layer scopes on the device ops -------------------------------------------

CONV_LAYERS = [
    {"type": "conv_relu", "->": {"n_kernels": 4, "kx": 3, "ky": 3},
     "<-": {"learning_rate": 0.01}},
    {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
    {"type": "softmax", "->": {"output_sample_shape": 5},
     "<-": {"learning_rate": 0.01}},
]


def _lowered_step():
    specs = tuple(fused.build_specs(CONV_LAYERS, (8, 8, 3)))
    params = fused.init_params(specs, prng.get(1))
    state = fused.init_opt_state(specs, params)
    x = numpy.zeros((4, 8, 8, 3), numpy.float32)
    labels = numpy.zeros(4, numpy.int32)
    return jax.jit(lambda p, s, x, l: fused._train_step(
        p, s, x, l, specs)).lower(params, state, x, labels)


def _strip_metadata(hlo_text):
    return re.sub(r",? ?metadata=\{[^}]*\}", "", hlo_text)


def _op_names(lowered):
    """The names jax gives the lowered ops (HLO ``op_name``)."""
    return set(re.findall(r'loc\("([^"]+)"',
                          lowered.as_text(debug_info=True)))


def _has(names, component):
    """Some op stands under the path component ``component``."""
    return any(component in n.split("/")[:-1] for n in names)


def test_scopes_name_forward_and_backward_ops():
    names = _op_names(_lowered_step())
    for scope in ("L00.conv", "L01.pool", "L02.fc", "loss"):
        assert _has(names, "jvp(%s)" % scope), scope
        assert _has(names, "transpose(jvp(%s))" % scope), scope
    assert _has(names, "update.L00") and _has(names, "update.L02")
    assert not _has(names, "update.L01")    # a pool has no parameters
    # and in the compiled program's metadata, where the profiler reads it
    compiled = _lowered_step().compile().as_text()
    assert 'op_name="jit(<lambda>)/transpose(jvp(L00.conv))/' in compiled


def test_scopes_change_nothing_but_metadata(monkeypatch):
    lowered = []
    for scoped in (True, False):    # one call site: the same stack frames
        if not scoped:
            monkeypatch.setattr(jax, "named_scope",
                                lambda name: contextlib.nullcontext())
        lowered.append(_lowered_step())
    with_scopes, without = lowered
    assert "L00.conv" in with_scopes.as_text(debug_info=True)
    assert "L00.conv" not in without.as_text(debug_info=True)
    # what is lowered, locations aside
    assert with_scopes.as_text() == without.as_text()
    # and the program the compiler makes of it
    a = with_scopes.compile().as_text()
    b = without.compile().as_text()
    assert "L00.conv" in a and "L00.conv" not in b
    assert _strip_metadata(a) == _strip_metadata(b)


def test_window_program_scopes_the_gather_and_the_accumulators():
    net = fused.FusedNet(CONV_LAYERS, (8, 8, 3), rand=prng.get(1))
    net.set_dataset(numpy.zeros((16, 8, 8, 3), numpy.float32),
                    list(range(16)))
    fn = net._get_window_fn(2, "indexed")
    hy = jax.tree.map(lambda v: numpy.full((2,), v, numpy.float32),
                      fused.default_hypers(net.specs))
    names = _op_names(fn.lower(
        net.params, net.state, net._key, net._data_d, net._labels_d,
        jnp.zeros((2, 4), jnp.int32), None, jnp.array([4, 4], jnp.int32),
        hy, net._window_acc()))
    for scope in ("gather", "eval_stats", "acc", "jvp(loss)",
                  "jvp(L00.conv)", "update.L00"):
        assert _has(names, scope), scope
