"""The initial parameters' draw keeps the legacy calls' bits.

The fillers draw by ``standard_normal`` / ``random_sample`` in chunks (the
legacy ``normal`` / ``uniform`` hold the GIL for the whole draw) and scale
in float64 as those calls do, so every weight is the one they gave, and the
stream is left where they left it:

* ``normal_into`` / ``uniform_into`` against ``normal`` / ``uniform``,
  chunk by chunk and whole, for several means, spreads and ranges;
* a ``moe`` entry's experts and shared expert, each from a stream of its
  own, drawn on the pool beside the main stream;
* ``fused.init_params`` of the tiny token configurations and of a small
  image net against a serial copy of the draw as it was before the change
  (kept below), byte for byte, and the stream's state after it;
* a stream lent to one thread's draw makes every other thread's use wait.
"""
import json
import os
import sys
import threading
import time

import numpy
import pytest

import benchmarks
from znicz_tpu.core import prng
from znicz_tpu.ops import transformer
from znicz_tpu.parallel import fused

BENCH = os.path.dirname(os.path.abspath(benchmarks.__file__))

#: a tiny token configuration -> its file under ``benchmarks/tests``
TINY = {"tiny_looped_lm": "tiny_lm", "tiny_routed_lm": "tiny_routed",
        "tiny_balanced_lm": "tiny_balanced",
        "tiny_latent_lm": "tiny_latent"}


def _tiny(name):
    with open(os.path.join(BENCH, "tests", TINY[name], "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    return fused.build_specs(cfg["layers"], tuple(cfg["input_sample_shape"]))


IMAGE_NET = [
    {"type": "conv_relu", "->": {"n_kernels": 6, "kx": 3, "ky": 3,
                                 "weights_filling": "gaussian",
                                 "weights_stddev": 0.05}},
    {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
    {"type": "all2all_tanh", "->": {"output_sample_shape": 24,
                                    "weights_filling": "uniform",
                                    "weights_stddev": 0.1,
                                    "bias_filling": "gaussian",
                                    "bias_stddev": 0.3}},
    {"type": "softmax", "->": {"output_sample_shape": 5}},
]


# -- the draw before the change, serial, on the legacy calls ------------------

def _legacy_fill(state, filling, arr, value):
    if filling == "uniform":
        arr[...] = state.uniform(-value, value,
                                 size=arr.shape).astype(arr.dtype)
    elif filling == "gaussian":
        arr[...] = state.normal(0, value, size=arr.shape).astype(arr.dtype)
    elif filling == "constant":
        arr[:] = value
    else:
        raise ValueError(filling)


def _legacy_init(specs, state, dtype=numpy.float32):
    params = []
    for spec in specs:
        if spec.kind == "moe":
            base = int(state.randint(0, 2 ** 31 - 1, 1).astype(int)[0])
            first, count = transformer._held(spec.attrs)
            table = transformer.leaves(spec)
            out = {name: numpy.full(shape, 0.0 if filling == "gaussian"
                                    else value, dtype=dtype)
                   for name, (shape, filling, value, _) in table.items()}
            for j in range(count):
                own = numpy.random.RandomState([base, first + j])
                for name in ("wg", "wu", "wd"):
                    out[name][j] = own.normal(0, table[name][2],
                                              out[name].shape[1:])
            own = numpy.random.RandomState([base,
                                            int(spec.attrs["experts"])])
            for name in ("sg", "su", "sd"):
                if name in out:
                    out[name][...] = own.normal(0, table[name][2],
                                                out[name].shape)
            params.append(out)
        elif spec.kind in transformer.KINDS:
            out = {}
            for name, (shape, filling, value, _) in \
                    transformer.leaves(spec).items():
                out[name] = numpy.zeros(shape, dtype=dtype)
                _legacy_fill(state, filling, out[name], value)
            params.append(out)
        elif spec.kind in ("fc", "conv"):
            shapes = fused.param_shapes([spec])[0]
            stddev = spec.init_stddev()
            p = {"w": numpy.zeros(shapes["w"], dtype=dtype)}
            _legacy_fill(state, spec.weights_filling, p["w"], stddev)
            if spec.include_bias:
                p["b"] = numpy.zeros(shapes["b"], dtype=dtype)
                _legacy_fill(state, spec.bias_filling, p["b"],
                             stddev if spec.bias_stddev is None
                             else spec.bias_stddev)
            params.append(p)
        else:
            params.append({})
    return params


def _same_state(a, b):
    sa, sb = a.get_state(), b.get_state()
    assert sa[0] == sb[0] and (sa[1] == sb[1]).all() and sa[2:] == sb[2:]


# -- the fillers --------------------------------------------------------------

@pytest.mark.parametrize("chunk", [7, prng.CHUNK], ids=["chunked", "whole"])
@pytest.mark.parametrize("mean,stddev", [
    (0, 0.02), (0.0, 1.0), (1.5, 0.3), (-2.0, 7.25),
    (0, numpy.float32(0.0137))])
def test_normal_into_gives_the_legacy_normal(monkeypatch, chunk, mean,
                                             stddev):
    monkeypatch.setattr(prng, "CHUNK", chunk)
    new, old = numpy.random.RandomState(11), numpy.random.RandomState(11)
    for shape in ((101,), (3, 5, 7), (1,)):
        out = numpy.empty(shape, numpy.float32)
        prng.normal_into(new, out, mean, stddev)
        want = old.normal(mean, stddev, shape).astype(numpy.float32)
        assert out.tobytes() == want.tobytes(), shape
    _same_state(new, old)


@pytest.mark.parametrize("chunk", [5, prng.CHUNK], ids=["chunked", "whole"])
@pytest.mark.parametrize("low,high", [(-1.0, 1.0), (-0.05, 0.05),
                                      (2.0, 3.5)])
def test_uniform_into_gives_the_legacy_uniform(monkeypatch, chunk, low,
                                               high):
    monkeypatch.setattr(prng, "CHUNK", chunk)
    new, old = numpy.random.RandomState(3), numpy.random.RandomState(3)
    out = numpy.empty((4, 9), numpy.float32)
    prng.uniform_into(new, out, low, high)
    want = old.uniform(low, high, out.shape).astype(numpy.float32)
    assert out.tobytes() == want.tobytes()
    _same_state(new, old)


def test_generator_fillers_clip_and_fill_views_as_before(monkeypatch):
    monkeypatch.setattr(prng, "CHUNK", 6)
    new, old = prng.RandomGenerator().seed(5), numpy.random.RandomState()
    old.seed(prng.RandomGenerator().seed(5)._seed_arr)
    whole = numpy.zeros((6, 8), numpy.float64)
    new.fill_normal_real(whole[:, ::2], 0.5, 2.0, clip_to_sigma=1.0)
    want = numpy.clip(old.normal(0.5, 2.0, (6, 4)), -1.5, 2.5)
    assert (whole[:, ::2] == want).all() and (whole[:, 1::2] == 0).all()
    out = numpy.empty(13, numpy.float32)
    new.fill(out, -0.25, 0.75)
    assert out.tobytes() == old.uniform(
        -0.25, 0.75, 13).astype(numpy.float32).tobytes()
    _same_state(new.state, old)


# -- the whole draw -----------------------------------------------------------

@pytest.mark.parametrize("name", sorted(TINY) + ["image_net"])
def test_init_params_equals_the_serial_legacy_draw(monkeypatch, name):
    specs = fused.build_specs(IMAGE_NET, (10, 10, 3)) \
        if name == "image_net" else _tiny(name)
    # chunks smaller than a leaf, so that a leaf is drawn in several
    monkeypatch.setattr(prng, "CHUNK", 257)
    rand = prng.RandomGenerator().seed(2147483659)
    state = numpy.random.RandomState()
    state.seed(rand._seed_arr)
    got = fused.init_params(specs, rand, numpy.float32)
    want = _legacy_init(specs, state)
    assert [list(p) for p in got] == [list(p) for p in want]
    for p, q in zip(got, want):
        for leaf in p:
            assert p[leaf].dtype == q[leaf].dtype
            assert p[leaf].tobytes() == q[leaf].tobytes(), leaf
    _same_state(rand.state, state)
    if name != "image_net":
        assert any(s.kind == "moe" for s in specs) == (
            name != "tiny_looped_lm")


def test_the_shapes_known_before_the_draw_are_the_draws():
    for specs in [_tiny(n) for n in sorted(TINY)] + [
            fused.build_specs(IMAGE_NET, (10, 10, 3))]:
        drawn = fused.init_params(specs, prng.RandomGenerator().seed(1))
        assert fused.param_shapes(specs) == [
            {k: v.shape for k, v in p.items()} for p in drawn]


def test_a_moe_entrys_experts_draw_from_their_own_streams():
    """A share of the experts draws for each expert what the whole layer
    draws for it, and the shared expert the same everywhere."""
    specs = _tiny("tiny_balanced_lm")
    moe = [i for i, s in enumerate(specs) if s.kind == "moe"]
    spec = specs[moe[0]]
    assert spec.attrs.get("shared_hidden")
    experts = int(spec.attrs["experts"])

    def drawn(held):
        entry = transformer.TokenSpec(
            type="moe", in_shape=spec.in_shape, out_shape=spec.out_shape,
            name=spec.name, attrs=dict(spec.attrs, held=held))
        return transformer.init(entry, prng.RandomGenerator().seed(9),
                                numpy.float32, fused._fill)()

    whole, share = drawn((0, experts)), drawn((2, experts - 3))
    for name in ("wg", "wu", "wd"):
        assert share[name].tobytes() == whole[name][2:experts - 1].tobytes()
    for name in ("sg", "su", "sd"):
        assert share[name].tobytes() == whole[name].tobytes()


# -- the stream lent to one thread --------------------------------------------

def test_a_lent_stream_makes_other_threads_wait():
    rand = prng.RandomGenerator().seed(4)
    go = threading.Event()
    drawn = []

    def draw():
        go.wait(5)
        time.sleep(0.05)
        drawn.append(rand.normal(0, 1, 3))
        release()

    thread = threading.Thread(target=draw, name="lent")
    release = rand.lend(thread)
    thread.start()
    go.set()
    # waits for the lent draw, then continues the stream after it
    after = rand.normal(0, 1, 2)
    thread.join(10)
    assert not thread.is_alive()
    again = prng.RandomGenerator().seed(4)
    assert (drawn[0] == again.normal(0, 1, 3)).all()
    assert (after == again.normal(0, 1, 2)).all()
    assert rand._lent is None


def test_readers_on_many_threads_never_cut_into_a_lent_draw(monkeypatch):
    """A draw of many small chunks on a lent stream while more threads
    than cores read the stream, the interpreter switching threads as
    often as it can: the draw is the stream's first values, whole."""
    monkeypatch.setattr(prng, "CHUNK", 3)
    rand = prng.RandomGenerator().seed(8)
    out = numpy.empty(3000, numpy.float64)
    read = []

    def draw():
        try:
            rand.fill_normal_real(out, 0.0, 1.0)
        finally:
            release()

    def reader():
        read.append(rand.normal(0, 1, 1)[0])

    thread = threading.Thread(target=draw, name="lent")
    readers = [threading.Thread(target=reader, name="reader%d" % i)
               for i in range(4 * (os.cpu_count() or 1))]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        release = rand.lend(thread)
        thread.start()
        for r in readers:
            r.start()
        for t in [thread] + readers:
            t.join(30)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(switch)
    again = prng.RandomGenerator().seed(8)
    assert out.tobytes() == again.normal(0, 1, out.size).tobytes()
    assert sorted(read) == sorted(again.normal(0, 1, len(readers)))
