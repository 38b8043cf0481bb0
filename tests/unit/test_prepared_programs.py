"""The programs of the first segment, prepared while the parameters are
drawn.

The fused trainer's initialize starts the initial parameters' draw on a
thread of its own and meanwhile traces, lowers and compiles from shapes
the programs that the first segment calls (``FusedNet.prepare``):

* a tiny token workflow counts ``trainer.programs_prepared``, and its
  first window and validation minibatch lower and compile nothing: jax's
  call path finds the prepared programs;
* it lowers and compiles no more programs than the same run whose
  programs are traced at their first call, as they were before;
* the optimizer state is made on the device as zeros, each slot with its
  parameter's sharding, on the CPU's fake-device mesh too;
* a resume right after initialize keeps the restored parameters, and the
  stream is left as the whole draw leaves it.
"""
import numpy
import pytest

import jax
import jax.numpy as jnp

from znicz_tpu.core import prng, telemetry
from znicz_tpu.core.backends import JaxDevice
from znicz_tpu.core.config import root
from znicz_tpu.parallel import fused, make_mesh
from znicz_tpu.samples.research import looped_lm
from znicz_tpu.units.fused_trainer import FusedForwardBackward


@pytest.fixture(autouse=True)
def _streams_restored():
    prng.get(1), prng.get(2)
    before = prng.states()
    yield
    prng.restore(before)


@pytest.fixture
def tel():
    was = root.common.telemetry.get("enabled", False)
    telemetry.enable()
    telemetry.reset()
    yield telemetry
    telemetry.reset()
    root.common.telemetry.enabled = was


def _workflow(epochs=1):
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    return looped_lm.build(fused={"window": 2},
                           decision_config={"max_epochs": epochs})


def _run(epochs=1):
    jax.clear_caches()
    wf = _workflow(epochs)
    wf.initialize(device=JaxDevice())
    wf.run()
    return wf


def _under(spans, names):
    by_id = {s[3]: s for s in spans}
    return [s for s in spans
            if by_id.get(s[4], ("",))[0] in names]


def test_the_first_calls_run_the_prepared_programs(tel):
    _run()
    spans = tel.spans()
    assert tel.counter("trainer.programs_prepared").value == 2
    calls = _under(spans, ("trainer.dispatch", "trainer.valid.dispatch"))
    assert not [s for s in calls if s[0] in ("jax.lower", "jax.compile")]
    # the call's trace is jax's lookup of the traced program: it runs no
    # python of the program, so no other trace lies inside it
    looked_up = {s[3] for s in calls if s[0] == "jax.trace"}
    assert len(looked_up) == 2
    assert not [s for s in spans if s[4] in looked_up]
    prepared = [s[5]["fun"] for s in _under(
        spans, ("unit.fused_trainer.initialize",)) if s[0] == "jax.compile"]
    assert {"jit(window_fn)", "jit(fwd_tokens_at)"} <= set(prepared)


def test_no_more_programs_than_when_each_is_traced_at_its_call(
        tel, monkeypatch):
    def counts():
        spans = tel.spans()
        return {name: sum(1 for s in spans if s[0] == name)
                for name in ("jax.trace", "jax.lower", "jax.compile")}

    _run()
    prepared = counts()
    telemetry.reset()
    monkeypatch.setattr(FusedForwardBackward, "_prepare_programs",
                        lambda self: None)
    _run()
    at_call = counts()
    assert tel.counter("trainer.programs_prepared").value == 0
    assert prepared["jax.lower"] == at_call["jax.lower"]
    assert prepared["jax.compile"] == at_call["jax.compile"]
    # one lookup more a prepared program, at its first call
    assert prepared["jax.trace"] == at_call["jax.trace"] + 2


@pytest.mark.parametrize("shards", [None, (4, 2)], ids=["one_device",
                                                        "mesh4x2"])
def test_optimizer_state_is_born_on_the_device(shards):
    layers = [{"type": "all2all_tanh", "->": {"output_sample_shape": 16},
               "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
              {"type": "softmax", "->": {"output_sample_shape": 8},
               "<-": {"learning_rate": 0.1}}]
    mesh = None if shards is None else make_mesh(8, model_parallel=2)
    net = fused.FusedNet(layers, (12,), mesh=mesh,
                         rand=prng.RandomGenerator().seed(3))
    slots = 0
    for spec, p, st in zip(net.specs, net.params, net.state):
        for name, leaf in p.items():
            for slot in st[name].values():
                assert isinstance(slot, jax.Array)
                assert slot.shape == leaf.shape and slot.dtype == leaf.dtype
                assert not numpy.asarray(slot).any()
                assert slot.sharding == leaf.sharding
                slots += 1
    assert slots == 4
    if shards is not None:
        assert net.state[0]["w"]["vel"].sharding.spec == \
            jax.sharding.PartitionSpec("model", None)
    # the same state the host made before
    host = fused.init_opt_state(net.specs, fused.init_params(
        net.specs, prng.RandomGenerator().seed(3)))
    assert jax.tree.structure(host) == jax.tree.structure(net.state)


def test_a_resume_right_after_initialize_keeps_the_restored_state():
    wf = _workflow()
    wf.initialize(device=JaxDevice())
    trainer = wf.fused_trainer
    restored = jax.tree.map(lambda a: numpy.full(a.shape, 0.25, a.dtype),
                            trainer.net.host_params())
    sd = trainer.fused_state
    sd["params"] = restored
    # a capture taken before the draw was over, applied at once
    wf2 = _workflow()
    wf2.initialize(device=JaxDevice())
    wf2.fused_trainer._apply_state(sd)
    for got, want in zip(wf2.fused_trainer.host_params(), restored):
        for name in want:
            assert (numpy.asarray(got[name]) == want[name]).all(), name
    # the draw ran whole: the stream is where the serial draw leaves it
    again = prng.RandomGenerator().seed(1234)
    fused.init_params(wf2.fused_trainer.net.specs, again)
    assert repr(prng.get(1).get_state()["np"]) == \
        repr(again.get_state()["np"])
    assert jnp.asarray(wf2.fused_trainer.net._key).shape == (2,)
