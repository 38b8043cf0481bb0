"""CI smoke: mesh-sharded asynchronous fused training end to end — the
wine fused config trained on a 1-device and a 4-device (data-parallel)
mesh over forced virtual CPU host devices, asserting the sharded
control-plane contract (ISSUE 6):

* identical final decision aggregates: per-epoch error integers and the
  confusion matrix EXACT, max_err_output_sum within a few float32 units
  in the last place (the shard fold is a max, reduction-order
  independent, but the per-sample sums under it come from two
  differently partitioned programs),
* the one-readback-per-segment invariant SURVIVES sharding:
  ``trainer.readbacks == segments`` and telemetry ``d2h_calls ==
  segments`` on the 4-shard run, exactly like the 1-device run,
* the telemetry summary reports the mesh extents
  (``data_shards``/``model_shards``) the run executed under.

Run by ``tools/ci.sh`` (fast lane).  Exit code 0 = pass.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the virtual device count must be forced BEFORE jax initializes a
# backend (same recipe as tests/conftest.py)
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy  # noqa: E402

from znicz_tpu.core.config import root  # noqa: E402
from znicz_tpu.core import prng, telemetry  # noqa: E402
from znicz_tpu.core.backends import JaxDevice  # noqa: E402

EPOCHS = 3
WINDOW = 4
MB = 16  # wine: 178 samples -> 12 minibatches; divisible by 4 shards

LAYERS = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
     "<-": {"learning_rate": 0.1}},
    {"type": "softmax", "->": {"output_sample_shape": 3},
     "<-": {"learning_rate": 0.1}},
]


def run(fused_cfg):
    import znicz_tpu.loader.loader_wine  # noqa: F401
    from znicz_tpu.standard_workflow import StandardWorkflow
    telemetry.reset()
    prng.get(1).seed(1234)
    prng.get(2).seed(5678)
    wf = StandardWorkflow(
        None, layers=[dict(l) for l in LAYERS],
        loader_name="wine_loader",
        loader_config={"minibatch_size": MB},
        decision_config={"max_epochs": EPOCHS, "fail_iterations": 100},
        snapshotter_config={"prefix": "msmoke", "interval": 10 ** 9,
                            "time_interval": 1e9, "compression": ""},
        fused=dict({"window": WINDOW}, **fused_cfg))
    wf.initialize(device=JaxDevice())
    wf.run()
    return wf, telemetry.summary()


def main():
    tmp = tempfile.mkdtemp(prefix="mesh_smoke_")
    root.common.dirs.snapshots = os.path.join(tmp, "snapshots")
    telemetry.enable()

    wf_1, tele_1 = run({})
    wf_4, tele_4 = run({"mesh": 4})

    assert wf_4.fused_trainer.net.data_shards == 4
    assert tele_4.get("data_shards") == 4, tele_4

    # identical integer aggregates
    assert list(wf_1.decision.epoch_n_err) == \
        list(wf_4.decision.epoch_n_err), \
        (wf_1.decision.epoch_n_err, wf_4.decision.epoch_n_err)
    for ca, cb in zip(wf_1.decision.confusion_matrixes,
                      wf_4.decision.confusion_matrixes):
        if ca is None or cb is None:
            assert ca is None and cb is None
            continue
        numpy.testing.assert_array_equal(ca, cb)
    # the max fold: exact as a max, but its float32 operands are made
    # by one program on one device and by another on four shards (the
    # tier-1 twin and its reason: tests/functional/test_mesh_training.py
    # MESH_MAX_ULPS)
    numpy.testing.assert_array_max_ulp(
        numpy.float32(wf_1.decision.max_err_y_sums),
        numpy.float32(wf_4.decision.max_err_y_sums), maxulp=4)

    # parameters: the gradient psum reassociates the same f32 batch sum
    for la, lb in zip(wf_1.fused_trainer.host_params(),
                      wf_4.fused_trainer.host_params()):
        for k in la:
            numpy.testing.assert_allclose(la[k], lb[k], rtol=1e-5,
                                          atol=1e-6)

    # the PR 5 invariant survives sharding: one readback per segment on
    # BOTH runs (wine has a single TRAIN segment per epoch)
    segments = EPOCHS
    assert tele_1.get("readbacks") == segments, tele_1
    assert tele_4.get("readbacks") == segments, tele_4
    assert tele_4.get("d2h_calls") == segments, tele_4

    print("mesh smoke OK: %d epochs, 1-dev vs 4-shard aggregates "
          "identical, readbacks %d==%d (1/segment), d2h calls %d, "
          "d2h %d B vs %d B per run"
          % (EPOCHS, tele_1["readbacks"], tele_4["readbacks"],
             tele_4["d2h_calls"], tele_1["d2h_bytes"],
             tele_4["d2h_bytes"]))


if __name__ == "__main__":
    main()
