"""Real-data parity runs.

The accuracy-parity path must be ONE command on a networked machine and
must fail FAST and explicitly in this zero-egress environment — never
silently train on the synthetic fallback.
"""

import os
import time

import numpy
import pytest

from znicz_tpu import parity


def test_ensure_dataset_offline_fails_fast_with_clear_message(tmp_path):
    start = time.time()
    with pytest.raises(SystemExit) as e:
        parity.ensure_dataset("mnist", directory=str(tmp_path))
    msg = str(e.value)
    assert "network required" in msg
    assert "MNIST" in msg or "mnist" in msg
    assert str(tmp_path) in msg  # tells the user where to put files
    # fail fast: bounded by the per-request timeout, not a hang
    assert time.time() - start < 4 * parity.TIMEOUT


def test_ensure_dataset_skips_when_files_present(tmp_path):
    for f in parity.DATASETS["mnist"]["files"]:
        open(os.path.join(str(tmp_path), f), "wb").close()
    assert parity.ensure_dataset("mnist", directory=str(tmp_path)) == \
        str(tmp_path)


def test_parity_run_trains_on_provisioned_files(tmp_path, monkeypatch):
    """With the dataset present (tiny IDX files standing in for the real
    ones), --parity style invocation trains without network and prints
    the table row."""
    import struct

    def write_idx(path, images, labels_path, labels):
        n = len(labels)
        with open(path, "wb") as f:
            f.write(struct.pack(">2i", 2051, n))
            f.write(struct.pack(">2i", 28, 28))
            f.write(images.astype(numpy.uint8).tobytes())
        with open(labels_path, "wb") as f:
            f.write(struct.pack(">2i", 2049, n))
            f.write(labels.astype(numpy.uint8).tobytes())

    r = numpy.random.RandomState(0)
    d = str(tmp_path)
    write_idx(os.path.join(d, "train-images.idx3-ubyte"),
              r.randint(0, 255, (60000, 28, 28)),
              os.path.join(d, "train-labels.idx1-ubyte"),
              r.randint(0, 10, 60000))
    write_idx(os.path.join(d, "t10k-images.idx3-ubyte"),
              r.randint(0, 255, (10000, 28, 28)),
              os.path.join(d, "t10k-labels.idx1-ubyte"),
              r.randint(0, 10, 10000))

    monkeypatch.setitem(parity.PARITY_RUNS, "mnist",
                        [("MNIST MLP", 1.92, {})])
    from znicz_tpu.core.config import root
    saved = root.mnistr.decision.max_epochs
    root.mnistr.decision.max_epochs = 1
    try:
        # fused f32 (bf16 is the real-TPU default; on the CPU test host
        # it is emulated and pointlessly slow) + a short unit-path
        # cross-check — the WIRING is what this test pins
        rows = parity.run_parity("mnist", data_dir=d, fused={},
                                 cross_check=4)
    finally:
        root.mnistr.decision.max_epochs = saved
    (label, ref_err, ours), = rows
    assert label == "MNIST MLP" and ref_err == 1.92
    assert ours is not None and 0.0 <= ours <= 100.0


def test_cli_parity_flag_is_wired():
    """--parity reaches parity.run_parity through the CLI parser."""
    from znicz_tpu import __main__ as cli
    called = {}

    def fake(sample, device=None, fused="auto", **kwargs):
        called["sample"] = sample
        called["fused"] = fused
        return []

    orig = parity.run_parity
    parity.run_parity = fake
    try:
        cli.main(["mnist", "--parity"])
    finally:
        parity.run_parity = orig
    assert called["sample"] == "mnist"
