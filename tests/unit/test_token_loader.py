"""Rows of token ids: packing, the loader, and the resident path that
keeps integers integers."""
import numpy
import pytest

import jax.numpy as jnp

from znicz_tpu.loader.base import FullBatchLoader
from znicz_tpu.loader.tokens import pack_rows
from znicz_tpu.parallel import fused
from znicz_tpu.samples.research import looped_lm


def _packed():
    lengths = [5, 3, 9, 4, 20]
    ids = numpy.arange(100, 132)
    return pack_rows(lengths, ids, 4, 8)


def test_labels_are_the_next_id_inside_the_same_document_and_row():
    ids, labels, segments = _packed()
    flat_doc = numpy.repeat(numpy.arange(5), [5, 3, 9, 4, 20])[:32]
    for r in range(4):
        for c in range(8):
            at = r * 8 + c
            inside = c < 7 and flat_doc[at + 1] == flat_doc[at]
            assert labels[r, c] == (ids[r, c + 1] if inside else -1)
    assert (labels[:, -1] == -1).all()
    assert ids.dtype == labels.dtype == segments.dtype == numpy.int32


def test_a_document_split_at_a_rows_end_is_two_segments():
    _, labels, segments = _packed()
    # the third document (9 tokens from position 8) fills row 1 and spills
    assert (segments[1] == 1).all() and segments[2, 0] == 1
    assert labels[1, 7] == -1
    assert list(segments[0]) == [1] * 5 + [2] * 3
    assert list(segments[2]) == [1] + [2] * 4 + [3] * 3


def test_documents_must_cover_the_stream():
    with pytest.raises(ValueError, match="cover"):
        pack_rows([3, 3], numpy.arange(16), 2, 8)


@pytest.fixture(scope="module")
def net_and_rows():
    rs = numpy.random.RandomState(2)
    ids = rs.randint(0, 60000, (6, 16)).astype(numpy.int32)
    ids[0, 0] = 49151
    labels = rs.randint(-1, 60000, (6, 16)).astype(numpy.int32)
    segments = rs.randint(1, 9, (6, 16)).astype(numpy.int32)
    layers = looped_lm.make_layers(vocab=64, dim=16, heads=2, kv_heads=2,
                                   head_dim=8, hidden=16, n_layers=1,
                                   passes=2)
    net = fused.FusedNet(layers, (16,), objective="tokens",
                         compute_dtype=jnp.bfloat16)
    net.set_dataset(ids, labels, segments=segments)
    return net, ids, labels, segments


@pytest.mark.parametrize("what", ["ids", "labels", "segments"])
def test_integers_survive_set_dataset_under_a_bf16_compute_type(
        net_and_rows, what):
    net, ids, labels, segments = net_and_rows
    held = {"ids": net._data_d, "labels": net._labels_d,
            "segments": net._segments_d}[what]
    want = {"ids": ids, "labels": labels, "segments": segments}[what]
    assert held.dtype == jnp.int32
    assert (numpy.asarray(held) == want).all()


def test_float_rows_are_still_stored_in_the_compute_type():
    net = fused.FusedNet(
        [{"type": "softmax", "->": {"output_sample_shape": 3}}], (4,),
        compute_dtype=jnp.bfloat16)
    net.set_dataset(numpy.ones((5, 4), numpy.float32), [0, 1, 2, 0, 1])
    assert net._data_d.dtype == jnp.bfloat16
    assert net._segments_d is None


def test_rows_labels_and_segments_are_gathered_alike(net_and_rows):
    net, ids, labels, segments = net_and_rows
    idx = jnp.asarray([4, -1, 2], jnp.int32)
    x, lbl, seg, rows = fused._gather_token_rows(
        net._data_d, (net._labels_d, net._segments_d), idx)
    assert (numpy.asarray(x)[[0, 2]] == ids[[4, 2]]).all()
    assert (numpy.asarray(lbl)[[0, 2]] == labels[[4, 2]]).all()
    assert (numpy.asarray(seg)[[0, 2]] == segments[[4, 2]]).all()
    assert (numpy.asarray(lbl)[1] == -1).all()   # a padded slot grades none
    assert int(rows[0]) == 2


def test_loader_keeps_the_stock_fill_and_integer_buffers():
    """So that the resident path, PR 26's put-off fill and the indexed
    validation engage; set_dataset's span carries the three arrays' bytes."""
    from znicz_tpu.core import telemetry
    from znicz_tpu.core.backends import JaxDevice
    from znicz_tpu.core.config import root
    was = root.common.telemetry.get("enabled", False)
    telemetry.enable()
    telemetry.reset()
    try:
        wf = looped_lm.build(fused={"window": 2},
                             decision_config={"max_epochs": 2})
        wf.initialize(device=JaxDevice())
        wf.run()
        loader = wf.loader
        assert type(loader).fill_minibatch is FullBatchLoader.fill_minibatch
        assert loader.minibatch_data.dtype == numpy.int32
        assert loader.minibatch_labels.shape == loader.minibatch_data.shape
        assert wf.fused_trainer._use_device_data and loader.skip_fill
        assert telemetry.counter("loader.fill_deferred").value > 0
        assert telemetry.counter("loader.fill_forced").value == 0
        span = [s for s in telemetry.spans()
                if s[0] == "trainer.set_dataset"]
        assert len(span) == 1
        assert span[0][5]["bytes"] == 3 * loader.original_data.mem.nbytes
    finally:
        root.common.telemetry.enabled = was
