"""Loader-tier tests on synthetic fixture files in real on-disk formats.

Covers LMDB (+ hand-written Datum protobuf
codec, cross-validated against the real protobuf runtime), STL-10 binary
files, ImageNet preprocessed .dat, and the ImageLoader base family.
"""

import json
import os
import pickle

import numpy

from znicz_tpu.core.workflow import DummyWorkflow
from znicz_tpu.loader.base import VALID, TRAIN, UserLoaderRegistry
from znicz_tpu.loader.caffe import Datum, BlobProto
from znicz_tpu.loader.lmdb_native import LMDBReader, write_lmdb


# -- Datum codec ------------------------------------------------------------

def _proto_datum_roundtrip(payload):
    """Parse ``payload`` with the REAL protobuf runtime (schema built
    dynamically to match caffe.proto) — the independent referee."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    pool = descriptor_pool.DescriptorPool()
    fdp = descriptor_pb2.FileDescriptorProto()
    fdp.name = "caffe_test.proto"
    fdp.package = "caffe_test"
    msg = fdp.message_type.add()
    msg.name = "Datum"
    F = descriptor_pb2.FieldDescriptorProto
    for name, number, ftype, label in (
            ("channels", 1, F.TYPE_INT32, F.LABEL_OPTIONAL),
            ("height", 2, F.TYPE_INT32, F.LABEL_OPTIONAL),
            ("width", 3, F.TYPE_INT32, F.LABEL_OPTIONAL),
            ("data", 4, F.TYPE_BYTES, F.LABEL_OPTIONAL),
            ("label", 5, F.TYPE_INT32, F.LABEL_OPTIONAL),
            ("float_data", 6, F.TYPE_FLOAT, F.LABEL_REPEATED)):
        f = msg.field.add()
        f.name, f.number, f.type, f.label = name, number, ftype, label
    pool.Add(fdp)
    cls = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("caffe_test.Datum"))
    m = cls()
    m.ParseFromString(payload)
    return m


def test_datum_codec_roundtrip_and_cross_validation():
    d = Datum(channels=3, height=4, width=5, data=bytes(range(60)),
              label=7, float_data=[1.5, -2.25])
    payload = d.SerializeToString()

    # our own parse
    d2 = Datum().ParseFromString(payload)
    assert (d2.channels, d2.height, d2.width, d2.label) == (3, 4, 5, 7)
    assert d2.data == bytes(range(60))
    assert d2.float_data == [1.5, -2.25]

    # the real protobuf runtime agrees both ways
    m = _proto_datum_roundtrip(payload)
    assert (m.channels, m.height, m.width, m.label) == (3, 4, 5, 7)
    assert m.data == bytes(range(60))
    assert list(m.float_data) == [1.5, -2.25]
    d3 = Datum().ParseFromString(m.SerializeToString())
    assert d3.data == d.data and d3.label == d.label


def test_blobproto_roundtrip():
    b = BlobProto()
    b.num, b.channels, b.height, b.width = 1, 3, 2, 2
    b.data = [0.5, 1.0, -1.0, 2.0]
    b2 = BlobProto().ParseFromString(b.SerializeToString())
    assert b2.data == b.data and b2.channels == 3


# -- native LMDB ------------------------------------------------------------

def test_lmdb_native_roundtrip_with_branches_and_overflow(tmp_path):
    items = [(b"k%04d" % i, bytes([i % 251]) * (40 + 113 * (i % 9)))
             for i in range(400)]
    items.append((b"zz_big", b"\xAB" * 30000))  # overflow chain
    path = write_lmdb(str(tmp_path / "db"), items)
    r = LMDBReader(path)
    assert r.entries == len(items)
    got = list(r.items())
    assert got == sorted(items)
    assert r.get(b"k0123") == dict(items)[b"k0123"]
    assert r.get(b"zz_big") == b"\xAB" * 30000
    assert r.get(b"missing") is None


# -- LMDBLoader on a Caffe-format fixture -----------------------------------

def _make_caffe_db(path, n, h=8, w=8, c=3, label_of=lambda i: i % 4,
                   seed=0):
    r = numpy.random.RandomState(seed)
    items = []
    images = []
    for i in range(n):
        img = r.randint(0, 256, (c, h, w), dtype=numpy.uint8)  # CHW
        d = Datum(channels=c, height=h, width=w,
                  data=img.tobytes(), label=label_of(i))
        items.append((b"%08d" % i, d.SerializeToString()))
        images.append(numpy.transpose(img, (1, 2, 0)))  # HWC truth
    write_lmdb(path, items)
    return images


def test_lmdb_loader_serves_caffe_datums(tmp_path):
    train_images = _make_caffe_db(str(tmp_path / "train"), 24)
    _make_caffe_db(str(tmp_path / "valid"), 8, seed=1)

    wf = DummyWorkflow()
    cls = UserLoaderRegistry.get_factory("lmdb")
    loader = cls(wf, train_path=str(tmp_path / "train"),
                 validation_path=str(tmp_path / "valid"),
                 db_shape=(8, 8, 3), minibatch_size=8)
    loader.initialize()
    assert loader.class_lengths == [0, 8, 24]
    assert loader.unique_labels_count == 4

    # serve one full epoch; check a train minibatch against the source
    seen = {TRAIN: 0, VALID: 0}
    for _ in range(100):
        loader.run()
        seen[loader.minibatch_class] += loader.minibatch_size
        if loader.minibatch_class == TRAIN:
            for i in range(loader.minibatch_size):
                gidx = int(loader.minibatch_indices.mem[i])
                start, _ = loader.class_index_range(TRAIN)
                img = train_images[gidx - start]
                assert numpy.array_equal(
                    loader.minibatch_data.mem[i], img)
                assert loader.minibatch_labels.mem[i] == \
                    (gidx - start) % 4
        if loader.epoch_ended:
            break
    assert seen == {TRAIN: 24, VALID: 8}
    # info+data reads of one key share the cached datum
    key = (TRAIN, b"%08d" % 0)
    loader.get_image_info(key)
    loader.get_image_data(key)
    assert loader.cache_hits > 0


def test_streaming_image_loader_applies_normalization(tmp_path):
    """Streaming loaders must normalize minibatches (regression: raw
    0..255 uint8 values saturate tanh nets)."""
    _make_caffe_db(str(tmp_path / "train"), 16)
    wf = DummyWorkflow()
    cls = UserLoaderRegistry.get_factory("lmdb")
    loader = cls(wf, train_path=str(tmp_path / "train"),
                 db_shape=(8, 8, 3), minibatch_size=8,
                 normalization_type="linear")
    loader.initialize()
    loader.run()
    mb = loader.minibatch_data.mem[:loader.minibatch_size]
    assert mb.min() >= -1.0 - 1e-6 and mb.max() <= 1.0 + 1e-6
    assert mb.min() < -0.5 and mb.max() > 0.5  # actually rescaled


# -- STL-10 fixture ---------------------------------------------------------

def _make_stl10(directory, n_train=10, n_valid=6):
    os.makedirs(directory, exist_ok=True)
    names = ["airplane", "bird", "car", "cat"]
    with open(os.path.join(directory, "class_names.txt"), "w") as f:
        f.write("\n".join(names))
    r = numpy.random.RandomState(7)
    sets = {}
    for prefix, n in (("train", n_train), ("test", n_valid)):
        x = r.randint(0, 256, (n, 3, 96, 96), dtype=numpy.uint8)
        y = (numpy.arange(n) % len(names) + 1).astype(numpy.uint8)
        x.tofile(os.path.join(directory, "%s_X.bin" % prefix))
        y.tofile(os.path.join(directory, "%s_y.bin" % prefix))
        sets[prefix] = (x, y)
    return sets, names


def test_stl10_loader(tmp_path):
    sets, names = _make_stl10(str(tmp_path))
    wf = DummyWorkflow()
    cls = UserLoaderRegistry.get_factory("full_batch_stl_10")
    loader = cls(wf, directory=str(tmp_path), minibatch_size=4)
    loader.initialize()
    assert loader.class_lengths == [0, 6, 10]
    assert loader.unique_labels_count == len(names)
    # full-batch decode matches the binary content (CHW -> HWC)
    x_valid, y_valid = sets["test"]
    start, _ = loader.class_index_range(VALID)
    got = loader.original_data.mem[start]
    want = numpy.transpose(x_valid[0], (1, 2, 0))
    assert numpy.array_equal(got, want)
    # label text -> deterministic int mapping
    assert loader.labels_mapping[names[0]] == 0


# -- ImageNet-base fixture --------------------------------------------------

def test_imagenet_loader_base(tmp_path):
    sy = sx = 16
    counts = {"test": 0, "val": 4, "train": 12}
    n = sum(counts.values())
    r = numpy.random.RandomState(3)
    samples = r.randint(0, 256, (n, sy, sx, 3), dtype=numpy.uint8)
    samples.tofile(str(tmp_path / "samples.dat"))
    labels = [("class_%d" % (i % 5), i % 5) for i in range(n)]
    with open(str(tmp_path / "labels.pickle"), "wb") as f:
        pickle.dump(labels, f)
    with open(str(tmp_path / "count.json"), "w") as f:
        json.dump(counts, f)
    mean = samples.mean(axis=0)
    rdisp = numpy.ones_like(mean, dtype=numpy.float32)
    with open(str(tmp_path / "matrixes.pickle"), "wb") as f:
        pickle.dump([mean, rdisp], f)

    wf = DummyWorkflow()
    cls = UserLoaderRegistry.get_factory("imagenet_loader_base")
    loader = cls(wf, sy=sy, sx=sx, minibatch_size=4,
                 samples_filename=str(tmp_path / "samples.dat"),
                 original_labels_filename=str(tmp_path / "labels.pickle"),
                 count_samples_filename=str(tmp_path / "count.json"),
                 matrixes_filename=str(tmp_path / "matrixes.pickle"))
    loader.initialize()
    assert loader.class_lengths == [0, 4, 12]
    assert loader.has_mean_file
    assert loader.mean.shape == (sy, sx, 3)

    loader.run()
    for i in range(loader.minibatch_size):
        gidx = int(loader.minibatch_indices.mem[i])
        assert numpy.array_equal(loader.minibatch_data.mem[i],
                                 samples[gidx])
        assert loader.minibatch_labels.mem[i] == gidx % 5


# -- file-list / auto-label image loaders -----------------------------------

def _write_png(path, arr):
    from PIL import Image
    os.makedirs(os.path.dirname(path), exist_ok=True)
    Image.fromarray(arr).save(path)


def test_auto_label_image_loader(tmp_path):
    r = numpy.random.RandomState(5)
    images = {}
    for label in ("cats", "dogs"):
        for i in range(3):
            arr = r.randint(0, 256, (10, 12, 3), dtype=numpy.uint8)
            p = str(tmp_path / "train" / label / ("%d.png" % i))
            _write_png(p, arr)
            images[p] = arr
    wf = DummyWorkflow()
    cls = UserLoaderRegistry.get_factory("auto_label_file_image")
    loader = cls(wf, train_paths=[str(tmp_path / "train")],
                 minibatch_size=3)
    loader.initialize()
    assert loader.class_lengths == [0, 0, 6]
    assert loader.unique_labels_count == 2
    loader.run()
    assert loader.minibatch_data.mem.shape == (3, 10, 12, 3)


def test_file_list_image_loader_with_scale(tmp_path):
    r = numpy.random.RandomState(6)
    lines = []
    for i in range(4):
        arr = r.randint(0, 256, (9, 9, 3), dtype=numpy.uint8)
        p = str(tmp_path / ("img%d.png" % i))
        _write_png(p, arr)
        lines.append("%s %d" % (p, i % 2))
    list_file = str(tmp_path / "train.txt")
    with open(list_file, "w") as f:
        f.write("\n".join(lines))
    wf = DummyWorkflow()
    cls = UserLoaderRegistry.get_factory("full_batch_file_list_image")
    loader = cls(wf, train_paths=list_file, scale=(6, 6),
                 minibatch_size=2)
    loader.initialize()
    assert loader.class_lengths == [0, 0, 4]
    assert loader.original_data.shape == (4, 6, 6, 3)
    assert sorted(set(loader.original_labels)) == [0, 1]


def test_pickles_image_loader(tmp_path):
    """PicklesImageFullBatchLoader: CIFAR-dict and raw-array pickles,
    CHW -> NHWC reshape, per-file labels for unlabeled pickles."""
    import pickle as _pickle
    from znicz_tpu.loader.pickles import PicklesImageFullBatchLoader

    r = numpy.random.RandomState(3)
    # CIFAR-style dict batch (flat rows + labels)
    train = {b"data": r.randint(0, 256, (20, 3 * 8 * 8), numpy.uint8),
             b"labels": list(numpy.arange(20) % 4)}
    p_train = tmp_path / "data_batch_1"
    with open(p_train, "wb") as f:
        _pickle.dump(train, f)
    # raw array batch, unlabeled -> gets a per-file label
    valid = r.randint(0, 256, (6, 3 * 8 * 8)).astype(numpy.uint8)
    p_valid = tmp_path / "valid_batch"
    with open(p_valid, "wb") as f:
        _pickle.dump(valid, f)

    ldr = PicklesImageFullBatchLoader(
        None, train_pickles=[str(p_train)],
        validation_pickles=[str(p_valid)],
        image_shape=(3, 8, 8), minibatch_size=5)
    ldr.initialize()
    assert ldr.class_lengths == [0, 6, 20]
    assert ldr.original_data.shape == (26, 8, 8, 3)
    # CHW->HWC round trip of the first validation image
    want = valid[0].reshape(3, 8, 8).transpose(1, 2, 0)
    assert numpy.array_equal(ldr.original_data.mem[0], want)
    ldr.run()
    assert int(ldr.minibatch_size) == 5


def test_interactive_loader_drives_forward_workflow():
    """InteractiveLoader feeds a forward-only workflow one queue at a
    time (reference AlexNet forward service pattern)."""
    from znicz_tpu.core.workflow import DummyWorkflow
    from znicz_tpu.loader.interactive import InteractiveLoader
    from znicz_tpu.units.all2all import All2AllTanh
    from znicz_tpu.core import prng

    w = DummyWorkflow()
    loader = InteractiveLoader(w, sample_shape=(4,), minibatch_size=2)
    loader.initialize()
    fwd = All2AllTanh(w, output_sample_shape=3,
                      weights_stddev=0.05, bias_stddev=0.05,
                      rand=prng.RandomGenerator().seed(5))
    fwd.input = loader.minibatch_data
    fwd.initialize()

    r = numpy.random.RandomState(0)
    for _ in range(3):
        loader.feed(r.uniform(-1, 1, 4))
    loader.finish()

    outs = []
    while not bool(loader.complete):
        loader.run()
        fwd.run()
        fwd.output.map_read()
        outs.append(numpy.array(
            fwd.output.mem[:int(loader.minibatch_size)]))
    got = numpy.concatenate(outs, axis=0)
    assert got.shape == (3, 3)
    assert bool(loader.epoch_ended)
    # empty queue without finish() is an error
    l2 = InteractiveLoader(None, sample_shape=(4,))
    l2.initialize()
    import pytest as _pytest
    with _pytest.raises(RuntimeError):
        l2.run()


def test_pickles_and_interactive_registered():
    import znicz_tpu.loader  # noqa: F401 (registration side effects)
    from znicz_tpu.loader.base import UserLoaderRegistry
    from znicz_tpu.loader.pickles import PicklesImageFullBatchLoader
    from znicz_tpu.loader.interactive import InteractiveLoader
    assert UserLoaderRegistry.get_factory(
        "full_batch_pickles_image") is PicklesImageFullBatchLoader
    assert UserLoaderRegistry.get_factory(
        "interactive") is InteractiveLoader
    assert UserLoaderRegistry.get_factory("minibatches")


def test_pickles_per_split_fallback_labels(tmp_path):
    """Unlabeled per-file labels restart per split so position means
    the same class in train and valid (review regression)."""
    import pickle as _pickle
    from znicz_tpu.loader.pickles import PicklesImageFullBatchLoader
    r = numpy.random.RandomState(1)

    def dump(name):
        p = tmp_path / name
        with open(p, "wb") as f:
            _pickle.dump(r.randint(0, 256, (4, 3 * 8 * 8)).astype(
                numpy.uint8), f)
        return str(p)

    ldr = PicklesImageFullBatchLoader(
        None, validation_pickles=[dump("cat_v"), dump("dog_v")],
        train_pickles=[dump("cat_t"), dump("dog_t")],
        image_shape=(3, 8, 8), minibatch_size=4)
    ldr.initialize()
    labels = list(ldr.original_labels)
    # [VALID cat=0 x4, dog=1 x4 | TRAIN cat=0 x4, dog=1 x4]
    assert labels == [0] * 4 + [1] * 4 + [0] * 4 + [1] * 4
