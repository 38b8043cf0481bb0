"""Seeded synthetic labelled images, and the loader that serves them.

The data set is made once per run from ``--seed``: windows into a pool of
random bytes scaled to [-1, 1] float32, so every row differs, and labels
that cover every class.  Layout on the sample axis is the loader contract's
[TEST | VALID | TRAIN].  The harness keeps the same arrays for the
reference, which therefore gathers its rows from data the benchmark made.
"""

import numpy

#: sub-stream tags under the run's seed
TAG_IMAGES, TAG_LABELS, TAG_WEIGHTS, TAG_SHUFFLE, TAG_DROPOUT = range(5)


def sub_seed(seed, tag):
    """A 31-bit seed for one sub-stream of the run (``--seed`` itself may
    pass 2**31, which a signed 32-bit PRNG key cannot hold)."""
    return int(numpy.random.SeedSequence(
        [int(seed), int(tag)]).generate_state(1)[0] & 0x7FFFFFFF)


#: floats in the noise pool that images are cut from
POOL = 1 << 25


def make_images(seed, n, sample_shape, n_classes):
    """(data float32 (n, *sample_shape) in [-1, 1], labels int32 (n,)).

    Drawing 1.3e9 values a run would be most of a minute of set-up, so the
    rows are windows into one pool of seeded noise, each at an offset of
    its own: every row differs, and making them is one memcpy a row."""
    if n < n_classes:
        raise ValueError("need at least one image per class (%d < %d)"
                         % (n, n_classes))
    d = int(numpy.prod(sample_shape))
    rng = numpy.random.Generator(
        numpy.random.PCG64(sub_seed(seed, TAG_IMAGES)))
    size = max(POOL, 4 * d)
    pool = numpy.frombuffer(rng.bytes(size), dtype=numpy.uint8).astype(
        numpy.float32)
    pool -= 127.5
    pool *= 1.0 / 127.5
    span = size - d
    if n > span:
        raise ValueError("pool too small for %d distinct rows" % n)
    # a stride coprime to the span visits every offset once
    stride = 1000003
    while numpy.gcd(stride, span) != 1:
        stride += 2
    offsets = (numpy.arange(n, dtype=numpy.int64) * stride) % span
    data = numpy.empty((n, d), numpy.float32)
    for i, off in enumerate(offsets):
        data[i] = pool[off:off + d]
    lrng = numpy.random.Generator(
        numpy.random.PCG64(sub_seed(seed, TAG_LABELS)))
    labels = lrng.permutation(numpy.arange(n) % n_classes).astype(numpy.int32)
    return data.reshape((n,) + tuple(sample_shape)), labels


def register_loader():
    """Define the loader class (it needs the program's base classes, so
    this is called only where the program is imported anyway)."""
    from znicz_tpu.loader.base import (FullBatchLoader, IFullBatchLoader,
                                       TEST, VALID, TRAIN)

    class BenchSeededImages(FullBatchLoader, IFullBatchLoader):
        """Stock full-batch loader (no ``fill_minibatch`` override, so the
        fused trainer's device-resident path still engages) over arrays
        the benchmark hands in through ``loader_config``."""

        MAPPING = "bench_seeded_images"

        def __init__(self, workflow, **kwargs):
            kwargs.setdefault("normalization_type", "none")
            super(BenchSeededImages, self).__init__(workflow, **kwargs)
            self._bench_data = kwargs["bench_data"]
            self._bench_labels = kwargs["bench_labels"]
            self._n_valid = int(kwargs["n_valid"])

        def load_data(self):
            self.original_data.reset(self._bench_data)
            del self._original_labels[:]
            self._original_labels.extend(self._bench_labels.tolist())
            self.class_lengths[TEST] = 0
            self.class_lengths[VALID] = self._n_valid
            self.class_lengths[TRAIN] = len(self._bench_data) - self._n_valid

    return BenchSeededImages
