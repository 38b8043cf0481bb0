"""Seeds of a run's sub-streams, and seeded float rows made in bulk.

Everything a run makes comes from ``--seed``: each stream (rows, labels or
targets, weights, shuffle, dropout) has a sub-seed of its own.  What the rows
*are* (labelled images, regression pairs, token sequences) and the loader
that serves them belong to the configuration's family
(``benchmarks/families/``); this module holds what they share.
"""

import numpy

#: sub-stream tags under the run's seed
TAG_IMAGES, TAG_LABELS, TAG_WEIGHTS, TAG_SHUFFLE, TAG_DROPOUT = range(5)


def sub_seed(seed, tag):
    """A 31-bit seed for one sub-stream of the run (``--seed`` itself may
    pass 2**31, which a signed 32-bit PRNG key cannot hold)."""
    return int(numpy.random.SeedSequence(
        [int(seed), int(tag)]).generate_state(1)[0] & 0x7FFFFFFF)


#: floats in the noise pool that rows are cut from
POOL = 1 << 25


def seeded_rows(seed, n, sample_shape, tag=TAG_IMAGES):
    """float32 (n, *sample_shape) in [-1, 1] from the sub-stream ``tag``.

    Drawing 1.3e9 values a run would be most of a minute of set-up, so the
    rows are windows into one pool of seeded noise, each at an offset of
    its own: every row differs, and making them is one memcpy a row."""
    d = int(numpy.prod(sample_shape))
    rng = numpy.random.Generator(numpy.random.PCG64(sub_seed(seed, tag)))
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
    return data.reshape((n,) + tuple(sample_shape))
