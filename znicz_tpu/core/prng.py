"""Seedable PRNG streams.

TPU-era equivalent of ``veles.prng`` (SURVEY.md §2.9).  The reference keeps a
registry of named streams (``prng.get(key)``), seeded from 1024-int32 seed
files by the functional-test harness (tests/functional/standard_test.py:67-73)
and used for weight init (``rand.fill`` / ``rand.fill_normal_real``,
all2all.py:119-127), loader shuffling, and dropout mask generation
(dropout.py:110,149).

TPU-first addition: every stream can also mint ``jax.random`` keys
(:meth:`RandomGenerator.jax_key`) so device-side randomness (dropout,
stochastic pooling) is reproducible from the same seed, replacing the
reference's device-side xorshift state arrays (dropout.py:112-117).

The fillers draw by ``standard_normal`` / ``random_sample`` in chunks and
scale in float64 as the legacy ``normal`` / ``uniform`` do, so they give
those calls' bits while letting other threads run (the legacy calls hold
the GIL for the whole draw).  A stream can be lent to one thread's draw
(:meth:`RandomGenerator.lend`): every other thread's use waits for it.
"""

import threading

import numpy

#: values a filler draws at a time: one float64 chunk of this many values
#: is all the scratch a fill takes, whatever the array's size
CHUNK = 1 << 22


def _fill_chunks(out, draw, scale, shift, clip=None):
    """``out[...] = shift + scale * draw(n)``, cast to ``out``'s type, a
    chunk of :data:`CHUNK` values at a time (``clip``: (low, high) of the
    float64 values before the cast)."""
    whole = out if out.flags.c_contiguous else numpy.empty(out.shape,
                                                           out.dtype)
    flat = whole.reshape(-1)
    for start in range(0, flat.size, CHUNK):
        vals = draw(min(CHUNK, flat.size - start))
        vals *= scale
        vals += shift
        if clip is not None:
            numpy.clip(vals, clip[0], clip[1], out=vals)
        flat[start:start + vals.size] = vals
    if whole is not out:
        out[...] = whole


def normal_into(state, out, mean=0.0, stddev=1.0, clip_to_sigma=None):
    """Fill ``out`` in place with what ``state.normal(mean, stddev,
    out.shape)`` draws, cast to its type, bit for bit: the legacy
    ``normal`` is ``loc + scale * gauss`` over the stream that
    ``standard_normal`` reads, and a chunked draw continues that stream as
    one whole draw would.  ``state`` is a ``numpy.random.RandomState``."""
    clip = None if clip_to_sigma is None else (
        mean - clip_to_sigma * stddev, mean + clip_to_sigma * stddev)
    _fill_chunks(out, state.standard_normal, stddev, mean, clip)


def uniform_into(state, out, low=-1.0, high=1.0):
    """Fill ``out`` in place with what ``state.uniform(low, high,
    out.shape)`` draws, cast to its type, bit for bit (the legacy
    ``uniform`` is ``low + (high - low) * random_sample``)."""
    low, high = float(low), float(high)
    _fill_chunks(out, state.random_sample, high - low, low)


class RandomGenerator(object):
    """One seedable random stream wrapping ``numpy.random.RandomState``."""

    #: (thread, its draw's end) while the stream is lent (:meth:`lend`)
    _lent = None

    def __init__(self, key=None):
        self.key = key
        self._state = numpy.random.RandomState()
        self._seed_arr = None
        self._key_counter = 0
        self.seed(numpy.frombuffer(b"znicz-tpu-default-seed-0123456789ab",
                                   dtype=numpy.uint8))

    # -- seeding ------------------------------------------------------------
    def seed(self, seed, dtype=None, count=None):
        """Seed from an int, an array, or a file path of raw ``dtype`` values.

        Mirrors the reference harness contract
        (tests/functional/standard_test.py:67-73): seed files are raw binary,
        read as ``count`` items of ``dtype``.
        """
        self._settle()
        if isinstance(seed, str):
            seed = numpy.fromfile(seed, dtype=dtype or numpy.int32,
                                  count=count or 1024)
        if isinstance(seed, (int, numpy.integer)):
            arr = numpy.asarray([seed], dtype=numpy.uint32)
        else:
            raw = numpy.ascontiguousarray(seed).tobytes()
            raw += b"\x00" * (-len(raw) % 4)
            arr = numpy.frombuffer(raw, dtype=numpy.uint32).copy()
        self._seed_arr = arr
        self._state.seed(arr)
        self._key_counter = 0
        return self

    @property
    def state(self):
        self._settle()
        return self._state

    # -- one thread's draw --------------------------------------------------
    def lend(self, thread):
        """Lend the stream to ``thread`` (not started yet) for a draw:
        until the function returned is called, any other thread that
        draws, seeds or reads the state waits, so that every draw after
        the lent one is what it would be had the lent one run first, on
        the caller's thread.  A stream lent already is lent on once that
        draw is over."""
        self._settle()
        done = threading.Event()
        self._lent = (thread, done)

        def release():
            self._lent = None
            done.set()
        return release

    def _settle(self):
        lent = self._lent
        if lent is not None and lent[0] is not threading.current_thread():
            lent[1].wait()

    # -- in-place fillers (reference: all2all.py:119-127) -------------------
    def fill(self, arr, vle_min=-1.0, vle_max=1.0):
        """Uniform fill of a numpy array in place."""
        self._settle()
        uniform_into(self._state, arr, vle_min, vle_max)

    def fill_normal_real(self, arr, mean=0.0, stddev=1.0, clip_to_sigma=None):
        self._settle()
        normal_into(self._state, arr, mean, stddev, clip_to_sigma)

    # -- draws --------------------------------------------------------------
    def normal(self, loc=0.0, scale=1.0, size=None):
        self._settle()
        return self._state.normal(loc, scale, size)

    def uniform(self, low=0.0, high=1.0, size=None):
        self._settle()
        return self._state.uniform(low, high, size)

    def randint(self, low, high=None, size=None, dtype=int):
        self._settle()
        return self._state.randint(low, high, size).astype(dtype)

    def rand(self, *shape):
        self._settle()
        return self._state.rand(*shape)

    def shuffle(self, arr):
        self._settle()
        self._state.shuffle(arr)

    def permutation(self, n):
        self._settle()
        return self._state.permutation(n)

    def choice(self, a, size=None, replace=True, p=None):
        self._settle()
        return self._state.choice(a, size, replace, p)

    # -- state capture (checkpoint/resume exactness) ------------------------
    def get_state(self):
        """Opaque resumable state (numpy RandomState + key counter)."""
        self._settle()
        return {"np": self._state.get_state(),
                "seed_arr": None if self._seed_arr is None
                else numpy.array(self._seed_arr),
                "key_counter": self._key_counter}

    def set_state(self, state):
        self._settle()
        self._state.set_state(state["np"])
        self._seed_arr = state["seed_arr"]
        self._key_counter = state["key_counter"]
        return self

    # -- TPU-first: deterministic jax.random keys ---------------------------
    def jax_key(self):
        """Mint the next ``jax.random`` key in this stream.

        Deterministic given the seed: key #n after seeding is always the
        same.  This is how device-side randomness (dropout masks, stochastic
        pooling) stays reproducible under jit.
        """
        import jax
        base = int(self._seed_arr.view(numpy.uint32)[:2].sum()) & 0x7FFFFFFF
        self._key_counter += 1
        return jax.random.fold_in(
            jax.random.PRNGKey(base), self._key_counter)


# -- stream registry (reference: veles.prng.get) ---------------------------
_streams = {}


def get(key=1):
    """Return the process-global stream with the given key (default 1).

    The reference seeds two streams (keys 1 and 2) in functional tests.
    """
    rg = _streams.get(key)
    if rg is None:
        rg = _streams[key] = RandomGenerator(key)
    return rg


def states():
    """Capture every registered stream's state (snapshot payload)."""
    return {key: rg.get_state() for key, rg in _streams.items()}


def restore(state_map):
    """Restore stream states captured by :func:`states` (resume)."""
    for key, st in state_map.items():
        get(key).set_state(st)
