"""Mirrored host/device tensor buffers.

TPU-era equivalent of ``veles.memory.Array`` (SURVEY.md layer L1).  The
reference's central invariant — crossing the host/device boundary is explicit
and lazy via ``map_read/map_write/map_invalidate/unmap`` — is kept, but the
device side is an immutable ``jax.Array``: device "writes" replace the buffer
(:meth:`Array.set_dev`), which is exactly how XLA wants it.  Chains of units
pass device buffers to each other without host round-trips; ``.mem`` pulls to
host on demand.

States:
  HOST  — host numpy copy is authoritative (device stale/absent)
  DEV   — device jax.Array is authoritative (host stale/absent)
  SYNC  — both valid

A host write may also be put off (:meth:`Array.defer`): the ``DEV`` state
pulls from the device on the first host read, a deferred write is made on
the first read of either side — and never, if nothing reads the buffer
before it is overwritten.
"""

import numpy

from znicz_tpu.core import profiler
from znicz_tpu.core import telemetry

HOST, DEV, SYNC = "host", "dev", "sync"


def roundup(n, m):
    """Round ``n`` up to a multiple of ``m``
    (reference: veles.memory.roundup)."""
    r = n % m
    return n if r == 0 else n + m - r


class Array(object):
    """A tensor mirrored between host numpy and device jax.Array."""

    __slots__ = ("_host", "_dev", "_state", "name", "_dev_nbytes",
                 "_deferred")

    def __init__(self, data=None, name=None):
        self._host = None
        self._dev = None
        self._state = HOST
        self.name = name
        #: device bytes this Array has accounted in the profiler's
        #: memory ledger (stays 0 while the profiler is disabled)
        self._dev_nbytes = 0
        #: a host write put off until the first read (:meth:`defer`)
        self._deferred = None
        if data is not None:
            self.mem = data

    def _ledger_swap(self, new_dev):
        """Device-memory ledger hook — called ONLY when the profiler is
        enabled, at the three points ``_dev`` changes (upload, set_dev,
        reset)."""
        nbytes = int(getattr(new_dev, "nbytes", 0) or 0) \
            if new_dev is not None else 0
        profiler.ledger_swap(self.name, self._dev_nbytes, nbytes)
        self._dev_nbytes = nbytes

    # -- deferred host write ------------------------------------------------
    def defer(self, write):
        """Put a host write off: ``write()`` runs once, before the first
        read of the contents from either side (``mem``, ``dev``,
        ``map_read``/``map_write``, the views), and fills the host buffer
        through the usual ``map_invalidate`` + ``mem``.  A later
        ``defer``, ``reset``, ``mem =``, ``set_dev`` or
        ``map_invalidate`` replaces the contents wholesale and drops a
        write still pending.  The writer owns what ``write`` captures (a
        live buffer must be copied when the write is put off)."""
        self._deferred = write

    @property
    def pending(self):
        """Whether a deferred host write has not been made yet — nobody
        has read the contents since :meth:`defer`."""
        return self._deferred is not None

    def _settle(self):
        write, self._deferred = self._deferred, None
        write()

    # -- allocation / reset -------------------------------------------------
    def reset(self, arr=None):
        """Drop current contents; optionally adopt a new host array.

        Reference: ``Array.reset`` (used by unit initialize to realloc).
        """
        if self._dev is not None and profiler.enabled():
            self._ledger_swap(None)
        self._host = None if arr is None else numpy.asarray(arr)
        self._dev = None
        self._state = HOST
        self._deferred = None
        return self

    @property
    def mem(self):
        """Host numpy view (syncs from device if the device copy is newer)."""
        if self._deferred is not None:
            self._settle()
        if self._state == DEV:
            self._host = numpy.asarray(self._dev)
            self._state = SYNC
            if telemetry.enabled():
                telemetry.add_bytes("d2h", self._host.nbytes)
        return self._host

    @mem.setter
    def mem(self, value):
        if value is None:
            self.reset()
            return
        self._host = value if isinstance(value, numpy.ndarray) \
            else numpy.asarray(value)
        self._state = HOST
        self._deferred = None

    # -- explicit mapping (reference contract, nn_units.py:51) --------------
    def map_read(self):
        if self._deferred is not None:
            self._settle()
        if self._state == DEV:
            self._host = numpy.asarray(self._dev)
            self._state = SYNC
            if telemetry.enabled():
                telemetry.add_bytes("d2h", self._host.nbytes)
        return self

    def map_write(self):
        self.map_read()
        if self._host is not None and not self._host.flags.writeable:
            self._host = numpy.array(self._host)  # jax gives read-only views
        self._state = HOST
        return self

    def map_invalidate(self):
        """Host will be overwritten wholesale; skip device download."""
        self._deferred = None
        if self._host is None and self._dev is not None:
            self._host = numpy.empty(self._dev.shape,
                                     dtype=numpy.dtype(str(self._dev.dtype)))
        elif self._host is not None and not self._host.flags.writeable:
            self._host = numpy.empty_like(self._host)
        self._state = HOST
        return self

    def unmap(self):
        """Hand ownership to the device (uploads if host was dirty)."""
        self.dev
        return self

    # -- device side --------------------------------------------------------
    @property
    def dev(self):
        """Device jax.Array (uploads host if the host copy is newer).

        On the CPU backend the upload hands the device a PRIVATE copy:
        ``jax.device_put`` of a numpy array is zero-copy there (the
        jax.Array aliases the host buffer), so an in-place host write —
        e.g. the loader refilling ``minibatch_data`` for the next
        minibatch — would otherwise race with still-pending async
        computations that read this value.  The copy is what makes the
        reference's map/unmap ownership contract actually hold under
        jax's async dispatch.  Accelerator backends DMA a copy into
        device memory anyway, so no extra host copy is paid there.
        """
        import jax
        if self._deferred is not None:
            self._settle()
        if self._state == HOST:
            if self._host is None:
                return None
            host = self._host
            if jax.default_backend() == "cpu":
                host = numpy.array(host)
            self._dev = jax.device_put(host)
            self._state = SYNC
            if telemetry.enabled():
                telemetry.add_bytes("h2d", host.nbytes)
            if profiler.enabled():
                self._ledger_swap(self._dev)
        return self._dev

    def set_dev(self, arr):
        """Adopt a new device array as authoritative (a device 'write')."""
        if profiler.enabled():
            self._ledger_swap(arr)
        self._dev = arr
        self._state = DEV
        self._deferred = None
        return self

    @property
    def devmem(self):  # reference-compatible alias
        return self.dev

    # -- shape & views ------------------------------------------------------
    def __bool__(self):
        return self._host is not None or self._dev is not None

    __nonzero__ = __bool__

    @property
    def shape(self):
        if self._state == DEV and self._dev is not None:
            return tuple(self._dev.shape)
        return self._host.shape if self._host is not None else \
            (tuple(self._dev.shape) if self._dev is not None else None)

    @shape.setter
    def shape(self, value):
        self.mem = self.mem.reshape(value)

    @property
    def size(self):
        s = self.shape
        return 0 if s is None else int(numpy.prod(s)) if s else 1

    @property
    def sample_size(self):
        """Elements per sample = size / shape[0] (reference semantics)."""
        s = self.shape
        return 0 if not s else self.size // s[0]

    @property
    def dtype(self):
        if self._host is not None:
            return self._host.dtype
        if self._dev is not None:
            return numpy.dtype(str(self._dev.dtype))
        return None

    @property
    def matrix(self):
        """2D (n_samples, sample_size) host view."""
        m = self.mem
        return m.reshape(m.shape[0], -1)

    @property
    def plain(self):
        """Flat host view."""
        return self.mem.reshape(-1)

    def __len__(self):
        s = self.shape
        return s[0] if s else 0

    def __getitem__(self, idx):
        return self.mem[idx]

    def __setitem__(self, idx, value):
        self.map_write()
        self.mem[idx] = value

    def __repr__(self):
        return "<Array %s %s %s state=%s>" % (
            self.name or "", self.shape, self.dtype, self._state)


def reshape(arr, shape):
    """Reshape an Array's host view (reference: veles.memory.reshape)."""
    arr.mem = arr.mem.reshape(shape)
    return arr.mem


def reshape_transposed(arr):
    m = arr.mem
    return m.reshape(m.shape[::-1])


def ravel(arr):
    return arr.mem.reshape(-1)


def interleave(arr):
    """CHW → HWC style interleave helper used by image tooling."""
    if arr.ndim == 3:
        return numpy.transpose(arr, (1, 2, 0))
    if arr.ndim == 4:
        return numpy.transpose(arr, (0, 2, 3, 1))
    raise ValueError("interleave expects 3D/4D")


class NumDiff(object):
    """Five-point numeric differentiation helper.

    Reference: ``veles.memory.NumDiff`` used by the gradient numdiff harness
    (tests/unit/gd_numdiff.py:74-78) — valid in float64 only.
    """

    #: Perturbation offsets in units of h.
    points = (2.0, 1.0, -1.0, -2.0)
    #: Five-point stencil coefficients / (12 h).
    coeffs = numpy.array([-1.0, 8.0, -8.0, 1.0], dtype=numpy.float64)
    divizor = 12.0
    h = 1.0e-4  # matches NumDiff usage scale in the reference tests

    def __init__(self):
        self.errs = numpy.zeros(len(NumDiff.points), dtype=numpy.float64)

    @property
    def derivative(self):
        return (self.errs * NumDiff.coeffs).sum() / (
            NumDiff.divizor * NumDiff.h)
