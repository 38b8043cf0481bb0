"""Device backends.

TPU-era equivalent of ``veles.backends`` (SURVEY.md layer L0).  The reference
dispatches NumpyDevice / OpenCL / CUDA; znicz_tpu dispatches NumpyDevice /
JaxDevice.  A JaxDevice wraps whatever jax platform is live (TPU on real
hardware, CPU in tests) — XLA JIT specialization replaces the reference's
per-shape ``#define`` kernel builds (conv.py:185-213).

Nothing here falls back: a jax backend that cannot start raises out of
:func:`get_device`, and the entry points log :func:`describe` once so a
run always says which platform it is on.
"""


from znicz_tpu.core.config import root


class Device(object):
    backend_name = "abstract"

    def sync(self):
        pass

    @property
    def exists(self):
        return True

    def __repr__(self):
        return "<%s>" % type(self).__name__


class NumpyDevice(Device):
    """Pure-numpy reference backend — the executable spec
    (reference test pattern: tests/unit/test_all2all.py:95-152)."""

    backend_name = "numpy"


class JaxDevice(Device):
    """XLA-backed device (TPU on hardware, CPU host platform in tests)."""

    backend_name = "jax"

    def __init__(self, platform=None):
        import jax
        self._jax = jax
        devices = jax.devices(platform) if platform else jax.devices()
        self.jax_device = devices[0]
        self.platform = self.jax_device.platform

    def sync(self):
        # Block until all dispatched work completes.
        import jax
        jax.effects_barrier()

    def __repr__(self):
        return "<JaxDevice %s>" % (self.jax_device,)


def describe():
    """``platform=... device_kind=... devices=N`` of the live jax
    backend — the line the training launcher and ``serve`` log once at
    start.  Initializes the backend (and raises if it cannot start)."""
    import jax
    devices = jax.devices()
    return "platform=%s device_kind=%s devices=%d" % (
        devices[0].platform, devices[0].device_kind, len(devices))


_default_device = None


def get_device(backend=None):
    """Resolve the process-default device per config
    (root.common.engine.backend: numpy | jax | auto)."""
    global _default_device
    backend = backend or root.common.engine.backend
    if backend == "numpy":
        return NumpyDevice()
    if backend == "jax":
        return JaxDevice()
    # auto: the jax backend or an error — a TPU that fails to start must
    # not turn into a numpy run that exits 0
    if _default_device is None:
        _default_device = JaxDevice()
    return _default_device
