"""Device/place abstraction.

Analog of the reference's Place variant + DeviceContextPool
(reference: paddle/fluid/platform/place.h:26-128,
device_context.h:107). On TPU there are no user-managed streams or
handles — XLA owns scheduling — so a Place is just a (backend, index)
identity used to pick a ``jax.Device``. ``TPUPlace`` is the north-star
first-class device.
"""
import jax

from . import errors


#: platforms that count as "TPU"
TPU_PLATFORMS = ("tpu",)


class Place:
    _kind = "unknown"

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def get_device_id(self):
        return self.device_id

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((self._kind, self.device_id))

    def __repr__(self):
        return f"Place({self._kind}:{self.device_id})"

    def jax_device(self):
        """Resolve to a live jax.Device. Multi-process (jax.distributed)
        runs must resolve to an ADDRESSABLE device: jax.devices() lists
        every process's devices and only the local ones accept puts
        (the reference's Place is likewise process-local)."""
        plat = self._platform()
        plats = (plat,) if plat != "tpu" else TPU_PLATFORMS
        devs = [d for d in jax.local_devices() if d.platform in plats]
        if not devs and plat == "cpu":
            # a CPUPlace under an accelerator default backend: the host
            # devices live in their own backend (addressable ones only)
            devs = jax.local_devices(backend="cpu")
        # an accelerator place that finds no such device is an error: a
        # CPU device handed back here would hide the missing chip
        errors.enforce(
            devs, f"{self!r}: no {plat!r} device is visible to this "
            f"process (platforms: "
            f"{sorted({d.platform for d in jax.local_devices()})})",
            errors.UnavailableError)
        errors.enforce(
            self.device_id < len(devs),
            f"{self!r}: device index out of range ({len(devs)} present)",
            errors.OutOfRangeError,
        )
        return devs[self.device_id]

    def _platform(self):
        return self._kind


class CPUPlace(Place):
    _kind = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    """First-class TPU device id (the reference's CUDAPlace analog)."""

    _kind = "tpu"


class CUDAPlace(Place):
    """Compat alias: maps to whatever accelerator jax exposes ('gpu' or TPU)."""

    _kind = "gpu"

    def _platform(self):
        plats = {d.platform for d in jax.devices()}
        if "gpu" in plats:
            return "gpu"
        if plats & set(TPU_PLATFORMS):
            return "tpu"
        return "cpu"


class CUDAPinnedPlace(CPUPlace):
    pass


class XPUPlace(TPUPlace):
    def __init__(self, dev_id=0):
        super().__init__(dev_id)


class NPUPlace(TPUPlace):
    pass


_CURRENT_DEVICE = None  # lazy: None = best available


def _best_place():
    plats = {d.platform for d in jax.devices()}
    if plats & set(TPU_PLATFORMS):
        return TPUPlace(0)
    if "gpu" in plats:
        return CUDAPlace(0)
    return CPUPlace()


def set_device(device):
    """paddle.set_device('tpu') / 'tpu:0' / 'cpu' / 'gpu:1'.

    Reference: python/paddle/device.py:168 set_device.
    """
    global _CURRENT_DEVICE
    if isinstance(device, Place):
        _CURRENT_DEVICE = device
        return device
    dev = device.lower()
    idx = 0
    if ":" in dev:
        dev, idx_s = dev.split(":")
        idx = int(idx_s)
    if dev == "cpu":
        _CURRENT_DEVICE = CPUPlace()
    elif dev in ("tpu", "xpu", "npu"):
        _CURRENT_DEVICE = TPUPlace(idx)
    elif dev in ("gpu", "cuda"):
        _CURRENT_DEVICE = CUDAPlace(idx)
    else:
        raise errors.InvalidArgumentError(f"unknown device {device!r}")
    return _CURRENT_DEVICE


def get_device():
    p = current_place()
    return f"{p._kind}:{p.device_id}" if not isinstance(p, CPUPlace) else "cpu"


def current_place():
    global _CURRENT_DEVICE
    if _CURRENT_DEVICE is None:
        _CURRENT_DEVICE = _best_place()
    return _CURRENT_DEVICE


def current_jax_device():
    return current_place().jax_device()


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_tpu():
    return True


def is_tpu_available():
    return bool({d.platform for d in jax.devices()} & set(TPU_PLATFORMS))


def device_count():
    plat = current_place()._platform()
    plats = (plat,) if plat != "tpu" else TPU_PLATFORMS
    n = len([d for d in jax.devices() if d.platform in plats])
    return n or len(jax.devices())
