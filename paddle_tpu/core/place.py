"""Device places, TPU-native.

Reference parity: phi::Place / GPUPlace / CPUPlace (`paddle/phi/common/place.h`
[UNVERIFIED]).  Here a Place names a JAX device.  ``TPUPlace`` is the
first-class accelerator place; ``CUDAPlace`` is provided as a compatibility
alias so reference-era scripts run unchanged (it maps to the default
accelerator).
"""
from __future__ import annotations

import functools

import jax

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "CUDAPlace", "XPUPlace", "CustomPlace",
    "CUDAPinnedPlace", "set_device", "get_device", "get_all_devices",
    "current_place", "is_compiled_with_cuda", "is_compiled_with_tpu",
    "device_count",
]


@functools.lru_cache(maxsize=None)
def _backend_devices(backend=None):
    return tuple(jax.devices(backend) if backend else jax.devices())


def _accel_backend() -> str:
    """The default accelerator backend name ('tpu' here; 'cpu' in tests)."""
    return jax.default_backend()


class Place:
    """Base place: (device_type, device_id)."""

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- paddle API --
    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_gpu_place(self):
        # On this framework the accelerator is the TPU; scripts probing
        # for "gpu" get the accelerator answer.
        return self.device_type in ("tpu", "gpu")

    def is_tpu_place(self):
        return self.device_type == "tpu"

    def get_device_id(self):
        return self.device_id

    def jax_device(self):
        """The jax device behind this place.  An accelerator place
        resolves on the default backend — on the CPU backend that is
        the host's (virtual) devices, which is what lets scripts
        written for a chip run in the CPU test suite.  An index past
        the device count is an error, never another device."""
        devs = _backend_devices(None)
        if self.device_type == "cpu" and jax.default_backend() != "cpu":
            devs = _backend_devices("cpu")
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self}: no such device, the {devs[0].platform} backend "
                f"has {len(devs)}")
        return devs[self.device_id]

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class XPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class CUDAPlace(Place):
    """Compat alias: maps onto the accelerator (TPU)."""

    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class CUDAPinnedPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class CustomPlace(Place):
    def __init__(self, device_type: str = "tpu", device_id: int = 0):
        super().__init__(device_type, device_id)


_current_place: Place | None = None


def _default_place() -> Place:
    if jax.default_backend() == "cpu":
        return CPUPlace()
    return TPUPlace(0)


def current_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = _default_place()
    return _current_place


def set_device(device) -> Place:
    """paddle.set_device('tpu') / 'tpu:1' / 'cpu' / 'gpu' (alias)."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return _current_place
    name = str(device)
    if ":" in name:
        kind, idx = name.split(":", 1)
        idx = int(idx)
    else:
        kind, idx = name, 0
    kind = kind.lower()
    if kind == "cpu":
        _current_place = CPUPlace()
    elif kind in ("tpu", "gpu", "cuda", "xpu", "npu"):
        _current_place = TPUPlace(idx)
    else:
        _current_place = CustomPlace(kind, idx)
    return _current_place


def get_device() -> str:
    p = current_place()
    if p.is_cpu_place():
        return "cpu"
    return f"{p.device_type}:{p.device_id}"


def get_all_devices():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def device_count() -> int:
    return len(jax.devices())


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False
