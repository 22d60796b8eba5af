"""Device / place management.

TPU-native equivalent of the reference's Place + DeviceContext machinery
(``paddle/phi/common/place.h:27``, ``paddle/phi/core/device_context.h:34``,
``python/paddle/device/__init__.py:294`` ``set_device``). PJRT (through JAX) owns
the actual device runtime, streams and the HBM allocator, so a Place here is a
thin handle onto a ``jax.Device`` plus helpers for host<->device transfer and
memory stats (ref ``paddle/fluid/memory/stats.h:112``).
"""

from __future__ import annotations

import threading
from typing import Optional

import jax

_state = threading.local()


class Place:
    """A device handle. ``Place('tpu', 0)``, ``Place('cpu')``.

    Mirrors ``phi::Place`` (``paddle/phi/common/place.h:27``) — equality is
    (device_type, device_id).
    """

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    @property
    def jax_device(self) -> jax.Device:
        devs = _devices_of_type(self.device_type)
        if not devs:
            raise RuntimeError(f"no {self.device_type!r} devices visible to JAX")
        return devs[self.device_id % len(devs)]

    def __eq__(self, other):
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"


def _devices_of_type(device_type: str):
    try:
        return jax.devices(device_type)
    except RuntimeError:
        return []


def _default_place() -> Place:
    """The platform JAX itself selected (``JAX_PLATFORMS`` or its own
    probe order).  No detection ladder here: a process that was pointed
    at the chip and cannot initialise it gets JAX's error, never a quiet
    CPU place — the CPU is reached by asking for it
    (``JAX_PLATFORMS=cpu`` or ``set_device("cpu")``)."""
    return Place(jax.default_backend(), 0)


def set_device(device: str) -> Place:
    """paddle.device.set_device equivalent (``device/__init__.py:294``).

    Accepts 'tpu', 'tpu:1', 'cpu', ...
    """
    if ":" in device:
        dev_type, idx = device.split(":", 1)
        place = Place(dev_type, int(idx))
    else:
        place = Place(device, 0)
    place.jax_device  # validate eagerly
    _state.place = place
    return place


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.device_id}"


def current_place() -> Place:
    place = getattr(_state, "place", None)
    if place is None:
        place = _default_place()
        _state.place = place
    return place


def is_compiled_with_tpu() -> bool:
    return bool(_devices_of_type("tpu"))


def device_count(device_type: Optional[str] = None) -> int:
    if device_type is None:
        device_type = current_place().device_type
    return len(_devices_of_type(device_type))


def synchronize(place: Optional[Place] = None) -> None:
    """Block until all outstanding work on the device is complete.

    Equivalent of ``paddle.device.cuda.synchronize`` — on PJRT we issue a tiny
    computation and block on it, which orders after previously enqueued work.
    """
    import jax.numpy as jnp

    dev = (place or current_place()).jax_device
    jax.device_put(jnp.zeros((), jnp.int32), dev).block_until_ready()


def memory_stats(place: Optional[Place] = None) -> dict:
    """Device memory statistics (ref ``memory/stats.h:112`` DEVICE_MEMORY_STAT_*).

    Backed by PJRT's per-device memory_stats when the platform reports them.
    """
    dev = (place or current_place()).jax_device
    try:
        stats = dev.memory_stats() or {}
    except Exception:  # platform without stats (CPU)
        stats = {}
    return {
        "allocated.current": stats.get("bytes_in_use", 0),
        "allocated.peak": stats.get("peak_bytes_in_use", 0),
        "reserved.total": stats.get("bytes_limit", 0),
        "num_allocs": stats.get("num_allocs", 0),
    }


def max_memory_allocated(place: Optional[Place] = None) -> int:
    return memory_stats(place)["allocated.peak"]


def memory_allocated(place: Optional[Place] = None) -> int:
    return memory_stats(place)["allocated.current"]


# -- capability probes + vendor Places (ref python/paddle/device/__init__.py)
# On this framework every accelerator place is the TPU chip; the CUDA/ROCm/
# NPU/MLU/XPU/IPU probes answer False so device-branching user code takes
# its generic path.
def get_cudnn_version():
    return None


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_mlu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    # XLA plays CINN's role (compiler backend) but the flag answers the
    # reference's question "is the CINN backend present" -> False
    return False


def XPUPlace(dev_id=0):
    return Place("tpu", dev_id)


def IPUPlace(dev_id=0):
    return Place("tpu", dev_id)


def MLUPlace(dev_id=0):
    return Place("tpu", dev_id)


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()} | {"cpu"})


def get_all_custom_device_type():
    return [t for t in get_all_device_type() if t not in ("cpu", "gpu", "tpu")]


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [d for d in get_available_device()
            if d.split(":")[0] not in ("cpu", "gpu", "tpu")]
