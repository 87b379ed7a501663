"""Device / place abstraction.

TPU-native equivalent of the reference's `paddle/fluid/platform/place.h`
(`Place` variant over CPUPlace/CUDAPlace/XPUPlace/NPUPlace) and
`device_context.h`. On TPU, streams/contexts/allocators are owned by XLA, so a
Place reduces to a handle onto a `jax.Device`; `DeviceContextPool` disappears.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from . import enforce


class Place:
    """Base place. Compares by device kind + index like the reference Place."""

    kind: str = "undefined"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return (isinstance(other, Place) and self.kind == other.kind
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.kind, self.device_id))

    def __repr__(self):
        return f"Place({self.kind}:{self.device_id})"

    def jax_device(self) -> jax.Device:
        devs = [d for d in jax.devices() if _kind_of(d) == self.kind]
        enforce.enforce(
            self.device_id < len(devs),
            f"No {self.kind} device with index {self.device_id}; "
            f"visible: {jax.devices()}",
            enforce.UnavailableError)
        return devs[self.device_id]


class CPUPlace(Place):
    kind = "cpu"


class TPUPlace(Place):
    """Reference analogue: CUDAPlace (place.h). The accelerator place."""
    kind = "tpu"


class CUDAPinnedPlace(Place):
    # On TPU there is no pinned staging pool exposed to users; kept for API
    # parity, maps to host memory.
    kind = "cpu"


class CUDAPlace(Place):
    """Drop-in accelerator place for reference scripts (`place.h`
    CUDAPlace): maps to the TPU — scripts doing
    `paddle.CUDAPlace(0) if use_gpu else CPUPlace()` run unchanged."""
    kind = "tpu"


class XPUPlace(Place):
    kind = "tpu"  # accelerator place alias (reference: Kunlun XPU)


class NPUPlace(Place):
    kind = "tpu"  # accelerator place alias (reference: Ascend NPU)


def _kind_of(dev: jax.Device) -> str:
    p = dev.platform.lower()
    if p == "tpu":
        return "tpu"
    if p in ("gpu", "cuda", "rocm"):
        return "gpu"
    return "cpu"


_current_device: Optional[str] = None


@functools.lru_cache(maxsize=None)
def _devices_of_kind(kind: str):
    return tuple(d for d in jax.devices() if _kind_of(d) == kind)


def is_compiled_with_tpu() -> bool:
    return len(_devices_of_kind("tpu")) > 0


def is_compiled_with_cuda() -> bool:  # API parity
    return False


def is_compiled_with_xpu() -> bool:  # API parity
    return False


def is_compiled_with_npu() -> bool:  # API parity
    return False


def is_compiled_with_rocm() -> bool:  # API parity
    return False


def get_cudnn_version():  # API parity: no cuDNN on this stack
    return None


def set_device(device: str) -> Place:
    """paddle.set_device equivalent: 'tpu', 'tpu:1', 'cpu'."""
    global _current_device
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = {"gpu": "tpu"}.get(name, name)  # accept 'gpu' for drop-in scripts
    place = TPUPlace(idx) if name == "tpu" else CPUPlace(idx)
    place.jax_device()  # validate
    _current_device = f"{place.kind}:{idx}"
    jax.config.update("jax_default_device", place.jax_device())
    return place


def get_device() -> str:
    if _current_device is not None:
        return _current_device
    return "tpu:0" if is_compiled_with_tpu() else "cpu:0"


def get_place() -> Place:
    name, _, idx = get_device().partition(":")
    return (TPUPlace if name == "tpu" else CPUPlace)(int(idx or 0))


def device_count(kind: str = "tpu") -> int:
    return len(_devices_of_kind(kind))


# --------------------------------------------------------------------------
# Device memory stats (reference: memory/stats.h STAT_ADD +
# `paddle.device.cuda.memory_allocated/max_memory_allocated`,
# `platform/monitor.h:44`). On TPU, XLA owns HBM — the numbers come from
# the PJRT device's memory_stats().
# --------------------------------------------------------------------------

def memory_stats(device=None) -> dict:
    """Raw PJRT memory stats dict for a device ({} when the backend does
    not report them, e.g. CPU). `device` may be None, a Place, a jax
    Device, an int device index, or a "tpu:0"-style string."""
    import jax
    if device is None:
        dev = jax.devices()[0]
    elif isinstance(device, Place):
        dev = device.jax_device()
    elif isinstance(device, int):
        dev = jax.devices()[device]
    elif isinstance(device, str):
        # 'tpu:1' / 'cpu' — resolve by KIND via the Place machinery
        # (indexing jax.devices() directly would hand back a TPU for a
        # 'cpu:0' request on a TPU host)
        name, _, idx = device.partition(":")
        idx = int(idx) if idx else 0
        name = {"gpu": "tpu"}.get(name, name)
        place = TPUPlace(idx) if name == "tpu" else CPUPlace(idx)
        dev = place.jax_device()
    elif isinstance(device, jax.Device):
        dev = device
    else:
        raise TypeError(f"unsupported device spec {device!r}")
    stats = getattr(dev, "memory_stats", lambda: None)()
    return dict(stats) if stats else {}


def memory_allocated(device=None) -> int:
    """Bytes currently allocated on the device (reference:
    `paddle.device.cuda.memory_allocated`)."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """High-watermark of allocated bytes (reference:
    `paddle.device.cuda.max_memory_allocated`)."""
    s = memory_stats(device)
    return int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))


def memory_reserved(device=None) -> int:
    """Bytes reserved by the allocator pool (== bytes_limit on TPU where
    XLA preallocates; reference: `memory_reserved`)."""
    s = memory_stats(device)
    return int(s.get("bytes_limit", s.get("bytes_reserved", 0)))
