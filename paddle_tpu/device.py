"""Device management (``paddle.device`` analog).

The reference's DeviceManager/Place machinery
(``phi/backends/device_manager.h:134``) maps onto JAX's PJRT layer:

- device enumeration/selection → ``jax.devices`` + a process-level default;
- the custom-device PLUGIN mechanism (``device_manager.h`` RegisterDevice /
  ``custom_device.cc``) → PJRT plugin registration
  (:func:`register_custom_device` wraps ``xla_bridge.register_plugin`` —
  a real dynamically-loaded backend, the same extension point the
  reference exposes to vendors);
- per-device memory introspection (``device_manager.h`` MemoryStats) →
  :func:`memory_stats` / :func:`max_memory_allocated` over PJRT
  ``device.memory_stats()`` (live on TPU; CPU PJRT reports none);
- streams/events (``phi/core/stream.h``) → XLA's single in-order stream
  per device: :class:`Stream`/:class:`Event` keep the reference API with
  documented program-order semantics (an Event records a marker value;
  synchronize blocks until everything enqueued before it is done).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax

_current = None


def get_all_devices():
    return jax.devices()


def device_count(device_type: str | None = None) -> int:
    if device_type:
        try:
            return len(jax.devices(device_type))
        except RuntimeError:
            return 0
    return jax.device_count()


def get_device() -> str:
    if _current is not None:
        return _current
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def set_device(device: str):
    """Accepts 'cpu', 'tpu', 'tpu:0', 'gpu:0' (mapped to default backend)."""
    global _current
    _current = device
    return device


def get_available_device() -> List[str]:
    """(``device/__init__.py`` get_available_device analog)."""
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device() -> List[str]:
    """Devices from non-builtin (plugin) platforms."""
    builtin = {"cpu", "gpu", "cuda", "rocm", "tpu"}
    return [f"{d.platform}:{d.id}" for d in jax.devices()
            if d.platform not in builtin]


def _resolve(device=None):
    """Map a device string to a jax device.  Platforms that are not part
    of the initialized backend ('gpu:0' on a TPU/CPU install) map to the
    default backend — the set_device contract — WITHOUT querying foreign
    platforms (a jax.devices('gpu') call would force discovery/init of
    every registered plugin backend)."""
    if device is None:
        if _current is not None:
            return _resolve(_current)
        return jax.devices()[0]
    if isinstance(device, str):
        plat, _, idx = device.partition(":")
        available = {d.platform for d in jax.devices()}
        devs = jax.devices(plat) if plat in available else jax.devices()
        i = int(idx) if idx else 0
        return devs[i] if i < len(devs) else devs[0]
    return device


# --- custom-device plugin registration (device_manager.h:134 analog) -------

def register_custom_device(name: str, library_path: str,
                           options: Optional[Dict] = None) -> None:
    """Register a PJRT plugin backend by shared-library path — the
    TPU-first analog of the reference's custom-device runtime registration
    (``phi/backends/custom/custom_device.cc``; vendors ship a .so, the
    framework dlopens it and the new device type becomes first-class).

    Must be called before the backend is first initialized.
    """
    from jax._src import xla_bridge

    xla_bridge.register_plugin(name, library_path=library_path,
                               options=options)


def is_compiled_with_custom_device(name: str) -> bool:
    """True if platform ``name`` is registered (initialized or pending).

    Deliberately never calls ``jax.devices(name)`` — that would
    force-initialize every registered backend as a side effect of a
    boolean query."""
    try:
        from jax._src import xla_bridge

        if name in xla_bridge._backend_factories:
            return True
    except Exception:
        pass
    return name in {d.platform for d in jax.devices()}


# --- memory introspection (device_manager.h MemoryStats analog) ------------

def memory_stats(device=None) -> Dict[str, int]:
    """Raw PJRT memory stats for ``device`` (empty dict when the backend
    doesn't report any — CPU PJRT — matching a loud-absence contract
    rather than fabricated numbers)."""
    d = _resolve(device)
    try:
        return dict(d.memory_stats() or {})
    except Exception:
        return {}


def memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def max_memory_reserved(device=None) -> int:
    # 'peak_bytes_reserved' only: a current-value or in-use substitute
    # would fabricate a "max" that can shrink (loud-absence contract)
    return int(memory_stats(device).get("peak_bytes_reserved", 0))


def is_compiled_with_cuda() -> bool:
    return False


def cuda_device_count() -> int:
    return 0


# --- streams / events (phi/core/stream.h analog) ---------------------------

class Event:
    """``paddle.device.Event``: XLA executes each device's work in program
    order on one stream, so an event is a marker for "everything enqueued
    so far"; ``synchronize`` blocks on it."""

    def __init__(self, device=None, enable_timing=False, blocking=False):
        if enable_timing:
            raise NotImplementedError(
                "Event(enable_timing=True) is not supported: XLA has no "
                "per-event device timestamps — use jax.profiler (paddle."
                "profiler) traces for device timing")
        self._device = _resolve(device)
        self._marker = None

    def record(self, stream: "Stream | None" = None):
        # a tiny committed computation AFTER the enqueued work: in-order
        # execution means its completion implies everything before it is done
        self._marker = jax.device_put(0, self._device) + 0
        return self

    def query(self) -> bool:
        if self._marker is None:
            return True
        return self._marker.is_ready()

    def synchronize(self):
        if self._marker is not None:
            self._marker.block_until_ready()


class Stream:
    """``paddle.device.Stream``: XLA maintains one in-order execution
    stream per device; the API exists for reference parity and attaches
    events/synchronization to a chosen device."""

    def __init__(self, device=None, priority=2):
        self.device = _resolve(device)

    def record_event(self, event: Optional[Event] = None) -> Event:
        event = event or Event(self.device)
        return event.record(self)

    def wait_event(self, event: Event):
        event.synchronize()

    def wait_stream(self, stream: "Stream"):
        synchronize(stream.device)

    def synchronize(self):
        synchronize(self.device)


def current_stream(device=None) -> Stream:
    return Stream(device)


class cuda:
    """Minimal ``paddle.device.cuda`` surface (no-op on TPU)."""

    @staticmethod
    def device_count():
        return 0

    @staticmethod
    def synchronize(device=None):
        return None

    @staticmethod
    def empty_cache():
        return None


def synchronize(device=None):
    """Block until all queued device work completes."""
    d = _resolve(device)
    (jax.device_put(0, d) + 0).block_until_ready()
