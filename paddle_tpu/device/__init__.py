"""Device management (reference: python/paddle/device, paddle/phi/backends).

The TPU runtime has one device class; CPUPlace/CUDAPlace etc. are accepted
for API compatibility and map onto jax devices.  `set_device` selects the
default jax device used for new tensors.
"""
from __future__ import annotations

import jax

__all__ = ["set_device", "get_device", "get_all_custom_device_type",
           "CPUPlace", "CUDAPlace", "XPUPlace", "TPUPlace", "CustomPlace",
           "cuda", "device_count", "is_available"]

_current = None


class _Place:
    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))


class CPUPlace(_Place):
    def __init__(self):
        super().__init__(0)

    def __repr__(self):
        return "Place(cpu)"


class CUDAPlace(_Place):
    pass


class CUDAPinnedPlace(_Place):
    def __init__(self):
        super().__init__(0)


class XPUPlace(_Place):
    pass


class TPUPlace(_Place):
    pass


class CustomPlace(_Place):
    def __init__(self, dev_type, device_id=0):
        super().__init__(device_id)
        self.dev_type = dev_type


def set_device(device: str):
    """Accepts 'cpu', 'tpu', 'tpu:0', also 'gpu:0' / 'xpu:0' (mapped to
    the default accelerator for source compatibility).  'tpu' means a
    TPU: where JAX has none this raises instead of answering with
    whatever device there is."""
    global _current
    name = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    if name in ("cpu", "tpu"):
        devs = jax.devices(name)    # RuntimeError: no such backend
    else:
        devs = jax.devices()
    _current = devs[idx % len(devs)]
    jax.config.update("jax_default_device", _current)
    return _current


def get_device() -> str:
    d = _current or jax.devices()[0]
    plat = d.platform
    name = "gpu" if plat == "tpu" else plat  # paddle-style string
    return f"{name}:{d.id}" if plat != "cpu" else "cpu"


def get_all_custom_device_type():
    return ["tpu"]


def device_count():
    return len(jax.devices())


def is_available():
    return True


class cuda:
    """paddle.device.cuda compat shims (map to the accelerator)."""

    @staticmethod
    def device_count():
        return len(jax.devices())

    @staticmethod
    def max_memory_allocated(device=None):
        d = jax.devices()[0]
        stats = getattr(d, "memory_stats", lambda: {})() or {}
        return stats.get("peak_bytes_in_use", 0)

    @staticmethod
    def memory_allocated(device=None):
        d = jax.devices()[0]
        stats = getattr(d, "memory_stats", lambda: {})() or {}
        return stats.get("bytes_in_use", 0)

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def synchronize(device=None):
        import jax
        (jax.device_put(0) + 0).block_until_ready()


class IPUPlace(_Place):
    def __init__(self):
        super().__init__(0)


class Stream:
    """Stream surface (reference device/__init__.py Stream over C++
    streams).  XLA owns real streams; this is an ordering token whose
    synchronize() drains the device queue."""

    def __init__(self, device=None, priority=2):
        self.device = device
        self.priority = priority

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        event.synchronize()

    def wait_stream(self, stream):
        stream.synchronize()

    def record_event(self, event=None):
        event = event or Event()
        event.record(self)
        return event


class Event:
    """Event surface (reference device/__init__.py Event)."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self.device = device
        self._recorded = False

    def record(self, stream=None):
        self._recorded = True

    def query(self):
        return True

    def synchronize(self):
        synchronize()


_current_stream = Stream()


def current_stream(device=None):
    return _current_stream


def set_stream(stream):
    global _current_stream
    prev = _current_stream
    _current_stream = stream
    return prev


class stream_guard:
    def __init__(self, stream):
        self._stream = stream
        self._prev = None

    def __enter__(self):
        self._prev = set_stream(self._stream)
        return self._stream

    def __exit__(self, *exc):
        set_stream(self._prev)
        return False


def synchronize(device=None):
    """Block until all queued device work completes (reference
    device/cuda synchronize); jax effectively syncs via a trivial fetch.
    Accepts None, a jax Device, or a paddle-style string ('gpu:0')."""
    import jax
    if device is None:
        dev = jax.devices()[0]
    elif isinstance(device, str):
        plat, _, idx = device.partition(":")
        idx = int(idx) if idx else 0
        try:
            dev = jax.devices(plat)[idx]
        except RuntimeError:
            dev = jax.devices()[0]  # platform not present: sync default
    else:
        dev = device
    jax.block_until_ready(jax.device_put(0, dev))


def get_all_device_type():
    import jax
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    import jax
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return []


def get_cudnn_version():
    return None  # no cuDNN on TPU


def is_compiled_with_cinn():
    return False


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_custom_device(device_type=None):
    import jax
    return any(d.platform not in ("cpu", "gpu", "tpu")
               for d in jax.devices())


def is_compiled_with_distribute():
    return True  # XLA collectives are always in
