"""Places — device tags.

Reference: ``paddle/platform/place.h:24,34,53`` defines CPUPlace / CUDAPlace
and a boost::variant Place consumed by kernel dispatch.  On TPU the analog is
a jax.Device (or a Mesh of them); Places here are thin selectors the Executor
resolves against ``jax.devices()``.
"""

import jax


class Place:
    def __eq__(self, other):
        return type(self) is type(other) and self.__dict__ == other.__dict__

    def __hash__(self):
        return hash((type(self).__name__, tuple(sorted(self.__dict__.items()))))

    def __repr__(self):
        args = ", ".join(f"{k}={v}" for k, v in self.__dict__.items())
        return f"{type(self).__name__}({args})"


class CPUPlace(Place):
    def get_device(self):
        return jax.devices("cpu")[0]


class TPUPlace(Place):
    """device_id indexes into the local TPU devices, like CUDAPlace(dev_id)."""

    def __init__(self, device_id=0):
        self.device_id = device_id

    def get_device(self):
        accel = [d for d in jax.devices() if d.platform != "cpu"]
        if not accel:
            raise RuntimeError(
                f"TPUPlace({self.device_id}): JAX found no accelerator "
                f"(devices: {jax.devices()})")
        return accel[self.device_id]


def is_compiled_with_tpu():
    """Analog of core.is_compiled_with_cuda() used to gate device tests."""
    try:
        return any(d.platform != "cpu" for d in jax.devices())
    except RuntimeError:
        return False
