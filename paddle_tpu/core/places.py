"""Device places.

Parity with the reference's `Place` variant (paddle/fluid/platform/place.h:25-75)
but TPU-first: ``TPUPlace`` is the primary accelerator place and maps onto a
``jax.Device``. ``CUDAPlace`` is accepted as an alias for the accelerator place
so reference-style scripts (``fluid.CUDAPlace(0)``) run unchanged.

Unlike the reference there is no DeviceContext/stream plumbing here: streams,
allocators and cross-device copies are owned by the XLA runtime. A Place only
answers "which jax.Device does this program execute on".
"""

import functools
import os

import jax


class Place:
    """Base device identity."""

    device_kind = None

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return (
            type(self) is type(other) and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)

    # -- jax bridge ---------------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device. A place whose kind of
        device this process does not have is an error, never another
        device: a program must not train on the CPU under a TPUPlace."""
        devs = _devices_for_kind(self.device_kind)
        if not devs:
            raise RuntimeError(
                "%r: this process has no %s device (JAX_PLATFORMS=%r, "
                "default backend %r with %d device(s))"
                % (self, "accelerator" if self.is_accelerator()
                   else "CPU", os.environ.get("JAX_PLATFORMS"),
                   jax.default_backend(), jax.local_device_count()))
        return devs[self.device_id % len(devs)]

    def is_accelerator(self):
        return False


@functools.cache
def _devices_for_kind(kind):
    # LOCAL devices only: in a multi-process (multi-host) group,
    # jax.devices() lists every process's devices and [0] would be rank
    # 0's — a single-device executor on another rank would then commit
    # state to a device it cannot address.
    if kind == "cpu":
        # backend="cpu" asks the CPU backend by name — plain
        # local_devices() lists only the DEFAULT backend, which on a
        # TPU host is the TPU. An accelerator-only JAX_PLATFORMS has no
        # CPU backend at all: no devices, and jax_device() raises.
        try:
            return tuple(jax.local_devices(backend="cpu"))
        except RuntimeError:
            return ()
    return tuple(d for d in jax.local_devices() if d.platform != "cpu")


class CPUPlace(Place):
    device_kind = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    device_kind = "accel"

    def is_accelerator(self):
        return True


class CUDAPlace(TPUPlace):
    """Reference-compat alias: routes to the accelerator (TPU) device."""


class CUDAPinnedPlace(CPUPlace):
    """Reference-compat alias; pinned host staging is managed by XLA."""

    def __init__(self):
        Place.__init__(self, 0)


def _default_place():
    devs = [d for d in jax.devices() if d.platform != "cpu"]
    return TPUPlace(0) if devs else CPUPlace()


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return any(d.platform != "cpu" for d in jax.devices())
