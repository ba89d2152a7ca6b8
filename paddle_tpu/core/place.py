"""Device identity types.

Analog of /root/reference/paddle/fluid/platform/place.h:79
(boost::variant<CUDAPlace, CPUPlace, CUDAPinnedPlace>). The TPU build's
variant is {CPUPlace, TPUPlace}; a Place resolves to a concrete
jax.Device, and the DeviceContextPool analog is JAX's device table —
streams/handles are owned by PJRT, not by us.
"""

from __future__ import annotations

__all__ = ["CPUPlace", "TPUPlace", "CUDAPlace", "Place", "is_compiled_with_tpu"]


class Place:
    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)

    def jax_device(self):
        import jax

        if isinstance(self, CPUPlace):
            try:
                return jax.devices("cpu")[self.device_id]
            except RuntimeError:
                return None  # cpu not a visible backend; let jax default
        devs = jax.devices()
        if devs[0].platform == "cpu" and not _cpu_requested():
            raise RuntimeError(
                "%r: JAX found no accelerator (default backend is the "
                "CPU). Set JAX_PLATFORMS=cpu to run on the CPU on "
                "purpose, or use CPUPlace()." % (self,))
        return devs[self.device_id % len(devs)]


def _cpu_requested() -> bool:
    """True when the process chose the CPU backend itself
    (``JAX_PLATFORMS=cpu`` / ``jax_platforms``), as the tests do."""
    import jax

    return (jax.config.jax_platforms or "").split(",")[0].strip() == "cpu"


class CPUPlace(Place):
    pass


class TPUPlace(Place):
    """The accelerator place: the default JAX backend. Resolving it on a
    machine whose default backend is the CPU raises, unless the process
    asked for the CPU explicitly (``JAX_PLATFORMS=cpu``, as the tests
    do) — a missing chip must never pass for a slow one."""


# The reference's CUDAPlace maps to the accelerator slot here; kept as an
# alias so reference-shaped user code ports without edits.
CUDAPlace = TPUPlace


class CUDAPinnedPlace(Place):
    """Pinned host memory place (reference place.h). Host staging is
    PJRT's job here; the class exists for API parity and feeds behave
    like CPUPlace."""


def is_compiled_with_tpu() -> bool:
    import jax

    return any(d.platform == "tpu" for d in jax.devices())
