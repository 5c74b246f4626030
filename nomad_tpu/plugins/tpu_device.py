"""TPU device plugin.

Fills the nvidia-device-plugin slot (reference ``devices/gpu/nvidia/``:
NVML fingerprint → device groups, Reserve → ``NVIDIA_VISIBLE_DEVICES``)
for the hardware this framework targets: fingerprints the host's TPU
chips through JAX (the NVML analog), exposes them as a schedulable device
group, and reserves instances by exporting ``TPU_VISIBLE_CHIPS`` /
``JAX_PLATFORMS`` so the task's JAX runtime binds only its assigned chips.
Degrades to no-devices on hosts without TPUs (nvidia fingerprint.go does
the same when NVML is absent).
"""
from __future__ import annotations

import time
from typing import List

from .device import (
    ContainerReservation,
    DetectedDevice,
    DeviceGroup,
    DevicePlugin,
    DeviceStats,
)


class TPUDevicePlugin(DevicePlugin):
    name = "tpu"
    config_schema_spec = {
        "platform": {"type": "string"},  # override auto-detection ("tpu")
    }

    def __init__(self) -> None:
        self.config = {}

    def config_schema(self):
        return self.config_schema_spec

    def _detect(self) -> List[DeviceGroup]:
        # jax.devices() initialises the backend in WHICHEVER process hosts
        # this plugin. In-process (builtin_device_plugin, e.g. a -dev
        # agent) that is the server's own process, which already owns the
        # chip. Out of process the catalog pins JAX_PLATFORMS=cpu
        # (catalog._plugin_env), so a plugin subprocess can never take
        # the chip from the server — it reports no TPU devices instead.
        try:
            import jax

            platform = self.config.get("platform", "")
            devices = (
                jax.devices(platform) if platform else jax.devices()
            )
        except Exception:  # noqa: BLE001 — no TPU runtime on this host
            return []
        groups = {}
        for d in devices:
            kind = getattr(d, "device_kind", "unknown")
            g = groups.get(kind)
            if g is None:
                g = groups[kind] = DeviceGroup(
                    vendor="google",
                    type=getattr(d, "platform", "tpu"),
                    name=kind,
                    attributes={},
                )
            g.devices.append(DetectedDevice(id=str(d.id)))
        for g in groups.values():
            g.attributes["count"] = str(len(g.devices))
        return list(groups.values())

    def fingerprint(self) -> List[DeviceGroup]:
        # no memoization: the device manager's periodic pass must see
        # chips appear (runtime comes up late) or go unhealthy
        return self._detect()

    def reserve(self, device_ids: List[str]) -> ContainerReservation:
        known = {d.id for g in self.fingerprint() for d in g.devices}
        for did in device_ids:
            if did not in known:
                raise ValueError(f"unknown TPU chip {did!r}")
        chips = ",".join(sorted(device_ids, key=lambda x: int(x) if x.isdigit() else 0))
        return ContainerReservation(
            envs={
                # the TPU runtime's visibility knob (the
                # NVIDIA_VISIBLE_DEVICES analog)
                "TPU_VISIBLE_CHIPS": chips,
                "TPU_CHIPS_PER_PROCESS_BOUNDS": f"1,1,{len(device_ids)}",
            }
        )

    def stats(self) -> DeviceStats:
        groups = self.fingerprint()
        return DeviceStats(
            instance_stats={
                d.id: {"healthy": 1.0}
                for g in groups
                for d in g.devices
            },
            timestamp_ns=time.time_ns(),
        )


def plugin() -> TPUDevicePlugin:
    return TPUDevicePlugin()
