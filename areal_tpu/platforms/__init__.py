"""Platform abstraction: device type, communication backend, topology
discovery.

Role of reference areal/platforms/ (`Platform` base at platform.py:10-141,
CUDA/CPU impls, `current_platform` singleton): the rest of the framework
asks the platform — never torch/jax directly — what accelerator it runs
on, which communication backend in-mesh collectives use, and how to
discover the pod topology. The TPU platform reads the TPU runtime's
environment (worker id/hostnames/chips) so launchers can place per-host
processes; CPU covers tests and virtual-device meshes.
"""

import os
from typing import Dict, List, Optional


class Platform:
    """Base platform contract (reference areal/platforms/platform.py:10)."""

    device_type: str = "unknown"
    # in-mesh collectives ride this fabric (reference: "nccl")
    communication_backend: str = "unknown"
    visible_devices_env: str = ""

    @property
    def process_index(self) -> int:
        import jax

        return jax.process_index()

    @property
    def process_count(self) -> int:
        import jax

        return jax.process_count()

    def local_device_count(self) -> int:
        import jax

        return jax.local_device_count()

    def pod_worker_hosts(self) -> List[str]:
        """Hostnames of every worker in the slice ([] = single host)."""
        return []

    def visible_devices_envvars(self, device_ids: List[int]) -> Dict[str, str]:
        """Env restricting a subprocess to the given local devices."""
        if not self.visible_devices_env:
            return {}
        return {
            self.visible_devices_env: ",".join(str(i) for i in device_ids)
        }


class TpuPlatform(Platform):
    """TPU slices: XLA collectives over ICI in-mesh, DCN across slices.

    Pod discovery reads the TPU runtime environment (set by the TPU VM
    runtime / GKE): TPU_WORKER_ID, TPU_WORKER_HOSTNAMES, TPU_CHIPS_PER_HOST
    — the analog of the reference's torchrun/Ray rank wiring."""

    device_type = "tpu"
    communication_backend = "xla:ici+dcn"
    visible_devices_env = "TPU_VISIBLE_CHIPS"

    def pod_worker_id(self) -> int:
        return int(os.environ.get("TPU_WORKER_ID", 0))

    def pod_worker_hosts(self) -> List[str]:
        hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
        return [h for h in hosts.split(",") if h]

    def chips_per_host(self) -> int:
        v = os.environ.get("TPU_CHIPS_PER_HOST")
        if v:
            return int(v)
        return self.local_device_count()


def local_chip_count() -> Optional[int]:
    """Chips this host offers, WITHOUT starting a jax backend — for launcher
    parents, which must leave every chip to the children they start (a chip
    belongs to one process at a time). Read from what the TPU runtime
    exports, else from the accelerator device files; None when neither says
    anything (CPU hosts, tests)."""
    visible = os.environ.get(TpuPlatform.visible_devices_env, "")
    if visible:
        return len([c for c in visible.split(",") if c])
    v = os.environ.get("TPU_CHIPS_PER_HOST")
    if v:
        return int(v)
    import glob

    n = len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )
    return n or None


class CpuPlatform(Platform):
    device_type = "cpu"
    communication_backend = "gloo"
    visible_devices_env = ""


class UnknownPlatform(Platform):
    pass


def _detect() -> Platform:
    try:
        import jax

        kind = jax.devices()[0].platform.lower()
    except Exception:
        return UnknownPlatform()
    if kind == "tpu":
        return TpuPlatform()
    if kind == "cpu":
        return CpuPlatform()
    return UnknownPlatform()


_current: Optional[Platform] = None


def current_platform() -> Platform:
    """Lazy singleton (reference areal/platforms/__init__.py registry);
    detection touches the jax backend, so it must not run at import."""
    global _current
    if _current is None:
        _current = _detect()
    return _current
