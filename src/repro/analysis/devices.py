"""Device memory envelopes: what a candidate program must fit inside.

The paper's FPGA path gates every offload pattern on a *resource-fit*
check — reject patterns whose HLS resource estimate exceeds the board —
before any measurement is spent.  Our GPU/TPU analogue needs the board
side of that inequality: a :class:`DeviceEnvelope` names a target's
high-bandwidth memory (HBM, or host RAM on CPU backends) and, where it
matters for kernel tiling, the fast on-chip scratch (TPU VMEM / GPU
shared memory).

Two sources:

* :func:`probe_device_envelope` asks the live ``jax.devices()`` runtime
  (``device.memory_stats()["bytes_limit"]`` where the backend exposes it;
  CPU backends expose nothing and degrade to host RAM via psutil).
* :data:`STATIC_ENVELOPES` is an overridable table of named targets for
  cross-compile "what-if" planning — size a serve config for an
  ``a100-40g`` from a CPU CI container, or against the synthetic
  ``tiny-32m`` board the preflight tests reject configs on.

:func:`resolve_envelope` is the one entry point the analysis passes use:
it accepts an envelope object, a static-table name, ``"host"``/None/True
(probe the live runtime), and nothing else.
"""

from __future__ import annotations

import dataclasses

MiB = 1 << 20
GiB = 1 << 30


@dataclasses.dataclass(frozen=True)
class DeviceEnvelope:
    """Memory capacity of one offload target.

    ``memory_bytes`` is the working-set bound (HBM, or host RAM for CPU
    backends); ``vmem_bytes`` the fast on-chip scratch a tiled kernel's
    working tiles must fit (TPU VMEM; None where tiling is the compiler's
    problem).  ``source`` records whether the numbers were probed from
    the live runtime or declared statically.
    """

    name: str
    platform: str  # "cpu" | "gpu" | "tpu"
    memory_bytes: int
    vmem_bytes: int | None = None
    source: str = "static"  # "static" | "probed"
    notes: str = ""

    def headroom_bytes(self, need_bytes: int) -> int:
        """Bytes left after ``need_bytes`` (negative = does not fit)."""
        return self.memory_bytes - int(need_bytes)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        mem = self.memory_bytes / GiB
        vmem = (
            f", vmem {self.vmem_bytes / MiB:.0f} MiB"
            if self.vmem_bytes
            else ""
        )
        return f"{self.name} ({self.platform}, {mem:.1f} GiB{vmem}, {self.source})"


#: Named what-if targets for cross-compile planning.  Capacities are the
#: published per-device numbers (approximate where vendors round); VMEM
#: is the per-core budget a Pallas kernel's resident tiles must fit.
STATIC_ENVELOPES: dict[str, DeviceEnvelope] = {
    e.name: e
    for e in (
        DeviceEnvelope("tpu-v4", "tpu", 32 * GiB, vmem_bytes=16 * MiB,
                       notes="32 GiB HBM2 per chip; ~16 MiB VMEM per core"),
        DeviceEnvelope("tpu-v5e", "tpu", 16 * GiB, vmem_bytes=16 * MiB,
                       notes="16 GiB HBM2 per chip"),
        DeviceEnvelope("tpu-v5p", "tpu", 95 * GiB, vmem_bytes=16 * MiB,
                       notes="95 GiB HBM2e per chip"),
        DeviceEnvelope("a100-40g", "gpu", 40 * GiB,
                       notes="A100 SXM/PCIe 40 GiB HBM2"),
        DeviceEnvelope("a100-80g", "gpu", 80 * GiB,
                       notes="A100 80 GiB HBM2e"),
        DeviceEnvelope("h100-80g", "gpu", 80 * GiB,
                       notes="H100 SXM 80 GiB HBM3"),
        DeviceEnvelope("l4-24g", "gpu", 24 * GiB,
                       notes="L4 24 GiB GDDR6 (inference tier)"),
        DeviceEnvelope("cpu-host-16g", "cpu", 16 * GiB,
                       notes="CI-container class host; the lint default so "
                             "ratcheted verdicts are host-independent"),
        DeviceEnvelope("tiny-32m", "cpu", 32 * MiB,
                       notes="synthetic undersized board for preflight "
                             "rejection tests and CI smoke"),
    )
}


def _host_memory_bytes() -> int:
    """Total host RAM, best effort (psutil, then sysconf, then 16 GiB)."""
    try:
        import psutil

        return int(psutil.virtual_memory().total)
    except Exception:  # noqa: BLE001 — psutil is optional
        pass
    try:
        import os

        return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    except (ValueError, OSError, AttributeError):
        return 16 * GiB


def probe_device_envelope(device=None) -> DeviceEnvelope:
    """Envelope of a live ``jax`` device.

    GPU/TPU backends report an allocator ``bytes_limit`` through
    ``memory_stats()``; CPU backends return None there, so the probe
    degrades to total host RAM (the CPU "HBM" is the host's).
    """
    import jax

    if device is None:
        device = jax.devices()[0]
    stats = device.memory_stats()
    limit = 0
    if stats:
        limit = int(
            stats.get("bytes_limit")
            or stats.get("bytes_reservable_limit")
            or 0
        )
    kind = getattr(device, "device_kind", device.platform)
    if limit > 0:
        return DeviceEnvelope(
            name=str(kind), platform=device.platform,
            memory_bytes=limit, source="probed",
        )
    return DeviceEnvelope(
        name=f"host:{kind}", platform=device.platform,
        memory_bytes=_host_memory_bytes(), source="probed",
        notes="backend exposes no memory_stats; host RAM used",
    )


def resolve_envelope(spec) -> DeviceEnvelope:
    """One resolution policy for every pass.

    ``DeviceEnvelope`` passes through; ``None``/``True``/``"host"`` probe
    the live runtime; any other string looks up :data:`STATIC_ENVELOPES`
    (unknown names fail loudly with the known ones listed).
    """
    if isinstance(spec, DeviceEnvelope):
        return spec
    if spec is None or spec is True or spec == "host":
        return probe_device_envelope()
    if isinstance(spec, str):
        try:
            return STATIC_ENVELOPES[spec]
        except KeyError:
            raise KeyError(
                f"unknown device envelope '{spec}'; known: "
                f"{sorted(STATIC_ENVELOPES)} (or 'host' to probe)"
            ) from None
    raise TypeError(
        f"envelope spec must be a DeviceEnvelope, a name, 'host' or None; "
        f"got {type(spec).__name__}"
    )
