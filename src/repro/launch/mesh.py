"""Production mesh definitions.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16) — the pod axis
carries pure data parallelism (gradient all-reduce crosses the DCI links;
everything bandwidth-hungry stays inside a pod).

Defined as functions, not module constants, so importing this module never
touches jax device state (the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count before first jax use).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests use small ones, e.g. (2,4) on 8 host devices).

    Every axis is ``Auto``: the sharding code places arrays with
    ``with_sharding_constraint`` and leaves propagation to GSPMD, while
    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which indexing
    a sharded array (``emb[tokens]``) raises ``DuplicateSpecError``.
    """
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def mesh_shape_dict(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


# TPU v5e hardware model used by the roofline analysis.
HW = {
    "peak_flops_bf16": 197e12,  # per chip
    "hbm_bw": 819e9,  # bytes/s per chip
    "ici_bw": 50e9,  # bytes/s per link
}
